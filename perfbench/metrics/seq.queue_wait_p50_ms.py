"""Layer: serving. Source: program_counter (the `dl4j_seq_queue_wait_seconds`
histogram, enqueue to the grant of a slot, read as the window closes).
Moves: ttft_p95_ms."""


def read(run):
    v = run.counters.get("queue_wait_p50_s")
    return None if v is None else 1e3 * v
