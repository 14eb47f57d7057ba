"""Layer: serving. Source: program_span (`sequence.request`, as
seq.ttft_inside_p50_ms). 95th percentile of `first_chunk_at -
enqueued_at`: the wait for a slot AND, behind earlier requests' chunks,
for the first chunk's turn (one chunk an iteration, first come first
served) — the wait `seq.queue_wait_p50_ms` ends too early to see. None
where the ring dropped spans. Moves: ttft_p95_ms."""

from perfbench.harness import load_module


def read(run):
    return load_module("metrics", "seq.ttft_inside_p50_ms").read_ms(
        run, "first_chunk_at", "enqueued_at", 95)
