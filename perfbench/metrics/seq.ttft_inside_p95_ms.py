"""Layer: serving. Source: program_span (`sequence.request`, as
seq.ttft_inside_p50_ms). 95th percentile of `first_token_at -
enqueued_at` over the requests enqueued and ended in the window. None
where the ring dropped spans. Moves: ttft_p95_ms."""

from perfbench.harness import load_module


def read(run):
    return load_module("metrics", "seq.ttft_inside_p50_ms").read_ms(
        run, "first_token_at", "enqueued_at", 95)
