"""Layer: serving. Source: program_span (`sequence.request`, as
seq.ttft_inside_p50_ms). Median of `first_token_at - first_chunk_at`:
the prompt's chunks, the host work between them and whatever other
requests' chunks the scheduler put in between. None where the ring
dropped spans. Moves: ttft_p50_ms."""

from perfbench.harness import load_module


def read(run):
    return load_module("metrics", "seq.ttft_inside_p50_ms").read_ms(
        run, "first_token_at", "first_chunk_at", 50)
