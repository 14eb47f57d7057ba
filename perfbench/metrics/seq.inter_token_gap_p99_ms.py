"""Layer: serving. Source: program_span (`sequence.request`, as
seq.inter_token_gap_p50_ms). 99th percentile of the gaps between
consecutive tokens of the requests enqueued and ended in the window: the
stalled gaps (an iteration that carried a prompt chunk, or more). None
where the ring dropped spans. Moves: output_tokens_per_s."""

from perfbench.harness import load_module


def read(run):
    return load_module("metrics", "seq.inter_token_gap_p50_ms") \
        .read_gap_ms(run, 99)
