"""Layer: serving. Source: program_span (`sequence.prefill`, as
seq.prefill_tokens_per_pass_mean). The same mean where the passes ride
on a decode batch: fewer and longer passes leave more iterations to the
decode step alone. None where the ring dropped spans. Moves:
output_tokens_per_s."""

from perfbench.harness import load_module


def read(run):
    return load_module("metrics", "seq.prefill_tokens_per_pass_mean") \
        .read(run)
