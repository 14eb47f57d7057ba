"""Layer: serving. Source: program_span (`sequence.step`, which carries
the live slots and the bucket of each decode dispatch). Mean share of
the bucket that held a live sequence. Moves: output_tokens_per_s."""

from perfbench.stats import mean


def read(run):
    spans = run.program_spans("sequence.step")
    if not spans:
        return None
    return 100.0 * mean(s["args"]["slots"] / s["args"]["bucket"]
                        for s in spans)
