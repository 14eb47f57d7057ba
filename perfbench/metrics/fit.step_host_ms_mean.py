"""Layer: trainers. Source: program_span (`train.step`, nn/graph.py: the
jitted step's dispatch plus the loss fetch, as the host sees one step).
Mean over the steps of the window. Moves: train_samples_per_s_per_chip."""

from perfbench.stats import mean


def read(run):
    spans = run.program_spans("train.step")
    return 1e3 * mean(s["dur"] for s in spans) if spans else None
