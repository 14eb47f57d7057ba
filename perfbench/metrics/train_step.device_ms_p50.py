"""Layer: model step. Source: device_trace (executions of the jitted
train step in the traced slice). Moves: train_samples_per_s_per_chip."""

from perfbench.stats import percentile


def read(run):
    ms = run.entry_device_ms("train_step")
    return percentile(ms, 50) if ms else None
