"""Layer: model step. Source: device_trace (executions of the jitted
`_decode_paged` in the traced slice). Moves: output_tokens_per_s."""

from perfbench.stats import percentile


def read(run):
    ms = run.entry_device_ms("decode_paged")
    return percentile(ms, 50) if ms else None
