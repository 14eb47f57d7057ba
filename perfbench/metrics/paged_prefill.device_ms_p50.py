"""Layer: model step. Source: device_trace (executions of the jitted
`_prefill_paged`, one prompt chunk each, in the traced slice). Moves:
ttft_p50_ms."""

from perfbench.stats import percentile


def read(run):
    ms = run.entry_device_ms("prefill_paged")
    return percentile(ms, 50) if ms else None
