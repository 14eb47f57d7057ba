"""Layer: kernels. Source: device_trace. The least time the chip could
take for one decode step: weights read once plus the live keys and values
of the slots (mean live slots from `sequence.step`, the mix's mean
context), against its FLOPs; over the step's median device time. Bound by
memory. Moves: output_tokens_per_s."""

from perfbench.harness import log
from perfbench.stats import mean, percentile


def read(run):
    ms = run.entry_device_ms("decode_paged")
    spans = run.program_spans("sequence.step")
    if not ms or not spans:
        return None
    m = run.config["model"]
    live = mean(s["args"]["slots"] for s in spans)
    kv = live * run.traffic["mean_context"]
    least, bound = run.arith.step_min_seconds(
        m, run.peaks, run.arith.decode_flops(m, live, kv), kv)
    log(f"decode step roofline: least {1e3 * least:.3f} ms, bound by {bound}")
    return 100.0 * least / (1e-3 * percentile(ms, 50))
