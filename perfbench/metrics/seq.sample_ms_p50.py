"""Layer: serving. Source: program_span (`sequence.sample`,
serving/sequence.py: after the logits' fetch, the per-slot scatter, the
host sampler and the finishing of requests). Median over the window's
iterations; what sampling on the device (ROADMAP S4) would take away.
None where the ring dropped spans. Moves: output_tokens_per_s."""

from deeplearning4j_tpu.runtime import telemetry
from perfbench.stats import percentile


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    spans = run.program_spans("sequence.sample")
    return 1e3 * percentile([s["dur"] for s in spans], 50) if spans \
        else None
