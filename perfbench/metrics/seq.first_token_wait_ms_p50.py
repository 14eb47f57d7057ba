"""Layer: serving. Source: program_span (`sequence.prefill_wait`,
serving/sequence.py: the first child of `sequence.prefill_finish`, the
host blocked on a prompt's last pass until the device has run it,
launch gap included). Median over the window's prompts, in ms. None
where the window holds none (a program without the span) and where the
ring dropped spans. Moves: ttft_p50_ms."""

from deeplearning4j_tpu.runtime import telemetry
from perfbench.stats import percentile


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    spans = run.program_spans("sequence.prefill_wait")
    return 1e3 * percentile([s["dur"] for s in spans], 50) if spans \
        else None
