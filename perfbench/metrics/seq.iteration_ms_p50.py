"""Layer: serving. Source: program_span (`sequence.iteration`,
serving/sequence.py: the whole of one scheduler iteration that found
work, admit to sampling). Median duration over the iterations that began
in the window: a measured interval, where `seq.decode_step_host_ms_p50`
takes the difference of consecutive `sequence.step` starts. None where
the ring dropped spans. Moves: output_tokens_per_s."""

from deeplearning4j_tpu.runtime import telemetry
from perfbench.stats import percentile


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    spans = run.program_spans("sequence.iteration")
    return 1e3 * percentile([s["dur"] for s in spans], 50) if spans \
        else None
