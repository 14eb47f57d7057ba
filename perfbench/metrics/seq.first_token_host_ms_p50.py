"""Layer: serving. Source: program_span (`sequence.prefill_finish`,
serving/sequence.py, one a prompt's last pass, and its child
`sequence.prefill_wait`). Median over the window's prompts, in ms, of
the finish less its wait: the host's time from the device's end of the
last pass to the request's end (the row's copy, the prefix registry,
the first token, the request's ending). None where no finish has a wait
child (a program without the span) and where the ring dropped spans.
Moves: ttft_p50_ms."""

from deeplearning4j_tpu.runtime import telemetry
from perfbench.stats import percentile


def read(run):
    trace = telemetry.get_registry().trace
    if trace.dropped:
        return None
    wait = {s["parent"]: s["dur"] for s in trace.spans()
            if s["name"] == "sequence.prefill_wait" and s["ph"] == "X"}
    host = [s["dur"] - wait[s["id"]]
            for s in run.program_spans("sequence.prefill_finish")
            if s["id"] in wait]
    return 1e3 * percentile(host, 50) if host else None
