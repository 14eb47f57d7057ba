"""Layer: serving. Source: program_span (`sequence.wake`,
serving/sequence.py: `GenerationRequest.wait` on the waiter's thread,
from the request's `finished_at` to the wait's return, with the
request's `rid`; and the instant `sequence.request`, for which requests
count). Median, in ms, over the requests enqueued inside the window that
ended in it without an error. None where none of them has the span (a
program without it) and where the ring dropped spans. Moves:
ttft_p50_ms."""

from deeplearning4j_tpu.runtime import telemetry
from perfbench.stats import percentile


def read(run):
    trace = telemetry.get_registry().trace
    if trace.dropped:
        return None
    w0, w1 = run.window["t0"], run.window["t1"]
    spans = trace.spans()
    rids = {s["rid"] for s in spans
            if s["name"] == "sequence.request" and s["ph"] == "i"
            and s["args"]["error"] is None
            and w0 <= s["args"]["enqueued_at"]
            and s["args"]["finished_at"] <= w1}
    wakes = [s["dur"] for s in spans
             if s["name"] == "sequence.wake" and s["rid"] in rids]
    return 1e3 * percentile(wakes, 50) if wakes else None
