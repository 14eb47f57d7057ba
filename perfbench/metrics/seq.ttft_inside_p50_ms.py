"""Layer: serving. Source: program_span (the instant event
`sequence.request` that PagedSequenceScheduler leaves when a request
ends, with the request's timeline on the scheduler's clock). Median of
`first_token_at - enqueued_at`: time to first token as the scheduler
sees it, without the generator's lateness, `generate()` and the waiter's
wake-up that the end-to-end value adds. None where the ring dropped
spans. Moves: ttft_p50_ms."""

from deeplearning4j_tpu.runtime import telemetry
from perfbench.stats import percentile


def requests(run):
    """Timelines (the event's args) of the requests that were enqueued
    inside the window and ended in it without an error, or None where
    the ring dropped spans. The kind closes the host with drain=False,
    which fails what is still in flight after the window: those carry
    an error and are left out."""
    trace = telemetry.get_registry().trace
    if trace.dropped:
        return None
    w0, w1 = run.window["t0"], run.window["t1"]
    return [s["args"] for s in trace.spans()
            if s["name"] == "sequence.request" and s["ph"] == "i"
            and s["args"]["error"] is None
            and w0 <= s["args"]["enqueued_at"]
            and s["args"]["finished_at"] <= w1]


def read_ms(run, later, earlier, q):
    """The q-th percentile, in ms, of `later - earlier` over requests()."""
    reqs = requests(run)
    if not reqs:
        return None
    return 1e3 * percentile([r[later] - r[earlier] for r in reqs], q)


def read(run):
    return read_ms(run, "first_token_at", "enqueued_at", 50)
