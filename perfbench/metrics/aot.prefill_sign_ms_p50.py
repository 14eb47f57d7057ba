"""Layer: compile caches. Source: program_span (`aot.sign`,
runtime/aot.py: `CachedJit.__call__`'s signature of a call's arguments
and its table lookup, recorded for a call served from the table, and
matched to the `sequence.prefill` span it ran inside by containment on
the same thread). Median over the window's prefill passes, in ms: the
host's work before a pass's executable is called. None where no pass
holds one (a program without the span) and where the ring dropped
spans. Moves: ttft_p50_ms."""

import bisect

from deeplearning4j_tpu.runtime import telemetry
from perfbench.stats import percentile


def inside_ms(run, name, outer):
    """Durations, in ms, of the `name` spans that lie inside one of the
    window's `outer` spans on the same thread; None where the ring
    dropped spans."""
    trace = telemetry.get_registry().trace
    if trace.dropped:
        return None
    by_tid = {}
    for s in sorted(trace.spans(), key=lambda s: s["ts"]):
        if s["name"] == name and s["ph"] == "X":
            by_tid.setdefault(s["tid"], []).append(s)
    starts = {tid: [s["ts"] for s in spans] for tid, spans in by_tid.items()}
    out = []
    for o in run.program_spans(outer):
        spans = by_tid.get(o["tid"], [])
        end = o["ts"] + o["dur"]
        at = bisect.bisect_left(starts.get(o["tid"], []), o["ts"])
        while at < len(spans) and spans[at]["ts"] <= end:
            if spans[at]["ts"] + spans[at]["dur"] <= end:
                out.append(1e3 * spans[at]["dur"])
            at += 1
    return out


def read_inside(run, name):
    durs = inside_ms(run, name, "sequence.prefill")
    return percentile(durs, 50) if durs else None


def read(run):
    return read_inside(run, "aot.sign")
