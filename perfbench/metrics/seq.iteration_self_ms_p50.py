"""Layer: serving. Source: program_span. The self time of
`sequence.iteration`: its duration less the part of it that its child
spans cover (the spans whose `parent` is its `id`: admit, prefill,
prefill_finish, decode_prep, step, sample): locks, bookkeeping, whatever
no child names. Median over the window's iterations; more than a
millisecond means a child span is missing. None where the ring dropped
spans. Moves: output_tokens_per_s."""

from deeplearning4j_tpu.runtime import telemetry
from perfbench.stats import percentile


def self_seconds(span, children):
    """`span`'s duration minus the union of its children's intervals,
    each clipped to the span."""
    lo, hi = span["ts"], span["ts"] + span["dur"]
    covered, at = 0.0, lo
    for s, e in sorted((c["ts"], c["ts"] + c["dur"]) for c in children):
        s, e = max(s, at), min(e, hi)
        if e > s:
            covered += e - s
            at = e
    return span["dur"] - covered


def read(run):
    trace = telemetry.get_registry().trace
    if trace.dropped:
        return None
    its = run.program_spans("sequence.iteration")
    if not its:
        return None
    kids = {}
    for s in trace.spans():
        if s["ph"] == "X" and s["parent"] is not None:
            kids.setdefault(s["parent"], []).append(s)
    return 1e3 * percentile(
        [self_seconds(s, kids.get(s["id"], ())) for s in its], 50)
