"""Layer: device. Source: device_trace (`run.traced["idle_gaps"]`: the
device's idle time in the traced slice by the innermost host span that
covers it). Percent of the slice in which the device was idle and the
scheduler was NOT waiting for demand: all idle time less the gaps named
`sequence.idle` (serving/sequence.py: one span per idle period of the
scheduler's loop). Idle for want of demand is the traffic's; this part
is the program's to cure. Two limits of `idle_gaps`, both tracered's:
it keeps the ten largest names only, so where it holds ten and none is
`sequence.idle` the waiting cannot be told from zero and this reads
None; and a gap goes to the covering span that started last, so a span
of the benchmark that starts inside an idle period would take that gap
from `sequence.idle` (no kind has such a span today). None too where
the program records no scheduler spans, or the ring dropped spans.
Moves: ttft_p95_ms."""

TOP_NAMES = 10      # tracered.reduce_trace cuts idle_gaps to this many

from deeplearning4j_tpu.runtime import telemetry


def read(run):
    if not run.traced or telemetry.get_registry().trace.dropped:
        return None
    if not run.program_spans("sequence.iteration"):
        return None
    gaps = dict(map(tuple, run.traced["idle_gaps"]))
    if "sequence.idle" not in gaps and len(gaps) >= TOP_NAMES:
        return None
    no_demand = gaps.get("sequence.idle", 0.0)
    idle = run.traced["window_s"] - run.traced["busy_s"]
    return 100.0 * (idle - no_demand) / run.traced["window_s"]
