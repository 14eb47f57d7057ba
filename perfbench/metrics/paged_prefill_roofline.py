"""Layer: kernels. Source: device_trace (executions of the jitted
`_prefill_paged` in the traced slice, the ones `run.entry_device_ms`
reads) over program_span (`sequence.prefill`, serving/sequence.py: one
span a pass, `chunk` the prompt tokens it took; the context before it is
what the request's earlier passes took). The work as the plan ran it:
for every pass that ran wholly inside the slice, the least time the chip
could take for it by `run.arith` (the weights read once, the keys and
values of the context so far and of the chunk, the chunk's FLOPs with one
row of logits); the sum of those over the sum of the same passes' device
time, in percent. A ratio of sums: passes of one, two and three pages
weigh by their time, and no median of a three-valued pass time is held
against a mean floor (the reader before PR 33 divided a prompt's work by
`ceil(len/page)` executions and read 45% for passes at 65%).

The trace and the spans are on two clocks and the harness keeps no
offset, so the executions are paired with the passes by their order: the
device runs the passes in the order the scheduler dispatched them, so the
executions of the trace are the passes j, j+1, ... of the window for one
j, and an idle device starts a pass a fixed delay after its dispatch
began. `pair` takes the j at which the gaps between the executions'
starts and between the spans' starts agree best. None where nothing was
traced, the slice held no pass, the ring dropped spans, or a span lacks
`chunk`. Moves: ttft_p50_ms."""

import numpy as np

from deeplearning4j_tpu.runtime import telemetry
from perfbench.harness import log

ENTRY = "prefill_paged"


def pair(dev_starts, span_starts):
    """(j, spread_s): execution i is pass j + i. With the right j the
    lag of execution i behind span j + i is the dispatch delay, the same
    to a fraction of a millisecond for every pass that found the device
    idle, and longer for those that queued behind another; with a wrong j
    it swings with the arrivals. So j minimises the spread of the lower
    third of the lags (5th to 30th percentile), which holds while three
    passes in ten find the device idle. Traffic that repeats a cycle
    gives the same spread a whole cycle apart, where the passes are the
    same passes: the first such j is taken."""
    e = np.asarray(dev_starts, float)
    s = np.asarray(span_starts, float)
    lags = e - np.lib.stride_tricks.sliding_window_view(s, len(e))
    lo, hi = np.percentile(lags, [5, 30], axis=1)
    j = int(np.argmin(hi - lo))
    return j, float(hi[j] - lo[j])


def pass_min_seconds(run, chunk, context):
    """Least seconds of one pass of `chunk` prompt tokens behind
    `context` tokens already in KV."""
    m = run.config["model"]
    pairs = run.arith.causal_pairs(context + chunk) \
        - run.arith.causal_pairs(context)
    flops = run.arith.prefill_flops(m, chunk, 1, pairs)
    return run.arith.step_min_seconds(m, run.peaks, flops,
                                      context + chunk)[0]


def read(run):
    if not run.traced or telemetry.get_registry().trace.dropped:
        return None
    execs = sorted((s, d) for n, s, d in run.traced["modules"]
                   if ENTRY in n)
    spans = run.program_spans("sequence.prefill")
    w0, w1 = run.traced["w0"], run.traced["w1"]
    inside = [i for i, (s, d) in enumerate(execs)
              if s >= w0 and s + d <= w1]
    if not inside or len(spans) < len(execs) \
            or any(sp["args"].get("chunk") is None for sp in spans):
        return None
    j, spread = pair([s for s, _ in execs], [sp["ts"] for sp in spans])
    context, seen = [], {}
    for sp in spans:            # tokens of the request's earlier passes
        context.append(seen.get(sp["rid"], 0))
        seen[sp["rid"]] = context[-1] + sp["args"]["chunk"]
    least = sum(pass_min_seconds(run, spans[j + i]["args"]["chunk"],
                                 context[j + i]) for i in inside)
    device = sum(execs[i][1] for i in inside)
    log(f"prefill roofline: {len(inside)} passes in the slice are passes "
        f"{j + inside[0]}..{j + inside[-1]} of the window's {len(spans)} "
        f"(lags agree to {1e3 * spread:.3f} ms), least {1e3 * least:.1f} ms "
        f"of {1e3 * device:.1f} ms on the device")
    return 100.0 * least / device
