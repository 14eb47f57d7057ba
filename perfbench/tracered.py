"""Reduction from a profiler trace to numbers.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into
plain lists; everything below it works on those lists, so the arithmetic
is checked against a small recorded trace (tests/recorded_trace.json)
without a chip. Times are seconds on the profiler's clock.

* device busy time: the union of the intervals in which an operation ran
  on a device ("XLA Ops" line of each ``/device:TPU:n`` plane);
* idle gaps: the complement of that union inside the window, each piece
  attributed to the innermost host span that covers it;
* per-entry device time: durations of the "XLA Modules" events (one per
  execution of a jitted program) whose name contains the entry's name.
"""

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SHORT_GAP_S = 20e-6
SHORT_GAPS = "gaps_under_20us_between_operations"
UNATTRIBUTED = "unattributed"


def short_op_name(name):
    """The profiler names a device operation by its whole HLO line
    ("%fusion.12 = (bf16[...]) fusion(...)"); keep "fusion.12"."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path, marker=None):
    """{"devices": {plane: {line: [(name, start_s, dur_s)]}},
    "marker_s": start of the host event called `marker`, or None}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, marker_s = {}, None
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        if not is_dev and marker is None:
            continue
        lines = {}
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for ev in line.events:
                if is_dev:
                    evs.append((short_op_name(ev.name), ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9))
                elif ev.name == marker and marker_s is None:
                    marker_s = ev.start_ns * 1e-9
            if is_dev:
                lines[line.name] = evs
        if is_dev:
            devices[plane.name] = lines
    return {"devices": devices, "marker_s": marker_s}


def merge_intervals(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, w0, w1):
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if min(e, w1) > max(s, w0)]


def busy_seconds(op_events, w0, w1):
    """(busy seconds, merged busy intervals) of one device in [w0, w1]."""
    merged = merge_intervals(clip(
        [(s, s + d) for _, s, d in op_events], w0, w1))
    return sum(e - s for s, e in merged), merged


def idle_gaps(merged_busy, w0, w1):
    gaps, t = [], w0
    for s, e in merged_busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def attribute_gaps(gaps, host_spans):
    """Seconds of idle time by what the host was doing. `host_spans` are
    (name, start_s, dur_s) on the same clock. A piece of a gap goes to the
    covering span that started last (the innermost); gaps shorter than
    20 us are the device's own turn-around and are summed apart."""
    spans = sorted(((s, s + d, n) for n, s, d in host_spans))
    out = {}
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_S:
            out[SHORT_GAPS] = out.get(SHORT_GAPS, 0.0) + (g1 - g0)
            continue
        over = [(s, e, n) for s, e, n in spans if e > g0 and s < g1]
        cuts = sorted({g0, g1} | {min(max(x, g0), g1)
                                  for s, e, _ in over for x in (s, e)})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            cover = [(s, n) for s, e, n in over if s <= mid < e]
            name = max(cover)[1] if cover else UNATTRIBUTED
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def entry_durations(module_events, entry, w0, w1):
    """Device seconds of each execution of the jitted program `entry`
    that lies wholly inside [w0, w1]."""
    return [d for n, s, d in module_events
            if entry in n and s >= w0 and s + d <= w1]


def top_ops(op_events, w0, w1, n=10):
    tot = {}
    for name, s, d in op_events:
        if s >= w0 and s + d <= w1:
            tot[name] = tot.get(name, 0.0) + d
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def reduce_trace(loaded, w0, w1, host_spans):
    """All the per-device numbers of one traced window [w0, w1]."""
    devs = loaded["devices"]
    if not devs:
        raise RuntimeError("the trace holds no /device:TPU plane")
    busy, gaps_by, ops_all, modules = [], {}, [], []
    for lines in devs.values():
        ops = lines.get(OPS_LINE, [])
        b, merged = busy_seconds(ops, w0, w1)
        busy.append(b)
        ops_all.extend(ops)
        modules.extend(lines.get(MODULES_LINE, []))
        for k, v in attribute_gaps(idle_gaps(merged, w0, w1),
                                   host_spans).items():
            gaps_by[k] = gaps_by.get(k, 0.0) + v / len(devs)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": w1 - w0,
        "modules": modules,
        "device_ops": [[k, v] for k, v in top_ops(ops_all, w0, w1)],
        "idle_gaps": [[k, v] for k, v in sorted(
            gaps_by.items(), key=lambda kv: -kv[1])[:10]],
    }
