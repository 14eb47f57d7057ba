"""Unified telemetry: metrics registry + span tracing, off the hot path.

The stack's observability was fragmented — a singleton section timer
(util.profiler.OpProfiler), ad-hoc ``stats`` dicts on the micro-batcher,
loadgen-only percentiles, print-style listeners. A system serving real
traffic needs first-class monitoring the way TensorFlow ships it as part
of the system design (arXiv:1605.08695); under whole-program compilation
(arXiv:1810.09868) the right unit of observation is the DISPATCHED
EXECUTABLE, not the op — which is exactly what lets every instrument in
this module live at dispatch boundaries, on host-side code that already
runs between device dispatches, with zero added device syncs and zero
added compiles (tier-1: RetraceSentinel in tests/test_telemetry.py and
tests/test_span_tree.py; the measured cost of the spans on the chip is
in docs/OBSERVABILITY.md).

Three cooperating pieces:

* ``MetricsRegistry`` — process-wide, thread-safe counters / gauges /
  fixed-bucket histograms (with exact percentile readout over a bounded
  sample reservoir), optional Prometheus-style labels, an injectable
  clock (pair with ``serving.queue.ManualClock`` so tier-1 latency tests
  run with zero sleeps), a JSON ``snapshot()`` and Prometheus
  text-exposition ``prometheus()`` (served on ``GET /metrics`` by
  ``serving.server.InferenceServer``).
* span tracing — ``span()``/``add_span()``/``event()`` record structured
  spans (train step wall, fitDataSet staging vs data-wait, AOT
  compile/deserialize, serving coalesce→dispatch→reply, the sequence
  scheduler's iteration tree) into a bounded ring buffer, exportable as
  Chrome trace-event JSON (``export_chrome_trace``) viewable in Perfetto
  (ui.perfetto.dev → open trace file). Every span carries an ``id``,
  the ``parent`` that caused it and the ``rid`` of its request;
  ``phase()`` is the set-up span that also feeds a counter.
  docs/OBSERVABILITY.md has the span taxonomy and a how-to.
* a process-wide kill switch — ``set_enabled(False)`` (or env
  ``DL4J_TPU_TELEMETRY=off``) turns every instrument write and span
  record into a cheap no-op; the cost of the spans is measured
  against exactly this mode.

This module imports NO jax and performs NO device operations — the
purity linter's PUR02 (host sync inside traced code) is clean over it by
construction, and it is safe to call from trace-time code (e.g. the
RetraceSentinel's compile counter).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import math
import os
import threading
import time

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "TraceBuffer",
    "get_registry", "set_enabled", "enabled", "percentile", "phase",
    "DEFAULT_BUCKETS",
]

# process-wide kill switch (the overhead A/B: instrumented vs disabled)
_ENABLED = os.environ.get("DL4J_TPU_TELEMETRY", "on").lower() \
    not in ("off", "0", "false", "no")


def set_enabled(on: bool) -> bool:
    """Flip the process-wide telemetry switch. Disabled = every
    instrument write and span record is a cheap no-op (reads — snapshot,
    prometheus, export — keep working on whatever was recorded)."""
    global _ENABLED
    _ENABLED = bool(on)
    return _ENABLED


def enabled() -> bool:
    return _ENABLED


# ----------------------------------------------------------------------
# shared percentile math (the ONE implementation: histogram readout and
# serving.loadgen both use it; tested against the numpy oracle)
# ----------------------------------------------------------------------
def percentile(values, q):
    """Linear-interpolated percentile (q in [0, 100]) of a sequence —
    the same 'linear' method numpy defaults to, without requiring the
    input pre-sorted. Returns None for an empty sequence."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return None
    q = float(q)
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    rank = (len(vals) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = math.ceil(rank)
    if lo == hi:
        return vals[int(rank)]
    frac = rank - lo
    return vals[lo] * (1.0 - frac) + vals[hi] * frac


# ----------------------------------------------------------------------
# instruments
# ----------------------------------------------------------------------

#: default latency buckets (seconds) — µs dispatches through multi-second
#: compiles all land in a named bucket
DEFAULT_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                   0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                   30.0, 60.0)

#: raw samples a histogram retains for exact percentile readout; past
#: this the reservoir is a sliding window of the most recent samples
DEFAULT_SAMPLE_CAP = 8192

_NAME_OK = None  # compiled lazily (module import stays re-importable)


def _check_name(name):
    global _NAME_OK
    if _NAME_OK is None:
        import re

        _NAME_OK = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    if not _NAME_OK.match(name):
        raise ValueError(
            f"invalid metric name {name!r}: Prometheus names match "
            "[a-zA-Z_:][a-zA-Z0-9_:]*")
    return name


def _escape_label(v):
    """Prometheus label-value escaping: backslash, double-quote, LF."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _escape_help(v):
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


class _Child:
    """One (instrument, label-values) time series. Counter/gauge state is
    a float; histogram state is bucket counts + sum + a bounded sample
    reservoir. All mutation goes through the parent instrument's lock."""

    __slots__ = ("_parent", "labels", "value", "bucket_counts", "sum",
                 "count", "samples")

    def __init__(self, parent, labels):
        self._parent = parent
        self.labels = labels          # dict, insertion == labelnames order
        self.value = 0.0
        if parent.kind == "histogram":
            self.bucket_counts = [0] * (len(parent.buckets) + 1)
            self.sum = 0.0
            self.count = 0
            self.samples = []         # bounded ring, newest last

    # -- counter / gauge -------------------------------------------------
    def inc(self, n=1.0):
        if not _ENABLED:
            return self
        if self._parent.kind == "counter" and n < 0:
            raise ValueError(f"counter increment must be >= 0, got {n}")
        with self._parent._lock:
            self.value += n
        return self

    def dec(self, n=1.0):
        if self._parent.kind != "gauge":
            raise TypeError(f"dec() on a {self._parent.kind}")
        return self.inc(-n)

    def set(self, v):
        if self._parent.kind != "gauge":
            raise TypeError(f"set() on a {self._parent.kind}")
        if not _ENABLED:
            return self
        with self._parent._lock:
            self.value = float(v)
        return self

    # -- histogram ---------------------------------------------------------
    def observe(self, v):
        if self._parent.kind != "histogram":
            raise TypeError(f"observe() on a {self._parent.kind}")
        if not _ENABLED:
            return self
        v = float(v)
        p = self._parent
        with p._lock:
            i = 0
            for i, edge in enumerate(p.buckets):  # noqa: B007
                if v <= edge:
                    break
            else:
                i = len(p.buckets)
            self.bucket_counts[i] += 1
            self.sum += v
            self.count += 1
            self.samples.append(v)
            if len(self.samples) > p.sample_cap:
                del self.samples[:len(self.samples) - p.sample_cap]
        return self

    def percentile(self, q):
        """Exact linear-interpolated percentile over the retained
        samples (exact for the whole series while count <= sample_cap;
        past that, over the most recent sample_cap observations)."""
        with self._parent._lock:
            vals = list(self.samples)
        return percentile(vals, q)

    def mean(self):
        """Mean over ALL observations (sum/count, not the bounded
        reservoir); None before the first observe. The fleet brownout
        controller's measured per-item service estimate
        (serving/fleet.py)."""
        with self._parent._lock:
            return self.sum / self.count if self.count else None

    def reset(self):
        """Zero this series in place (handles cached by callers stay
        attached — MicroBatcher/OpProfiler read-through views rely on
        it)."""
        with self._parent._lock:
            self.value = 0.0
            if self._parent.kind == "histogram":
                self.bucket_counts = [0] * (len(self._parent.buckets) + 1)
                self.sum = 0.0
                self.count = 0
                self.samples = []
        return self


class _Instrument:
    """Base: a named family of label-distinguished children. The
    unlabeled instrument IS its own () child, so `counter.inc()` and
    `counter.labels(x=1).inc()` are the same machinery."""

    kind = None

    def __init__(self, name, help="", labelnames=()):
        self.name = _check_name(name)
        self.help = str(help)
        self.labelnames = tuple(labelnames)
        self._lock = threading.RLock()
        self._children = {}
        if not self.labelnames:
            self._default = self._make_child_locked({})
        else:
            self._default = None

    def _make_child_locked(self, labels):
        # *_locked: caller holds self._lock (construction-time calls
        # trivially satisfy it — the instance is unpublished)
        child = _Child(self, labels)
        self._children[tuple(labels.values())] = child
        return child

    def _label_key(self, kv):
        if tuple(sorted(kv)) != tuple(sorted(self.labelnames)):
            raise ValueError(
                f"{self.name} declares labels {self.labelnames}, "
                f"got {tuple(sorted(kv))}")
        return tuple(str(kv[ln]) for ln in self.labelnames)

    def labels(self, **kv):
        """The child time series for exactly this label set (created on
        first use). Label names must match the declared labelnames."""
        key = self._label_key(kv)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._make_child_locked(
                    {ln: str(kv[ln]) for ln in self.labelnames})
        return child

    def labels_get(self, **kv):
        """The child for this label set, or None — a READ that never
        creates a series (facade read paths use it so probing an
        unknown label can't grow the registry)."""
        with self._lock:
            return self._children.get(self._label_key(kv))

    def remove(self, **kv):
        """Drop this label set's series from the family (no-op when it
        does not exist). A handle already cached by a caller keeps
        working but is detached — the series no longer appears in
        exposition/snapshot. Lifecycle owners (MicroBatcher.close) use
        it so per-instance series don't accumulate forever."""
        with self._lock:
            self._children.pop(self._label_key(kv), None)
        return self

    def _only(self):
        if self._default is None:
            raise ValueError(
                f"{self.name} is labeled {self.labelnames}: address a "
                "series via .labels(...)")
        return self._default

    def children(self):
        with self._lock:
            return list(self._children.values())

    def reset(self):
        for c in self.children():
            c.reset()
        return self


class Counter(_Instrument):
    kind = "counter"

    def inc(self, n=1.0):
        return self._only().inc(n)

    @property
    def value(self):
        return self._only().value


class Gauge(_Instrument):
    kind = "gauge"

    def set(self, v):
        return self._only().set(v)

    def inc(self, n=1.0):
        return self._only().inc(n)

    def dec(self, n=1.0):
        return self._only().dec(n)

    @property
    def value(self):
        return self._only().value


class Histogram(_Instrument):
    kind = "histogram"

    def __init__(self, name, help="", labelnames=(), buckets=None,
                 sample_cap=DEFAULT_SAMPLE_CAP):
        buckets = DEFAULT_BUCKETS if buckets is None else tuple(
            sorted(float(b) for b in buckets))
        if not buckets:
            raise ValueError("histogram needs at least one bucket edge")
        self.buckets = buckets
        self.sample_cap = int(sample_cap)
        super().__init__(name, help, labelnames)

    def observe(self, v):
        return self._only().observe(v)

    def percentile(self, q):
        return self._only().percentile(q)

    @property
    def count(self):
        return self._only().count

    @property
    def sum(self):
        return self._only().sum


# ----------------------------------------------------------------------
# span tracing
# ----------------------------------------------------------------------
#: span ids: one counter for the process, so an id names one span
#: whatever registry recorded it (next() on it is atomic under the GIL)
_SPAN_IDS = itertools.count(1)

_SPAN_KEYS = ("id", "parent", "rid", "name", "cat", "ts", "dur", "ph",
              "pid", "tid", "args")


#: spans the ring keeps: a busy paged scheduler with a decode step queued
#: ahead leaves about 10.5 spans an iteration (the AOT layer's two of a
#: dispatch and the fetch's wait included), so a 40 s window at 120
#: iterations a second is about 51 000, and the ring holds two and a
#: half times that, about 45 MB at some 350 bytes a span
TRACE_CAPACITY = 131072


class TraceBuffer:
    """Bounded ring of structured spans. A span reads as one dict:
    {id, parent (the id of the span that caused it, or None), rid (the
    request it belongs to, or None), name, cat, ts (seconds on the
    registry clock), dur (seconds), ph ('X' complete span / 'i'
    instant), pid, tid, args} — directly mappable to the Chrome
    trace-event format Perfetto loads. The ring keeps tuples and builds
    the dicts on read; ``dropped`` counts what the bound evicted since
    the last clear(): a reader that finds it above 0 is looking at the
    newest part of its window only."""

    def __init__(self, capacity=TRACE_CAPACITY):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._spans = collections.deque(maxlen=self.capacity)
        self.dropped = 0   # spans evicted by the ring bound

    def add(self, name, cat, ts, dur, args=None, ph="X", parent=None,
            rid=None, span_id=None):
        """Record one span; returns its id (`span_id` where the caller
        drew it beforehand with new_span_id(), so that children recorded
        earlier could name it), or None when telemetry is off."""
        if not _ENABLED:
            return None
        if span_id is None:
            span_id = next(_SPAN_IDS)
        span = (span_id, parent, rid, str(name), str(cat), float(ts),
                float(dur), ph, os.getpid(), threading.get_ident(),
                dict(args) if args else {})
        with self._lock:
            if len(self._spans) == self.capacity:
                self.dropped += 1
            self._spans.append(span)
        return span_id

    def spans(self):
        with self._lock:
            raw = list(self._spans)
        return [dict(zip(_SPAN_KEYS, s[:-1] + (dict(s[-1]),)))
                for s in raw]

    def clear(self):
        with self._lock:
            self._spans.clear()
            self.dropped = 0


class MetricsRegistry:
    """Process-wide instrument + trace registry (module docstring).

    clock: monotonic seconds callable (default time.perf_counter);
    inject serving.queue.ManualClock for deterministic tests. The clock
    stamps spans; components with their OWN clock (MicroBatcher) record
    spans with explicit timestamps via add_span.
    """

    def __init__(self, clock=None, trace_capacity=TRACE_CAPACITY):
        self.clock = clock if clock is not None else time.perf_counter
        self._lock = threading.RLock()
        self._instruments = {}
        self.trace = TraceBuffer(trace_capacity)
        self._open = threading.local()   # .stack: ids of open span()s

    # -- instrument factories (get-or-create, type-checked) -------------
    def _get_or_create(self, cls, name, help, labelnames, **kw):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is not None:
                if not isinstance(inst, cls):
                    raise TypeError(
                        f"{name} already registered as {inst.kind}, "
                        f"requested {cls.kind}")
                if tuple(labelnames) != inst.labelnames:
                    raise ValueError(
                        f"{name} already registered with labels "
                        f"{inst.labelnames}, requested {tuple(labelnames)}")
                return inst
            inst = cls(name, help, labelnames, **kw)
            self._instruments[name] = inst
            return inst

    def counter(self, name, help="", labels=()):
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name, help="", labels=()):
        return self._get_or_create(Gauge, name, help, labels)

    def histogram(self, name, help="", labels=(), buckets=None,
                  sample_cap=DEFAULT_SAMPLE_CAP):
        return self._get_or_create(Histogram, name, help, labels,
                                   buckets=buckets, sample_cap=sample_cap)

    def get(self, name):
        with self._lock:
            return self._instruments.get(name)

    def instruments(self):
        with self._lock:
            return dict(self._instruments)

    def reset(self):
        """Zero every series and clear the trace ring IN PLACE —
        instrument/child handles cached by callers stay attached."""
        for inst in self.instruments().values():
            inst.reset()
        self.trace.clear()
        return self

    # -- tracing ---------------------------------------------------------
    @staticmethod
    def new_span_id():
        """Draw a span id ahead of its span: code that owns its clock
        records children (``add_span(parent=...)``) before the parent
        that encloses them ends (``add_span(span_id=...)``)."""
        return next(_SPAN_IDS)

    def current_span_id(self):
        """The id of the innermost ``span()`` block open on this thread,
        or None — what ``parent`` a span recorded by hand inside it
        takes."""
        stack = getattr(self._open, "stack", None)
        return stack[-1] if stack else None

    @contextlib.contextmanager
    def span(self, name, cat="", rid=None, **args):
        """Record the wrapped block as one complete span on this
        registry's clock. Blocks nest: each thread keeps a stack of its
        open spans and a span's ``parent`` is the one it opened inside.
        No-op (beyond one switch read) when telemetry is disabled."""
        if not _ENABLED:
            yield
            return
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        span_id = next(_SPAN_IDS)
        parent = stack[-1] if stack else None
        stack.append(span_id)
        t0 = self.clock()
        try:
            yield
        finally:
            dur = self.clock() - t0
            stack.pop()
            self.trace.add(name, cat, t0, dur, args, parent=parent,
                           rid=rid, span_id=span_id)

    def add_span(self, name, cat, ts, dur, parent=None, rid=None,
                 span_id=None, **args):
        """Record a span with explicit start/duration (seconds) — for
        components that own their clock (MicroBatcher's ManualClock,
        the sequence schedulers), which also name its ``parent`` and
        ``rid`` themselves. Returns the span's id."""
        return self.trace.add(name, cat, ts, dur, args, parent=parent,
                              rid=rid, span_id=span_id)

    def event(self, name, cat="", ts=None, rid=None, **args):
        """Record an instant event (Chrome ph 'i') at `ts`, default
        now on this registry's clock."""
        return self.trace.add(name, cat,
                              self.clock() if ts is None else ts, 0.0,
                              args, ph="i", rid=rid)

    # -- export ----------------------------------------------------------
    def snapshot(self):
        """JSON-safe nested view of every instrument: the
        ``host.metrics_snapshot()`` / bench-record surface."""
        out = {}
        for name, inst in sorted(self.instruments().items()):
            series = []
            for c in inst.children():
                with inst._lock:
                    if inst.kind == "histogram":
                        rec = {"labels": dict(c.labels),
                               "count": c.count,
                               "sum": round(c.sum, 9),
                               "p50": percentile(c.samples, 50),
                               "p99": percentile(c.samples, 99),
                               "buckets": dict(zip(
                                   [str(b) for b in inst.buckets]
                                   + ["+Inf"], c.bucket_counts))}
                    else:
                        rec = {"labels": dict(c.labels), "value": c.value}
                series.append(rec)
            out[name] = {"kind": inst.kind, "help": inst.help,
                         "series": series}
        return out

    def prometheus(self):
        """Prometheus text exposition (format version 0.0.4): HELP/TYPE
        lines, label escaping, cumulative histogram buckets with the
        canonical le= edges plus _sum/_count."""
        lines = []
        for name, inst in sorted(self.instruments().items()):
            if inst.help:
                lines.append(f"# HELP {name} {_escape_help(inst.help)}")
            lines.append(f"# TYPE {name} {inst.kind}")
            for c in inst.children():
                base = ",".join(
                    f'{k}="{_escape_label(v)}"'
                    for k, v in c.labels.items())
                if inst.kind == "histogram":
                    with inst._lock:
                        counts = list(c.bucket_counts)
                        total, csum = c.count, c.sum
                    cum = 0
                    for edge, n in zip(inst.buckets, counts):
                        cum += n
                        lab = (base + "," if base else "") + \
                            f'le="{edge:g}"'
                        lines.append(f"{name}_bucket{{{lab}}} {cum}")
                    lab = (base + "," if base else "") + 'le="+Inf"'
                    lines.append(f"{name}_bucket{{{lab}}} {total}")
                    suffix = f"{{{base}}}" if base else ""
                    lines.append(f"{name}_sum{suffix} {csum:g}")
                    lines.append(f"{name}_count{suffix} {total}")
                else:
                    suffix = f"{{{base}}}" if base else ""
                    lines.append(f"{name}{suffix} {c.value:g}")
        return "\n".join(lines) + "\n"

    def chrome_trace(self):
        """The trace ring as a Chrome trace-event JSON object —
        ui.perfetto.dev opens the dumped file directly. ts/dur are
        microseconds per the trace-event spec."""
        events = []
        for s in self.trace.spans():
            ev = {"name": s["name"], "cat": s["cat"] or "default",
                  "ph": s["ph"], "ts": s["ts"] * 1e6,
                  "pid": s["pid"], "tid": s["tid"], "args": s["args"],
                  "id": s["id"]}
            for k in ("parent", "rid"):
                if s[k] is not None:
                    ev[k] = s[k]
            if s["ph"] == "X":
                ev["dur"] = s["dur"] * 1e6
            else:
                ev["s"] = "t"   # instant scope: thread
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path):
        """Write chrome_trace() to `path` (atomic tmp+rename); returns
        the path."""
        tmp = f"{path}.tmp"
        with open(tmp, "w") as fh:  # fault-ok[FLT02]: observability export, off every dispatch path — an export failure raises to the operator who asked for the file; nothing in the serving tier depends on it
            json.dump(self.chrome_trace(), fh)
        os.replace(tmp, path)
        return path


# ----------------------------------------------------------------------
# the process-wide default registry
# ----------------------------------------------------------------------
_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide registry every built-in instrument lives in.
    Its identity is stable for the process lifetime — cache instrument
    handles freely; registry.reset() zeroes values in place."""
    return _REGISTRY


@contextlib.contextmanager
def phase(name):
    """One phase of set-up (weights_init, warm): a span
    ``setup.<name>`` on the process-wide registry whose duration is also
    added to the counter ``dl4j_setup_seconds{phase}``. A counter
    because a benchmark clears the ring when its window opens; labelled
    by phase and not by model so that closing a model leaves it."""
    reg = _REGISTRY
    t0 = reg.clock()
    try:
        with reg.span(f"setup.{name}", "setup"):
            yield
    finally:
        reg.counter("dl4j_setup_seconds",
                    "host seconds spent in each phase of set-up",
                    labels=("phase",)).labels(phase=name).inc(
                        reg.clock() - t0)
