"""Profiling and per-step timing.

Reference: org.nd4j.linalg.profiler.OpProfiler + PerformanceListener's
timing half. On TPU the unit of work is the jitted step, not the single
op, so the profiler accounts (a) wall time per named section with
compile-time (first call) split from steady-state, and (b) optionally
wraps ``jax.profiler`` traces for inspection in TensorBoard/XProf.
"""

from __future__ import annotations

import contextlib
import threading
import time


class OpProfiler:
    """Singleton section timer (reference: OpProfiler.getInstance()),
    re-implemented as a thin facade over the telemetry registry
    (runtime.telemetry): every steady-state section observation lands
    in the ``dl4j_profiler_section_seconds{section=...}`` histogram and
    the first-call (compile) wall in the
    ``dl4j_profiler_compile_seconds{section=...}`` gauge, so old call
    sites keep their API while /metrics and metrics_snapshot() see the
    same data. Thread-safe (serving worker threads time sections
    concurrently — the old defaultdict mutation raced), clock
    injectable (``OpProfiler(clock=ManualClock())`` in tests)."""

    _instance = None
    _instance_lock = threading.Lock()

    @classmethod
    def getInstance(cls) -> "OpProfiler":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = OpProfiler()
            return cls._instance

    def __init__(self, clock=None, registry=None):
        from deeplearning4j_tpu.runtime import telemetry

        if registry is None:
            registry = telemetry.get_registry()
        self._registry = registry
        self._clock = clock if clock is not None else registry.clock
        self._lock = threading.RLock()
        self._steady = registry.histogram(
            "dl4j_profiler_section_seconds",
            "OpProfiler steady-state section wall (first call excluded)",
            labels=("section",))
        self._compile = registry.gauge(
            "dl4j_profiler_compile_seconds",
            "OpProfiler first-call wall ~ compile time under jit",
            labels=("section",))
        self._first = {}  # section -> first-call wall (compile split)

    def reset(self):
        """Zero this profiler's sections in place (its registry series
        included — handles stay attached, the singleton contract)."""
        with self._lock:
            for name in self._first:
                self._steady.labels(section=name).reset()
                self._compile.labels(section=name).reset()
            self._first = {}
        return self

    @contextlib.contextmanager
    def section(self, name: str):
        from deeplearning4j_tpu.runtime import telemetry

        t0 = self._clock()
        try:
            yield
        finally:
            # the kill switch skips ALL bookkeeping (incl. the
            # first-call split) so disabled-mode readings stay
            # internally consistent: invocations 0, times 0
            if telemetry.enabled():
                dt = self._clock() - t0
                with self._lock:
                    if name not in self._first:
                        self._first[name] = dt
                        self._compile.labels(section=name).set(dt)
                    else:
                        self._steady.labels(section=name).observe(dt)
                self._registry.trace.add(f"profiler.{name}", "profiler",
                                         t0, dt)

    def _steady_child(self, name):
        # READ path: must not create a series for a probed-but-never-
        # timed section name
        return self._steady.labels_get(section=name)

    def timeSpent(self, name: str) -> float:
        """Steady-state seconds (excludes the first, compiling call)."""
        c = self._steady_child(name)
        return c.sum if c is not None else 0.0

    def invocations(self, name: str) -> int:
        with self._lock:
            seen = name in self._first
        c = self._steady_child(name)
        return (c.count if c is not None else 0) + (1 if seen else 0)

    def compileTime(self, name: str) -> float:
        with self._lock:
            return self._first.get(name, 0.0)

    def averageTime(self, name: str) -> float:
        c = self._steady_child(name)
        return c.sum / max(c.count, 1) if c is not None else 0.0

    def printOutDashboard(self) -> str:
        lines = [f"{'section':<28}{'calls':>7}{'compile_s':>11}"
                 f"{'steady_avg_ms':>15}{'total_s':>9}"]
        with self._lock:
            names = sorted(self._first)  # snapshot vs concurrent sections
        for name in names:
            lines.append(f"{name:<28}{self.invocations(name):>7}"
                         f"{self.compileTime(name):>11.3f}"
                         f"{self.averageTime(name) * 1e3:>15.3f}"
                         f"{self.timeSpent(name):>9.3f}")
        out = "\n".join(lines)
        print(out)
        return out


# ----------------------------------------------------------------------
# FLOP accounting / MFU (reference: OpProfiler's op-level flop counters;
# on TPU the XLA compiler already knows the whole-step flop count, so we
# read it from the compiled executable instead of re-deriving per-op)
# ----------------------------------------------------------------------

def compiled_cost(fn, *args, **kwargs) -> dict:
    """FLOPs + HBM bytes of one call of `fn(*args, **kwargs)` as XLA
    compiled it: {'flops': float, 'bytes_accessed': float}. `fn` may
    already be jitted; costs come from lower().compile().cost_analysis()."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    ca = jitted.lower(*args, **kwargs).compile().cost_analysis() or {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


@contextlib.contextmanager
def trace(log_dir: str):
    """jax.profiler device trace around a block — open the dump with
    XProf/TensorBoard. (Reference analogue: ProfilerConfig + nvprof.)"""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region inside a trace (maps to jax.profiler.TraceAnnotation)."""
    import jax

    return jax.profiler.TraceAnnotation(name)
