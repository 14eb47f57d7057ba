"""Thread-safety lint tests (analysis/threads.py, pass 8 — ISSUE 14).

Matrix: every THR01-THR04 code triggered by a deliberately broken
fixture, the safe twins unflagged (double-checked lazy init, the
*_locked convention, Condition.wait on the held condition, RLock
reentrance), suppression semantics (justified thread-ok suppresses, a
bare tag does not), the tier-1 clean gate over the package's threaded
tier, the --concurrency CLI exit-code contract, and live regression
tests for the two races this PR's audit fixed (CachedJit single-flight
compile; HttpServerOwner concurrent start).
"""

import textwrap
import threading

import numpy as np
import pytest

from deeplearning4j_tpu.analysis.threads import (
    THREADED_TIER, lint_thread_paths, lint_thread_source,
)


def _codes(report):
    return set(report.codes())


def _errors(report, code):
    return [d for d in report.errors if d.code == code]


# ======================================================================
# THR01 — guarded state outside its lock
# ======================================================================

_THR01 = textwrap.dedent('''
    import threading

    class Stats:
        """Thread-safe section store."""

        def __init__(self):
            self._lock = threading.Lock()
            self._totals = {}
            self._notes = []

        def record(self, k, v):
            with self._lock:
                self._totals[k] = self._totals.get(k, 0) + v

        def bump(self, k):
            self._totals[k] = 0          # THR01: write outside the lock

        def peek(self, k):
            return self._totals.get(k)   # THR01: read outside the lock

        def note(self, s):
            self._notes.append(s)        # never lock-guarded: no finding
''')


class TestThr01:
    def test_unlocked_write_and_read_flag(self):
        rep = lint_thread_source(_THR01, "t.py")
        assert len(_errors(rep, "THR01")) == 2, rep.format()
        msgs = [d.message for d in _errors(rep, "THR01")]
        assert any("bump" in m for m in msgs)
        assert any("peek" in m for m in msgs)

    def test_mutator_call_counts_as_write(self):
        src = textwrap.dedent('''
            import threading

            class Q:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []

                def put(self, x):
                    with self._lock:
                        self._items.append(x)

                def drop(self):
                    self._items.clear()     # THR01 via mutator call
        ''')
        rep = lint_thread_source(src, "t.py")
        assert _errors(rep, "THR01"), rep.format()

    def test_init_and_locked_suffix_exempt(self):
        src = textwrap.dedent('''
            import threading

            class Q:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []      # construction: exempt

                def put(self, x):
                    with self._lock:
                        self._items.append(x)
                        self._drain_locked()

                def _drain_locked(self):
                    while self._items:    # caller holds the lock: exempt
                        self._items.pop()
        ''')
        rep = lint_thread_source(src, "t.py")
        assert rep.ok, rep.format()

    def test_lock_alias_recognized(self):
        src = textwrap.dedent('''
            import threading

            class R:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._v = 0

                def set(self, v):
                    with self._lock:
                        self._v = v

                def set2(self, v):
                    lock = self._lock
                    with lock:            # alias of the same lock
                        self._v = v
        ''')
        rep = lint_thread_source(src, "t.py")
        assert rep.ok, rep.format()

    def test_method_local_lock_does_not_mask(self):
        """A method-local `gate = threading.Lock()` must NOT register
        as a class lock: a same-named local in another method would
        otherwise read as 'lock held' and mask real THR01 findings
        (code-review regression)."""
        src = textwrap.dedent('''
            import threading

            class M:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def inc(self):
                    with self._lock:
                        self._n += 1

                def helper(self):
                    gate = threading.Lock()
                    with gate:
                        pass

                def racy(self, gate):
                    with gate:             # unrelated parameter
                        self._n = 0        # NOT under self._lock
        ''')
        rep = lint_thread_source(src, "t.py")
        assert _errors(rep, "THR01"), rep.format()

    def test_with_context_expr_visited(self):
        """Blocking calls inside a nested with-ITEM expression execute
        under the outer lock and must flag (code-review regression)."""
        src = textwrap.dedent('''
            import threading
            import time

            class W:
                def __init__(self):
                    self._lock = threading.Lock()

                def go(self):
                    with self._lock:
                        with self.open(time.sleep(5)):
                            pass

                def open(self, x):
                    return x
        ''')
        rep = lint_thread_source(src, "t.py")
        assert _errors(rep, "THR03"), rep.format()

    def test_non_concurrent_class_ignored(self):
        src = textwrap.dedent('''
            class Plain:
                def __init__(self):
                    self._items = []

                def put(self, x):
                    self._items.append(x)
        ''')
        assert lint_thread_source(src, "t.py").ok


# ======================================================================
# THR02 — lock-order inversion
# ======================================================================

_THR02 = textwrap.dedent('''
    import threading

    class Transfer:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def ab(self):
            with self._a:
                with self._b:
                    pass

        def ba(self):
            with self._b:
                with self._a:     # ABBA
                    pass
''')


class TestThr02:
    def test_abba_flags(self):
        rep = lint_thread_source(_THR02, "t.py")
        assert _errors(rep, "THR02"), rep.format()

    def test_consistent_order_clean(self):
        src = _THR02.replace("with self._b:\n            with self._a:",
                             "with self._a:\n            with self._b:")
        assert "# ABBA" in src and "with self._b:     # ABBA" in src, \
            "fixture rewrite missed — indentation drifted"
        rep = lint_thread_source(src, "t.py")
        assert not _errors(rep, "THR02"), rep.format()

    def test_rlock_reentrance_not_inversion(self):
        src = textwrap.dedent('''
            import threading

            class R:
                def __init__(self):
                    self._lock = threading.RLock()

                def outer(self):
                    with self._lock:
                        with self._lock:
                            pass
        ''')
        assert not _errors(lint_thread_source(src, "t.py"), "THR02")

    def test_one_level_call_edge(self):
        """Holding A while calling a method whose body takes B closes
        the cycle even without lexical nesting."""
        src = textwrap.dedent('''
            import threading

            class T:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    with self._a:
                        self.takes_b()

                def takes_b(self):
                    with self._b:
                        pass

                def ba(self):
                    with self._b:
                        with self._a:
                            pass
        ''')
        rep = lint_thread_source(src, "t.py")
        assert _errors(rep, "THR02"), rep.format()

    def test_aliased_lock_call_edge(self):
        """A lock held through a local alias (`lock = self._a`) still
        contributes interprocedural THR02 edges (code-review
        regression: the old duplicate walker missed aliases)."""
        src = textwrap.dedent('''
            import threading

            class T:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    lock = self._a
                    with lock:
                        self.takes_b()

                def takes_b(self):
                    with self._b:
                        pass

                def ba(self):
                    with self._b:
                        with self._a:
                            pass
        ''')
        rep = lint_thread_source(src, "t.py")
        assert _errors(rep, "THR02"), rep.format()


# ======================================================================
# THR03 — blocking under a held lock
# ======================================================================

class TestThr03:
    def test_sleep_under_lock_flags(self):
        src = textwrap.dedent('''
            import threading
            import time

            class W:
                def __init__(self):
                    self._lock = threading.Lock()

                def spin(self):
                    with self._lock:
                        time.sleep(0.1)
        ''')
        rep = lint_thread_source(src, "t.py")
        assert _errors(rep, "THR03"), rep.format()

    def test_queue_get_and_thread_join_flag(self):
        src = textwrap.dedent('''
            import queue
            import threading

            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()
                    self._worker = threading.Thread(target=self.spin)

                def take(self):
                    with self._lock:
                        return self._q.get()

                def stop(self):
                    with self._lock:
                        self._worker.join(timeout=1.0)
        ''')
        rep = lint_thread_source(src, "t.py")
        assert len(_errors(rep, "THR03")) == 2, rep.format()

    def test_dispatch_under_lock_flags(self):
        src = textwrap.dedent('''
            import threading

            class S:
                def __init__(self, jit):
                    self._lock = threading.Lock()
                    self._jit = jit

                def run(self, x):
                    with self._lock:
                        return self._jit(x)
        ''')
        assert _errors(lint_thread_source(src, "t.py"), "THR03")

    def test_condition_wait_on_held_lock_clean(self):
        """cond.wait RELEASES the held condition — the correct
        scheduler pattern (MicroBatcher._loop) must not flag."""
        src = textwrap.dedent('''
            import threading

            class L:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._items = []

                def loop(self):
                    with self._cond:
                        if not self._items:
                            self._cond.wait(0.05)
        ''')
        rep = lint_thread_source(src, "t.py")
        assert not _errors(rep, "THR03"), rep.format()

    def test_wait_on_other_object_flags(self):
        src = textwrap.dedent('''
            import threading

            class L:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._done = threading.Event()

                def block(self):
                    with self._lock:
                        self._done.wait(5.0)
        ''')
        assert _errors(lint_thread_source(src, "t.py"), "THR03")

    def test_string_join_not_flagged(self):
        src = textwrap.dedent('''
            import threading

            class F:
                def __init__(self):
                    self._lock = threading.Lock()

                def fmt(self, parts):
                    with self._lock:
                        return ", ".join(parts)
        ''')
        assert not _errors(lint_thread_source(src, "t.py"), "THR03")


# ======================================================================
# THR04 — unguarded lazy init
# ======================================================================

class TestThr04:
    def test_unguarded_lazy_init_flags(self):
        src = textwrap.dedent('''
            import threading

            class Server:
                def __init__(self):
                    self._worker = None

                def start(self):
                    if self._worker is None:
                        self._worker = threading.Thread(target=self.run)
                        self._worker.start()
        ''')
        rep = lint_thread_source(src, "t.py")
        assert _errors(rep, "THR04"), rep.format()

    def test_early_return_variant_flags(self):
        src = textwrap.dedent('''
            import threading

            class Server:
                def __init__(self):
                    self._httpd = None

                def start(self):
                    if self._httpd is not None:
                        return self
                    self._httpd = threading.Thread(target=self.run)
                    return self
        ''')
        rep = lint_thread_source(src, "t.py")
        assert _errors(rep, "THR04"), rep.format()

    def test_locked_but_not_rechecked_flags(self):
        """A lock slapped around ONLY the assignment — the None-check
        still runs unlocked and is never re-tested inside — is the
        PR 8 race with a fig leaf; it must flag (code-review
        regression)."""
        src = textwrap.dedent('''
            import threading

            class Lazy:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._res = None

                def get(self):
                    if self._res is None:
                        with self._lock:
                            self._res = object()
                    return self._res
        ''')
        rep = lint_thread_source(src, "t.py")
        assert _errors(rep, "THR04"), rep.format()

    def test_guard_expression_read_not_missed(self):
        """An unlocked read of a lock-guarded attr INSIDE the guard
        test (`if not self._closed:`) is a THR01 check-then-act race —
        the guard expression must be visited (code-review
        regression)."""
        src = textwrap.dedent('''
            import threading

            class Q:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._closed = False
                    self._items = []

                def close(self):
                    with self._lock:
                        self._closed = True

                def put(self, x):
                    if not self._closed:
                        self._items.append(x)
        ''')
        rep = lint_thread_source(src, "t.py")
        assert _errors(rep, "THR01"), rep.format()

    def test_double_checked_under_lock_clean(self):
        """The fixed PR 8 shape: fast-path check + re-check and assign
        INSIDE the lock passes (the fast-path read is THR01's business
        and takes its reasoned suppression)."""
        src = textwrap.dedent('''
            import threading

            class Lazy:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._res = None

                def get(self):
                    with self._lock:
                        if self._res is None:
                            self._res = object()
                        return self._res
        ''')
        rep = lint_thread_source(src, "t.py")
        assert not _errors(rep, "THR04"), rep.format()

    def test_single_threaded_class_ignored(self):
        src = textwrap.dedent('''
            class Lazy:
                def __init__(self):
                    self._res = None

                def get(self):
                    if self._res is None:
                        self._res = object()
                    return self._res
        ''')
        assert lint_thread_source(src, "t.py").ok


# ======================================================================
# suppressions
# ======================================================================

_SUPPRESSED = textwrap.dedent('''
    import threading

    class S:
        def __init__(self):
            self._lock = threading.Lock()
            self._v = 0

        def set(self, v):
            with self._lock:
                self._v = v

        def peek(self):
            return self._v  # thread-ok[THR01]: atomic int read, stale OK

        def peek2(self):
            return self._v  # thread-ok[THR01]
''')


class TestSuppression:
    def test_justified_tag_suppresses_bare_does_not(self):
        rep = lint_thread_source(_SUPPRESSED, "s.py")
        assert len(rep.suppressed) == 1, rep.format(verbose=True)
        assert len(_errors(rep, "THR01")) == 1
        assert not rep.ok   # the bare tag still fails

    def test_star_code_suppresses(self):
        src = _SUPPRESSED.replace("thread-ok[THR01]: atomic",
                                  "thread-ok[*]: atomic")
        rep = lint_thread_source(src, "s.py")
        assert len(rep.suppressed) == 1


# ======================================================================
# tier-1 gates: the package's threaded tier lints clean
# ======================================================================

@pytest.mark.lint
class TestSelfCheck:
    def test_threaded_tier_lints_clean(self):
        """ISSUE 14's audit obligation: the canonical threaded tier
        (serving/, telemetry, aot, autotune, resilience,
        async_iterator, inference, httpserve, profiler) carries zero
        unsuppressed THR findings — every real race was fixed, every
        false positive carries a reasoned thread-ok."""
        rep = lint_thread_paths()
        assert rep.ok, rep.format()
        # the audit left reasoned suppressions, not silence: the
        # double-checked fast paths and the single-flight compile are
        # DOCUMENTED decisions
        assert rep.suppressed, "expected reasoned thread-ok tags"

    def test_whole_package_lints_clean(self):
        import os

        pkg = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "deeplearning4j_tpu")
        rep = lint_thread_paths([pkg])
        assert rep.ok, rep.format()

    def test_tier_paths_exist(self):
        from deeplearning4j_tpu.analysis.threads import (
            threaded_tier_paths,
        )
        import os

        for p in threaded_tier_paths():
            assert os.path.exists(p), p
        assert len(THREADED_TIER) >= 8

    def test_sequence_and_fleet_modules_covered_and_clean(self):
        """ISSUE 15: the two new serving modules (the iteration-level
        scheduler and the fleet router) are INSIDE the linted tier —
        the `serving` directory entry picks them up file-by-file — and
        lint clean on their own: the slot table, step lock and replica
        book keep the PR 14 concurrency discipline."""
        import os

        from deeplearning4j_tpu.analysis.purity import iter_py_files
        from deeplearning4j_tpu.analysis.threads import (
            lint_thread_paths, threaded_tier_paths,
        )

        tier_files = {os.path.basename(p)
                      for p in iter_py_files(threaded_tier_paths())}
        assert {"sequence.py", "fleet.py"} <= tier_files
        import deeplearning4j_tpu as pkg

        base = os.path.join(os.path.dirname(os.path.abspath(
            pkg.__file__)), "serving")
        for mod in ("sequence.py", "fleet.py"):
            rep = lint_thread_paths([os.path.join(base, mod)])
            assert rep.ok, rep.format()

    def test_cli_concurrency_contract(self, tmp_path):
        """--concurrency keeps the CLI's 0/1/2 exit contract."""
        from deeplearning4j_tpu.analysis.cli import main

        assert main(["--concurrency"]) == 0           # package clean
        bad = tmp_path / "bad.py"
        bad.write_text(_THR02)
        assert main(["--concurrency", str(bad)]) == 1  # findings
        assert main(["--concurrency", "/no/such/path"]) == 2
        assert main(["--concurrency", "--zoo"]) == 2   # subject clash

    def test_cli_concurrency_json(self, tmp_path, capsys):
        import json

        from deeplearning4j_tpu.analysis.cli import main

        bad = tmp_path / "bad.py"
        bad.write_text(_THR01)
        assert main(["--concurrency", "--json", str(bad)]) == 1
        rec = json.loads(capsys.readouterr().out)
        assert rec["ok"] is False
        assert any("THR01" in c for r in rec["reports"]
                   for c in r["codes"])


def test_acceptance_all_thr_codes_covered():
    from deeplearning4j_tpu.analysis.diagnostics import ALL_CODES

    triggered = set()
    for src in (_THR01, _THR02):
        triggered |= _codes(lint_thread_source(src, "f.py"))
    triggered |= _codes(lint_thread_source(textwrap.dedent('''
        import threading
        import time

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._res = None

            def get(self):
                if self._res is None:
                    self._res = object()      # THR04
                return self._res

            def spin(self):
                with self._lock:
                    time.sleep(1)             # THR03
    '''), "f.py"))
    assert {"THR01", "THR02", "THR03", "THR04"} <= triggered, triggered
    assert triggered <= set(ALL_CODES)


# ======================================================================
# regression tests for the audit's fixes (live, threaded)
# ======================================================================

class TestAuditRegressions:
    def test_cachedjit_single_flight_compile(self):
        """PR 14 audit fix: N threads racing ONE CachedJit's first-seen
        signature must produce exactly one cache-miss compile (the
        second thread waits on the entry lock instead of paying a
        duplicate XLA compile), and every thread the right answer."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.runtime import aot

        calls = []

        def fn(x):
            calls.append(1)   # trace-time side effect = compile count
            return x * 2.0

        cj = aot.cached_jit(fn, fingerprint="test-single-flight",
                            entry="sf_test")
        cache = aot.session_cache()
        assert cache is not None
        before = cache.stats["misses"]
        x = jnp.arange(8, dtype=jnp.float32)
        results = [None] * 8
        errs = []
        start = threading.Barrier(8)

        def worker(i):
            try:
                start.wait()
                results[i] = np.asarray(cj(x))
            except Exception as e:   # pragma: no cover - failure path
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not errs, errs
        for r in results:
            np.testing.assert_array_equal(r, np.arange(8) * 2.0)
        assert len(calls) == 1, f"traced {len(calls)} times"
        assert cache.stats["misses"] - before == 1

    def test_executable_cache_stats_race_free(self):
        """note_miss from many threads never loses a count (the bare
        `stats['misses'] += 1` read-modify-write did)."""
        from deeplearning4j_tpu.runtime.aot import ExecutableCache

        cache = ExecutableCache()
        start = threading.Barrier(8)

        def worker():
            start.wait()
            for _ in range(500):
                cache.note_miss()

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert cache.stats["misses"] == 8 * 500

    def test_http_owner_concurrent_start_binds_once(self, monkeypatch):
        """PR 14 audit fix (THR04): concurrent start() calls agree on
        ONE bound server — previously each racing thread constructed
        its own ThreadingHTTPServer and all but one leaked."""
        import http.server as hs

        from deeplearning4j_tpu.util import httpserve

        built = []
        real = hs.ThreadingHTTPServer

        class Counting(real):
            def __init__(self, *a, **kw):
                built.append(1)
                super().__init__(*a, **kw)

        monkeypatch.setattr(hs, "ThreadingHTTPServer", Counting)

        class Owner(httpserve.HttpServerOwner):
            pass

        owner = Owner()
        start = threading.Barrier(6)

        def go():
            start.wait()
            owner._serve(httpserve.JsonHandler, 0)

        ts = [threading.Thread(target=go) for _ in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        try:
            assert len(built) == 1, f"{len(built)} servers were bound"
            assert owner.port is not None
        finally:
            owner.stop()
        assert owner.port is None
