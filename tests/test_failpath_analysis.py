"""Failure-path verifier gates (analysis/faults.py — pass 9,
docs/ANALYSIS.md; CLI ``--failpaths``).

What must hold:

- each FLT01-06 diagnostic fires on a minimal broken fixture and stays
  silent on the corresponding clean fixture;
- ``fault-ok[CODE]: reason`` suppresses a finding (carried, non-
  failing); a bare tag without a reason does NOT;
- the package's own threaded tier lints CLEAN under the pass, with
  only reasoned suppressions (the audit acceptance gate);
- the CLI subject honors the 0/1/2 exit contract and the one-subject-
  per-invocation rule, and ``--codes`` lists FLT01-06;
- every ``serving/*.py`` module is inside the linted tier (derived by
  glob, so a new serving module cannot silently dodge the pass);
- the runtime twin: ``seam_coverage`` proves every registered chaos
  seam fires at least once across a live soak (fleet + sequence +
  paged KV generate + HTTP + AOT disk + checkpoint paths), and a
  deliberately dead seam trips the gate;
- the audit regressions: the hedged-dispatch busy-wait is gone (CV
  wait, no ``sleep(0.0)``), a refused hedge enqueue is counted and
  charged, GET routes fire the ``server.request`` seam, disk-store
  failures are counted in cache stats, the single-flight compile wait
  is bounded, and ``register_seam``/arm-validation reject unknown
  seam names.
"""

import json
import os
import textwrap
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.analysis.cli import main
from deeplearning4j_tpu.analysis.faults import (
    coverage_gaps, lint_fault_paths, lint_fault_source, seam_coverage,
)
from deeplearning4j_tpu.runtime import chaos
from deeplearning4j_tpu.runtime.chaos import ChaosPlan, fault_point

_PKG = os.path.dirname(
    os.path.dirname(os.path.abspath(chaos.__file__)))

SEAMS = ("x.y",)


@pytest.fixture(autouse=True)
def _always_disarmed():
    """No test may leak an armed plan into the next."""
    chaos.disarm()
    yield
    chaos.disarm()


def _codes(report):
    return [d.code for d in report.errors]


# ----------------------------------------------------------------------
# broken / clean fixture pairs, one per diagnostic
# ----------------------------------------------------------------------
BROKEN = {
    "FLT01": """
        class A:
            def f(self):
                try:
                    g()
                except Exception:
                    pass
    """,
    "FLT02": """
        import threading

        class A:
            def _work(self):
                g()

            def start(self):
                threading.Thread(target=self._work).start()
    """,
    "FLT03": """
        class A:
            def f(self):
                self._event.wait()
    """,
    "FLT04": """
        import threading
        from deeplearning4j_tpu.runtime.chaos import fault_point

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self):
                with self._lock:
                    fault_point("x.y")
    """,
    "FLT05": """
        import time

        def spin(evt):
            while not evt.done:
                time.sleep(0.0)
    """,
    "FLT06": """
        from deeplearning4j_tpu.runtime.chaos import fault_point

        def f():
            fault_point("x.typo")
    """,
}

CLEAN = {
    "FLT01": """
        class A:
            def f(self):
                try:
                    g()
                except Exception:
                    self.stats["g_errors"] += 1
    """,
    "FLT02": """
        import threading
        from deeplearning4j_tpu.runtime.chaos import fault_point

        class A:
            def _work(self):
                fault_point("x.y")
                g()

            def start(self):
                threading.Thread(target=self._work).start()
    """,
    "FLT03": """
        class A:
            def f(self):
                self._event.wait(0.5)
    """,
    "FLT04": """
        import threading
        from deeplearning4j_tpu.runtime.chaos import fault_point

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self):
                fault_point("x.y")
                with self._lock:
                    g()
    """,
    "FLT05": """
        import time

        def spin(evt):
            while not evt.done:
                time.sleep(0.01)
    """,
    "FLT06": """
        from deeplearning4j_tpu.runtime.chaos import fault_point

        def f():
            fault_point("x.y")
    """,
}


class TestFixturePairs:
    @pytest.mark.parametrize("code", sorted(BROKEN))
    def test_broken_fixture_trips(self, code):
        rep = lint_fault_source(textwrap.dedent(BROKEN[code]),
                                seams=SEAMS)
        assert code in _codes(rep), rep.format(verbose=True)

    @pytest.mark.parametrize("code", sorted(CLEAN))
    def test_clean_fixture_passes(self, code):
        rep = lint_fault_source(textwrap.dedent(CLEAN[code]),
                                seams=SEAMS)
        assert code not in _codes(rep), rep.format(verbose=True)

    def test_acceptance_all_flt_codes_covered(self):
        """Every catalogued FLT code has a broken AND a clean
        fixture in this file (the tentpole acceptance criterion)."""
        from deeplearning4j_tpu.analysis.diagnostics import ALL_CODES

        flt = {c for c in ALL_CODES if c.startswith("FLT")}
        assert flt == set(BROKEN) == set(CLEAN)

    def test_classification_forms_all_accepted(self):
        """Raise, counter .inc(), caught-name use and stats AugAssign
        each count as classifying the failure (no FLT01)."""
        forms = (
            "raise",
            "self._m_err.inc()",
            "log(e)",
            'self.stats["x"] += 1',
        )
        for body in forms:
            src = textwrap.dedent(f"""
                class A:
                    def f(self):
                        try:
                            g()
                        except Exception as e:
                            {body}
            """)
            rep = lint_fault_source(src, seams=SEAMS)
            assert "FLT01" not in _codes(rep), (body, rep.format())

    def test_narrow_except_never_flagged(self):
        src = textwrap.dedent("""
            def f():
                try:
                    g()
                except ValueError:
                    pass
        """)
        assert "FLT01" not in _codes(lint_fault_source(src, seams=SEAMS))


#: a base that owns the thread, the lock and the loop, and a subclass
#: that owns the step: the shape of serving/sequence.py's slot
#: schedulers. `{seam}` is the body of the subclass's step.
_BASE_AND_HOOK = """
    import threading
    from deeplearning4j_tpu.runtime.chaos import fault_point

    class Base:
        def __init__(self):
            self._lock = threading.Lock()
            self._thread = threading.Thread(target=self._loop)

        def _loop(self):
            {loop}

    class Sub(Base):
        def _step(self):
            {step}
"""


class TestSameModuleInheritance:
    """``self.m()`` resolves through the class's same-module bases and
    subclasses, and a base's lock is its subclasses' lock."""

    @staticmethod
    def _lint(loop, step):
        return _codes(lint_fault_source(textwrap.dedent(
            _BASE_AND_HOOK.format(loop=loop, step=step)), seams=SEAMS))

    def test_seam_in_a_subclass_hook_covers_the_bases_thread(self):
        assert "FLT02" not in self._lint("self._step()",
                                         'fault_point("x.y")')

    def test_no_seam_in_any_override_still_trips(self):
        assert "FLT02" in self._lint("self._step()", "pass")

    def test_hook_with_a_seam_called_under_the_bases_lock_trips(self):
        loop = "with self._lock:\n                self._step()"
        assert "FLT04" in self._lint(loop, 'fault_point("x.y")')
        assert "FLT04" not in self._lint("self._step()",
                                         'fault_point("x.y")')

    def test_seam_under_the_inherited_lock_in_the_subclass_trips(self):
        step = 'with self._lock:\n                fault_point("x.y")'
        assert "FLT04" in self._lint("self._step()", step)


class TestSuppressions:
    def test_reasoned_suppression_carries_but_passes(self):
        src = textwrap.dedent("""
            def f():
                try:
                    g()
                except Exception:  # fault-ok[FLT01]: nothing to report, caller observes the None
                    pass
        """)
        rep = lint_fault_source(src, seams=SEAMS)
        assert rep.ok
        assert [d.code for d in rep.suppressed] == ["FLT01"]

    def test_bare_tag_without_reason_does_not_suppress(self):
        src = textwrap.dedent("""
            def f():
                try:
                    g()
                except Exception:  # fault-ok[FLT01]
                    pass
        """)
        rep = lint_fault_source(src, seams=SEAMS)
        assert "FLT01" in _codes(rep)

    def test_wrong_code_does_not_suppress(self):
        src = textwrap.dedent("""
            def f():
                try:
                    g()
                except Exception:  # fault-ok[FLT03]: not the right code
                    pass
        """)
        rep = lint_fault_source(src, seams=SEAMS)
        assert "FLT01" in _codes(rep)


# ----------------------------------------------------------------------
# dead-seam integrity (FLT06b) — static side
# ----------------------------------------------------------------------
class TestDeadSeam:
    def test_dead_registered_seam_trips_flt06(self, tmp_path):
        f = tmp_path / "mod.py"
        f.write_text(textwrap.dedent("""
            from deeplearning4j_tpu.runtime.chaos import fault_point

            def g():
                fault_point("x.y")
        """))
        rep = lint_fault_paths(paths=[str(f)],
                               seams=("x.y", "x.dead"))
        dead = [d for d in rep.errors if d.code == "FLT06"]
        assert len(dead) == 1
        assert "x.dead" in dead[0].message

    def test_all_seams_used_passes(self, tmp_path):
        f = tmp_path / "mod.py"
        f.write_text(textwrap.dedent("""
            from deeplearning4j_tpu.runtime.chaos import fault_point

            def g():
                fault_point("x.y")
        """))
        rep = lint_fault_paths(paths=[str(f)], seams=("x.y",))
        assert rep.ok, rep.format()


# ----------------------------------------------------------------------
# the tier self-check + CLI contract
# ----------------------------------------------------------------------
@pytest.mark.lint
class TestTierSelfCheck:
    def test_threaded_tier_lints_clean(self):
        """The audit acceptance gate: the package's own tier has ZERO
        unsuppressed failure-path findings, and every suppression
        carries a reason (unreasoned tags never suppress)."""
        rep = lint_fault_paths()
        assert rep.ok, rep.format(verbose=True)
        # the tier earned real suppressions during the audit — an
        # empty list would mean the pass silently stopped looking
        assert rep.suppressed

    def test_every_serving_module_is_in_the_tier(self):
        """Derived by GLOB, not by the tier list itself: a serving
        module added tomorrow joins the lint or fails this test."""
        import glob as _glob

        from deeplearning4j_tpu.analysis.purity import iter_py_files
        from deeplearning4j_tpu.analysis.threads import (
            threaded_tier_paths,
        )

        serving = sorted(_glob.glob(
            os.path.join(_PKG, "serving", "*.py")))
        assert serving, "serving/*.py glob came back empty"
        linted = {os.path.abspath(p)
                  for p in iter_py_files(threaded_tier_paths())}
        missing = [p for p in serving
                   if os.path.abspath(p) not in linted]
        assert not missing, (
            f"serving modules outside the --failpaths tier: {missing}")

    def test_cli_failpaths_clean_exit_zero(self, capsys):
        assert main(["--failpaths"]) == 0
        out = capsys.readouterr().out
        assert "faults:" in out

    def test_cli_failpaths_json(self, capsys):
        assert main(["--failpaths", "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["ok"] is True
        assert doc["reports"][0]["subject"].startswith("faults:")

    def test_cli_broken_file_exit_one(self, tmp_path, capsys):
        f = tmp_path / "bad.py"
        f.write_text(textwrap.dedent(BROKEN["FLT01"]))
        assert main(["--failpaths", str(f)]) == 1
        assert "FLT01" in capsys.readouterr().out

    def test_cli_missing_path_exit_two(self, capsys):
        assert main(["--failpaths", "/no/such/file.py"]) == 2

    def test_cli_subject_clash_exit_two(self, capsys):
        assert main(["--failpaths", "--zoo"]) == 2
        assert main(["--failpaths", "--concurrency"]) == 2

    def test_cli_codes_lists_flt(self, capsys):
        assert main(["--codes"]) == 0
        out = capsys.readouterr().out
        for code in ("FLT01", "FLT02", "FLT03", "FLT04", "FLT05",
                     "FLT06"):
            assert code in out


# ----------------------------------------------------------------------
# seam registry: register_seam + arm-time validation
# ----------------------------------------------------------------------
class TestSeamRegistry:
    def test_register_seam_idempotent_and_listed(self):
        try:
            assert chaos.register_seam("test.extra") == "test.extra"
            chaos.register_seam("test.extra")
            assert "test.extra" in chaos.registered_seams()
            # a built-in name registers as a no-op, never a duplicate
            chaos.register_seam("host.submit")
            assert chaos.registered_seams().count("host.submit") == 1
        finally:
            chaos._EXTRA_SEAMS.discard("test.extra")

    def test_register_seam_rejects_empty(self):
        with pytest.raises(ValueError):
            chaos.register_seam("")

    def test_arm_rejects_unknown_seam(self):
        plan = ChaosPlan().raise_n("no.such.seam", times=1)
        with pytest.raises(ValueError, match="no.such.seam"):
            chaos.arm(plan)
        assert chaos.armed_plan() is None

    def test_arm_accepts_registered_extra_seam(self):
        try:
            chaos.register_seam("test.extra2")
            plan = ChaosPlan().raise_n("test.extra2", times=1)
            chaos.arm(plan)
            assert chaos.armed_plan() is plan
            chaos.disarm()
        finally:
            chaos._EXTRA_SEAMS.discard("test.extra2")


# ----------------------------------------------------------------------
# runtime twin: seam coverage
# ----------------------------------------------------------------------
class TestSeamCoverageUnit:
    def test_counts_every_armed_invocation(self):
        counts = seam_coverage(
            lambda: [fault_point("host.submit") for _ in range(3)],
            seams=("host.submit", "queue.dispatch"))
        assert counts == {"host.submit": 3, "queue.dispatch": 0}

    def test_dead_seam_fixture_trips_the_gate(self):
        counts = seam_coverage(
            lambda: fault_point("host.submit"),
            seams=("host.submit", "test.dead"))
        assert coverage_gaps(counts) == ["test.dead"]

    def test_previous_plan_restored(self):
        plan = ChaosPlan().raise_n("host.submit", times=0)
        chaos.arm(plan)
        seam_coverage(lambda: None, seams=("host.submit",))
        assert chaos.armed_plan() is plan
        chaos.disarm()

    def test_disarmed_after_run_raises(self):
        def boom():
            raise RuntimeError("run failed")

        with pytest.raises(RuntimeError):
            seam_coverage(boom, seams=("host.submit",))
        assert chaos.armed_plan() is None


# ----------------------------------------------------------------------
# live subjects for the coverage gate + audit regressions
# ----------------------------------------------------------------------
def _mln(seed=7, nout=16):
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       NeuralNetConfiguration, Nesterovs,
                                       OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(Nesterovs(0.1, 0.9)).list()
            .layer(DenseLayer(nOut=nout, activation="relu"))
            .layer(OutputLayer(nOut=4, activation="softmax",
                               lossFunction="mcxent"))
            .setInputType(InputType.feedForward(8)).build())
    return MultiLayerNetwork(conf).init()


def _rnn_net(seed=7):
    from deeplearning4j_tpu.nn import (InputType, NeuralNetConfiguration,
                                       Nesterovs)
    from deeplearning4j_tpu.nn.conf.layers import RnnOutputLayer
    from deeplearning4j_tpu.nn.conf.recurrent import LSTM
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(Nesterovs(0.1, 0.9)).list()
            .layer(LSTM(nOut=8))
            .layer(RnnOutputLayer(nOut=5, activation="softmax",
                                  lossFunction="mcxent"))
            .setInputType(InputType.recurrent(4, 6)).build())
    return MultiLayerNetwork(conf).init()


def _mlp_net(seed=42):
    from deeplearning4j_tpu.nn import (Adam, DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration,
                                       OutputLayer)

    conf = (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Adam(1e-2)).activation("relu")
            .list()
            .layer(DenseLayer(nOut=16))
            .layer(OutputLayer(nOut=3, activation="softmax"))
            .setInputType(InputType.feedForward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _data_iter(n=16, batch=8, seed=0):
    from deeplearning4j_tpu.data import DataSetIterator

    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype("float32")
    y = np.eye(3, dtype="float32")[rng.randint(0, 3, n)]
    return DataSetIterator(x, y, batch)


def _rows(n, seed=0):
    return np.random.RandomState(seed).randn(n, 8).astype(np.float32)


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode())


@pytest.fixture
def fresh_cache():
    from deeplearning4j_tpu.runtime import aot

    prev = aot._SESSION
    cache = aot._SESSION = aot.ExecutableCache()
    yield cache
    aot._SESSION = prev


def _fleet(n_replicas, net, *, router_kw=None, **kw):
    from deeplearning4j_tpu.serving import FleetRouter, ModelHost

    kw.setdefault("batchBuckets", (8,))
    kw.setdefault("maxWaitMs", 1.0)
    fleet = FleetRouter(**(router_kw or {}))
    rids = [fleet.add_replica(ModelHost()) for _ in range(n_replicas)]
    fleet.register("m", net, **kw)
    return fleet, rids


@pytest.mark.faults
class TestSeamCoverageGate:
    def test_every_registered_seam_fires(self, tmp_path, fresh_cache):
        """The 100% gate: one soak drives fleet traffic, a sequence
        decode, a paged token generate, live HTTP GET+POST and a
        checkpointed fit — and EVERY seam in
        chaos.registered_seams() fires at least once. A seam this soak
        cannot reach is dead inventory."""
        from deeplearning4j_tpu.nn.transformer import CausalTransformerLM
        from deeplearning4j_tpu.runtime.resilience import (
            ResilientFit, RetryPolicy,
        )
        from deeplearning4j_tpu.serving import InferenceServer, ModelHost

        fleet, _ = _fleet(2, _mln())
        host = ModelHost()
        host.register_sequence("s", _rnn_net(), slotBuckets=(4,))
        host.register_sequence(
            "g", CausalTransformerLM(vocab=11, d_model=8, n_heads=1,
                                     n_layers=1, max_context=8,
                                     page_size=4, seed=0),
            slotBuckets=(2,), numPages=8)
        srv = InferenceServer(host).start(port=0, warmup=False)
        seq = np.random.RandomState(0).randn(3, 4).astype(np.float32)
        fast = RetryPolicy(maxRetries=2, initialDelay=0.001,
                           maxDelay=0.002, sleep=lambda s: None)
        base = f"http://127.0.0.1:{srv.port}"

        def run():
            # host.submit + queue.dispatch + fleet.dispatch
            fleet.submit("m", _rows(2))
            # host.submit_sequence + sequence.step
            host.submit_sequence("s", seq)
            # sequence.prefill + kv.page_alloc (the paged KV tier)
            host.generate("g", [1, 2, 3, 4, 5], max_new_tokens=1)
            # server.request — GET and POST both route through it
            _get(base + "/v1/models")
            # checkpoint.write on the first fit, checkpoint.restore
            # on the resuming second fit
            net = _mlp_net()
            ResilientFit(net, tmp_path / "ck", saveEveryNIterations=1,
                         keepLast=2,
                         retryPolicy=fast).fit(_data_iter())
            net2 = _mlp_net()
            ResilientFit(net2, tmp_path / "ck", saveEveryNIterations=1,
                         keepLast=2,
                         retryPolicy=fast).fit(_data_iter())

        try:
            counts = seam_coverage(run)
        finally:
            srv.stop()
            host.close(drain=True)
            fleet.close()
        assert set(counts) == set(chaos.registered_seams())
        assert coverage_gaps(counts) == [], counts

    def test_get_routes_fire_the_request_seam(self):
        """Audit regression: before this PR, GET routes were the one
        HTTP boundary a ChaosPlan could never exercise."""
        from deeplearning4j_tpu.serving import InferenceServer, ModelHost

        srv = InferenceServer(ModelHost()).start(port=0, warmup=False)
        try:
            counts = seam_coverage(
                lambda: _get(
                    f"http://127.0.0.1:{srv.port}/v1/models"),
                seams=("server.request",))
        finally:
            srv.stop()
        assert counts["server.request"] >= 1


# ----------------------------------------------------------------------
# audit regressions: the fixes the pass paid for itself with
# ----------------------------------------------------------------------
class TestDoneCallbacks:
    def _req(self):
        from deeplearning4j_tpu.serving.queue import InferenceRequest

        return InferenceRequest(np.zeros((1, 2), np.float32),
                                enqueued_at=0.0)

    def test_callback_runs_on_finish(self):
        req = self._req()
        calls = []
        req.add_done_callback(calls.append)
        assert not calls
        req.finish("r")
        assert calls == [req]

    def test_already_done_runs_immediately(self):
        req = self._req()
        req.finish("r")
        calls = []
        req.add_done_callback(calls.append)
        assert calls == [req]

    def test_event_set_before_callbacks(self):
        """The hedged waiter's no-lost-wakeup contract: by the time a
        callback runs, req.done is already True, so a notify that
        lands before the waiter's re-check is never needed twice."""
        req = self._req()
        seen = []
        req.add_done_callback(lambda r: seen.append(r.done))
        req.fail(RuntimeError("x"))
        assert seen == [True]

    def test_double_invocation_is_survivable(self):
        """append-then-recheck may run a callback twice in a race —
        the documented contract is idempotency, so a CV notify (the
        real consumer) must tolerate re-invocation."""
        req = self._req()
        cond = threading.Condition()

        def wake(_r):
            with cond:
                cond.notify_all()

        req.add_done_callback(wake)
        req.finish("r")
        wake(req)   # the racing duplicate


@pytest.mark.faults
class TestHedgeAudit:
    def test_no_busy_wait_left_in_fleet(self):
        """The FLT05 find that started the audit: sleep(0.0) in the
        hedged race loop. The lint over fleet.py must stay clean."""
        path = os.path.join(_PKG, "serving", "fleet.py")
        with open(path) as fh:
            assert "sleep(0.0)" not in fh.read()
        rep = lint_fault_paths(paths=[path])
        spins = [d for d in rep.errors if d.code == "FLT05"]
        assert not spins, [d.format() for d in spins]

    def test_hedge_wins_without_waiting_for_primary(self, fresh_cache):
        """The CV wakeup: with the primary slowed well past the hedge
        mark, the second replica's completion callback releases the
        waiter — the call returns far sooner than the primary."""
        import time

        from deeplearning4j_tpu.parallel.inference import (
            ParallelInference,
        )

        net = _mln()
        feats = _rows(2, seed=8)
        want = np.asarray(ParallelInference(
            net, batchBuckets=(8,)).output(feats).jax())
        fleet, _ = _fleet(2, net)
        try:
            fleet.submit("m", _rows(1))    # warm both code paths
            fleet.set_hedge("m", after_s=0.02)
            with ChaosPlan().slow("queue.dispatch", 0.8, at=0):
                t0 = time.perf_counter()
                got = np.asarray(fleet.submit("m", feats))
                wall = time.perf_counter() - t0
            np.testing.assert_array_equal(got, want)
            assert wall < 0.6, (
                f"hedged submit took {wall:.3f}s — the waiter slept "
                "through the second replica's completion")
        finally:
            fleet.close()

    def test_refused_hedge_enqueue_counted_and_charged(
            self, fresh_cache):
        """Audit regression: a hedge enqueue refusal used to vanish
        into a bare except — now it is counted under its error class
        and (non-backpressure) charged to the refusing replica."""
        net = _mln()
        fleet, _ = _fleet(2, net)
        try:
            fleet.submit("m", _rows(1))    # warm + seed the ranking
            ranked = list(fleet._ranked("m"))
            assert len(ranked) == 2
            _, host2 = ranked[1]

            def boom(*a, **k):
                raise RuntimeError("dead hedge replica")

            host2.submit = boom
            fleet.set_hedge("m", after_s=0.01)
            lab = fleet._m_failover.labels(model="m",
                                           error="RuntimeError")
            f0 = lab.value
            with ChaosPlan().slow("queue.dispatch", 0.2, at=0):
                out = fleet.submit("m", _rows(1, seed=5))
            assert np.asarray(out).shape == (1, 4)
            assert lab.value == f0 + 1
        finally:
            fleet.close()


class TestStoreErrorCounters:
    def test_tuning_store_failure_is_counted(self, tmp_path,
                                             monkeypatch):
        from deeplearning4j_tpu.runtime import autotune as at

        store = at.TuningStore(str(tmp_path))

        def boom(*a, **k):
            raise OSError("disk full")

        monkeypatch.setattr(at.tempfile, "mkstemp", boom)
        store.put("k", {"x": 1})
        assert store.stats["store_errors"] == 1
        assert store._mem["k"]["x"] == 1     # memory tier still works

    def test_single_flight_wait_is_bounded(self):
        """Audit regression for the FLT03 find: the cross-thread
        compile wait in aot._entry_for must carry a timeout (a killed
        owner degrades to a slow re-read loop, not a wedge)."""
        path = os.path.join(_PKG, "runtime", "aot.py")
        rep = lint_fault_paths(paths=[path])
        blocked = [d for d in rep.errors if d.code == "FLT03"]
        assert not blocked, [d.format() for d in blocked]
