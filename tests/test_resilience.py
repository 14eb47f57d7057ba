"""The fault matrix: retry backoff, preemption resume, NaN-step guard,
data-path retry, and serving-tier health/deadline behavior
(runtime.resilience + util.sharded_checkpoint + util.httpserve).

Every fault here is INJECTED deterministically (FaultInjector /
seeded RetryPolicy) — no sleeps-and-hope, no real process kills: a
simulated preemption is the Preemption exception escaping fit(), and a
restart is a fresh net + ResilientFit pointed at the same checkpoint
dir.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.data import DataSetIterator, RetryingDataSetIterator
from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, InputType, MultiLayerNetwork, DenseLayer,
    OutputLayer, Adam,
)
from deeplearning4j_tpu.optimize import ResilienceListener
from deeplearning4j_tpu.runtime.resilience import (
    FaultInjector, NonFiniteStepError, Preemption, ResilientFit,
    RetryPolicy, retry,
)
from deeplearning4j_tpu.util import sharded_checkpoint as ck

pytestmark = pytest.mark.faults


def _mlp(seed=42):
    return (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Adam(1e-2)).activation("relu")
            .list()
            .layer(DenseLayer(nOut=16))
            .layer(OutputLayer(nOut=3, activation="softmax"))
            .setInputType(InputType.feedForward(4))
            .build())


def _data(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype("float32")
    y = np.eye(3, dtype="float32")[rng.randint(0, 3, n)]
    return x, y


def _iter(n=64, batch=16, seed=0):
    x, y = _data(n, seed)
    return DataSetIterator(x, y, batch)  # deterministic order: replayable


def _tree_equal(a, b):
    import jax

    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for u, v in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


_FAST = RetryPolicy(maxRetries=3, initialDelay=0.001, maxDelay=0.004,
                    sleep=lambda s: None)


# ----------------------------------------------------------------------
# retry backoff
# ----------------------------------------------------------------------
class TestRetry:
    def test_deterministic_jitter_and_bounds(self):
        p = RetryPolicy(maxRetries=6, initialDelay=0.05, maxDelay=0.4,
                        multiplier=2.0, jitter=0.5, seed=11)
        d1, d2 = p.delays(), RetryPolicy(
            maxRetries=6, initialDelay=0.05, maxDelay=0.4, multiplier=2.0,
            jitter=0.5, seed=11).delays()
        assert d1 == d2  # same seed -> same schedule
        assert d1 != RetryPolicy(maxRetries=6, initialDelay=0.05,
                                 maxDelay=0.4, seed=12).delays()
        for k, d in enumerate(d1, start=1):
            base = min(0.4, 0.05 * 2.0 ** (k - 1))
            assert base * 0.5 <= d <= base  # jitter band
        assert all(d <= 0.4 for d in d1)  # cap holds past the knee

    def test_retry_succeeds_after_transients_then_gives_up(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise IOError("transient")
            return "ok"

        seen = []
        assert retry(flaky, _FAST,
                     on_retry=lambda a, e, d: seen.append((a, d))) == "ok"
        assert [a for a, _ in seen] == [1, 2]
        assert seen == [(a, d) for (a, _), d in
                        zip(seen, _FAST.delays()[:2])]  # scheduled delays

        def always():
            raise IOError("permanent")

        with pytest.raises(IOError, match="permanent"):
            retry(always, _FAST)

    def test_non_matching_exception_not_retried(self):
        calls = {"n": 0}

        def boom():
            calls["n"] += 1
            raise ValueError("not transient")

        with pytest.raises(ValueError):
            retry(boom, _FAST)
        assert calls["n"] == 1


# ----------------------------------------------------------------------
# preemption-safe fit: kill mid-epoch, restart, bitwise-identical params
# ----------------------------------------------------------------------
class TestPreemptionResume:
    def test_resume_matches_uninterrupted_bitwise(self, tmp_path):
        epochs, steps_per_epoch = 3, 4  # 64/16

        # ground truth: plain fit, no harness at all
        ref = MultiLayerNetwork(_mlp()).init()
        ref.fit(_iter(), epochs=epochs)

        # run killed mid-epoch 1 (global step 7 of 12), ckpt every 2 —
        # the latest checkpoint (step 6) is OLDER than the kill point,
        # so the restart must also REDO step 7 identically
        net = MultiLayerNetwork(_mlp()).init()
        inj = FaultInjector().killAfterStep(7)
        events = ResilienceListener()
        net.setListeners(events)
        rf = ResilientFit(net, tmp_path / "ck", saveEveryNIterations=2,
                          keepLast=2, retryPolicy=_FAST, injector=inj)
        with pytest.raises(Preemption):
            rf.fit(_iter(), epochs=epochs)
        assert ("preempt", 7) in inj.events
        assert net._iteration == 7  # died mid-epoch 1
        assert ck.latest_step(tmp_path / "ck") == 6

        # "restart": fresh process state — new net, new harness, same dir
        net2 = MultiLayerNetwork(_mlp()).init()
        events2 = ResilienceListener()
        net2.setListeners(events2)
        rf2 = ResilientFit(net2, tmp_path / "ck", saveEveryNIterations=2,
                           keepLast=2, retryPolicy=_FAST)
        rf2.fit(_iter(), epochs=epochs)

        assert events2.restores == 1
        assert net2._iteration == epochs * steps_per_epoch
        _tree_equal(ref._params, net2._params)       # bitwise
        _tree_equal(ref._upd_states, net2._upd_states)

    def test_keep_last_n_rotation_and_latest_step(self, tmp_path):
        net = MultiLayerNetwork(_mlp()).init()
        rf = ResilientFit(net, tmp_path / "ck", saveEveryNIterations=1,
                          keepLast=2, retryPolicy=_FAST)
        rf.fit(_iter(), epochs=2)  # 8 saves, keep 2
        kept = sorted(p.name for p in (tmp_path / "ck").iterdir()
                      if p.name.startswith("step_"))
        assert kept == ["step_7", "step_8"]
        assert ck.latest_step(tmp_path / "ck") == 8

    def test_atomic_save_never_exposes_torn_checkpoint(self, tmp_path):
        # a staged-but-uncommitted save (preempted mid-write) must be
        # invisible to latest_step and swept by gc
        d = tmp_path / "ck"
        net = MultiLayerNetwork(_mlp()).init()
        net.fit(_iter())
        ck.ShardedModelSerializer.writeModel(net, ck.step_path(d, 4))
        torn = ck.step_path(d, 9) + ".tmp-123-456"
        (tmp_path / "ck").mkdir(exist_ok=True)
        import os

        os.makedirs(torn)
        with open(os.path.join(torn, "manifest.json"), "w") as f:
            f.write("{")  # half-written
        assert ck.latest_step(d) == 4
        restored = ck.ShardedModelSerializer.restore(ck.step_path(d, 4))
        _tree_equal(net._params, restored._params)
        ck.gc_checkpoints(d, keepLast=5)
        assert not os.path.exists(torn)

    def test_manifest_extra_roundtrip(self, tmp_path):
        net = MultiLayerNetwork(_mlp()).init()
        net.fit(_iter())
        p = ck.step_path(tmp_path, 1)
        ck.ShardedModelSerializer.writeModel(
            net, p, extra={"batch_in_epoch": 3})
        assert ck.read_manifest(p)["extra"] == {"batch_in_epoch": 3}


# ----------------------------------------------------------------------
# non-finite step guard
# ----------------------------------------------------------------------
class TestNanGuard:
    def test_poisoned_step_skipped_not_applied(self, tmp_path):
        net = MultiLayerNetwork(_mlp()).init()
        events = ResilienceListener()
        net.setListeners(events)
        inj = FaultInjector().poisonStep(2)  # third step is NaN
        rf = ResilientFit(net, injector=inj, retryPolicy=_FAST)

        import jax

        snap = {}

        class Snapshot(ResilienceListener):
            # params BEFORE the poisoned step, grabbed via the listener
            # stream (iteration 2 done == about to run step at it=2)
            def iterationDone(self, model, iteration, epoch):
                if iteration == 2:
                    snap["params"] = jax.tree_util.tree_map(
                        lambda a: np.asarray(a).copy(), model._params)

        net.addListeners(Snapshot())
        rf.fit(_iter(), epochs=1)

        assert events.skippedSteps == 1
        assert [e for e in events.events if e[0] == "skip"] \
            and events.events[0][1] == 3  # skip surfaced at iteration 3
        assert ("poison", 2) in inj.events
        assert "params" in snap
        # the NaN update was NOT applied: training continued finite
        for leaf in jax.tree_util.tree_leaves(net._params):
            assert np.isfinite(np.asarray(leaf)).all()
        assert net._iteration == 4  # all batches consumed, one skipped

    def test_params_frozen_across_skip(self):
        # sharper version of the above: compare directly around the skip
        import jax

        net = MultiLayerNetwork(_mlp()).init()
        inj = FaultInjector().poisonStep(1)
        rf = ResilientFit(net, injector=inj, retryPolicy=_FAST)
        before, after = {}, {}

        class Grab:
            def iterationDone(self, model, iteration, epoch):
                c = jax.tree_util.tree_map(
                    lambda a: np.asarray(a).copy(), model._params)
                if iteration == 1:
                    before["p"] = c
                elif iteration == 2:  # right after the skipped step
                    after["p"] = c

            def __getattr__(self, _):
                return lambda *a, **k: None

        net.setListeners(Grab())
        rf.fit(_iter(), epochs=1)
        _tree_equal(before["p"], after["p"])

    def test_consecutive_bad_steps_abort(self):
        net = MultiLayerNetwork(_mlp()).init()
        inj = FaultInjector().poisonStep(1, 2)
        rf = ResilientFit(net, injector=inj, retryPolicy=_FAST,
                          maxConsecutiveBadSteps=2)
        with pytest.raises(NonFiniteStepError, match="2 consecutive"):
            rf.fit(_iter(), epochs=1)

    def test_guard_overhead_free_path_identical(self):
        # on finite data the guarded trajectory IS the plain trajectory
        a = MultiLayerNetwork(_mlp()).init()
        a.fit(_iter(), epochs=2)
        b = MultiLayerNetwork(_mlp()).init()
        ResilientFit(b, retryPolicy=_FAST).fit(_iter(), epochs=2)
        _tree_equal(a._params, b._params)
        _tree_equal(a._upd_states, b._upd_states)


class TestParallelWrapperGuard:
    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_guarded_dp_matches_plain_and_skips_nan(self, tmp_path):
        from deeplearning4j_tpu.parallel import ParallelWrapper

        # plain data-parallel run (8-device virtual mesh)
        ref = MultiLayerNetwork(_mlp()).init()
        ParallelWrapper(ref).fit(_iter(), epochs=2)

        # guarded run on clean data: identical trajectory
        net = MultiLayerNetwork(_mlp()).init()
        rf = ResilientFit(ParallelWrapper(net), retryPolicy=_FAST)
        rf.fit(_iter(), epochs=2)
        _tree_equal(ref._params, net._params)

        # guarded run with one poisoned step: skipped, training survives
        import jax

        net2 = MultiLayerNetwork(_mlp()).init()
        events = ResilienceListener()
        net2.setListeners(events)
        inj = FaultInjector().poisonStep(3)
        rf2 = ResilientFit(ParallelWrapper(net2), tmp_path / "ck",
                           saveEveryNIterations=4, retryPolicy=_FAST,
                           injector=inj)
        rf2.fit(_iter(), epochs=2)
        assert events.skippedSteps == 1 and events.saves == 2
        for leaf in jax.tree_util.tree_leaves(net2._params):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_threshold_compression_trains_under_guard(self):
        """ISSUE 11: the threshold step is wrappable now — its residual
        rides the updater-state carry, so the non-finite guard rolls it
        back with the rest of the state on a skipped step."""
        from deeplearning4j_tpu.parallel import ParallelWrapper

        net = MultiLayerNetwork(_mlp()).init()
        pw = ParallelWrapper(net, gradient_compression="threshold",
                             threshold=1e-2)
        rf = ResilientFit(pw, retryPolicy=_FAST)
        rf.fit(_iter(), epochs=1)
        assert np.isfinite(net.score())
        assert rf.skippedSteps == 0

    def test_parameter_averaging_rejected_not_silently_replaced(self):
        # PATM's local-steps+periodic-pmean semantics live in its own
        # _fit_batch; wrapping it must refuse, not quietly run sync DP
        from deeplearning4j_tpu.parallel import (
            ParameterAveragingTrainingMaster,
        )

        net = MultiLayerNetwork(_mlp()).init()
        pm = ParameterAveragingTrainingMaster(net, averagingFrequency=5)
        rf = ResilientFit(pm, retryPolicy=_FAST)
        with pytest.raises(ValueError, match="ParameterAveraging"):
            rf.fit(_iter(), epochs=1)


# ----------------------------------------------------------------------
# data-path faults
# ----------------------------------------------------------------------
class TestDataFaults:
    def test_iterator_ioerror_retried_through_fit(self, tmp_path):
        net = MultiLayerNetwork(_mlp()).init()
        inj = FaultInjector().failOnBatch(1, times=2)
        rf = ResilientFit(net, injector=inj, retryPolicy=_FAST)
        rf.fit(inj.wrapIterator(_iter()), epochs=1)
        assert net._iteration == 4  # no batch lost to the two faults
        assert [e for e in inj.events if e[0] == "data_fault"] == \
            [("data_fault", 1), ("data_fault", 1)]
        # same trajectory as a fault-free run: the retry re-fetched the
        # SAME batch, it did not skip it
        ref = MultiLayerNetwork(_mlp()).init()
        ref.fit(_iter(), epochs=1)
        _tree_equal(ref._params, net._params)

    def test_retrying_iterator_standalone(self):
        inj = FaultInjector().failOnBatch(0, times=1).failOnBatch(2, times=3)
        it = RetryingDataSetIterator(inj.wrapIterator(_iter()),
                                     policy=_FAST)
        n = 0
        for _ in it:
            n += 1
        assert n == 4
        assert it.retries == 4

    def test_retries_exhausted_raises_original(self):
        inj = FaultInjector().failOnBatch(0, times=10)
        it = RetryingDataSetIterator(inj.wrapIterator(_iter()),
                                     policy=_FAST)
        it.reset()
        assert it.hasNext()
        with pytest.raises(IOError, match="injected data fault"):
            it.next()

    def test_dying_iterator_not_silently_truncated(self):
        # an iterator that raises once then latches exhausted (async
        # wrapper semantics) must surface the error — NOT let the retry
        # swallow it and record a truncated epoch as complete
        class DiesMidEpoch:
            def __init__(self):
                self.base = _iter()
                self.dead = False
                self.raised = False

            def reset(self):
                self.base.reset()

            def hasNext(self):
                if self.dead:
                    return False
                if self.base._cursor >= 32 and not self.raised:
                    self.raised, self.dead = True, True
                    raise IOError("producer died")
                return self.base.hasNext()

            def next(self, num=None):
                return self.base.next()

        net = MultiLayerNetwork(_mlp()).init()
        rf = ResilientFit(net, retryPolicy=_FAST)
        with pytest.raises(IOError, match="producer died"):
            rf.fit(DiesMidEpoch(), epochs=1)
        assert net._epoch == 0  # epoch NOT recorded complete

    def test_random_faults_seed_deterministic(self):
        a = FaultInjector(seed=5).randomIOFaults(100, rate=0.2)
        b = FaultInjector(seed=5).randomIOFaults(100, rate=0.2)
        c = FaultInjector(seed=6).randomIOFaults(100, rate=0.2)
        assert set(a._io_faults) == set(b._io_faults)
        assert set(a._io_faults) != set(c._io_faults)
        assert 5 <= len(a._io_faults) <= 40  # ~20 of 100


# ----------------------------------------------------------------------
# serving tier: /healthz + request deadline
# ----------------------------------------------------------------------
def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


class TestServingResilience:
    def test_healthz_on_real_servers(self, tmp_path):
        from deeplearning4j_tpu.clustering import NearestNeighborsServer
        from deeplearning4j_tpu.optimize.ui import UIServer

        log = tmp_path / "s.jsonl"
        log.write_text(json.dumps(
            {"type": "stats", "iteration": 0, "score": 1.0}) + "\n")
        ui = UIServer().attach(str(log)).start(port=0)
        srv = NearestNeighborsServer(
            points=np.random.RandomState(0).randn(16, 4)).start(port=0)
        try:
            for s in (ui, srv):
                status, body = _get(f"http://127.0.0.1:{s.port}/healthz")
                assert status == 200
                assert json.loads(body) == {"status": "ok"}
            # drain: readiness flips to 503 without stopping the server
            srv.setReady(False)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://127.0.0.1:{srv.port}/healthz")
            assert ei.value.code == 503
            assert json.loads(ei.value.read().decode()) == {
                "status": "unready"}
            srv.setReady(True)
            status, _ = _get(f"http://127.0.0.1:{srv.port}/healthz")
            assert status == 200
        finally:
            ui.stop()
            srv.stop()

    def test_request_deadline_returns_503_not_hang(self):
        from deeplearning4j_tpu.util.httpserve import (
            HttpServerOwner, JsonHandler,
        )

        class SlowOwner(HttpServerOwner):
            def start(self, port=0, requestDeadline=None):
                class Handler(JsonHandler):
                    def handle_GET(self):
                        if self.path == "/fast":
                            return self._json({"ok": True})
                        time.sleep(30)  # pathological handler
                        return self._json({"ok": "late"})

                return self._serve(Handler, port,
                                   requestDeadline=requestDeadline)

        srv = SlowOwner().start(port=0, requestDeadline=0.3)
        try:
            t0 = time.monotonic()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://127.0.0.1:{srv.port}/slow", timeout=10)
            elapsed = time.monotonic() - t0
            assert ei.value.code == 503
            assert "deadline" in json.loads(ei.value.read().decode())["error"]
            assert elapsed < 5  # released promptly, not after 30 s
            # server still serves, and /healthz is never deadline-bound
            assert _get(f"http://127.0.0.1:{srv.port}/fast")[0] == 200
            assert _get(f"http://127.0.0.1:{srv.port}/healthz")[0] == 200
        finally:
            srv.stop()


# ----------------------------------------------------------------------
# async prefetch worker faults
# ----------------------------------------------------------------------
class TestAsyncIteratorFaults:
    def test_worker_exception_prompt_and_no_thread_leak(self):
        import threading

        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.runtime.async_iterator import (
            AsyncDataSetIterator,
        )

        class Explodes:
            def __init__(self):
                self.n = 0

            def reset(self):
                self.n = 0

            def hasNext(self):
                return True

            def next(self):
                self.n += 1
                if self.n > 3:
                    raise IOError("backing store went away")
                return DataSet(np.zeros((4, 2), np.float32),
                               np.zeros((4, 2), np.float32))

        before = threading.active_count()
        ait = AsyncDataSetIterator(Explodes(), queueSize=4,
                                   forcePython=True)
        t0 = time.monotonic()
        with pytest.raises(IOError, match="backing store"):
            while ait.hasNext():
                ait.next()
        assert time.monotonic() - t0 < 5  # propagated promptly, no stall
        # the raising worker thread is joined, not leaked
        deadline = time.monotonic() + 3
        while threading.active_count() > before and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        assert threading.active_count() <= before
        assert ait._thread is None

    def test_reset_after_worker_error_recovers(self):
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.runtime.async_iterator import (
            AsyncDataSetIterator,
        )

        class FailsOnce:
            def __init__(self):
                self.runs = 0
                self.n = 0

            def reset(self):
                self.runs += 1
                self.n = 0

            def hasNext(self):
                return self.n < 4

            def next(self):
                self.n += 1
                if self.runs == 1 and self.n == 2:
                    raise IOError("transient")
                return DataSet(np.full((2, 2), self.n, np.float32),
                               np.zeros((2, 2), np.float32))

        ait = AsyncDataSetIterator(FailsOnce(), forcePython=True)
        with pytest.raises(IOError):
            while ait.hasNext():
                ait.next()
        ait.reset()  # second pass is clean
        got = 0
        while ait.hasNext():
            ait.next()
            got += 1
        assert got == 4
        ait.close()
