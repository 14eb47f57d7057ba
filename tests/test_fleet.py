"""Serving-fleet gates (serving/fleet.py, docs/SERVING.md "Sequence
serving + the fleet").

What must hold:

- routing: requests land on the LEAST-LOADED replica; a full replica
  sheds to its peers (failover) and only a fleet-wide full queue
  surfaces QueueFullError;
- rolling deploys: swap_all rolls replicas one at a time under live
  concurrent load with zero failed requests and zero request-path
  compiles (the per-host zero-5xx contract held fleet-wide);
- autoscaling: SLO'd models produce scale_up/scale_down DECISIONS from
  live queue depth + measured p99, delivered through the on_scale
  callback surface (no processes are spawned — decisions only);
- observability: the fleet snapshot (per-replica queue depth + slot
  occupancy, per-model aggregates) is ADDITIVE over the per-host PR 13
  snapshot schema bench.py consumes;
- loadgen: the closed-loop client mode (slow-client storm) is seeded,
  blocks on responses, and records per-error-class counts.
"""

import threading
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.runtime import aot
from deeplearning4j_tpu.serving import (
    FleetRouter, ModelHost, ModelSLO, QueueFullError, loadgen,
)
from deeplearning4j_tpu.serving.fleet import (
    scenario_diurnal_ramp, scenario_hot_model_skew,
    scenario_slow_client_storm,
)


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


def _rows(n, seed=0):
    return np.random.RandomState(seed).randn(n, 8).astype(np.float32)


@pytest.fixture
def fresh_cache():
    prev = aot._SESSION
    cache = aot._SESSION = aot.ExecutableCache()
    yield cache
    aot._SESSION = prev


def _fleet(n_replicas, net, **kw):
    kw.setdefault("batchBuckets", (8,))
    kw.setdefault("maxWaitMs", 1.0)
    fleet = FleetRouter()
    rids = [fleet.add_replica(ModelHost()) for _ in range(n_replicas)]
    fleet.register("m", net, **kw)
    return fleet, rids


class TestModelSLO:
    def test_validation_and_dict(self):
        slo = ModelSLO(p99_ms=50, queue_high=8, queue_low=1,
                       min_replicas=2, max_replicas=6)
        assert slo.as_dict()["p99_ms"] == 50.0
        with pytest.raises(ValueError, match="scale-down band"):
            ModelSLO(queue_high=1.0, queue_low=4.0)


class TestFleetRouting:
    def test_replica_lifecycle_errors(self, fresh_cache):
        fleet = FleetRouter()
        rid = fleet.add_replica(ModelHost(), replica_id="a")
        with pytest.raises(ValueError, match="already attached"):
            fleet.add_replica(ModelHost(), replica_id="a")
        with pytest.raises(KeyError, match="unknown replica"):
            fleet.remove_replica("ghost")
        with pytest.raises(KeyError, match="no replica serves"):
            fleet.submit("nope", _rows(1))
        fleet.remove_replica(rid)
        assert fleet.replica_ids() == []
        fleet.close()

    def test_least_loaded_dispatch_avoids_wedged_replica(self,
                                                         fresh_cache):
        """Wedge replica A's dispatcher so its queue holds work; the
        router must send new traffic to idle replica B."""
        fleet, (ra, rb) = _fleet(2, _mln(), queueLimit=8)
        try:
            hosts = dict(fleet._hosts())
            ba = hosts[ra].model("m").batcher
            orig = ba._dispatch
            release = threading.Event()
            ba._dispatch = lambda f: (release.wait(30), orig(f))[1]
            # occupy A: one in-flight + one queued
            for _ in range(2):
                threading.Thread(
                    target=lambda: hosts[ra].submit("m", _rows(1)),
                    daemon=True).start()
            deadline = time.time() + 10
            while fleet._queued_work(hosts[ra], "m") < 1 \
                    and time.time() < deadline:
                time.sleep(0.01)
            assert fleet._queued_work(hosts[ra], "m") >= 1
            # new traffic routes to the idle replica and completes
            # (requests inside the wedged dispatch count too — see
            # test_wedged_dispatch_still_counts_as_outstanding)
            # immediately even though A is wedged
            out = fleet.submit("m", _rows(2, seed=3))
            assert np.asarray(out).shape == (2, 4)
            bb = hosts[rb].model("m").batcher
            assert bb.stats["requests"] >= 1
            release.set()
        finally:
            release.set()
            fleet.close()

    def test_failover_on_full_queue_then_fleet_wide_429(self,
                                                        fresh_cache):
        fleet, (ra, rb) = _fleet(2, _mln(), queueLimit=1)
        try:
            hosts = dict(fleet._hosts())
            releases = []
            for rid in (ra, rb):
                b = hosts[rid].model("m").batcher
                orig = b._dispatch
                release = threading.Event()
                entered = threading.Event()
                b._dispatch = (lambda en, rel, o: lambda f:
                               (en.set(), rel.wait(30), o(f))[2])(
                                   entered, release, orig)
                releases.append(release)
                # wedge: one IN-FLIGHT (proven by `entered`), then one
                # request filling the 1-deep queue
                threading.Thread(
                    target=lambda h=hosts[rid]: h.submit("m", _rows(1)),
                    daemon=True).start()
                assert entered.wait(20)
                threading.Thread(
                    target=lambda h=hosts[rid]: h.submit("m", _rows(1)),
                    daemon=True).start()
                deadline = time.time() + 10
                while b.depth < 1 and time.time() < deadline:
                    time.sleep(0.01)
                assert b.depth == 1
            lab = fleet._m_failover.labels(model="m",
                                           error="QueueFullError")
            reg_before = lab.value
            with pytest.raises(QueueFullError):
                fleet.submit("m", _rows(1, seed=9))
            # the router tried the peer before giving up, and the
            # failover was counted under its error class
            assert lab.value == reg_before + 1
            for ev in releases:
                ev.set()
        finally:
            for ev in releases:
                ev.set()
            fleet.close()


class TestFleetRollingSwap:
    def test_swap_all_zero_errors_zero_compiles_under_load(
            self, fresh_cache):
        """Fleet-wide rolling deploy mid-soak: every response is
        bitwise one of the two versions, nothing fails, and with the
        new version's executables already hot the whole soak pays zero
        compiles (CompileWatch)."""
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        net1 = _mln()
        net2 = _mln()   # identical conf -> identical cache keys
        net2._params = jax.tree_util.tree_map(lambda a: a * 1.5,
                                              net2._params)
        o1 = ParallelInference(net1, batchBuckets=(8,))
        o2 = ParallelInference(net2, batchBuckets=(8,))
        n_threads, n_each = 3, 16
        feats = {(t, i): _rows(1 + (t + i) % 4, seed=50 + t * 100 + i)
                 for t in range(n_threads) for i in range(n_each)}
        want1 = {k: np.asarray(o1.output(v).jax())
                 for k, v in feats.items()}
        want2 = {k: np.asarray(o2.output(v).jax())
                 for k, v in feats.items()}

        fleet, _ = _fleet(2, net1, queueLimit=256)
        failures, versions = [], set()
        swap_at = threading.Event()

        def client(t):
            for i in range(n_each):
                if t == 0 and i == 3:
                    swap_at.set()
                k = (t, i)
                try:
                    got = np.asarray(fleet.submit("m", feats[k]))
                except Exception as e:
                    failures.append((k, repr(e)))
                    continue
                if np.array_equal(got, want1[k]):
                    versions.add(1)
                elif np.array_equal(got, want2[k]):
                    versions.add(2)
                else:
                    failures.append((k, "matches NEITHER version"))

        try:
            with aot.CompileWatch(fresh_cache) as watch:
                ts = [threading.Thread(target=client, args=(t,))
                      for t in range(n_threads)]
                for t in ts:
                    t.start()
                assert swap_at.wait(30)
                rep = fleet.swap_all("m", net2)
                for t in ts:
                    t.join(timeout=60)
            assert not failures, failures[:5]
            assert {r["version"] for r in rep.values()} == {2}
            assert all(
                {b: d["status"] for b, d in r["warm"].items()}
                == {8: "warm"} for r in rep.values())
            watch.assert_no_compiles("fleet rolling swap soak")
            assert 2 in versions
        finally:
            fleet.close()

    def test_swap_all_covers_sequence_models(self, fresh_cache):
        """swap_all routes by each host's registration kind: a
        sequence model registered fleet-wide rolls with the same
        zero-compile warm-then-flip, and an unregistered name raises
        before any replica is touched."""
        from deeplearning4j_tpu.nn import (InputType,
                                           NeuralNetConfiguration,
                                           Nesterovs)
        from deeplearning4j_tpu.nn.conf.layers import RnnOutputLayer
        from deeplearning4j_tpu.nn.conf.recurrent import LSTM
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        def rnn(seed=3):
            conf = (NeuralNetConfiguration.Builder().seed(seed)
                    .updater(Nesterovs(0.1, 0.9)).list()
                    .layer(LSTM(nOut=6))
                    .layer(RnnOutputLayer(nOut=3, activation="softmax",
                                          lossFunction="mcxent"))
                    .setInputType(InputType.recurrent(4, 5)).build())
            return MultiLayerNetwork(conf).init()

        net1, net2 = rnn(), rnn()   # identical conf -> same cache keys
        net2._params = jax.tree_util.tree_map(lambda a: a * 1.5,
                                              net2._params)
        fleet = FleetRouter()
        for _ in range(2):
            fleet.add_replica(ModelHost())
        try:
            fleet.register_sequence("seq", net1, slotBuckets=(2,))
            feats = np.random.RandomState(5).randn(3, 4).astype(
                np.float32)
            before = np.asarray(fleet.submit_sequence("seq", feats))
            with aot.CompileWatch(fresh_cache) as watch:
                rep = fleet.swap_all("seq", net2)
                after = np.asarray(fleet.submit_sequence("seq", feats))
            assert {r["version"] for r in rep.values()} == {2}
            watch.assert_no_compiles("sequence swap_all")
            assert not np.array_equal(before, after)  # new weights serve
            with pytest.raises(KeyError, match="register it fleet-wide"):
                fleet.swap_all("ghost", net2)
        finally:
            fleet.close()


class TestAutoscale:
    def test_queue_depth_scale_up_then_idle_scale_down(self,
                                                       fresh_cache):
        fleet, (ra, rb) = _fleet(2, _mln(), queueLimit=64)
        try:
            fleet.set_slo("m", queue_high=2.0, queue_low=0.5,
                          min_replicas=1, max_replicas=4)
            seen = []
            fleet.on_scale(seen.append)
            hosts = dict(fleet._hosts())
            # pile queued work directly onto both replicas' batchers
            # (wait=False keeps them pending; dispatch wedged)
            releases = []
            for rid in (ra, rb):
                b = hosts[rid].model("m").batcher
                orig = b._dispatch
                ev = threading.Event()
                b._dispatch = (lambda e, o: lambda f:
                               (e.wait(30), o(f))[1])(ev, orig)
                releases.append(ev)
                for j in range(6):
                    b.submit(_rows(1, seed=j), wait=False)
            decisions = fleet.autoscale_tick()
            up = [d for d in decisions if d["model"] == "m"][0]
            assert up["action"] == "scale_up"
            assert up["desired_replicas"] == 3
            assert any("queue_high" in r for r in up["reasons"])
            assert seen and seen[-1]["action"] == "scale_up"
            for ev in releases:
                ev.set()
            # drain, then an idle fleet votes scale_down to min
            deadline = time.time() + 20
            while any(fleet._queued_work(h, "m") for _, h
                      in fleet._hosts()) and time.time() < deadline:
                time.sleep(0.02)
            decisions = fleet.autoscale_tick()
            down = [d for d in decisions if d["model"] == "m"][0]
            assert down["action"] == "scale_down"
            assert down["desired_replicas"] == 1
        finally:
            for ev in releases:
                ev.set()
            fleet.close()

    def test_p99_slo_votes_scale_up_and_hold_not_dispatched(
            self, fresh_cache):
        fleet, _ = _fleet(1, _mln())
        try:
            fleet.set_slo("m", p99_ms=0.0001, queue_high=1e9,
                          queue_low=-1.0, max_replicas=3)
            seen = []
            fleet.on_scale(seen.append)
            for i in range(4):
                fleet.submit("m", _rows(1, seed=i))
            d = [x for x in fleet.autoscale_tick()
                 if x["model"] == "m"][0]
            assert d["action"] == "scale_up"
            assert any("p99" in r for r in d["reasons"])
            # a healthy SLO holds — and hold decisions are returned
            # but NOT dispatched to callbacks
            fleet.set_slo("m", p99_ms=None, queue_high=1e9,
                          queue_low=-1.0)
            seen.clear()
            d = [x for x in fleet.autoscale_tick()
                 if x["model"] == "m"][0]
            assert d["action"] == "hold" and not seen
        finally:
            fleet.close()


class TestFleetObservability:
    def test_snapshot_additive_schema(self, fresh_cache):
        net = _mln()
        fleet, (ra, rb) = _fleet(2, net)
        try:
            fleet.submit("m", _rows(2, seed=1))
            snap = fleet.metrics_snapshot()
            assert set(snap) == {"registry", "replicas", "models",
                                 "slos"}
            assert set(snap["replicas"]) == {ra, rb}
            for view in snap["replicas"].values():
                assert set(view) == {"queue_depth", "models",
                                     "sequences"}
                # the nested per-host view is the PR 13 schema
                assert set(view["models"]["m"]) == {
                    "version", "stats", "queue_depth", "occupancy"}
            agg = snap["models"]["m"]
            assert agg["kind"] == "oneshot" and agg["replicas"] == 2
        finally:
            fleet.close()


class TestClosedLoopLoadgen:
    def test_closed_loop_counts_and_error_classes(self):
        calls = []

        def submit(x):
            calls.append(x)
            if int(x[0, 0]) % 3 == 0:
                raise QueueFullError("full")

        rec = loadgen.run_closed_loop(
            submit, lambda c, i: np.full((1, 1), c * 100 + i,
                                         np.float32),
            n_clients=3, requests_per_client=6, think_time_s=0.0,
            seed=0)
        assert rec["mode"] == "closed" and rec["clients"] == 3
        assert rec["requests"] == 18
        assert rec["completed"] + sum(rec["errors"].values()) == 18
        assert rec["errors"].get("QueueFullError", 0) > 0
        assert len(calls) == 18     # every client kept going past errors

    def test_closed_loop_blocks_on_response(self):
        """At most n_clients requests are ever in flight — the closed-
        loop property an open loop does not have."""
        in_flight = [0]
        peak = [0]
        lock = threading.Lock()

        def submit(x):
            with lock:
                in_flight[0] += 1
                peak[0] = max(peak[0], in_flight[0])
            time.sleep(0.002)
            with lock:
                in_flight[0] -= 1

        rec = loadgen.run_closed_loop(
            submit, lambda c, i: np.zeros((1, 1), np.float32),
            n_clients=2, requests_per_client=5, think_time_s=0.0,
            seed=1)
        assert rec["completed"] == 10
        assert peak[0] <= 2

    def test_seeded_think_time_reproducible(self):
        sleeps_a, sleeps_b = [], []
        for sink in (sleeps_a, sleeps_b):
            loadgen.run_closed_loop(
                lambda x: None,
                lambda c, i: np.zeros((1, 1), np.float32),
                n_clients=2, requests_per_client=3, think_time_s=0.01,
                seed=5, sleep=sink.append)
        # clients run concurrently, so compare the multiset: the drawn
        # think times are seed-determined even though arrival order is
        # interleaved
        assert sorted(sleeps_a) == sorted(sleeps_b)
        assert len(sleeps_a) == 6


class TestScenarios:
    def test_slow_client_storm_record(self, fresh_cache):
        fleet, _ = _fleet(2, _mln(), queueLimit=128)
        try:
            rec = scenario_slow_client_storm(
                lambda x: fleet.submit("m", x),
                lambda c, i: _rows(1, seed=c * 10 + i),
                n_clients=6, requests_per_client=3, think_time_s=0.0,
                seed=2)
            assert rec["scenario"] == "slow_client_storm"
            assert rec["completed"] == 18 and rec["errors"] == {}
            assert rec["p99_ms"] is not None
        finally:
            fleet.close()

    def test_slow_client_storm_hedged_rerun(self, fresh_cache):
        """hedged_submit reruns the SAME seeded storm through the
        hedging path and the record gains the fire-rate + p99 delta
        (ISSUE 16 satellite)."""
        fleet, _ = _fleet(2, _mln(), queueLimit=128)
        try:
            hedges = fleet._m_hedges.labels(model="m")
            armed = []

            def hedged_submit(x):
                if not armed:   # arm lazily: the base storm runs clean
                    fleet.set_hedge("m", after_s=10.0)
                    armed.append(1)
                return fleet.submit("m", x)

            rec = scenario_slow_client_storm(
                lambda x: fleet.submit("m", x),
                lambda c, i: _rows(1, seed=c * 10 + i),
                n_clients=4, requests_per_client=3, think_time_s=0.0,
                seed=2, hedged_submit=hedged_submit,
                hedge_stats=lambda: hedges.value)
            h = rec["hedged"]
            assert h["completed"] == 12 and h["errors"] == {}
            # a 10 s mark never fires on this workload: the record
            # still carries the (zero) fire-rate and the p99 delta
            assert h["hedges_fired"] == 0 and h["hedge_rate"] == 0.0
            assert isinstance(h["p99_delta_ms"], float)
        finally:
            fleet.close()

    def test_diurnal_ramp_phases_and_error_classes(self):
        fails = [0]

        def submit(x):
            fails[0] += 1
            if fails[0] % 5 == 0:
                raise QueueFullError("full")

        rec = scenario_diurnal_ramp(
            submit, lambda i: _rows(1, seed=i), base_rate=200.0,
            peak_rate=800.0, phases=3, requests_per_phase=10, seed=3)
        assert rec["scenario"] == "diurnal_ramp"
        assert len(rec["phases"]) == 3
        # the ramp peaks in the middle
        rates = [p["rate_rps"] for p in rec["phases"]]
        assert rates[1] == max(rates)
        assert rec["errors"].get("QueueFullError", 0) > 0
        assert rec["completed"] + sum(rec["errors"].values()) == 30

    def test_hot_model_skew_split(self, fresh_cache):
        net = _mln()
        fleet = FleetRouter([ModelHost()])
        try:
            fleet.register("hot", net, batchBuckets=(8,))
            fleet.register("cold", net, batchBuckets=(8,))
            rec = scenario_hot_model_skew(
                lambda n: (lambda x: fleet.submit(n, x)),
                lambda i: _rows(1, seed=i),
                models=["hot", "cold"], hot_fraction=0.8, rate=500.0,
                n_requests=40, seed=4)
            assert rec["scenario"] == "hot_model_skew"
            assert rec["hot_model"] == "hot"
            hot_n = rec["per_model"]["hot"]["requests"]
            cold_n = rec["per_model"]["cold"]["requests"]
            assert hot_n + cold_n == 40 and hot_n > cold_n
            assert rec["completed"] == 40
            with pytest.raises(ValueError, match=">= 2 models"):
                scenario_hot_model_skew(
                    lambda n: (lambda x: None), lambda i: None,
                    models=["one"])
        finally:
            fleet.close()
