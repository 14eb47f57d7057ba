"""Chaos-hardening gates (runtime/chaos.py, serving/breaker.py, and
the fleet failure domains in serving/fleet.py — docs/RESILIENCE.md
"Chaos harness", docs/SERVING.md "Failure domains").

What must hold:

- determinism: the same seed produces the SAME fault sequence
  (``plan.events``) over the same traffic — chaos runs are replayable,
  never sleeps-and-hope;
- the fault kinds (raise / wedge / slow / corrupt) each do exactly
  what they schedule, with an injectable sleep so no test blocks;
- the circuit breaker walks closed -> open -> half-open -> closed at
  EXACTLY the ManualClock-predicted steps;
- a quarantined replica serves only probes and is re-admitted after
  exactly ``readmit_after`` consecutive probe successes;
- the retry budget caps failover amplification at ratio + burst;
- brownout sheds ONLY requests whose deadline is already hopeless;
- the chaos soak: a live fleet under a seeded plan (wedged + flapping
  + slow replica) completes with ZERO client-visible non-injected
  failures and ZERO steady-state compiles (CompileWatch);
- the armed-but-quiet harness costs <= 1.03x the disarmed serving
  path (best-of-trials medians);
- the checkpoint content digest: a digest-mismatched snapshot is
  treated as ABSENT and ResilientFit falls back to the previous one.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.runtime import aot, chaos
from deeplearning4j_tpu.runtime.chaos import (
    ChaosError, ChaosPlan, fault_point,
)
from deeplearning4j_tpu.serving import (
    BrownoutController, CircuitBreaker, DeadlineExceededError,
    FleetRouter, ManualClock, ModelHost, ReplicaHealth, RetryBudget,
)

pytestmark = pytest.mark.faults


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


@pytest.fixture(autouse=True)
def _always_disarmed():
    """No test may leak an armed plan into the next."""
    chaos.disarm()
    yield
    chaos.disarm()


def _fleet(n_replicas, net, *, router_kw=None, **kw):
    kw.setdefault("batchBuckets", (8,))
    kw.setdefault("maxWaitMs", 1.0)
    fleet = FleetRouter(**(router_kw or {}))
    rids = [fleet.add_replica(ModelHost()) for _ in range(n_replicas)]
    fleet.register("m", net, **kw)
    return fleet, rids


def _count_dispatches(hosts, name="m"):
    """Per-replica dispatch counters (the serving counters in
    telemetry are labeled by MODEL, so they aggregate over replicas —
    wrap each replica's batcher dispatch to see where traffic lands).
    Serial submits coalesce 1:1, so dispatch calls == requests."""
    hits = {}
    for rid, host in hosts.items():
        hits[rid] = 0
        b = host.model(name).batcher

        def counted(feats, _rid=rid, _orig=b._dispatch):
            hits[_rid] += 1
            return _orig(feats)

        b._dispatch = counted
    return hits


# ----------------------------------------------------------------------
# ChaosPlan: determinism + fault kinds
# ----------------------------------------------------------------------
class TestChaosPlanDeterminism:
    def _drive(self, plan, n=40):
        """Fixed traffic: n invocations across two seams, injected
        raises swallowed. Returns the plan's replay record."""
        with plan:
            for i in range(n):
                seam = "fleet.dispatch" if i % 2 else "queue.dispatch"
                try:
                    fault_point(seam, payload=i)
                except ChaosError:
                    pass
        return list(plan.events)

    def _plan(self, seed):
        return (ChaosPlan(seed=seed, sleep=lambda s: None)
                .random_raises("fleet.dispatch", rate=0.3, window=20)
                .random_slows("queue.dispatch", rate=0.3, window=20,
                              seconds=0.01)
                .raise_n("queue.dispatch", at=1))

    def test_same_seed_same_traffic_identical_fault_sequence(self):
        ev_a = self._drive(self._plan(seed=5))
        ev_b = self._drive(self._plan(seed=5))
        assert ev_a == ev_b
        assert ev_a, "the seeded plan must actually fire"
        # every event is (seam, kind, ordinal)
        assert all(len(e) == 3 for e in ev_a)

    def test_different_seed_different_schedule(self):
        scheds = {json.dumps(self._plan(seed=s).schedule(),
                             sort_keys=True) for s in range(6)}
        assert len(scheds) > 1

    def test_schedule_is_fixed_before_arming(self):
        """random_* rules draw their ordinals at SCHEDULE time from
        the seeded RNG — the replay record is a pure function of the
        schedule plus each seam's invocation order."""
        a = self._plan(seed=9).schedule()
        b = self._plan(seed=9).schedule()
        assert a == b

    def test_disarmed_is_identity_and_armed_skips_ruleless_seams(self):
        payload = object()
        assert fault_point("fleet.dispatch", payload) is payload
        plan = ChaosPlan(seed=0).raise_n("queue.dispatch", at=0)
        with plan:
            # a seam with no rules takes the armed fast path: payload
            # untouched, invocation NOT counted, nothing fired
            assert fault_point("fleet.dispatch", payload) is payload
            with pytest.raises(ChaosError):
                fault_point("queue.dispatch")
        assert plan.fired("fleet.dispatch") == 0
        assert plan.fired("queue.dispatch") == 1
        assert chaos.armed_plan() is None  # __exit__ disarmed

    def test_arm_disarm_roundtrip(self):
        plan = ChaosPlan()
        assert chaos.arm(plan) is plan
        assert chaos.armed_plan() is plan
        assert chaos.disarm() is plan
        assert chaos.disarm() is None


class TestFaultKinds:
    def test_raise_n_exact_ordinals_and_custom_exc(self):
        class Boom(OSError):
            pass

        plan = ChaosPlan().raise_n("checkpoint.restore", times=2, at=1,
                                   exc=Boom, message="disk gone")
        with plan:
            fault_point("checkpoint.restore")       # ordinal 0: clean
            for _ in range(2):                      # ordinals 1, 2
                with pytest.raises(Boom, match="disk gone"):
                    fault_point("checkpoint.restore")
            fault_point("checkpoint.restore")       # ordinal 3: clean
        assert plan.events == [("checkpoint.restore", "raise", 1),
                               ("checkpoint.restore", "raise", 2)]

    def test_slow_and_wedge_use_injected_sleep(self):
        slept = []
        plan = (ChaosPlan(sleep=slept.append)
                .slow("queue.dispatch", 0.25, at=0)
                .wedge("queue.dispatch", 7.0, at=1))
        with plan:
            fault_point("queue.dispatch")
            fault_point("queue.dispatch")
        assert slept == [0.25, 7.0]

    def test_wedge_release_event_unblocks(self):
        release = threading.Event()
        release.set()  # pre-released: the wedge returns immediately
        plan = ChaosPlan().wedge("sequence.step", 60.0, at=0,
                                 release=release)
        t0 = time.monotonic()
        with plan:
            fault_point("sequence.step")
        assert time.monotonic() - t0 < 5.0
        assert plan.events == [("sequence.step", "wedge", 0)]

    def test_corrupt_default_and_custom_mutate(self):
        plan = (ChaosPlan()
                .corrupt("host.submit", at=0)
                .corrupt("checkpoint.restore", at=0)
                .corrupt("checkpoint.write", at=0,
                         mutate=lambda p: p * 10))
        with plan:
            arr = fault_point("host.submit",
                              np.ones(4, dtype=np.float32))
            path = fault_point("checkpoint.restore", "/tmp/x.bin")
            n = fault_point("checkpoint.write", 4)
        assert np.isnan(arr[0]) and not np.isnan(arr[1:]).any()
        assert path == "/tmp/x.bin.chaos-corrupt"
        assert n == 40

    def test_fired_counts_reach_telemetry(self):
        from deeplearning4j_tpu.runtime import telemetry

        plan = ChaosPlan().raise_n("server.request", times=3)
        with plan:
            for _ in range(3):
                with pytest.raises(ChaosError):
                    fault_point("server.request")
        child = telemetry.get_registry().counter(
            "dl4j_chaos_injections_total",
            "chaos faults fired, by seam and kind",
            labels=("seam", "kind")).labels(seam="server.request",
                                            kind="raise")
        assert child.value >= 3
        assert plan.fired() == 3


# ----------------------------------------------------------------------
# breaker / quarantine / budget / brownout (pure units, ManualClock)
# ----------------------------------------------------------------------
class TestCircuitBreaker:
    def test_exact_manualclock_transitions(self):
        clk = ManualClock()
        br = CircuitBreaker(window=8, failure_ratio=0.5, min_samples=4,
                            open_for_s=10.0, close_after=2, clock=clk)
        # below min_samples nothing can trip, even at 100% failure
        assert br.record(False) == "closed"
        assert br.record(False) == "closed"
        assert br.record(True) == "closed"
        # 4th sample: 3 failures / 4 samples >= 0.5 -> OPEN, now
        assert br.record(False) == "open"
        assert br.opened_total == 1 and not br.allow()
        clk.advance(9.999)
        assert br.state == "open"          # one tick early: still open
        clk.advance(0.001)
        assert br.state == "half_open"     # exactly open_for_s
        assert br.allow()
        assert br.record(True) == "half_open"  # 1 of close_after=2
        assert br.record(True) == "closed"
        assert br.snapshot()["window"] == []   # re-closed clean

    def test_half_open_failure_retrips_immediately(self):
        clk = ManualClock()
        br = CircuitBreaker(window=4, failure_ratio=0.5, min_samples=2,
                            open_for_s=5.0, close_after=2, clock=clk)
        br.record(False), br.record(False)
        assert br.state == "open"
        clk.advance(5.0)
        assert br.record(False) == "open"  # half-open probe failed
        assert br.opened_total == 2
        clk.advance(4.999)
        assert br.state == "open"          # the clock restarted

    def test_successes_never_trip(self):
        br = CircuitBreaker(window=4, min_samples=1, clock=ManualClock())
        for _ in range(50):
            assert br.record(True) == "closed"


class TestReplicaHealthQuarantine:
    def test_readmission_after_exact_probe_streak(self):
        h = ReplicaHealth(readmit_after=3, clock=ManualClock())
        assert h.admissible()
        h.quarantine()
        assert h.quarantined and not h.admissible()
        assert h.note_probe(True) is False   # streak 1
        assert h.note_probe(True) is False   # streak 2
        assert h.note_probe(False) is False  # failure RESETS the streak
        for _ in range(2):
            assert h.note_probe(True) is False
        assert h.note_probe(True) is True    # 3 consecutive: readmitted
        assert not h.quarantined and h.admissible()
        assert h.breaker.state == "closed"   # re-admission starts clean

    def test_probe_ignored_when_not_quarantined(self):
        h = ReplicaHealth(readmit_after=1, clock=ManualClock())
        assert h.note_probe(True) is False


class TestRetryBudget:
    def test_burst_then_ratio_cap(self):
        b = RetryBudget(ratio=0.5, burst=2.0)
        assert b.try_spend() and b.try_spend()  # the burst
        assert not b.try_spend()                # empty: fail fast
        b.note_request()                        # +0.5
        assert not b.try_spend()
        b.note_request()                        # +0.5 -> 1.0
        assert b.try_spend()
        snap = b.snapshot()
        assert snap["spent"] == 3 and snap["denied"] == 2
        assert snap["requests"] == 2

    def test_deposits_capped_at_burst(self):
        b = RetryBudget(ratio=1.0, burst=1.0)
        for _ in range(100):
            b.note_request()
        assert b.try_spend()
        assert not b.try_spend()  # the bucket never exceeded burst


class TestBrownout:
    def test_sheds_only_hopeless_deadlines(self):
        bo = BrownoutController(est_item_s=0.1)
        assert not bo.should_shed(4, deadline_s=0.5)   # 0.4 <= 0.5
        assert bo.should_shed(6, deadline_s=0.5)       # 0.6 > 0.5
        assert not bo.should_shed(1000, deadline_s=None)
        assert bo.snapshot() == {"shed": 1, "admitted": 2,
                                 "est_item_s": 0.1, "margin": 1.0}

    def test_no_estimate_never_sheds(self):
        bo = BrownoutController()   # no static estimate
        assert bo.estimate_wait_s(10) is None
        assert not bo.should_shed(10 ** 6, deadline_s=1e-9)
        # the measured estimate kicks in when the caller has one
        assert bo.should_shed(10, deadline_s=0.5, measured_item_s=0.1)

    def test_margin_scales_the_estimate(self):
        bo = BrownoutController(est_item_s=0.1, margin=2.0)
        assert bo.estimate_wait_s(5) == pytest.approx(1.0)


# ----------------------------------------------------------------------
# fleet failure domains (live hosts)
# ----------------------------------------------------------------------
class TestFleetFailureDomains:
    def test_failover_on_injected_dispatch_fault(self, fresh_cache):
        """An injected dispatch-path raise on the first replica is
        absorbed by failover, counted under its error class, and
        charges that replica's breaker."""
        fleet, rids = _fleet(2, _mln())
        try:
            lab = fleet._m_failover.labels(model="m",
                                           error="ChaosError")
            before = lab.value
            with ChaosPlan().raise_n("fleet.dispatch", at=0):
                out = fleet.submit("m", _rows(2, seed=1))
            assert np.asarray(out).shape == (2, 4)
            assert lab.value == before + 1
            # exactly one replica took the charge
            charged = [r for r in rids
                       if False in fleet.health(r).snapshot()["window"]]
            assert len(charged) == 1
        finally:
            fleet.close()

    def test_breaker_opens_and_recovers_at_exact_clock_steps(
            self, fresh_cache):
        """Fleet-wide chaos trips every breaker at the predicted
        record; recovery walks open -> half-open -> closed at exactly
        the ManualClock-predicted steps, mirrored into the gauge."""
        clk = ManualClock()
        fleet, rids = _fleet(
            2, _mln(), router_kw=dict(
                clock=clk,
                breaker=dict(window=4, failure_ratio=0.5,
                             min_samples=2, open_for_s=10.0,
                             close_after=1)))
        try:
            plan = ChaosPlan().raise_n("fleet.dispatch", times=10 ** 6)
            with plan:
                for _ in range(2):      # 2 failures per replica: trip
                    with pytest.raises(ChaosError):
                        fleet.submit("m", _rows(1))
            for r in rids:
                assert fleet.health(r).breaker.state == "open"
                assert fleet._m_breaker.labels(replica=r).value == 2.0
            # fail open: ALL replicas barred still serves (disarmed)
            out = fleet.submit("m", _rows(1, seed=2))
            assert np.asarray(out).shape == (1, 4)
            clk.advance(10.0)           # exactly open_for_s
            for r in rids:
                assert fleet.health(r).breaker.state == "half_open"
            fleet.submit("m", _rows(1, seed=3))  # close_after=1
            states = {fleet.health(r).breaker.state for r in rids}
            assert "closed" in states   # the serving replica re-closed
        finally:
            fleet.close()

    def test_open_breaker_excludes_replica_from_ranking(
            self, fresh_cache):
        clk = ManualClock()
        fleet, (ra, rb) = _fleet(
            2, _mln(), router_kw=dict(
                clock=clk, breaker=dict(min_samples=1, window=4,
                                        failure_ratio=0.5,
                                        open_for_s=30.0)))
        try:
            fleet.health(ra).record(False)      # trip ra directly
            assert fleet.health(ra).breaker.state == "open"
            hosts = dict(fleet._hosts())
            hits = _count_dispatches(hosts)
            for i in range(4):
                fleet.submit("m", _rows(1, seed=10 + i))
            assert hits[ra] == 0        # every request avoided ra
            assert hits[rb] >= 1
        finally:
            fleet.close()

    def test_quarantine_probe_readmission_cycle(self, fresh_cache):
        fleet, (ra, rb) = _fleet(
            2, _mln(), router_kw=dict(readmit_after=3))
        try:
            fleet.quarantine(rb)
            assert fleet._m_breaker.labels(replica=rb).value == 2.0
            hosts = dict(fleet._hosts())
            hits = _count_dispatches(hosts)
            fleet.submit("m", _rows(1))     # organic traffic: ra only
            assert hits[rb] == 0
            fleet.set_probe("m", _rows(1, seed=4))
            ticks = [fleet.probe_tick() for _ in range(3)]
            flat = [r for t in ticks for r in t]
            assert [r["ok"] for r in flat] == [True] * 3
            assert [r["readmitted"] for r in flat] == [False, False,
                                                       True]
            assert not fleet.health(rb).quarantined
            assert fleet._m_breaker.labels(replica=rb).value == 0.0
            assert fleet.probe_tick() == []  # nobody quarantined now
            # only the 3 probe canaries ever reached the quarantined
            # replica
            assert hits[rb] == 3
        finally:
            fleet.close()

    def test_brownout_sheds_hopeless_admits_feasible(self, fresh_cache):
        fleet, (ra,) = _fleet(1, _mln(), queueLimit=8)
        try:
            bo = fleet.set_brownout("m", est_item_s=10.0)
            shed_lab = fleet._m_shed.labels(model="m")
            base = shed_lab.value
            # wedge the only replica so work actually queues
            host = dict(fleet._hosts())[ra]
            b = host.model("m").batcher
            orig = b._dispatch
            release = threading.Event()
            b._dispatch = lambda f: (release.wait(30), orig(f))[1]
            threading.Thread(target=lambda: host.submit("m", _rows(1)),
                             daemon=True).start()
            deadline = time.time() + 10
            while fleet._queued_work(host, "m") < 1 \
                    and time.time() < deadline:
                time.sleep(0.01)
            # >= 1 queued item x 10 s/item >> 0.5 s: hopeless, shed NOW
            with pytest.raises(DeadlineExceededError, match="brownout"):
                fleet.submit("m", _rows(1, seed=5), deadline_s=0.5)
            assert shed_lab.value == base + 1 and bo.shed == 1
            release.set()
            # an idle queue admits the same deadline
            host.model("m").batcher  # drain
            while fleet._queued_work(host, "m") > 0 \
                    and time.time() < deadline:
                time.sleep(0.01)
            out = fleet.submit("m", _rows(1, seed=6), deadline_s=30.0)
            assert np.asarray(out).shape == (1, 4)
            assert shed_lab.value == base + 1      # nothing else shed
            # deadline-less requests are never brownout candidates
            fleet.submit("m", _rows(1, seed=7))
        finally:
            release.set()
            fleet.close()

    def test_hedged_dispatch_second_replica_wins(self, fresh_cache):
        """Slow the primary's coalesced dispatch (chaos seam); the
        hedge fires at the mark, the second replica answers first and
        wins, and the result is still correct."""
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        net = _mln()
        feats = _rows(2, seed=8)
        want = np.asarray(ParallelInference(
            net, batchBuckets=(8,)).output(feats).jax())
        fleet, rids = _fleet(2, net)
        try:
            fleet.submit("m", _rows(1))    # warm both code paths
            fleet.set_hedge("m", after_s=0.02)
            hedges = fleet._m_hedges.labels(model="m")
            wins = fleet._m_hedge_wins.labels(model="m")
            h0, w0 = hedges.value, wins.value
            # ordinal 0 = the primary's dispatch (the hedge only
            # exists 20 ms later): slow it well past the mark
            with ChaosPlan().slow("queue.dispatch", 0.5, at=0):
                got = np.asarray(fleet.submit("m", feats))
            np.testing.assert_array_equal(got, want)
            assert hedges.value == h0 + 1
            assert wins.value == w0 + 1
        finally:
            fleet.close()

    def test_hedge_not_fired_when_primary_is_fast(self, fresh_cache):
        fleet, _ = _fleet(2, _mln())
        try:
            fleet.submit("m", _rows(1))
            fleet.set_hedge("m", after_s=5.0)
            hedges = fleet._m_hedges.labels(model="m")
            h0 = hedges.value
            out = fleet.submit("m", _rows(2, seed=9))
            assert np.asarray(out).shape == (2, 4)
            assert hedges.value == h0      # primary answered in time
        finally:
            fleet.close()


# ----------------------------------------------------------------------
# the chaos soak + the overhead gate
# ----------------------------------------------------------------------
class TestChaosSoak:
    def test_soak_zero_noninjected_failures_zero_compiles(
            self, fresh_cache):
        """The acceptance soak: a 3-replica fleet under a seeded plan
        (a wedged dispatch, flapping dispatch-path raises, seeded slow
        batches) serves every request bitwise-correctly, surfaces ZERO
        client-visible errors (the raises are absorbed by budget-capped
        failover — counted, exactly), and pays ZERO steady-state
        compiles."""
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        net = _mln()
        n_threads, n_each = 3, 20
        feats = {(t, i): _rows(1 + (t + i) % 4, seed=100 + t * 50 + i)
                 for t in range(n_threads) for i in range(n_each)}
        oracle = ParallelInference(net, batchBuckets=(8,))
        want = {k: np.asarray(oracle.output(v).jax())
                for k, v in feats.items()}

        fleet, rids = _fleet(3, net, queueLimit=64)
        failures = []

        def client(t):
            for i in range(n_each):
                k = (t, i)
                try:
                    got = np.asarray(fleet.submit("m", feats[k]))
                except Exception as e:   # noqa: BLE001 - the assertion
                    failures.append((k, repr(e)))
                    continue
                if not np.array_equal(got, want[k]):
                    failures.append((k, "wrong answer"))

        # flapping: sparse raise ordinals (spaced far wider than the
        # in-flight window) so a single request can never draw two
        # consecutive injected raises across its failover attempts —
        # zero client-visible failures is DETERMINISTIC, not lucky
        plan = ChaosPlan(seed=11)
        for at in (3, 17, 31, 45):
            plan.raise_n("fleet.dispatch", at=at)
        plan.wedge("queue.dispatch", 0.25, at=5)       # wedged replica
        plan.random_slows("queue.dispatch", rate=0.10, window=60,
                          seconds=0.01)                # slow replica
        lab = fleet._m_failover.labels(model="m", error="ChaosError")
        fo0 = lab.value
        try:
            fleet.submit("m", _rows(2, seed=999))      # warm
            with aot.CompileWatch(fresh_cache) as watch:
                with plan:
                    ts = [threading.Thread(target=client, args=(t,))
                          for t in range(n_threads)]
                    for th in ts:
                        th.start()
                    for th in ts:
                        th.join(timeout=120)
            assert not failures, failures[:5]
            assert watch.misses == 0
            raises = plan.fired("fleet.dispatch")
            assert raises == 4                      # all ordinals hit
            # every injected raise became exactly one counted failover
            assert lab.value - fo0 == raises
            assert plan.fired("queue.dispatch") >= 1
            # amplification stayed inside the ratio cap
            snap = fleet._budget("m").snapshot()
            assert snap["spent"] <= snap["ratio"] * snap["requests"] \
                + snap["burst"]
        finally:
            fleet.close()

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_armed_quiet_harness_overhead_within_3pct(
            self, fresh_cache):
        """The fast-path gate: a plan armed with rules only on an
        UNTOUCHED seam must cost <= 1.03x the disarmed serving path
        (best-of-trials medians — the bench `serving_chaos` leg gates
        the same ratio end-to-end)."""
        fleet, _ = _fleet(1, _mln(), maxWaitMs=0.1)
        feats = _rows(1, seed=12)
        quiet = ChaosPlan().raise_n("checkpoint.write", times=10 ** 6)

        def trial(n=120):
            samples = []
            for _ in range(n):
                t0 = time.perf_counter()
                fleet.submit("m", feats)
                samples.append(time.perf_counter() - t0)
            return float(np.median(samples))

        try:
            for _ in range(30):       # warm executables + code paths
                fleet.submit("m", feats)
            disarmed, armed = [], []
            for _ in range(4):        # interleave against drift
                disarmed.append(trial())
                with quiet:
                    armed.append(trial())
            ratio = min(armed) / min(disarmed)
            assert ratio <= 1.03, (
                f"armed-but-quiet harness cost {ratio:.4f}x the "
                f"disarmed path (gate: 1.03x); medians "
                f"disarmed={disarmed} armed={armed}")
        finally:
            fleet.close()


# ----------------------------------------------------------------------
# checkpoint digest + the chaos checkpoint seams
# ----------------------------------------------------------------------
class TestCheckpointDigest:
    def _mlp_net(self, seed=42):
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

    def _iter(self, n=64, batch=16, seed=0):
        from deeplearning4j_tpu.data import DataSetIterator

        rng = np.random.RandomState(seed)
        x = rng.randn(n, 4).astype("float32")
        y = np.eye(3, dtype="float32")[rng.randint(0, 3, n)]
        return DataSetIterator(x, y, batch)

    def _tamper(self, step_dir):
        """Flip the recorded digest — the on-disk state no longer
        hashes to what the manifest promises."""
        mpath = os.path.join(step_dir, "manifest.json")
        with open(mpath) as f:
            manifest = json.load(f)
        assert "digest" in manifest
        manifest["digest"] = "0" * len(manifest["digest"])
        with open(mpath, "w") as f:
            json.dump(manifest, f)

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_digest_rides_the_commit_and_verifies(self, tmp_path):
        from deeplearning4j_tpu.util import sharded_checkpoint as ck

        net = self._mlp_net()
        net.fit(self._iter())
        p = ck.step_path(tmp_path, 1)
        ck.ShardedModelSerializer.writeModel(net, p)
        digest = ck.read_manifest(p)["digest"]
        assert len(digest) == 64        # sha256 hex
        restored = ck.ShardedModelSerializer.restore(p)
        got = np.asarray(restored.output(_rows(2, seed=1)[:, :4]))
        assert got.shape == (2, 3)
        # the digest is a function of the STATE, not the step
        p2 = ck.step_path(tmp_path, 2)
        ck.ShardedModelSerializer.writeModel(net, p2)
        assert ck.read_manifest(p2)["digest"] == digest

    def test_tampered_digest_raises_on_restore(self, tmp_path):
        from deeplearning4j_tpu.util import sharded_checkpoint as ck

        net = self._mlp_net()
        p = ck.step_path(tmp_path, 1)
        ck.ShardedModelSerializer.writeModel(net, p)
        self._tamper(p)
        with pytest.raises(ck.CheckpointDigestError):
            ck.ShardedModelSerializer.restore(p)

    def test_resilient_fit_falls_back_past_corrupt_snapshot(
            self, tmp_path):
        """The satellite gate: the newest checkpoint fails its digest
        -> treated as ABSENT, the resume walks back to the previous
        snapshot, and the replayed run still matches the no-fault
        reference bitwise."""
        import jax

        from deeplearning4j_tpu.runtime.resilience import (
            ResilientFit, RetryPolicy,
        )
        from deeplearning4j_tpu.util import sharded_checkpoint as ck

        fast = RetryPolicy(maxRetries=3, initialDelay=0.001,
                           maxDelay=0.004, sleep=lambda s: None)
        ref = self._mlp_net()
        ref.fit(self._iter(), epochs=2)

        net = self._mlp_net()
        rf = ResilientFit(net, tmp_path / "ck", saveEveryNIterations=2,
                          keepLast=3, retryPolicy=fast)
        rf.fit(self._iter(), epochs=2)   # 8 steps: ckpts 4, 6, 8 kept
        steps = ck.complete_steps(tmp_path / "ck")
        assert steps == [4, 6, 8]
        self._tamper(ck.step_path(tmp_path / "ck", 8))

        net2 = self._mlp_net()
        rf2 = ResilientFit(net2, tmp_path / "ck",
                           saveEveryNIterations=2, keepLast=3,
                           retryPolicy=fast)
        rf2.fit(self._iter(), epochs=2)  # resumes from 6, replays 7-8
        fa = jax.tree_util.tree_leaves(ref._params)
        fb = jax.tree_util.tree_leaves(net2._params)
        assert len(fa) == len(fb)
        for u, v in zip(fa, fb):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_all_snapshots_corrupt_means_fresh_start(self, tmp_path):
        from deeplearning4j_tpu.runtime.resilience import (
            ResilientFit, RetryPolicy,
        )
        from deeplearning4j_tpu.util import sharded_checkpoint as ck

        fast = RetryPolicy(maxRetries=2, initialDelay=0.001,
                           maxDelay=0.002, sleep=lambda s: None)
        net = self._mlp_net()
        ResilientFit(net, tmp_path / "ck", saveEveryNIterations=4,
                     keepLast=2, retryPolicy=fast).fit(self._iter())
        for s in ck.complete_steps(tmp_path / "ck"):
            self._tamper(ck.step_path(tmp_path / "ck", s))
        net2 = self._mlp_net()
        rf2 = ResilientFit(net2, tmp_path / "ck",
                           saveEveryNIterations=4, keepLast=2,
                           retryPolicy=fast)
        rf2.fit(self._iter())            # fresh start, no crash
        assert net2._iteration == 4

    def test_chaos_checkpoint_seams_ride_the_retry(self, tmp_path):
        """An injected IO-shaped raise on checkpoint.write /
        checkpoint.restore is absorbed by the SAME retry() the organic
        transient faults ride (retryOn = IOError/OSError/Timeout) —
        the `exc` override models the fault class the seam sees in
        production."""
        from deeplearning4j_tpu.runtime.resilience import (
            ResilientFit, RetryPolicy,
        )
        from deeplearning4j_tpu.util import sharded_checkpoint as ck

        class DiskFault(ChaosError, OSError):
            """Injected, but shaped like the transient it simulates."""

        fast = RetryPolicy(maxRetries=3, initialDelay=0.001,
                           maxDelay=0.004, sleep=lambda s: None)
        net = self._mlp_net()
        with ChaosPlan().raise_n("checkpoint.write", at=0,
                                 exc=DiskFault):
            ResilientFit(net, tmp_path / "ck", saveEveryNIterations=4,
                         keepLast=2,
                         retryPolicy=fast).fit(self._iter())
        assert ck.latest_step(tmp_path / "ck") == 4
        net2 = self._mlp_net()
        with ChaosPlan().raise_n("checkpoint.restore", at=0,
                                 exc=DiskFault) as plan:
            ResilientFit(net2, tmp_path / "ck", saveEveryNIterations=4,
                         keepLast=2,
                         retryPolicy=fast).fit(self._iter(), epochs=2)
        assert plan.fired("checkpoint.restore") == 1
        assert net2._iteration == 8      # resumed from 4, continued
