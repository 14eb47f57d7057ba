"""Iteration-level sequence serving gates (serving/sequence.py,
nn/multilayer.py rnnStepBatched, docs/SERVING.md "Sequence serving").

What must hold:

- parity: slot-batched per-step outputs are BITWISE equal to serial
  ``rnnTimeStep`` per slot — ragged lengths, mid-sequence refills and
  zero-padded slots included (fixed slot bucket: within one bucket
  parity is structural);
- scheduling: early-exit slots are refilled from the queue
  MID-SEQUENCE and occupancy accounting is exact (the queue's bound,
  deadlines queued and mid-flight and both ways to close are the
  base's: tests/test_slot_scheduler_contract.py holds them for both
  kinds of slot);
- compile discipline: ``warm()`` precompiles one executable per slot
  bucket and a whole mixed-length serve pays ZERO further compiles
  (CompileWatch);
- the scheduler exposes the MicroBatcher's deterministic test seam:
  ManualClock + thread-less ``poll()``/``drain()``, zero sleeps.
"""

import threading

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.runtime import aot
from deeplearning4j_tpu.serving import (
    ManualClock, ModelHost, SequenceScheduler, greedy_onehot_feedback,
)


# ----------------------------------------------------------------------
# subjects
# ----------------------------------------------------------------------

def _rnn_net(seed=7):
    """LSTM + GRU + RnnOutputLayer — one carry of each shape."""
    from deeplearning4j_tpu.nn import (InputType, NeuralNetConfiguration,
                                       Nesterovs)
    from deeplearning4j_tpu.nn.conf.layers import RnnOutputLayer
    from deeplearning4j_tpu.nn.conf.recurrent import GRU, LSTM
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(Nesterovs(0.1, 0.9)).list()
            .layer(LSTM(nOut=8))
            .layer(GRU(nOut=8))
            .layer(RnnOutputLayer(nOut=5, activation="softmax",
                                  lossFunction="mcxent"))
            .setInputType(InputType.recurrent(4, 6)).build())
    return MultiLayerNetwork(conf).init()


def _char_net(seed=3, vocab=5):
    """vocab-in/vocab-out char-rnn shape (generation feedback tests)."""
    from deeplearning4j_tpu.nn import (InputType, NeuralNetConfiguration,
                                       Nesterovs)
    from deeplearning4j_tpu.nn.conf.layers import RnnOutputLayer
    from deeplearning4j_tpu.nn.conf.recurrent import LSTM
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(Nesterovs(0.1, 0.9)).list()
            .layer(LSTM(nOut=8))
            .layer(RnnOutputLayer(nOut=vocab, activation="softmax",
                                  lossFunction="mcxent"))
            .setInputType(InputType.recurrent(vocab, 6)).build())
    return MultiLayerNetwork(conf).init()


def _seqs(lens, seed=0, width=4):
    rng = np.random.RandomState(seed)
    return [rng.randn(t, width).astype(np.float32) for t in lens]


def _serial_oracle(net, seqs):
    """Per-sequence serial rnnTimeStep outputs (the bitwise bar)."""
    outs = []
    for s in seqs:
        net.rnnClearPreviousState()
        outs.append(np.concatenate(
            [np.asarray(net.rnnTimeStep(s[t:t + 1]).jax())
             for t in range(s.shape[0])], axis=0))
    net.rnnClearPreviousState()
    return outs


def _sched(net, **kw):
    kw.setdefault("slot_buckets", (4,))
    kw.setdefault("queue_limit", 32)
    clk = kw.pop("clock", None) or ManualClock()
    return SequenceScheduler(net, clock=clk, start_thread=False,
                             **kw), clk


@pytest.fixture
def fresh_cache():
    """Fresh MEMORY-ONLY session cache (hermetic miss counting)."""
    prev = aot._SESSION
    cache = aot._SESSION = aot.ExecutableCache()
    yield cache
    aot._SESSION = prev


# ----------------------------------------------------------------------
# the functional slot-batched step (nn/multilayer.py)
# ----------------------------------------------------------------------

class TestCarryAPI:
    def test_carry_spec_shapes(self):
        net = _rnn_net()
        assert net.rnnCarrySpec() == (("h", "c"), ("h",), ())
        zeros = net.rnnCarryZeros(3)
        assert sorted(zeros[0]) == ["c", "h"]
        assert zeros[0]["h"].shape == (3, 8)
        assert sorted(zeros[1]) == ["h"] and zeros[2] == {}

    def test_non_stepwise_nets_rejected_loudly(self):
        from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                           NeuralNetConfiguration,
                                           Nesterovs, OutputLayer)
        from deeplearning4j_tpu.nn.conf.layers import RnnOutputLayer
        from deeplearning4j_tpu.nn.conf.recurrent import (Bidirectional,
                                                          LSTM)
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        bidi = (NeuralNetConfiguration.Builder().seed(1)
                .updater(Nesterovs(0.1, 0.9)).list()
                .layer(Bidirectional(layer=LSTM(nOut=8)))
                .layer(RnnOutputLayer(nOut=4, activation="softmax",
                                      lossFunction="mcxent"))
                .setInputType(InputType.recurrent(4, 6)).build())
        with pytest.raises(ValueError, match="Bidirectional"):
            MultiLayerNetwork(bidi).init().rnnCarrySpec()

        ff = (NeuralNetConfiguration.Builder().seed(1)
              .updater(Nesterovs(0.1, 0.9)).list()
              .layer(DenseLayer(nOut=8, activation="relu"))
              .layer(OutputLayer(nOut=4, activation="softmax",
                                 lossFunction="mcxent"))
              .setInputType(InputType.feedForward(4)).build())
        with pytest.raises(ValueError, match="no recurrent layers"):
            MultiLayerNetwork(ff).init().rnnCarrySpec()
        with pytest.raises(ValueError, match="no recurrent layers"):
            SequenceScheduler(MultiLayerNetwork(ff).init())

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_step_batched_bitwise_vs_rnn_time_step(self):
        """One jitted slot-batched step == the eager stateful path,
        bitwise, carried state included — the foundation the whole
        scheduler's parity rests on."""
        net = _rnn_net()
        rng = np.random.RandomState(1)
        xs = rng.randn(3, 2, 4).astype(np.float32)  # [B=3, T=2, F]
        net.rnnClearPreviousState()
        want = [np.asarray(net.rnnTimeStep(xs[:, t]).jax())
                for t in range(2)]
        net.rnnClearPreviousState()
        carries = [jax.tree_util.tree_map(np.asarray, d)
                   for d in net.rnnCarryZeros(3)]
        for t in range(2):
            out, nc = net.rnnStepBatched(xs[:, t], carries)
            np.testing.assert_array_equal(np.asarray(out), want[t])
            carries = [{k: np.asarray(v) for k, v in d.items()}
                       for d in nc]


# ----------------------------------------------------------------------
# scheduler matrix: deterministic (ManualClock, no thread, no sleeps)
# ----------------------------------------------------------------------

class TestSchedulerDeterministic:
    @pytest.mark.slow  # tier-1 budget (PR 21): 7 s on 8 CPU cores
    def test_ragged_lengths_bitwise_and_occupancy(self):
        net = _rnn_net()
        lens = [5, 2, 7, 1, 3, 4]
        seqs = _seqs(lens, seed=0)
        oracle = _serial_oracle(net, seqs)
        sched, _ = _sched(net)
        reqs = [sched.submit(s, wait=False) for s in seqs]
        polls = 0
        while sched.poll():
            polls += 1
        for r, want in zip(reqs, oracle):
            assert r.done and r.error is None
            np.testing.assert_array_equal(r.result, want)
        st = sched.stats
        assert st["completed"] == len(seqs)
        # occupancy accounting is exact: the live-slot sum over all
        # dispatches is the total token count, and every bucket is 4
        assert st["slot_steps"] == sum(lens)
        assert sum(n for n, _ in sched.occupancy) == sum(lens)
        assert all(b == 4 for _, b in sched.occupancy)
        assert st["dispatches"] == polls == len(sched.occupancy)
        # 6 sequences through 4 slots: at least 2 admissions landed
        # while other sequences were mid-flight
        assert st["refills"] >= 2
        sched.close()

    def test_refill_mid_sequence_reuses_freed_slot(self):
        net = _rnn_net()
        seqs = _seqs([3, 1, 2], seed=1)
        oracle = _serial_oracle(net, seqs)
        sched, _ = _sched(net, slot_buckets=(2,))
        reqs = [sched.submit(s, wait=False) for s in seqs]
        assert sched.poll() == 2          # seqs 0,1 admitted; 1 done
        assert reqs[1].done and not reqs[0].done
        assert sched.active_slots == 1    # slot freed by early exit
        assert sched.poll() == 2          # seq 2 refilled MID-sequence
        assert sched.stats["refills"] == 1
        sched.drain()
        for r, want in zip(reqs, oracle):
            np.testing.assert_array_equal(r.result, want)
        sched.close()

    def test_submit_validation(self):
        net = _rnn_net()
        sched, _ = _sched(net)
        with pytest.raises(ValueError, match="feature width"):
            sched.submit(np.zeros((2, 3), np.float32), wait=False)
        with pytest.raises(ValueError, match="steps >= 1"):
            sched.submit(np.zeros((0, 4), np.float32), wait=False)
        with pytest.raises(ValueError, match="feedback"):
            sched.submit(np.zeros((2, 4), np.float32), wait=False,
                         extra_steps=3)
        sched.close()

    def test_dispatch_failure_fails_live_slots(self):
        net = _rnn_net()
        sched, _ = _sched(net)
        reqs = [sched.submit(s, wait=False) for s in _seqs([3, 2],
                                                           seed=10)]
        sched.poll()
        net_step, net._jit_rnn_step = net._jit_rnn_step, None  # break it
        try:
            assert sched.poll() == 0
        finally:
            net._jit_rnn_step = net_step
        for r in reqs:
            assert isinstance(r.error, TypeError)
            with pytest.raises(TypeError):
                r.wait(0)
        assert sched.stats["errors"] == 2
        sched.close()

    def test_generation_feedback_bitwise(self):
        """Closed-loop generation (prompt + extra_steps with greedy
        one-hot feedback) matches the serial rnnTimeStep + argmax loop
        bitwise."""
        net = _char_net()
        vocab = 5
        rng = np.random.RandomState(11)
        prompt = np.eye(vocab, dtype=np.float32)[
            rng.randint(0, vocab, 2)]
        extra = 3
        # serial oracle: stateful stepping with greedy re-feed
        net.rnnClearPreviousState()
        outs, x = [], prompt[0]
        for t in range(2 + extra):
            y = np.asarray(net.rnnTimeStep(x[None, :]).jax())[0]
            outs.append(y)
            x = prompt[t + 1] if t + 1 < 2 else \
                np.eye(vocab, dtype=np.float32)[int(np.argmax(y))]
        net.rnnClearPreviousState()
        sched, _ = _sched(net, feedback=greedy_onehot_feedback(vocab))
        req = sched.submit(prompt, wait=False, extra_steps=extra)
        sched.drain()
        assert req.result.shape == (2 + extra, vocab)
        np.testing.assert_array_equal(req.result, np.stack(outs))
        sched.close()

    def test_raising_feedback_fails_request_not_scheduler(self):
        """A feedback that raises (or returns a wrong-width row) fails
        ITS sequence and frees the slot; the other slots and later
        submits keep serving — user feedback bugs must never kill the
        scheduler (the wait contract: no caller blocked forever)."""
        net = _char_net()
        vocab = 5
        prompt = np.eye(vocab, dtype=np.float32)[[0, 1]]
        sched, _ = _sched(net)
        good = sched.submit(prompt, wait=False)
        boom = sched.submit(prompt, wait=False, extra_steps=2,
                            feedback=lambda row: 1 / 0)
        wide = sched.submit(prompt, wait=False, extra_steps=1,
                            feedback=lambda row: np.zeros(
                                vocab + 3, np.float32))
        sched.drain()
        assert good.result.shape == (2, vocab)
        with pytest.raises(ZeroDivisionError):
            boom.wait(0)
        with pytest.raises(ValueError, match="feedback row"):
            wide.wait(0)
        assert sched.active_slots == 0 and sched.depth == 0
        assert sched.stats["errors"] == 2
        # the scheduler still serves after the user-code failures
        again = sched.submit(prompt, wait=False)
        sched.drain()
        assert again.result.shape == (2, vocab)
        sched.close()


# ----------------------------------------------------------------------
# compile discipline
# ----------------------------------------------------------------------

class TestCompileDiscipline:
    def test_warm_then_zero_steady_state_compiles(self, fresh_cache):
        """warm() pays exactly one compile per slot bucket; a whole
        ragged mixed-length serve after it — refills, early exits,
        occupancy swings — pays ZERO (the CompileWatch gate the fleet
        soak and bench leg reuse)."""
        net = _rnn_net()
        sched, _ = _sched(net, slot_buckets=(2, 4))
        rep = sched.warm()
        assert {b: r["status"] for b, r in rep.items()} == \
            {2: "cold", 4: "cold"}
        assert fresh_cache.stats["misses"] == 2
        with aot.CompileWatch(fresh_cache) as watch:
            reqs = [sched.submit(s, wait=False)
                    for s in _seqs([5, 1, 3, 2, 4, 1, 2], seed=12)]
            sched.drain()
        assert all(r.done and r.error is None for r in reqs)
        watch.assert_no_compiles("mixed-length sequence serve")
        # warming again is free
        assert {b: r["status"] for b, r in sched.warm().items()} == \
            {2: "warm", 4: "warm"}
        sched.close()


# ----------------------------------------------------------------------
# host integration: sequence models behind ModelHost
# ----------------------------------------------------------------------

class TestHostSequenceModels:
    def test_register_submit_policy_snapshot(self, fresh_cache):
        host = ModelHost()
        try:
            net = _rnn_net()
            rep = host.register_sequence("charlstm", net,
                                         slotBuckets=(4,))
            assert rep["version"] == 1
            assert {b: r["status"] for b, r in rep["warm"].items()} \
                == {4: "cold"}
            pol = host.describe()["charlstm"]
            assert pol["kind"] == "sequence"
            assert pol["slotBuckets"] == [4]
            assert pol["featureSize"] == 4
            with pytest.raises(ValueError, match="swap_sequence"):
                host.register_sequence("charlstm", net)
            with pytest.raises(ValueError, match="registered"):
                host.register("charlstm", net)

            seq = _seqs([4], seed=14)[0]
            want = _serial_oracle(net, [seq])[0]
            got = host.submit_sequence("charlstm", seq)
            np.testing.assert_array_equal(np.asarray(got), want)

            snap = host.metrics_snapshot()
            # PR 13 schema intact, fleet view additive
            assert set(snap) == {"registry", "models", "sequences"}
            view = snap["sequences"]["charlstm"]
            assert view["version"] == 1
            assert view["stats"]["completed"] == 1
            assert view["queue_depth"] == 0
            assert view["active_slots"] == 0
            assert view["slot_occupancy"]["dispatches"] >= 4
            assert host.queued_work("charlstm") == 0
            assert host.queued_work("ghost") is None
            assert "charlstm" in host and "charlstm" in host.names()
        finally:
            host.close()

    def test_swap_sequence_zero_compiles_and_new_weights(self,
                                                         fresh_cache):
        host = ModelHost()
        try:
            net1 = _rnn_net()
            net2 = _rnn_net()   # identical conf -> identical cache keys
            net2._params = jax.tree_util.tree_map(lambda a: a * 1.5,
                                                  net2._params)
            seq = _seqs([3], seed=15)[0]
            want2 = _serial_oracle(net2, [seq])[0]
            host.register_sequence("m", net1, slotBuckets=(4,))
            host.submit_sequence("m", seq)
            with aot.CompileWatch(fresh_cache) as watch:
                rep = host.swap_sequence("m", net2)
                got = host.submit_sequence("m", seq)
            assert rep["version"] == 2
            assert {b: r["status"] for b, r in rep["warm"].items()} \
                == {4: "warm"}
            watch.assert_no_compiles("sequence rolling swap")
            np.testing.assert_array_equal(np.asarray(got), want2)
            with pytest.raises(KeyError, match="register_sequence"):
                host.swap_sequence("ghost", net2)
        finally:
            host.close()

    def test_register_sequence_warm_failure_closes_scheduler(
            self, fresh_cache, monkeypatch):
        """A failed warm() must not leak the half-built model: its
        scheduler thread is joined, its telemetry series released, and
        the name is immediately re-registrable."""
        from deeplearning4j_tpu.serving import host as host_mod

        net = _rnn_net()
        captured = {}

        def bad_warm(self, cache=None):
            captured["sm"] = self
            raise RuntimeError("warm kaboom")

        monkeypatch.setattr(host_mod.ServedSequenceModel, "warm",
                            bad_warm)
        host = ModelHost()
        try:
            with pytest.raises(RuntimeError, match="warm kaboom"):
                host.register_sequence("s", net, slotBuckets=(2,))
            sched = captured["sm"].scheduler
            assert sched._thread is None      # joined, not leaked
            monkeypatch.undo()
            host.register_sequence("s", net, slotBuckets=(2,))
            assert host.kind("s") == "sequence"
        finally:
            host.close()

    def test_http_generate_route(self, fresh_cache):
        import json
        import urllib.error
        import urllib.request

        from deeplearning4j_tpu.serving import InferenceServer

        host = ModelHost()
        net = _rnn_net()
        host.register_sequence("charlstm", net, slotBuckets=(4,))
        srv = InferenceServer(host).start(port=0)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            seq = _seqs([3], seed=16)[0]
            want = _serial_oracle(net, [seq])[0]

            def post(url, obj):
                req = urllib.request.Request(
                    url, data=json.dumps(obj).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, json.loads(r.read().decode())

            status, body = post(base + "/v1/models/charlstm:generate",
                                {"steps": seq.tolist()})
            assert status == 200 and body["steps"] == 3
            np.testing.assert_array_equal(
                np.asarray(body["outputs"], np.float32), want)
            # policy table carries the sequence row
            with urllib.request.urlopen(base + "/v1/models",
                                        timeout=10) as r:
                table = json.loads(r.read().decode())["models"]
            assert table["charlstm"]["kind"] == "sequence"
            for url, obj, code in [
                    (base + "/v1/models/ghost:generate",
                     {"steps": seq.tolist()}, 404),
                    (base + "/v1/models/charlstm:generate", {}, 400),
                    (base + "/v1/models/charlstm:generate",
                     {"steps": np.zeros((2, 3)).tolist()}, 400)]:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    post(url, obj)
                assert ei.value.code == code, url
        finally:
            srv.stop(close_host=True)

    @pytest.mark.slow  # tier-1 budget (PR 21): 5 s on 8 CPU cores
    def test_threaded_scheduler_serves_blocking_submits(self,
                                                        fresh_cache):
        """clock=None -> the background iteration loop serves blocking
        submit() callers from handler threads (the production mode)."""
        net = _rnn_net()
        host = ModelHost()
        host.register_sequence("m", net, slotBuckets=(4,))
        seqs = _seqs([3, 5, 2, 4], seed=17)
        oracle = _serial_oracle(net, seqs)
        got = [None] * len(seqs)

        def client(i):
            got[i] = np.asarray(
                host.submit_sequence("m", seqs[i], deadline_s=30.0))

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(len(seqs))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        try:
            for g, want in zip(got, oracle):
                assert g is not None
                np.testing.assert_array_equal(g, want)
        finally:
            host.close()


# ----------------------------------------------------------------------
# non-f32 dtype policies (docs/SERVING.md: the bf16 1-ulp note)
# ----------------------------------------------------------------------

class TestNonF32Policies:

    @staticmethod
    def _bf16_net(seed=7):
        from deeplearning4j_tpu.ndarray.dtype import DataType
        from deeplearning4j_tpu.nn import (InputType,
                                           NeuralNetConfiguration,
                                           Nesterovs)
        from deeplearning4j_tpu.nn.conf.layers import RnnOutputLayer
        from deeplearning4j_tpu.nn.conf.recurrent import GRU, LSTM
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = (NeuralNetConfiguration.Builder().seed(seed)
                .updater(Nesterovs(0.1, 0.9))
                .dataType(DataType.BFLOAT16).list()
                .layer(LSTM(nOut=8))
                .layer(GRU(nOut=8))
                .layer(RnnOutputLayer(nOut=5, activation="softmax",
                                      lossFunction="mcxent"))
                .setInputType(InputType.recurrent(4, 6)).build())
        return MultiLayerNetwork(conf).init()

    @pytest.mark.slow  # tier-1 budget (PR 21): 8 s on 8 CPU cores
    def test_bf16_carries_live_in_compute_dtype(self, fresh_cache):
        """Regression: the slot table hardcoded float32 carries, so a
        bf16 model's cell math ran f32-promoted — every step diverged
        from what the model computes. Carries must live in the compute
        dtype; the batched trajectory is then BITWISE the jitted
        functional drive (same bucket, zero-padded), and within 1 bf16
        ulp of the eager serial rnnTimeStep (XLA fusion moves the
        narrow-dtype roundings — the documented limit)."""
        import jax.numpy as jnp

        net = self._bf16_net()
        bf16 = np.dtype(jnp.bfloat16)
        sched, clk = _sched(net)
        assert np.dtype(sched._carry_dtype) == bf16

        seqs = _seqs([3, 6, 4], seed=1)
        reqs = [sched.submit(s, wait=False) for s in seqs]
        sched.drain()
        got = [np.asarray(r.wait(5)) for r in reqs]
        assert all(g.dtype == bf16 for g in got)

        for s, g in zip(seqs, got):
            # deterministic reference: solo zero-padded functional
            # drive through the SAME bucket-4 executable
            S = sched.max_slots
            carry = [{k: np.zeros((S, 8), bf16) for k in keys}
                     for keys in net.rnnCarrySpec()]
            ref = []
            for st in s:
                x = np.zeros((S, s.shape[1]), np.float32)
                x[0] = st
                y, nc = net.rnnStepBatched(x, carry)
                ref.append(np.array(np.asarray(y))[0])
                carry = []
                for d in nc:
                    col = {k: np.array(np.asarray(v), copy=True)
                           for k, v in d.items()}
                    for k in col:
                        col[k][1:] = 0   # free slots re-zeroed, like _gather
                    carry.append(col)
            np.testing.assert_array_equal(np.stack(ref), g)
            # eager serial reference: 1-ulp band, not bitwise
            net.rnnClearPreviousState()
            serial = np.stack(
                [np.array(np.asarray(net.rnnTimeStep(st[None, :, None])))[0, :, 0]
                 for st in s])
            np.testing.assert_allclose(
                serial.astype(np.float32), g.astype(np.float32),
                atol=2 * 2.0 ** -9, rtol=0)
        net.rnnClearPreviousState()
