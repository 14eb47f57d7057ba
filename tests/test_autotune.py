"""Runtime autotuning arbiter (runtime/autotune.py, docs/AUTOTUNE.md).

Layers of proof, cheapest first:

- registry plumbing: knob get/set/restore, registry <-> AOT ambient
  fingerprint sync (a knob the key cannot see would let a tuned and a
  stock run share an executable), key independence from the current
  knob values;
- store: JSON round trip through a real directory, stale-format and
  corrupt-file recovery, memory-tier reuse;
- the sweep on a TINY conv+pool subject (sub-second compiles): finds
  the indices pool backward on CPU, proves parity, persists — and a
  second-process call (fresh store instance on the same directory)
  recalls the winners with ZERO compiles (aot.CompileWatch gate) and
  zero re-sweeps;
- kernel routing compile-neutrality: a BN+pool network under the fused
  epilogue and tuned pooling still compiles its train step EXACTLY
  once across a multi-step fit (RetraceSentinel);
- the full LeNet-b64 sweep reproducing the banked winner table is
  marked slow (the pinned expectation rides the tier-1 tuned gate in
  test_hbm_attribution instead).
"""

import numpy as np
import pytest

from deeplearning4j_tpu.runtime import aot
from deeplearning4j_tpu.runtime import autotune as at


def _tiny_pool_net(seed=3):
    """conv -> maxpool -> dense-10: the smallest subject whose train
    step the maxpool_bwd knob can rewrite (sub-second XLA compile)."""
    from deeplearning4j_tpu.nn import (ConvolutionLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, Nesterovs,
                                       OutputLayer, SubsamplingLayer)

    conf = (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Nesterovs(0.1, 0.9))
            .activation("relu").list()
            .layer(ConvolutionLayer(nOut=4, kernelSize=(3, 3)))
            .layer(SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                    stride=(2, 2)))
            .layer(OutputLayer(nOut=10, activation="softmax",
                               lossFunction="mcxent"))
            .setInputType(InputType.convolutional(10, 10, 1))
            .build())
    return MultiLayerNetwork(conf).init(), (8, 1, 10, 10)


class TestKnobRegistry:
    def test_registry_matches_ambient_fingerprint(self):
        """Every registered knob must appear in the AOT ambient
        fingerprint under its own name — otherwise installing a tuned
        config could reuse a stock executable (the satellite-fix
        contract; the key-separation direction is gated in
        test_aot_cache)."""
        amb = aot.ambient_fingerprint()
        for knob in at.KNOBS:
            assert knob.name in amb, (
                f"knob {knob.name} missing from aot.ambient_fingerprint"
                " — tuned and stock runs could share an executable")
            assert amb[knob.name] == knob.get()

    def test_get_set_restore(self):
        knob = at._KNOBS_BY_NAME["maxpool_bwd"]
        old = knob.get()
        try:
            prev = knob.set("indices")
            assert prev == old
            assert knob.get() == "indices"
        finally:
            knob.set(old)
        with pytest.raises(ValueError, match="not in"):
            knob.set("definitely-not-an-impl")

    def test_applied_context_restores_on_exception(self):
        before = at.current_knobs()
        with pytest.raises(RuntimeError):
            with at.applied({"maxpool_bwd": "indices",
                             "bn_epilogue": "unfused"}):
                assert at.current_knobs()["maxpool_bwd"] == "indices"
                raise RuntimeError("boom")
        assert at.current_knobs() == before

    def test_install_returns_previous(self):
        before = at.current_knobs()
        old = at.install({"maxpool_bwd": "argmax"})
        try:
            assert old == {"maxpool_bwd": before["maxpool_bwd"]}
            assert at.current_knobs()["maxpool_bwd"] == "argmax"
        finally:
            at.install(old)
        assert at.current_knobs() == before

    def test_unknown_knob_rejected(self):
        net, x_shape = _tiny_pool_net()
        with pytest.raises(ValueError, match="unknown knob"):
            at.autotune(net, x_shape, knobs=["no_such_knob"],
                        store_=at.TuningStore())


class TestKey:
    def test_key_independent_of_current_knob_values(self):
        """The tuned process must look up the SAME record it wrote when
        stock — knob values are the tuning's output, not its key."""
        net, _ = _tiny_pool_net()
        k0 = at.tuning_key(net)
        with at.applied({"maxpool_bwd": "indices",
                         "bn_epilogue": "unfused",
                         "loss_tail": "wide"}):
            assert at.tuning_key(net) == k0

    def test_key_depends_on_program(self):
        net_a, _ = _tiny_pool_net(seed=3)
        net_b, _ = _tiny_pool_net(seed=4)  # different conf JSON
        assert at.tuning_key(net_a) != at.tuning_key(net_b)


class TestStore:
    def test_disk_round_trip_and_second_instance(self, tmp_path):
        st = at.TuningStore(str(tmp_path))
        rec = {"knobs": {"maxpool_bwd": "indices"}, "tuned_bytes": 42}
        st.put("k" * 64, rec)
        # fresh instance on the same dir = the second-process path
        st2 = at.TuningStore(str(tmp_path))
        got = st2.get("k" * 64)
        assert got["knobs"] == {"maxpool_bwd": "indices"}
        assert st2.stats["hits"] == 1

    def test_stale_format_removed(self, tmp_path):
        st = at.TuningStore(str(tmp_path))
        st.put("s" * 64, {"knobs": {}})
        path = st._path("s" * 64)
        import json

        with open(path, "r", encoding="utf-8") as fh:
            rec = json.load(fh)
        rec["tune_format"] = -1
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(rec, fh)
        st2 = at.TuningStore(str(tmp_path))
        assert st2.get("s" * 64) is None
        assert st2.stats["stale"] == 1
        assert not path or not __import__("os").path.exists(path)

    def test_corrupt_file_is_a_miss(self, tmp_path):
        st = at.TuningStore(str(tmp_path))
        with open(st._path("c" * 64), "w") as fh:
            fh.write("{not json")
        assert st.get("c" * 64) is None
        assert st.stats["corrupt"] == 1


class TestParity:
    def test_bitwise_and_tolerance_bands(self):
        ok = at._parity_ok
        assert ok([1.0, 0.5], [1.0, 0.5], 0.0)
        assert not ok([1.0, 0.5], [1.0, 0.5000001], 0.0)
        assert ok([1.0, 0.5], [1.001, 0.5005], 0.05)
        assert not ok([1.0, 0.5], [1.2, 0.5], 0.05)
        assert not ok([1.0, 0.5], [float("nan"), 0.5], 0.05)


class TestSweep:
    def test_tiny_sweep_finds_indices_and_persists(self, tmp_path):
        """The heart of ISSUE 12's acceptance, at tier-1 cost: the
        sweep adopts the indices pool backward on CPU (fewer attributed
        bytes, bitwise parity), persists the record, leaves the process
        knobs untouched — and the second-process call recalls it with
        ZERO compiles and zero re-sweeps."""
        net, x_shape = _tiny_pool_net()
        st = at.TuningStore(str(tmp_path))
        before = at.current_knobs()
        res = at.autotune(net, x_shape, knobs=["maxpool_bwd"],
                          store_=st, steps=2)
        assert res.swept
        assert at.current_knobs() == before  # sweep leaves no trace
        assert res.knobs["maxpool_bwd"] == "indices"
        assert res.tuned_bytes < res.baseline_bytes * 0.9
        adopted = [p for p in res.per_knob if p["verdict"] == "adopted"]
        assert [p["to"] for p in adopted] == ["indices"]

        # second process: fresh store instance on the same directory,
        # fresh AOT watch — the recall must compile NOTHING
        st2 = at.TuningStore(str(tmp_path))
        cache = aot.session_cache() or aot.enable()
        with aot.CompileWatch(cache) as watch:
            res2 = at.autotune(net, x_shape, knobs=["maxpool_bwd"],
                               store_=st2, steps=2)
        watch.assert_no_compiles("second-process autotune recall")
        assert not res2.swept
        assert res2.knobs == res.knobs
        assert res2.tuned_bytes == res.tuned_bytes

    def test_sweep_on_previously_fit_net_still_sees_knobs(self,
                                                          tmp_path):
        """Latent-bug regression (caught while verifying round 12):
        jax's global trace cache keys on bound-method equality, so
        after net.fit() a naive jax.jit(net._train_step).lower() serves
        the STALE pre-flip jaxpr and every candidate reads 'identical'.
        lower_train_step wraps the step in a fresh-identity lambda —
        a sweep on a trained net must still adopt the indices win."""
        import jax.numpy as jnp

        net, x_shape = _tiny_pool_net(seed=11)
        rng = np.random.RandomState(0)
        x = rng.rand(x_shape[0], *x_shape[1:]).astype("float32")
        y = np.eye(10, dtype="float32")[
            rng.randint(0, 10, x_shape[0])]
        for _ in range(2):
            net.fit(x, y)
        st = at.TuningStore(str(tmp_path))
        res = at.autotune(net, x_shape, knobs=["maxpool_bwd"],
                          store_=st, steps=2)
        assert res.knobs["maxpool_bwd"] == "indices"
        assert res.tuned_bytes < res.baseline_bytes * 0.9

    def test_force_resweeps(self, tmp_path):
        net, x_shape = _tiny_pool_net()
        st = at.TuningStore(str(tmp_path))
        at.autotune(net, x_shape, knobs=["maxpool_bwd"], store_=st,
                    steps=2)
        res = at.autotune(net, x_shape, knobs=["maxpool_bwd"],
                          store_=st, steps=2, force=True)
        assert res.swept

    def test_identical_hlo_candidates_skip_compiles(self, tmp_path):
        """A knob that cannot touch this program (flash_bwd on an
        attention-free CNN) must be detected by the HLO hash and cost
        zero compiles/parity runs."""
        net, x_shape = _tiny_pool_net(seed=5)
        st = at.TuningStore(str(tmp_path))
        # bn_tail is also a no-op here: an f32 net's wide/compute
        # tails lower identically (wide_tail is already true for f32)
        res = at.autotune(net, x_shape,
                          knobs=["flash_bwd", "bn_tail"],
                          store_=st, steps=2)
        verdicts = {p["knob"]: p["verdict"] for p in res.per_knob}
        assert verdicts == {"flash_bwd": "identical",
                            "bn_tail": "identical"}
        assert res.knobs["flash_bwd"] == "kernel"  # default kept

    def test_warm_start_installs_winners(self, tmp_path):
        net, x_shape = _tiny_pool_net()
        st = at.TuningStore(str(tmp_path))
        assert at.warm_start(net, store_=st) is None  # no record yet
        at.autotune(net, x_shape, knobs=["maxpool_bwd"], store_=st,
                    steps=2)
        before = at.current_knobs()
        try:
            installed = at.warm_start(net, store_=st)
            assert installed["maxpool_bwd"] == "indices"
            assert at.current_knobs()["maxpool_bwd"] == "indices"
        finally:
            at.install(before)

    def test_precompile_autotune_kwarg(self, tmp_path):
        """net.precompile(autotune=True) warms the TUNED program: the
        persisted knobs are installed before the executables warm."""
        net, x_shape = _tiny_pool_net()
        st = at.TuningStore(str(tmp_path))
        at.autotune(net, x_shape, knobs=["maxpool_bwd"], store_=st,
                    steps=2)
        before = at.current_knobs()
        prev_store = at._STORE
        at._STORE = st
        try:
            net.precompile(batchSize=x_shape[0], entries=("train",),
                           autotune=True)
            assert at.current_knobs()["maxpool_bwd"] == "indices"
        finally:
            at._STORE = prev_store
            at.install(before)


class TestKernelRoutingCompileNeutral:
    def test_single_compile_with_tuned_kernels(self):
        """RetraceSentinel proof (ISSUE 12 satellite): routing through
        the fused BN epilogue + indices pool backward adds ZERO extra
        compiles — a multi-step fit traces the train step exactly
        once, same as stock."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.analysis.retrace import RetraceSentinel
        from deeplearning4j_tpu.nn import (BatchNormalization,
                                           ConvolutionLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           Nesterovs, OutputLayer,
                                           SubsamplingLayer)

        with at.applied({"maxpool_bwd": "indices",
                         "bn_epilogue": "fused",
                         "global_maxpool_bwd": "indices"}):
            conf = (NeuralNetConfiguration.Builder()
                    .seed(9).updater(Nesterovs(0.1, 0.9))
                    .activation("relu").list()
                    .layer(ConvolutionLayer(nOut=4, kernelSize=(3, 3)))
                    .layer(BatchNormalization())
                    .layer(SubsamplingLayer(poolingType="max",
                                            kernelSize=(2, 2),
                                            stride=(2, 2)))
                    .layer(OutputLayer(nOut=5, activation="softmax",
                                       lossFunction="mcxent"))
                    .setInputType(InputType.convolutional(10, 10, 1))
                    .build())
            net = MultiLayerNetwork(conf).init()
            sentinel = RetraceSentinel(max_compiles=1)
            sentinel.install(net)
            rng = np.random.RandomState(0)
            x = rng.rand(8, 1, 10, 10).astype("float32")
            y = np.eye(5, dtype="float32")[rng.randint(0, 5, 8)]
            for _ in range(3):
                net.fit(x, y)
            assert sentinel.compiles("train_step") == 1


class TestBnEpilogue:
    """Fused BN -> activation (-> add) epilogue (ops/norm.py): parity
    against the stock composition, train + inference, every supported
    activation, plus the layer routing and the relu-bitwise contract."""

    def _data(self, seed=0, shape=(8, 6, 6, 5)):
        import jax.numpy as jnp

        rng = np.random.RandomState(seed)
        C = shape[-1]
        return (jnp.asarray(rng.randn(*shape).astype("float32")),
                jnp.asarray(rng.rand(C).astype("float32") + 0.5),
                jnp.asarray(rng.randn(C).astype("float32")),
                jnp.asarray(rng.randn(C).astype("float32")),
                jnp.asarray(rng.rand(C).astype("float32") + 0.5))

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    @pytest.mark.parametrize(
        "act", ["identity", "relu", "leakyrelu", "tanh", "sigmoid"])
    def test_train_fwd_bwd_parity(self, act):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn import activations as _act
        from deeplearning4j_tpu.ops import norm as N

        x, gm, bt, rm, rv = self._data()

        def f_fused(x, gm, bt):
            o, _rm, _rv = N.batch_norm_act(x, gm, bt, rm, rv,
                                           train=True, activation=act)
            return jnp.sum(o ** 2)

        def f_ref(x, gm, bt):
            y, _rm, _rv = N.batch_norm(x, gm, bt, rm, rv, train=True)
            return jnp.sum(_act.get(act)(y) ** 2)

        np.testing.assert_allclose(float(f_fused(x, gm, bt)),
                                   float(f_ref(x, gm, bt)), rtol=1e-6)
        gf = jax.grad(f_fused, argnums=(0, 1, 2))(x, gm, bt)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, gm, bt)
        # relu/leakyrelu/identity masks are exact functions of the
        # output sign — bitwise; tanh/sigmoid grad-from-output is
        # ulp-level vs autodiff-through-input
        exact = act in ("identity", "relu", "leakyrelu")
        for a, b in zip(gf, gr):
            if exact:
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
            else:
                np.testing.assert_allclose(np.asarray(a),
                                           np.asarray(b),
                                           rtol=2e-5, atol=2e-5)

    def test_relu_kink_subgradient_matches_registry(self):
        """The dead-channel regression (caught in round 12): an
        all-zero input channel with beta == 0 puts every element at
        the relu kink (y == 0 exactly). The epilogue must reproduce
        jax.nn.relu's grad(0) == 0 — dbeta for that channel is 0, not
        jnp.maximum's half-cotangent."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.ops import norm as N

        x = jnp.zeros((6, 2), jnp.float32).at[:, 1].set(jnp.asarray(
            np.random.RandomState(0).randn(6).astype("float32")))
        gm = jnp.ones(2, jnp.float32)
        bt = jnp.zeros(2, jnp.float32)  # channel 0 lands AT the kink
        w = jnp.asarray(np.random.RandomState(1).randn(6, 2)
                        .astype("float32"))

        def f_fused(bt):
            o, _m, _v = N._bn_act_train(x, gm, bt, 1e-5, "relu")
            return jnp.sum(w * o)

        def f_legacy(bt):
            y, _m, _v = N._bn_train(x, gm, bt, 1e-5)
            return jnp.sum(w * jax.nn.relu(y))

        gf = jax.grad(f_fused)(bt)
        gl = jax.grad(f_legacy)(bt)
        np.testing.assert_array_equal(np.asarray(gf), np.asarray(gl))
        assert float(gf[0]) == 0.0  # the kink channel: zero, not half

    def test_running_stats_match_stock(self):
        from deeplearning4j_tpu.ops import norm as N

        x, gm, bt, rm, rv = self._data(seed=1)
        _o, rm_f, rv_f = N.batch_norm_act(x, gm, bt, rm, rv, train=True,
                                          activation="relu")
        _y, rm_s, rv_s = N.batch_norm(x, gm, bt, rm, rv, train=True)
        np.testing.assert_array_equal(np.asarray(rm_f), np.asarray(rm_s))
        np.testing.assert_array_equal(np.asarray(rv_f), np.asarray(rv_s))

    def test_inference_parity(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn import activations as _act
        from deeplearning4j_tpu.ops import norm as N

        x, gm, bt, rm, rv = self._data(seed=2)
        o, _m, _v = N.batch_norm_act(x, gm, bt, rm, rv, train=False,
                                     activation="sigmoid")
        y, _m2, _v2 = N.batch_norm(x, gm, bt, rm, rv, train=False)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_act.get("sigmoid")(y)),
            rtol=1e-6, atol=1e-7)

    def test_residual_add_fused(self):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.ops import norm as N

        x, gm, bt, rm, rv = self._data(seed=3)
        res = jnp.asarray(np.random.RandomState(9).randn(
            *x.shape).astype("float32"))

        def f_fused(x, res):
            o, _m, _v = N.batch_norm_act(x, gm, bt, rm, rv, train=True,
                                         activation="relu",
                                         residual=res)
            return jnp.sum(o ** 2)

        def f_ref(x, res):
            y, _m, _v = N.batch_norm(x, gm, bt, rm, rv, train=True)
            return jnp.sum(jnp.maximum(y + res, 0) ** 2)

        gf = jax.grad(f_fused, argnums=(0, 1))(x, res)
        gr = jax.grad(f_ref, argnums=(0, 1))(x, res)
        for a, b in zip(gf, gr):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_unsupported_activation_raises_op_level(self):
        from deeplearning4j_tpu.ops import norm as N

        x, gm, bt, rm, rv = self._data(seed=4)
        with pytest.raises(ValueError, match="not epilogue-fusable"):
            N.batch_norm_act(x, gm, bt, rm, rv, train=True,
                             activation="swish")
        assert not N.bn_act_supported("swish")
        assert N.bn_act_supported("relu")

    def test_unfused_knob_is_stock_composition(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn import activations as _act
        from deeplearning4j_tpu.ops import norm as N

        x, gm, bt, rm, rv = self._data(seed=5)
        with at.applied({"bn_epilogue": "unfused"}):
            o, _m, _v = N.batch_norm_act(x, gm, bt, rm, rv, train=True,
                                         activation="relu")
        y, _m2, _v2 = N.batch_norm(x, gm, bt, rm, rv, train=True)
        np.testing.assert_array_equal(
            np.asarray(o), np.asarray(_act.get("relu")(y)))

    def test_bn_layer_trains_bitwise_fused_vs_unfused(self):
        """Network-level: a conv+BN(relu) net walks the BITWISE same
        trajectory under both epilogue modes — including the relu-kink
        subgradient at a dead conv channel (all-zero BN input + zero
        beta puts the WHOLE channel at y == 0 exactly at init; the
        epilogue must reproduce jax.nn.relu's grad(0) == 0 convention,
        which the out>0 strict mask does — the bug this test caught
        during round 12: jnp.maximum's half-gradient at the kink)."""
        import jax

        from deeplearning4j_tpu.nn import (BatchNormalization,
                                           ConvolutionLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           Nesterovs, OutputLayer)

        def run(mode):
            with at.applied({"bn_epilogue": mode}):
                conf = (NeuralNetConfiguration.Builder()
                        .seed(17).updater(Nesterovs(0.1, 0.9))
                        .activation("relu").list()
                        .layer(ConvolutionLayer(nOut=4,
                                                kernelSize=(3, 3)))
                        .layer(BatchNormalization())
                        .layer(OutputLayer(nOut=5, activation="softmax",
                                           lossFunction="mcxent"))
                        .setInputType(
                            InputType.convolutional(8, 8, 1))
                        .build())
                net = MultiLayerNetwork(conf).init()
                rng = np.random.RandomState(1)
                x = rng.rand(8, 1, 8, 8).astype("float32")
                y = np.eye(5, dtype="float32")[rng.randint(0, 5, 8)]
                for _ in range(3):
                    net.fit(x, y)
                return net

        net_f, net_u = run("fused"), run("unfused")
        for a, b in zip(jax.tree_util.tree_leaves(net_f._params),
                        jax.tree_util.tree_leaves(net_u._params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(net_f._states),
                        jax.tree_util.tree_leaves(net_u._states)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rnn_bn_layer_parity(self):
        """The [B,F,T] recurrent BN path (transpose -> BN -> transpose)
        routes through the epilogue too — parity with unfused."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.conf.layers import BatchNormalization

        layer = BatchNormalization()
        layer.activation = "relu"
        layer.nOut = layer.nIn = 4
        import jax

        params, state = layer.initialize(jax.random.key(0),
                                         _FakeRnnInput(4), jnp.float32)
        x = jnp.asarray(np.random.RandomState(2).randn(
            3, 4, 6).astype("float32"))
        y_f, st_f = layer.forward(params, state, x, True, None)
        with at.applied({"bn_epilogue": "unfused"}):
            y_u, st_u = layer.forward(params, state, x, True, None)
        np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_u))
        for k in ("mean", "var"):
            np.testing.assert_array_equal(np.asarray(st_f[k]),
                                          np.asarray(st_u[k]))


class _FakeRnnInput:
    """Minimal InputType stand-in for layer.initialize (RNN kind)."""

    def __init__(self, size):
        from deeplearning4j_tpu.nn.conf.inputs import InputType

        self.kind = InputType.RNN
        self.size = size


@pytest.mark.slow
class TestFullLeNetSweep:
    def test_lenet_sweep_finds_indices(self, tmp_path):
        """The banked winner table (BENCH autotune leg / the tier-1
        tuned-ceiling gate's pinned knobs): a full-registry sweep of
        the LeNet b64 attribution subject adopts maxpool_bwd=indices
        and nothing else on XLA:CPU, cutting attributed bytes >= 40%."""
        st = at.TuningStore(str(tmp_path))
        res = at.autotune_subject("lenet", store_=st)
        assert res.knobs["maxpool_bwd"] == "indices"
        changed = {p["knob"] for p in res.per_knob
                   if p["verdict"] == "adopted"}
        assert changed == {"maxpool_bwd"}
        assert res.tuned_bytes <= res.baseline_bytes * 0.6
