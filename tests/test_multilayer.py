"""MultiLayerNetwork tests.

Mirrors the reference's deeplearning4j-core test strategy:
MultiLayerTest (build/fit/output/score), GradientCheckTests
(finite-difference vs backprop), convergence smoke tests, and
evaluation integration.
"""

import numpy as np
import jax
import pytest

from deeplearning4j_tpu.ndarray import DataType
from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, InputType, MultiLayerNetwork,
    DenseLayer, OutputLayer, RnnOutputLayer, ConvolutionLayer, SubsamplingLayer,
    BatchNormalization, GlobalPoolingLayer, DropoutLayer, ActivationLayer,
    EmbeddingLayer, LSTM, GravesLSTM, SimpleRnn, Bidirectional, LastTimeStep,
    Adam, Sgd, Nesterovs, RmsProp, AdaGrad,
    WeightInit, BackpropType, GradientNormalization,
)
from deeplearning4j_tpu.data import DataSet, DataSetIterator


def _separable_data(n=128, nin=4, nout=3, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, nin).astype("float32")
    w = rng.randn(nin, nout)
    yidx = np.argmax(x @ w, axis=1)
    return x, np.eye(nout, dtype="float32")[yidx], yidx


def _mlp(updater=None, seed=42, **kw):
    return (NeuralNetConfiguration.Builder()
            .seed(seed)
            .updater(updater or Adam(1e-2))
            .weightInit(WeightInit.XAVIER)
            .activation("relu")
            .list()
            .layer(DenseLayer(nOut=16))
            .layer(OutputLayer(nOut=3, activation="softmax", lossFunction="mcxent"))
            .setInputType(InputType.feedForward(4))
            .build())


class TestBuild:
    def test_nin_inference(self):
        conf = _mlp()
        net = MultiLayerNetwork(conf).init()
        assert conf.layers[0].nIn == 4
        assert conf.layers[1].nIn == 16
        assert net.numParams() == 4 * 16 + 16 + 16 * 3 + 3

    def test_explicit_nin(self):
        conf = (NeuralNetConfiguration.Builder().updater(Sgd(0.1)).list()
                .layer(DenseLayer(nIn=5, nOut=7))
                .layer(OutputLayer(nIn=7, nOut=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        assert net.numParams() == 5 * 7 + 7 + 7 * 2 + 2

    def test_builder_fluent_parity(self):
        # Java-style Layer.Builder() chains work too
        layer = DenseLayer.Builder().nIn(3).nOut(4).activation("tanh").build()
        assert layer.nIn == 3 and layer.nOut == 4 and layer.activation == "tanh"

    def test_missing_input_type_raises(self):
        with pytest.raises(ValueError):
            (NeuralNetConfiguration.Builder().list()
             .layer(DenseLayer(nOut=4))
             .layer(OutputLayer(nOut=2))
             .build())

    def test_summary(self):
        net = MultiLayerNetwork(_mlp()).init()
        s = net.summary()
        assert "DenseLayer" in s and "Total params" in s


class TestFit:
    def test_mlp_converges(self):
        x, y, yidx = _separable_data()
        net = MultiLayerNetwork(_mlp()).init()
        it = DataSetIterator(x, y, 64, shuffle=True)
        first = None
        for _ in range(30):
            net.fit(it)
            first = first if first is not None else net.score()
        assert net.score() < 0.5 * first
        acc = (net.output(x).argMax(1).toNumpy() == yidx).mean()
        assert acc > 0.9

    def test_fit_xy_direct(self):
        x, y, _ = _separable_data()
        net = MultiLayerNetwork(_mlp()).init()
        s0 = None
        for _ in range(20):
            net.fit(x, y)
            s0 = s0 if s0 is not None else net.score()
        assert net.score() < s0

    def test_fit_dataset(self):
        x, y, _ = _separable_data()
        net = MultiLayerNetwork(_mlp()).init()
        net.fit(DataSet(x, y))
        assert np.isfinite(net.score())

    @pytest.mark.parametrize("upd", [Sgd(0.05), Nesterovs(0.05, 0.9),
                                     RmsProp(0.01), AdaGrad(0.05), Adam(1e-2)])
    def test_updaters_reduce_loss(self, upd):
        x, y, _ = _separable_data()
        net = MultiLayerNetwork(_mlp(updater=upd)).init()
        losses = []
        for _ in range(15):
            net.fit(x, y)
            losses.append(net.score())
        assert losses[-1] < losses[0]

    def test_seed_reproducibility(self):
        x, y, _ = _separable_data()
        nets = []
        for _ in range(2):
            net = MultiLayerNetwork(_mlp(seed=99)).init()
            for _ in range(3):
                net.fit(x, y)
            nets.append(net.params().toNumpy())
        np.testing.assert_array_equal(nets[0], nets[1])

    def test_final_partial_batch_padded(self):
        x, y, _ = _separable_data(n=100)  # 100 % 64 != 0
        net = MultiLayerNetwork(_mlp()).init()
        it = DataSetIterator(x, y, 64)
        net.fit(it)  # should not crash or retrace on a ragged batch
        assert np.isfinite(net.score())


class TestFitSteps:
    """fitSteps(k) — the TPU-native on-device k-step loop — must be
    bit-for-bit the same trajectory as k consecutive fit() calls on the
    same batch (same RNG stream, same iteration counters)."""

    def test_matches_k_fit_calls(self):
        x, y, _ = _separable_data()
        a = MultiLayerNetwork(_mlp(seed=7)).init()
        b = MultiLayerNetwork(_mlp(seed=7)).init()
        for _ in range(5):
            a.fit(x, y)
        b.fitSteps(x, y, numSteps=5)
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(), rtol=2e-6, atol=2e-6)
        assert abs(a.score() - b.score()) < 1e-5
        assert a._iteration == b._iteration == 5

    def test_matches_with_dropout_rng_stream(self):
        """Dropout keys advance per inner step exactly as fit()'s."""
        def conf():
            return (NeuralNetConfiguration.Builder().seed(3)
                    .updater(Sgd(0.05)).weightInit(WeightInit.XAVIER)
                    .activation("relu").list()
                    .layer(DenseLayer(nOut=16, dropOut=0.7))
                    .layer(OutputLayer(nOut=3, activation="softmax",
                                       lossFunction="mcxent"))
                    .setInputType(InputType.feedForward(4)).build())
        x, y, _ = _separable_data()
        a = MultiLayerNetwork(conf()).init()
        b = MultiLayerNetwork(conf()).init()
        for _ in range(4):
            a.fit(x, y)
        b.fitSteps(x, y, numSteps=4)
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(), rtol=2e-6, atol=2e-6)

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_tbptt_window_sweep(self):
        V, B, T, L = 5, 4, 8, 4

        def conf():
            return (NeuralNetConfiguration.Builder().seed(11)
                    .updater(Adam(5e-3)).list()
                    .layer(GravesLSTM(nOut=8))
                    .layer(RnnOutputLayer(nOut=V, activation="softmax",
                                          lossFunction="mcxent"))
                    .setInputType(InputType.recurrent(V, T))
                    .backpropType(BackpropType.TruncatedBPTT)
                    .tBPTTLength(L).build())

        rng = np.random.RandomState(0)
        ids = rng.randint(0, V, (B, T))
        x = np.eye(V, dtype="float32")[ids].transpose(0, 2, 1)
        y = np.eye(V, dtype="float32")[np.roll(ids, -1, 1)].transpose(0, 2, 1)
        a = MultiLayerNetwork(conf()).init()
        b = MultiLayerNetwork(conf()).init()
        for _ in range(3):
            a.fit(x, y)
        b.fitSteps(x, y, numSteps=3)
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(), rtol=5e-6, atol=5e-6)
        assert a._iteration == b._iteration  # 3 sequences x 2 windows

    def test_tbptt_ragged_tail_raises(self):
        V, B, T, L = 5, 4, 10, 4  # 10 % 4 != 0

        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
                .list()
                .layer(GravesLSTM(nOut=8))
                .layer(RnnOutputLayer(nOut=V, activation="softmax",
                                      lossFunction="mcxent"))
                .setInputType(InputType.recurrent(V, T))
                .backpropType(BackpropType.TruncatedBPTT)
                .tBPTTLength(L).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(0)
        x = rng.rand(B, V, T).astype("float32")
        y = rng.rand(B, V, T).astype("float32")
        with pytest.raises(ValueError, match="divisible"):
            net.fitSteps(x, y, numSteps=2)


class TestCnn:
    def test_lenet_shape_inference_and_fit(self):
        conf = (NeuralNetConfiguration.Builder()
                .seed(1).updater(Adam(1e-3))
                .list()
                .layer(ConvolutionLayer(nOut=4, kernelSize=(5, 5), activation="relu"))
                .layer(SubsamplingLayer(kernelSize=(2, 2), stride=(2, 2)))
                .layer(DenseLayer(nOut=16, activation="relu"))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.convolutionalFlat(12, 12, 1))
                .build())
        # 12-5+1=8 conv out; 8/2=4 pool out
        assert conf.layers[2].nIn == 4 * 4 * 4
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).rand(8, 144).astype("float32")
        y = np.eye(3, dtype="float32")[np.random.RandomState(1).randint(0, 3, 8)]
        net.fit(x, y)
        assert np.isfinite(net.score())
        out = net.output(x)
        assert out.shape() == (8, 3)
        np.testing.assert_allclose(out.sum(1).toNumpy(), np.ones(8), rtol=1e-4)

    def test_batchnorm_updates_running_stats(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
                .layer(DenseLayer(nOut=8, activation="identity"))
                .layer(BatchNormalization())
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(4))
                .build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).randn(32, 4).astype("float32") * 3 + 1
        y = np.eye(2, dtype="float32")[np.random.RandomState(1).randint(0, 2, 32)]
        m0 = np.array(net._states[1]["mean"])
        net.fit(x, y)
        m1 = np.array(net._states[1]["mean"])
        assert not np.allclose(m0, m1)

    def test_same_mode_conv(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
                .layer(ConvolutionLayer(nOut=2, kernelSize=(3, 3),
                                        convolutionMode="same", activation="relu"))
                .layer(GlobalPoolingLayer(poolingType="avg"))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.convolutional(9, 9, 1))
                .build())
        # Same mode: spatial dims preserved
        assert conf.layerInputTypes[1].height == 9
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).rand(4, 1, 9, 9).astype("float32")
        out = net.output(x)
        assert out.shape() == (4, 2)


class TestRnn:
    def _seq_data(self, n=64, F=3, T=8, seed=0):
        rng = np.random.RandomState(seed)
        x = rng.randn(n, F, T).astype("float32") * 0.1
        trend = rng.randint(0, 2, n)
        ramp = np.linspace(-1, 1, T)
        x[:, 0, :] += np.where(trend[:, None] == 1, ramp, -ramp)
        y = np.eye(2, dtype="float32")[trend]
        return x, np.repeat(y[:, :, None], T, axis=2), y, trend

    def test_lstm_fit_and_output_shape(self):
        x, yseq, y, trend = self._seq_data()
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(2e-2)).list()
                .layer(LSTM(nOut=8))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(3, 8))
                .build())
        net = MultiLayerNetwork(conf).init()
        for _ in range(80):
            net.fit(x, yseq)
        out = net.output(x)
        assert out.shape() == (64, 2, 8)
        acc = (out.toNumpy()[:, :, -1].argmax(1) == trend).mean()
        assert acc > 0.9

    def test_graves_lstm_has_peepholes(self):
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.1)).list()
                .layer(GravesLSTM(nOut=4))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(3, 5))
                .build())
        net = MultiLayerNetwork(conf).init()
        assert "pi" in net._params[0] and "pf" in net._params[0]

    def test_bidirectional_concat_doubles_features(self):
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.1)).list()
                .layer(Bidirectional(LSTM(nOut=4)))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(3, 5))
                .build())
        assert conf.layers[1].nIn == 8
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).randn(4, 3, 5).astype("float32")
        assert net.output(x).shape() == (4, 2, 5)

    def test_tbptt(self):
        x, yseq, _, _ = self._seq_data(T=16)
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(5e-3)).list()
                .layer(LSTM(nOut=8))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(3, 16))
                .build())
        conf.backpropType = BackpropType.TruncatedBPTT
        conf.tbpttFwdLength = conf.tbpttBackLength = 8
        net = MultiLayerNetwork(conf).init()
        losses = []
        for _ in range(10):
            net.fit(x, yseq)
            losses.append(net.score())
        assert losses[-1] < losses[0]

    def test_rnn_timestep_stateful(self):
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.1)).list()
                .layer(LSTM(nOut=4))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(3, 6))
                .build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).randn(2, 3, 6).astype("float32")
        full = net.output(x).toNumpy()
        net.rnnClearPreviousState()
        # feeding one timestep at a time must reproduce the full sequence
        steps = []
        for t in range(6):
            o = net.rnnTimeStep(x[:, :, t:t + 1]).toNumpy()
            steps.append(o[:, :, 0])
        np.testing.assert_allclose(full[:, :, -1], steps[-1], rtol=1e-4, atol=1e-5)

    def test_label_mask_ignores_padded_steps(self):
        x, yseq, _, _ = self._seq_data(n=16)
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.1)).list()
                .layer(LSTM(nOut=4))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(3, 8))
                .build())
        net = MultiLayerNetwork(conf).init()
        lmask_full = np.ones((16, 8), np.float32)
        lmask_half = np.ones((16, 8), np.float32)
        lmask_half[:, 4:] = 0
        s_full = net.score(DataSet(x, yseq, labelsMask=lmask_full))
        s_half = net.score(DataSet(x, yseq, labelsMask=lmask_half))
        assert not np.isclose(s_full, s_half)


class TestGradients:
    """Finite-difference gradient checks (reference: GradientCheckTests).
    Run in fp64 on CPU."""

    def _gradcheck(self, conf, x, y, eps=1e-6, tol=1e-4):
        import jax.numpy as jnp

        net = MultiLayerNetwork(conf).init()
        net._params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float64), net._params)
        x = x.astype("float64")
        y = y.astype("float64")
        grads, score = net.computeGradientAndScore(x, y)
        flat, treedef = jax.tree_util.tree_flatten(net._params)
        gflat, _ = jax.tree_util.tree_flatten(grads)
        rng = np.random.RandomState(0)
        for ai, (a, g) in enumerate(zip(flat, gflat)):
            # sample a few coordinates per array
            idxs = [tuple(rng.randint(0, s) for s in a.shape) for _ in range(3)]
            for idx in idxs:
                pert = a.at[idx].add(eps)
                flat2 = list(flat)
                flat2[ai] = pert
                net._params = jax.tree_util.tree_unflatten(treedef, flat2)
                s_plus = float(net._jit_loss(net._params, net._states, x, y, None, None))
                pert = a.at[idx].add(-eps)
                flat2[ai] = pert
                net._params = jax.tree_util.tree_unflatten(treedef, flat2)
                s_minus = float(net._jit_loss(net._params, net._states, x, y, None, None))
                fd = (s_plus - s_minus) / (2 * eps)
                bp = float(g[idx])
                assert abs(fd - bp) < tol * max(1.0, abs(fd), abs(bp)), \
                    f"array {ai} idx {idx}: fd={fd} bp={bp}"
            net._params = jax.tree_util.tree_unflatten(treedef, flat)

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_dense_gradients(self):
        x, y, _ = _separable_data(n=8)
        conf = (NeuralNetConfiguration.Builder().seed(3)
                .updater(Sgd(0.1)).dataType(DataType.DOUBLE)
                .activation("tanh").list()
                .layer(DenseLayer(nOut=6))
                .layer(OutputLayer(nOut=3, activation="softmax", lossFunction="mcxent"))
                .setInputType(InputType.feedForward(4)).build())
        self._gradcheck(conf, x, y)

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_conv_gradients(self):
        rng = np.random.RandomState(0)
        x = rng.rand(4, 1, 6, 6).astype("float64")
        y = np.eye(2)[rng.randint(0, 2, 4)]
        conf = (NeuralNetConfiguration.Builder().seed(3)
                .updater(Sgd(0.1)).dataType(DataType.DOUBLE).list()
                .layer(ConvolutionLayer(nOut=3, kernelSize=(3, 3), activation="tanh"))
                .layer(SubsamplingLayer(kernelSize=(2, 2), stride=(2, 2)))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.convolutional(6, 6, 1)).build())
        self._gradcheck(conf, x, y)

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_lstm_gradients(self):
        rng = np.random.RandomState(0)
        x = rng.randn(4, 3, 5).astype("float64")
        y = np.eye(2)[rng.randint(0, 2, 4)]
        y = np.repeat(y[:, :, None], 5, axis=2)
        conf = (NeuralNetConfiguration.Builder().seed(3)
                .updater(Sgd(0.1)).dataType(DataType.DOUBLE).list()
                .layer(GravesLSTM(nOut=4))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(3, 5)).build())
        self._gradcheck(conf, x, y, tol=1e-3)

    def test_l2_regularization_included(self):
        x, y, _ = _separable_data(n=8)
        conf_reg = (NeuralNetConfiguration.Builder().seed(3).updater(Sgd(0.1))
                    .l2(0.1).list()
                    .layer(DenseLayer(nOut=6, activation="tanh"))
                    .layer(OutputLayer(nOut=3, activation="softmax"))
                    .setInputType(InputType.feedForward(4)).build())
        conf_none = (NeuralNetConfiguration.Builder().seed(3).updater(Sgd(0.1))
                     .list()
                     .layer(DenseLayer(nOut=6, activation="tanh"))
                     .layer(OutputLayer(nOut=3, activation="softmax"))
                     .setInputType(InputType.feedForward(4)).build())
        s_reg = MultiLayerNetwork(conf_reg).init().score(DataSet(x, y))
        s_none = MultiLayerNetwork(conf_none).init().score(DataSet(x, y))
        assert s_reg > s_none

    def test_gradient_clipping_applies(self):
        x, y, _ = _separable_data(n=8)
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Sgd(1.0))
                .gradientNormalization(GradientNormalization.ClipElementWiseAbsoluteValue)
                .gradientNormalizationThreshold(1e-8)
                .list()
                .layer(DenseLayer(nOut=6, activation="tanh"))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        p0 = net.params().toNumpy()
        net.fit(x, y)
        p1 = net.params().toNumpy()
        # with threshold 1e-8 and lr 1, params move by at most ~1e-8 each
        assert np.max(np.abs(p1 - p0)) < 1e-6


class TestDropoutAndEval:
    def test_dropout_only_in_train(self):
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Sgd(0.1))
                .list()
                .layer(DenseLayer(nOut=16, activation="relu", dropOut=0.5))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).randn(8, 4).astype("float32")
        o1 = net.output(x).toNumpy()
        o2 = net.output(x).toNumpy()
        np.testing.assert_array_equal(o1, o2)  # inference is deterministic

    def test_evaluate(self):
        x, y, yidx = _separable_data()
        net = MultiLayerNetwork(_mlp()).init()
        it = DataSetIterator(x, y, 64)
        for _ in range(30):
            net.fit(it)
        e = net.evaluate(DataSetIterator(x, y, 64))
        assert e.accuracy() > 0.9
        assert 0 <= e.f1() <= 1
        assert "Accuracy" in e.stats()

    def test_embedding_layer(self):
        rng = np.random.RandomState(0)
        x = rng.randint(0, 10, (32, 1)).astype("float32")
        y = np.eye(2, dtype="float32")[(x[:, 0] % 2).astype(int)]
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(5e-2)).list()
                .layer(EmbeddingLayer(nIn=10, nOut=8))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(1)).build())
        net = MultiLayerNetwork(conf).init()
        for _ in range(40):
            net.fit(x, y)
        acc = (net.output(x).argMax(1).toNumpy() == (x[:, 0] % 2)).mean()
        assert acc > 0.9


class TestFusedBatchNormVJP:
    """The hand-written BN backward (ops/norm._bn_train) must match finite
    differences exactly — it replaces autodiff through mean/var with the
    fused two-pass formulas."""

    def test_gradcheck_fp64(self):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.ops.norm import batch_norm

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 3, 5), jnp.float64)
        g = jnp.asarray(rng.rand(5) + 0.5, jnp.float64)
        b = jnp.asarray(rng.randn(5), jnp.float64)
        rm, rv = jnp.zeros(5, jnp.float64), jnp.ones(5, jnp.float64)

        def loss(x, g, b):
            y, _, _ = batch_norm(x, g, b, rm, rv, train=True)
            return jnp.sum(jnp.sin(y) * y)

        grads = jax.grad(loss, argnums=(0, 1, 2))(x, g, b)
        eps = 1e-6
        for ai, arr in enumerate([x, g, b]):
            flat = np.asarray(arr).ravel()
            for i in rng.choice(flat.size, min(6, flat.size), replace=False):
                ap, am = flat.copy(), flat.copy()
                ap[i] += eps
                am[i] -= eps
                args_p, args_m = [x, g, b], [x, g, b]
                args_p[ai] = jnp.asarray(ap.reshape(arr.shape))
                args_m[ai] = jnp.asarray(am.reshape(arr.shape))
                fd = (float(loss(*args_p)) - float(loss(*args_m))) / (2 * eps)
                an = float(np.asarray(grads[ai]).ravel()[i])
                assert abs(fd - an) < 1e-6 * max(1, abs(fd))

    def test_locked_gamma_beta_still_work(self):
        import jax.numpy as jnp
        from deeplearning4j_tpu.ops.norm import batch_norm

        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(8, 5).astype("float32"))
        rm, rv = jnp.zeros(5), jnp.ones(5)
        y, _, _ = batch_norm(x, None, None, rm, rv, train=True)
        np.testing.assert_allclose(np.asarray(y).mean(0), 0.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y).std(0), 1.0, atol=1e-2)


class TestActivationCheckpointing:
    """activationCheckpointing (jax.checkpoint remat): identical numerics,
    different memory/FLOPs schedule. TPU-first feature — trajectory parity
    is the testable contract on CPU."""

    def _conf(self, ck):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           DenseLayer, OutputLayer, Adam)
        b = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-2))
             .activation("tanh"))
        if ck:
            b = b.activationCheckpointing(True)
        return (b.list()
                .layer(DenseLayer(nOut=16))
                .layer(DenseLayer(nOut=16))
                .layer(DenseLayer(nOut=16))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.feedForward(6)).build())

    def test_mln_trajectory_parity(self):
        from deeplearning4j_tpu.nn import MultiLayerNetwork

        rng = np.random.RandomState(0)
        x = rng.randn(16, 6).astype("float32")
        y = np.eye(3, dtype="float32")[rng.randint(0, 3, 16)]
        plain = MultiLayerNetwork(self._conf(False)).init()
        remat = MultiLayerNetwork(self._conf(True)).init()
        assert remat.conf.activationCheckpointing
        for _ in range(5):
            plain.fit(x, y)
            remat.fit(x, y)
        np.testing.assert_allclose(plain.params().toNumpy(),
                                   remat.params().toNumpy(),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(plain.score(), remat.score(), rtol=1e-6)

    def test_graph_trajectory_parity(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           ComputationGraph, DenseLayer,
                                           OutputLayer, Adam)

        def gconf(ck):
            b = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
                 .activation("relu"))
            if ck:
                b = b.activationCheckpointing(True)
            return (b.graphBuilder().addInputs("in")
                    .addLayer("h1", DenseLayer(nOut=12), "in")
                    .addLayer("h2", DenseLayer(nOut=12), "h1")
                    .addLayer("out", OutputLayer(nOut=2, activation="softmax"),
                              "h2")
                    .setOutputs("out")
                    .setInputTypes(InputType.feedForward(5)).build())

        rng = np.random.RandomState(1)
        x = rng.randn(8, 5).astype("float32")
        y = np.eye(2, dtype="float32")[rng.randint(0, 2, 8)]
        a = ComputationGraph(gconf(False)).init()
        b = ComputationGraph(gconf(True)).init()
        for _ in range(5):
            a.fit(x, y)
            b.fit(x, y)
        np.testing.assert_allclose(a.score(), b.score(), rtol=1e-6)
        for la, lb in zip(jax.tree_util.tree_leaves(a._params),
                          jax.tree_util.tree_leaves(b._params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-5, atol=1e-7)

    def test_remat_actually_in_the_traced_program(self):
        """Parity alone would pass if the flag were ignored; the remat
        primitive must be present in the jaxpr iff the flag is set."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn import MultiLayerNetwork

        x = np.zeros((4, 6), "float32")
        y = np.eye(3, dtype="float32")[[0, 1, 2, 0]]
        for ck in (False, True):
            net = MultiLayerNetwork(self._conf(ck)).init()
            jpr = jax.make_jaxpr(
                lambda p, s: net._loss_fn(p, s, jnp.asarray(x),
                                          jnp.asarray(y), jax.random.key(0),
                                          None, None, False))(
                net._params, net._states)
            assert ("remat" in str(jpr)) == ck


class TestModelInterfaceParity:
    """Model-interface surface (reference: org.deeplearning4j.nn.api.Model):
    setParams/getParam/setParamTable/clone on both network types."""

    def _mln(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           DenseLayer, OutputLayer, Adam,
                                           MultiLayerNetwork)
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
                .list()
                .layer(DenseLayer(nOut=7, activation="tanh"))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.feedForward(5)).build())
        return MultiLayerNetwork(conf).init()

    def _graph(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           DenseLayer, OutputLayer, Adam,
                                           ComputationGraph)
        conf = (NeuralNetConfiguration.Builder().seed(4).updater(Adam(1e-2))
                .graphBuilder().addInputs("in")
                .addLayer("h_1", DenseLayer(nOut=6, activation="relu"), "in")
                .addLayer("out", OutputLayer(nOut=2, activation="softmax"),
                          "h_1")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(4)).build())
        return ComputationGraph(conf).init()

    def test_set_params_roundtrip(self):
        net = self._mln()
        flat = net.params().toNumpy() + 0.25  # distinct target vector
        other = self._mln()
        assert not np.allclose(other.params().toNumpy(), flat)
        other.setParams(flat)
        np.testing.assert_allclose(other.params().toNumpy(), flat,
                                   rtol=1e-6)
        with pytest.raises(ValueError, match="setParams"):
            net.setParams(flat[:-1])

    def test_get_param_and_set_param_table(self):
        net = self._mln()
        w0 = net.getParam("0_W").toNumpy()
        assert w0.shape == (5, 7)
        table = {"0_W": np.ones_like(w0)}
        net.setParamTable(table)
        np.testing.assert_allclose(net.getParam("0_W").toNumpy(), 1.0)
        with pytest.raises(ValueError, match="shape"):
            net.setParamTable({"0_W": np.ones((2, 2), "float32")})

    def test_graph_param_table_underscore_names(self):
        net = self._graph()
        t = net.paramTable()
        assert "h_1_W" in t and t["h_1_W"].shape() == (4, 6)
        np.testing.assert_allclose(net.getParam("h_1_W").toNumpy(),
                                   t["h_1_W"].toNumpy())
        net.setParamTable({"h_1_b": np.full(6, 0.5, "float32")})
        np.testing.assert_allclose(net.getParam("h_1_b").toNumpy(), 0.5)

    def test_clone_is_independent(self):
        rng = np.random.RandomState(0)
        for net, fit in (
                (self._mln(), lambda n: n.fit(
                    rng.randn(8, 5).astype("float32"),
                    np.eye(3, dtype="float32")[rng.randint(0, 3, 8)])),
                (self._graph(), lambda n: n.fit(
                    rng.randn(8, 4).astype("float32"),
                    np.eye(2, dtype="float32")[rng.randint(0, 2, 8)]))):
            dup = net.clone()
            np.testing.assert_allclose(dup.params().toNumpy(),
                                       net.params().toNumpy())
            fit(net)  # training the original must not touch the clone
            assert not np.allclose(dup.params().toNumpy(),
                                   net.params().toNumpy())

    def test_clone_carries_training_position(self):
        # LR schedules and the dropout key stream are iteration-keyed:
        # a clone resuming at 0 would silently diverge from the original
        rng = np.random.RandomState(5)
        net = self._mln()
        for _ in range(3):
            net.fit(rng.randn(4, 5).astype("float32"),
                    np.eye(3, dtype="float32")[rng.randint(0, 3, 4)])
        dup = net.clone()
        assert dup._iteration == net._iteration == 3
        assert dup._epoch == net._epoch

    def test_graph_set_params_roundtrip(self):
        net = self._graph()
        flat = net.params().toNumpy() + 0.125
        net.setParams(flat)
        np.testing.assert_allclose(net.params().toNumpy(), flat, rtol=1e-6)
        with pytest.raises(ValueError, match="setParams"):
            net.setParams(flat[:-1])

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_graph_compute_gradient_and_score(self):
        net = self._graph()
        rng = np.random.RandomState(1)
        x = rng.randn(6, 4).astype("float32")
        y = np.eye(2, dtype="float32")[rng.randint(0, 2, 6)]
        grads, score = net.computeGradientAndScore(x, y)
        assert np.isfinite(score)
        g = np.asarray(grads["h_1"]["W"])
        assert g.shape == (4, 6) and np.abs(g).sum() > 0


class TestVAEReconstructionProbability:
    """reconstructionLogProbability / reconstructionProbability
    (reference: VariationalAutoencoder's anomaly-detection API,
    importance-weighted MC estimate of log p(x))."""

    def _pretrained(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork,
                                           VariationalAutoencoder,
                                           OutputLayer, Adam)
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(5e-3))
                .activation("tanh").list()
                .layer(VariationalAutoencoder(
                    nOut=2, encoderLayerSizes=(16,),
                    decoderLayerSizes=(16,)))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(6)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(1)
        x = (rng.randn(128, 6) * 0.3 + 1.5).astype("float32")
        net.pretrainLayer(0, x, epochs=150)
        return net, x, rng

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_in_distribution_scores_higher_than_ood(self):
        net, x, rng = self._pretrained()
        lp_in = np.asarray(
            net.reconstructionLogProbability(x[:32], numSamples=8).jax())
        ood = (rng.randn(32, 6) * 0.3 - 6.0).astype("float32")
        lp_out = np.asarray(
            net.reconstructionLogProbability(ood, numSamples=8).jax())
        assert lp_in.shape == (32,)
        assert lp_in.mean() > lp_out.mean() + 10, (
            lp_in.mean(), lp_out.mean())

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_probability_is_exp_of_log(self):
        import jax
        net, x, _ = self._pretrained()
        vae = net.layers[0]
        lp = vae.reconstructionLogProbability(
            net._params[0], x[:4], numSamples=3, key=jax.random.key(5))
        p = vae.reconstructionProbability(
            net._params[0], x[:4], numSamples=3, key=jax.random.key(5))
        np.testing.assert_allclose(np.asarray(p), np.exp(np.asarray(lp)),
                                   rtol=1e-5)

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_scores_track_preceding_layer_training(self):
        # the cached jit must see CURRENT weights of preceding layers,
        # not trace-time constants (layerIdx > 0 threads params/states)
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           VariationalAutoencoder,
                                           OutputLayer, Adam)
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
                .activation("tanh").list()
                .layer(DenseLayer(nOut=5))
                .layer(VariationalAutoencoder(
                    nOut=2, encoderLayerSizes=(8,), decoderLayerSizes=(8,)))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(2)
        x = rng.randn(16, 4).astype("float32")
        lp0 = np.asarray(net.reconstructionLogProbability(
            x, numSamples=2, layerIdx=1).jax())
        # change layer 0's weights directly: scores MUST change
        net.setParamTable({"0_W": np.asarray(
            net.getParam("0_W").toNumpy() * 3.0)})
        lp1 = np.asarray(net.reconstructionLogProbability(
            x, numSamples=2, layerIdx=1).jax())
        assert not np.allclose(lp0, lp1), "stale closure over layer-0 params"

    def test_non_vae_layer_rejected(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           OutputLayer, Adam)
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
                .list()
                .layer(DenseLayer(nOut=4))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(3)).build())
        net = MultiLayerNetwork(conf).init()
        with pytest.raises(ValueError, match="VariationalAutoencoder"):
            net.reconstructionLogProbability(np.zeros((1, 3), "float32"))


class TestLossLongTail:
    """Upstream LossFunctions long tail (reference: LossSparseMCXENT,
    LossMAPE, LossMSLE, LossWasserstein, LossReconstructionCrossEntropy)
    vs handwritten oracles."""

    def test_sparse_mcxent_matches_dense(self):
        from deeplearning4j_tpu.nn import losses as _losses
        import jax.numpy as jnp

        rs = np.random.RandomState(0)
        logits = jnp.asarray(rs.randn(6, 4).astype("float32"))
        idx = rs.randint(0, 4, 6)
        dense = _losses.compute("mcxent", jnp.asarray(
            np.eye(4, dtype="float32")[idx]), logits, "softmax")
        sparse = _losses.compute("sparse_mcxent",
                                 jnp.asarray(idx.astype("float32")[:, None]),
                                 logits, "softmax")
        np.testing.assert_allclose(float(sparse), float(dense), rtol=1e-6)

    def test_mape_msle_oracles(self):
        from deeplearning4j_tpu.nn import losses as _losses
        import jax.numpy as jnp

        y = jnp.asarray([[2.0, 4.0]])
        yhat = jnp.asarray([[1.0, 5.0]])
        mape = _losses.compute("mape", y, yhat, "identity")
        # reference LossMAPE divides by nOut (muli(100/size(1)))
        np.testing.assert_allclose(
            float(mape), 100 * (0.5 + 0.25) / 2, rtol=1e-6)
        msle = _losses.compute("msle", y, yhat, "identity")
        expect = (np.log(3 / 2) ** 2 + np.log(5 / 6) ** 2) / 2
        np.testing.assert_allclose(float(msle), expect, rtol=1e-6)

    def test_sparse_mcxent_recurrent_and_weighted(self):
        from deeplearning4j_tpu.nn import losses as _losses
        import jax.numpy as jnp

        rs = np.random.RandomState(1)
        pre = jnp.asarray(rs.randn(2, 4, 3).astype("float32"))  # [B,T,C]
        idx = rs.randint(0, 3, (2, 4))
        dense = _losses.compute(
            "mcxent", jnp.asarray(np.eye(3, dtype="float32")[idx]),
            pre, "softmax")
        sparse = _losses.compute(
            "sparse_mcxent", jnp.asarray(idx[..., None].astype("float32")),
            pre, "softmax")
        np.testing.assert_allclose(float(sparse), float(dense), rtol=1e-6)
        # per-class weights gather by each example's class
        logits = jnp.asarray(rs.randn(4, 3).astype("float32"))
        idx2 = np.asarray([0, 1, 2, 1])
        w = np.asarray([1.0, 2.0, 4.0], "float32")
        got = _losses.compute("sparse_mcxent",
                              jnp.asarray(idx2.astype("float32")[:, None]),
                              logits, "softmax", weights=jnp.asarray(w))
        logp = np.asarray(jax.nn.log_softmax(np.asarray(logits), -1))
        expect = np.mean([-logp[i, c] * w[c] for i, c in enumerate(idx2)])
        np.testing.assert_allclose(float(got), expect, rtol=1e-6)

    def test_wasserstein_critic_sign(self):
        from deeplearning4j_tpu.nn import losses as _losses
        import jax.numpy as jnp

        score = jnp.asarray([[3.0], [-1.0]])
        lbl = jnp.asarray([[1.0], [-1.0]])  # real=+1, generated=-1
        w = _losses.compute("wasserstein", lbl, score, "identity")
        np.testing.assert_allclose(float(w), (3.0 + 1.0) / 2, rtol=1e-6)

    def test_reconstruction_xent_trains_autoencoder(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           OutputLayer, Adam)
        conf = (NeuralNetConfiguration.Builder().seed(2).updater(Adam(5e-3))
                .list()
                .layer(DenseLayer(nOut=3, activation="tanh"))
                .layer(OutputLayer(nOut=6, activation="sigmoid",
                                   lossFunction="reconstruction_crossentropy"))
                .setInputType(InputType.feedForward(6)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(0)
        # four repeated patterns: compressible through the 3-wide
        # bottleneck (iid random bits are not)
        patterns = (rng.rand(4, 6) > 0.5).astype("float32")
        x = patterns[rng.randint(0, 4, 64)]
        first = None
        for _ in range(120):
            net.fit(x, x)  # autoencode
            first = first if first is not None else net.score()
        assert net.score() < 0.5 * first, (first, net.score())
