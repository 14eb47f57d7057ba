"""Long-tail layers (reference: nn/conf/layers/{Convolution3D, Cropping*,
Upsampling*, LocallyConnected*, PReLULayer, CenterLossOutputLayer,
SpaceToDepth, SpaceToBatchLayer}, nn/conf/dropout/*, nn/conf/constraint/*,
nn/conf/layers/variational/VariationalAutoencoder) — init/forward shapes,
numeric oracles, gradchecks, and training behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ndarray import DataType
from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, InputType, MultiLayerNetwork,
    DenseLayer, OutputLayer, GlobalPoolingLayer, ActivationLayer,
    Convolution3D, Cropping1D, Cropping2D, Cropping3D,
    Upsampling1D, Upsampling3D, SpaceToDepth, SpaceToBatch,
    LocallyConnected1D, LocallyConnected2D, PReLULayer,
    CenterLossOutputLayer, VariationalAutoencoder,
    GaussianDropout, GaussianNoise, AlphaDropout, SpatialDropout,
    MaxNormConstraint, MinMaxNormConstraint, NonNegativeConstraint,
    UnitNormConstraint,
    ConvolutionLayer, Adam, Sgd,
)


def _net(*layers, inputType, seed=7, updater=None, dtype=DataType.DOUBLE,
         **builder_kw):
    b = (NeuralNetConfiguration.Builder()
         .seed(seed).updater(updater or Sgd(0.1)).dataType(dtype))
    for k, v in builder_kw.items():
        b = getattr(b, k)(*v) if isinstance(v, tuple) else getattr(b, k)(v)
    lb = b.list()
    for l in layers:
        lb = lb.layer(l)
    return MultiLayerNetwork(lb.setInputType(inputType).build()).init()


class TestConv3D:
    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_shapes_and_output(self):
        net = _net(Convolution3D(nOut=4, kernelSize=(2, 2, 2), stride=(1, 1, 1),
                                 activation="relu"),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=3, activation="softmax"),
                   inputType=InputType.convolutional3D(5, 6, 7, 2))
        x = np.random.RandomState(0).randn(3, 2, 5, 6, 7)  # NCDHW
        out = net.output(x)
        assert out.shape() == (3, 3)
        acts = net.feedForward(x)
        assert acts[1].shape() == (3, 4, 5, 6, 4)  # NDHWC internal

    def test_numeric_vs_manual(self):
        """2x2x2 conv on a tiny volume vs explicit loop oracle."""
        rng = np.random.RandomState(1)
        x = rng.randn(1, 3, 3, 3, 1).astype("float64")  # NDHWC
        w = rng.randn(2, 2, 2, 1, 1).astype("float64")
        from deeplearning4j_tpu.ops.conv import conv3d

        y = np.asarray(conv3d(jnp.asarray(x), jnp.asarray(w)))
        ref = np.zeros((1, 2, 2, 2, 1))
        for d in range(2):
            for i in range(2):
                for j in range(2):
                    ref[0, d, i, j, 0] = np.sum(
                        x[0, d:d + 2, i:i + 2, j:j + 2, 0] * w[..., 0, 0])
        np.testing.assert_allclose(y, ref, rtol=1e-10)

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_gradcheck(self):
        net = _net(Convolution3D(nOut=2, kernelSize=(2, 2, 2), activation="tanh"),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.convolutional3D(3, 3, 3, 1))
        rng = np.random.RandomState(0)
        x = rng.randn(2, 1, 3, 3, 3)
        y = np.eye(2)[rng.randint(0, 2, 2)]
        grads, _ = net.computeGradientAndScore(x, y)
        W = net._params[0]["W"]
        eps = 1e-6
        idx = (0, 1, 0, 0, 1)
        p_plus = W.at[idx].add(eps)
        p_minus = W.at[idx].add(-eps)
        import copy
        sp = [dict(p) for p in net._params]
        sp[0] = dict(sp[0]); sp[0]["W"] = p_plus
        lp = float(net._loss_fn(sp, net._states, jnp.asarray(x), jnp.asarray(y),
                                None, None, None, False)[0])
        sp[0]["W"] = p_minus
        lm = float(net._loss_fn(sp, net._states, jnp.asarray(x), jnp.asarray(y),
                                None, None, None, False)[0])
        fd = (lp - lm) / (2 * eps)
        np.testing.assert_allclose(float(grads[0]["W"][idx]), fd, rtol=1e-4,
                                   atol=1e-7)


class TestSpatialReshaping:
    def test_cropping1d(self):
        net = _net(Cropping1D((1, 2)),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.recurrent(3, 8))
        x = np.random.RandomState(0).randn(2, 3, 8)
        acts = net.feedForward(x)
        assert acts[1].shape() == (2, 3, 5)
        np.testing.assert_allclose(acts[1].toNumpy(),
                                   x[:, :, 1:6].astype("float64"))

    def test_cropping3d(self):
        net = _net(Cropping3D((1, 0, 1, 1, 0, 2)),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.convolutional3D(4, 5, 6, 2))
        x = np.random.RandomState(0).randn(2, 2, 4, 5, 6)
        acts = net.feedForward(x)
        assert acts[1].shape() == (2, 3, 3, 4, 2)

    def test_upsampling1d(self):
        net = _net(Upsampling1D(3),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.recurrent(2, 4))
        x = np.random.RandomState(0).randn(1, 2, 4)
        acts = net.feedForward(x)
        assert acts[1].shape() == (1, 2, 12)
        np.testing.assert_allclose(acts[1].toNumpy()[0, 0, :3], x[0, 0, 0])

    def test_upsampling3d(self):
        net = _net(Upsampling3D(2),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.convolutional3D(2, 3, 4, 1))
        x = np.random.RandomState(0).randn(1, 1, 2, 3, 4)
        acts = net.feedForward(x)
        assert acts[1].shape() == (1, 4, 6, 8, 1)

    def test_space_to_depth_roundtrip_values(self):
        net = _net(SpaceToDepth(blocks=2),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.convolutional(4, 4, 3))
        x = np.random.RandomState(0).randn(2, 3, 4, 4)
        acts = net.feedForward(x)
        assert acts[1].shape() == (2, 2, 2, 12)
        # all input values preserved, just rearranged
        np.testing.assert_allclose(np.sort(acts[1].toNumpy().ravel()),
                                   np.sort(x.ravel()))

    def test_space_to_batch_shapes(self):
        net = _net(SpaceToBatch(blocks=2),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.convolutional(4, 4, 3))
        x = np.random.RandomState(0).randn(2, 3, 4, 4)
        acts = net.feedForward(x)
        assert acts[1].shape() == (8, 2, 2, 3)

    def test_space_to_depth_bad_blocks(self):
        with pytest.raises(ValueError, match="divide"):
            _net(SpaceToDepth(blocks=3),
                 GlobalPoolingLayer(),
                 OutputLayer(nOut=2),
                 inputType=InputType.convolutional(4, 4, 3))


class TestLocallyConnected:
    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_lc2d_matches_conv_when_weights_shared(self):
        """If every position's weights are set equal, LC2D == conv2d."""
        rng = np.random.RandomState(0)
        netc = _net(ConvolutionLayer(nOut=3, kernelSize=(2, 2), stride=(1, 1),
                                     activation="identity"),
                    GlobalPoolingLayer(poolingType="avg"),
                    OutputLayer(nOut=2, activation="softmax"),
                    inputType=InputType.convolutional(5, 5, 2))
        netl = _net(LocallyConnected2D(nOut=3, kernelSize=(2, 2), stride=(1, 1),
                                       activation="identity"),
                    GlobalPoolingLayer(poolingType="avg"),
                    OutputLayer(nOut=2, activation="softmax"),
                    inputType=InputType.convolutional(5, 5, 2))
        Wc = np.asarray(netc._params[0]["W"])  # [2,2,2,3]
        # broadcast the shared kernel to every output position
        Wl = np.tile(Wc.reshape(1, 1, -1, 3), (4, 4, 1, 1))
        netl._params[0]["W"] = jnp.asarray(Wl)
        netl._params[0]["b"] = jnp.zeros_like(netl._params[0]["b"])
        netc._params[0]["b"] = jnp.zeros_like(netc._params[0]["b"])
        x = rng.randn(2, 2, 5, 5)
        np.testing.assert_allclose(netl.feedForward(x)[1].toNumpy(),
                                   netc.feedForward(x)[1].toNumpy(),
                                   rtol=1e-6, atol=1e-8)

    def test_lc2d_trains(self):
        rng = np.random.RandomState(0)
        x = rng.randn(32, 1, 6, 6).astype("float32")
        yi = (x[:, 0, :3, :3].mean((1, 2)) > x[:, 0, 3:, 3:].mean((1, 2))).astype(int)
        y = np.eye(2, dtype="float32")[yi]
        net = _net(LocallyConnected2D(nOut=4, kernelSize=(3, 3), stride=(3, 3),
                                      activation="relu"),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.convolutional(6, 6, 1),
                   updater=Adam(1e-2), dtype=DataType.FLOAT)
        first = None
        for _ in range(60):
            net.fit(x, y)
            first = first if first is not None else net.score()
        assert net.score() < 0.6 * first

    def test_lc1d_shapes(self):
        net = _net(LocallyConnected1D(nOut=5, kernelSize=3, stride=2,
                                      activation="tanh"),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.recurrent(4, 9))
        x = np.random.RandomState(0).randn(2, 4, 9)
        acts = net.feedForward(x)
        assert acts[1].shape() == (2, 5, 4)  # (9-3)//2+1 = 4 positions

    def test_lc1d_needs_fixed_length(self):
        with pytest.raises(ValueError, match="timeSeriesLength"):
            _net(LocallyConnected1D(nOut=5, kernelSize=3),
                 GlobalPoolingLayer(),
                 OutputLayer(nOut=2),
                 inputType=InputType.recurrent(4))


class TestPReLU:
    def test_forward_math(self):
        net = _net(PReLULayer(alphaInit=0.25),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.feedForward(4))
        x = np.array([[1.0, -2.0, 0.5, -0.5]])
        acts = net.feedForward(x)
        np.testing.assert_allclose(acts[1].toNumpy(),
                                   [[1.0, -0.5, 0.5, -0.125]])

    def test_alpha_learns(self):
        rng = np.random.RandomState(0)
        x = rng.randn(64, 4).astype("float32")
        y = np.eye(2, dtype="float32")[(x.sum(1) > 0).astype(int)]
        net = _net(DenseLayer(nOut=8), PReLULayer(),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.feedForward(4),
                   updater=Adam(1e-2), dtype=DataType.FLOAT)
        a0 = np.asarray(net._params[1]["alpha"]).copy()
        for _ in range(20):
            net.fit(x, y)
        assert not np.allclose(a0, np.asarray(net._params[1]["alpha"]))


class TestCenterLoss:
    def test_center_loss_trains_and_outputs(self):
        rng = np.random.RandomState(3)
        x, yi = [], []
        for c in range(3):
            x.append(rng.randn(40, 4) + 4 * np.eye(4)[c][None] * 2)
            yi += [c] * 40
        x = np.concatenate(x).astype("float32")
        y = np.eye(3, dtype="float32")[yi]
        net = _net(DenseLayer(nOut=16, activation="relu"),
                   CenterLossOutputLayer(nOut=3, activation="softmax",
                                         lambda_=0.05),
                   inputType=InputType.feedForward(4),
                   updater=Adam(5e-3), dtype=DataType.FLOAT)
        for _ in range(40):
            net.fit(x, y)
        out = net.output(x)
        assert out.shape() == (120, 3)  # extra feature channels dropped
        acc = (out.argMax(1).toNumpy() == np.array(yi)).mean()
        assert acc > 0.9
        # centers moved off the zero init toward the class features
        centers = np.asarray(net._params[1]["centers"])
        assert np.abs(centers).max() > 0.01


class TestDropoutVariants:
    def _apply(self, d, shape=(2000,), seed=0):
        x = jnp.ones(shape)
        return np.asarray(d.apply(x, jax.random.key(seed)))

    def test_gaussian_dropout_moments(self):
        y = self._apply(GaussianDropout(0.5), (20000,))
        assert abs(y.mean() - 1.0) < 0.05
        assert abs(y.std() - 1.0) < 0.1  # sqrt((1-0.5)/0.5) = 1

    def test_gaussian_noise_additive(self):
        y = self._apply(GaussianNoise(0.2), (20000,))
        assert abs(y.mean() - 1.0) < 0.02
        assert abs(y.std() - 0.2) < 0.05

    def test_alpha_dropout_preserves_selu_moments(self):
        x = jax.random.normal(jax.random.key(1), (50000,))
        y = np.asarray(AlphaDropout(0.9).apply(x, jax.random.key(2)))
        assert abs(y.mean() - float(x.mean())) < 0.1
        assert abs(y.std() - float(x.std())) < 0.1

    def test_spatial_dropout_whole_channels(self):
        x = jnp.ones((4, 5, 5, 16))
        y = np.asarray(SpatialDropout(0.5).apply(x, jax.random.key(0)))
        per_channel = y.reshape(4, 25, 16)
        # every channel map is all-zero or all-scaled
        for b in range(4):
            for c in range(16):
                vals = np.unique(per_channel[b, :, c])
                assert len(vals) == 1

    def test_dropout_object_in_layer(self):
        rng = np.random.RandomState(0)
        x = rng.randn(32, 4).astype("float32")
        y = np.eye(2, dtype="float32")[(x.sum(1) > 0).astype(int)]
        net = _net(DenseLayer(nOut=16, dropOut=SpatialDropout(0.9),
                              activation="relu"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.feedForward(4),
                   updater=Adam(1e-2), dtype=DataType.FLOAT)
        net.fit(x, y)
        assert np.isfinite(net.score())

    def test_bad_rates_raise(self):
        with pytest.raises(ValueError):
            GaussianDropout(0.0)
        with pytest.raises(ValueError):
            AlphaDropout(1.5)


class TestConstraints:
    def test_max_norm_enforced_in_training(self):
        rng = np.random.RandomState(0)
        x = rng.randn(32, 4).astype("float32")
        y = np.eye(2, dtype="float32")[(x.sum(1) > 0).astype(int)]
        net = _net(DenseLayer(nOut=16), OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.feedForward(4),
                   updater=Sgd(0.5), dtype=DataType.FLOAT,
                   constrainWeights=(MaxNormConstraint(0.5),))
        for _ in range(10):
            net.fit(x, y)
        for p in net._params:
            norms = np.sqrt((np.asarray(p["W"]) ** 2).sum(0))
            assert np.all(norms <= 0.5 + 1e-5)
            # bias untouched by constrainWeights
        assert np.isfinite(net.score())

    def test_unit_norm(self):
        c = UnitNormConstraint()
        p = jnp.asarray(np.random.RandomState(0).randn(5, 3).astype("float32"))
        out = np.asarray(c.apply(p))
        np.testing.assert_allclose(np.sqrt((out ** 2).sum(0)), 1.0, rtol=1e-5)

    def test_non_negative(self):
        c = NonNegativeConstraint()
        out = np.asarray(c.apply(jnp.asarray([-1.0, 2.0, -3.0])))
        np.testing.assert_allclose(out, [0.0, 2.0, 0.0])

    def test_min_max_norm(self):
        c = MinMaxNormConstraint(minNorm=1.0, maxNorm=2.0)
        p = jnp.asarray([[3.0, 0.1], [4.0, 0.1]])  # norms: 5, ~0.141
        out = np.asarray(c.apply(p))
        norms = np.sqrt((out ** 2).sum(0))
        np.testing.assert_allclose(norms, [2.0, 1.0], rtol=1e-5)


class TestVAE:
    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_pretrain_improves_elbo_and_reconstruction(self):
        rng = np.random.RandomState(0)
        # two gaussian clusters in 8-d
        x = np.concatenate([rng.randn(64, 8) * 0.3 + 2,
                            rng.randn(64, 8) * 0.3 - 2]).astype("float32")
        net = _net(VariationalAutoencoder(nOut=2, encoderLayerSizes=(16,),
                                          decoderLayerSizes=(16,),
                                          activation="tanh"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.feedForward(8),
                   updater=Adam(5e-3), dtype=DataType.FLOAT)
        vae = net.layers[0]
        key = jax.random.key(0)
        l0 = float(vae.pretrain_loss(net._params[0], jnp.asarray(x), key))
        net.pretrainLayer(0, x, epochs=150)
        l1 = float(vae.pretrain_loss(net._params[0], jnp.asarray(x), key))
        assert l1 < l0 - 1.0, f"ELBO should improve: {l0} -> {l1}"
        rec = np.asarray(vae.reconstruct(net._params[0], jnp.asarray(x)))
        base = ((x - x.mean(0)) ** 2).mean()
        assert ((x - rec) ** 2).mean() < base * 0.6

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_vae_as_feature_layer(self):
        net = _net(VariationalAutoencoder(nOut=3, activation="tanh"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.feedForward(6),
                   dtype=DataType.FLOAT)
        x = np.random.RandomState(0).randn(4, 6).astype("float32")
        assert net.output(x).shape() == (4, 2)
        acts = net.feedForward(x)
        assert acts[1].shape() == (4, 3)  # latent means

    def test_pretrain_rejects_non_pretrainable(self):
        net = _net(DenseLayer(nOut=4), OutputLayer(nOut=2),
                   inputType=InputType.feedForward(3), dtype=DataType.FLOAT)
        with pytest.raises(ValueError, match="pretrainable"):
            net.pretrainLayer(0, np.zeros((2, 3), "float32"))

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_bernoulli_reconstruction(self):
        rng = np.random.RandomState(0)
        x = (rng.rand(64, 6) > 0.5).astype("float32")
        net = _net(VariationalAutoencoder(nOut=2,
                                          reconstructionDistribution="bernoulli",
                                          activation="tanh"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.feedForward(6),
                   updater=Adam(1e-2), dtype=DataType.FLOAT)
        net.pretrainLayer(0, x, epochs=30)
        rec = np.asarray(net.layers[0].reconstruct(net._params[0],
                                                   jnp.asarray(x)))
        assert rec.min() >= 0.0 and rec.max() <= 1.0


class TestReviewRegressions:
    def test_constrain_chain_appends(self):
        """constrainBias then constrainWeights must keep BOTH."""
        b = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
             .constrainBias(NonNegativeConstraint())
             .constrainWeights(MaxNormConstraint(2.0)))
        cs = b._d["constraints"]
        assert len(cs) == 2
        assert any(c.applyToBiases and not c.applyToWeights for c in cs)
        assert any(c.applyToWeights and not c.applyToBiases for c in cs)

    def test_regularization_skips_centers_and_alpha(self):
        layer = CenterLossOutputLayer(nOut=3)
        layer.l2 = 1.0
        layer.l1 = 0.0
        layer.weightDecay = 0.0
        layer.l1Bias = layer.l2Bias = 0.0
        params = {"W": jnp.ones((4, 3)), "b": jnp.ones((3,)),
                  "centers": jnp.full((3, 4), 100.0)}
        reg = float(layer.regularization(params))
        assert reg == pytest.approx(0.5 * 12.0)  # only W counted

    def test_constraint_skips_centers(self):
        c = MaxNormConstraint(0.1)
        assert not c.appliesTo("centers")
        assert not c.appliesTo("alpha")
        assert c.appliesTo("W")


class TestSmallUtilityLayers:
    """Subsampling1D / ZeroPadding1D / RepeatVector /
    ElementWiseMultiplication / plain AutoEncoder (upstream long tail)."""

    def test_subsampling1d_max(self):
        from deeplearning4j_tpu.nn import Subsampling1DLayer, GlobalPoolingLayer

        net = _net(Subsampling1DLayer(poolingType="max", kernelSize=2, stride=2),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.recurrent(3, 8))
        x = np.arange(2 * 3 * 8, dtype="float64").reshape(2, 3, 8)
        acts = net.feedForward(x)
        assert acts[1].shape() == (2, 3, 4)
        np.testing.assert_allclose(acts[1].toNumpy(),
                                   x.reshape(2, 3, 4, 2).max(-1))

    def test_zeropadding1d(self):
        from deeplearning4j_tpu.nn import ZeroPadding1DLayer, GlobalPoolingLayer

        net = _net(ZeroPadding1DLayer(padding=(1, 2)),
                   GlobalPoolingLayer(poolingType="avg"),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.recurrent(2, 5))
        x = np.random.RandomState(0).randn(1, 2, 5)
        acts = net.feedForward(x)
        assert acts[1].shape() == (1, 2, 8)
        np.testing.assert_allclose(acts[1].toNumpy()[:, :, 0], 0.0)
        np.testing.assert_allclose(acts[1].toNumpy()[:, :, -2:], 0.0)

    def test_repeat_vector(self):
        from deeplearning4j_tpu.nn import RepeatVector, RnnOutputLayer

        net = _net(DenseLayer(nOut=4), RepeatVector(n=6),
                   RnnOutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.feedForward(3))
        x = np.random.RandomState(0).randn(2, 3)
        acts = net.feedForward(x)
        assert acts[2].shape() == (2, 4, 6)
        for t in range(6):
            np.testing.assert_allclose(acts[2].toNumpy()[:, :, t],
                                       acts[2].toNumpy()[:, :, 0])

    def test_elementwise_multiplication_learns_scale(self):
        from deeplearning4j_tpu.nn import ElementWiseMultiplicationLayer

        rng = np.random.RandomState(0)
        x = rng.randn(64, 4).astype("float32")
        y = np.eye(2, dtype="float32")[(x[:, 0] > 0).astype(int)]
        net = _net(ElementWiseMultiplicationLayer(),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.feedForward(4),
                   updater=Adam(5e-2), dtype=DataType.FLOAT)
        w0 = np.asarray(net._params[0]["W"]).copy()
        for _ in range(20):
            net.fit(x, y)
        assert not np.allclose(w0, np.asarray(net._params[0]["W"]))
        assert np.isfinite(net.score())

    def test_autoencoder_pretrains_and_reconstructs(self):
        from deeplearning4j_tpu.nn import AutoEncoder
        import jax.numpy as jnp

        rng = np.random.RandomState(0)
        # data on a 2-d manifold inside 8-d
        z = rng.randn(128, 2)
        x = np.tanh(z @ rng.randn(2, 8)).astype("float32")
        net = _net(AutoEncoder(nOut=3, activation="tanh",
                               corruptionLevel=0.1),
                   OutputLayer(nOut=2, activation="softmax"),
                   inputType=InputType.feedForward(8),
                   updater=Adam(1e-2), dtype=DataType.FLOAT)
        ae = net.layers[0]
        l0 = float(ae.pretrain_loss(net._params[0], jnp.asarray(x), None))
        net.pretrainLayer(0, x, epochs=200)
        l1 = float(ae.pretrain_loss(net._params[0], jnp.asarray(x), None))
        assert l1 < 0.5 * l0, f"reconstruction should improve: {l0} -> {l1}"


class TestCapsNet:
    """Capsule layers (reference: conf.layers.{PrimaryCapsules,
    CapsuleLayer, CapsuleStrengthLayer}, Sabour 2017): shapes, squash
    norm bound, routing convergence on separable data."""

    def _net(self, routings=3):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, ConvolutionLayer,
                                           PrimaryCapsules, CapsuleLayer,
                                           CapsuleStrengthLayer, Adam)
        from deeplearning4j_tpu.nn.conf.layers import LossLayer

        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(2e-3))
                .list()
                .layer(ConvolutionLayer(nOut=16, kernelSize=(5, 5),
                                        activation="relu"))
                .layer(PrimaryCapsules(capsules=4, capsuleDimensions=6,
                                       kernelSize=(5, 5), stride=(2, 2)))
                .layer(CapsuleLayer(capsules=3, capsuleDimensions=8,
                                    routings=routings))
                .layer(CapsuleStrengthLayer())
                .layer(LossLayer(lossFunction="mcxent",
                                 activation="softmax"))
                .setInputType(InputType.convolutional(20, 20, 1)).build())
        return MultiLayerNetwork(conf).init()

    @pytest.mark.slow  # tier-1 budget (PR 21): 6 s on 8 CPU cores
    def test_shapes_and_squash_bound(self):
        net = self._net()
        x = np.random.RandomState(0).rand(2, 1, 20, 20).astype("float32")
        out = net.output(x)
        assert out.shape() == (2, 3)
        np.testing.assert_allclose(out.toNumpy().sum(1), np.ones(2),
                                   rtol=1e-3)
        # capsule outputs are squashed: every capsule length < 1
        import jax.numpy as jnp
        h, _ = net._run_layers(net._params, net._strip_carries(net._states),
                               net._entry_raw(x) if hasattr(net, "_entry_raw")
                               else jnp.asarray(x), False, None, None)
        # (h is the loss-layer preact [B,3]: strengths in [0,1))
        assert float(jnp.max(h)) < 1.0 + 1e-5

    def test_capsnet_converges(self):
        net = self._net()
        rng = np.random.RandomState(0)
        templates = rng.rand(3, 1, 20, 20).astype("float32")
        yi = rng.randint(0, 3, 12)
        x = 0.85 * templates[yi] + 0.15 * rng.rand(12, 1, 20, 20).astype("float32")
        y = np.eye(3, dtype="float32")[yi]
        first = None
        for _ in range(25):
            net.fit(x, y)
            first = first if first is not None else net.score()
        assert np.isfinite(net.score())
        assert net.score() < 0.6 * first, (first, net.score())

    def test_routing_iterations_change_output(self):
        a = self._net(routings=1)
        b = self._net(routings=3)
        b._params = a._params  # same weights, different routing depth
        x = np.random.RandomState(1).rand(2, 1, 20, 20).astype("float32")
        oa = a.output(x).toNumpy()
        ob = b.output(x).toNumpy()
        assert not np.allclose(oa, ob), "routing must refine agreement"

    def test_unknown_capsule_count_rejected(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           CapsuleLayer, LSTM)
        from deeplearning4j_tpu.nn.conf.layers import LossLayer

        with pytest.raises(ValueError, match="capsule"):
            (NeuralNetConfiguration.Builder().list()
             .layer(LSTM(nOut=8))
             .layer(CapsuleLayer(capsules=3, capsuleDimensions=4))
             .layer(LossLayer(lossFunction="mse", activation="identity"))
             .setInputType(InputType.recurrent(5))  # no length known
             .build())

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_global_weight_init_and_dropout_respected(self):
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork,
                                           PrimaryCapsules, CapsuleLayer,
                                           CapsuleStrengthLayer, Adam)
        from deeplearning4j_tpu.nn.conf.layers import LossLayer

        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-3))
                .weightInit("normal").dropOut(0.5)
                .list()
                .layer(PrimaryCapsules(capsules=2, capsuleDimensions=4,
                                       kernelSize=(3, 3), stride=(2, 2)))
                .layer(CapsuleLayer(capsules=2, capsuleDimensions=4,
                                    routings=2))
                .layer(CapsuleStrengthLayer())
                .layer(LossLayer(lossFunction="mcxent",
                                 activation="softmax"))
                .setInputType(InputType.convolutional(12, 12, 1)).build())
        net = MultiLayerNetwork(conf).init()
        # per-layer biasInit flows through (set on the layer config)
        assert np.asarray(net._params[0]["b"]).shape == (8,)
        # dropout active in train mode: two train-mode losses with the
        # same data differ across iterations only via dropout masks
        x = np.random.RandomState(0).rand(4, 1, 12, 12).astype("float32")
        y = np.eye(2, dtype="float32")[[0, 1, 0, 1]]
        net.fit(x, y)
        s1 = net.score()
        net.fit(x, y)
        assert np.isfinite(s1) and np.isfinite(net.score())


class TestSameDiffCustomLayers:
    """SameDiffLayer/SameDiffLambdaLayer (reference:
    conf.layers.samediff.*) — the custom-layer extension point; the
    defined expression traces into the network's single jitted step."""

    def test_lambda_layer_parity_and_training(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           OutputLayer, Adam,
                                           SameDiffLambdaLayer)

        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
                .list()
                .layer(DenseLayer(nOut=8, activation="identity"))
                .layer(SameDiffLambdaLayer(
                    lambdaFn=lambda sd, x: sd.math.mul(
                        x, sd.nn.sigmoid(x))))  # custom swish
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(0)
        x = rng.randn(16, 4).astype("float32")
        y = np.eye(2, dtype="float32")[(x.sum(1) > 0).astype(int)]
        first = None
        for _ in range(25):
            net.fit(x, y)
            first = first if first is not None else net.score()
        assert net.score() < 0.6 * first
        # parity: identical net with the built-in swish activation
        conf2 = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
                 .list()
                 .layer(DenseLayer(nOut=8, activation="swish"))
                 .layer(OutputLayer(nOut=2, activation="softmax"))
                 .setInputType(InputType.feedForward(4)).build())
        net2 = MultiLayerNetwork(conf2).init()
        # same seed -> dense/output weights initialized identically? layer
        # count differs, so copy them across explicitly
        net2._params[0] = net._params[0]
        net2._params[1] = net._params[2]
        np.testing.assert_allclose(net.output(x).toNumpy(),
                                   net2.output(x).toNumpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_samediff_layer_custom_dense_matches_builtin(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           OutputLayer, Adam, SameDiffLayer)

        class MyDense(SameDiffLayer):
            def __init__(self, nOut, **kw):
                super().__init__(**kw)
                self.nOut = nOut

            def defineParameters(self, inputType):
                return {"W": (inputType.size, self.nOut),
                        "b": (self.nOut,)}

            def defineLayer(self, sd, x, p):
                return sd.math.tanh(sd.nn.linear(x, p["W"], p["b"]))

        conf = (NeuralNetConfiguration.Builder().seed(5).updater(Adam(5e-2))
                .list()
                .layer(MyDense(nOut=12))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(0)
        x = rng.randn(32, 4).astype("float32")
        y = np.eye(2, dtype="float32")[(x.sum(1) > 0).astype(int)]
        # forward parity against a built-in DenseLayer with the SAME params
        conf2 = (NeuralNetConfiguration.Builder().seed(5).updater(Adam(5e-2))
                 .list()
                 .layer(DenseLayer(nOut=12, activation="tanh"))
                 .layer(OutputLayer(nOut=2, activation="softmax"))
                 .setInputType(InputType.feedForward(4)).build())
        ref = MultiLayerNetwork(conf2).init()
        ref._params = net._params
        np.testing.assert_allclose(net.output(x).toNumpy(),
                                   ref.output(x).toNumpy(),
                                   rtol=1e-5, atol=1e-6)
        # and the custom params TRAIN (grads flow through the expression)
        w0 = np.asarray(net._params[0]["W"]).copy()
        first = None
        for _ in range(30):
            net.fit(x, y)
            first = first if first is not None else net.score()
        assert net.score() < 0.5 * first
        assert np.abs(np.asarray(net._params[0]["W"]) - w0).max() > 1e-3

    def test_lambda_output_type_inference(self):
        from deeplearning4j_tpu.nn import SameDiffLambdaLayer
        from deeplearning4j_tpu.nn.conf.inputs import InputType

        l = SameDiffLambdaLayer(
            lambdaFn=lambda sd, x: sd.math.mean(x, 2, keepDims=True))
        out = l.getOutputType(InputType.recurrent(6, 10))
        assert out.kind == InputType.RNN and out.size == 6

    def test_train_mode_and_key_thread_into_expression(self):
        """Stochastic ops inside a custom layer must see the step's train
        flag and PRNG key (a silently-eval-mode dropout was a bug)."""
        import jax
        from deeplearning4j_tpu.nn import SameDiffLambdaLayer

        l = SameDiffLambdaLayer(
            lambdaFn=lambda sd, x: sd.nn.dropout(x, 0.5))
        x = np.ones((4, 6), "float32")
        ev, _ = l.forward({}, {}, jnp.asarray(x), False, None)
        assert np.array_equal(np.asarray(ev), x)  # inference: identity
        tr, _ = l.forward({}, {}, jnp.asarray(x), True, jax.random.key(0))
        tr = np.asarray(tr)
        assert (tr == 0).any() and (tr == 2.0).any()  # masked + rescaled


class TestOCNNOutputLayer:
    """One-class NN head (reference: conf.ocnn.OCNNOutputLayer,
    Chalapathy et al. 2018): trained on normal data only, its score
    separates normals from outliers."""

    def _net(self, nu=0.1):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           OCNNOutputLayer, Adam)
        conf = (NeuralNetConfiguration.Builder().seed(5).updater(Adam(5e-3))
                .list()
                .layer(DenseLayer(nOut=8, activation="tanh"))
                .layer(OCNNOutputLayer(hiddenSize=16, nu=nu,
                                       activation="sigmoid"))
                .setInputType(InputType.feedForward(4)).build())
        return MultiLayerNetwork(conf).init()

    def test_anomaly_separation(self):
        net = self._net()
        rng = np.random.RandomState(0)
        normal = (rng.randn(256, 4) * 0.4 + 1.0).astype("float32")
        dummy_y = np.zeros((256, 1), "float32")  # one-class: ignored
        first = None
        for _ in range(60):
            net.fit(normal, dummy_y)
            first = first if first is not None else net.score()
        assert net.score() < first
        s_in = np.asarray(net.output(normal[:64]).jax()).ravel()
        outliers = (rng.randn(64, 4) * 0.4 - 4.0).astype("float32")
        s_out = np.asarray(net.output(outliers).jax()).ravel()
        # decision threshold = nu-quantile of training scores
        r = np.quantile(np.asarray(net.output(normal).jax()).ravel(), 0.1)
        assert (s_in >= r).mean() > 0.85         # normals mostly above r
        assert (s_out < r).mean() > 0.95, (      # outliers flagged
            s_in.mean(), s_out.mean(), r)

    def test_config_validation(self):
        from deeplearning4j_tpu.nn import OCNNOutputLayer

        with pytest.raises(ValueError, match="nu"):
            OCNNOutputLayer(nu=0.0)
        with pytest.raises(ValueError, match="nOut"):
            OCNNOutputLayer(nOut=3)

    def test_objective_includes_weight_norms(self):
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn import OCNNOutputLayer
        from deeplearning4j_tpu.nn.conf.inputs import InputType as IT
        import jax

        layer = OCNNOutputLayer(hiddenSize=4, nu=0.5, weightInit="xavier")
        p, _ = layer.initialize(jax.random.key(0), IT.feedForward(3),
                                jnp.float32)
        reg = float(layer.regularization(p))
        expect = 0.5 * (np.sum(np.square(np.asarray(p["V"])))
                        + np.sum(np.square(np.asarray(p["w"]))))
        np.testing.assert_allclose(reg, expect, rtol=1e-6)


class TestFrozenLayerAndGravesBidirectional:
    """misc.FrozenLayer semantics (the transfer.FrozenLayer marker +
    _run_layers' inference-mode forcing) and GravesBidirectionalLSTM."""

    def test_frozen_layer_params_fixed_and_inference_mode(self):
        import jax
        from deeplearning4j_tpu.nn import (
            Adam, DenseLayer, FrozenLayer, MultiLayerNetwork,
            NeuralNetConfiguration, OutputLayer)
        rng = np.random.RandomState(0)
        X = rng.randn(32, 4).astype("float32")
        Y = np.eye(2, dtype="float32")[(X.sum(1) > 0).astype(int)]
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
                .list()
                .layer(FrozenLayer(DenseLayer(nIn=4, nOut=8,
                                              activation="tanh",
                                              dropOut=0.5)))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        w0 = np.asarray(net.getParam("0_W")).copy()
        for _ in range(5):
            net.fit(X, Y)
        np.testing.assert_array_equal(np.asarray(net.getParam("0_W")), w0)
        # the reference FrozenLayer's DISTINGUISHING behavior: the frozen
        # layer runs inference-mode even under train=True — dropout off,
        # so different step keys give identical activations (an UNfrozen
        # dropout layer would differ)
        pa, _ = net._run_layers(net._params, net._states, X[:4], True,
                                jax.random.key(0), None)
        pb, _ = net._run_layers(net._params, net._states, X[:4], True,
                                jax.random.key(1), None)
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
        net.layers[0].frozen = False  # control: dropout becomes live
        pc, _ = net._run_layers(net._params, net._states, X[:4], True,
                                jax.random.key(0), None)
        pd, _ = net._run_layers(net._params, net._states, X[:4], True,
                                jax.random.key(1), None)
        assert not np.array_equal(np.asarray(pc), np.asarray(pd))

    def test_graves_bidirectional_lstm(self):
        from deeplearning4j_tpu.nn import (
            Adam, GravesBidirectionalLSTM, InputType, MultiLayerNetwork,
            NeuralNetConfiguration, RnnOutputLayer)
        rng = np.random.RandomState(1)
        X = rng.randn(8, 3, 5).astype("float32")   # [B, C, T]
        Y = np.zeros((8, 2, 5), "float32")
        Y[:, 0] = 1.0
        # reference ergonomics: nIn on the layer, no setInputType call
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
                .list()
                .layer(GravesBidirectionalLSTM(nIn=3, nOut=4))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        # upstream SUMS fwd+bwd: hidden width stays nOut=4
        assert np.asarray(net.getParam("1_W")).shape[0] == 4
        out = net.output(X).toNumpy()
        assert out.shape == (8, 2, 5)
        s0 = None
        for _ in range(5):
            net.fit(X, Y)
            if s0 is None:
                s0 = net.score()
        assert net.score() < s0
