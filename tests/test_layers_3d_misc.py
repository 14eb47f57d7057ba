"""Round-4 straggler layers (reference: Subsampling3DLayer,
ZeroPadding3DLayer, Deconvolution3D, util.MaskLayer,
recurrent.MaskZeroLayer, misc.FrozenLayerWithBackprop)."""

import numpy as np
import pytest

from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, InputType, MultiLayerNetwork, Adam,
    Convolution3D, Subsampling3DLayer, ZeroPadding3D, Deconvolution3D,
    MaskLayer, MaskZeroLayer, FrozenLayerWithBackprop, DenseLayer,
    OutputLayer, RnnOutputLayer, LSTM, DropoutLayer, OutputLayer as OL,
)


class Test3DLayers:
    def _net(self, *layers, shape=(2, 6, 6, 6)):
        c, d, h, w = shape
        from deeplearning4j_tpu.nn import GlobalPoolingLayer

        lb = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-3))
              .list())
        for l in layers:
            lb.layer(l)
        lb.layer(GlobalPoolingLayer(poolingType="avg"))
        lb.layer(OutputLayer(nOut=3, activation="softmax",
                             lossFunction="mcxent"))
        conf = lb.setInputType(InputType.convolutional3D(d, h, w, c)).build()
        return MultiLayerNetwork(conf).init()

    def test_subsampling3d_shapes_and_oracle(self):
        net = self._net(Subsampling3DLayer(poolingType="max",
                                           kernelSize=2, stride=2))
        x = np.random.RandomState(0).rand(2, 2, 6, 6, 6).astype("float32")
        acts = net.feedForward(x)
        pooled = np.asarray(acts[1].jax())  # NDHWC internal
        assert pooled.shape == (2, 3, 3, 3, 2)
        xi = np.asarray(acts[0].jax())  # NDHWC entry
        oracle = xi.reshape(2, 3, 2, 3, 2, 3, 2, 2).max((2, 4, 6))
        np.testing.assert_allclose(pooled, oracle, atol=1e-6)
        # avg variant
        net2 = self._net(Subsampling3DLayer(poolingType="avg",
                                            kernelSize=2, stride=2))
        a2 = np.asarray(net2.feedForward(x)[1].jax())
        np.testing.assert_allclose(
            a2, xi.reshape(2, 3, 2, 3, 2, 3, 2, 2).mean((2, 4, 6)),
            atol=1e-6)

    def test_zeropad3d_shapes_and_content(self):
        net = self._net(ZeroPadding3D(padding=(1, 2, 0)))
        x = np.random.RandomState(1).rand(1, 2, 4, 4, 4).astype("float32")
        padded = np.asarray(net.feedForward(x)[1].jax())
        assert padded.shape == (1, 6, 8, 4, 2)  # D+2, H+4, W+0, C
        assert padded[0, 0].sum() == 0 and padded[0, -1].sum() == 0
        np.testing.assert_allclose(
            padded[0, 1:-1, 2:-2, :, :],
            np.asarray(net.feedForward(x)[0].jax())[0])

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_deconv3d_inverts_conv_shape_and_trains(self):
        net = self._net(
            Convolution3D(nOut=4, kernelSize=2, stride=2),
            Deconvolution3D(nOut=2, kernelSize=2, stride=2),
        )
        x = np.random.RandomState(2).rand(2, 2, 6, 6, 6).astype("float32")
        acts = net.feedForward(x)
        assert np.asarray(acts[1].jax()).shape == (2, 3, 3, 3, 4)
        assert np.asarray(acts[2].jax()).shape == (2, 6, 6, 6, 2)  # restored
        y = np.eye(3, dtype="float32")[np.random.RandomState(3).randint(0, 3, 2)]
        losses = []
        for _ in range(10):
            net.fit(x, y)
            losses.append(net.score())
        assert np.isfinite(losses).all() and losses[-1] < losses[0]


class TestMaskLayers:
    def test_mask_layer_zeroes_masked_steps(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
                .list()
                .layer(LSTM(nOut=6))
                .layer(MaskLayer())
                .layer(RnnOutputLayer(nOut=2, activation="softmax",
                                      lossFunction="mcxent"))
                .setInputType(InputType.recurrent(3)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).rand(2, 3, 5).astype("float32")
        mask = np.array([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], "float32")
        h = net._run_layers(net._params, net._strip_carries(net._states),
                            x, False, None, mask)[0]
        # direct check through the internal path: masked steps are zero
        # after MaskLayer... use feedForward-equivalent via _run_layers of
        # first two layers: easiest is layer-level forward
        ml = MaskLayer()
        act = np.random.RandomState(1).rand(2, 6, 5).astype("float32")
        out, _ = ml.forward({}, {}, act, False, None, mask)
        out = np.asarray(out)
        assert out[0, :, 3:].sum() == 0
        np.testing.assert_allclose(out[1], act[1])

    def test_mask_zero_layer_derives_mask_from_input(self):
        inner = LSTM(nOut=4)
        conf = (NeuralNetConfiguration.Builder().seed(2).updater(Adam(1e-2))
                .list()
                .layer(MaskZeroLayer(inner))
                .layer(RnnOutputLayer(nOut=2, activation="softmax",
                                      lossFunction="mcxent"))
                .setInputType(InputType.recurrent(3)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(3).rand(2, 3, 6).astype("float32")
        x[0, :, 4:] = 0.0  # zero-padded tail -> must be masked out
        x_trunc = x[:, :, :4]
        full = np.asarray(net.output(x).jax())
        # an LSTM under MaskZeroLayer ignores the zero tail: the carry at
        # step 4 equals the carry of the truncated sequence; outputs on
        # real steps must match
        conf2 = (NeuralNetConfiguration.Builder().seed(2).updater(Adam(1e-2))
                 .list()
                 .layer(MaskZeroLayer(LSTM(nOut=4)))
                 .layer(RnnOutputLayer(nOut=2, activation="softmax",
                                       lossFunction="mcxent"))
                 .setInputType(InputType.recurrent(3)).build())
        net2 = MultiLayerNetwork(conf2).initFrom(
            net._params, net._states, net._upd_states)
        trunc = np.asarray(net2.output(x_trunc).jax())
        np.testing.assert_allclose(full[0, :, :4], trunc[0], atol=1e-5)


class TestFrozenWithBackprop:
    def _fit(self, wrap):
        inner = DenseLayer(nOut=8, activation="tanh")
        first = FrozenLayerWithBackprop(inner) if wrap else inner
        conf = (NeuralNetConfiguration.Builder().seed(5).updater(Adam(1e-2))
                .list()
                .layer(first)
                .layer(OL(nOut=2, activation="softmax",
                          lossFunction="mcxent"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(0)
        x = rng.rand(16, 4).astype("float32")
        y = np.eye(2, dtype="float32")[rng.randint(0, 2, 16)]
        w0 = np.asarray(net._params[0]["W"])
        for _ in range(5):
            net.fit(x, y)
        return net, w0

    def test_params_frozen_but_head_trains(self):
        net, w0 = self._fit(wrap=True)
        np.testing.assert_array_equal(np.asarray(net._params[0]["W"]), w0)
        net_u, w0u = self._fit(wrap=False)
        assert not np.array_equal(np.asarray(net_u._params[0]["W"]), w0u)
        assert np.isfinite(net.score())

    def test_keeps_train_mode_unlike_plain_frozen(self):
        # a frozen DROPOUT layer: plain frozen disables dropout
        # (inference mode); FrozenLayerWithBackprop keeps it active
        d = DropoutLayer(dropOut=0.5)
        wrapped = FrozenLayerWithBackprop(DropoutLayer(dropOut=0.5))
        conf = (NeuralNetConfiguration.Builder().seed(9).list()
                .layer(wrapped)
                .layer(OL(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(6)).build())
        net = MultiLayerNetwork(conf).init()
        import jax

        x = np.ones((4, 6), "float32")
        h, _ = net._run_layers(net._params,
                               net._strip_carries(net._states), x, True,
                               jax.random.key(0), None)
        # train-mode path reached the head; dropout zeros visible in the
        # wrapped layer's output
        act, _ = wrapped.forward({}, {}, np.ones((4, 6), "float32"), True,
                                 jax.random.key(1), None)
        assert (np.asarray(act) == 0).any()  # dropout ACTIVE though frozen
        plain = DropoutLayer(dropOut=0.5)
        plain.frozen = True
        # plain frozen layer runs in inference mode inside the net; at
        # layer level inference forward is identity
        act2, _ = plain.forward({}, {}, np.ones((4, 6), "float32"), False,
                                None, None)
        np.testing.assert_array_equal(np.asarray(act2), 1.0)


class TestDeconv2DShapeConsistency:
    """Regression (round 4): Deconvolution2D's forward used forward-conv
    padding pairs in conv_transpose, so output shapes disagreed with
    getOutputType for any k != 2*pad + 1. Pin several configs."""

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    @pytest.mark.parametrize("k,s,p", [(2, 2, 0), (3, 2, 0), (3, 1, 1),
                                       (4, 2, 1), (5, 3, 2)])
    def test_forward_matches_shape_inference(self, k, s, p):
        from deeplearning4j_tpu.nn import Deconvolution2D, GlobalPoolingLayer

        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-3))
                .list()
                .layer(Deconvolution2D(nOut=3, kernelSize=(k, k),
                                       stride=(s, s), padding=(p, p)))
                .layer(GlobalPoolingLayer(poolingType="avg"))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.convolutional(5, 5, 2)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).rand(2, 2, 5, 5).astype("float32")
        act = np.asarray(net.feedForward(x)[1].jax())  # NHWC internal
        it = conf.layerInputTypes[1]  # declared deconv output type
        assert act.shape == (2, it.height, it.width, 3), (
            act.shape, (it.height, it.width))
        expected = s * (5 - 1) + k - 2 * p
        assert it.height == expected


class TestWrapperRobustness:
    """Round-4 review regressions: wrappers must survive deepcopy (the
    TransferLearning path), builder shape inference must look through
    them, and inner regularization must not vanish."""

    def test_deepcopy_and_pickle(self):
        import copy
        import pickle

        w = FrozenLayerWithBackprop(DenseLayer(nOut=4))
        w2 = copy.deepcopy(w)
        assert w2.nOut == 4 and w2.frozen
        w3 = pickle.loads(pickle.dumps(w))
        assert w3.nOut == 4 and w3.frozenKeepTraining

    def test_builder_unwraps_for_preprocessors(self):
        from deeplearning4j_tpu.nn import ConvolutionLayer

        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-3))
                .list()
                .layer(ConvolutionLayer(nOut=3, kernelSize=(3, 3),
                                        activation="relu"))
                .layer(FrozenLayerWithBackprop(DenseLayer(nOut=4,
                                                          activation="tanh")))
                .layer(OL(nOut=2, activation="softmax"))
                .setInputType(InputType.convolutional(6, 6, 2)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).rand(2, 2, 6, 6).astype("float32")
        out = np.asarray(net.output(x).jax())  # CnnToFF auto-inserted
        assert out.shape == (2, 2)

    def test_builder_unwraps_first_layer_nin(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-3))
                .list()
                .layer(MaskZeroLayer(LSTM(nIn=3, nOut=4)))
                .layer(RnnOutputLayer(nIn=4, nOut=2, activation="softmax",
                                      lossFunction="mcxent"))
                .build())  # no setInputType: inferred recurrent(3)
        assert conf.inputType.kind == InputType.RNN
        assert conf.inputType.size == 3

    def test_mask_zero_keeps_inner_regularization(self):
        def build(l2):
            conf = (NeuralNetConfiguration.Builder().seed(3)
                    .updater(Adam(1e-2)).list()
                    .layer(MaskZeroLayer(LSTM(nOut=4, l2=l2)))
                    .layer(RnnOutputLayer(nOut=2, activation="softmax",
                                          lossFunction="mcxent"))
                    .setInputType(InputType.recurrent(3)).build())
            return MultiLayerNetwork(conf).init()

        net = build(0.5)
        reg = float(net._regularization(net._params))
        assert reg > 0.0, "inner l2 silently dropped"
        assert float(build(0.0)._regularization(net._params)) == 0.0


class TestRaggedAudioIterator:
    def test_descriptive_error_for_ragged_records(self, tmp_path):
        import wave as _wave

        from deeplearning4j_tpu.data import (RecordReaderDataSetIterator,
                                             WavFileRecordReader)

        (tmp_path / "a").mkdir()
        for name, n in (("x.wav", 300), ("y.wav", 200)):
            with _wave.open(str(tmp_path / "a" / name), "wb") as w:
                w.setnchannels(1)
                w.setsampwidth(2)
                w.setframerate(8000)
                w.writeframes(np.zeros(n, "<i2").tobytes())
        with pytest.raises(ValueError, match="length="):
            RecordReaderDataSetIterator(
                WavFileRecordReader().initialize(tmp_path), batchSize=2)
