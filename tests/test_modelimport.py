"""Keras model import with numeric parity against real tf.keras models
(reference: deeplearning4j-modelimport KerasModelImport tests)."""

import json

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")
keras = tf.keras

from deeplearning4j_tpu.modelimport import (
    KerasModelImport,
    InvalidKerasConfigurationException,
    UnsupportedKerasConfigurationException,
)


def _wmap(model):
    return {l.name: l.get_weights() for l in model.layers if l.get_weights()}


def _parity(keras_model, net, x_keras, x_native, rtol=2e-4, atol=2e-5):
    want = np.asarray(keras_model.predict(x_keras, verbose=0))
    got = net.output(x_native).toNumpy()
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)


class TestSequentialImport:
    def test_mlp_parity(self):
        m = keras.Sequential([
            keras.layers.Input((20,)),
            keras.layers.Dense(32, activation="relu"),
            keras.layers.Dense(10, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(0).rand(8, 20).astype("float32")
        _parity(m, net, x, x)

    def test_mlp_with_dropout_and_activation_layers(self):
        m = keras.Sequential([
            keras.layers.Input((12,)),
            keras.layers.Dense(16),
            keras.layers.Activation("tanh"),
            keras.layers.Dropout(0.4),
            keras.layers.Dense(3, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(1).rand(4, 12).astype("float32")
        _parity(m, net, x, x)  # dropout inactive at inference

    def test_cnn_parity_with_flatten_reorder(self):
        m = keras.Sequential([
            keras.layers.Input((8, 8, 3)),
            keras.layers.Conv2D(4, 3, activation="relu", padding="valid"),
            keras.layers.MaxPooling2D(2),
            keras.layers.Flatten(),
            keras.layers.Dense(5, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(2).rand(4, 8, 8, 3).astype("float32")
        _parity(m, net, x, x.transpose(0, 3, 1, 2))  # NHWC -> NCHW

    def test_cnn_same_padding_and_avgpool(self):
        m = keras.Sequential([
            keras.layers.Input((6, 6, 2)),
            keras.layers.Conv2D(3, 3, padding="same", activation="relu"),
            keras.layers.AveragePooling2D(2),
            keras.layers.Flatten(),
            keras.layers.Dense(4, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(3).rand(2, 6, 6, 2).astype("float32")
        _parity(m, net, x, x.transpose(0, 3, 1, 2))

    def test_batchnorm_inference_parity(self):
        m = keras.Sequential([
            keras.layers.Input((10,)),
            keras.layers.Dense(8, activation="relu"),
            keras.layers.BatchNormalization(),
            keras.layers.Dense(3, activation="softmax"),
        ])
        # give the BN non-trivial moving stats
        bn = m.layers[1]
        gamma, beta, mean, var = bn.get_weights()
        bn.set_weights([gamma * 1.3, beta + 0.2,
                        mean + 0.5, var * 2.0])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(4).rand(6, 10).astype("float32")
        _parity(m, net, x, x)

    def test_lstm_parity(self):
        m = keras.Sequential([
            keras.layers.Input((6, 5)),  # [T, F]
            keras.layers.LSTM(7),
            keras.layers.Dense(3, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(5).rand(4, 6, 5).astype("float32")
        _parity(m, net, x, x.transpose(0, 2, 1))  # [B,T,F] -> [B,F,T]

    def test_global_pooling(self):
        m = keras.Sequential([
            keras.layers.Input((8, 8, 3)),
            keras.layers.Conv2D(4, 3, activation="relu"),
            keras.layers.GlobalAveragePooling2D(),
            keras.layers.Dense(2, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(6).rand(2, 8, 8, 3).astype("float32")
        _parity(m, net, x, x.transpose(0, 3, 1, 2))

    def test_config_only_import(self):
        m = keras.Sequential([
            keras.layers.Input((20,)),
            keras.layers.Dense(32, activation="relu"),
            keras.layers.Dense(10, activation="softmax"),
        ])
        conf = KerasModelImport.importKerasModelConfiguration(m.to_json())
        assert len(conf.layers) == 2
        assert conf.layers[0].nIn == 20 and conf.layers[0].nOut == 32

    def test_unsupported_layer_raises(self):
        raw = {"class_name": "Sequential", "config": {"layers": [
            {"class_name": "InputLayer", "config": {"batch_shape": [None, 4]}},
            {"class_name": "Lambda", "config": {"name": "weird"}},
        ]}}
        with pytest.raises(UnsupportedKerasConfigurationException):
            KerasModelImport.importKerasSequentialModelAndWeights(json.dumps(raw))

    def test_missing_weights_raises(self):
        m = keras.Sequential([
            keras.layers.Input((4,)),
            keras.layers.Dense(2, activation="softmax"),
        ])
        with pytest.raises(InvalidKerasConfigurationException):
            KerasModelImport.importKerasSequentialModelAndWeights(m.to_json(), {})


class TestLegacyH5:
    def _write_legacy_h5(self, path, model):
        """Emulate the legacy tf.keras H5 layout (model_config attr +
        model_weights/<name> groups with weight_names)."""
        import h5py

        with h5py.File(path, "w") as f:
            f.attrs["model_config"] = model.to_json()
            g = f.create_group("model_weights")
            for l in model.layers:
                ws = l.get_weights()
                if not ws:
                    continue
                lg = g.create_group(l.name)
                names = []
                for i, w in enumerate(ws):
                    dname = f"{l.name}/param_{i}:0"
                    lg.create_dataset(dname, data=w)
                    names.append(dname.encode())
                lg.attrs["weight_names"] = names

    def test_h5_roundtrip_parity(self, tmp_path):
        m = keras.Sequential([
            keras.layers.Input((10,)),
            keras.layers.Dense(16, activation="relu"),
            keras.layers.Dense(4, activation="softmax"),
        ])
        p = str(tmp_path / "model.h5")
        self._write_legacy_h5(p, m)
        net = KerasModelImport.importKerasSequentialModelAndWeights(p, p)
        x = np.random.RandomState(7).rand(5, 10).astype("float32")
        _parity(m, net, x, x)


class TestFunctionalImport:
    def test_residual_add_parity(self):
        inp = keras.layers.Input((16,), name="in0")
        h1 = keras.layers.Dense(16, activation="relu", name="d1")(inp)
        h2 = keras.layers.Dense(16, activation="relu", name="d2")(h1)
        s = keras.layers.Add(name="res")([h1, h2])
        out = keras.layers.Dense(4, activation="softmax", name="out")(s)
        m = keras.Model(inp, out)
        graph = KerasModelImport.importKerasModelAndWeights(m.to_json(), _wmap(m))
        x = np.random.RandomState(8).rand(6, 16).astype("float32")
        want = np.asarray(m.predict(x, verbose=0))
        got = graph.outputSingle(x).toNumpy()
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_concat_branches_parity(self):
        inp = keras.layers.Input((10,), name="in0")
        a = keras.layers.Dense(6, activation="tanh", name="a")(inp)
        b = keras.layers.Dense(6, activation="relu", name="b")(inp)
        c = keras.layers.Concatenate(name="cat")([a, b])
        out = keras.layers.Dense(3, activation="softmax", name="out")(c)
        m = keras.Model(inp, out)
        graph = KerasModelImport.importKerasModelAndWeights(m.to_json(), _wmap(m))
        x = np.random.RandomState(9).rand(4, 10).astype("float32")
        want = np.asarray(m.predict(x, verbose=0))
        got = graph.outputSingle(x).toNumpy()
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


class TestReviewRegressions:
    def test_variable_length_lstm_input(self):
        raw = {"class_name": "Sequential", "config": {"layers": [
            {"class_name": "InputLayer", "config": {"batch_shape": [None, None, 5]}},
            {"class_name": "LSTM", "config": {"name": "l", "units": 4,
                                              "return_sequences": False,
                                              "activation": "tanh"}},
            {"class_name": "Dense", "config": {"name": "d", "units": 2,
                                               "activation": "softmax"}},
        ]}}
        net = KerasModelImport.importKerasSequentialModelAndWeights(json.dumps(raw))
        x = np.random.RandomState(0).rand(2, 5, 9).astype("float32")  # [B,F,T]
        assert net.output(x).shape() == (2, 2)

    def test_trailing_activation_folds_into_output(self):
        m = keras.Sequential([
            keras.layers.Input((6,)),
            keras.layers.Dense(8, activation="relu"),
            keras.layers.Dense(3),
            keras.layers.Activation("softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        assert len(net.layers) == 2
        assert net.layers[-1].activation == "softmax"
        assert net.layers[-1].lossFunction == "mcxent"
        x = np.random.RandomState(1).rand(4, 6).astype("float32")
        _parity(m, net, x, x)

    def test_batchnorm_scale_false(self):
        m = keras.Sequential([
            keras.layers.Input((5,)),
            keras.layers.BatchNormalization(scale=False),
            keras.layers.Dense(2, activation="softmax"),
        ])
        bn = m.layers[0]
        beta, mean, var = bn.get_weights()
        bn.set_weights([beta + 0.3, mean + 0.1, var * 1.7])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(2).rand(6, 5).astype("float32")
        _parity(m, net, x, x)

    def test_asymmetric_padding_supported(self):
        # round 4: asymmetric ((top,bottom),(left,right)) is now mapped
        # onto ZeroPaddingLayer's native 4-tuple (MobileNet stride-2
        # blocks pad (0,1)) — previously rejected
        m = keras.Sequential([
            keras.layers.ZeroPadding2D(padding=((0, 1), (0, 1)), name="zp"),
            keras.layers.Conv2D(2, 3, strides=2, name="c"),
        ])
        m.build((2, 8, 8, 1))
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), weights=_wmap(m))
        x = np.random.RandomState(5).rand(2, 8, 8, 1).astype("float32")
        want = np.asarray(m(x))  # keras NHWC
        # headless MLN (no output layer) returns the raw NHWC activation
        got = np.asarray(net.output(x.transpose(0, 3, 1, 2)).jax())
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_functional_cnn_flatten_parity(self):
        inp = keras.layers.Input((6, 6, 2), name="in0")
        c = keras.layers.Conv2D(3, 3, activation="relu", name="c")(inp)
        f = keras.layers.Flatten(name="fl")(c)
        out = keras.layers.Dense(4, activation="softmax", name="out")(f)
        m = keras.Model(inp, out)
        graph = KerasModelImport.importKerasModelAndWeights(m.to_json(), _wmap(m))
        x = np.random.RandomState(3).rand(2, 6, 6, 2).astype("float32")
        want = np.asarray(m.predict(x, verbose=0))
        got = graph.outputSingle(x.transpose(0, 3, 1, 2)).toNumpy()
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_depthwise_conv_weights(self):
        m = keras.Sequential([
            keras.layers.Input((6, 6, 3)),
            keras.layers.DepthwiseConv2D(3, depth_multiplier=2, activation="relu"),
            keras.layers.Flatten(),
            keras.layers.Dense(2, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(4).rand(2, 6, 6, 3).astype("float32")
        _parity(m, net, x, x.transpose(0, 3, 1, 2))


class TestMultiHeadAttentionImport:
    def test_mha_self_attention_parity(self):
        inp = keras.layers.Input((6, 8), name="seq")  # [T, E]
        att = keras.layers.MultiHeadAttention(
            num_heads=2, key_dim=4, name="mha")(inp, inp)
        pool = keras.layers.GlobalAveragePooling1D(name="gp")(att)
        out = keras.layers.Dense(3, activation="softmax", name="out")(pool)
        m = keras.Model(inp, out)
        wmap = _wmap(m)
        graph = KerasModelImport.importKerasModelAndWeights(m.to_json(), wmap)
        x = np.random.RandomState(11).rand(4, 6, 8).astype("float32")
        want = np.asarray(m.predict(x, verbose=0))
        got = graph.outputSingle(x.transpose(0, 2, 1)).toNumpy()
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_mha_value_dim_mismatch_rejected(self):
        inp = keras.layers.Input((6, 8), name="seq")
        att = keras.layers.MultiHeadAttention(
            num_heads=2, key_dim=4, value_dim=5, name="mha")(inp, inp)
        out = keras.layers.Dense(3, name="out")(
            keras.layers.GlobalAveragePooling1D(name="gp")(att))
        m = keras.Model(inp, out)
        with pytest.raises(UnsupportedKerasConfigurationException, match="value_dim"):
            KerasModelImport.importKerasModelAndWeights(m.to_json(), _wmap(m))


class TestExtendedLayerImport:
    """Importer coverage for the round-3 layer additions (PReLU,
    SeparableConv2D, Conv3D, spatial/gaussian dropout, cropping,
    1D/3D upsampling) — numeric parity at inference."""

    def test_prelu_parity(self):
        m = keras.Sequential([
            keras.layers.Input((6,)),
            keras.layers.Dense(8),
            keras.layers.PReLU(),
            keras.layers.Dense(3, activation="softmax"),
        ])
        # make alphas non-trivial so parity actually exercises them
        m.layers[1].set_weights([np.full((8,), 0.3, "float32")])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(0).randn(4, 6).astype("float32")
        _parity(m, net, x, x)

    def test_separable_conv_parity(self):
        m = keras.Sequential([
            keras.layers.Input((10, 10, 3)),
            keras.layers.SeparableConv2D(8, 3, depth_multiplier=2,
                                         activation="relu"),
            keras.layers.GlobalAveragePooling2D(),
            keras.layers.Dense(4, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(1).rand(2, 10, 10, 3).astype("float32")
        _parity(m, net, x, x.transpose(0, 3, 1, 2))

    def test_conv3d_parity(self):
        m = keras.Sequential([
            keras.layers.Input((4, 6, 6, 2)),
            keras.layers.Conv3D(5, 2, activation="relu"),
            keras.layers.GlobalAveragePooling3D() if hasattr(
                keras.layers, "GlobalAveragePooling3D") else
            keras.layers.Flatten(),
            keras.layers.Dense(3, activation="softmax"),
        ])
        try:
            net = KerasModelImport.importKerasSequentialModelAndWeights(
                m.to_json(), _wmap(m))
        except UnsupportedKerasConfigurationException as e:
            pytest.skip(f"3d pooling path unsupported: {e}")
        x = np.random.RandomState(2).rand(2, 4, 6, 6, 2).astype("float32")
        _parity(m, net, x, x.transpose(0, 4, 1, 2, 3))

    def test_dropout_variants_import_inactive_at_inference(self):
        m = keras.Sequential([
            keras.layers.Input((8,)),
            keras.layers.Dense(16, activation="relu"),
            keras.layers.GaussianDropout(0.3),
            keras.layers.GaussianNoise(0.2),
            keras.layers.AlphaDropout(0.1) if hasattr(
                keras.layers, "AlphaDropout") else keras.layers.Dropout(0.1),
            keras.layers.Dense(3, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(3).rand(4, 8).astype("float32")
        _parity(m, net, x, x)

    def test_cropping_and_upsampling1d(self):
        m = keras.Sequential([
            keras.layers.Input((6, 8, 3)),
            keras.layers.Cropping2D(((1, 0), (2, 1))),
            keras.layers.Conv2D(4, 2, activation="relu"),
            keras.layers.GlobalAveragePooling2D(),
            keras.layers.Dense(2, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(4).rand(2, 6, 8, 3).astype("float32")
        _parity(m, net, x, x.transpose(0, 3, 1, 2))

    def test_trailing_noise_layer_keeps_output_head(self):
        """A trailing regularization layer must not steal is_last from the
        final Dense (it would lose the loss head)."""
        m = keras.Sequential([
            keras.layers.Input((6,)),
            keras.layers.Dense(8, activation="relu"),
            keras.layers.Dense(2, activation="softmax"),
            keras.layers.GaussianNoise(0.1),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        from deeplearning4j_tpu.nn.conf.layers import BaseOutputLayer
        assert any(isinstance(l, BaseOutputLayer) for l in net.layers)
        x = np.random.RandomState(5).rand(4, 6).astype("float32")
        y = np.eye(2, dtype="float32")[[0, 1, 0, 1]]
        net.fit(x, y)  # loss head present -> trains
        assert np.isfinite(net.score())

    def test_prelu_3d_shared_axes_rejected(self):
        raw = {"class_name": "Sequential", "config": {"layers": [
            {"class_name": "InputLayer",
             "config": {"batch_shape": [None, 4, 4, 4, 2]}},
            {"class_name": "PReLU",
             "config": {"name": "p", "shared_axes": [1, 2, 3, 4]}},
        ]}}
        with pytest.raises(UnsupportedKerasConfigurationException,
                           match="shared_axes"):
            KerasModelImport.importKerasSequentialModelAndWeights(
                json.dumps(raw), {})

    def test_1d_pooling_and_padding_parity(self):
        m = keras.Sequential([
            keras.layers.Input((12, 4)),          # [B, T, F]
            keras.layers.ZeroPadding1D(2),
            keras.layers.Cropping1D((1, 1)),
            keras.layers.MaxPooling1D(2),
            keras.layers.LSTM(8),
            keras.layers.Dense(3, activation="softmax"),
        ])
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), _wmap(m))
        x = np.random.RandomState(6).rand(2, 12, 4).astype("float32")
        _parity(m, net, x, x.transpose(0, 2, 1), rtol=1e-3, atol=1e-4)


class TestKerasApplicationsImport:
    """Whole-architecture imports from real keras.applications configs +
    weights (round 4: ReLU layer, asymmetric ZeroPadding2D, Reshape,
    GlobalPooling keepdims)."""

    def _parity(self, km):
        w = {l.name: l.get_weights() for l in km.layers if l.get_weights()}
        net = KerasModelImport.importKerasModelAndWeights(km.to_json(),
                                                          weights=w)
        x = np.random.RandomState(0).rand(2, 64, 64, 3).astype("float32")
        golden = km.predict(x, verbose=0)
        ours = np.asarray(net.output(x.transpose(0, 3, 1, 2)).jax())
        np.testing.assert_allclose(ours, golden, rtol=1e-3, atol=1e-4)

    @pytest.mark.slow  # tier-1 budget (PR 21): 8 s on 8 CPU cores
    def test_mobilenet_v1_exact(self):
        # exercises: standalone ReLU(max_value=6), DepthwiseConv2D,
        # GlobalAveragePooling2D(keepdims=True), Reshape, asymmetric pad
        keras.utils.set_random_seed(3)
        self._parity(tf.keras.applications.MobileNet(
            weights=None, input_shape=(64, 64, 3), classes=5))

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_mobilenet_v2_exact(self):
        keras.utils.set_random_seed(4)
        self._parity(tf.keras.applications.MobileNetV2(
            weights=None, input_shape=(64, 64, 3), classes=5))

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_densenet_config_imports(self):
        keras.utils.set_random_seed(5)
        km = tf.keras.applications.DenseNet121(
            weights=None, input_shape=(64, 64, 3), classes=5)
        net = KerasModelImport.importKerasModelAndWeights(km.to_json())
        assert net is not None

    def test_leaky_relu_alpha_parity(self):
        keras.utils.set_random_seed(6)
        m = keras.Sequential([
            keras.layers.Dense(8),
            keras.layers.LeakyReLU(negative_slope=0.05),  # NON-default:
            # guards reading Keras 3's negative_slope key, not just the
            # 0.3 fallback
            keras.layers.ReLU(negative_slope=0.1),
            keras.layers.Dense(3),
        ])
        m.build((4, 6))
        w = {l.name: l.get_weights() for l in m.layers if l.get_weights()}
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), weights=w)
        x = np.random.RandomState(1).randn(4, 6).astype("float32")
        golden = np.asarray(m(x))
        ours = np.asarray(net.output(x).jax())
        np.testing.assert_allclose(ours, golden, rtol=1e-4, atol=1e-5)

    def test_reshape_wildcard_flatten(self):
        keras.utils.set_random_seed(7)
        m = keras.Sequential([
            keras.layers.Conv2D(3, 3, name="c"),
            keras.layers.Reshape((-1,), name="rs"),
            keras.layers.Dense(4, name="d"),
        ])
        m.build((2, 6, 6, 2))
        w = {l.name: l.get_weights() for l in m.layers if l.get_weights()}
        net = KerasModelImport.importKerasSequentialModelAndWeights(
            m.to_json(), weights=w)
        x = np.random.RandomState(2).rand(2, 6, 6, 2).astype("float32")
        golden = np.asarray(m(x))
        ours = np.asarray(net.output(x.transpose(0, 3, 1, 2)).jax())
        np.testing.assert_allclose(ours, golden, rtol=1e-4, atol=1e-5)

    def test_relu_unsupported_params_loud(self):
        spec = {"class_name": "Sequential", "config": {"layers": [
            {"class_name": "InputLayer",
             "config": {"batch_input_shape": [None, 4]}},
            {"class_name": "ReLU",
             "config": {"name": "r", "max_value": 4.0}},
            {"class_name": "Dense",
             "config": {"name": "d", "units": 2}},
        ]}}
        with pytest.raises(UnsupportedKerasConfigurationException,
                           match="max_value"):
            KerasModelImport.importKerasSequentialModelAndWeights(spec)


class TestEfficientNetImport:
    """Round-4 second wave: Rescaling + Normalization (the EfficientNet
    preprocessing stem) and SE-block broadcasting Multiply."""

    def test_rescaling_normalization_parity(self):
        keras.utils.set_random_seed(8)
        inp = keras.Input((8, 8, 3), name="in0")
        x = keras.layers.Rescaling(scale=1 / 127.5, offset=-1.0,
                                   name="resc")(inp)
        norm = keras.layers.Normalization(
            axis=-1, mean=[0.2, -0.1, 0.4], variance=[1.5, 0.7, 2.0],
            name="nrm")
        x = norm(x)
        x = keras.layers.Conv2D(4, 3, activation="relu", name="cv")(x)
        x = keras.layers.GlobalAveragePooling2D(name="gap")(x)
        out = keras.layers.Dense(3, activation="softmax", name="d")(x)
        km = keras.Model(inp, out)
        w = {l.name: l.get_weights() for l in km.layers if l.get_weights()}
        net = KerasModelImport.importKerasModelAndWeights(km.to_json(),
                                                          weights=w)
        xv = np.random.RandomState(0).rand(2, 8, 8, 3).astype("float32") * 255
        golden = km.predict(xv, verbose=0)
        ours = np.asarray(net.output(xv.transpose(0, 3, 1, 2)).jax())
        np.testing.assert_allclose(ours, golden, rtol=1e-4, atol=1e-5)

    def test_normalization_guards(self):
        inp = keras.Input((4, 4, 3))
        x = keras.layers.Normalization(axis=1, mean=np.zeros((4, 1, 1)),
                                       variance=np.ones((4, 1, 1)))(inp)
        km = keras.Model(inp, keras.layers.Flatten()(x))
        with pytest.raises(UnsupportedKerasConfigurationException,
                           match="axis"):
            KerasModelImport.importKerasModelAndWeights(km.to_json())

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_efficientnetb0_exact(self):
        # the full architecture: Rescaling/Normalization stem, MBConv
        # blocks with broadcasting SE Multiply, swish, DepthwiseConv2D
        keras.utils.set_random_seed(9)
        km = tf.keras.applications.EfficientNetB0(
            weights=None, input_shape=(64, 64, 3), classes=5)
        w = {l.name: l.get_weights() for l in km.layers if l.get_weights()}
        net = KerasModelImport.importKerasModelAndWeights(km.to_json(),
                                                          weights=w)
        x = np.random.RandomState(1).rand(2, 64, 64, 3).astype("float32") * 255
        golden = km.predict(x, verbose=0)
        ours = np.asarray(net.output(x.transpose(0, 3, 1, 2)).jax())
        np.testing.assert_allclose(ours, golden, rtol=1e-3, atol=1e-4)

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    @pytest.mark.parametrize("app,size", [
        ("EfficientNetV2B0", 64), ("Xception", 96), ("ResNet50V2", 64)])
    def test_more_applications_exact(self, app, size):
        # came for free with the EfficientNet layers — pin them
        keras.utils.set_random_seed(11)
        km = getattr(tf.keras.applications, app)(
            weights=None, input_shape=(size, size, 3), classes=5)
        w = {l.name: l.get_weights() for l in km.layers if l.get_weights()}
        net = KerasModelImport.importKerasModelAndWeights(km.to_json(),
                                                          weights=w)
        x = np.random.RandomState(1).rand(2, size, size, 3).astype(
            "float32") * 255
        golden = km.predict(x, verbose=0)
        ours = np.asarray(net.output(x.transpose(0, 3, 1, 2)).jax())
        np.testing.assert_allclose(ours, golden, rtol=1e-3, atol=1e-4)


class TestKeras3ArchiveImport:
    """Keras-3 `.keras` zip archives (reference parity: upstream's
    single-h5 convention — one file carries config AND weights; Keras 3
    moved to a zip of config.json + model.weights.h5 with positional
    variable storage)."""

    def _save(self, tmp_path, model, name):
        p = str(tmp_path / name)
        model.save(p)
        return p

    def test_sequential_archive_exact_parity(self, tmp_path):
        keras = pytest.importorskip("keras")
        keras.utils.set_random_seed(11)
        m = keras.Sequential([
            keras.layers.Input((6,)),
            keras.layers.Dense(10, activation="relu", name="h1"),
            keras.layers.Dense(4, activation="softmax", name="out"),
        ])
        p = self._save(tmp_path, m, "seq.keras")
        from deeplearning4j_tpu.modelimport import KerasModelImport

        net = KerasModelImport.importKerasSequentialModelAndWeights(p)
        x = np.random.RandomState(0).randn(3, 6).astype("float32")
        golden = np.asarray(m(x))
        ours = np.asarray(net.output(x).jax())
        np.testing.assert_allclose(ours, golden, atol=1e-5, rtol=1e-4)

    def test_functional_archive_with_cnn(self, tmp_path):
        keras = pytest.importorskip("keras")
        keras.utils.set_random_seed(12)
        inp = keras.layers.Input((8, 8, 2))
        h = keras.layers.Conv2D(4, 3, padding="same",
                                activation="relu", name="c1")(inp)
        h = keras.layers.MaxPooling2D(2, name="p1")(h)
        h = keras.layers.Flatten(name="f")(h)
        out = keras.layers.Dense(3, activation="softmax", name="o")(h)
        m = keras.Model(inp, out)
        p = self._save(tmp_path, m, "cnn.keras")
        from deeplearning4j_tpu.modelimport import KerasModelImport

        net = KerasModelImport.importKerasModelAndWeights(p)
        x = np.random.RandomState(1).rand(2, 8, 8, 2).astype("float32")
        golden = np.asarray(m(x))
        # NHWC keras input -> NCHW at this API boundary
        ours = np.asarray(
            net.outputSingle(np.transpose(x, (0, 3, 1, 2))).jax())
        np.testing.assert_allclose(ours, golden, atol=1e-4, rtol=1e-3)

    def test_eleven_plus_layers_order_not_alphabetical(self, tmp_path):
        # h5py iterates groups alphabetically: dense_10 < dense_2. The
        # loader must map by RECOMPUTED group name, not iteration order,
        # or uniform-width MLPs with 11+ layers import permuted weights.
        keras = pytest.importorskip("keras")
        keras.utils.set_random_seed(13)
        m = keras.Sequential(
            [keras.layers.Input((4,))]
            + [keras.layers.Dense(4, activation="tanh", name=f"L{i}")
               for i in range(12)]
            + [keras.layers.Dense(2, activation="softmax", name="out")])
        p = self._save(tmp_path, m, "deep.keras")
        from deeplearning4j_tpu.modelimport import KerasModelImport

        net = KerasModelImport.importKerasSequentialModelAndWeights(p)
        x = np.random.RandomState(3).randn(5, 4).astype("float32")
        np.testing.assert_allclose(np.asarray(net.output(x).jax()),
                                   np.asarray(m(x)), atol=1e-5, rtol=1e-4)

    def test_dropout_and_flatten_do_not_desync_mapping(self, tmp_path):
        # var-less layers get no weight group; name-computed lookup must
        # skip them without shifting later layers' weights
        keras = pytest.importorskip("keras")
        keras.utils.set_random_seed(14)
        m = keras.Sequential([
            keras.layers.Input((6,)),
            keras.layers.Dense(8, activation="relu"),
            keras.layers.Dropout(0.5),
            keras.layers.Dense(3, activation="softmax"),
        ])
        p = self._save(tmp_path, m, "drop.keras")
        from deeplearning4j_tpu.modelimport import KerasModelImport

        net = KerasModelImport.importKerasSequentialModelAndWeights(p)
        x = np.random.RandomState(4).randn(3, 6).astype("float32")
        np.testing.assert_allclose(np.asarray(net.output(x).jax()),
                                   np.asarray(m(x, training=False)),
                                   atol=1e-5, rtol=1e-4)

    def test_config_only_parse(self, tmp_path):
        keras = pytest.importorskip("keras")
        m = keras.Sequential([keras.layers.Input((5,)),
                              keras.layers.Dense(2, name="d")])
        p = self._save(tmp_path, m, "cfg.keras")
        from deeplearning4j_tpu.modelimport import KerasModelImport

        cfg = KerasModelImport._parse_config(p)
        assert cfg["class_name"] == "Sequential"
