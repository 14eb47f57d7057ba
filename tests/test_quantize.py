"""Weight-only int8 inference quantization (nn/quantize.py) — the
bench int8_inference leg's machinery, pinned on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.quantize import (dequantize_params,
                                            int8_infer_fn, param_bytes,
                                            quantize_leaf_int8,
                                            quantize_params_int8)


class TestLeafQuantization:
    def test_roundtrip_error_bounded_per_channel(self):
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(16, 8).astype("float32") * 3.0)
        q, s = quantize_leaf_int8(w)
        assert q.dtype == jnp.int8
        assert s.shape == (8,)  # per output channel
        deq = np.asarray(q, np.float32) * np.asarray(s)
        # symmetric absmax: error <= scale/2 per element
        err = np.abs(deq - np.asarray(w))
        assert np.all(err <= np.asarray(s) / 2 + 1e-7)

    def test_zero_tensor_safe(self):
        q, s = quantize_leaf_int8(jnp.zeros((4, 4), jnp.float32))
        assert np.all(np.asarray(q) == 0)
        assert np.all(np.isfinite(np.asarray(s)))

    def test_vector_uses_per_tensor_scale(self):
        q, s = quantize_leaf_int8(jnp.asarray([1.0, -2.0, 0.5]))
        assert np.asarray(s).shape == ()
        assert np.asarray(q)[1] == -127


class TestTreeQuantization:
    def test_structure_preserved_and_bytes_quartered(self):
        rng = np.random.RandomState(1)
        params = [{"W": jnp.asarray(rng.randn(32, 16).astype("float32")),
                   "b": jnp.asarray(np.zeros(16, "float32"))},
                  {}]
        qp, sc = quantize_params_int8(params)
        assert jax.tree_util.tree_structure(qp) == \
            jax.tree_util.tree_structure(params)
        assert qp[0]["W"].dtype == jnp.int8
        # vector leaves (biases, BN gamma/beta) pass through unquantized
        assert qp[0]["b"].dtype == jnp.float32
        # fp32 -> int8: 4x cut on the matrix weight bytes; the bias
        # vector rides along at full width
        b_bytes = 16 * 4
        assert (param_bytes(qp) - b_bytes) * 4 \
            <= param_bytes(params) - b_bytes + 4 * 16
        deq = dequantize_params(qp, sc, jnp.float32)
        np.testing.assert_allclose(np.asarray(deq[0]["W"]),
                                   np.asarray(params[0]["W"]),
                                   atol=float(np.max(np.asarray(sc[0]["W"]))
                                              / 2) + 1e-6)

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_int8_infer_agrees_on_small_net(self):
        from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           OutputLayer, Sgd)

        conf = (NeuralNetConfiguration.Builder().seed(2).updater(Sgd(0.1))
                .activation("relu").list()
                .layer(DenseLayer(nOut=32))
                .layer(OutputLayer(nOut=5, activation="softmax",
                                   lossFunction="mcxent"))
                .setInputType(InputType.feedForward(12)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(16, 12).astype("float32"))
        infer, qp, sc = int8_infer_fn(net)
        o8 = np.asarray(infer(qp, sc, x))
        o32 = np.asarray(net._forward_infer(net._params,
                                            net._strip_carries(net._states),
                                            x))
        # int8 weights perturb logits slightly; class decisions hold on
        # a comfortably-margined random net
        assert np.mean(np.argmax(o8, -1) == np.argmax(o32, -1)) >= 0.9
        np.testing.assert_allclose(o8, o32, atol=0.05)
