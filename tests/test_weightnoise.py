"""Weight noise (reference: conf.weightnoise.{DropConnect, WeightNoise})
— train-time weight perturbation, clean inference, gradients flow."""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, InputType, MultiLayerNetwork, DenseLayer,
    OutputLayer, Adam, DropConnect, WeightNoise,
)
from deeplearning4j_tpu.nn.weights import NormalDistribution


def _net(wn=None, global_wn=None, seed=5):
    b = NeuralNetConfiguration.Builder().seed(seed).updater(Adam(1e-2))
    if global_wn is not None:
        b = b.weightNoise(global_wn)
    conf = (b.list()
            .layer(DenseLayer(nOut=8, activation="tanh", weightNoise=wn))
            .layer(OutputLayer(nOut=2, activation="softmax",
                               lossFunction="mcxent"))
            .setInputType(InputType.feedForward(4)).build())
    return MultiLayerNetwork(conf).init()


def _data(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, 4).astype("float32"),
            np.eye(2, dtype="float32")[rng.randint(0, 2, n)])


class TestDropConnect:
    def test_retain_one_is_identity_and_inference_clean(self):
        x, y = _data()
        a, b = _net(DropConnect(1.0)), _net(None)
        np.testing.assert_array_equal(np.asarray(a.output(x).jax()),
                                      np.asarray(b.output(x).jax()))
        # inference ignores weight noise entirely
        c = _net(DropConnect(0.3))
        np.testing.assert_array_equal(np.asarray(c.output(x).jax()),
                                      np.asarray(b.output(x).jax()))

    def test_training_perturbed_but_converges(self):
        x, y = _data(64, 1)
        net = _net(DropConnect(0.8))
        losses = []
        for _ in range(60):
            net.fit(x, y)
            losses.append(net.score())
        assert np.isfinite(losses).all()
        assert np.mean(losses[-10:]) < np.mean(losses[:10])

    def test_train_forward_depends_on_key(self):
        net = _net(DropConnect(0.5))
        x, _ = _data()
        h1 = net._run_layers(net._params, net._strip_carries(net._states),
                             x, True, jax.random.key(1), None)[0]
        h2 = net._run_layers(net._params, net._strip_carries(net._states),
                             x, True, jax.random.key(2), None)[0]
        h1b = net._run_layers(net._params, net._strip_carries(net._states),
                              x, True, jax.random.key(1), None)[0]
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h1b))
        assert not np.array_equal(np.asarray(h1), np.asarray(h2))

    def test_invalid_prob_rejected(self):
        with pytest.raises(ValueError, match="weightRetainProb"):
            DropConnect(0.0)


class TestWeightNoise:
    def test_additive_noise_trains_and_inference_clean(self):
        x, y = _data(32, 2)
        wn = WeightNoise(NormalDistribution(0.0, 0.05))
        net = _net(wn)
        base = _net(None)
        np.testing.assert_array_equal(np.asarray(net.output(x).jax()),
                                      np.asarray(base.output(x).jax()))
        for _ in range(5):
            net.fit(x, y)
        assert np.isfinite(net.score())

    def test_bias_untouched_by_default(self):
        # multiplicative noise with mean 5: if the bias were perturbed,
        # a zero-input forward would change; it must not
        wn = WeightNoise(NormalDistribution(5.0, 0.0), additive=False)
        net = _net(wn)
        x = np.zeros((4, 4), "float32")
        h = net._run_layers(net._params, net._strip_carries(net._states),
                            x, True, jax.random.key(3), None)[0]
        base = net._run_layers(net._params,
                               net._strip_carries(net._states), x, False,
                               None, None)[0]
        np.testing.assert_allclose(np.asarray(h), np.asarray(base),
                                   atol=1e-6)

    def test_global_builder_setting_applies_to_layers(self):
        x, _ = _data()
        net = _net(None, global_wn=DropConnect(0.5))
        assert isinstance(net.layers[0].weightNoise, DropConnect)
        h1 = net._run_layers(net._params, net._strip_carries(net._states),
                             x, True, jax.random.key(1), None)[0]
        h2 = net._run_layers(net._params, net._strip_carries(net._states),
                             x, True, jax.random.key(2), None)[0]
        assert not np.array_equal(np.asarray(h1), np.asarray(h2))


class TestNestedParams:
    @pytest.mark.slow  # tier-1 budget (PR 21): 5 s on 8 CPU cores
    def test_bidirectional_wrapper_gets_noise(self):
        # Bidirectional stores nested {'fwd': {...}, 'bwd': {...}} params;
        # weight noise must walk the pytree instead of crashing on dicts
        from deeplearning4j_tpu.nn import (LSTM, Bidirectional,
                                           RnnOutputLayer)

        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
                .weightNoise(DropConnect(0.5)).list()
                .layer(Bidirectional(LSTM(nOut=4)))
                .layer(RnnOutputLayer(nOut=2, activation="softmax",
                                      lossFunction="mcxent"))
                .setInputType(InputType.recurrent(3)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).rand(2, 3, 5).astype("float32")
        y = np.zeros((2, 2, 5), "float32")
        y[:, 0, :] = 1
        net.fit(x, y)  # crashed with AttributeError before the pytree walk
        assert np.isfinite(net.score())
        h1 = net._run_layers(net._params, net._strip_carries(net._states),
                             x, True, jax.random.key(1), None)[0]
        h2 = net._run_layers(net._params, net._strip_carries(net._states),
                             x, True, jax.random.key(2), None)[0]
        assert not np.array_equal(np.asarray(h1), np.asarray(h2))

    def test_center_loss_centers_never_perturbed(self):
        from deeplearning4j_tpu.nn.conf.layers import CenterLossOutputLayer
        import jax.numpy as jnp

        wn = WeightNoise(NormalDistribution(5.0, 0.0), applyToBias=True)
        params = {"W": jnp.ones((3, 2)), "b": jnp.zeros(2),
                  "centers": jnp.ones((2, 3))}
        out = wn.apply(params, jax.random.key(0))
        np.testing.assert_array_equal(np.asarray(out["centers"]),
                                      np.asarray(params["centers"]))
        assert float(out["W"][0, 0]) == 6.0      # weight perturbed
        assert float(out["b"][0]) == 5.0          # bias: applyToBias=True
