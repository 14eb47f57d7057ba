"""checkpointPolicy="save_conv_outputs": named-residual remat.

The whole train-step loss runs under jax.checkpoint with
save_only_these_names("dl4j_mxu_out") — conv/dense outputs are the only
saved residuals; BN/activation/add/pool intermediates are recomputed in
the backward. Contract tested here: (1) the training trajectory is
IDENTICAL to the stock path (recompute is the same math), (2) the policy
actually changes what is saved (elementwise residuals disappear,
the named conv outputs appear), (3) the zoo flagship threads the option
through. The bytes/time win is measured on hardware by bench.py's
remat A/B, not here (CPU backend).
"""

import contextlib
import io

import numpy as np
import pytest

import jax
from jax.ad_checkpoint import print_saved_residuals

from deeplearning4j_tpu.nn import (Adam, BatchNormalization, ComputationGraph,
                                   ConvolutionLayer, DenseLayer,
                                   GlobalPoolingLayer, InputType,
                                   NeuralNetConfiguration, OutputLayer,
                                   SubsamplingLayer)


def _gconf(policy):
    b = (NeuralNetConfiguration.Builder().seed(11).updater(Adam(1e-2))
         .checkpointPolicy(policy))
    return (b.graphBuilder().addInputs("in")
            .addLayer("c1", ConvolutionLayer(nOut=6, kernelSize=(3, 3),
                                             padding=(1, 1),
                                             activation="identity"), "in")
            .addLayer("bn1", BatchNormalization(activation="relu"), "c1")
            .addLayer("p1", SubsamplingLayer(poolingType="max",
                                             kernelSize=(2, 2),
                                             stride=(2, 2)), "bn1")
            .addLayer("c2", ConvolutionLayer(nOut=8, kernelSize=(3, 3),
                                             padding=(1, 1),
                                             activation="identity"), "p1")
            .addLayer("bn2", BatchNormalization(activation="relu"), "c2")
            .addLayer("gap", GlobalPoolingLayer(poolingType="avg"), "bn2")
            .addLayer("d1", DenseLayer(nOut=16, activation="relu"), "gap")
            .addLayer("out", OutputLayer(nOut=3, activation="softmax"), "d1")
            .setOutputs("out")
            .setInputTypes(InputType.convolutional(8, 8, 2)).build())


def _data(seed=0, n=8):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 2, 8, 8).astype("float32")
    y = np.eye(3, dtype="float32")[rng.randint(0, 3, n)]
    return x, y


class TestSaveConvOutputsPolicy:
    @pytest.mark.slow  # tier-1 budget (PR 21): 5 s on 8 CPU cores
    def test_trajectory_parity_with_stock(self):
        # recompute is the same math — parameters must track exactly
        x, y = _data()
        stock = ComputationGraph(_gconf(None)).init()
        remat = ComputationGraph(_gconf("save_conv_outputs")).init()
        assert remat.conf.checkpointPolicy == "save_conv_outputs"
        for _ in range(5):
            stock.fit(x, y)
            remat.fit(x, y)
        np.testing.assert_allclose(stock.params().toNumpy(),
                                   remat.params().toNumpy(),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(stock.score(), remat.score(), rtol=1e-6)

    def test_bn_running_stats_track(self):
        # BN state updates cross the checkpoint boundary as outputs
        x, y = _data(1)
        stock = ComputationGraph(_gconf(None)).init()
        remat = ComputationGraph(_gconf("save_conv_outputs")).init()
        for _ in range(3):
            stock.fit(x, y)
            remat.fit(x, y)
        sm = stock._states["bn1"]["mean"]
        rm = remat._states["bn1"]["mean"]
        np.testing.assert_allclose(np.asarray(sm), np.asarray(rm),
                                   rtol=1e-5, atol=1e-7)
        assert float(np.abs(np.asarray(sm)).sum()) > 0  # stats moved

    def _saved_residual_report(self, net, x, y):
        import jax.numpy as jnp

        fn = net._ckpt_loss_fn(False)
        # NCHW at the API boundary — _run_graph owns the entry transpose
        args = (net._params, net._strip_carries(net._states),
                {"in": jnp.asarray(x)}, [jnp.asarray(y)],
                jax.random.key(0), None, None)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            print_saved_residuals(fn, *args)
        return buf.getvalue()

    def test_policy_changes_saved_residuals(self):
        x, y = _data(2)
        stock = ComputationGraph(_gconf(None)).init()
        remat = ComputationGraph(_gconf("save_conv_outputs")).init()
        stock_report = self._saved_residual_report(stock, x, y)
        remat_report = self._saved_residual_report(remat, x, y)

        def nonarg(report):
            return [ln for ln in report.splitlines()
                    if ln.strip() and "from the argument" not in ln
                    and "from a literal" not in ln]

        # the 3 tagged MXU outputs (c1, c2, d1) are saved — the tag site
        # is the checkpoint_name call in _run_graph; checkpoint_name
        # lowers through an identity whose source line IS that call
        tagged = [ln for ln in nonarg(remat_report) if "_run_graph" in ln]
        assert len(tagged) == 3, remat_report
        # everything else drops except custom-VJP residuals (BatchNorm's
        # fused backward is opaque to the remat policy — one residual
        # per BN survives); relu masks, pool outputs, log_softmax
        # intermediates all disappear
        assert len(nonarg(remat_report)) <= 3 + 2, remat_report
        assert len(nonarg(remat_report)) < len(nonarg(stock_report)) / 3, (
            f"expected the residual list to collapse; "
            f"stock={len(nonarg(stock_report))} "
            f"remat={len(nonarg(remat_report))}")

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError, match="checkpointPolicy"):
            NeuralNetConfiguration.Builder().checkpointPolicy("save_everything")

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_mln_trajectory_parity(self):
        # the policy is a shared Builder option — MultiLayerNetwork
        # implements it too (same tag + jax.checkpoint wrap)
        from deeplearning4j_tpu.nn import MultiLayerNetwork

        def mconf(policy):
            b = (NeuralNetConfiguration.Builder().seed(9).updater(Adam(1e-2))
                 .checkpointPolicy(policy))
            return (b.list()
                    .layer(ConvolutionLayer(nOut=5, kernelSize=(3, 3),
                                            padding=(1, 1),
                                            activation="identity"))
                    .layer(BatchNormalization(activation="relu"))
                    .layer(GlobalPoolingLayer(poolingType="avg"))
                    .layer(DenseLayer(nOut=8, activation="relu"))
                    .layer(OutputLayer(nOut=3, activation="softmax"))
                    .setInputType(InputType.convolutional(6, 6, 2)).build())

        rng = np.random.RandomState(4)
        x = rng.randn(8, 2, 6, 6).astype("float32")
        y = np.eye(3, dtype="float32")[rng.randint(0, 3, 8)]
        stock = MultiLayerNetwork(mconf(None)).init()
        remat = MultiLayerNetwork(mconf("save_conv_outputs")).init()
        assert remat.conf.checkpointPolicy == "save_conv_outputs"
        for _ in range(4):
            stock.fit(x, y)
            remat.fit(x, y)
        np.testing.assert_allclose(stock.params().toNumpy(),
                                   remat.params().toNumpy(),
                                   rtol=1e-5, atol=1e-7)

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_zoo_flagship_threads_policy(self):
        from deeplearning4j_tpu.zoo import ResNet50

        net = ResNet50(numClasses=10, inputShape=(3, 32, 32),
                       checkpointPolicy="save_conv_outputs").init()
        assert net.conf.checkpointPolicy == "save_conv_outputs"
        # EVERY graph-built zoo model honors the option (applied in
        # ZooModel.init, not per-model conf()); unknown values reject
        from deeplearning4j_tpu.zoo import SqueezeNet

        sq = SqueezeNet(numClasses=5, inputShape=(3, 48, 48),
                        checkpointPolicy="save_conv_outputs").init()
        assert sq.conf.checkpointPolicy == "save_conv_outputs"
        with pytest.raises(ValueError, match="checkpointPolicy"):
            ResNet50(numClasses=5, checkpointPolicy="bogus").init()
        rng = np.random.RandomState(3)
        x = rng.rand(2, 3, 32, 32).astype("float32")
        y = np.eye(10, dtype="float32")[rng.randint(0, 10, 2)]
        net.fit(x, y)
        s1 = net.score()
        net.fit(x, y)
        assert np.isfinite(s1) and np.isfinite(net.score())
