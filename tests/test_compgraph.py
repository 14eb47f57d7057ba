"""ComputationGraph tests (reference: ComputationGraphTestRNN,
TestComputationGraphNetwork in deeplearning4j-core)."""

import numpy as np
import pytest

from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, InputType, ComputationGraph,
    DenseLayer, OutputLayer, ConvolutionLayer, SubsamplingLayer,
    BatchNormalization, ActivationLayer, GlobalPoolingLayer,
    MergeVertex, ElementWiseVertex, SubsetVertex, ScaleVertex, ShiftVertex,
    L2NormalizeVertex, StackVertex, UnstackVertex,
    Adam, Sgd, WeightInit,
)
from deeplearning4j_tpu.data import DataSet, MultiDataSet


def _xor_ish(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype("float32")
    w = rng.randn(4, 3)
    yi = np.argmax(x @ w, axis=1)
    return x, np.eye(3, dtype="float32")[yi], yi


class TestGraphBuild:
    def test_residual_graph(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
                .graphBuilder()
                .addInputs("in")
                .addLayer("d1", DenseLayer(nOut=16, activation="relu"), "in")
                .addLayer("d2", DenseLayer(nOut=16, activation="identity"), "d1")
                .addVertex("res", ElementWiseVertex("add"), "d1", "d2")
                .addLayer("out", OutputLayer(nOut=3, activation="softmax"), "res")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(4))
                .build())
        net = ComputationGraph(conf).init()
        x, y, yi = _xor_ish()
        for _ in range(60):
            net.fit(x, y)
        acc = (net.outputSingle(x).argMax(1).toNumpy() == yi).mean()
        assert acc > 0.9

    def test_cycle_detection(self):
        b = (NeuralNetConfiguration.Builder().updater(Sgd(0.1)).graphBuilder()
             .addInputs("in")
             .addLayer("a", DenseLayer(nOut=4), "b")
             .addLayer("b", DenseLayer(nOut=4), "a")
             .addLayer("out", OutputLayer(nOut=2), "b")
             .setOutputs("out")
             .setInputTypes(InputType.feedForward(3)))
        with pytest.raises(ValueError, match="Cycle"):
            b.build()

    def test_unknown_input_reference(self):
        b = (NeuralNetConfiguration.Builder().updater(Sgd(0.1)).graphBuilder()
             .addInputs("in")
             .addLayer("a", DenseLayer(nOut=4), "nope")
             .setOutputs("a")
             .setInputTypes(InputType.feedForward(3)))
        with pytest.raises(ValueError, match="unknown input"):
            b.build()

    def test_merge_shape_inference(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
                .graphBuilder()
                .addInputs("a", "b")
                .addLayer("da", DenseLayer(nOut=8), "a")
                .addLayer("db", DenseLayer(nOut=8), "b")
                .addVertex("m", MergeVertex(), "da", "db")
                .addLayer("out", OutputLayer(nOut=2, activation="softmax"), "m")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(3), InputType.feedForward(5))
                .build())
        assert conf.nodes["out"].payload.nIn == 16


class TestVertices:
    def _one_vertex_net(self, vertex, nout_in=6):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
                .graphBuilder()
                .addInputs("in")
                .addLayer("d", DenseLayer(nOut=nout_in, activation="identity"), "in")
                .addVertex("v", vertex, "d")
                .addLayer("out", OutputLayer(nOut=2, activation="softmax"), "v")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(4))
                .build())
        return ComputationGraph(conf).init()

    def test_subset_vertex(self):
        net = self._one_vertex_net(SubsetVertex(1, 3))
        assert net.conf.nodes["out"].payload.nIn == 3
        x = np.random.RandomState(0).randn(4, 4).astype("float32")
        assert net.outputSingle(x).shape() == (4, 2)

    def test_scale_shift_l2(self):
        for v in (ScaleVertex(2.0), ShiftVertex(1.0), L2NormalizeVertex()):
            net = self._one_vertex_net(v)
            x = np.random.RandomState(0).randn(4, 4).astype("float32")
            assert net.outputSingle(x).shape() == (4, 2)

    def test_stack_unstack_roundtrip(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
                .graphBuilder()
                .addInputs("a", "b")
                .addVertex("s", StackVertex(), "a", "b")
                .addLayer("d", DenseLayer(nOut=5, activation="identity"), "s")
                .addVertex("u0", UnstackVertex(0, 2), "d")
                .addLayer("out", OutputLayer(nOut=2, activation="softmax"), "u0")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(3), InputType.feedForward(3))
                .build())
        net = ComputationGraph(conf).init()
        xa = np.random.RandomState(0).randn(4, 3).astype("float32")
        xb = np.random.RandomState(1).randn(4, 3).astype("float32")
        out = net.output(xa, xb)
        assert out.shape() == (4, 2)

    def test_elementwise_ops(self):
        for op in ("add", "product", "average", "max", "subtract"):
            conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
                    .graphBuilder()
                    .addInputs("in")
                    .addLayer("d1", DenseLayer(nOut=4, activation="identity"), "in")
                    .addLayer("d2", DenseLayer(nOut=4, activation="identity"), "in")
                    .addVertex("v", ElementWiseVertex(op), "d1", "d2")
                    .addLayer("out", OutputLayer(nOut=2, activation="softmax"), "v")
                    .setOutputs("out")
                    .setInputTypes(InputType.feedForward(3))
                    .build())
            net = ComputationGraph(conf).init()
            x = np.random.RandomState(0).randn(4, 3).astype("float32")
            assert net.outputSingle(x).shape() == (4, 2)


class TestMultiIO:
    def test_two_inputs(self):
        conf = (NeuralNetConfiguration.Builder().seed(2).updater(Adam(1e-2))
                .graphBuilder()
                .addInputs("a", "b")
                .addLayer("da", DenseLayer(nOut=8, activation="relu"), "a")
                .addLayer("db", DenseLayer(nOut=8, activation="relu"), "b")
                .addVertex("m", MergeVertex(), "da", "db")
                .addLayer("out", OutputLayer(nOut=2, activation="softmax"), "m")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(3), InputType.feedForward(5))
                .build())
        net = ComputationGraph(conf).init()
        rng = np.random.RandomState(0)
        xa = rng.randn(16, 3).astype("float32")
        xb = rng.randn(16, 5).astype("float32")
        y = np.eye(2, dtype="float32")[rng.randint(0, 2, 16)]
        net.fit(MultiDataSet([xa, xb], [y]))
        assert np.isfinite(net.score())

    def test_two_outputs(self):
        conf = (NeuralNetConfiguration.Builder().seed(2).updater(Adam(1e-2))
                .graphBuilder()
                .addInputs("in")
                .addLayer("trunk", DenseLayer(nOut=16, activation="relu"), "in")
                .addLayer("out1", OutputLayer(nOut=2, activation="softmax"), "trunk")
                .addLayer("out2", OutputLayer(nOut=4, activation="softmax"), "trunk")
                .setOutputs("out1", "out2")
                .setInputTypes(InputType.feedForward(4))
                .build())
        net = ComputationGraph(conf).init()
        rng = np.random.RandomState(0)
        x = rng.randn(8, 4).astype("float32")
        y1 = np.eye(2, dtype="float32")[rng.randint(0, 2, 8)]
        y2 = np.eye(4, dtype="float32")[rng.randint(0, 4, 8)]
        net.fit(MultiDataSet([x], [y1, y2]))
        o1, o2 = net.output(x)
        assert o1.shape() == (8, 2) and o2.shape() == (8, 4)

    def test_cnn_branch_merge(self):
        conf = (NeuralNetConfiguration.Builder().seed(2).updater(Adam(1e-2))
                .graphBuilder()
                .addInputs("img")
                .addLayer("c3", ConvolutionLayer(nOut=4, kernelSize=(3, 3),
                                                 convolutionMode="same",
                                                 activation="relu"), "img")
                .addLayer("c5", ConvolutionLayer(nOut=4, kernelSize=(5, 5),
                                                 convolutionMode="same",
                                                 activation="relu"), "img")
                .addVertex("m", MergeVertex(), "c3", "c5")
                .addLayer("gp", GlobalPoolingLayer(poolingType="avg"), "m")
                .addLayer("out", OutputLayer(nOut=3, activation="softmax"), "gp")
                .setOutputs("out")
                .setInputTypes(InputType.convolutional(8, 8, 1))
                .build())
        # merge concatenates channels: 4+4=8
        assert conf.nodes["gp"].inputType.kind == "feedforward"
        assert conf.nodes["out"].payload.nIn == 8
        net = ComputationGraph(conf).init()
        x = np.random.RandomState(0).rand(4, 1, 8, 8).astype("float32")
        y = np.eye(3, dtype="float32")[np.random.RandomState(1).randint(0, 3, 4)]
        net.fit(x, y)
        assert np.isfinite(net.score())


class TestGraphTBPTT:
    def _seq_data(self, n=16, T=16, seed=0):
        rng = np.random.RandomState(seed)
        x = rng.randn(n, 3, T).astype("float32")
        yi = (x.sum(axis=1) > 0).astype(int)          # [n,T]
        y = np.eye(2, dtype="float32")[yi]            # [n,T,2]
        return x, np.transpose(y, (0, 2, 1))          # labels NCW [n,2,T]

    def test_graph_tbptt_converges(self):
        from deeplearning4j_tpu.nn import LSTM, RnnOutputLayer

        x, yseq = self._seq_data(T=16)
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(5e-3))
                .graphBuilder()
                .addInputs("in")
                .addLayer("lstm", LSTM(nOut=8), "in")
                .addLayer("out", RnnOutputLayer(nOut=2, activation="softmax"), "lstm")
                .setOutputs("out")
                .setInputTypes(InputType.recurrent(3, 16))
                .backpropType("tbptt")
                .tBPTTForwardLength(8).tBPTTBackwardLength(8)
                .build())
        net = ComputationGraph(conf).init()
        losses = []
        for _ in range(10):
            net.fit(x, yseq)
            losses.append(net.score())
        assert losses[-1] < losses[0]
        # 16 steps / 8-step windows = 2 iterations per fit
        assert net.getIterationCount() == 20

    def test_graph_tbptt_matches_mln(self):
        """CG tbptt must produce the same loss trajectory as the MLN
        implementation it mirrors (same seed, same layers)."""
        from deeplearning4j_tpu.nn import (LSTM, RnnOutputLayer,
                                           MultiLayerNetwork, BackpropType)

        x, yseq = self._seq_data(T=16)
        mconf = (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.05)).list()
                 .layer(LSTM(nOut=8))
                 .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                 .setInputType(InputType.recurrent(3, 16))
                 .build())
        mconf.backpropType = BackpropType.TruncatedBPTT
        mconf.tbpttFwdLength = mconf.tbpttBackLength = 8
        mln = MultiLayerNetwork(mconf).init()

        gconf = (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.05))
                 .graphBuilder()
                 .addInputs("in")
                 .addLayer("lstm", LSTM(nOut=8), "in")
                 .addLayer("out", RnnOutputLayer(nOut=2, activation="softmax"), "lstm")
                 .setOutputs("out")
                 .setInputTypes(InputType.recurrent(3, 16))
                 .backpropType("tbptt")
                 .tBPTTForwardLength(8).tBPTTBackwardLength(8)
                 .build())
        cg = ComputationGraph(gconf).init()
        for _ in range(3):
            mln.fit(x, yseq)
            cg.fit(x, yseq)
        # same layer inits come from different fold_in streams, so exact
        # equality is not expected — but both must converge equivalently
        assert abs(mln.score() - cg.score()) < 0.2


class TestGraphPretrain:
    """ComputationGraph.pretrain/pretrainLayer (reference parity with the
    MultiLayerNetwork VAE pretraining path)."""

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_vae_vertex_pretrains(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           ComputationGraph,
                                           VariationalAutoencoder,
                                           OutputLayer, Adam)
        import jax
        import jax.numpy as jnp

        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(5e-3))
                .activation("tanh").graphBuilder()
                .addInputs("in")
                .addLayer("vae", VariationalAutoencoder(
                    nOut=2, encoderLayerSizes=(16,), decoderLayerSizes=(16,)),
                    "in")
                .addLayer("out", OutputLayer(nOut=2, activation="softmax"), "vae")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(8)).build())
        net = ComputationGraph(conf).init()
        rng = np.random.RandomState(0)
        x = np.concatenate([rng.randn(64, 8) * 0.3 + 2,
                            rng.randn(64, 8) * 0.3 - 2]).astype("float32")
        vae = conf.nodes["vae"].payload
        key = jax.random.key(0)
        l0 = float(vae.pretrain_loss(net._params["vae"], jnp.asarray(x), key))
        net.pretrainLayer("vae", x, epochs=120)
        l1 = float(vae.pretrain_loss(net._params["vae"], jnp.asarray(x), key))
        assert l1 < l0 - 1.0, f"ELBO should improve: {l0} -> {l1}"

    def test_pretrain_rejects_non_pretrainable(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           ComputationGraph, DenseLayer,
                                           OutputLayer, Sgd)

        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
                .graphBuilder().addInputs("in")
                .addLayer("d", DenseLayer(nOut=4), "in")
                .addLayer("out", OutputLayer(nOut=2), "d")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(3)).build())
        net = ComputationGraph(conf).init()
        with pytest.raises(ValueError, match="pretrainable"):
            net.pretrainLayer("d", np.zeros((2, 3), "float32"))


class TestRound4Vertices:
    """L2/DotProduct (siamese) and the seq2seq time vertices
    (reference: graph.{L2Vertex, DotProductVertex},
    graph.rnn.{ReverseTimeSeriesVertex, LastTimeStepVertex,
    DuplicateToTimeSeriesVertex})."""

    def test_siamese_distance_vertices(self):
        from deeplearning4j_tpu.nn import (
            NeuralNetConfiguration, InputType, ComputationGraph, DenseLayer,
            OutputLayer, Adam, L2Vertex, DotProductVertex, MergeVertex,
        )

        g = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
             .graphBuilder().addInputs("a", "b"))
        g.addLayer("ea", DenseLayer(nOut=6, activation="tanh"), "a")
        g.addLayer("eb", DenseLayer(nOut=6, activation="tanh"), "b")
        g.addVertex("l2", L2Vertex(), "ea", "eb")
        g.addVertex("dot", DotProductVertex(), "ea", "eb")
        g.addVertex("feat", MergeVertex(), "l2", "dot")
        g.addLayer("out", OutputLayer(nOut=2, activation="softmax",
                                      lossFunction="mcxent"), "feat")
        net = ComputationGraph(
            g.setOutputs("out")
             .setInputTypes(InputType.feedForward(4),
                            InputType.feedForward(4)).build()).init()
        rng = np.random.RandomState(0)
        xa = rng.rand(8, 4).astype("float32")
        xb = rng.rand(8, 4).astype("float32")
        acts = net.feedForward([xa, xb])
        ea, eb = acts["ea"].toNumpy(), acts["eb"].toNumpy()
        np.testing.assert_allclose(
            acts["l2"].toNumpy()[:, 0],
            np.sqrt(((ea - eb) ** 2).sum(1) + 1e-8), rtol=1e-5)
        np.testing.assert_allclose(
            acts["dot"].toNumpy()[:, 0], (ea * eb).sum(1), rtol=1e-5)
        y = np.eye(2, dtype="float32")[rng.randint(0, 2, 8)]
        net.fit([xa, xb], [y])
        assert np.isfinite(net.score())

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_seq2seq_time_vertices(self):
        from deeplearning4j_tpu.nn import (
            NeuralNetConfiguration, InputType, ComputationGraph, LSTM,
            RnnOutputLayer, Adam, ReverseTimeSeriesVertex,
            LastTimeStepVertex, DuplicateToTimeSeriesVertex,
        )

        g = (NeuralNetConfiguration.Builder().seed(2).updater(Adam(1e-2))
             .graphBuilder().addInputs("src"))
        g.addVertex("rev", ReverseTimeSeriesVertex(), "src")
        g.addLayer("enc", LSTM(nOut=5), "rev")
        g.addVertex("summary", LastTimeStepVertex(), "enc")
        g.addVertex("dup", DuplicateToTimeSeriesVertex(), "summary", "src")
        g.addLayer("dec", LSTM(nOut=5), "dup")
        g.addLayer("out", RnnOutputLayer(nOut=3, activation="softmax",
                                         lossFunction="mcxent"), "dec")
        net = ComputationGraph(
            g.setOutputs("out")
             .setInputTypes(InputType.recurrent(4, 6)).build()).init()
        rng = np.random.RandomState(1)
        x = rng.rand(2, 4, 6).astype("float32")
        acts = net.feedForward([x])
        np.testing.assert_allclose(acts["rev"].toNumpy(),
                                   x[:, :, ::-1], rtol=1e-6)
        enc = acts["enc"].toNumpy()
        np.testing.assert_allclose(acts["summary"].toNumpy(),
                                   enc[:, :, -1], rtol=1e-6)
        dup = acts["dup"].toNumpy()
        assert dup.shape == (2, 5, 6)
        for t in range(6):
            np.testing.assert_allclose(dup[:, :, t],
                                       acts["summary"].toNumpy(), rtol=1e-6)
        y = np.zeros((2, 3, 6), "float32")
        y[:, 0, :] = 1
        net.fit(x, [y])
        assert np.isfinite(net.score())

    def test_duplicate_vertex_needs_two_inputs(self):
        from deeplearning4j_tpu.nn import DuplicateToTimeSeriesVertex

        with pytest.raises(ValueError, match="two inputs"):
            DuplicateToTimeSeriesVertex().apply([np.zeros((2, 3))])

    def test_mask_aware_reverse_and_last_step(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn import (LastTimeStepVertex,
                                           ReverseTimeSeriesVertex)

        x = np.arange(2 * 1 * 5, dtype="float32").reshape(2, 1, 5)
        mask = np.array([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], "float32")
        rev, m = ReverseTimeSeriesVertex().applyMasked(
            [jnp.asarray(x)], [jnp.asarray(mask)])
        # example 0: valid prefix [0,1,2] reversed, padding [3,4] in place
        np.testing.assert_allclose(np.asarray(rev)[0, 0],
                                   [2, 1, 0, 3, 4])
        np.testing.assert_allclose(np.asarray(rev)[1, 0],
                                   [9, 8, 7, 6, 5])
        np.testing.assert_array_equal(np.asarray(m), mask)
        last, lm = LastTimeStepVertex().applyMasked(
            [jnp.asarray(x)], [jnp.asarray(mask)])
        np.testing.assert_allclose(np.asarray(last)[:, 0], [2.0, 9.0])
        assert lm is None
        # no-mask paths match plain apply
        np.testing.assert_allclose(
            np.asarray(ReverseTimeSeriesVertex().applyMasked(
                [jnp.asarray(x)], [None])[0]), x[:, :, ::-1])

    def test_time_vertices_rejected_under_tbptt(self):
        from deeplearning4j_tpu.nn import (
            NeuralNetConfiguration, InputType, LSTM, RnnOutputLayer, Adam,
            ReverseTimeSeriesVertex,
        )

        g = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
             .graphBuilder().addInputs("src"))
        g.addVertex("rev", ReverseTimeSeriesVertex(), "src")
        g.addLayer("enc", LSTM(nOut=4), "rev")
        g.addLayer("out", RnnOutputLayer(nOut=2, activation="softmax",
                                         lossFunction="mcxent"), "enc")
        g.backpropType("tbptt").tBPTTForwardLength(3)
        with pytest.raises(ValueError, match="truncated BPTT"):
            (g.setOutputs("out")
              .setInputTypes(InputType.recurrent(4, 6)).build())

    def test_duplicate_vertex_single_input_fails_at_build(self):
        from deeplearning4j_tpu.nn import (
            NeuralNetConfiguration, InputType, LSTM, RnnOutputLayer, Adam,
            DuplicateToTimeSeriesVertex, LastTimeStepVertex,
        )

        g = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
             .graphBuilder().addInputs("src"))
        g.addLayer("enc", LSTM(nOut=4), "src")
        g.addVertex("summary", LastTimeStepVertex(), "enc")
        g.addVertex("dup", DuplicateToTimeSeriesVertex(), "summary")
        g.addLayer("out", RnnOutputLayer(nOut=2, activation="softmax",
                                         lossFunction="mcxent"), "dup")
        with pytest.raises(ValueError, match="two inputs"):
            (g.setOutputs("out")
              .setInputTypes(InputType.recurrent(4, 6)).build())


class TestGraphFitSteps:
    """ComputationGraph.fitSteps — same bit-parity bar as the
    MultiLayerNetwork/SameDiff variants (TestFitSteps there)."""

    def _conf(self):
        return (NeuralNetConfiguration.Builder().seed(9).updater(Adam(1e-2))
                .graphBuilder()
                .addInputs("in")
                .addLayer("d1", DenseLayer(nOut=16, activation="relu"), "in")
                .addLayer("d2", DenseLayer(nOut=16, activation="identity"),
                          "d1")
                .addVertex("res", ElementWiseVertex("add"), "d1", "d2")
                .addLayer("out", OutputLayer(nOut=3, activation="softmax"),
                          "res")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(4))
                .build())

    def test_matches_k_fit_calls(self):
        x, y, _ = _xor_ish()
        a = ComputationGraph(self._conf()).init()
        b = ComputationGraph(self._conf()).init()
        for _ in range(5):
            a.fit(x, y)
        b.fitSteps(x, y, numSteps=5)
        np.testing.assert_allclose(a.params().toNumpy(),
                                   b.params().toNumpy(),
                                   rtol=2e-6, atol=2e-6)
        assert abs(a.score() - b.score()) < 1e-5
        assert a._iteration == b._iteration == 5

    def test_multidataset_batch(self):
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Sgd(0.1))
                .graphBuilder()
                .addInputs("a", "b")
                .addLayer("da", DenseLayer(nOut=8, activation="tanh"), "a")
                .addLayer("db", DenseLayer(nOut=8, activation="tanh"), "b")
                .addVertex("m", MergeVertex(), "da", "db")
                .addLayer("out", OutputLayer(nOut=2, activation="softmax"),
                          "m")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(4),
                               InputType.feedForward(3))
                .build())
        rng = np.random.RandomState(0)
        mds = MultiDataSet(
            [rng.randn(16, 4).astype("float32"),
             rng.randn(16, 3).astype("float32")],
            [np.eye(2, dtype="float32")[rng.randint(0, 2, 16)]])
        g = ComputationGraph(conf).init()
        g.fitSteps(mds, numSteps=4)
        assert np.isfinite(g.score())
        assert g._iteration == 4

    def test_iterator_rejected(self):
        g = ComputationGraph(self._conf()).init()
        with pytest.raises(ValueError, match="iterator"):
            g.fitSteps(iter([]), numSteps=2)
