"""Transfer learning (reference: deeplearning4j-nn
org.deeplearning4j.nn.transferlearning.TransferLearningMLNTest)."""

import numpy as np
import pytest

from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, DenseLayer, OutputLayer, MultiLayerNetwork,
    Adam, Sgd, TransferLearning, FineTuneConfiguration, FrozenLayer,
    TransferLearningHelper, ConvolutionLayer, SubsamplingLayer, InputType,
)
from deeplearning4j_tpu.nn.losses import LossFunctions
from deeplearning4j_tpu.data import DataSet

LF = LossFunctions.LossFunction


def _base_net(nOut=3, seed=42):
    conf = (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Adam(5e-2))
            .list()
            .layer(DenseLayer(nIn=8, nOut=32, activation="relu"))
            .layer(DenseLayer(nOut=16, activation="relu"))
            .layer(OutputLayer(nOut=nOut, activation="softmax", lossFunction=LF.MCXENT))
            .setInputType(InputType.feedForward(8))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(nOut=3, n=96, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 8).astype("float32")
    y = np.argmax(X[:, :nOut], axis=1)
    return DataSet(X, np.eye(nOut, dtype="float32")[y])


def _p(net, i, k):
    return np.asarray(net._params[i][k])


class TestFrozenLayers:
    def test_frozen_params_unchanged_by_fit(self):
        net = _base_net()
        tl = (TransferLearning.Builder(net)
              .setFeatureExtractor(1)  # freeze layers 0 and 1
              .build())
        w0, w1 = _p(tl, 0, "W").copy(), _p(tl, 1, "W").copy()
        w2 = _p(tl, 2, "W").copy()
        ds = _data()
        for _ in range(5):
            tl.fit(ds)
        assert np.array_equal(w0, _p(tl, 0, "W"))
        assert np.array_equal(w1, _p(tl, 1, "W"))
        assert not np.array_equal(w2, _p(tl, 2, "W"))

    def test_frozen_net_still_learns_on_top(self):
        net = _base_net()
        tl = TransferLearning.Builder(net).setFeatureExtractor(1).build()
        ds = _data()
        s0 = tl.score(ds)
        for _ in range(40):
            tl.fit(ds)
        assert tl.score(ds) < s0

    def test_frozen_layer_marker(self):
        net = _base_net()
        FrozenLayer(net.layers[0])
        ds = _data()
        w0 = _p(net, 0, "W").copy()
        net.fit(ds)
        assert np.array_equal(w0, _p(net, 0, "W"))


class TestTransferBuilder:
    def test_weights_copied_for_retained_layers(self):
        net = _base_net()
        tl = TransferLearning.Builder(net).setFeatureExtractor(0).build()
        for i in range(3):
            assert np.array_equal(_p(net, i, "W"), _p(tl, i, "W"))

    def test_nout_replace_reinits_and_rewires(self):
        net = _base_net(nOut=3)
        tl = (TransferLearning.Builder(net)
              .setFeatureExtractor(1)
              .nOutReplace(2, 5)  # new 5-class head
              .build())
        assert _p(tl, 2, "W").shape == (16, 5)
        # retained layers keep trained weights
        assert np.array_equal(_p(net, 0, "W"), _p(tl, 0, "W"))
        out = tl.output(_data(nOut=5).getFeatures())
        assert out.shape() == (96, 5)
        # new head trains fine
        ds5 = _data(nOut=5)
        s0 = tl.score(ds5)
        for _ in range(30):
            tl.fit(ds5)
        assert tl.score(ds5) < s0

    def test_nout_replace_mid_layer_rewires_next(self):
        net = _base_net()
        tl = (TransferLearning.Builder(net)
              .nOutReplace(1, 24)
              .build())
        assert _p(tl, 1, "W").shape == (32, 24)
        assert _p(tl, 2, "W").shape == (24, 3)
        # layer 0 retained
        assert np.array_equal(_p(net, 0, "W"), _p(tl, 0, "W"))

    def test_remove_and_add_output_layer(self):
        net = _base_net(nOut=3)
        tl = (TransferLearning.Builder(net)
              .setFeatureExtractor(1)
              .removeOutputLayer()
              .addLayer(DenseLayer(nOut=12, activation="relu"))
              .addLayer(OutputLayer(nOut=7, activation="softmax",
                                    lossFunction=LF.MCXENT))
              .build())
        assert len(tl.layers) == 4
        assert _p(tl, 2, "W").shape == (16, 12)
        assert _p(tl, 3, "W").shape == (12, 7)
        out = tl.output(_data().getFeatures())
        assert out.shape() == (96, 7)

    def test_fine_tune_configuration_applies_to_unfrozen(self):
        net = _base_net()
        ftc = (FineTuneConfiguration.Builder()
               .updater(Sgd(1e-3)).l2(1e-4).seed(123)
               .build())
        tl = (TransferLearning.Builder(net)
              .fineTuneConfiguration(ftc)
              .setFeatureExtractor(0)
              .build())
        assert tl.conf.seed == 123
        from deeplearning4j_tpu.nn.updaters import Sgd as SgdUpd

        assert isinstance(tl.layers[1].updater, SgdUpd)
        assert tl.layers[1].l2 == 1e-4
        # frozen layer untouched by fine-tune overrides
        assert not isinstance(tl.layers[0].updater, SgdUpd)

    def test_cnn_transfer_with_preprocessors(self):
        conf = (NeuralNetConfiguration.Builder()
                .seed(1).updater(Adam(1e-2))
                .list()
                .layer(ConvolutionLayer(nOut=4, kernelSize=(3, 3), stride=(1, 1)))
                .layer(SubsamplingLayer(kernelSize=(2, 2), stride=(2, 2)))
                .layer(OutputLayer(nOut=3, activation="softmax", lossFunction=LF.MCXENT))
                .setInputType(InputType.convolutional(8, 8, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        tl = (TransferLearning.Builder(net)
              .setFeatureExtractor(1)
              .nOutReplace(2, 6)
              .build())
        x = np.random.RandomState(0).rand(4, 1, 8, 8).astype("float32")
        assert tl.output(x).shape() == (4, 6)
        assert np.array_equal(_p(net, 0, "W"), _p(tl, 0, "W"))


class TestTransferLearningHelper:
    def test_featurize_matches_full_forward(self):
        net = _base_net()
        helper = TransferLearningHelper(net, frozenTill=1)
        ds = _data()
        feat = helper.featurize(ds)
        out_full = net.output(ds.getFeatures()).toNumpy()
        out_feat = helper.outputFromFeaturized(feat.getFeatures()).toNumpy()
        np.testing.assert_allclose(out_full, out_feat, rtol=2e-5, atol=2e-6)

    def test_fit_featurized_trains_top_only(self):
        net = _base_net()
        helper = TransferLearningHelper(net, frozenTill=1)
        ds = _data()
        w0 = _p(net, 0, "W").copy()
        feat = helper.featurize(ds)
        s0 = net.score(ds)
        for _ in range(30):
            helper.fitFeaturized(feat)
        assert np.array_equal(w0, _p(net, 0, "W"))  # bottom untouched
        assert net.score(ds) < s0                    # top learned

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_cnn_featurize_layout(self):
        conf = (NeuralNetConfiguration.Builder()
                .seed(1).updater(Adam(1e-2))
                .list()
                .layer(ConvolutionLayer(nOut=4, kernelSize=(3, 3), stride=(1, 1)))
                .layer(ConvolutionLayer(nOut=6, kernelSize=(3, 3), stride=(1, 1)))
                .layer(OutputLayer(nOut=3, activation="softmax", lossFunction=LF.MCXENT))
                .setInputType(InputType.convolutional(8, 8, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        helper = TransferLearningHelper(net, frozenTill=0)
        x = np.random.RandomState(0).rand(4, 1, 8, 8).astype("float32")
        ds = DataSet(x, np.eye(3, dtype="float32")[[0, 1, 2, 0]])
        feat = helper.featurize(ds)
        # API layout: NCHW
        assert feat.getFeatures().shape()[1] == 4
        out_full = net.output(x).toNumpy()
        out_feat = helper.outputFromFeaturized(feat.getFeatures()).toNumpy()
        np.testing.assert_allclose(out_full, out_feat, rtol=2e-5, atol=2e-6)


class TestFrozenInferenceMode:
    def test_frozen_bn_stats_do_not_drift(self):
        """A frozen BatchNormalization must run in inference mode during
        fine-tuning: its running mean/var stay exactly as they were
        (reference: FrozenLayer forces the wrapped layer to inference)."""
        from deeplearning4j_tpu.nn import BatchNormalization

        conf = (NeuralNetConfiguration.Builder()
                .seed(3).updater(Adam(5e-2)).list()
                .layer(DenseLayer(nIn=8, nOut=16, activation="relu"))
                .layer(BatchNormalization())
                .layer(OutputLayer(nOut=3, activation="softmax", lossFunction=LF.MCXENT))
                .setInputType(InputType.feedForward(8))
                .build())
        net = MultiLayerNetwork(conf).init()
        ds = _data()
        net.fit(ds)  # move running stats off their init values
        tl = TransferLearning.Builder(net).setFeatureExtractor(1).build()
        m0 = np.asarray(tl._states[1]["mean"]).copy()
        v0 = np.asarray(tl._states[1]["var"]).copy()
        assert not np.allclose(m0, 0.0)  # stats actually moved pre-freeze
        for _ in range(5):
            tl.fit(ds)
        np.testing.assert_array_equal(m0, np.asarray(tl._states[1]["mean"]))
        np.testing.assert_array_equal(v0, np.asarray(tl._states[1]["var"]))

    def test_frozen_dropout_inactive(self):
        """Dropout in the frozen prefix must be off during fine-tune: two
        fits from identical initial state produce identical top-layer
        updates regardless of the dropout rng."""
        conf = (NeuralNetConfiguration.Builder()
                .seed(3).updater(Sgd(0.1)).list()
                .layer(DenseLayer(nIn=8, nOut=16, activation="relu", dropOut=0.5))
                .layer(OutputLayer(nOut=3, activation="softmax", lossFunction=LF.MCXENT))
                .setInputType(InputType.feedForward(8))
                .build())
        ds = _data()
        outs = []
        for _ in range(2):
            net = MultiLayerNetwork(conf).init()
            tl = TransferLearning.Builder(net).setFeatureExtractor(0).build()
            # different iteration counters => different dropout keys if the
            # frozen layer's dropout were (wrongly) active
            tl._iteration = 7 * len(outs)
            tl.fit(ds)
            outs.append(_p(tl, 1, "W").copy())
        np.testing.assert_array_equal(outs[0], outs[1])


class TestTransferGraphBuilder:
    """TransferLearning.GraphBuilder (reference: the ComputationGraph
    variant) — the classic fine-tune flow on a DAG: freeze the trunk,
    replace the head, graft trained weights."""

    def _graph(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           ComputationGraph, DenseLayer,
                                           OutputLayer, Adam)

        g = (NeuralNetConfiguration.Builder().seed(5).updater(Adam(1e-2))
             .activation("tanh").graphBuilder().addInputs("in")
             .addLayer("trunk1", DenseLayer(nOut=12), "in")
             .addLayer("trunk2", DenseLayer(nOut=10), "trunk1")
             .addLayer("head", OutputLayer(nOut=3, activation="softmax"),
                       "trunk2")
             .setOutputs("head")
             .setInputTypes(InputType.feedForward(6)).build())
        net = ComputationGraph(g).init()
        rng = np.random.RandomState(0)
        x = rng.randn(16, 6).astype("float32")
        y = np.eye(3, dtype="float32")[rng.randint(0, 3, 16)]
        for _ in range(3):
            net.fit(x, y)
        return net

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_replace_head_grafts_trunk_and_freezes(self):
        from deeplearning4j_tpu.nn import TransferLearning, OutputLayer

        orig = self._graph()
        t1 = np.asarray(orig._params["trunk1"]["W"]).copy()
        net = (TransferLearning.GraphBuilder(orig)
               .setFeatureExtractor("trunk2")
               .removeVertexKeepConnections("head")
               .addLayer("head", OutputLayer(nOut=5, activation="softmax"),
                         "trunk2")
               .build())
        # trunk weights grafted, head fresh with the new width
        np.testing.assert_array_equal(
            np.asarray(net._params["trunk1"]["W"]), t1)
        assert net._params["head"]["W"].shape[-1] == 5
        assert net.conf.nodes["trunk1"].payload.frozen
        assert net.conf.nodes["trunk2"].payload.frozen
        assert not getattr(net.conf.nodes["head"].payload, "frozen", False)
        # frozen trunk must not move under training; the new head must
        rng = np.random.RandomState(1)
        x = rng.randn(8, 6).astype("float32")
        y = np.eye(5, dtype="float32")[rng.randint(0, 5, 8)]
        h0 = np.asarray(net._params["head"]["W"]).copy()
        for _ in range(3):
            net.fit(x, y)
        np.testing.assert_array_equal(
            np.asarray(net._params["trunk1"]["W"]), t1)
        assert np.abs(np.asarray(net._params["head"]["W"]) - h0).max() > 0

    def test_nout_replace_refreshes_successor(self):
        from deeplearning4j_tpu.nn import TransferLearning

        orig = self._graph()
        net = (TransferLearning.GraphBuilder(orig)
               .nOutReplace("trunk2", 20)
               .build())
        assert net._params["trunk2"]["W"].shape[-1] == 20
        assert net._params["head"]["W"].shape[0] == 20
        # trunk1 untouched -> grafted
        np.testing.assert_array_equal(
            np.asarray(net._params["trunk1"]["W"]),
            np.asarray(orig._params["trunk1"]["W"]))

    def test_dangling_reference_rejected(self):
        from deeplearning4j_tpu.nn import TransferLearning

        orig = self._graph()
        with pytest.raises(ValueError, match="removed vertex"):
            (TransferLearning.GraphBuilder(orig)
             .removeVertexAndConnections("trunk2").build())

    def test_mln_rejected_with_clear_error(self):
        from deeplearning4j_tpu.nn import (TransferLearning,
                                           NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           OutputLayer)

        conf = (NeuralNetConfiguration.Builder().list()
                .layer(DenseLayer(nOut=4))
                .layer(OutputLayer(nOut=2))
                .setInputType(InputType.feedForward(3)).build())
        with pytest.raises(TypeError, match="ComputationGraph"):
            TransferLearning.GraphBuilder(MultiLayerNetwork(conf).init())

    def test_width_change_propagates_through_vertex(self):
        """nOutReplace upstream of a parameterless vertex (the residual
        case) must re-infer the downstream layer's nIn, not crash in XLA."""
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           ComputationGraph, DenseLayer,
                                           OutputLayer, Adam,
                                           TransferLearning)
        from deeplearning4j_tpu.nn.conf.graph import ScaleVertex

        g = (NeuralNetConfiguration.Builder().seed(2).updater(Adam(1e-2))
             .graphBuilder().addInputs("in")
             .addLayer("trunk1", DenseLayer(nOut=12, activation="tanh"), "in")
             .addVertex("scale", ScaleVertex(0.5), "trunk1")
             .addLayer("head", OutputLayer(nOut=3, activation="softmax"),
                       "scale")
             .setOutputs("head")
             .setInputTypes(InputType.feedForward(6)).build())
        orig = ComputationGraph(g).init()
        net = (TransferLearning.GraphBuilder(orig)
               .nOutReplace("trunk1", 20).build())
        assert net._params["head"]["W"].shape[0] == 20
        rng = np.random.RandomState(0)
        x = rng.randn(8, 6).astype("float32")
        y = np.eye(3, dtype="float32")[rng.randint(0, 3, 8)]
        net.fit(x, y)  # would raise a dot_general shape error before
        assert np.isfinite(net.score())

    def test_removed_output_without_set_outputs_rejected(self):
        from deeplearning4j_tpu.nn import TransferLearning, OutputLayer

        orig = self._graph()
        with pytest.raises(ValueError, match="setOutputs"):
            (TransferLearning.GraphBuilder(orig)
             .removeVertexAndConnections("head")
             .addLayer("newhead", OutputLayer(nOut=2, activation="softmax"),
                       "trunk2")
             .build())

    def test_unknown_nout_replace_name_rejected(self):
        from deeplearning4j_tpu.nn import TransferLearning

        orig = self._graph()
        with pytest.raises(ValueError, match="unknown layer"):
            TransferLearning.GraphBuilder(orig).nOutReplace("trnk1", 20)
