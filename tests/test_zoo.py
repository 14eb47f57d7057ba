"""Model zoo tests (reference: deeplearning4j-zoo TestInstantiation).

Full-size zoo models are too slow for the CPU test mesh, so models are
built at reduced input sizes / widths and checked for: construction,
parameter counts where architecture-defining, one fit step, output shape.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.zoo import (
    LeNet, SimpleCNN, AlexNet, VGG16, ResNet50, TextGenerationLSTM,
)


class TestZoo:
    def test_lenet(self):
        net = LeNet(numClasses=10).init()
        # reference LeNet on 28x28: conv(20)@5x5 -> pool -> conv(50)@5x5 ->
        # pool -> dense(500) -> out(10)
        assert net.numParams() == (20 * 25 + 20) + (50 * 20 * 25 + 50) + \
            (4 * 4 * 50 * 500 + 500) + (500 * 10 + 10)
        x = np.random.RandomState(0).rand(4, 784).astype("float32")
        y = np.eye(10, dtype="float32")[np.random.RandomState(1).randint(0, 10, 4)]
        net.fit(x, y)
        assert net.output(x).shape() == (4, 10)

    def test_resnet50_param_count(self):
        net = ResNet50(numClasses=1000, inputShape=(3, 64, 64)).init()
        # canonical ResNet-50 v1 parameter count (ImageNet head)
        assert abs(net.numParams() - 25_557_032) / 25_557_032 < 0.02

    @pytest.mark.slow  # tier-1 budget (PR 21): 11 s on 8 CPU cores
    def test_resnet50_trains(self):
        from deeplearning4j_tpu.nn import Adam

        # gentle updater: the reference's default (SGD momentum 0.1) is an
        # ImageNet-scale setting; on 2 random images it diverges while BN
        # running stats are still at their init, exactly like the reference.
        net = ResNet50(numClasses=4, inputShape=(3, 32, 32), updater=Adam(1e-4)).init()
        rng = np.random.RandomState(0)
        x = rng.rand(2, 3, 32, 32).astype("float32")
        y = np.eye(4, dtype="float32")[rng.randint(0, 4, 2)]
        losses = []
        for _ in range(3):
            net.fit(x, y)
            losses.append(net.score())
        assert all(np.isfinite(l) for l in losses)
        out = net.outputSingle(x)
        assert out.shape() == (2, 4)
        np.testing.assert_allclose(out.sum(1).toNumpy(), np.ones(2), rtol=1e-3)

    @pytest.mark.slow  # tier-1 budget (PR 21): 6 s on 8 CPU cores
    def test_simplecnn_builds_and_fits(self):
        net = SimpleCNN(numClasses=3, inputShape=(3, 16, 16)).init()
        x = np.random.RandomState(0).rand(2, 3, 16, 16).astype("float32")
        y = np.eye(3, dtype="float32")[np.random.RandomState(1).randint(0, 3, 2)]
        net.fit(x, y)
        assert np.isfinite(net.score())

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_textgen_lstm(self):
        net = TextGenerationLSTM(totalUniqueCharacters=20, maxLength=10).init()
        rng = np.random.RandomState(0)
        idx = rng.randint(0, 20, (2, 10))
        x = np.eye(20, dtype="float32")[idx].transpose(0, 2, 1)
        y = np.eye(20, dtype="float32")[np.roll(idx, -1, axis=1)].transpose(0, 2, 1)
        net.fit(x, y)
        assert np.isfinite(net.score())
        out = net.output(x)
        assert out.shape() == (2, 20, 10)

    def test_pretrained_raises_clearly(self):
        with pytest.raises(NotImplementedError, match="egress"):
            LeNet().initPretrained()

    def test_vgg16_conf_builds(self):
        # construction-only at reduced size (full VGG too heavy for CPU CI)
        conf = VGG16(numClasses=5, inputShape=(3, 32, 32)).conf()
        assert len(conf.layers) == 13 + 5 + 2 + 1  # convs + pools + dense + out


class TestZooDetectionAndSeparable:
    @pytest.mark.slow  # tier-1 budget (PR 21): 9 s on 8 CPU cores
    def test_darknet19(self):
        from deeplearning4j_tpu.zoo import Darknet19

        net = Darknet19(numClasses=10, inputShape=(3, 32, 32)).init()
        x = np.random.RandomState(0).rand(2, 3, 32, 32).astype("float32")
        y = np.eye(10, dtype="float32")[np.random.RandomState(1).randint(0, 10, 2)]
        net.fit(x, y)
        out = net.output(x)
        assert out.shape() == (2, 10)
        np.testing.assert_allclose(out.toNumpy().sum(1), np.ones(2), rtol=1e-3)

    @pytest.mark.slow  # tier-1 budget (PR 21): 6 s on 8 CPU cores
    def test_tiny_yolo(self):
        from deeplearning4j_tpu.zoo import TinyYOLO

        net = TinyYOLO(numClasses=4, inputShape=(3, 64, 64)).init()
        # 64/32 = 2x2 grid; head channels = A*(5+C) = 5*9
        x = np.random.RandomState(0).rand(2, 3, 64, 64).astype("float32")
        out = net.output(x)
        assert out.shape() == (2, 2, 2, 5 * 9)
        lab = np.zeros((2, 4 + 4, 2, 2), np.float32)
        lab[0, 0:4, 0, 0] = (0.1, 0.1, 0.9, 0.9)
        lab[0, 4, 0, 0] = 1.0
        from deeplearning4j_tpu.data import DataSet

        ds = DataSet(x, lab)
        s0 = net.score(ds)
        net.fit(ds)
        assert np.isfinite(s0) and np.isfinite(net.score(ds))

    @pytest.mark.slow  # tier-1 budget (PR 21): 12 s on 8 CPU cores
    def test_squeezenet(self):
        from deeplearning4j_tpu.zoo import SqueezeNet

        net = SqueezeNet(numClasses=7, inputShape=(3, 64, 64)).init()
        x = np.random.RandomState(0).rand(2, 3, 64, 64).astype("float32")
        out = net.outputSingle(x)
        assert out.shape() == (2, 7)
        np.testing.assert_allclose(out.toNumpy().sum(1), np.ones(2), rtol=1e-3)

    @pytest.mark.slow  # tier-1 budget (PR 21): 13 s on 8 CPU cores
    def test_xception(self):
        from deeplearning4j_tpu.zoo import Xception

        # tiny middle flow to keep the CPU test fast
        net = Xception(numClasses=5, inputShape=(3, 64, 64), middleFlowBlocks=1).init()
        x = np.random.RandomState(0).rand(2, 3, 64, 64).astype("float32")
        out = net.outputSingle(x)
        assert out.shape() == (2, 5)
        np.testing.assert_allclose(out.toNumpy().sum(1), np.ones(2), rtol=1e-3)


class TestZooTailConvergence:
    """Convergence depth for the zoo tail: each model
    must FIT — decreasing loss on a small separable synthetic set — not
    merely construct. Mirrors the ResNet-50/LeNet treatment."""

    def _cluster_data(self, n, C, hw, classes, seed=0):
        rng = np.random.RandomState(seed)
        templates = rng.rand(classes, C, hw, hw).astype("float32")
        yi = rng.randint(0, classes, n)
        x = 0.8 * templates[yi] + 0.2 * rng.rand(n, C, hw, hw).astype("float32")
        return x, np.eye(classes, dtype="float32")[yi], yi

    def _assert_converges(self, net, x, y, iters=12, factor=0.7):
        first = None
        for _ in range(iters):
            net.fit(x, y)
            first = first if first is not None else net.score()
        assert np.isfinite(net.score())
        assert net.score() < factor * first, \
            f"loss {first} -> {net.score()} (no convergence)"

    @pytest.mark.slow  # tier-1 budget (PR 21): 8 s on 8 CPU cores
    def test_darknet19_converges(self):
        from deeplearning4j_tpu.zoo import Darknet19
        from deeplearning4j_tpu.nn import Adam

        net = Darknet19(numClasses=3, inputShape=(3, 32, 32),
                        updater=Adam(3e-4)).init()
        x, y, _ = self._cluster_data(8, 3, 32, 3)
        self._assert_converges(net, x, y)

    @pytest.mark.slow  # tier-1 budget (PR 21): 7 s on 8 CPU cores
    def test_squeezenet_converges(self):
        from deeplearning4j_tpu.zoo import SqueezeNet
        from deeplearning4j_tpu.nn import Adam

        # 64px: SqueezeNet's stride-heavy stem starves fire modules at 32px
        net = SqueezeNet(numClasses=3, inputShape=(3, 64, 64),
                         updater=Adam(5e-4)).init()
        x, y, _ = self._cluster_data(8, 3, 64, 3)
        self._assert_converges(net, x, y, iters=20)

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_xception_converges(self):
        from deeplearning4j_tpu.zoo import Xception
        from deeplearning4j_tpu.nn import Adam

        net = Xception(numClasses=3, inputShape=(3, 32, 32),
                       middleFlowBlocks=1, updater=Adam(3e-4)).init()
        x, y, _ = self._cluster_data(8, 3, 32, 3)
        self._assert_converges(net, x, y)

    @pytest.mark.slow  # tier-1 budget (PR 21): 5 s on 8 CPU cores
    def test_tiny_yolo_converges(self):
        from deeplearning4j_tpu.zoo import TinyYOLO
        from deeplearning4j_tpu.nn import Adam
        from deeplearning4j_tpu.data import DataSet

        net = TinyYOLO(numClasses=2, inputShape=(3, 32, 32),
                       updater=Adam(1e-3)).init()
        rng = np.random.RandomState(0)
        x = rng.rand(4, 3, 32, 32).astype("float32")
        # one object per image on the 1x1 grid (32/32)
        lab = np.zeros((4, 4 + 2, 1, 1), np.float32)
        for i in range(4):
            lab[i, 0:4, 0, 0] = (0.2, 0.2, 0.8, 0.8)
            lab[i, 4 + (i % 2), 0, 0] = 1.0
        ds = DataSet(x, lab)
        losses = [net.score(ds)]
        for _ in range(20):
            net.fit(ds)
            losses.append(net.score(ds))
        assert all(np.isfinite(l) for l in losses)
        # composite YOLO loss dips then plateaus as the confidence term
        # balances; judge convergence by the best loss reached
        assert min(losses) < 0.7 * losses[0], \
            f"yolo loss {losses[0]} -> best {min(losses)}"


class TestSpaceToDepthStem:
    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_s2d_stem_exact_parity_with_standard(self):
        """The space-to-depth stem with mapped weights computes EXACTLY the
        standard 7x7/s2 stem's function (MLPerf conv1 rewrite)."""
        from deeplearning4j_tpu.zoo import ResNet50

        std = ResNet50(numClasses=4, inputShape=(3, 64, 64)).init()
        s2d = ResNet50(numClasses=4, inputShape=(3, 64, 64),
                       stemMode="space_to_depth").init()
        # port every param across; conv1 gets the rearranged kernel
        import jax.numpy as jnp

        for name, p in std._params.items():
            if name == "conv1":
                s2d._params["conv1"]["W"] = jnp.asarray(
                    ResNet50.stem_weights_to_s2d(p["W"]))
            elif name in s2d._params:
                s2d._params[name] = p
        s2d._states = {n: (std._states[n] if n in std._states else s)
                       for n, s in s2d._states.items()}
        x = np.random.RandomState(0).rand(2, 3, 64, 64).astype("float32")
        a = std.outputSingle(x).toNumpy()
        b = s2d.outputSingle(x).toNumpy()
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    @pytest.mark.slow  # tier-1 budget (PR 21): 7 s on 8 CPU cores
    def test_s2d_stem_trains(self):
        from deeplearning4j_tpu.zoo import ResNet50
        from deeplearning4j_tpu.nn import Adam

        net = ResNet50(numClasses=3, inputShape=(3, 32, 32),
                       stemMode="space_to_depth", updater=Adam(1e-4)).init()
        rng = np.random.RandomState(0)
        x = rng.rand(2, 3, 32, 32).astype("float32")
        y = np.eye(3, dtype="float32")[rng.randint(0, 3, 2)]
        net.fit(x, y)
        assert np.isfinite(net.score())

    def test_bad_stem_mode(self):
        from deeplearning4j_tpu.zoo import ResNet50

        with pytest.raises(ValueError, match="stemMode"):
            ResNet50(stemMode="nope")


class TestZooUpstreamTail:
    """The remaining upstream zoo entries (reference:
    org.deeplearning4j.zoo.model.{YOLO2, InceptionResNetV1,
    FaceNetNN4Small2, NASNet}), built at reduced size for the CPU mesh:
    construction, forward shape, and a finite fit step each."""

    @pytest.mark.slow  # tier-1 budget (PR 21): 12 s on 8 CPU cores
    def test_yolo2_builds_and_fits(self):
        from deeplearning4j_tpu.zoo import YOLO2
        from deeplearning4j_tpu.data import DataSet

        net = YOLO2(numClasses=3, inputShape=(3, 64, 64),
                    anchors=((1.0, 1.0), (2.0, 2.0))).init()
        x = np.random.RandomState(0).rand(2, 3, 64, 64).astype("float32")
        # 64px / 32 stride = 2x2 grid; head = A*(5+C) = 2*8 channels
        # (ComputationGraph API boundary is NCHW)
        out = net.output(x)
        assert out.shape() == (2, 2 * 8, 2, 2)
        lab = np.zeros((2, 4 + 3, 2, 2), np.float32)
        # box center (1.5, 0.25) in grid units lies in cell row 0, col 1 —
        # the cell the label occupies (labels-at-center-cell convention)
        lab[0, 0:4, 0, 1] = (1.1, 0.1, 1.9, 0.4)
        lab[0, 5, 0, 1] = 1.0
        ds = DataSet(x, lab)
        net.fit(ds)
        assert np.isfinite(net.score(ds))

    def test_yolo2_passthrough_wiring(self):
        from deeplearning4j_tpu.zoo import YOLO2

        conf = YOLO2(numClasses=3, inputShape=(3, 64, 64),
                     anchors=((1.0, 1.0),)).conf()
        names = set(conf.nodes)
        assert {"route_s2d", "route_cat"} <= names

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_inception_resnet_v1(self):
        from deeplearning4j_tpu.zoo import InceptionResNetV1

        net = InceptionResNetV1(numClasses=5, embeddingSize=16,
                                inputShape=(3, 96, 96)).init()
        x = np.random.RandomState(0).rand(2, 3, 96, 96).astype("float32")
        out = net.outputSingle(x)
        assert out.shape() == (2, 5)
        np.testing.assert_allclose(out.toNumpy().sum(1), np.ones(2),
                                   rtol=1e-3)
        # L2-normalized embedding feeds the center-loss head
        emb = net.feedForward(x)["embeddings"]
        np.testing.assert_allclose(
            np.linalg.norm(emb.toNumpy(), axis=1), np.ones(2), rtol=1e-3)
        y = np.eye(5, dtype="float32")[np.random.RandomState(1).randint(0, 5, 2)]
        net.fit(x, y)
        assert np.isfinite(net.score())

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_facenet_nn4_small2(self):
        from deeplearning4j_tpu.zoo import FaceNetNN4Small2

        net = FaceNetNN4Small2(numClasses=6, embeddingSize=16,
                               inputShape=(3, 64, 64)).init()
        x = np.random.RandomState(0).rand(2, 3, 64, 64).astype("float32")
        out = net.outputSingle(x)
        assert out.shape() == (2, 6)
        emb = net.feedForward(x)["embeddings"]
        np.testing.assert_allclose(
            np.linalg.norm(emb.toNumpy(), axis=1), np.ones(2), rtol=1e-3)
        y = np.eye(6, dtype="float32")[np.random.RandomState(1).randint(0, 6, 2)]
        net.fit(x, y)
        assert np.isfinite(net.score())

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_nasnet(self):
        from deeplearning4j_tpu.zoo import NASNet

        net = NASNet(numClasses=4, numCells=1, penultimateFilters=96,
                     stemFilters=8, inputShape=(3, 64, 64)).init()
        x = np.random.RandomState(0).rand(2, 3, 64, 64).astype("float32")
        out = net.outputSingle(x)
        assert out.shape() == (2, 4)
        np.testing.assert_allclose(out.toNumpy().sum(1), np.ones(2),
                                   rtol=1e-3)
        y = np.eye(4, dtype="float32")[np.random.RandomState(1).randint(0, 4, 2)]
        net.fit(x, y)
        assert np.isfinite(net.score())

    # never reached under the 870 s limit before PR 21; at the end of a
    # complete tier-1 run its compile aborts XLA:CPU (executable buildup,
    # ROADMAP Known-remaining) and takes the whole run's exit code with it
    @pytest.mark.slow
    def test_facenet_converges(self):
        """Convergence depth for the round-3 zoo additions: the center-
        loss inception trunk must FIT, not merely construct (the other
        three new models are covered by fit-smoke above; their per-iter
        CPU cost is too high for a convergence loop in CI)."""
        from deeplearning4j_tpu.zoo import FaceNetNN4Small2
        from deeplearning4j_tpu.nn import Adam

        rng = np.random.RandomState(0)
        templates = rng.rand(3, 3, 64, 64).astype("float32")
        yi = rng.randint(0, 3, 8)
        x = 0.8 * templates[yi] + 0.2 * rng.rand(8, 3, 64, 64).astype("float32")
        y = np.eye(3, dtype="float32")[yi]
        net = FaceNetNN4Small2(numClasses=3, embeddingSize=16,
                               inputShape=(3, 64, 64),
                               updater=Adam(3e-4)).init()
        first = None
        for _ in range(10):
            net.fit(x, y)
            first = first if first is not None else net.score()
        assert np.isfinite(net.score())
        assert net.score() < 0.6 * first, (first, net.score())
