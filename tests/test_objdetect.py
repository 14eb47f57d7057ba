"""YOLOv2 detection head + utils (reference: deeplearning4j-core
org.deeplearning4j.nn.layers.objdetect.TestYolo2OutputLayer)."""

import numpy as np
import pytest

from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, ConvolutionLayer, InputType, MultiLayerNetwork, Adam,
)
from deeplearning4j_tpu.nn.objdetect import (
    Yolo2OutputLayer, DetectedObject, YoloUtils,
)
from deeplearning4j_tpu.data import DataSet

ANCHORS = ((1.0, 1.0), (2.5, 2.5))
C = 3      # classes
A = len(ANCHORS)
G = 4      # grid
IN = 16    # input resolution (stride 4)


def _net(seed=7, lr=1e-2):
    conf = (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Adam(lr))
            .list()
            .layer(ConvolutionLayer(nOut=16, kernelSize=(3, 3),
                                    convolutionMode="same", activation="relu"))
            .layer(ConvolutionLayer(nOut=16, kernelSize=(4, 4), stride=(4, 4),
                                    activation="relu"))
            .layer(ConvolutionLayer(nOut=A * (5 + C), kernelSize=(1, 1),
                                    activation="identity"))
            .layer(Yolo2OutputLayer(boundingBoxes=ANCHORS))
            .setInputType(InputType.convolutional(IN, IN, 1))
            .build())
    return MultiLayerNetwork(conf).init()


def _labels(boxes):
    """boxes: [(b, x1, y1, x2, y2, cls)...] in grid units -> [B,4+C,G,G]."""
    lab = np.zeros((2, 4 + C, G, G), np.float32)
    for (b, x1, y1, x2, y2, cls) in boxes:
        cx, cy = int((x1 + x2) / 2), int((y1 + y2) / 2)
        lab[b, 0:4, cy, cx] = (x1, y1, x2, y2)
        lab[b, 4 + cls, cy, cx] = 1.0
    return lab


class TestYoloLoss:
    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_loss_finite_and_positive(self):
        net = _net()
        x = np.random.RandomState(0).rand(2, 1, IN, IN).astype("float32")
        y = _labels([(0, 0.2, 0.3, 1.4, 1.8, 0), (1, 2.0, 2.0, 3.5, 3.9, 2)])
        s = net.score(DataSet(x, y))
        assert np.isfinite(s) and s > 0

    def test_training_decreases_loss(self):
        net = _net()
        x = np.random.RandomState(0).rand(2, 1, IN, IN).astype("float32")
        y = _labels([(0, 0.2, 0.3, 1.4, 1.8, 0), (1, 2.0, 2.0, 3.5, 3.9, 2)])
        ds = DataSet(x, y)
        s0 = net.score(ds)
        for _ in range(60):
            net.fit(ds)
        assert net.score(ds) < s0 * 0.5

    def test_no_objects_only_noobj_term(self):
        net = _net()
        x = np.random.RandomState(0).rand(2, 1, IN, IN).astype("float32")
        y = np.zeros((2, 4 + C, G, G), np.float32)
        s = net.score(DataSet(x, y))
        assert np.isfinite(s) and s >= 0

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_overfit_then_detect(self):
        # train hard on one example; the head must localize the box
        net = _net(lr=5e-2)
        rng = np.random.RandomState(1)
        x = rng.rand(2, 1, IN, IN).astype("float32")
        y = _labels([(0, 1.0, 1.0, 2.0, 2.0, 1), (1, 2.2, 0.1, 3.8, 1.9, 2)])
        ds = DataSet(x, y)
        for _ in range(250):
            net.fit(ds)
        out = net.output(x)
        layer = net.layers[-1]
        dets = YoloUtils.getPredictedObjects(layer, out, threshold=0.5,
                                             nmsThreshold=0.4)
        ex0 = [d for d in dets if d.exampleNumber == 0]
        assert ex0, "no detections for example 0"
        best = max(ex0, key=lambda d: d.confidence)
        assert best.predictedClass == 1
        assert abs(best.centerX - 1.5) < 0.5 and abs(best.centerY - 1.5) < 0.5

    @pytest.mark.slow  # tier-1 budget (PR 21): 10 s on 8 CPU cores
    def test_gradients_flow(self):
        net = _net()
        x = np.random.RandomState(0).rand(2, 1, IN, IN).astype("float32")
        y = _labels([(0, 0.2, 0.3, 1.4, 1.8, 0)])
        grads, score = net.computeGradientAndScore(x, y)
        flat = [np.asarray(g) for layer in grads for g in layer.values()]
        assert all(np.isfinite(g).all() for g in flat)
        assert any(np.abs(g).max() > 0 for g in flat)


class TestYoloUtils:
    def _det(self, cx, cy, w, h, cls=0, conf=0.9, ex=0):
        return DetectedObject(ex, cx, cy, w, h, cls, None, conf)

    def test_iou(self):
        a = self._det(1.0, 1.0, 2.0, 2.0)
        assert YoloUtils.iou(a, a) == pytest.approx(1.0)
        b = self._det(3.0, 1.0, 2.0, 2.0)  # adjacent, no overlap
        assert YoloUtils.iou(a, b) == pytest.approx(0.0)
        c = self._det(2.0, 1.0, 2.0, 2.0)  # half overlap
        assert YoloUtils.iou(a, c) == pytest.approx(1.0 / 3.0)

    def test_nms_suppresses_same_class_only(self):
        d1 = self._det(1.0, 1.0, 2.0, 2.0, cls=0, conf=0.9)
        d2 = self._det(1.1, 1.0, 2.0, 2.0, cls=0, conf=0.7)  # overlaps d1
        d3 = self._det(1.1, 1.0, 2.0, 2.0, cls=1, conf=0.6)  # other class
        d4 = self._det(5.0, 5.0, 2.0, 2.0, cls=0, conf=0.8)  # far away
        keep = YoloUtils.nonMaxSuppression([d1, d2, d3, d4], 0.4)
        assert d1 in keep and d3 in keep and d4 in keep
        assert d2 not in keep

    def test_corner_accessors(self):
        d = self._det(2.0, 3.0, 2.0, 4.0)
        assert d.getTopLeftXY() == (1.0, 1.0)
        assert d.getBottomRightXY() == (3.0, 5.0)
