"""Buffer compression + int8 quantized inference.

Reference strategy: nd4j's CompressionTests (round-trip every codec,
ratio sanity, default-algo switching) plus a measured accuracy-delta
check for the TPU-first dequant-on-use inference path.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.ndarray import (BasicNDArrayCompressor,
                                        CompressedNDArray, Int8Inference,
                                        Nd4j)
from deeplearning4j_tpu.ndarray.compression import (dequantize,
                                                    quantize_int8,
                                                    quantized_bytes, QLeaf)


class TestCodecs:
    def setup_method(self):
        self.c = Nd4j.getCompressor()
        self.c.setDefaultCompression("GZIP")

    def test_singleton_and_catalog(self):
        assert self.c is BasicNDArrayCompressor.getInstance()
        assert set(self.c.getAvailableCompressors()) == \
            {"GZIP", "FLOAT16", "INT8", "THRESHOLD", "NOOP"}

    def test_gzip_lossless_roundtrip(self):
        x = Nd4j.rand(17, 9, seed=3)
        ca = self.c.compress(x, "GZIP")
        assert isinstance(ca, CompressedNDArray) and ca.isCompressed()
        back = self.c.decompress(ca)
        np.testing.assert_array_equal(back.toNumpy(), x.toNumpy())
        # structured data compresses; ratio on zeros is tiny
        z = self.c.compress(Nd4j.zeros(64, 64))
        assert z.ratio() < 0.05

    def test_float16_bounded_loss(self):
        x = np.random.RandomState(0).randn(32, 8).astype("float32")
        back = self.c.decompress(self.c.compress(x, "FLOAT16")).toNumpy()
        assert back.dtype == np.float32
        np.testing.assert_allclose(back, x, rtol=1e-3, atol=1e-3)
        assert self.c.compress(x, "FLOAT16").ratio() == pytest.approx(0.5)

    def test_int8_bounded_loss_and_ratio(self):
        x = np.random.RandomState(1).randn(64, 16).astype("float32")
        ca = self.c.compress(x, "INT8")
        back = self.c.decompress(ca).toNumpy()
        # absmax affine: error bounded by half a quantization step
        step = np.abs(x).max() / 127.0
        assert np.abs(back - x).max() <= step / 2 + 1e-7
        assert ca.ratio() == pytest.approx(0.25, abs=0.01)

    def test_noop_identity(self):
        x = np.arange(12.0).reshape(3, 4)
        back = self.c.decompress(self.c.compress(x, "NOOP")).toNumpy()
        np.testing.assert_array_equal(back, x)

    def test_default_algo_switch_and_errors(self):
        self.c.setDefaultCompression("INT8")
        assert self.c.getDefaultCompression() == "INT8"
        assert self.c.compress(np.ones((2, 2), "float32")).algo == "INT8"
        with pytest.raises(ValueError, match="unknown compressor"):
            self.c.setDefaultCompression("LZ4")
        with pytest.raises(ValueError, match="float"):
            self.c.compress(np.ones((2, 2), np.int32), "FLOAT16")
        self.c.setDefaultCompression("GZIP")

    def test_int_arrays_gzip_roundtrip(self):
        x = np.random.RandomState(2).randint(-5, 5, (10, 10))
        back = self.c.decompress(self.c.compress(x, "GZIP")).toNumpy()
        np.testing.assert_array_equal(back, x)


class TestInt8Quantization:
    def test_quantize_dequantize_pytree(self):
        params = [{"W": np.random.RandomState(0).randn(128, 64)
                   .astype("float32"),
                   "b": np.zeros(64, "float32")}]
        qp = quantize_int8(params)
        assert isinstance(qp[0]["W"], QLeaf)
        assert qp[0]["W"].q.dtype == np.int8
        assert not isinstance(qp[0]["b"], QLeaf)  # 1-D stays fp
        back = dequantize(qp)
        # per-channel absmax: each column's error within half a step
        W = params[0]["W"]
        steps = np.abs(W).max(0) / 127.0
        assert (np.abs(np.asarray(back[0]["W"]) - W).max(0)
                <= steps / 2 + 1e-7).all()
        qb, fb = quantized_bytes(qp)
        assert qb < 0.3 * fb

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_quantized_network_accuracy_delta(self):
        """Train a classifier to high accuracy, quantize, measure the
        delta — the int8 path must stay within 2 points of fp32 top-1
        and agree with fp32 on >95% of predictions."""
        from deeplearning4j_tpu.nn import (Adam, DenseLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           OutputLayer, WeightInit)

        rng = np.random.RandomState(0)
        x = rng.randn(512, 10).astype("float32")
        y_idx = np.argmax(x @ rng.randn(10, 4), axis=1)
        y = np.eye(4, dtype="float32")[y_idx]
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(5e-3))
                .weightInit(WeightInit.XAVIER).activation("relu").list()
                .layer(DenseLayer(nOut=32))
                .layer(DenseLayer(nOut=16))
                .layer(OutputLayer(nOut=4, activation="softmax",
                                   lossFunction="mcxent"))
                .setInputType(InputType.feedForward(10)).build())
        net = MultiLayerNetwork(conf).init()
        net.fitSteps(x, y, numSteps=150)

        fp_pred = net.output(x).argMax(1).toNumpy()
        fp_acc = (fp_pred == y_idx).mean()
        assert fp_acc > 0.9  # the delta only means something off a good model

        q = Int8Inference(net)
        q_pred = q.output(x).argMax(1).toNumpy()
        assert (q_pred == fp_pred).mean() > 0.95
        assert abs((q_pred == y_idx).mean() - fp_acc) < 0.02
        assert q.memoryRatio() < 0.35


class TestInt8ZooGraph:
    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_resnet50_graph_int8_logit_parity(self):
        """The zoo bar: Int8Inference must wrap a zoo
        ComputationGraph (ResNet-50) and track its fp32 logits — cosine
        > 0.995 and >=90% top-1 agreement on the synthetic harness."""
        from deeplearning4j_tpu.ndarray import DataType
        from deeplearning4j_tpu.nn import Nesterovs
        from deeplearning4j_tpu.zoo import ResNet50

        net = ResNet50(numClasses=10, inputShape=(3, 32, 32),
                       updater=Nesterovs(0.1, 0.9),
                       dataType=DataType.FLOAT).init()
        rng = np.random.RandomState(0)
        x = rng.rand(16, 3, 32, 32).astype("float32")
        fp = net.output(x).toNumpy()
        q = Int8Inference(net)
        qo = q.output(x).toNumpy()
        assert qo.shape == fp.shape
        num = (fp * qo).sum()
        cos = num / (np.linalg.norm(fp) * np.linalg.norm(qo) + 1e-12)
        assert cos > 0.995, cos
        agree = (fp.argmax(1) == qo.argmax(1)).mean()
        assert agree >= 0.9, agree
        assert q.memoryRatio() < 0.35  # 25.6M params: int8 dominates
