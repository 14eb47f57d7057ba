"""ZooModel.initPretrained(localFile) with real tf.keras oracles.

Reference: deeplearning4j-zoo ZooModel.initPretrained — upstream downloads
published weights; here the user supplies a local Keras-applications h5
and zoo.pretrained maps it onto the native graph. The oracle is the
actual keras.applications model with the SAME (random) weights: its
predict() output is the golden activation the loaded native net must
reproduce.
"""

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")
keras = tf.keras

from deeplearning4j_tpu.zoo import ResNet50, VGG16, LeNet  # noqa: E402
from deeplearning4j_tpu.zoo.pretrained import convertPretrained  # noqa: E402
from deeplearning4j_tpu.modelimport.keras import (  # noqa: E402
    InvalidKerasConfigurationException,
)


@pytest.fixture(scope="module")
def resnet_h5(tmp_path_factory):
    """Small-input keras.applications.ResNet50 (random weights, seeded),
    saved in the legacy h5 layout + its golden predict() output."""
    keras.utils.set_random_seed(7)
    km = keras.applications.ResNet50(weights=None, include_top=True,
                                     input_shape=(64, 64, 3), classes=10)
    path = str(tmp_path_factory.mktemp("resnet") / "resnet50.h5")
    km.save(path)
    rng = np.random.RandomState(0)
    x = rng.rand(2, 64, 64, 3).astype("float32")
    golden = km.predict(x, verbose=0)
    return path, x, golden


class TestResNet50Pretrained:
    @pytest.mark.slow  # tier-1 budget (PR 21): 15 s on 8 CPU cores
    def test_golden_activation_parity(self, resnet_h5):
        path, x, golden = resnet_h5
        model = ResNet50(numClasses=10, inputShape=(3, 64, 64))
        net = model.initPretrained(localFile=path)
        ours = np.asarray(net.output(x.transpose(0, 3, 1, 2)).jax())
        np.testing.assert_allclose(ours, golden, rtol=1e-3, atol=1e-5)

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_convert_to_native_checkpoint_roundtrip(self, resnet_h5, tmp_path):
        path, x, golden = resnet_h5
        model = ResNet50(numClasses=10, inputShape=(3, 64, 64))
        ckpt = str(tmp_path / "resnet50_native.dl4j.npz")
        net = convertPretrained(model, path, ckpt)
        restored = model.initPretrained(localFile=ckpt)
        a = np.asarray(net.output(x.transpose(0, 3, 1, 2)).jax())
        b = np.asarray(restored.output(x.transpose(0, 3, 1, 2)).jax())
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(b, golden, rtol=1e-3, atol=1e-5)

    @pytest.mark.slow  # tier-1 budget (round 6): heavy compile-parity leg
    def test_transfer_learning_finetunes_from_pretrained(self, resnet_h5):
        from deeplearning4j_tpu.nn.transfer import TransferLearning

        path, x, _ = resnet_h5
        model = ResNet50(numClasses=10, inputShape=(3, 64, 64))
        net = model.initPretrained(localFile=path)
        tnet = (TransferLearning.GraphBuilder(net)
                .setFeatureExtractor("gap")       # freeze the whole backbone
                .nOutReplace("fc", 3)             # new 3-class head
                .build())
        rng = np.random.RandomState(1)
        xb = rng.rand(8, 3, 64, 64).astype("float32")
        yb = np.eye(3, dtype="float32")[rng.randint(0, 3, 8)]
        losses = []
        for _ in range(8):
            tnet.fit(xb, [yb])
            losses.append(tnet.score())
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0], losses

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_wrong_architecture_h5_is_loud(self, resnet_h5, tmp_path):
        path, _, _ = resnet_h5
        model = VGG16(numClasses=10, inputShape=(3, 64, 64))
        with pytest.raises(InvalidKerasConfigurationException,
                           match="block1_conv1"):
            model.initPretrained(localFile=path)

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_unmapped_model_is_loud(self, resnet_h5):
        path, _, _ = resnet_h5
        with pytest.raises(InvalidKerasConfigurationException,
                           match="no Keras-applications weight mapping"):
            LeNet(numClasses=10).initPretrained(localFile=path)

    def test_no_file_keeps_no_egress_error(self):
        with pytest.raises(NotImplementedError, match="localFile"):
            ResNet50(numClasses=10).initPretrained()
        # upstream-style positional PretrainedType call: same clear error,
        # not a FileNotFoundError on a path named "imagenet"
        with pytest.raises(NotImplementedError, match="imagenet"):
            ResNet50(numClasses=10).initPretrained("imagenet")

    def test_missing_file_is_loud(self):
        with pytest.raises(FileNotFoundError, match="no/such/file"):
            ResNet50(numClasses=10).initPretrained(
                localFile="/no/such/file.h5")


class TestVGG16Pretrained:
    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_golden_activation_parity(self, tmp_path):
        keras.utils.set_random_seed(11)
        km = keras.applications.VGG16(weights=None, include_top=True,
                                      input_shape=(48, 48, 3), classes=10)
        path = str(tmp_path / "vgg16.h5")
        km.save(path)
        rng = np.random.RandomState(2)
        x = rng.rand(2, 48, 48, 3).astype("float32")
        golden = km.predict(x, verbose=0)
        model = VGG16(numClasses=10, inputShape=(3, 48, 48))
        net = model.initPretrained(localFile=path)
        ours = np.asarray(net.output(x.transpose(0, 3, 1, 2)).jax())
        np.testing.assert_allclose(ours, golden, rtol=1e-3, atol=1e-5)


class TestKeras3ArchivePretrained:
    @pytest.mark.slow  # tier-1 budget (PR 21): 7 s on 8 CPU cores
    def test_resnet50_from_keras_archive(self, tmp_path):
        # .keras archives carry config layer names (conv1_conv etc.) via
        # the recomputed-group-name loader, so the SAME name map applies
        keras.utils.set_random_seed(17)
        km = keras.applications.ResNet50(weights=None, include_top=True,
                                         input_shape=(64, 64, 3),
                                         classes=7)
        path = str(tmp_path / "resnet50.keras")
        km.save(path)
        rng = np.random.RandomState(5)
        x = rng.rand(2, 64, 64, 3).astype("float32")
        golden = km.predict(x, verbose=0)
        model = ResNet50(numClasses=7, inputShape=(3, 64, 64))
        net = model.initPretrained(localFile=path)
        ours = np.asarray(net.output(x.transpose(0, 3, 1, 2)).jax())
        np.testing.assert_allclose(ours, golden, rtol=1e-3, atol=1e-5)
