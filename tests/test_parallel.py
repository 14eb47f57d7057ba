"""Distributed tests on the virtual 8-device CPU mesh.

Mirrors the reference's Spark distributed parity tests (gradient-sharing
result == local result) plus TPU-first coverage the reference lacks:
tensor-parallel shardings and ring-attention sequence parallelism.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, InputType, MultiLayerNetwork,
    DenseLayer, OutputLayer, Adam, Sgd,
)
from deeplearning4j_tpu.data import DataSetIterator
from deeplearning4j_tpu.parallel import (
    build_mesh, data_parallel_mesh, ParallelWrapper, SharedTrainingMaster,
    ParameterAveragingTrainingMaster,
    shard_params, spec_for_param, ring_attention, ulysses_attention,
    DATA_AXIS, MODEL_AXIS, SEQ_AXIS,
)


def _mlp(seed=42):
    return (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Adam(1e-2)).activation("relu")
            .list()
            .layer(DenseLayer(nOut=32))
            .layer(OutputLayer(nOut=3, activation="softmax"))
            .setInputType(InputType.feedForward(4))
            .build())


def _data(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype("float32")
    w = rng.randn(4, 3)
    yi = np.argmax(x @ w, axis=1)
    return x, np.eye(3, dtype="float32")[yi], yi


class TestMesh:
    def test_eight_devices(self):
        assert len(jax.devices()) == 8

    def test_build_mesh_infer(self):
        mesh = build_mesh({"data": -1, "model": 2})
        assert mesh.shape == {"data": 4, "model": 2}

    def test_build_mesh_too_large(self):
        with pytest.raises(ValueError, match="devices"):
            build_mesh({"data": 16})

    def test_build_mesh_subset(self):
        mesh = build_mesh({"data": 3})  # fewer than available is fine
        assert mesh.shape == {"data": 3}


class TestDataParallel:
    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_dp_matches_single_device(self):
        """Gradient sharing over the mesh must produce bit-identical params
        to single-device training on the same global batch (the property
        the reference's parameter averaging only approximates)."""
        x, y, _ = _data(64)

        net_a = MultiLayerNetwork(_mlp()).init()
        for _ in range(5):
            net_a.fit(x, y)
        pa = net_a.params().toNumpy()

        net_b = MultiLayerNetwork(_mlp()).init()
        pw = ParallelWrapper(net_b, mesh=data_parallel_mesh())
        for _ in range(5):
            pw.fit(x, y)
        pb = net_b.params().toNumpy()
        np.testing.assert_allclose(pa, pb, rtol=1e-5, atol=1e-6)

    def test_dp_iterator_training_converges(self):
        x, y, yi = _data(256)
        net = MultiLayerNetwork(_mlp()).init()
        pw = ParallelWrapper(net)
        it = DataSetIterator(x, y, 64, shuffle=True)
        for _ in range(20):
            pw.fit(it)
        acc = (net.output(x).argMax(1).toNumpy() == yi).mean()
        assert acc > 0.9

    def test_dp_batch_not_divisible_raises(self):
        x, y, _ = _data(30)  # 30 % 8 != 0
        net = MultiLayerNetwork(_mlp()).init()
        pw = ParallelWrapper(net)
        with pytest.raises(ValueError, match="divisible"):
            pw.fit(x, y)

    def test_params_replicated_after_dp(self):
        x, y, _ = _data(64)
        net = MultiLayerNetwork(_mlp()).init()
        pw = ParallelWrapper(net)
        pw.fit(x, y)
        leaf = jax.tree_util.tree_leaves(net._params)[0]
        assert leaf.sharding.is_fully_replicated

    def test_quantized_allreduce_close_to_exact(self):
        """SharedTrainingMaster enables int8 gradient compression by
        default — the caller must NOT need to opt in."""
        x, y, _ = _data(64)
        net_a = MultiLayerNetwork(_mlp()).init()
        for _ in range(3):
            net_a.fit(x, y)
        net_b = MultiLayerNetwork(_mlp()).init()
        pw = SharedTrainingMaster(net_b)
        assert pw.gradient_compression == "int8"
        for _ in range(3):
            pw.fit(x, y)
        pa, pb = net_a.params().toNumpy(), net_b.params().toNumpy()
        # int8 quantization: close but not exact
        assert np.max(np.abs(pa - pb)) < 5e-2
        assert not np.allclose(pa, pb, atol=0)

    def test_shared_master_dense_opt_out(self):
        x, y, _ = _data(64)
        net_a = MultiLayerNetwork(_mlp()).init()
        for _ in range(3):
            net_a.fit(x, y)
        net_b = MultiLayerNetwork(_mlp()).init()
        pw = SharedTrainingMaster(net_b, gradient_compression=None)
        assert pw.gradient_compression is None
        for _ in range(3):
            pw.fit(x, y)
        np.testing.assert_allclose(net_a.params().toNumpy(),
                                   net_b.params().toNumpy(),
                                   rtol=1e-5, atol=1e-6)


class TestParameterAveraging:
    def _sgd_mlp(self, seed=42):
        return (NeuralNetConfiguration.Builder()
                .seed(seed).updater(Sgd(0.1)).activation("relu")
                .list()
                .layer(DenseLayer(nOut=32))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.feedForward(4))
                .build())

    def test_freq1_sgd_matches_sync(self):
        """averagingFrequency=1 + plain SGD: mean of one-local-step params
        equals the synchronous gradient-sharing step exactly."""
        x, y, _ = _data(64)
        net_a = MultiLayerNetwork(self._sgd_mlp()).init()
        for _ in range(4):
            net_a.fit(x, y)
        net_b = MultiLayerNetwork(self._sgd_mlp()).init()
        pm = ParameterAveragingTrainingMaster(net_b, averagingFrequency=1)
        for _ in range(4):
            pm.fit(x, y)
        np.testing.assert_allclose(net_a.params().toNumpy(),
                                   net_b.params().toNumpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_replicas_diverge_then_average(self):
        """Between averaging points replicas drift apart (local steps);
        right after an averaging step all replicas are identical."""
        x, y, _ = _data(64, seed=3)
        net = MultiLayerNetwork(_mlp()).init()
        pm = ParameterAveragingTrainingMaster(net, averagingFrequency=5)
        for _ in range(3):  # its 0,1,2 — no averaging yet
            pm.fit(x, y)
        leaf = jax.tree_util.tree_leaves(pm._stacked[0])[0]
        spread = float(jnp.max(jnp.abs(leaf - leaf.mean(0, keepdims=True))))
        assert spread > 0, "replicas should drift between averaging points"
        for _ in range(2):  # it 4 triggers (it+1) % 5 == 0
            pm.fit(x, y)
        leaf = jax.tree_util.tree_leaves(pm._stacked[0])[0]
        spread = float(jnp.max(jnp.abs(leaf - leaf.mean(0, keepdims=True))))
        assert spread < 1e-6, "replicas must coincide right after averaging"

    def test_averaging_converges(self):
        x, y, yi = _data(256)
        net = MultiLayerNetwork(_mlp()).init()
        pm = ParameterAveragingTrainingMaster(net, averagingFrequency=4)
        it = DataSetIterator(x, y, 64, shuffle=True)
        for _ in range(20):
            pm.fit(it)
        acc = (net.output(x).argMax(1).toNumpy() == yi).mean()
        assert acc > 0.9

    def test_bad_frequency_raises(self):
        net = MultiLayerNetwork(_mlp()).init()
        with pytest.raises(ValueError, match="averagingFrequency"):
            ParameterAveragingTrainingMaster(net, averagingFrequency=0)


class TestTensorParallel:
    def test_spec_rules(self):
        assert spec_for_param("W", (512, 512)) == P(None, MODEL_AXIS)
        assert spec_for_param("W", (3, 3, 256, 256)) == P(None, None, None, MODEL_AXIS)
        assert spec_for_param("b", (16,)) == P()  # too small -> replicated

    def test_sharded_forward_matches_replicated(self):
        mesh = build_mesh({DATA_AXIS: 4, MODEL_AXIS: 2})
        conf = (NeuralNetConfiguration.Builder()
                .seed(3).updater(Sgd(0.1)).activation("relu").list()
                .layer(DenseLayer(nOut=256))
                .layer(DenseLayer(nOut=256))
                .layer(OutputLayer(nOut=4, activation="softmax"))
                .setInputType(InputType.feedForward(8)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).randn(16, 8).astype("float32")
        ref = net.output(x).toNumpy()

        net._params = shard_params(net._params, mesh, min_shard_size=1024)
        # sharding annotations must not change numerics
        out = net.output(jax.device_put(
            jnp.asarray(x), NamedSharding(mesh, P(DATA_AXIS, None))))
        np.testing.assert_allclose(ref, out.toNumpy(), rtol=2e-5, atol=1e-6)

    def test_sharded_training_step_runs(self):
        mesh = build_mesh({DATA_AXIS: 4, MODEL_AXIS: 2})
        conf = (NeuralNetConfiguration.Builder()
                .seed(3).updater(Adam(1e-2)).activation("relu").list()
                .layer(DenseLayer(nOut=128))
                .layer(OutputLayer(nOut=4, activation="softmax"))
                .setInputType(InputType.feedForward(8)).build())
        net = MultiLayerNetwork(conf).init()
        net._params = shard_params(net._params, mesh, min_shard_size=256)
        net._upd_states = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P())), net._upd_states)
        x, y = (np.random.RandomState(0).randn(16, 8).astype("float32"),
                np.eye(4, dtype="float32")[np.random.RandomState(1).randint(0, 4, 16)])
        net.fit(x, y)
        assert np.isfinite(net.score())


class TestSequenceParallel:
    def _qkv(self, B=2, H=4, T=32, D=8, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
        return mk(), mk(), mk()

    def _reference_attention(self, q, k, v, causal):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
        if causal:
            T = q.shape[2]
            m = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            s = jnp.where(m[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_attention_exact(self, causal):
        mesh = build_mesh({SEQ_AXIS: 8})
        q, k, v = self._qkv()
        ref = self._reference_attention(q, k, v, causal)
        spec = NamedSharding(mesh, P(None, None, SEQ_AXIS, None))
        qs, ks, vs = (jax.device_put(a, spec) for a in (q, k, v))
        out = ring_attention(qs, ks, vs, mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_ulysses_attention_exact(self):
        mesh = build_mesh({SEQ_AXIS: 4})
        q, k, v = self._qkv(H=4, T=32)
        ref = self._reference_attention(q, k, v, False)
        spec = NamedSharding(mesh, P(None, None, SEQ_AXIS, None))
        qs, ks, vs = (jax.device_put(a, spec) for a in (q, k, v))
        out = ulysses_attention(qs, ks, vs, mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_blockwise_attention_matches_exact(self):
        from deeplearning4j_tpu.ops.attention import blockwise_attention

        q, k, v = self._qkv(T=40)
        ref = self._reference_attention(q, k, v, False)
        out = blockwise_attention(q, k, v, block_size=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_blockwise_causal(self):
        from deeplearning4j_tpu.ops.attention import blockwise_attention

        q, k, v = self._qkv(T=32)
        ref = self._reference_attention(q, k, v, True)
        out = blockwise_attention(q, k, v, block_size=8, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


class TestPipelineParallel:
    """GPipe-style microbatch pipeline over the 'pipe' mesh axis
    (parallel/pipeline.py). No upstream analog — TPU-first addition."""

    def _deep_mlp(self, seed=5, H=32):
        from deeplearning4j_tpu.nn import ActivationLayer  # noqa: F401

        b = (NeuralNetConfiguration.Builder()
             .seed(seed).updater(Sgd(0.05)).activation("tanh").list()
             .layer(DenseLayer(nOut=H)))           # prologue: 4 -> H
        for _ in range(4):                          # homogeneous body run
            b = b.layer(DenseLayer(nOut=H))
        b = (b.layer(OutputLayer(nOut=3, activation="softmax"))
             .setInputType(InputType.feedForward(4)))
        return b.build()

    def test_partition_stages(self):
        from deeplearning4j_tpu.parallel import partition_stages

        net = MultiLayerNetwork(self._deep_mlp()).init()
        pro, body, epi = partition_stages(net.layers, net._params, 4)
        assert pro == [0]            # the 4->H dense has a different W shape
        assert body == [1, 2, 3, 4]
        assert epi == [5]

    def test_partition_rejects_heterogeneous(self):
        from deeplearning4j_tpu.parallel import partition_stages

        conf = (NeuralNetConfiguration.Builder()
                .seed(1).updater(Sgd(0.1)).list()
                .layer(DenseLayer(nOut=16))
                .layer(DenseLayer(nOut=8))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        with pytest.raises(ValueError, match="identical"):
            partition_stages(net.layers, net._params, 4)

    def test_pipeline_matches_single_device(self):
        """With SGD the pipelined step computes the same loss/params as
        plain single-device training on the same batch (microbatching
        changes nothing without BN; mean-of-microbatch-means == full mean)."""
        from deeplearning4j_tpu.parallel import PipelineParallel

        x, y, _ = _data(64)
        ref = MultiLayerNetwork(self._deep_mlp()).init()
        for _ in range(3):
            ref.fit(x, y)

        net = MultiLayerNetwork(self._deep_mlp()).init()
        mesh = build_mesh({"pipe": 4})
        pp = PipelineParallel(net, mesh, n_microbatches=4)
        for _ in range(3):
            pp.fit(x, y)
        np.testing.assert_allclose(ref.params().toNumpy(),
                                   net.params().toNumpy(),
                                   rtol=1e-4, atol=1e-5)
        assert abs(ref.score() - net.score()) < 1e-4

    def test_pipeline_composes_with_dp(self):
        from deeplearning4j_tpu.parallel import PipelineParallel

        x, y, _ = _data(64)
        ref = MultiLayerNetwork(self._deep_mlp()).init()
        for _ in range(2):
            ref.fit(x, y)

        net = MultiLayerNetwork(self._deep_mlp()).init()
        mesh = build_mesh({DATA_AXIS: 2, "pipe": 4})
        pp = PipelineParallel(net, mesh, n_microbatches=4)
        for _ in range(2):
            pp.fit(x, y)
        np.testing.assert_allclose(ref.params().toNumpy(),
                                   net.params().toNumpy(),
                                   rtol=1e-4, atol=1e-5)

    def test_pipeline_converges(self):
        from deeplearning4j_tpu.parallel import PipelineParallel

        x, y, yi = _data(128, seed=4)
        net = MultiLayerNetwork(self._deep_mlp()).init()
        mesh = build_mesh({"pipe": 4})
        pp = PipelineParallel(net, mesh, n_microbatches=4)
        first = None
        for _ in range(30):
            pp.fit(x, y)
            first = first if first is not None else net.score()
        assert net.score() < 0.7 * first

    def test_bad_microbatch_divisibility(self):
        from deeplearning4j_tpu.parallel import PipelineParallel

        x, y, _ = _data(30)
        net = MultiLayerNetwork(self._deep_mlp()).init()
        pp = PipelineParallel(net, build_mesh({"pipe": 4}), n_microbatches=4)
        with pytest.raises(ValueError, match="divisible"):
            pp.fit(x, y)


class TestPipelineRegressions:
    def test_equal_dropout_objects_are_homogeneous(self):
        """Separately constructed but equal Dropout objects must not break
        stage partitioning (value-based config comparison)."""
        from deeplearning4j_tpu.nn import Dropout
        from deeplearning4j_tpu.parallel import partition_stages

        b = (NeuralNetConfiguration.Builder()
             .seed(5).updater(Sgd(0.05)).activation("tanh").list()
             .layer(DenseLayer(nOut=16)))
        for _ in range(4):
            b = b.layer(DenseLayer(nOut=16, dropOut=Dropout(0.9)))
        conf = (b.layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        pro, body, epi = partition_stages(net.layers, net._params, 4)
        assert body == [1, 2, 3, 4]

    def test_heterogeneous_activation_rejected(self):
        from deeplearning4j_tpu.parallel import partition_stages

        b = (NeuralNetConfiguration.Builder()
             .seed(5).updater(Sgd(0.05)).list()
             .layer(DenseLayer(nOut=16, activation="tanh")))
        for i in range(4):
            b = b.layer(DenseLayer(nOut=16,
                                   activation="relu" if i % 2 else "tanh"))
        conf = (b.layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        with pytest.raises(ValueError, match="identical"):
            partition_stages(net.layers, net._params, 4)

    def test_pipeline_applies_constraints(self):
        """A constrained net must keep its weight norms bounded under
        PipelineParallel just like under net.fit()."""
        from deeplearning4j_tpu.nn import MaxNormConstraint
        from deeplearning4j_tpu.parallel import PipelineParallel

        x, y, _ = _data(64)
        b = (NeuralNetConfiguration.Builder()
             .seed(5).updater(Sgd(0.5)).activation("tanh")
             .constrainWeights(MaxNormConstraint(0.3)).list()
             .layer(DenseLayer(nOut=16)))
        for _ in range(4):
            b = b.layer(DenseLayer(nOut=16))
        conf = (b.layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        net = MultiLayerNetwork(conf).init()
        pp = PipelineParallel(net, build_mesh({"pipe": 4}), n_microbatches=4)
        for _ in range(5):
            pp.fit(x, y)
        for p in net._params:
            norms = np.sqrt((np.asarray(p["W"]) ** 2).sum(0))
            assert np.all(norms <= 0.3 + 1e-4)


class TestMultiHost:
    """Multi-host bootstrap plumbing (parallel/multihost.py). Real DCN
    behavior needs a pod; here we certify the single-slice degradation,
    axis ordering, and coordinator role on the virtual mesh."""

    def test_hybrid_mesh_single_slice_fallback(self):
        from deeplearning4j_tpu.parallel import hybrid_mesh

        mesh = hybrid_mesh({"data": 2}, {"model": 4})
        assert mesh.shape == {"data": 2, "model": 4}
        # ici axis innermost: each model group is 4 contiguous devices
        dev = np.array(mesh.devices)
        assert dev.shape == (2, 4)

    def test_hybrid_mesh_trains_dp(self):
        from deeplearning4j_tpu.parallel import hybrid_mesh

        x, y, _ = _data(64)
        net = MultiLayerNetwork(_mlp()).init()
        mesh = hybrid_mesh({"data": 8}, {})
        pw = ParallelWrapper(net, mesh=mesh)
        pw.fit(x, y)
        assert np.isfinite(net.score())

    def test_coordinator_and_host_count(self):
        from deeplearning4j_tpu.parallel import is_coordinator, num_hosts

        assert is_coordinator()  # single-process test runtime
        assert num_hosts() == 1

    def test_dcn_axes_without_slices_raises(self):
        from deeplearning4j_tpu.parallel import hybrid_mesh

        with pytest.raises(ValueError, match="devices|slices"):
            hybrid_mesh({"data": 16}, {"model": 4})

    def _fake_slices(self, n_slices, per_slice):
        real = jax.devices()

        class FakeDev:
            def __init__(self, d, s, i):
                self._d = d
                self.slice_index = s
                self.id = i
                self.process_index = getattr(d, "process_index", 0)
                self.platform = d.platform
                self.device_kind = d.device_kind

            def __getattr__(self, a):
                return getattr(object.__getattribute__(self, "_d"), a)

        return [FakeDev(real[i], i // per_slice, i)
                for i in range(n_slices * per_slice)]

    def test_hybrid_mesh_multi_slice_keeps_ici_in_slice(self):
        """Simulated 2 slices x 4 devices: dcn axis spans slices, every
        ici group stays inside one slice."""
        from deeplearning4j_tpu.parallel import hybrid_mesh

        devs = self._fake_slices(2, 4)
        m = hybrid_mesh({"data": 2}, {"model": 4}, devices=devs)
        assert m.shape == {"data": 2, "model": 4}
        arr = np.array(m.devices, dtype=object)
        for row in arr:
            assert len({d.slice_index for d in row}) == 1

    def test_hybrid_mesh_multi_slice_two_ici_axes(self):
        from deeplearning4j_tpu.parallel import hybrid_mesh

        devs = self._fake_slices(2, 4)
        m = hybrid_mesh({"data": 2}, {"model": 2, "seq": 2}, devices=devs)
        assert m.shape == {"data": 2, "model": 2, "seq": 2}

    def test_hybrid_mesh_uncovered_devices_rejected(self):
        from deeplearning4j_tpu.parallel import hybrid_mesh

        devs = self._fake_slices(2, 4)
        with pytest.raises(ValueError, match="cover"):
            hybrid_mesh({"data": 2}, {}, devices=devs)


class TestParallelInference:
    """Reference: org.deeplearning4j.parallelism.ParallelInference —
    here the worker pool is a data-axis mesh and one SPMD dispatch."""

    def _mlp(self, nIn=12, nOut=5, seed=3):
        conf = (NeuralNetConfiguration.Builder().seed(seed)
                .updater(Adam(1e-2)).activation("tanh").list()
                .layer(DenseLayer(nOut=16))
                .layer(OutputLayer(nOut=nOut, activation="softmax"))
                .setInputType(InputType.feedForward(nIn)).build())
        return MultiLayerNetwork(conf).init()

    def test_parity_with_single_device_output(self):
        from deeplearning4j_tpu.parallel import ParallelInference

        net = self._mlp()
        pi = ParallelInference(net)
        x = np.random.RandomState(0).randn(24, 12).astype("float32")
        np.testing.assert_allclose(pi.output(x).toNumpy(),
                                   net.output(x).toNumpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_ragged_batch_padding(self):
        from deeplearning4j_tpu.parallel import ParallelInference

        net = self._mlp()
        pi = ParallelInference(net)
        # B=13 not divisible by the 8-device mesh: pad + slice path
        x = np.random.RandomState(1).randn(13, 12).astype("float32")
        out = pi.output(x)
        assert out.shape() == (13, 5)
        np.testing.assert_allclose(out.toNumpy(), net.output(x).toNumpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_batch_limit_chunking(self):
        from deeplearning4j_tpu.parallel import ParallelInference

        net = self._mlp()
        pi = ParallelInference(net, batchLimit=16)
        x = np.random.RandomState(2).randn(40, 12).astype("float32")
        np.testing.assert_allclose(pi.output(x).toNumpy(),
                                   net.output(x).toNumpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_builder_and_computation_graph(self):
        from deeplearning4j_tpu.parallel import ParallelInference
        from deeplearning4j_tpu.nn import ComputationGraph

        g = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-2))
             .graphBuilder().addInputs("in")
             .addLayer("h", DenseLayer(nOut=8, activation="relu"), "in")
             .addLayer("out", OutputLayer(nOut=3, activation="softmax"), "h")
             .setOutputs("out")
             .setInputTypes(InputType.feedForward(6)).build())
        net = ComputationGraph(g).init()
        pi = (ParallelInference.Builder(net).workers(4).batchLimit(32)
              .inferenceMode("BATCHED").queueLimit(64).build())
        x = np.random.RandomState(3).randn(10, 6).astype("float32")
        np.testing.assert_allclose(pi.output(x).toNumpy(),
                                   net.outputSingle(x).toNumpy(),
                                   rtol=1e-5, atol=1e-6)


class TestThresholdGradientSharing:
    """gradient_compression='threshold' (reference: Strom 2015 — the
    sparse, error-compensated update algorithm behind upstream
    SharedTrainingMaster's threshold encoding)."""

    def _mlp(self, seed=5):
        conf = (NeuralNetConfiguration.Builder().seed(seed)
                .updater(Sgd(0.5)).activation("tanh").list()
                .layer(DenseLayer(nOut=16))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.feedForward(8)).build())
        return MultiLayerNetwork(conf).init()

    def _data(self, n=32, seed=0):
        rng = np.random.RandomState(seed)
        yi = rng.randint(0, 3, n)
        x = (np.eye(3)[yi] @ np.array([[2.0] * 8, [-2.0] * 8, [0.0] * 8])
             + 0.1 * rng.randn(n, 8)).astype("float32")
        return x, np.eye(3, dtype="float32")[yi]

    def test_huge_threshold_transmits_nothing(self):
        from deeplearning4j_tpu.parallel import ParallelWrapper

        net = self._mlp()
        before = jax.tree_util.tree_map(np.asarray, net._params)
        pw = ParallelWrapper(net, gradient_compression="threshold",
                             threshold=1e9)
        x, y = self._data()
        pw.fit(x, y)
        after = net._params
        for b, a in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after)):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
        # ...but the gradient is not lost: it sits in the residual
        assert max(float(jnp.max(jnp.abs(l))) for l in
                   jax.tree_util.tree_leaves(pw._residual[0])) > 0

    def test_error_feedback_flushes_small_gradients(self):
        """Per-step gradients below the threshold still reach the params
        once their residual accumulates past it — without error feedback
        a too-large threshold would stall training forever."""
        from deeplearning4j_tpu.parallel import ParallelWrapper

        net = self._mlp()
        pw = ParallelWrapper(net, gradient_compression="threshold",
                             threshold=0.05)
        x, y = self._data()
        first = None
        for _ in range(40):
            pw.fit(x, y)
            first = first if first is not None else net.score()
        assert np.isfinite(net.score())
        assert net.score() < 0.5 * first, (first, net.score())

    def test_threshold_converges_comparable_to_dense(self):
        from deeplearning4j_tpu.parallel import ParallelWrapper

        x, y = self._data()
        dense = self._mlp(seed=5)
        ParallelWrapper(dense).fit(x, y)
        net = self._mlp(seed=5)
        # encodingCapacity=1.0: tau is the only limiter (the classic
        # Strom regime); the default fixed capacity additionally bounds
        # per-step traffic and trades convergence speed for wire bytes
        pw = ParallelWrapper(net, gradient_compression="threshold",
                             threshold=1e-2, encodingCapacity=1.0)
        for _ in range(100):
            pw.fit(x, y)
        # sign-style +-t updates converge slower than dense psum per step
        # (the trade upstream makes for sparse wire traffic), but must
        # still reach a good fit on separable data
        assert net.score() < 0.25, net.score()

    def test_capacity_limited_encoder_still_converges(self):
        """The default FIXED-capacity encoder (top-|.| candidates only)
        transmits at most ceil(0.125*n) entries per leaf per step; error
        feedback must still deliver the full gradient mass over time."""
        from deeplearning4j_tpu.parallel import ParallelWrapper

        x, y = self._data()
        net = self._mlp(seed=5)
        pw = ParallelWrapper(net, gradient_compression="threshold",
                             threshold=1e-2)
        assert pw.encoding_capacity == 0.125
        first = None
        for _ in range(150):
            pw.fit(x, y)
            first = first if first is not None else net.score()
        assert net.score() < 0.5 * first, (first, net.score())

    def test_bad_compression_name_rejected(self):
        from deeplearning4j_tpu.parallel import ParallelWrapper

        with pytest.raises(ValueError, match="gradient_compression"):
            ParallelWrapper(self._mlp(), gradient_compression="sparse")

    def test_shared_master_threshold_algorithm_arg(self):
        from deeplearning4j_tpu.parallel import SharedTrainingMaster

        m = SharedTrainingMaster(self._mlp(), thresholdAlgorithm=1e-2)
        assert m.gradient_compression == "threshold"
        assert m.threshold == 1e-2
        # default (no algorithm given) stays int8
        assert SharedTrainingMaster(self._mlp()).gradient_compression == "int8"
        # conflicting args: a threshold algorithm cannot silently lose to
        # an explicit non-threshold compression
        with pytest.raises(ValueError, match="thresholdAlgorithm"):
            SharedTrainingMaster(self._mlp(), thresholdAlgorithm=1e-2,
                                 gradient_compression="int8")
        # explicit "threshold" alongside the algorithm is fine
        m2 = SharedTrainingMaster(self._mlp(), thresholdAlgorithm=1e-3,
                                  gradient_compression="threshold")
        assert m2.threshold == 1e-3

    def test_adaptive_threshold_tracks_target_sparsity(self):
        """targetSparsity (reference: AdaptiveThresholdAlgorithm): a
        wildly-too-large starting threshold must adapt DOWN until real
        transmission resumes; a tiny one must adapt UP."""
        from deeplearning4j_tpu.parallel import ParallelWrapper

        x, y = self._data()

        net = self._mlp()
        pw = ParallelWrapper(net, gradient_compression="threshold",
                             threshold=100.0, targetSparsity=0.2)
        for _ in range(30):
            pw.fit(x, y)
        t_down = float(pw._residual[1])
        assert t_down < 100.0 / 5, t_down  # decayed by >5x

        net2 = self._mlp()
        pw2 = ParallelWrapper(net2, gradient_compression="threshold",
                              threshold=1e-8, targetSparsity=0.2)
        for _ in range(30):
            pw2.fit(x, y)
        t_up = float(pw2._residual[1])
        assert t_up > 1e-8 * 5, t_up  # grew by >5x
        assert np.isfinite(net.score()) and np.isfinite(net2.score())


class TestComputationGraphDataParallel:
    """ParallelWrapper over a ComputationGraph (single-IO): dense parity
    with single-device training, compressed modes via the graph-side
    transform hooks."""

    def _graph(self, seed=11):
        from deeplearning4j_tpu.nn import ComputationGraph

        g = (NeuralNetConfiguration.Builder().seed(seed).updater(Sgd(0.1))
             .activation("tanh").graphBuilder().addInputs("in")
             .addLayer("h", DenseLayer(nOut=16), "in")
             .addLayer("out", OutputLayer(nOut=3, activation="softmax"), "h")
             .setOutputs("out")
             .setInputTypes(InputType.feedForward(4)).build())
        return ComputationGraph(g).init()

    def test_dense_matches_single_device(self):
        x, y, _ = _data(64)
        a = self._graph()
        for _ in range(4):
            a.fit(x, y)
        b = self._graph()
        pw = ParallelWrapper(b)
        for _ in range(4):
            pw.fit(x, y)
        pa = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(a._params)])
        pb = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(b._params)])
        np.testing.assert_allclose(pa, pb, rtol=1e-5, atol=1e-6)

    def test_threshold_mode_trains_graph(self):
        x, y, _ = _data(64)
        net = self._graph()
        pw = ParallelWrapper(net, gradient_compression="threshold",
                             threshold=1e-2)
        first = None
        for _ in range(30):
            pw.fit(x, y)
            first = first if first is not None else net.score()
        assert np.isfinite(net.score()) and net.score() < first

    def test_multi_io_graph_rejected_clearly(self):
        from deeplearning4j_tpu.nn import ComputationGraph, MergeVertex

        g = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
             .graphBuilder().addInputs("a", "b")
             .addVertex("m", MergeVertex(), "a", "b")
             .addLayer("out", OutputLayer(nOut=2, activation="softmax"), "m")
             .setOutputs("out")
             .setInputTypes(InputType.feedForward(2), InputType.feedForward(2))
             .build())
        net = ComputationGraph(g).init()
        x, y, _ = _data(64)
        with pytest.raises(ValueError, match="single-input"):
            ParallelWrapper(net).fit(x[:, :2], y)


class TestSparkFacade:
    """SparkDl4jMultiLayer / SparkComputationGraph entry-point parity
    (reference: dl4j-spark impl.multilayer/impl.graph wrappers)."""

    def test_fit_with_parameter_averaging_builder(self):
        from deeplearning4j_tpu.parallel import (
            SparkDl4jMultiLayer, ParameterAveragingTrainingMasterBuilder)
        x, y, yi = _data(96)
        tm = (ParameterAveragingTrainingMasterBuilder()
              .averagingFrequency(1).build())
        spark_net = SparkDl4jMultiLayer(data_parallel_mesh(), _mlp(), tm)
        it = DataSetIterator(x, y, 32)
        for _ in range(30):
            spark_net.fit(it)
        net = spark_net.getNetwork()
        acc = (np.asarray(net.output(x).jax()).argmax(1) == yi).mean()
        assert acc > 0.9, acc
        from deeplearning4j_tpu.parallel.trainer import \
            ParameterAveragingTrainingMaster
        assert isinstance(spark_net.getTrainingMaster(),
                          ParameterAveragingTrainingMaster)

    def test_fit_with_shared_master_and_evaluate(self):
        from deeplearning4j_tpu.parallel import (
            SparkDl4jMultiLayer, SharedTrainingMasterBuilder)
        x, y, yi = _data(96, seed=3)
        tm = SharedTrainingMasterBuilder().gradientCompression(None).build()
        spark_net = SparkDl4jMultiLayer(None, _mlp(7), tm)
        it = DataSetIterator(x, y, 32)
        for _ in range(30):
            spark_net.fit(it)
        ev = spark_net.evaluate(DataSetIterator(x, y, 32))
        assert ev.accuracy() > 0.9

    def test_rdd_analog_list_of_datasets(self):
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.parallel import SparkDl4jMultiLayer
        x, y, yi = _data(64, seed=5)
        rdd = [DataSet(x[i:i + 32], y[i:i + 32]) for i in (0, 32)]
        spark_net = SparkDl4jMultiLayer(None, _mlp(9))
        for _ in range(25):
            spark_net.fit(rdd)
        acc = (np.asarray(spark_net.getNetwork().output(x).jax()).argmax(1)
               == yi).mean()
        assert acc > 0.85, acc

    def test_rdd_list_honors_epochs(self):
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.parallel import SparkDl4jMultiLayer

        class CountingMaster(ParallelWrapper):
            fits = 0

            def fit(self, data, labels=None, epochs=None):
                CountingMaster.fits += 1
                return super().fit(data, labels, epochs)

        x, y, _ = _data(32)
        net = MultiLayerNetwork(_mlp()).init()
        spark_net = SparkDl4jMultiLayer(None, net, CountingMaster(net))
        spark_net.fit([DataSet(x, y)], epochs=3)
        assert CountingMaster.fits == 3

    def test_accepts_prebuilt_net_and_bound_master(self):
        from deeplearning4j_tpu.parallel import SparkDl4jMultiLayer
        net = MultiLayerNetwork(_mlp()).init()
        pw = ParallelWrapper(net)
        spark_net = SparkDl4jMultiLayer(None, net, pw)
        assert spark_net.getNetwork() is net
        assert spark_net.getTrainingMaster() is pw

    def test_rejects_bad_master(self):
        from deeplearning4j_tpu.parallel import SparkDl4jMultiLayer
        with pytest.raises(ValueError, match="trainingMaster"):
            SparkDl4jMultiLayer(None, _mlp(), trainingMaster="averaging")

    def test_computation_graph_facade(self):
        from deeplearning4j_tpu.parallel import SparkComputationGraph
        x, y, yi = _data(64, seed=8)
        conf = (NeuralNetConfiguration.Builder()
                .seed(1).updater(Adam(1e-2)).graphBuilder()
                .addInputs("in")
                .addLayer("h", DenseLayer(nOut=32, activation="relu"), "in")
                .addLayer("out", OutputLayer(nOut=3, activation="softmax"),
                          "h")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(4))
                .build())
        spark_g = SparkComputationGraph(None, conf)
        it = DataSetIterator(x, y, 32)
        for _ in range(25):
            spark_g.fit(it)
        acc = (np.asarray(spark_g.getNetwork().output(x).jax()).argmax(1)
               == yi).mean()
        assert acc > 0.85, acc


_TWO_PROC_CHILD = r'''
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

pid, coord = int(sys.argv[1]), sys.argv[2]
from deeplearning4j_tpu.parallel import multihost
try:
    multihost.initialize(coordinator_address=coord, num_processes=2,
                         process_id=pid)
except RuntimeError as e:
    # rc 3 = environment cannot run jax.distributed (sandboxed sockets
    # etc.); any other failure must FAIL the test, not skip it
    print("CHILDSKIP " + str(e)[:300], file=sys.stderr, flush=True)
    sys.exit(3)
assert jax.process_count() == 2, jax.process_count()
mesh = multihost.hybrid_mesh({"data": 2}, {"model": 2})
assert dict(mesh.shape) == {"data": 2, "model": 2}

rng = np.random.RandomState(0)
X = rng.randn(64, 8).astype("float32")
W = rng.randn(8, 4).astype("float32")
Y = rng.randn(64, 4).astype("float32")
local = slice(pid * 32, (pid + 1) * 32)
xsh = NamedSharding(mesh, P("data", None))
gx = jax.make_array_from_process_local_data(xsh, X[local], X.shape)
gy = jax.make_array_from_process_local_data(xsh, Y[local], Y.shape)
gw = jax.device_put(W, NamedSharding(mesh, P(None, "model")))

@jax.jit
def step(w, x, y):
    loss, g = jax.value_and_grad(
        lambda w: jnp.mean((x @ w - y) ** 2))(w)
    return loss, w - 0.1 * g

loss, w2 = step(gw, gx, gy)  # XLA inserts the cross-process psum
print("CHILDREC " + json.dumps({
    "process": pid, "is_coord": bool(multihost.is_coordinator()),
    "hosts": int(multihost.num_hosts()), "loss": float(loss),
    "w2_sum": float(jnp.sum(w2))}), flush=True)
'''


class TestMultiHostTwoProcess:
    """The DCN path must cross a process boundary at least once. This
    spawns TWO OS processes, joins them through
    multihost.initialize (jax.distributed on the CPU backend,
    coordinator on 127.0.0.1), builds the hybrid mesh across both, and
    runs one DP+MP-sharded train step where each process contributes
    only ITS half of the batch — asserting loss/param parity against a
    single-process numpy oracle."""

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_two_process_dp_step_parity(self, tmp_path):
        import json
        import os
        import socket
        import subprocess
        import sys as _sys

        with socket.socket() as s:  # free loopback port for the coordinator
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        coord = f"127.0.0.1:{port}"
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
        script = tmp_path / "child.py"
        script.write_text(_TWO_PROC_CHILD)
        procs = [subprocess.Popen(
            [_sys.executable, str(script), str(pid), coord],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env, cwd=here) for pid in range(2)]
        outs = []
        for p in procs:
            try:
                out, err = p.communicate(timeout=240)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail("two-process distributed step hung (240 s)")
            outs.append((p.returncode, out, err))
        recs = {}
        for rc, out, err in outs:
            if rc == 3 and "CHILDSKIP" in err:
                # the child's explicit environment gate (socket sandbox
                # etc.) — loud, and ONLY for initialize-time RuntimeError
                pytest.skip("jax.distributed unavailable here: "
                            + err.strip()[-300:])
            if rc != 0:
                pytest.fail(f"child failed rc={rc}: {err.strip()[-800:]}")
            for line in out.splitlines():
                if line.startswith("CHILDREC "):
                    r = json.loads(line[len("CHILDREC "):])
                    recs[r["process"]] = r
        assert sorted(recs) == [0, 1], f"missing child records: {outs}"

        # single-process oracle, same data
        rng = np.random.RandomState(0)
        X = rng.randn(64, 8).astype("float32")
        W = rng.randn(8, 4).astype("float32")
        Y = rng.randn(64, 4).astype("float32")
        pred = X @ W
        loss_ref = float(np.mean((pred - Y) ** 2))
        g = 2.0 * X.T @ (pred - Y) / pred.size
        w2_ref = float(np.sum(W - 0.1 * g))

        for pid in (0, 1):
            assert recs[pid]["hosts"] == 2
            np.testing.assert_allclose(recs[pid]["loss"], loss_ref,
                                       rtol=1e-5)
            np.testing.assert_allclose(recs[pid]["w2_sum"], w2_ref,
                                       rtol=1e-4)
        assert recs[0]["is_coord"] and not recs[1]["is_coord"]
