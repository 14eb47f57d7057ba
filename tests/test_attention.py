"""Attention layers (reference: deeplearning4j-core
org.deeplearning4j.nn.layers.recurrent/TestSelfAttentionLayer,
AttentionLayerTest — shapes, gradient checks, masking, and a
transformer-encoder convergence test)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ndarray import DataType
from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, InputType, MultiLayerNetwork, ComputationGraph,
    SelfAttentionLayer, LearnedSelfAttentionLayer, RecurrentAttentionLayer,
    AttentionVertex, GlobalPoolingLayer, OutputLayer, RnnOutputLayer,
    DenseLayer, ElementWiseVertex, ActivationLayer, Adam, Sgd, LSTM,
)
from deeplearning4j_tpu.data import DataSet


def _seq_cls_data(n=16, F=4, T=6, nOut=3, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, F, T).astype("float32")
    yi = np.argmax(x.mean(axis=2)[:, :nOut], axis=1)
    return x, np.eye(nOut, dtype="float32")[yi], yi


class TestShapes:
    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_self_attention_shape(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
                .layer(SelfAttentionLayer(nOut=8, nHeads=2))
                .layer(GlobalPoolingLayer(poolingType="avg"))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.recurrent(4, 6)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).randn(5, 4, 6).astype("float32")
        assert net.output(x).shape() == (5, 3)
        acts = net.feedForward(x)
        assert acts[1].shape() == (5, 8, 6)  # [B, nOut, T]

    def test_self_attention_no_projection(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
                .layer(SelfAttentionLayer(projectInput=False))
                .layer(GlobalPoolingLayer(poolingType="avg"))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(4, 6)).build())
        net = MultiLayerNetwork(conf).init()
        assert net._params[0] == {}  # parameterless
        x = np.random.RandomState(0).randn(5, 4, 6).astype("float32")
        acts = net.feedForward(x)
        assert acts[1].shape() == (5, 4, 6)

    def test_no_projection_multi_head_rejected(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
                .layer(SelfAttentionLayer(projectInput=False, nHeads=2))
                .layer(GlobalPoolingLayer())
                .layer(OutputLayer(nOut=2))
                .setInputType(InputType.recurrent(4, 6)).build())
        with pytest.raises(ValueError, match="projectInput"):
            MultiLayerNetwork(conf).init()

    def test_learned_self_attention_pools_to_nqueries(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
                .layer(LearnedSelfAttentionLayer(nOut=8, nHeads=2, nQueries=3))
                .layer(GlobalPoolingLayer(poolingType="avg"))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(4, 10)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).randn(5, 4, 10).astype("float32")
        acts = net.feedForward(x)
        assert acts[1].shape() == (5, 8, 3)  # T collapsed to nQueries

    def test_recurrent_attention_shape_and_carry(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
                .layer(RecurrentAttentionLayer(nOut=8, nHeads=2))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(4, 6)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.RandomState(0).randn(5, 4, 6).astype("float32")
        assert net.output(x).shape() == (5, 2, 6)

    def test_attention_vertex_cross_attention_shapes(self):
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
                .graphBuilder()
                .addInputs("q", "kv")
                .addVertex("attn", AttentionVertex(nOut=8, nHeads=2), "q", "kv")
                .addLayer("gp", GlobalPoolingLayer(poolingType="avg"), "attn")
                .addLayer("out", OutputLayer(nOut=3, activation="softmax"), "gp")
                .setOutputs("out")
                .setInputTypes(InputType.recurrent(4, 5), InputType.recurrent(6, 9))
                .build())
        net = ComputationGraph(conf).init()
        q = np.random.RandomState(0).randn(2, 4, 5).astype("float32")
        kv = np.random.RandomState(1).randn(2, 6, 9).astype("float32")
        out = net.output([q, kv])
        assert out.shape() == (2, 3)


class TestMasking:
    def test_masked_keys_are_ignored(self):
        """Scores at masked key positions must not affect the output:
        attention over [x ; garbage(masked)] == attention over x padded."""
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
                .layer(SelfAttentionLayer(nOut=6, nHeads=1))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(4, 8)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(0)
        x = rng.randn(3, 4, 8).astype("float32")
        x2 = x.copy()
        x2[:, :, 5:] = 99.0  # garbage in masked region
        mask = np.ones((3, 8), np.float32)
        mask[:, 5:] = 0
        h1, _ = net.layers[0].forward(net._params[0], {}, jnp.asarray(x),
                                      False, None, jnp.asarray(mask))
        h2, _ = net.layers[0].forward(net._params[0], {}, jnp.asarray(x2),
                                      False, None, jnp.asarray(mask))
        np.testing.assert_allclose(np.asarray(h1[:, :, :5]),
                                   np.asarray(h2[:, :, :5]), atol=1e-5)
        # masked positions zeroed
        assert np.all(np.asarray(h1[:, :, 5:]) == 0)


class TestBlockwiseParity:
    def test_blockwise_equals_fused_in_layer(self):
        conf_kw = dict(nOut=8, nHeads=2)
        rng = np.random.RandomState(0)
        x = rng.randn(2, 4, 16).astype("float32")
        conf = (NeuralNetConfiguration.Builder().seed(5).updater(Sgd(0.1)).list()
                .layer(SelfAttentionLayer(**conf_kw))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(4, 16)).build())
        net = MultiLayerNetwork(conf).init()
        layer = net.layers[0]
        h_fused, _ = layer.forward(net._params[0], {}, jnp.asarray(x), False, None)
        layer.blockSize = 4
        h_block, _ = layer.forward(net._params[0], {}, jnp.asarray(x), False, None)
        np.testing.assert_allclose(np.asarray(h_fused), np.asarray(h_block),
                                   rtol=2e-5, atol=2e-5)


class TestGradients:
    """Finite-difference gradcheck per attention layer (fp64)."""

    def _gradcheck(self, conf, x, y, eps=1e-6, tol=1e-4):
        net = MultiLayerNetwork(conf).init()
        net._params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float64), net._params)
        x = x.astype("float64")
        y = y.astype("float64")
        grads, _ = net.computeGradientAndScore(x, y)
        flat, treedef = jax.tree_util.tree_flatten(net._params)
        gflat, _ = jax.tree_util.tree_flatten(grads)
        rng = np.random.RandomState(0)
        for ai, (a, g) in enumerate(zip(flat, gflat)):
            idxs = [tuple(rng.randint(0, s) for s in a.shape) for _ in range(3)]
            for idx in idxs:
                flat2 = list(flat)
                flat2[ai] = a.at[idx].add(eps)
                net._params = jax.tree_util.tree_unflatten(treedef, flat2)
                s_plus = float(net._jit_loss(net._params, net._states, x, y, None, None))
                flat2[ai] = a.at[idx].add(-eps)
                net._params = jax.tree_util.tree_unflatten(treedef, flat2)
                s_minus = float(net._jit_loss(net._params, net._states, x, y, None, None))
                fd = (s_plus - s_minus) / (2 * eps)
                bp = float(g[idx])
                assert abs(fd - bp) < tol * max(1.0, abs(fd), abs(bp)), \
                    f"array {ai} idx {idx}: fd={fd} bp={bp}"
            net._params = jax.tree_util.tree_unflatten(treedef, flat)

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_self_attention_gradients(self):
        x, y, _ = _seq_cls_data(n=4, F=4, T=5)
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Sgd(0.1))
                .dataType(DataType.DOUBLE).list()
                .layer(SelfAttentionLayer(nOut=6, nHeads=2))
                .layer(GlobalPoolingLayer(poolingType="avg"))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.recurrent(4, 5)).build())
        self._gradcheck(conf, x, y)

    def test_learned_self_attention_gradients(self):
        x, y, _ = _seq_cls_data(n=4, F=4, T=5)
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Sgd(0.1))
                .dataType(DataType.DOUBLE).list()
                .layer(LearnedSelfAttentionLayer(nOut=6, nHeads=2, nQueries=2))
                .layer(GlobalPoolingLayer(poolingType="avg"))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.recurrent(4, 5)).build())
        self._gradcheck(conf, x, y)

    def test_recurrent_attention_gradients(self):
        x, y, _ = _seq_cls_data(n=4, F=4, T=5)
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Sgd(0.1))
                .dataType(DataType.DOUBLE).list()
                .layer(RecurrentAttentionLayer(nOut=4, nHeads=1))
                .layer(GlobalPoolingLayer(poolingType="avg"))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.recurrent(4, 5)).build())
        self._gradcheck(conf, x, y, tol=1e-3)


class TestConvergence:
    def test_self_attention_classifier_converges(self):
        x, y, yi = _seq_cls_data(n=32, F=4, T=6)
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-2)).list()
                .layer(SelfAttentionLayer(nOut=16, nHeads=4))
                .layer(GlobalPoolingLayer(poolingType="avg"))
                .layer(OutputLayer(nOut=3, activation="softmax"))
                .setInputType(InputType.recurrent(4, 6)).build())
        net = MultiLayerNetwork(conf).init()
        ds = DataSet(x, y)
        for _ in range(80):
            net.fit(ds)
        acc = (net.output(x).argMax(1).toNumpy() == yi).mean()
        assert acc > 0.85

    def test_transformer_encoder_block_trains(self):
        """A transformer-encoder block —
        self-attention + residual + FFN + residual — trains via
        ComputationGraph."""
        from deeplearning4j_tpu.nn import PreprocessorVertex
        from deeplearning4j_tpu.nn.conf.preprocessors import FeedForwardToRnnPreProcessor

        x, y, yi = _seq_cls_data(n=32, F=8, T=6)
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(5e-3))
                .graphBuilder()
                .addInputs("in")
                .addVertex("attn", AttentionVertex(nOut=8, nHeads=2), "in")
                .addVertex("res1", ElementWiseVertex("add"), "in", "attn")
                .addLayer("ffn1", DenseLayer(nOut=32, activation="relu"), "res1")
                .addLayer("ffn2", DenseLayer(nOut=8, activation="identity"), "ffn1")
                .addVertex("seq", PreprocessorVertex(FeedForwardToRnnPreProcessor()), "ffn2")
                .addVertex("res2", ElementWiseVertex("add"), "res1", "seq")
                .addLayer("gp", GlobalPoolingLayer(poolingType="avg"), "res2")
                .addLayer("out", OutputLayer(nOut=3, activation="softmax"), "gp")
                .setOutputs("out")
                .setInputTypes(InputType.recurrent(8, 6))
                .build())
        net = ComputationGraph(conf).init()
        losses = []
        for _ in range(120):
            net.fit(x, y)
            losses.append(net.score())
        acc = (net.outputSingle(x).argMax(1).toNumpy() == yi).mean()
        assert losses[-1] < losses[0]
        assert acc > 0.85

    def test_recurrent_attention_seq_model_converges(self):
        rng = np.random.RandomState(0)
        x = rng.randn(24, 3, 8).astype("float32")
        yi = (np.cumsum(x.sum(axis=1), axis=1) > 0).astype(int)  # [B,T]
        y = np.transpose(np.eye(2, dtype="float32")[yi], (0, 2, 1))
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-2)).list()
                .layer(RecurrentAttentionLayer(nOut=8, nHeads=2))
                .layer(RnnOutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(3, 8)).build())
        net = MultiLayerNetwork(conf).init()
        losses = []
        for _ in range(60):
            net.fit(x, y)
            losses.append(net.score())
        assert losses[-1] < 0.55 * losses[0]


class TestFlashKernel:
    """Pallas flash kernel checked in interpreter mode on CPU against the
    fused reference (forward + backward), including padded/causal grids
    and the dispatcher wiring into multi_head_attention/_mha_apply."""

    @pytest.fixture
    def interpret(self, monkeypatch):
        from deeplearning4j_tpu.ops import pallas_attention as pa

        monkeypatch.setattr(pa, "_INTERPRET", True)
        return pa

    def _qkv(self, B=2, H=2, Tq=64, Tk=64, D=16, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda T: jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
        return mk(Tq), mk(Tk), mk(Tk)

    @pytest.mark.parametrize("causal", [False, True])
    def test_kernel_matches_fused(self, interpret, causal):
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        q, k, v = self._qkv()
        out = interpret.flash_attention(q, k, v, causal=causal,
                                        block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_kernel_padded_grid(self, interpret):
        """T not a multiple of the block size exercises the pad+mask path."""
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        q, k, v = self._qkv(Tq=70, Tk=70)
        out = interpret.flash_attention(q, k, v, block_q=32, block_k=32)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_kernel_cross_attention_lengths(self, interpret):
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        q, k, v = self._qkv(Tq=24, Tk=56)
        out = interpret.flash_attention(q, k, v, block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_kernel_bf16_inputs(self, interpret):
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        q, k, v = (a.astype(jnp.bfloat16) for a in self._qkv())
        out = interpret.flash_attention(q, k, v, block_q=16, block_k=16)
        assert out.dtype == jnp.bfloat16
        ref = dot_product_attention(*(a.astype(jnp.float32)
                                      for a in self._qkv()))
        np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                                   np.asarray(ref), rtol=0.05, atol=0.05)

    @pytest.mark.parametrize("causal", [False, True])
    def test_kernel_gradients_match_fused(self, interpret, causal):
        """The custom VJP (blockwise recompute) must agree with autodiff
        through the fused reference."""
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        q, k, v = self._qkv(Tq=32, Tk=32, D=8)

        def f_flash(q, k, v):
            return jnp.sum(interpret.flash_attention(
                q, k, v, causal=causal, block_q=16, block_k=16) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    # -- round 12: the hand-written flash backward kernels ------------

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("Tq,Tk,bq,bk", [
        (64, 64, 16, 16),   # aligned grid
        (70, 70, 32, 32),   # padded grid (T % block != 0)
        (24, 56, 16, 16),   # cross-attention lengths
        (33, 17, 16, 8),    # ragged both sides, mixed blocks
    ])
    def test_bwd_kernels_match_fused_reference(self, interpret, causal,
                                               Tq, Tk, bq, bk):
        """The default backward is now the pallas dq/dkv kernel pair
        (DL4J_TPU_FLASH_BWD=kernel): gradients vs autodiff through the
        fused reference, including causal masking across padded and
        cross-length grids (rows whose valid-key set the kernels must
        rebuild from the saved logsumexp)."""
        from deeplearning4j_tpu.ops import pallas_attention as pa
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        assert pa._BWD_IMPL == "kernel"  # the shipped default
        q, k, v = self._qkv(Tq=Tq, Tk=Tk, D=8, seed=3)

        def f_flash(q, k, v):
            return jnp.sum(interpret.flash_attention(
                q, k, v, causal=causal, block_q=bq, block_k=bk) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(
                dot_product_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, nm in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4,
                err_msg=f"d{nm} Tq={Tq} Tk={Tk} causal={causal}")

    def test_bwd_kernel_vs_recompute_knob(self, interpret):
        """The two backward strategies must agree with each other (both
        are exact-math flash backwards; only HBM traffic differs) and
        the knob must restore cleanly."""
        from deeplearning4j_tpu.ops import pallas_attention as pa

        q, k, v = self._qkv(Tq=48, Tk=48, D=8, seed=5)

        def g(qq, kk, vv):
            return jax.grad(lambda a, b, c: jnp.sum(
                interpret.flash_attention(
                    a, b, c, causal=True, block_q=16,
                    block_k=16) ** 2), argnums=(0, 1, 2))(qq, kk, vv)

        g_kernel = g(q, k, v)
        old = pa.set_flash_bwd("recompute")
        try:
            g_rec = g(q, k, v)
        finally:
            pa.set_flash_bwd(old)
        for a, b in zip(g_kernel, g_rec):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_bwd_kernel_bf16_dtypes(self, interpret):
        """bf16 q/k/v produce bf16 gradients (fp32 accumulators cast
        at the kernel edge) within bf16 tolerance of the fp32 oracle."""
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        q32, k32, v32 = self._qkv(Tq=32, Tk=32, D=8, seed=6)
        q, k, v = (a.astype(jnp.bfloat16) for a in (q32, k32, v32))
        gf = jax.grad(lambda a, b, c: jnp.sum(
            interpret.flash_attention(
                a, b, c, block_q=16,
                block_k=16).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: jnp.sum(
            dot_product_attention(a, b, c) ** 2),
            argnums=(0, 1, 2))(q32, k32, v32)
        for a, b in zip(gf, gr):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                       np.asarray(b), rtol=0.1,
                                       atol=0.1)

    def test_fwd_lse_matches_reference_logsumexp(self, interpret):
        """The logsumexp the backward kernels consume must be the true
        softmax normalizer (checked against a direct computation)."""
        q, k, v = self._qkv(Tq=32, Tk=32, D=8, seed=7)
        _out, lse = interpret._flash_fwd_impl(q, k, v, False, 16, 16)
        B, H, T, D = q.shape
        s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k))
        s = s / np.sqrt(D)
        ref = np.log(np.sum(np.exp(
            s - s.max(-1, keepdims=True)), -1)) + s.max(-1)
        np.testing.assert_allclose(
            np.asarray(lse).reshape(B * H, T),
            ref.reshape(B * H, T), rtol=1e-5, atol=1e-5)

    def test_mha_routes_through_kernel(self, interpret, monkeypatch):
        """multi_head_attention and the layer-side _mha_apply must reach
        the pallas kernel (not silently fall back) when it is available."""
        from deeplearning4j_tpu.ops import pallas_attention as pa
        from deeplearning4j_tpu.ops.attention import multi_head_attention
        from deeplearning4j_tpu.nn.conf.attention import _mha_apply, _mha_params

        calls = {"n": 0}
        orig = pa._flash_fwd_impl

        def counted(*a, **kw):
            calls["n"] += 1
            return orig(*a, **kw)

        monkeypatch.setattr(pa, "_flash_fwd_impl", counted)

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 20, 8).astype("float32"))
        Wq, Wk, Wv = (jnp.asarray(rng.randn(8, 8).astype("float32"))
                      for _ in range(3))
        Wo = jnp.asarray(rng.randn(8, 8).astype("float32"))
        multi_head_attention(x, Wq, Wk, Wv, Wo, nHeads=2)
        assert calls["n"] == 1

        params = _mha_params(jax.random.key(0), 8, 2, 4, 8, "xavier",
                             jnp.float32, None)
        _mha_apply(params, x, x, 2)
        assert calls["n"] == 2


class TestDispatchTable:
    """Pin flash_attention's dispatch table: on TPU the kernel at T=512
    and T=8192, the blockwise scan in the mid-T window (T=2048) and
    wherever the kernel's shape rule fails. The window comes from one
    pre-PR-1 builder capture (ROADMAP S5 owns its re-measurement).
    _choose_impl is the pure decision function the real dispatcher
    uses."""

    # (T, dispatched impl on TPU)
    MEASURED = [(512, "flash"), (2048, "blockwise"), (8192, "flash")]

    @pytest.mark.parametrize("T,winner", MEASURED)
    def test_tpu_dispatch_matches_banked_table(self, T, winner):
        from deeplearning4j_tpu.ops.pallas_attention import _choose_impl

        assert _choose_impl(T, on_tpu=True) == winner

    def test_short_seq_uses_fused_on_tpu(self):
        from deeplearning4j_tpu.ops.pallas_attention import _choose_impl

        assert _choose_impl(256, on_tpu=True) == "fused"
        # bounded-memory request never takes the O(T^2)-score path
        assert _choose_impl(256, on_tpu=True, force_streaming=True) \
            == "blockwise"

    def test_window_boundaries(self):
        """The blockwise window must cover the measured T=2048 win and
        release both measured flash wins."""
        from deeplearning4j_tpu.ops.pallas_attention import (
            _BLOCKWISE_WINDOW, _MIN_FLASH_SEQ, _choose_impl)

        lo, hi = _BLOCKWISE_WINDOW
        assert _MIN_FLASH_SEQ <= lo <= 2048 < hi <= 8192
        assert _choose_impl(lo, on_tpu=True) == "blockwise"
        assert _choose_impl(hi, on_tpu=True) == "flash"

    def test_mask_and_cpu_routes(self):
        from deeplearning4j_tpu.ops.pallas_attention import _choose_impl

        # LONG ragged masks still stream, on every backend
        assert _choose_impl(4096, on_tpu=True, has_mask=True) == "blockwise"
        # CPU: fused up to 2048, blockwise beyond (memory, not speed)
        assert _choose_impl(512, on_tpu=False) == "fused"
        assert _choose_impl(8192, on_tpu=False) == "blockwise"
        # interpreter-mode tests force the kernel path
        assert _choose_impl(64, on_tpu=False, interpret=True) == "flash"

    def test_masked_short_seq_routes_fused(self):
        """The round-6 mask dimension: below the fused/flash crossover
        a masked call takes the fused path (dot_product_attention grew
        key_mask support) instead of unconditionally paying the
        blockwise scan; an explicit bounded-memory request still
        streams."""
        from deeplearning4j_tpu.ops.pallas_attention import (
            _MIN_FLASH_SEQ, _choose_impl)

        for on_tpu in (True, False):
            assert _choose_impl(256, on_tpu=on_tpu,
                                has_mask=True) == "fused"
            assert _choose_impl(_MIN_FLASH_SEQ - 1, on_tpu=on_tpu,
                                has_mask=True) == "fused"
            # at/after the crossover: the scan's O(T) memory wins
            assert _choose_impl(_MIN_FLASH_SEQ, on_tpu=on_tpu,
                                has_mask=True) == "blockwise"
            # bounded-memory contract outranks the mask fast path
            assert _choose_impl(256, on_tpu=on_tpu, has_mask=True,
                                force_streaming=True) == "blockwise"


class TestFusedMaskParity:
    """dot_product_attention(key_mask=...) vs the blockwise-masked
    reference: same semantics (masked keys ignored, fully-masked rows
    emit 0), so the round-6 dispatch rewire cannot change results."""

    def _qkv(self, B=2, H=2, T=16, D=8):
        rng = np.random.RandomState(3)
        mk = lambda: jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
        return mk(), mk(), mk()

    def test_fused_masked_equals_blockwise_masked(self):
        from deeplearning4j_tpu.ops.attention import (
            blockwise_attention, dot_product_attention)

        q, k, v = self._qkv()
        km = np.ones((2, 16), bool)
        km[0, 10:] = False   # ragged batch row
        km[1, :] = True
        km = jnp.asarray(km)
        o_f = dot_product_attention(q, k, v, key_mask=km)
        o_b = blockwise_attention(q, k, v, block_size=4, key_mask=km)
        np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_b),
                                   rtol=2e-5, atol=2e-5)

    def test_fully_masked_rows_emit_zero(self):
        from deeplearning4j_tpu.ops.attention import (
            blockwise_attention, dot_product_attention)

        q, k, v = self._qkv()
        km = np.ones((2, 16), bool)
        km[0, :] = False     # every key of batch 0 masked
        km = jnp.asarray(km)
        o_f = dot_product_attention(q, k, v, key_mask=km)
        o_b = blockwise_attention(q, k, v, block_size=4, key_mask=km)
        assert np.all(np.asarray(o_f[0]) == 0)
        np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_b),
                                   rtol=2e-5, atol=2e-5)

    def test_causal_key_mask_row_with_no_valid_key(self):
        """A row whose only causally-visible keys are ALL masked (query
        0 with key 0 padding) must emit 0 on both paths: the fused
        zero-row guard has to consider the COMBINED causal+key_mask
        validity, not just any(key_mask)."""
        from deeplearning4j_tpu.ops.attention import (
            blockwise_attention, dot_product_attention)

        q, k, v = self._qkv()
        km = np.ones((2, 16), bool)
        km[0, 0] = False     # query row 0 of batch 0 sees no valid key
        km = jnp.asarray(km)
        o_f = dot_product_attention(q, k, v, causal=True, key_mask=km)
        o_b = blockwise_attention(q, k, v, block_size=4, causal=True,
                                  key_mask=km)
        assert np.all(np.asarray(o_f[0, :, 0]) == 0)
        np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_b),
                                   rtol=2e-5, atol=2e-5)

    def test_flash_attention_mask_dispatch_parity(self):
        """The public entry: flash_attention with a key_mask at short T
        (now the fused path) matches the explicit blockwise scan."""
        from deeplearning4j_tpu.ops.attention import blockwise_attention
        from deeplearning4j_tpu.ops.pallas_attention import flash_attention

        q, k, v = self._qkv()
        km = np.ones((2, 16), bool)
        km[0, 7:] = False
        km = jnp.asarray(km)
        o = flash_attention(q, k, v, key_mask=km)
        o_ref = blockwise_attention(q, k, v, block_size=4, key_mask=km)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
