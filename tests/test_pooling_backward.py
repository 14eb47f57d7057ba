"""Argmax-routed maxpool backward vs the select-and-scatter oracle.

The custom VJP in ops/pooling.py exists to kill the single largest HBM
consumer in the ResNet-50 train step (206 MB select-and-scatter).
These tests pin (a) forward parity, (b) exact gradient
parity with JAX's stock reduce_window gradient — including on tied inputs,
where both sides must route to the FIRST maximal window element — and
(c) that the compiled gradient HLO actually contains no select-and-scatter
(anti-silent-fallback, same pattern as tests/test_attention.py's routing
assertion).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import pooling


@pytest.fixture(autouse=True)
def _argmax_impl(monkeypatch):
    """This whole file tests the ARGMAX rewrite. The library default is
    stock (see the switch's comment in ops/pooling.py), so
    without this pin every new-vs-reference parity assertion would
    compare the stock path against itself and pass vacuously."""
    monkeypatch.setattr(pooling, "_BACKWARD_IMPL", "argmax")


CASES = [
    # kernel, stride, padding  (ResNet stem pool = 3x3/2 SAME is the target)
    ((3, 3), (2, 2), "SAME"),
    ((2, 2), (2, 2), "SAME"),
    ((3, 3), (2, 2), ((1, 1), (1, 1))),
    ((2, 2), (2, 2), ((0, 0), (0, 0))),
    ((3, 2), (1, 2), ((0, 1), (1, 0))),  # asymmetric everything
    ((3, 3), (1, 1), "SAME"),            # fully overlapping windows
]


def _loss_pair(kernel, stride, padding):
    def loss_new(x, dy):
        return jnp.sum(pooling.max_pool2d(x, kernel, stride, padding) * dy)

    def loss_ref(x, dy):
        return jnp.sum(
            pooling.max_pool2d_reference(x, kernel, stride, padding) * dy)

    return loss_new, loss_ref


@pytest.mark.parametrize("kernel,stride,padding", CASES)
def test_forward_matches_reference(kernel, stride, padding):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 13, 11, 5))
    y = pooling.max_pool2d(x, kernel, stride, padding)
    y_ref = pooling.max_pool2d_reference(x, kernel, stride, padding)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@pytest.mark.slow  # tier-1 budget (PR 21): 10 s on 8 CPU cores
@pytest.mark.parametrize("kernel,stride,padding", CASES)
def test_gradient_matches_select_and_scatter(kernel, stride, padding):
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (2, 13, 11, 5), dtype=jnp.float64)
    loss_new, loss_ref = _loss_pair(kernel, stride, padding)
    dy_shape = pooling.max_pool2d_reference(x, kernel, stride, padding).shape
    dy = jax.random.normal(jax.random.PRNGKey(2), dy_shape, dtype=jnp.float64)
    g_new = jax.grad(loss_new)(x, dy)
    g_ref = jax.grad(loss_ref)(x, dy)
    # atol floor: overlapping windows sum several dy terms in a different
    # association order than select-and-scatter — fp64 ulps, nothing more.
    np.testing.assert_allclose(np.asarray(g_new), np.asarray(g_ref),
                               rtol=0, atol=1e-12)


@pytest.mark.slow  # tier-1 budget (PR 21): 12 s on 8 CPU cores
@pytest.mark.parametrize("kernel,stride,padding", CASES)
def test_gradient_tie_routing_matches(kernel, stride, padding):
    # Integer-valued floats force many intra-window ties (the post-relu
    # regime the ResNet stem pool actually sees: lots of equal zeros).
    # XLA's select-and-scatter ge-select routes to the first maximal
    # element in window order; the argmax backward must do the same.
    key = jax.random.PRNGKey(3)
    x = jnp.floor(
        jax.random.uniform(key, (2, 12, 10, 4), dtype=jnp.float64) * 3.0)
    x = jnp.maximum(x - 1.0, 0.0)  # plenty of exact zeros
    loss_new, loss_ref = _loss_pair(kernel, stride, padding)
    dy_shape = pooling.max_pool2d_reference(x, kernel, stride, padding).shape
    dy = jax.random.normal(jax.random.PRNGKey(4), dy_shape, dtype=jnp.float64)
    g_new = jax.grad(loss_new)(x, dy)
    g_ref = jax.grad(loss_ref)(x, dy)
    # A routing (tie-break) divergence would show up as a FULL dy-sized
    # mismatch at some element, not an ulp — atol=1e-12 still catches it.
    np.testing.assert_allclose(np.asarray(g_new), np.asarray(g_ref),
                               rtol=0, atol=1e-12)


@pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
def test_finite_difference_gradcheck():
    # fp64 central differences at a tie-free point.
    rng = np.random.default_rng(7)
    x = np.asarray(
        jax.random.permutation(jax.random.PRNGKey(5), 1 * 8 * 7 * 3),
        dtype=np.float64).reshape(1, 8, 7, 3) * 0.01  # distinct values, no ties

    def loss(xx):
        return jnp.sum(jnp.sin(pooling.max_pool2d(xx, (3, 3), (2, 2), "SAME")))

    g = np.asarray(jax.grad(loss)(jnp.asarray(x)))
    eps = 1e-6
    for _ in range(20):
        i = tuple(rng.integers(0, d) for d in x.shape)
        xp = x.copy(); xp[i] += eps
        xm = x.copy(); xm[i] -= eps
        fd = (float(loss(jnp.asarray(xp))) - float(loss(jnp.asarray(xm)))) / (2 * eps)
        assert abs(fd - g[i]) < 1e-5, (i, fd, g[i])


def test_no_select_and_scatter_in_grad_hlo():
    # The point of the custom VJP: with the argmax impl selected (the
    # file-wide fixture), the compiled backward must not contain
    # select-and-scatter. Fails loudly if the routing ever bypasses the
    # rewrite (e.g. wrapper bypass).
    def loss(x):
        return jnp.sum(pooling.max_pool2d(x, (3, 3), (2, 2), "SAME") ** 2)

    # Check the pre-optimization StableHLO: the CPU backend later rewrites
    # select_and_scatter into scatter, which would mask the distinction in
    # compiled text (TPU keeps it, and there it is the expensive op).
    x = jnp.ones((2, 16, 16, 4), jnp.float32)
    hlo = jax.jit(jax.grad(loss)).lower(x).as_text()
    assert "select_and_scatter" not in hlo and "scatter" not in hlo

    def loss_ref(x):
        return jnp.sum(
            pooling.max_pool2d_reference(x, (3, 3), (2, 2), "SAME") ** 2)

    hlo_ref = jax.jit(jax.grad(loss_ref)).lower(x).as_text()
    assert "select_and_scatter" in hlo_ref, (
        "oracle lost its select-and-scatter — parity tests no longer "
        "compare against the stock path")


def test_large_window_falls_back_to_reference():
    # >36-element windows route to the stock gradient by design.
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, 32, 2))
    y = pooling.max_pool2d(x, (7, 7), (7, 7), ((0, 0), (0, 0)))
    y_ref = pooling.max_pool2d_reference(x, (7, 7), (7, 7), ((0, 0), (0, 0)))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


def test_forward_mode_ad_documented_behavior():
    # Pinned tradeoff (see max_pool2d docstring): reverse-mode rules out
    # forward-mode through the custom vjp; the reference path keeps it.
    x = jnp.ones((1, 4, 4, 1))
    with pytest.raises(TypeError, match="forward-mode|jvp"):
        jax.jacfwd(lambda t: pooling.max_pool2d(t, (2, 2), (2, 2), "SAME"))(x)
    jac = jax.jacfwd(
        lambda t: pooling.max_pool2d_reference(t, (2, 2), (2, 2), "SAME"))(x)
    assert np.isfinite(np.asarray(jac)).all()


@pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
def test_bf16_dtype_preserved():
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 8, 8, 3)).astype(jnp.bfloat16)
    y = pooling.max_pool2d(x, (3, 3), (2, 2), "SAME")
    assert y.dtype == jnp.bfloat16

    def loss(xx):
        return jnp.sum(pooling.max_pool2d(xx, (3, 3), (2, 2), "SAME").astype(jnp.float32))

    g = jax.grad(loss)(x)
    assert g.dtype == jnp.bfloat16


# ======================================================================
# round 12: the saved-indices backward ("indices" impl) — the arbiter's
# CPU winner (LeNet b64: 129.1 -> 69.2 MB attributed bytes, -46%)
# ======================================================================

#: the non-overlapping cases the indices impl owns (stride >= kernel)
NON_OVERLAP_CASES = [
    ((2, 2), (2, 2), "SAME"),
    ((2, 2), (2, 2), ((0, 0), (0, 0))),
    ((2, 2), (3, 3), "SAME"),            # stride > kernel (gaps)
    ((3, 3), (3, 3), "SAME"),
    ((2, 3), (2, 3), ((1, 1), (0, 0))),  # asymmetric + explicit pads
    ((3, 3), (3, 3), ((0, 0), (1, 1))),
]


class TestIndicesImpl:
    @pytest.fixture(autouse=True)
    def _indices_impl(self, monkeypatch):
        monkeypatch.setattr(pooling, "_BACKWARD_IMPL", "indices")

    @pytest.mark.slow  # tier-1 budget (PR 21): 14 s on 8 CPU cores
    @pytest.mark.parametrize("kernel,stride,padding", NON_OVERLAP_CASES)
    def test_forward_and_gradient_bitwise(self, kernel, stride, padding):
        """First-match tie rule == select-and-scatter's ge-select, so
        parity is BITWISE (array_equal, not allclose) — non-overlapping
        windows sum nothing, there is no reassociation to forgive."""
        x = jax.random.normal(jax.random.PRNGKey(11), (2, 13, 11, 5),
                              dtype=jnp.float64)
        y = pooling.max_pool2d(x, kernel, stride, padding)
        y_ref = pooling.max_pool2d_reference(x, kernel, stride, padding)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
        loss_new, loss_ref = _loss_pair(kernel, stride, padding)
        dy = jax.random.normal(jax.random.PRNGKey(12), y.shape,
                               dtype=jnp.float64)
        g_new = jax.grad(loss_new)(x, dy)
        g_ref = jax.grad(loss_ref)(x, dy)
        np.testing.assert_array_equal(np.asarray(g_new),
                                      np.asarray(g_ref))

    @pytest.mark.slow  # tier-1 budget (PR 21): 7 s on 8 CPU cores
    @pytest.mark.parametrize("kernel,stride,padding", NON_OVERLAP_CASES)
    def test_tie_routing_bitwise(self, kernel, stride, padding):
        x = jnp.floor(jax.random.uniform(
            jax.random.PRNGKey(13), (2, 12, 10, 4),
            dtype=jnp.float64) * 3.0)
        x = jnp.maximum(x - 1.0, 0.0)  # plenty of exact-zero ties
        loss_new, loss_ref = _loss_pair(kernel, stride, padding)
        dy_shape = pooling.max_pool2d_reference(
            x, kernel, stride, padding).shape
        dy = jax.random.normal(jax.random.PRNGKey(14), dy_shape,
                               dtype=jnp.float64)
        np.testing.assert_array_equal(
            np.asarray(jax.grad(loss_new)(x, dy)),
            np.asarray(jax.grad(loss_ref)(x, dy)))

    def test_overlapping_windows_route_to_stock(self):
        """Under 'indices' an overlapping pool (the ResNet stem 3x3/2)
        keeps the stock gradient: the one-pass backward needs each
        input position in at most one window, and the scatter-add form
        measured WORSE than select-and-scatter (131.3 vs 129.1 MB)."""
        assert pooling._choose_pool_bwd((3, 3), (2, 2),
                                        impl="indices") == "stock"
        assert pooling._choose_pool_bwd((2, 2), (2, 2),
                                        impl="indices") == "indices"
        assert pooling._choose_pool_bwd((7, 7), (7, 7),
                                        impl="indices") == "stock"
        x = jnp.ones((2, 16, 16, 4), jnp.float32)

        def loss(xx):
            return jnp.sum(
                pooling.max_pool2d(xx, (3, 3), (2, 2), "SAME") ** 2)

        hlo = jax.jit(jax.grad(loss)).lower(x).as_text()
        assert "select_and_scatter" in hlo  # the stock path, by design

    def test_no_scatter_in_grad_hlo(self):
        """The impl's point: a non-overlapping pool's backward lowers
        to pure elementwise/pad HLO — no select_and_scatter, no
        scatter, and (unlike CPU's select-and-scatter rewrite) no
        standalone activation-scale iota."""
        def loss(x):
            return jnp.sum(
                pooling.max_pool2d(x, (2, 2), (2, 2), "SAME") ** 2)

        x = jnp.ones((2, 16, 16, 4), jnp.float32)
        hlo = jax.jit(jax.grad(loss)).lower(x).as_text()
        assert "select_and_scatter" not in hlo and "scatter" not in hlo

    def test_residual_is_int8_pooled_scale(self):
        """The byte win's mechanism, pinned: the backward's only data
        dependency beyond dy is the int8 winner table at POOLED scale —
        x itself is not a residual (the jaxpr proves it: no f32 input-
        scale tensor flows from the fwd into the bwd closure)."""
        import jax.tree_util as jtu

        x = jax.random.normal(jax.random.PRNGKey(15), (2, 8, 8, 3))
        _, vjp = jax.vjp(
            lambda t: pooling._max_pool2d_indices(
                t, (2, 2), (2, 2), "SAME"), x)
        res_leaves = [l for l in jtu.tree_leaves(vjp)
                      if hasattr(l, "dtype")]
        # residuals: int8 winner table [2,4,4,3] + the zero-byte H,W
        # carrier; nothing at input scale, nothing floating-point
        assert all(l.dtype == jnp.int8 for l in res_leaves), \
            [(l.shape, str(l.dtype)) for l in res_leaves]
        assert all(l.size <= 2 * 4 * 4 * 3 for l in res_leaves)

    def test_fit_trains_identically_to_stock(self):
        """End-to-end: a conv+pool net fit under 'indices' walks the
        BITWISE same trajectory as stock (the arbiter's parity
        contract at network level)."""
        from deeplearning4j_tpu.nn import (ConvolutionLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           Nesterovs, OutputLayer,
                                           SubsamplingLayer)

        def run(impl):
            old = pooling._BACKWARD_IMPL
            pooling._BACKWARD_IMPL = impl
            try:
                conf = (NeuralNetConfiguration.Builder()
                        .seed(21).updater(Nesterovs(0.1, 0.9))
                        .activation("relu").list()
                        .layer(ConvolutionLayer(nOut=4,
                                                kernelSize=(3, 3)))
                        .layer(SubsamplingLayer(poolingType="max",
                                                kernelSize=(2, 2),
                                                stride=(2, 2)))
                        .layer(OutputLayer(nOut=5, activation="softmax",
                                           lossFunction="mcxent"))
                        .setInputType(InputType.convolutional(10, 10, 1))
                        .build())
                net = MultiLayerNetwork(conf).init()
                rng = np.random.RandomState(3)
                x = rng.rand(8, 1, 10, 10).astype("float32")
                y = np.eye(5, dtype="float32")[rng.randint(0, 5, 8)]
                for _ in range(3):
                    net.fit(x, y)
                return net
            finally:
                pooling._BACKWARD_IMPL = old

        net_i, net_s = run("indices"), run("stock")
        for a, b in zip(jax.tree_util.tree_leaves(net_i._params),
                        jax.tree_util.tree_leaves(net_s._params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestGlobalMaxIndices:
    @pytest.mark.slow  # tier-1 budget (PR 21): 6 s on 8 CPU cores
    @pytest.mark.parametrize("shape,axes", [
        ((4, 6, 6, 3), (1, 2)),      # NHWC spatial
        ((4, 5, 6, 7, 3), (1, 2, 3)),  # NDHWC
        ((4, 3, 9), (2,)),            # NCW time pooling
    ])
    def test_parity_on_tie_free_data(self, shape, axes, monkeypatch):
        monkeypatch.setattr(pooling, "_GLOBAL_MAXPOOL_BWD", "indices")
        x = jax.random.normal(jax.random.PRNGKey(31), shape,
                              dtype=jnp.float64)
        y = pooling.global_pool(x, "max", axes)
        y_ref = jnp.max(x, axis=axes)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
        g = jax.grad(lambda t: jnp.sum(
            pooling.global_pool(t, "max", axes) ** 2))(x)
        g_ref = jax.grad(lambda t: jnp.sum(
            jnp.max(t, axis=axes) ** 2))(x)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))

    def test_tie_semantics_first_match_vs_stock_spread(self,
                                                       monkeypatch):
        """Documented divergence ON TIES ONLY: stock jnp.max autodiff
        SPLITS the cotangent evenly among tied maxima; the indices
        backward routes the whole of it to the FIRST (the
        subsampling-pool / select-and-scatter convention). Both
        conserve mass; they place it differently. Ties at float
        activation scale are measure-zero — tie-free parity above is
        bitwise."""
        x = jnp.ones((1, 3, 1), jnp.float32)  # all tied
        g_stock = jax.grad(
            lambda t: jnp.sum(jnp.max(t, axis=(1,))))(x)
        monkeypatch.setattr(pooling, "_GLOBAL_MAXPOOL_BWD", "indices")
        g_idx = jax.grad(
            lambda t: jnp.sum(pooling.global_pool(t, "max", (1,))))(x)
        assert float(jnp.sum(g_idx)) == 1.0    # mass conserved
        assert float(jnp.sum(g_stock)) == 1.0  # stock conserves too
        np.testing.assert_array_equal(
            np.asarray(g_idx)[0, :, 0], [1.0, 0.0, 0.0])  # first wins
        np.testing.assert_allclose(
            np.asarray(g_stock)[0, :, 0], [1 / 3] * 3, rtol=1e-6)

    def test_negative_axes_normalized(self, monkeypatch):
        """(-2, -1) is valid for the stock jnp.max path — the indices
        route must normalize rather than crash (review finding)."""
        monkeypatch.setattr(pooling, "_GLOBAL_MAXPOOL_BWD", "indices")
        x = jax.random.normal(jax.random.PRNGKey(40), (2, 3, 4),
                              dtype=jnp.float64)
        y = pooling.global_pool(x, "max", (-2, -1))
        np.testing.assert_array_equal(np.asarray(y),
                                      np.asarray(jnp.max(x, axis=(1, 2))))
        g = jax.grad(lambda t: jnp.sum(
            pooling.global_pool(t, "max", (-2, -1)) ** 2))(x)
        g_ref = jax.grad(lambda t: jnp.sum(
            jnp.max(t, axis=(1, 2)) ** 2))(x)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))

    def test_masked_and_stock_mode_unrouted(self, monkeypatch):
        """The indices route must not touch masked pooling or non-max
        types — they keep the legacy path bit-for-bit."""
        monkeypatch.setattr(pooling, "_GLOBAL_MAXPOOL_BWD", "indices")
        x = jax.random.normal(jax.random.PRNGKey(33), (2, 4, 6))
        mask = jnp.asarray(
            np.random.RandomState(0).rand(2, 4, 6) > 0.3)
        y = pooling.global_pool(x, "max", (2,), mask=mask)
        monkeypatch.setattr(pooling, "_GLOBAL_MAXPOOL_BWD", "stock")
        y_ref = pooling.global_pool(x, "max", (2,), mask=mask)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
        for t in ("avg", "sum", "pnorm"):
            monkeypatch.setattr(pooling, "_GLOBAL_MAXPOOL_BWD",
                                "indices")
            a = pooling.global_pool(x, t, (2,))
            monkeypatch.setattr(pooling, "_GLOBAL_MAXPOOL_BWD", "stock")
            b = pooling.global_pool(x, t, (2,))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
