"""Pooling primitives (NHWC).

Reference: libnd4j maxpool2d/avgpool2d/pnormpool2d (SubsamplingLayer) and
global pooling reductions (GlobalPoolingLayer). lax.reduce_window is the
single underlying primitive; XLA fuses the divisor correction for avg
pooling.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops.conv import _pair

# Windows larger than this get the stock select-and-scatter gradient: the
# unrolled argmax backward emits k*k pad/where terms, which stops paying for
# itself (HLO bloat) well before 6x6.
_ARGMAX_BWD_MAX_WINDOW = 36

# Backward implementation switch. The argmax rewrite was built for TPU,
# where XLA's select-and-scatter materializes a single 206 MB op in the
# ResNet stem — but a pre-PR-1 builder capture on a TPU v5e had the
# stock gradient ~1.9x faster than the argmax form at the stem-pool
# shape (not re-measured on the current code; ROADMAP D3 owns the
# verdict), and on CPU it is ~5x faster (XLA-CPU rewrites
# select-and-scatter into a vectorized scatter). Stock is therefore the
# default on every backend; the argmax path stays available
# (DL4J_TPU_MAXPOOL_BWD=argmax) and gradient-parity-pinned for backends
# where the trade may differ. bench.py still A/Bs both per run.
#
# Round 12 adds a third impl, "indices": the forward computes max AND
# the per-window argmax in one fused pass of k*k strided slices and
# saves the winner index as an INT8 residual (k*k <= 36 fits), so the
# backward never re-reads x and never lowers to select-and-scatter.
# For NON-OVERLAPPING windows (stride >= kernel — every pool in the
# zoo flagships) the backward is ONE elementwise pass: upsample dy,
# compare the saved index against a static in-window offset pattern.
# Measured on XLA:CPU it cuts the LeNet b64 train step from 129.1 MB
# to 69.2 MB (-46%) with BITWISE-equal gradients (first-match tie
# rule, same as select-and-scatter's ge-select). Overlapping windows
# keep the stock gradient under "indices" (the interior-padded
# scatter-add form measured WORSE than select-and-scatter on CPU:
# 131.3 vs 129.1 MB). Not the default — the runtime autotune arbiter
# (runtime/autotune.py, docs/AUTOTUNE.md) picks it per backend from
# measurement instead of taste.
_BACKWARD_IMPLS = ("stock", "argmax", "indices")
_BACKWARD_IMPL = os.environ.get("DL4J_TPU_MAXPOOL_BWD", "stock").lower()
if _BACKWARD_IMPL not in _BACKWARD_IMPLS:
    raise ValueError(
        f"DL4J_TPU_MAXPOOL_BWD must be one of {_BACKWARD_IMPLS}, got "
        f"{os.environ['DL4J_TPU_MAXPOOL_BWD']!r}")

#: global max-pool backward: "stock" = jnp.max autodiff (re-reads x in
#: the backward to rebuild the winner mask; ties each receive the full
#: cotangent), "indices" = save the int32 argmax in the forward, the
#: backward is one elementwise pass with no x re-read (first-match tie
#: rule). Tunable per backend by the autotune arbiter.
_GLOBAL_IMPLS = ("stock", "indices")
_GLOBAL_MAXPOOL_BWD = os.environ.get(
    "DL4J_TPU_GLOBAL_MAXPOOL_BWD", "stock").lower()
if _GLOBAL_MAXPOOL_BWD not in _GLOBAL_IMPLS:
    raise ValueError(
        f"DL4J_TPU_GLOBAL_MAXPOOL_BWD must be one of {_GLOBAL_IMPLS}, "
        f"got {os.environ['DL4J_TPU_GLOBAL_MAXPOOL_BWD']!r}")


def set_maxpool_bwd(impl):
    """Set the max_pool2d backward impl (the autotune arbiter's entry;
    DL4J_TPU_MAXPOOL_BWD seeds the initial value). Returns the previous
    impl. Callers must re-jit (the AOT ambient fingerprint carries the
    value, so cached executables never cross impls)."""
    global _BACKWARD_IMPL
    impl = str(impl).lower()
    if impl not in _BACKWARD_IMPLS:
        raise ValueError(
            f"maxpool_bwd must be one of {_BACKWARD_IMPLS}, got {impl!r}")
    old, _BACKWARD_IMPL = _BACKWARD_IMPL, impl
    return old


def set_global_maxpool_bwd(impl):
    """Set the global_pool max backward impl; returns the previous."""
    global _GLOBAL_MAXPOOL_BWD
    impl = str(impl).lower()
    if impl not in _GLOBAL_IMPLS:
        raise ValueError(
            f"global_maxpool_bwd must be one of {_GLOBAL_IMPLS}, "
            f"got {impl!r}")
    old, _GLOBAL_MAXPOOL_BWD = _GLOBAL_MAXPOOL_BWD, impl
    return old


def max_pool2d_reference(x, kernel, stride, padding):
    """Stock maxpool whose JAX gradient lowers to XLA select-and-scatter.

    Kept as the numerical oracle for `max_pool2d`'s custom backward (see
    tests/test_pooling_backward.py). Reference: libnd4j maxpool2d +
    cudnnPoolingBackward (CudnnSubsamplingHelper) — upstream likewise
    special-cases this backward off the generic path.
    """
    k, s = _pair(kernel), _pair(stride)
    pad = padding if padding == "SAME" else ((0, 0),) + tuple(padding) + ((0, 0),)
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, k[0], k[1], 1),
        window_strides=(1, s[0], s[1], 1),
        padding=pad if padding != "SAME" else "SAME",
    )


def _pool_pads(H, W, k, s, padding):
    """Resolve padding to explicit ((lo,hi),(lo,hi)) plus output dims."""
    if padding == "SAME":
        Ho = -(-H // s[0])
        Wo = -(-W // s[1])
        th = max((Ho - 1) * s[0] + k[0] - H, 0)
        tw = max((Wo - 1) * s[1] + k[1] - W, 0)
        pads = ((th // 2, th - th // 2), (tw // 2, tw - tw // 2))
    else:
        pads = (tuple(padding[0]), tuple(padding[1]))
        Ho = (H + pads[0][0] + pads[0][1] - k[0]) // s[0] + 1
        Wo = (W + pads[1][0] + pads[1][1] - k[1]) // s[1] + 1
    return pads, Ho, Wo


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _max_pool2d_argmax(x, k, s, padding):
    return max_pool2d_reference(x, k, s, padding)


def _max_pool2d_argmax_fwd(x, k, s, padding):
    return max_pool2d_reference(x, k, s, padding), x


def _max_pool2d_argmax_bwd(k, s, padding, x, dy):
    # select-and-scatter is unfusable and HBM-heavy on TPU (206 MB
    # materialized for the ResNet-50 stem pool at batch 128). Instead:
    # recompute the per-window argmax (first-match, matching XLA's
    # ge-select tie rule) from the saved input with k*k strided slices,
    # then route dy back with k*k interior-padded adds — all fusable
    # elementwise/pad HLOs.
    B, H, W, C = x.shape
    pads, Ho, Wo = _pool_pads(H, W, k, s, padding)
    Hp = H + pads[0][0] + pads[0][1]
    Wp = W + pads[1][0] + pads[1][1]
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)),
                 constant_values=-jnp.inf)
    best = None
    besti = None
    j = 0
    for dh in range(k[0]):
        for dw in range(k[1]):
            v = lax.slice(xp, (0, dh, dw, 0),
                          (B, dh + (Ho - 1) * s[0] + 1,
                           dw + (Wo - 1) * s[1] + 1, C),
                          (1, s[0], s[1], 1))
            if best is None:
                best = v
                besti = jnp.zeros(v.shape, jnp.int32)
            else:
                take = v > best  # strict >: first (lowest-index) tie wins
                best = jnp.where(take, v, best)
                besti = jnp.where(take, j, besti)
            j += 1
    zero = jnp.zeros((), dy.dtype)
    dxp = jnp.zeros((B, Hp, Wp, C), dy.dtype)
    j = 0
    for dh in range(k[0]):
        for dw in range(k[1]):
            contrib = jnp.where(besti == j, dy, zero)
            dxp = dxp + lax.pad(
                contrib, zero,
                ((0, 0, 0),
                 (dh, Hp - dh - ((Ho - 1) * s[0] + 1), s[0] - 1),
                 (dw, Wp - dw - ((Wo - 1) * s[1] + 1), s[1] - 1),
                 (0, 0, 0)))
            j += 1
    dx = lax.slice(dxp, (0, pads[0][0], pads[1][0], 0),
                   (B, pads[0][0] + H, pads[1][0] + W, C))
    return (dx,)


_max_pool2d_argmax.defvjp(_max_pool2d_argmax_fwd, _max_pool2d_argmax_bwd)


def _max_pool2d_indices_fwd_math(x, k, s, padding):
    """Fused max + per-window argmax in one pass of k*k strided slices.
    Returns (y, besti int8) — strict > keeps the FIRST (lowest-index)
    tie, the same rule as XLA select-and-scatter's ge-select and the
    argmax path, so all three impls are bitwise-interchangeable."""
    B, H, W, C = x.shape
    pads, Ho, Wo = _pool_pads(H, W, k, s, padding)
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)),
                 constant_values=-jnp.inf)
    best = None
    besti = None
    j = 0
    for dh in range(k[0]):
        for dw in range(k[1]):
            v = lax.slice(xp, (0, dh, dw, 0),
                          (B, dh + (Ho - 1) * s[0] + 1,
                           dw + (Wo - 1) * s[1] + 1, C),
                          (1, s[0], s[1], 1))
            if best is None:
                best = v
                besti = jnp.zeros(v.shape, jnp.int8)
            else:
                take = v > best
                best = jnp.where(take, v, best)
                besti = jnp.where(take, jnp.int8(j), besti)
            j += 1
    return best, besti


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _max_pool2d_indices(x, k, s, padding):
    return _max_pool2d_indices_fwd_math(x, k, s, padding)[0]


def _max_pool2d_indices_fwd(x, k, s, padding):
    y, besti = _max_pool2d_indices_fwd_math(x, k, s, padding)
    # residuals: the int8 winner table (pooled scale) plus a ZERO-BYTE
    # carrier whose aval remembers the input's H,W (custom_vjp residuals
    # must be jax types; the shape rides the aval, no data moves)
    return y, (besti, jnp.zeros((x.shape[1], x.shape[2], 0), jnp.int8))


def _max_pool2d_indices_bwd(k, s, padding, res, dy):
    # non-overlapping windows only (stride >= kernel; max_pool2d routes
    # overlapping windows to the stock path): every padded input
    # position lands in AT MOST one window, so dy routes back in ONE
    # elementwise pass — upsample dy/besti by the stride and keep the
    # positions whose in-window offset matches the saved winner. No
    # scatter, no select-and-scatter, no re-read of x.
    besti, hw = res
    H, W = hw.shape[0], hw.shape[1]
    B, Ho, Wo, C = dy.shape
    pads, _, _ = _pool_pads(H, W, k, s, padding)
    dy_up = jnp.repeat(jnp.repeat(dy, s[0], axis=1), s[1], axis=2)
    bi_up = jnp.repeat(jnp.repeat(besti, s[0], axis=1), s[1], axis=2)
    Hc, Wc = Ho * s[0], Wo * s[1]  # padded coords covered by windows
    hp = jnp.arange(Hc) % s[0]     # in-window row/col offsets
    wp = jnp.arange(Wc) % s[1]
    jpat = (hp[:, None] * k[1] + wp[None, :]).astype(jnp.int8)
    covered = (hp[:, None] < k[0]) & (wp[None, :] < k[1])
    m = (bi_up == jpat[None, :, :, None]) & covered[None, :, :, None]
    dxp = jnp.where(m, dy_up, jnp.zeros((), dy.dtype))
    # padded coords [p_lo, p_lo + extent); window coverage can stop
    # short of the input extent (truncation) — pad the tail with zeros
    need_h, need_w = pads[0][0] + H, pads[1][0] + W
    if need_h > Hc or need_w > Wc:
        dxp = jnp.pad(dxp, ((0, 0), (0, max(0, need_h - Hc)),
                            (0, max(0, need_w - Wc)), (0, 0)))
    dx = lax.slice(dxp, (0, pads[0][0], pads[1][0], 0),
                   (B, need_h, need_w, C))
    return (dx,)


_max_pool2d_indices.defvjp(_max_pool2d_indices_fwd, _max_pool2d_indices_bwd)


def _flatten_pool_spec(shape, axes):
    """(pre, pool, post) sizes for a CONTIGUOUS run of pooled axes."""
    a0, a1 = axes[0], axes[-1]
    pre = 1
    for d in shape[:a0]:
        pre *= d
    pool = 1
    for d in shape[a0:a1 + 1]:
        pool *= d
    post = 1
    for d in shape[a1 + 1:]:
        post *= d
    return pre, pool, post


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _global_max_indices(x, axes):
    return jnp.max(x, axis=axes)


def _global_max_indices_fwd(x, axes):
    pre, pool, post = _flatten_pool_spec(x.shape, axes)
    xr = x.reshape(pre, pool, post)
    y = jnp.max(xr, axis=1)
    idx = jnp.argmax(xr, axis=1).astype(jnp.int32)
    out_shape = tuple(d for i, d in enumerate(x.shape) if i not in axes)
    # zero-byte carrier: pooled dims ride the aval, everything else is 0
    carrier = jnp.zeros(tuple(d if i in axes else 0
                              for i, d in enumerate(x.shape)), jnp.int8)
    return y.reshape(out_shape), (idx, carrier)


def _global_max_indices_bwd(axes, res, dy):
    idx, carrier = res
    out_dims = iter(dy.shape)
    full_shape = tuple(carrier.shape[i] if i in axes else next(out_dims)
                       for i in range(carrier.ndim))
    pre, pool, post = _flatten_pool_spec(full_shape, axes)
    dyr = dy.reshape(pre, post)
    # first-match winner only (stock jnp.max autodiff hands EVERY tied
    # maximum the full cotangent; see tests/test_pooling_backward.py)
    mask = lax.broadcasted_iota(jnp.int32, (pre, pool, post), 1) \
        == idx[:, None, :]
    dxr = jnp.where(mask, dyr[:, None, :], jnp.zeros((), dy.dtype))
    return (dxr.reshape(full_shape),)


_global_max_indices.defvjp(_global_max_indices_fwd, _global_max_indices_bwd)


def max_pool2d(x, kernel, stride, padding):
    """Max pooling with an argmax-routed custom backward.

    Known tradeoff: the custom_vjp blocks FORWARD-mode autodiff —
    jax.jvp/jacfwd through windows <= _ARGMAX_BWD_MAX_WINDOW raise
    TypeError (larger windows fall back to the stock path and still
    support it). Nothing in this framework differentiates pooling
    forward-mode (training and gradchecks are reverse-mode); the vjp
    form is kept because it controls the residual exactly — save x
    only, recompute the argmax in the backward — where a custom_jvp
    formulation would leave k*k window masks as residuals. Use
    max_pool2d_reference if you need jacfwd.
    """
    k, s = _pair(kernel), _pair(stride)
    if isinstance(padding, str):
        if padding != "SAME":
            raise ValueError(
                f"string padding must be 'SAME', got {padding!r} "
                "(use explicit ((lo,hi),(lo,hi)) pairs otherwise)")
        pad = "SAME"
    else:
        pad = (tuple(padding[0]), tuple(padding[1]))
    impl = _choose_pool_bwd(k, s, impl=_BACKWARD_IMPL)
    if impl == "indices":
        return _max_pool2d_indices(x, k, s, pad)
    if impl == "argmax":
        return _max_pool2d_argmax(x, k, s, pad)
    return max_pool2d_reference(x, k, s, pad)


def _choose_pool_bwd(k, s, *, impl):
    """Pure dispatch decision -> 'stock' | 'argmax' | 'indices' for a
    (kernel, stride) pair under the configured impl — split out so
    tests pin the routing without running a kernel (the _choose_impl
    pattern from ops/pallas_attention.py). 'indices' requires
    non-overlapping windows (stride >= kernel): overlapping pools would
    need the interior-padded scatter-add backward, which measured WORSE
    than select-and-scatter on XLA:CPU — they keep the stock gradient."""
    if impl == "indices":
        if s[0] >= k[0] and s[1] >= k[1] \
                and k[0] * k[1] <= _ARGMAX_BWD_MAX_WINDOW:
            return "indices"
        return "stock"
    if impl == "argmax":
        if k[0] * k[1] > _ARGMAX_BWD_MAX_WINDOW:
            return "stock"
        return "argmax"
    return "stock"

def avg_pool2d(x, kernel, stride, padding, count_include_pad=True):
    k, s = _pair(kernel), _pair(stride)
    pad = padding if padding == "SAME" else ((0, 0),) + tuple(padding) + ((0, 0),)
    summed = lax.reduce_window(
        x, 0.0, lax.add,
        window_dimensions=(1, k[0], k[1], 1),
        window_strides=(1, s[0], s[1], 1),
        padding=pad if padding != "SAME" else "SAME",
    )
    if count_include_pad and padding != "SAME":
        return summed / (k[0] * k[1])
    ones = jnp.ones_like(x)
    counts = lax.reduce_window(
        ones, 0.0, lax.add,
        window_dimensions=(1, k[0], k[1], 1),
        window_strides=(1, s[0], s[1], 1),
        padding=pad if padding != "SAME" else "SAME",
    )
    return summed / counts


def pnorm_pool2d(x, kernel, stride, padding, p=2):
    k, s = _pair(kernel), _pair(stride)
    pad = padding if padding == "SAME" else ((0, 0),) + tuple(padding) + ((0, 0),)
    summed = lax.reduce_window(
        jnp.power(jnp.abs(x), p), 0.0, lax.add,
        window_dimensions=(1, k[0], k[1], 1),
        window_strides=(1, s[0], s[1], 1),
        padding=pad if padding != "SAME" else "SAME",
    )
    return jnp.power(summed, 1.0 / p)


def _triple(v):
    return (v, v, v) if isinstance(v, int) else tuple(v)


def max_pool3d(x, kernel, stride, padding):
    """[B,D,H,W,C] max pooling (reference: Subsampling3DLayer). Stock
    gradient — 3D pooling is not on the flagship hot path."""
    k, s = _triple(kernel), _triple(stride)
    pad = padding if padding == "SAME" else \
        ((0, 0),) + tuple(padding) + ((0, 0),)
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, k[0], k[1], k[2], 1),
        window_strides=(1, s[0], s[1], s[2], 1),
        padding=pad if padding != "SAME" else "SAME",
    )


def avg_pool3d(x, kernel, stride, padding, count_include_pad=True):
    k, s = _triple(kernel), _triple(stride)
    pad = padding if padding == "SAME" else \
        ((0, 0),) + tuple(padding) + ((0, 0),)
    summed = lax.reduce_window(
        x, 0.0, lax.add,
        window_dimensions=(1, k[0], k[1], k[2], 1),
        window_strides=(1, s[0], s[1], s[2], 1),
        padding=pad if padding != "SAME" else "SAME",
    )
    if count_include_pad and padding != "SAME":
        return summed / (k[0] * k[1] * k[2])
    ones = jnp.ones_like(x)
    counts = lax.reduce_window(
        ones, 0.0, lax.add,
        window_dimensions=(1, k[0], k[1], k[2], 1),
        window_strides=(1, s[0], s[1], s[2], 1),
        padding=pad if padding != "SAME" else "SAME",
    )
    return summed / counts


def upsample2d(x, size):
    """Nearest-neighbour upsampling [B,H,W,C] (reference: Upsampling2D)."""
    sh, sw = _pair(size)
    x = jnp.repeat(x, sh, axis=1)
    return jnp.repeat(x, sw, axis=2)


def global_pool(x, pooling_type, axes, mask=None, pnorm=2):
    """Global pooling over `axes` with optional mask over those axes.

    Reference: GlobalPoolingLayer (used for masked RNN sequence pooling and
    CNN global pooling).
    """
    t = str(pooling_type).lower()
    # normalize negative axes up front: the indices route's flatten
    # arithmetic and membership tests assume positive indices (a
    # caller passing (-2, -1) — valid for jnp.max — must not crash
    # only once the arbiter selects "indices")
    axes = tuple(sorted(a % x.ndim for a in axes))
    if (t == "max" and mask is None and _GLOBAL_MAXPOOL_BWD == "indices"
            and axes == tuple(range(axes[0], axes[-1] + 1))):
        # saved-indices backward (arbiter-selected): one elementwise
        # pass, no x re-read. Contiguous pooled axes only (every call
        # site: (1,2) NHWC, (1,2,3) NDHWC, (2,) NCW) — anything else
        # keeps the stock gradient below.
        return _global_max_indices(x, axes)
    if mask is not None:
        # mask must already be broadcastable to x (callers reshape, e.g.
        # [B,T] -> [B,1,T] for NCW recurrent data)
        m = jnp.broadcast_to(mask, x.shape)
        if t == "max":
            x = jnp.where(m > 0, x, -jnp.inf)
        else:
            x = x * m
        denom = jnp.maximum(jnp.sum(m, axis=axes), 1.0)
    else:
        denom = None
    if t == "max":
        return jnp.max(x, axis=axes)
    if t == "sum":
        return jnp.sum(x, axis=axes)
    if t == "avg":
        if denom is not None:
            return jnp.sum(x, axis=axes) / denom
        return jnp.mean(x, axis=axes)
    if t == "pnorm":
        s = jnp.sum(jnp.power(jnp.abs(x), pnorm), axis=axes)
        return jnp.power(s, 1.0 / pnorm)
    raise ValueError(f"Unknown pooling type {pooling_type}")
