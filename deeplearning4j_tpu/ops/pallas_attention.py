"""Flash attention as a hand-written Pallas TPU kernel.

Reference: the upstream attention layers (SelfAttentionLayer et al.) run
through cuDNN-era fused kernels on GPU; SURVEY.md row 21 commits this repo
to a flash-style Pallas kernel for the TPU hot path, with the lax.scan
blockwise form (ops/attention.py) as the portable form.

Design: one grid step per (batch*heads, q-block); the kernel streams KV
blocks through VMEM with an online-softmax recurrence (Rabe & Staats /
FlashAttention), so the [T, T] score matrix never materialises in HBM.
Score matmuls hit the MXU with fp32 accumulation regardless of the input
dtype (bf16 inputs stay bf16 in HBM/VMEM).

Backward: hand-written flash backward kernels (default, round 12) — the
forward additionally emits the per-row logsumexp, and two Pallas kernels
rebuild the probabilities blockwise from (q, k, lse) to produce dq and
dk/dv with fp32 accumulators, O(T) memory, and no [T, T] score
materialisation (the FlashAttention-2 backward recurrence). The previous
strategy — recompute the blockwise forward under jax.vjp and let XLA
differentiate it — stays available as DL4J_TPU_FLASH_BWD=recompute (and
as the autotune arbiter's alternative candidate); it costs extra
activation-scale HBM traffic for the scan carries.

TPU layout: the logsumexp and delta rows are LANE-DENSE — stored
[B*H, 1, T_pad] and blocked (1, 1, block_q) — because Mosaic refuses a
block whose second-to-last dim is 1 over a longer array axis. K and V
(dq kernel: K, V; dk/dv kernel: q, do) stay whole-sequence resident in
VMEM per program, so the kernels only fit up to a sequence length the
dispatcher states as a rule on shapes (`_kernel_fits`).

`flash_attention` chooses among the kernel, the fused XLA form and the
blockwise scan from the platform and the shapes alone (`_choose_impl`);
a kernel it chose either runs or raises.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.attention import blockwise_attention

_NEG_INF = -1e30

#: logsumexp sentinel for rows with NO valid key (fully padded): large
#: POSITIVE, so the backward's exp(s - lse) underflows to exactly 0 for
#: every key instead of overflowing (a -inf lse would give exp(+inf))
_LSE_EMPTY = 1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *,
                block_k: int, Tk: int, causal: bool, block_q: int,
                scale: float):
    """One (bh, q-block) program. Refs carry a leading singleton bh axis:
    q_ref [1, bq, D], k_ref/v_ref [1, Tk_pad, D]. Emits the output
    block and — only when the caller requested it (the kernel-backward
    path; inference and the recompute backward skip the extra HBM
    write) — the per-row logsumexp."""
    from jax.experimental import pallas as pl

    _, bq, D = q_ref.shape
    Tk_pad = k_ref.shape[1]
    n_kb = Tk_pad // block_k
    iq = pl.program_id(1)

    q = q_ref[0].astype(jnp.float32) * scale

    acc0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, carry):
        acc, m, l = carry
        kj = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vj = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kj, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        valid = k_pos < Tk
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        # explicit zero where invalid: a fully-masked block's sentinel
        # otherwise normalises itself away (exp(s - m) == 1)
        p = jnp.where(valid, p, 0.0)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, vj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    if causal:
        # skip KV blocks entirely above the diagonal for this q block
        n_used = jnp.minimum(
            (iq + 1) * block_q + block_k - 1, Tk_pad) // block_k
    else:
        n_used = n_kb
    acc, m, l = jax.lax.fori_loop(0, n_used, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.where(l == 0, 1.0, l)[:, None]).astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(l), _LSE_EMPTY)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, block_k: int, Tk: int, causal: bool,
                   block_q: int, scale: float):
    """dq for one (bh, q-block): stream KV blocks, rebuild p from the
    saved logsumexp (no second online softmax), accumulate
    dq += (p * (dp - delta)) @ k in fp32. delta = rowsum(do * o) is
    precomputed outside (one elementwise pass)."""
    from jax.experimental import pallas as pl

    _, bq, D = q_ref.shape
    Tk_pad = k_ref.shape[1]
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, dq):
        kj = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vj = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kj, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        valid = k_pos < Tk
        if causal:
            valid = valid & (q_pos >= k_pos)
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, vj, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(ds, kj, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    if causal:
        n_used = jnp.minimum(
            (iq + 1) * block_q + block_k - 1, Tk_pad) // block_k
    else:
        n_used = Tk_pad // block_k
    dq = jax.lax.fori_loop(0, n_used, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, block_q: int, Tk: int,
                    causal: bool, block_k: int, scale: float):
    """dk and dv for one (bh, kv-block): stream q blocks (causal skips
    the blocks fully above this kv block's diagonal), accumulate
    dv += p^T @ do and dk += (p * (dp - delta))^T @ (q * scale)."""
    from jax.experimental import pallas as pl

    _, bk, D = k_ref.shape
    Tq_pad = q_ref.shape[1]
    jk = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    k_pos = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, bk), 1)
    k_valid = (jk * block_k
               + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)) < Tk

    def body(i, carry):
        dk, dv = carry
        qi = q_ref[0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32) * scale
        doi = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lsei = lse_ref[0, 0, pl.ds(i * block_q, block_q)]
        deltai = delta_ref[0, 0, pl.ds(i * block_q, block_q)]
        s = jax.lax.dot_general(qi, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        valid = k_pos < Tk
        if causal:
            valid = valid & (q_pos >= k_pos)
        p = jnp.where(valid, jnp.exp(s - lsei[:, None]), 0.0)
        dv_new = dv + jax.lax.dot_general(
            p, doi, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(doi, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - deltai[:, None])
        dk_new = dk + jax.lax.dot_general(
            ds, qi, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    i0 = (jk * block_k) // block_q if causal else 0
    dk, dv = jax.lax.fori_loop(
        i0, Tq_pad // block_q, body,
        (jnp.zeros((bk, D), jnp.float32), jnp.zeros((bk, D), jnp.float32)))
    # zero the KV padding rows so the slice-off can't leak garbage
    dk_ref[0] = jnp.where(k_valid, dk, 0.0).astype(dk_ref.dtype)
    dv_ref[0] = jnp.where(k_valid, dv, 0.0).astype(dv_ref.dtype)


# test hook: when True, pallas_call runs in interpreter mode (works on CPU)
# and flash_attention always takes the kernel path regardless of backend
# (tests/test_attention.py::TestFlashKernel sets this to check the kernel
# against the fused reference, forward and backward)
_INTERPRET = False

#: backward strategy for the pallas kernel path: "kernel" (default) =
#: the hand-written flash backward kernels (_bwd_dq_kernel /
#: _bwd_dkv_kernel; probabilities rebuilt from the saved logsumexp);
#: "recompute" = jax.vjp through the blockwise scan (the pre-round-12
#: behavior). Tunable via the autotune arbiter; part of the AOT
#: ambient fingerprint.
_BWD_IMPLS = ("kernel", "recompute")
_BWD_IMPL = os.environ.get("DL4J_TPU_FLASH_BWD", "kernel").lower()
if _BWD_IMPL not in _BWD_IMPLS:
    raise ValueError(
        f"DL4J_TPU_FLASH_BWD must be one of {_BWD_IMPLS}, got "
        f"{os.environ['DL4J_TPU_FLASH_BWD']!r}")


def set_flash_bwd(impl):
    """Set the flash-attention backward impl; returns the previous
    value (the autotune arbiter's entry)."""
    global _BWD_IMPL
    impl = str(impl).lower()
    if impl not in _BWD_IMPLS:
        raise ValueError(
            f"flash_bwd must be one of {_BWD_IMPLS}, got {impl!r}")
    old, _BWD_IMPL = _BWD_IMPL, impl
    return old


def _pad_flat(x, T, pad):
    """[B,H,T,D] -> [B*H, T+pad, D] (zero row padding)."""
    B, H, _, D = x.shape
    xf = x.reshape(B * H, T, D)
    if pad:
        xf = jnp.pad(xf, ((0, 0), (0, pad), (0, 0)))
    return xf


def _flash_fwd_impl(q, k, v, causal, block_q, block_k, need_lse=True):
    """q [B,H,Tq,D], k/v [B,H,Tk,D] -> ([B,H,Tq,D], lse [B*H,1,Tq_pad]
    or None) via pallas_call. The logsumexp (padded lane-dense row form
    — the backward kernels reuse it without reshaping) is only
    materialised when requested: inference and the recompute backward
    skip the extra (B*H, Tq) fp32 HBM write entirely."""
    from jax.experimental import pallas as pl

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    pq = (-Tq) % bq
    pk = (-Tk) % bk
    qf = _pad_flat(q, Tq, pq)
    kf = _pad_flat(k, Tk, pk)
    vf = _pad_flat(v, Tk, pk)
    Tqp, Tkp = Tq + pq, Tk + pk

    kernel = functools.partial(
        _fwd_kernel, block_k=bk, Tk=Tk, causal=causal, block_q=bq,
        scale=1.0 / (D ** 0.5))
    out_specs = [pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B * H, Tqp, D), q.dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec((1, 1, bq), lambda bh, i: (bh, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((B * H, 1, Tqp),
                                              jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=(B * H, Tqp // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, Tkp, D), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, Tkp, D), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_INTERPRET,
    )(qf, kf, vf)
    out, lse = (res if need_lse else (res[0], None))
    return out[:, :Tq].reshape(B, H, Tq, D), lse


def _flash_bwd_impl(q, k, v, o, lse, do, causal, block_q, block_k):
    """The flash backward: dq kernel over q blocks, dk/dv kernel over
    KV blocks. delta = rowsum(do * o) is one elementwise pass; p is
    rebuilt blockwise from the saved logsumexp — no [T,T] buffer, no
    second online softmax, fp32 accumulators throughout."""
    from jax.experimental import pallas as pl

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    pq = (-Tq) % bq
    pk = (-Tk) % bk
    Tqp, Tkp = Tq + pq, Tk + pk
    qf = _pad_flat(q, Tq, pq)
    dof = _pad_flat(do, Tq, pq)
    of = _pad_flat(o, Tq, pq)
    kf = _pad_flat(k, Tk, pk)
    vf = _pad_flat(v, Tk, pk)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]
    scale = 1.0 / (D ** 0.5)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=bk, Tk=Tk,
                          causal=causal, block_q=bq, scale=scale),
        grid=(B * H, Tqp // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, Tkp, D), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, Tkp, D), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda bh, i: (bh, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda bh, i: (bh, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tqp, D), q.dtype),
        interpret=_INTERPRET,
    )(qf, kf, vf, dof, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, Tk=Tk,
                          causal=causal, block_k=bk, scale=scale),
        grid=(B * H, Tkp // bk),
        in_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, Tqp, D), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, Tqp, D), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, 1, Tqp), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, 1, Tqp), lambda bh, j: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, j: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tkp, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tkp, D), v.dtype),
        ],
        interpret=_INTERPRET,
    )(kf, vf, qf, dof, lse, delta)
    return (dq[:, :Tq].reshape(B, H, Tq, D),
            dk[:, :Tk].reshape(B, H, Tk, D),
            dv[:, :Tk].reshape(B, H, Tk, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_k):
    # primal (no differentiation): never materialise the lse
    return _flash_fwd_impl(q, k, v, causal, block_q, block_k,
                           need_lse=False)[0]


def _flash_fwd(q, k, v, causal, block_q, block_k):
    # the bwd strategy decides the residuals at trace time: the kernel
    # backward needs (o, lse); the recompute backward re-runs the
    # blockwise forward from (q, k, v) alone and must not pay the lse
    # write or carry dead residuals
    need = _BWD_IMPL == "kernel"
    out, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k,
                               need_lse=need)
    # o rides as a residual UNPADDED: it is the primal output, so the
    # buffer is shared with whatever the caller keeps alive anyway
    return out, (q, k, v, out if need else None, lse)


def _flash_bwd(causal, block_q, block_k, res, g):
    q, k, v, o, lse = res
    if lse is not None:
        # (checking the RESIDUALS, not _BWD_IMPL again: a knob flip
        # between the fwd and bwd trace must not mismatch them)
        return _flash_bwd_impl(q, k, v, o, lse, g, causal, block_q,
                               block_k)
    # recompute-VJP through the O(T)-memory blockwise reference
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(q_, k_, v_, block_size=block_k,
                                               causal=causal), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# below this sequence length the fused XLA attention wins: the [T,T]
# score tile fits comfortably on-chip and pallas_call launch overhead
# isn't amortised
_MIN_FLASH_SEQ = 512

# Mid-T window where the lax.scan blockwise form is dispatched instead
# of the kernel. The boundaries come from one pre-PR-1 builder capture
# (bf16 B4 H8 D64: blockwise fastest at T=2048, the kernel at 512 and
# 8192) that has not been re-measured on the current kernels — ROADMAP
# S5 owns the sweep that keeps or deletes this window.
_BLOCKWISE_WINDOW = (1024, 4096)

#: VMEM the whole-sequence-resident operands may take. Every kernel
#: keeps two [T_pad, D] operands resident per program (fwd/dq: K and V;
#: dk/dv: q and do), double-buffered, lanes padded to 128; the other
#: 5 MiB of the 16 MiB scoped-VMEM limit go to the per-block buffers,
#: the lse/delta rows and the loop's fp32 temporaries. Compiled against
#: a v5e descriptor (libtpu 0.0.34, block 512, causal, fwd-with-lse the
#: binding kernel) the largest T that fits is 18944 (bf16 D=64), 12288
#: (bf16 D=128), 9216 (f32 D=64), 5632 (f32 D=128); this budget admits
#: 11264 / 11264 / 5632 / 5632.
_RESIDENT_VMEM_BUDGET = 11 * 2 ** 20


def _kernel_fits(Tq, Tk, D, itemsize, block_q, block_k):
    """Shape rule for the pallas kernels on TPU (module docstring):
    blocks that tile the sequence must be multiples of 128 (lane-dense
    lse/delta blocks, aligned dynamic slices), and the resident
    operands must fit _RESIDENT_VMEM_BUDGET."""
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    if bq % 128 or bk % 128:
        return False
    T_pad = max(Tq + (-Tq) % bq, Tk + (-Tk) % bk)
    return 4 * T_pad * max(D, 128) * itemsize <= _RESIDENT_VMEM_BUDGET


def _choose_impl(T, *, on_tpu, force_streaming=False, has_mask=False,
                 interpret=False, kernel_fits=True):
    """Pure dispatch decision -> 'flash' | 'fused' | 'blockwise'.

    Split out of flash_attention so tests can pin the choice per (T,
    backend) without running a kernel
    (tests/test_attention.py::TestDispatchTable). kernel_fits is
    _kernel_fits(...) for the call's shapes: where the kernel cannot
    compile, the O(T)-memory scan runs instead."""
    if has_mask:
        # the pallas kernel carries no mask; below the fused/flash
        # crossover the fused form (key_mask support in
        # dot_product_attention, round 6) beats the blockwise scan —
        # the [T,T] score tile fits on-chip and masking is one
        # jnp.where. Longer masked T keeps the O(T)-memory scan, as
        # does an explicit bounded-memory request.
        if T < _MIN_FLASH_SEQ and not force_streaming:
            return "fused"
        return "blockwise"
    if interpret:
        return "flash"
    if not on_tpu:
        if not force_streaming and T <= 2048:
            return "fused"
        return "blockwise"
    if T < _MIN_FLASH_SEQ:
        return "blockwise" if force_streaming else "fused"
    lo, hi = _BLOCKWISE_WINDOW
    if lo <= T < hi or not kernel_fits:
        return "blockwise"
    return "flash"


# ----------------------------------------------------------------------
# paged KV attention: block-table decode + chunked prefill (serving)
# ----------------------------------------------------------------------
# The serving tier (serving/kvcache.py) stores KV in fixed-size pages
# inside device-resident pools [L, P, page, H, Dh]; a per-slot block
# table maps logical KV block j -> physical page bt[s, j] (the
# vLLM/PagedAttention shape). The kernels below index K/V through that
# table instead of a contiguous [T, Dh] buffer, one page per grid step,
# with page_size as the online-softmax block: the per-head page order
# is the dense flash kernel's block order.
#
# What the kernels read. Layer index, block tables and live lengths are
# scalar-prefetched, so the pool operand is the WHOLE pool (a layer's
# slice as an operand of a custom call would be a copy XLA cannot fuse
# away) and its index map picks (layer, bt[s, p]). Past a slot's last
# live page the index map repeats that page — a repeated block index is
# not fetched again — and the body does not run: a dead page is neither
# read nor visited (tests/test_paged_attention.py poisons them with
# NaN). The pool is viewed [L, P, page*H, Dh], rows ordered (token,
# head): with H a multiple of the dtype's sublane tile that view is the
# same bytes in the TPU's tiled layout as [L, P, page, H, Dh], where a
# [.., page, H*Dh] view would be a relayout of the whole pool.
#
# Numerics: scores, softmax and the (acc, m, l) carry are float32 in
# both kernels. The PREFILL kernel keeps one product per head and runs
# _fwd_kernel's block body op for op — float32 operands, the query
# scaled before the product — so its rows are bitwise the dense flash
# kernel's on the same tokens (page == block_k) and paged_attend's in
# bfloat16. The DECODE kernel scores all heads of a page in one product
# and hands the MXU its operands in the pool's dtype (bf16 products are
# exact in the float32 accumulator): the scale follows the product and
# the probabilities are rounded to the pool's dtype for p.V, as XLA's
# default precision rounds them in paged_attend on the TPU. Its sums
# associate differently, so it sits a rounding of the dtype from the
# dense kernel, not on it (tests/test_paged_attention.py states the
# gap).

_NT_DIMS = (((1,), (1,)), ((), ()))


def _page_update(q, kj, vj, valid, m, l, acc, scale=None):
    """One page of online softmax inside the paged kernels: q [R, D]
    against kj/vj [N, D] under valid [R, N]; carry m/l [R, 1], acc
    [R, D] (fp32). Prefill calls it per head (R = N = page: one query
    tile of the chunk against one page) with float32 operands and q
    already scaled: the same ops in the same order as _fwd_kernel's
    block body. Decode calls it once for
    all heads (R = H, N = page*H, `valid` holding the head-diagonal)
    with operands in the pool's dtype and `scale` to apply to the
    float32 product."""
    s = jax.lax.dot_general(q, kj, _NT_DIMS,
                            preferred_element_type=jnp.float32)
    if scale is not None:
        s = s * scale
    s = jnp.where(valid, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m - m_new)
    # explicit zero where invalid: a fully-masked row's sentinel
    # otherwise normalises itself away (exp(s - m) == 1)
    pr = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    return (m_new, l * corr + jnp.sum(pr, axis=1, keepdims=True),
            acc * corr + jnp.dot(pr.astype(vj.dtype), vj,
                                 preferred_element_type=jnp.float32))


def _init_carry(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _paged_decode_kernel(lay_ref, bt_ref, sl_ref, q_ref, pos_ref, k_ref,
                         v_ref, *refs, page: int, scale: float):
    """One (slot, page) program of the block-table decode grid, all
    heads in one pair of products.

    Scalar-prefetch refs: lay_ref [1] layer, bt_ref [S, MP] block
    table, sl_ref [S] live KV length per slot. q_ref [H, Dh] is the
    slot's query row per head; k_ref/v_ref [page*H, Dh] the page the
    index map picked, rows (token, head). q . k^T is [H, page*H]: row
    h' against every (token, head) row, of which the head-diagonal
    h == h' is this head's scores. pos_ref [H, page*H] holds the token
    index on that diagonal and a value past any length off it, so one
    comparison masks both the other heads' rows and the tokens past
    the slot's length (the decode query sits at position length-1, so
    the length mask is the causal mask). The masked probabilities are
    exact zeros off the diagonal and p . v is [H, Dh] directly. The
    carry (acc, m, l) lives in VMEM scratch across the page axis
    (innermost grid dim); the last grid step normalises and writes. A
    padded slot (sl == 0) visits no page: l stays 0 and the l == 0
    guard emits exact zeros, with the _LSE_EMPTY (+1e30) sentinel on
    the lse output, like the dense kernel's fully-padded rows."""
    from jax.experimental import pallas as pl

    if len(refs) == 5:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
        lse_ref = None
    p = pl.program_id(1)
    length = sl_ref[pl.program_id(0)]

    @pl.when(p == 0)
    def _init():
        _init_carry(acc_ref, m_ref, l_ref)

    @pl.when(p * page < length)
    def _visit():
        valid = p * page + pos_ref[...] < length
        m_ref[...], l_ref[...], acc_ref[...] = _page_update(
            q_ref[...], k_ref[...], v_ref[...], valid,
            m_ref[...], l_ref[...], acc_ref[...], scale=scale)

    @pl.when(p == pl.num_programs(1) - 1)
    def _finalize():
        l_f = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l_f == 0, 1.0, l_f)
                      ).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[...] = jnp.where(l_f > 0, m_ref[...] + jnp.log(l_f),
                                     _LSE_EMPTY)


def _pool_rows(pool):
    """[L, P, page, H, Dh] (or one layer's [P, page, H, Dh]) ->
    [L, P, page*H, Dh]: the rows-by-(token, head) view the paged
    kernels block over (section comment: the same bytes on the TPU)."""
    if pool.ndim == 4:
        pool = pool[None]
    L, P, page, H, Dh = pool.shape
    return pool.reshape(L, P, page * H, Dh)


def _live_pages(length, page):
    """Pages a context of `length` tokens reaches: the pages the
    kernels' bodies run on (`p * page < length`)."""
    return (length + page - 1) // page


def _last_live_page(length, page):
    """Index of the last page a context of `length` tokens reaches (0
    for an empty one): where the kernels' index maps stop advancing."""
    return jnp.maximum(_live_pages(length, page) - 1, 0)


def paged_pages_visited(impl, lengths, page, table_width):
    """Pages one step's attention reads for slots of live KV `lengths`
    (host integers, one or an array) under `impl` (what
    paged_attention_impl said): each slot's live pages for the kernels,
    its whole table for paged_attend. Derived from the rule the
    kernels run by (_live_pages), not counted on the device: the spans
    that carry it (sequence.step, sequence.prefill) say what the
    dispatcher chose, and the device trace says whether that is what
    ran."""
    lengths = np.atleast_1d(np.asarray(lengths))
    if impl != "pallas":
        return int(lengths.size * table_width)
    return int(np.sum(_live_pages(lengths, page)))


def _layer_operand(k_pool, layer):
    if (layer is None) != (k_pool.ndim == 4):
        raise ValueError(
            "paged kernels take a layer's pool [P, page, H, Dh] or the "
            "whole pool [L, P, page, H, Dh] with a layer index")
    return jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                   jnp.int32), (1,))


@functools.partial(jax.jit, static_argnames=("need_lse", "interpret"))
def _paged_decode_call(layer, block_tables, seq_lens, q, k_rows, v_rows,
                       *, need_lse, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, Dh = q.shape
    page = k_rows.shape[2] // H
    MP = block_tables.shape[1]
    kernel = functools.partial(_paged_decode_kernel, page=page,
                               scale=1.0 / (Dh ** 0.5))
    row = lambda s, p, lay, bt, sl: (s, 0, 0)
    live = pl.BlockSpec(
        (None, None, page * H, Dh),
        lambda s, p, lay, bt, sl: (
            lay[0], bt[s, jnp.minimum(p, _last_live_page(sl[s], page))],
            0, 0))
    # token index of each (token, head) row on its head's diagonal, a
    # value past any length elsewhere
    lane = jax.lax.broadcasted_iota(jnp.int32, (H, page * H), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (H, page * H), 0)
    pos = jnp.where(lane % H == head, lane // H, 2 ** 30)
    out_shape = [jax.ShapeDtypeStruct((S, H, Dh), q.dtype)]
    out_specs = [pl.BlockSpec((None, H, Dh), row)]
    if need_lse:
        out_shape.append(jax.ShapeDtypeStruct((S, H, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((None, H, 1), row))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, MP),
        in_specs=[pl.BlockSpec((None, H, Dh), row),
                  pl.BlockSpec((H, page * H),
                               lambda s, p, lay, bt, sl: (0, 0)),
                  live, live],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((H, Dh), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)],
    )
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=out_shape, interpret=interpret)(
        layer, block_tables, seq_lens, q, pos, k_rows, v_rows)


def paged_flash_decode(q, k_pool, v_pool, block_tables, seq_lens,
                       layer=None, need_lse=False, interpret=None):
    """Block-table flash decode: one query row per slot, K/V read
    through the slot's block table, live pages only.

    q [S, H, Dh]; k_pool/v_pool the whole pools [L, P, page, H, Dh]
    with `layer` the (traced or static) layer index, or one layer's
    [P, page, H, Dh] with layer=None; block_tables [S, MP] int32
    (physical page per logical block; entries past a slot's last live
    page are never read); seq_lens [S] int32 (live KV tokens per slot;
    0 = padded slot -> zero output row + _LSE_EMPTY sentinel). Returns
    [S, H, Dh] (and lse [S, H] fp32 when need_lse)."""
    res = _paged_decode_call(
        _layer_operand(k_pool, layer),
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(seq_lens, jnp.int32), q, _pool_rows(k_pool),
        _pool_rows(v_pool), need_lse=bool(need_lse),
        interpret=bool(_INTERPRET if interpret is None else interpret))
    return (res[0], res[1][..., 0]) if need_lse else res[0]


def _tile_length(prm, i, page):
    """Live KV length the query tile `i` of a prefill chunk sees: its
    own page and no later one, the chunk's live length at most. prm =
    (t0, L) as _paged_prefill_kernel takes them."""
    return jnp.minimum(prm[1], prm[0] + (i + 1) * page)


def _paged_prefill_kernel(lay_ref, bt_ref, prm_ref, q_ref, k_ref, v_ref,
                          o_ref, acc_ref, m_ref, l_ref, kf_ref, vf_ref,
                          *, page: int, scale: float):
    """One (query tile, page) program of the chunked-prefill grid, all
    heads per program. A chunk is C = n * page query rows of ONE slot at
    positions t0..t0+C-1; tile i is its i-th page of rows (positions
    t0 + i*page ..), run against the live pages of the slot's block
    table up to the tile's own page — freshly written, the last one the
    tile visits, so in-chunk attention is causal by the page bound and
    the q_pos >= k_pos mask. Each tile is the program a page-sized chunk
    at t0 + i*page would be: the same pages in the same order against
    the same [page, Dh] blocks, the carry re-initialised at its first
    page. q_ref [H, page, Dh]; k_ref/v_ref [page*H, Dh], rows (token,
    head): a head's [page, Dh] is every H-th row, read by a strided load
    from a float32 copy of the page (kf_ref/vf_ref; sublane strides are
    a 32-bit affair), the float32 operands _fwd_kernel's block body
    takes. prm_ref carries (t0, L) where L = t0 + valid chunk rows;
    padded chunk rows (q_pos >= L) emit garbage the caller slices off,
    and their KV rows are masked from every valid query by k_pos < L."""
    from jax.experimental import pallas as pl

    H, tile, Dh = q_ref.shape
    i = pl.program_id(0)
    p = pl.program_id(1)
    t0 = prm_ref[0] + i * page
    L = _tile_length(prm_ref, i, page)

    @pl.when(p == 0)
    def _init():
        _init_carry(acc_ref, m_ref, l_ref)

    @pl.when(p * page < L)
    def _visit():
        q_pos = t0 + jax.lax.broadcasted_iota(jnp.int32, (tile, page), 0)
        k_pos = p * page + jax.lax.broadcasted_iota(jnp.int32,
                                                    (tile, page), 1)
        valid = (k_pos < L) & (q_pos >= k_pos)
        kf_ref[...] = k_ref[...].astype(jnp.float32)
        vf_ref[...] = v_ref[...].astype(jnp.float32)
        for h in range(H):
            rows = pl.ds(h, page, stride=H)
            m_ref[h], l_ref[h], acc_ref[h] = _page_update(
                q_ref[h].astype(jnp.float32) * scale, kf_ref[rows, :],
                vf_ref[rows, :], valid, m_ref[h], l_ref[h], acc_ref[h])

    @pl.when(p == pl.num_programs(1) - 1)
    def _finalize():
        l_f = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l_f == 0, 1.0, l_f)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_prefill_call(layer, block_table, prm, q_heads, k_rows, v_rows,
                        *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, C, Dh = q_heads.shape
    page = k_rows.shape[2] // H
    kernel = functools.partial(_paged_prefill_kernel, page=page,
                               scale=1.0 / (Dh ** 0.5))
    tile = pl.BlockSpec((H, page, Dh),
                        lambda i, p, lay, bt, prm_: (0, i, 0))
    live = pl.BlockSpec(
        (None, None, page * H, Dh),
        lambda i, p, lay, bt, prm_: (
            lay[0],
            bt[jnp.minimum(p, _last_live_page(_tile_length(prm_, i, page),
                                              page))],
            0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(C // page, block_table.shape[0]),
        in_specs=[tile, live, live],
        out_specs=tile,
        scratch_shapes=[pltpu.VMEM((H, page, Dh), jnp.float32),
                        pltpu.VMEM((H, page, 1), jnp.float32),
                        pltpu.VMEM((H, page, 1), jnp.float32),
                        pltpu.VMEM((page * H, Dh), jnp.float32),
                        pltpu.VMEM((page * H, Dh), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, C, Dh), q_heads.dtype),
        interpret=interpret)(
        layer, block_table, prm, q_heads, k_rows, v_rows)


def paged_flash_prefill(q_chunk, k_pool, v_pool, block_table, t0,
                        n_valid, layer=None, interpret=None):
    """Chunked-prefill attention for ONE slot: the prompt chunk's
    queries (C rows at offset t0, C a whole number of pages) against the
    slot's block table up to each row's own page — the chunk's pages
    must already be written into the pool (kvcache append, then this
    kernel; causal in-chunk by construction). One grid step per (query
    tile of one page, table page): a chunk of n pages gives row for row
    the bits of the same rows fed as n page-sized chunks.

    q_chunk [C, H, Dh]; k_pool/v_pool and `layer` as paged_flash_decode
    takes them; block_table [MP] int32; t0 = chunk offset (multiple of
    page_size); n_valid = live rows in this chunk (< C only for the
    prompt's tail chunk). Returns [C, H, Dh]; rows past n_valid are
    padding garbage the caller slices off."""
    page = k_pool.shape[-3]
    if q_chunk.shape[0] % page:
        raise ValueError(
            f"a prefill chunk is a whole number of pages, got "
            f"{q_chunk.shape[0]} rows at page {page}")
    t0 = jnp.asarray(t0, jnp.int32)
    prm = jnp.stack([t0, t0 + jnp.asarray(n_valid, jnp.int32)])
    out = _paged_prefill_call(
        _layer_operand(k_pool, layer), jnp.asarray(block_table, jnp.int32),
        prm, jnp.moveaxis(q_chunk, 1, 0), _pool_rows(k_pool),
        _pool_rows(v_pool),
        interpret=bool(_INTERPRET if interpret is None else interpret))
    return jnp.moveaxis(out, 0, 1)


def _paged_attend_core(q, k_pages, v_pages, length, q0):
    """Portable twin of the paged kernels for ONE (slot, head): q
    [R, Dh] raw queries at positions q0..q0+R-1, k_pages/v_pages
    [MP, page, Dh] gathered pages, length = live KV tokens. Page-
    sequential online softmax — the SAME accumulation order and ops
    as the kernels (and, page == block_k, as the dense flash kernel).
    This is what the serving step functions run on every backend."""
    R, Dh = q.shape
    MP, page, _ = k_pages.shape
    qs = q.astype(jnp.float32) * (1.0 / (Dh ** 0.5))
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (R, page), 0)

    def body(j, carry):
        acc, m, l = carry
        kj = jax.lax.dynamic_index_in_dim(
            k_pages, j, 0, keepdims=False).astype(jnp.float32)
        vj = jax.lax.dynamic_index_in_dim(
            v_pages, j, 0, keepdims=False).astype(jnp.float32)
        s = jax.lax.dot_general(qs, kj, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32,
                                                    (R, page), 1)
        valid = (k_pos < length) & (q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        corr = jnp.exp(m - m_new)
        pr = jnp.exp(s - m_new[:, None])
        pr = jnp.where(valid, pr, 0.0)
        l_new = l * corr + jnp.sum(pr, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            pr, vj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc, m, l = jax.lax.fori_loop(
        0, MP, body,
        (jnp.zeros((R, Dh), jnp.float32),
         jnp.full((R,), _NEG_INF, jnp.float32),
         jnp.zeros((R,), jnp.float32)))
    return (acc / jnp.where(l == 0, 1.0, l)[:, None]).astype(q.dtype)


def paged_attend(q, k_pages, v_pages, lengths, q_starts):
    """Batched portable paged attention (the serving hot path's form,
    jit-safe): q [S, R, H, Dh] (R = 1 for decode, R = chunk for
    prefill), k_pages/v_pages [S, MP, page, H, Dh] (pool pages already
    gathered through each slot's block table by the caller — one jnp
    take; the pallas kernels do this gather per-page in VMEM instead),
    lengths [S] live KV tokens, q_starts [S] position of q row 0.
    Returns [S, R, H, Dh]; a length-0 slot yields exact zero rows."""
    qt = jnp.moveaxis(q, 2, 1)                # [S, H, R, Dh]
    kt = jnp.moveaxis(k_pages, 3, 1)          # [S, H, MP, page, Dh]
    vt = jnp.moveaxis(v_pages, 3, 1)
    per_head = jax.vmap(_paged_attend_core,
                        in_axes=(0, 0, 0, None, None))
    per_slot = jax.vmap(per_head, in_axes=(0, 0, 0, 0, 0))
    out = per_slot(qt, kt, vt, lengths, q_starts)
    return jnp.moveaxis(out, 1, 2)


#: VMEM the paged kernels' blocks may take: K and V pages double-
#: buffered, the prefill kernel's two float32 page copies, its q and
#: output blocks (double-buffered) and its float32 carry. The benchmark's
#: shape (page 128, H 16, Dh 128, bfloat16) takes 9 MiB of it and
#: compiles for a v5e inside the 16 MiB scoped limit
#: (tests/test_pallas_tpu_lowering.py).
_PAGED_VMEM_BUDGET = 10 * 2 ** 20


def _paged_kernel_fits(page, H, Dh, itemsize):
    """Shape rule for the paged kernels on the TPU: Dh lane-aligned
    (the MXU contraction and every block's last dim); H and page
    multiples of the dtype's sublane tile (H so that the pool's
    [page*H, Dh] view is the pool's own bytes and a head's rows sit at
    a whole sublane stride, page so that a query tile's [page, Dh] tiles
    are whole); and the blocks inside _PAGED_VMEM_BUDGET."""
    sub = 32 // itemsize
    if Dh % 128 or H % sub or page % sub:
        return False
    rows = page * H * Dh
    return (4 * rows * itemsize + 2 * rows * 4      # K, V pages; copies
            + 4 * rows * itemsize                   # q tile and output
            + rows * 4 + 2 * page * H * 128 * 4     # acc; m, l (padded)
            <= _PAGED_VMEM_BUDGET)


def paged_attention_impl(page, H, Dh, dtype):
    """'pallas' or 'reference': what paged_attention runs for these
    shapes here. Read at trace time from the backend and the shapes
    alone; a kernel it chose either compiles or raises."""
    fits = _paged_kernel_fits(page, H, Dh, jnp.dtype(dtype).itemsize)
    return "pallas" if _on_tpu() and fits else "reference"


def paged_attention(q, k_pools, v_pools, layer, block_tables, lengths,
                    q_starts):
    """The serving step functions' attention over a block table: q
    [S, R, H, Dh] (R = 1: one decode row per slot; S = 1 and R a whole
    number of pages: one prefill chunk of one slot), k_pools/v_pools the
    whole pools [L, P, page, H, Dh], layer an int (a Python one or a
    traced one), block_tables [S, MP], lengths [S] live KV tokens,
    q_starts [S] position of q row 0. Returns [S, R, H, Dh].

    On the TPU, at shapes _paged_kernel_fits admits, the pallas kernels
    read the live pages straight from the pool; everywhere else
    paged_attend runs on the layer's gathered tables (the CPU path and
    the reference)."""
    _, _, page, H, Dh = k_pools.shape
    if paged_attention_impl(page, H, Dh, k_pools.dtype) == "reference":
        return paged_attend(q, k_pools[layer][block_tables],
                            v_pools[layer][block_tables], lengths,
                            q_starts)
    if q.shape[1] == 1:
        return paged_flash_decode(q[:, 0], k_pools, v_pools, block_tables,
                                  lengths, layer=layer)[:, None]
    if q.shape[0] != 1:
        raise ValueError(
            f"paged_attention takes one row per slot or one chunk of one "
            f"slot, got q {q.shape}")
    return paged_flash_prefill(q[0], k_pools, v_pools, block_tables[0],
                               q_starts[0], lengths[0] - q_starts[0],
                               layer=layer)[None]


def flash_attention(q, k, v, causal=False, key_mask=None,
                    block_q=512, block_k=512, force_streaming=False):
    """Attention [B,H,T,D] with automatic kernel dispatch.

    On TPU: fused XLA below 512 (scores fit on-chip), the Pallas flash
    kernel at long T where its shape rule holds (_kernel_fits), and
    the lax.scan blockwise form in the mid-T window and wherever the
    kernel does not fit. Ragged masks and non-TPU backends use the
    fused or blockwise form (same online-softmax math, same O(T)
    memory).

    force_streaming=True (set when the caller passed an explicit
    block_size, i.e. asked for bounded memory) never takes the fused
    O(T^2)-score path — only the pallas kernel or the blockwise scan.
    """
    from deeplearning4j_tpu.ops.attention import dot_product_attention

    Tq, Tk, D = q.shape[2], k.shape[2], q.shape[3]
    impl = _choose_impl(
        max(Tq, Tk), on_tpu=_on_tpu(), force_streaming=force_streaming,
        has_mask=key_mask is not None, interpret=_INTERPRET,
        kernel_fits=_kernel_fits(Tq, Tk, D, q.dtype.itemsize, block_q,
                                 block_k))
    if impl == "fused":
        return dot_product_attention(q, k, v, causal=causal,
                                     key_mask=key_mask)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, block_size=block_k, causal=causal,
                                   key_mask=key_mask)
    return _flash(q, k, v, causal, block_q, block_k)
