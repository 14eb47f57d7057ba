"""Latent (MLA) attention over a paged cache of latent rows.

Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, section
2.1) caches one row a token a layer: the normalised KV latent ``c``
(``kv_lora_rank`` wide) and the rotary key ``k_r`` shared by every head,
side by side in one pool, R = rank + rope width a row. A page is stored
feature-major, ``[L, P, R, page]``: the page's 128 tokens are the lane
dimension, so the pool's layout on the TPU is row-major whatever R is
(a minor dimension of 576, not a multiple of 128, makes XLA lay the
pool out with the page minor and copy all of it into the kernel's
layout at every call), and a page is the ``K^T`` operand of the
scores' product as it lies.

A head's key is ``[c W_UK_h | k_r]`` and its value ``c W_UV_h``; the
"absorbed" form folds ``W_UK_h`` into the query and ``W_UV_h`` behind
the output, so attention runs on the cached rows themselves:

    score(h, t) = [q_nope_h W_UK_h^T | q_rope_h] . row_t * scale
    out_h       = (sum_t p(h, t) c_t) W_UV_h

Every head reads the same rows, so a page is read once for all of them.
The callers (nn/latent_moe.py) fold and unfold; this module takes the
folded queries ``[.., R]`` and returns the latent outputs ``[.., rank]``.

* ``latent_flash_decode``: the pallas block-table kernel, one query row
  per head per slot. Its grid walks a slot's pages in groups of
  ``PAGES_PER_BLOCK`` (one pool operand per page of a group), and the
  group is the unit of the online softmax: a program joins its pages
  into one [D, group x page] block, scores all of its keys in one
  product, masks those at or past the slot's length, takes one max, one
  exp, one sum and one rescale of the float32 carry (acc, m, l, in VMEM
  across the group axis), and adds the group's output in one product.
  The operands read a table made before the call: a slot's page while
  it holds live rows, and past the slot's last live page the page the
  operand held in the group before, so that it stands still and nothing
  is fetched; a group past the last live row does nothing.
* ``latent_attend``: its twin on the pool, online softmax over the live
  pages of the longest slot, any number of query rows per slot, each
  with its own position (causal mask), a group of pages a step: the
  kernel's group for decode off the TPU (its order, so the two agree
  bit for bit in interpret mode), ``PAGES_PER_BLOCK`` pages for a
  prefill chunk on every backend. What the kernel is checked against.
* ``latent_attention``: the step functions' one entry; the kernel where
  ``latent_kernel_fits`` admits the shapes on the TPU and the rows are
  one per head at one position (decode), the twin elsewhere.

Both score with the pool's dtype as operands into float32 and round the
probabilities to the pool's dtype for p . c, as the GPT decode kernel
does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["PAGES_PER_BLOCK", "latent_attend", "latent_attention",
           "latent_attention_impl", "latent_flash_decode",
           "latent_kernel_fits"]

_NEG_INF = -1e30

#: pages one program of the decode kernel reads, and the unit of its
#: online softmax: 16 pages of 128 latent rows of 576 bfloat16 are 2,048
#: keys and 2.4 MB. On a v5e at the GLM cell's shapes (PERF.md, section
#: 6) a layer's work without its fetches took 0.89 ms page by page (a
#: max, exp, sum and carry rescale and two products, under a branch, for
#: each page's 0.18 us of HBM) and 0.31 ms a group at a time; with them
#: 1.23 and 0.66 ms, against 0.34 ms for the bytes. What is left is the
#: fetch of sixteen operands a grid step and the step's own cost
PAGES_PER_BLOCK = 16

#: VMEM the decode kernel's blocks may take (latent_kernel_fits counts
#: them)
_VMEM_BUDGET = 10 * 2 ** 20


def latent_attend(q, pool, layer, block_tables, lengths, q_pos, rank,
                  scale, pages_per_step):
    """Latent attention on the pool, every backend: q [S, R, D] folded
    queries (D = rank + rope width) at positions q_pos [S, R], pool
    [L, P, D, page], layer an int or a traced int, block_tables [S, MP],
    lengths [S] live rows per slot. Row r of slot s sees the rows t with
    t < lengths[s] and t <= q_pos[s, r]. Walks the pages up to the
    longest slot's last live one, `pages_per_step` pages a step of the
    online softmax: the decode kernel's group gives its order, and more
    pass over the float32 carry fewer times (a prefill chunk's is
    [C x H, rank]).
    Returns [S, R, rank] in q's dtype; a row that sees nothing is exact
    zeros."""
    S, R, _ = q.shape
    D, page = pool.shape[2], pool.shape[3]
    G = int(pages_per_step)
    block_tables, lengths = jnp.asarray(block_tables), jnp.asarray(lengths)
    pad = -block_tables.shape[1] % G
    if pad:             # the null page, masked like any dead one
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    n_steps = (jnp.max(lengths) + G * page - 1) // (G * page)

    def body(j, carry):
        m, l, acc = carry
        # the pages straight from the pool: a layer's slice first
        # would copy the layer's whole pool
        ids = jax.lax.dynamic_slice_in_dim(block_tables, j * G, G, axis=1)
        rows = jnp.moveaxis(pool[layer, ids], 1, 2).reshape(S, D, G * page)
        s = jnp.einsum("srd,sdp->srp", q, rows,
                       preferred_element_type=jnp.float32) * scale
        k_pos = j * G * page + jnp.arange(G * page)
        valid = (k_pos[None, None] < lengths[:, None, None]) \
            & (k_pos[None, None] <= q_pos[..., None])
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        return (m_new, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                acc * corr + jnp.einsum(
                    "srp,svp->srv", p.astype(rows.dtype), rows[:, :rank],
                    preferred_element_type=jnp.float32))

    m, l, acc = jax.lax.fori_loop(
        0, n_steps, body,
        (jnp.full((S, R, 1), _NEG_INF, jnp.float32),
         jnp.zeros((S, R, 1), jnp.float32),
         jnp.zeros((S, R, rank), jnp.float32)))
    return (acc / jnp.where(l == 0, 1.0, l)).astype(q.dtype)


def _decode_kernel(lay_ref, tbl_ref, sl_ref, q_ref, *refs, page, rank,
                   group, scale):
    """One (slot, group of pages) program: q_ref [H, D] the slot's folded
    queries, refs[:group] the group's pages [D, page], then the output
    [H, rank] and the carry acc [H, rank], m, l [H, 1] in VMEM. A group
    holding a live row is one step of the online softmax over its group
    x page keys; the others do nothing."""
    from jax.experimental import pallas as pl

    pages = refs[:group]
    o_ref, acc_ref, m_ref, l_ref = refs[group:]
    g = pl.program_id(1)
    start = g * group * page
    length = sl_ref[pl.program_id(0)]

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(start < length)
    def _visit():
        # pages past the slot's last live one hold another live page of
        # the slot (finite rows), masked here like the rows past length
        rows = jnp.concatenate([p[...] for p in pages], axis=1)
        s = jnp.dot(q_ref[...], rows,
                    preferred_element_type=jnp.float32) * scale
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.where(k_pos < length, jnp.exp(s - m_new), 0.0)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:rank], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(g == pl.num_programs(1) - 1)
    def _finalize():
        l_f = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l_f == 0, 1.0, l_f)
                      ).astype(o_ref.dtype)


def _decode_group(MP):
    """Pages a program of the decode kernel takes for tables MP wide."""
    return min(PAGES_PER_BLOCK, MP)


def _operand_pages(block_tables, seq_lens, page, group):
    """[S, n x group] (n groups cover the table): the pool page operand i
    reads at grid step (s, g), at [s, g x group + i]. The slot's page
    g x group + i while it is live; past the slot's last live page the
    latest live page of the slot at a position i modulo group (the page
    the operand held one group before: it stands still, nothing is
    fetched), or the last live page where the slot has none there."""
    n = -(-block_tables.shape[1] // group)
    last = jnp.maximum((seq_lens + page - 1) // page - 1, 0)[:, None]
    j = jnp.arange(n * group)[None]
    pos = j - group * ((jnp.maximum(j - last, 0) + group - 1) // group)
    pos = jnp.where(pos < 0, last, pos)
    return jnp.take_along_axis(block_tables, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def _latent_decode_attend(layer, block_tables, seq_lens, q, pool, *, rank,
                          scale, interpret):
    """The decode call site (its name is the kernel's in a device
    trace)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, D = q.shape
    page = pool.shape[3]
    group = _decode_group(block_tables.shape[1])
    table = _operand_pages(block_tables, seq_lens, page, group)
    kernel = functools.partial(_decode_kernel, page=page, rank=rank,
                               group=group, scale=scale)

    def page_spec(i):
        return pl.BlockSpec(
            (None, None, D, page),
            lambda s, g, lay, tbl, sl: (lay[0], tbl[s, g * group + i], 0, 0))

    row = lambda s, g, lay, tbl, sl: (s, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, table.shape[1] // group),
        in_specs=[pl.BlockSpec((None, H, D), row)]
        + [page_spec(i) for i in range(group)],
        out_specs=pl.BlockSpec((None, H, rank), row),
        scratch_shapes=[pltpu.VMEM((H, rank), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, rank), q.dtype),
        interpret=interpret)(layer, table, seq_lens, q, *([pool] * group))


def latent_flash_decode(q, pool, layer, block_tables, seq_lens, rank,
                        scale, interpret=False):
    """Block-table latent decode: q [S, H, D] one folded query row per
    head and slot, pool [L, P, D, page] with `layer` its (traced) layer,
    block_tables [S, MP], seq_lens [S] live rows (the new token's
    included; 0 = padded slot, exact zeros out). Returns [S, H, rank]."""
    return _latent_decode_attend(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(seq_lens, jnp.int32), q, pool, rank=int(rank),
        scale=float(scale), interpret=bool(interpret))


def latent_kernel_fits(page, H, D, rank, itemsize):
    """Shape rule for the decode kernel on the TPU: the page a multiple
    of the dtype's sublane tile, and the blocks (a group of pages
    double-buffered and joined, the query and output blocks, the carry,
    the group's float32 scores and probabilities and the rounded
    probabilities) inside _VMEM_BUDGET."""
    if page % (32 // itemsize):
        return False
    keys = PAGES_PER_BLOCK * page
    need = (3 * keys * D * itemsize + 2 * H * (D + rank) * itemsize
            + H * (rank + 256) * 4 + H * keys * (8 + itemsize))
    return need <= _VMEM_BUDGET


def latent_attention_impl(page, H, D, rank, dtype):
    """'pallas' or 'reference': what latent_attention runs for one query
    row per head and slot (decode) on this backend at these shapes."""
    from deeplearning4j_tpu.ops.pallas_attention import _on_tpu

    fits = latent_kernel_fits(page, H, D, rank, jnp.dtype(dtype).itemsize)
    return "pallas" if _on_tpu() and fits else "reference"


def latent_attention(q, pool, layer, block_tables, lengths, q_pos, rank,
                     scale):
    """The step functions' latent attention: q [S, R, D] folded queries
    at positions q_pos [S, R]. One row per head at the slot's last
    position (decode: q_pos None) takes the kernel where
    latent_attention_impl says so, else latent_attend a group of the
    kernel's a step, in its order; rows at their own positions (a
    prefill chunk) take latent_attend, PAGES_PER_BLOCK pages a step.
    Returns [S, R, rank]."""
    D, page = pool.shape[2], pool.shape[3]
    if q_pos is None and latent_attention_impl(
            page, q.shape[1], D, rank, pool.dtype) == "pallas":
        return latent_flash_decode(q, pool, layer, block_tables, lengths,
                                   rank, scale)
    if q_pos is None:
        q_pos = jnp.broadcast_to((jnp.asarray(lengths) - 1)[:, None],
                                 q.shape[:2])
        return latent_attend(q, pool, layer, block_tables, lengths, q_pos,
                             rank, scale,
                             _decode_group(jnp.shape(block_tables)[1]))
    return latent_attend(q, pool, layer, block_tables, lengths, q_pos,
                         rank, scale, PAGES_PER_BLOCK)
