"""Sequence / context parallelism for long sequences.

Reference: the reference has no sequence parallelism — its LSTM BPTT path
is bounded by single-GPU memory. This module is the TPU-first capability
that replaces it for long-context attention models:

  * ring_attention — blockwise attention where each chip holds a T/n slice
    of Q/K/V and K,V blocks rotate around the ICI ring via ppermute
    (Liu et al., Ring Attention; see PAPERS.md retrieval theme). Exact
    (not approximate) attention with O(T/n) memory per chip and
    communication overlapped with the block matmuls by XLA.
  * ulysses_attention — all-to-all style: resharding [seq-parallel] ->
    [head-parallel] around a local attention, communication O(T·E/n)
    (DeepSpeed-Ulysses pattern).

Both are shard_map programs over a mesh "seq" axis and compose with the
"data" axis for dp×sp training.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops.attention import _block_attn
from deeplearning4j_tpu.parallel.mesh import SEQ_AXIS


def _ring_attention_local(q, k, v, axis_name, causal, chunk_index_fn=None):
    """Per-shard body: q,k,v are the local [B,H,Tl,D] slices."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, H, Tl, D = q.shape

    acc0 = jnp.zeros_like(q)
    m0 = jnp.full((B, H, Tl), -jnp.inf, q.dtype)
    l0 = jnp.zeros((B, H, Tl), q.dtype)

    q_pos = (my * Tl + jnp.arange(Tl))[:, None]

    def step(i, carry_kv):
        (acc, m, l), (kr, vr) = carry_kv
        # source shard of the kv block currently held: it has rotated i hops
        src = (my - i) % n
        mask = None
        if causal:
            k_pos = (src * Tl + jnp.arange(Tl))[None, :]
            mask = (q_pos >= k_pos)[None, None]
        acc, m, l = _block_attn(q, kr, vr, (acc, m, l), mask=mask)
        # rotate kv to the next chip on the ring (ICI neighbour exchange)
        perm = [(j, (j + 1) % n) for j in range(n)]
        kr = lax.ppermute(kr, axis_name, perm)
        vr = lax.ppermute(vr, axis_name, perm)
        return (acc, m, l), (kr, vr)

    carry = ((acc0, m0, l0), (k, v))
    carry = lax.fori_loop(0, n, step, carry)
    (acc, m, l), _ = carry
    return acc / l[..., None]


def ring_attention(q, k, v, mesh: Mesh, axis: str = SEQ_AXIS, causal: bool = False):
    """Exact distributed attention over sequence-sharded q,k,v [B,H,T,D]
    (T sharded over `axis`). Returns output with the same sharding."""
    spec = P(None, None, axis, None)

    fn = shard_map(
        functools.partial(_ring_attention_local, axis_name=axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return fn(q, k, v)


def _ulysses_local(q, k, v, axis_name, causal):
    """All-to-all resharding: [B, H/n local? ...]. Incoming shards are
    sequence-sharded [B,H,Tl,D]; all_to_all regroups to head-sharded
    [B,Hl,T,D], local full-T attention, then the reverse."""
    def seq_to_head(x):
        # [B,H,Tl,D] -> split H into n groups -> a2a over seq axis -> concat T
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def head_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    from deeplearning4j_tpu.ops.attention import dot_product_attention

    o = dot_product_attention(qh, kh, vh, causal=causal)
    return head_to_seq(o)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = SEQ_AXIS, causal: bool = False):
    """DeepSpeed-Ulysses style sequence parallelism (requires H % n == 0)."""
    spec = P(None, None, axis, None)
    fn = shard_map(
        functools.partial(_ulysses_local, axis_name=axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return fn(q, k, v)
