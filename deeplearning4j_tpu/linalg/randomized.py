"""Randomized SVD / PCA on mesh-sharded operands.

Reference: arXiv:2112.09017 runs its largest TPU factorizations with
randomized range finders (Halko-Martinsson-Tropp); upstream DL4J's PCA
(org.nd4j.linalg.dimensionalityreduction.PCA) gathers to one host.
Here the data matrix stays row-sharded end to end:

  * the sketch Y = A @ Omega and every subspace-iteration product is a
    local block matmul,
  * orthonormalization is CholeskyQR2 — two rounds of
    (Gram psum -> local Cholesky -> local triangular solve), the
    communication-optimal tall-skinny QR for l << n,
  * only l x l / l x d factors are ever replicated ("small factors
    replicated"); the final SVD of the projected B = Q^T A is a local
    op on a replicated small matrix.

One shard_map body = one XLA executable per (shape, k) — the
whole-program-compilation contract the RetraceSentinel test pins.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from deeplearning4j_tpu.linalg.distributed import (
    DistributedMatrix, _entry, _gather_cols,
)

__all__ = ["rsvd", "pca"]


def _cholqr(y, row_axis):
    """Distributed tall-skinny QR step: Gram over the sharded rows (one
    psum), Cholesky + triangular solve locally on the replicated l x l
    factor. SHIFTED (Fukaya et al.): an oversampled sketch of a
    low-rank matrix has a singular Gram, so a trace-scaled jitter keeps
    the Cholesky finite — the spurious directions it admits carry ~eps
    singular weight and fall out of the rank-k truncation. Returns Q
    with the same row sharding as y."""
    g = lax.psum(y.T @ y, row_axis)
    shift = (jnp.finfo(y.dtype).eps * g.shape[0]
             * jnp.trace(g)) + jnp.finfo(y.dtype).tiny
    l_ = jnp.linalg.cholesky(g + shift * jnp.eye(g.shape[0], dtype=g.dtype))
    # q = y @ inv(L)^T  via a triangular solve of the small factor
    return jax.scipy.linalg.solve_triangular(l_, y.T, lower=True).T


def _cholqr2(y, row_axis):
    """CholeskyQR2: a second round repairs the sqrt(cond) orthogonality
    loss of single CholeskyQR in fp32."""
    return _cholqr(_cholqr(y, row_axis), row_axis)


def _rsvd_body(al, omega, row_axis, col_axis, n_iter, k, center, n):
    """Whole randomized SVD per chip: al [n/R, d(/C)] local block,
    omega [d, l] replicated. Returns (u_local [n/R, k], s [k],
    vt [k, d]) with s/vt replicated."""
    af = _gather_cols(al, col_axis)
    if center:
        mu = lax.psum(jnp.sum(af, 0), row_axis) / n
        af = af - mu[None, :]
    else:
        mu = jnp.zeros((af.shape[1],), af.dtype)

    y = _cholqr2(af @ omega, row_axis)
    for _ in range(n_iter):  # static unroll: n_iter is small (2-8)
        z = lax.psum(af.T @ y, row_axis)      # [d, l] replicated
        z, _ = jnp.linalg.qr(z)               # local small QR
        y = _cholqr2(af @ z, row_axis)
    b = lax.psum(y.T @ af, row_axis)          # [l, d] replicated
    ub, s, vt = jnp.linalg.svd(b, full_matrices=False)
    u = y @ ub[:, :k]
    return u, s[:k], vt[:k], mu


def rsvd(a: DistributedMatrix, k, oversample=8, n_iter=4, seed=0):
    """Randomized truncated SVD of a row-sharded DistributedMatrix
    [n, d]: A ~= U diag(s) Vt with U [n, k] row-sharded and s [k] /
    Vt [k, d] replicated. -> (U: DistributedMatrix, s, Vt).

    `oversample` widens the sketch (l = k + oversample, clamped to
    min(n, d)); `n_iter` subspace iterations sharpen the spectrum for
    slowly decaying tails (Halko et al. 2011)."""
    u, s, vt, _ = _rsvd_run(a, k, oversample, n_iter, seed, center=False)
    return u, s, vt


def pca(a: DistributedMatrix, k, oversample=8, n_iter=4, seed=0):
    """Randomized PCA of a row-sharded data matrix [n, d]: column means
    computed distributed (one psum), centering fused into the same
    executable as the factorization — the global centered matrix is
    never materialised. -> (components [k, d], explained_variance [k],
    mean [d]), all replicated."""
    n = a.shape[0]
    if n < 2:
        raise ValueError(f"pca needs >= 2 rows, got {n}")
    _, s, vt, mu = _rsvd_run(a, k, oversample, n_iter, seed, center=True)
    return vt, (s ** 2) / (n - 1), mu


def _rsvd_run(a, k, oversample, n_iter, seed, center):
    if a.row_axis is None:
        raise ValueError("rsvd/pca need a row-sharded DistributedMatrix "
                         "(small factors replicate; rows stay sharded)")
    n, d = a.shape
    k = int(k)
    if not (1 <= k <= min(n, d)):
        raise ValueError(f"k={k} outside [1, {min(n, d)}]")
    l_ = min(k + int(oversample), min(n, d))
    mesh, r, c = a.mesh, a.row_axis, a.col_axis

    omega = jax.random.normal(jax.random.key(int(seed)), (d, l_),
                              a.dtype)

    def build():
        body = functools.partial(_rsvd_body, row_axis=r, col_axis=c,
                                 n_iter=int(n_iter), k=k,
                                 center=bool(center), n=n)
        return shard_map(
            body, mesh=mesh, in_specs=(P(r, c), P(None, None)),
            out_specs=(P(r, None), P(), P(None, None), P()),
            check_vma=False)

    # n is closed over by the body (the centering divisor), so it MUST
    # key the entry — a cached wrapper built for one row count would
    # silently mis-center a retrace at another (cf. covariance's key)
    fn = _entry("pca" if center else "rsvd", mesh,
                (r, c, k, l_, int(n_iter), bool(center), n), build)
    u, s, vt, mu = fn(a.jax(), omega)
    u = DistributedMatrix(u, mesh, row_axis=r, col_axis=None,
                          _placed=True)
    return u, s, vt, mu
