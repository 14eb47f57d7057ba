"""Matrix-free conjugate-gradient and least-squares solvers.

Reference: the repeatedly-failing optax-CG path (nn/solvers'
Polak-Ribiere + Armijo chain never reached the convex noise floor —
the seed-old tier-1 failure). This module is the native replacement
the ROADMAP promised: a pytree-aware LINEAR CG core that runs as one
XLA while_loop (whole-program compilation per arXiv:1810.09868 — no
host round-trips per iteration), reused by

  * `cg`        — solve M x = b for any SPD matvec (pytrees welcome:
                  nn/solvers routes truncated-Newton steps through it)
  * `lstsq`     — min ||A x - b||^2 (+ l2 ridge) via the normal
                  equations with A a row-sharded DistributedMatrix:
                  the A^T(A x) matvec reduces over the sharded row axis
                  with one psum per iteration, all inside the loop
  * convergence diagnostics — CGResult carries iterations, the final
                  residual norm, and a converged flag, because a solver
                  that silently returns garbage past maxiter is how the
                  optax path failed for eight PRs.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from deeplearning4j_tpu.linalg.distributed import (
    DistributedMatrix, ROW_AXIS, _check_divisible, _entry, _gather_cols,
)

__all__ = ["CGResult", "cg", "lstsq"]


class CGResult(NamedTuple):
    """Solution + convergence diagnostics of one CG solve."""

    x: Any
    iterations: jnp.ndarray     # int32: matvecs spent
    residual_norm: jnp.ndarray  # ||b - M x|| at exit
    converged: jnp.ndarray      # bool: tolerance reached before maxiter


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _vdot(a, b):
    leaves = jax.tree_util.tree_leaves(_tmap(jnp.vdot, a, b))
    return functools.reduce(jnp.add, leaves) if leaves \
        else jnp.asarray(0.0)


def _axpy(alpha, x, y):
    """y + alpha * x, leafwise, preserving y's dtypes (a python/f64
    alpha must not promote f32 state under x64 mode)."""
    return _tmap(lambda xi, yi: (yi + alpha * xi).astype(yi.dtype), x, y)


def cg(matvec, b, x0=None, *, tol=1e-5, atol=0.0, maxiter=None, M=None):
    """Conjugate gradients for S x = b, S symmetric positive
    (semi-)definite, given only the matvec. b/x may be any pytree;
    `M` is an optional preconditioner matvec (approximates S^-1).

    Jit-safe end to end: the loop is one lax.while_loop, so under jit
    the entire solve is a single XLA computation — with a
    DistributedMatrix normal-equation matvec the per-iteration psum
    stays inside the loop on device. Terminates when
    ||r|| <= max(tol * ||b||, atol) or at maxiter; CGResult.converged
    says which.
    """
    if maxiter is None:
        maxiter = sum(int(np.prod(l.shape)) for l in
                      jax.tree_util.tree_leaves(b)) or 1
    maxiter = int(maxiter)
    if maxiter < 1:
        raise ValueError(f"maxiter must be >= 1, got {maxiter}")
    precond = (lambda v: v) if M is None else M
    x0 = _tmap(jnp.zeros_like, b) if x0 is None else x0

    b_norm = jnp.sqrt(_vdot(b, b))
    thresh2 = jnp.maximum(tol * b_norm, atol) ** 2

    r0 = _tmap(lambda bi, mi: bi - mi, b, matvec(x0))
    z0 = precond(r0)
    gamma0 = _vdot(r0, z0)

    def cond(state):
        x, r, z, p, gamma, rr, k = state
        return (rr > thresh2) & (k < maxiter)

    def body(state):
        x, r, z, p, gamma, rr, k = state
        mp = matvec(p)
        alpha = gamma / _vdot(p, mp)
        x = _axpy(alpha, p, x)
        r = _axpy(-alpha, mp, r)
        z = precond(r)
        gamma_new = _vdot(r, z)
        beta = gamma_new / gamma
        p = _tmap(lambda zi, pi: (zi + beta * pi).astype(pi.dtype), z, p)
        return x, r, z, p, gamma_new, _vdot(r, r), k + 1

    state = (x0, r0, z0, z0, gamma0, _vdot(r0, r0),
             jnp.asarray(0, jnp.int32))
    x, r, _, _, _, rr, k = lax.while_loop(cond, body, state)
    return CGResult(x, k, jnp.sqrt(rr), rr <= thresh2)


# ----------------------------------------------------------------------
# distributed least squares
# ----------------------------------------------------------------------

def _lstsq_impl(al, bl, l2, tol, maxiter, row_axis, col_axis):
    """shard_map body: the WHOLE normal-equation CG solve per chip.
    al [n/R, k(/C)] is the local block, bl [n/R, m] the local rhs rows;
    x lives replicated (identical across chips — every reduction is a
    psum, so the iterates agree bitwise). One executable, one psum per
    CG iteration plus two for the setup."""
    af = _gather_cols(al, col_axis)

    def normal_matvec(x):
        return (lax.psum(af.T @ (af @ x), row_axis)
                + l2 * x).astype(x.dtype)

    atb = lax.psum(af.T @ bl, row_axis)
    res = cg(normal_matvec, atb, tol=tol, maxiter=maxiter)
    return res.x, res.iterations, res.residual_norm, res.converged


def _build_lstsq(mesh, r, c, l2, tol, maxiter):
    """The ONE builder behind the "lstsq" entry — shared by lstsq and
    _warm_lstsq so a warm-started executable can never diverge from the
    dispatch-path program (they share the _entry cache key, so they
    must share the body; cf. _build_matmul_ta)."""
    body = functools.partial(_lstsq_impl, row_axis=r, col_axis=c,
                             l2=float(l2), tol=float(tol),
                             maxiter=int(maxiter))
    return shard_map(
        body, mesh=mesh, in_specs=(P(r, c), P(r, None)),
        out_specs=(P(None, None), P(), P(), P()), check_vma=False)


def lstsq(a: DistributedMatrix, b, l2=0.0, *, tol=1e-6, maxiter=None):
    """min_x ||A x - b||^2 + l2 ||x||^2 for a row-sharded (optionally
    also column-sharded) DistributedMatrix A [n, k] and host/replicated
    rhs b [n] or [n, m]; b's rows are placed over the same row shards.
    -> CGResult with x replicated [k(, m)].

    Matrix-free: A is only ever applied, never formed as A^T A — the
    per-chip footprint is A's block plus k-sized vectors, so the solve
    works on operands bigger than one chip.
    """
    if a.row_axis is None:
        raise ValueError("lstsq needs a row-sharded DistributedMatrix "
                         "(the normal-equation reduction is over the "
                         "sharded row axis)")
    mesh, r, c = a.mesh, a.row_axis, a.col_axis
    b_arr = jnp.asarray(getattr(b, "toNumpy", lambda: b)()
                        if not isinstance(b, jnp.ndarray) else b)
    vector_rhs = b_arr.ndim == 1
    if vector_rhs:
        b_arr = b_arr[:, None]
    if b_arr.shape[0] != a.shape[0]:
        raise ValueError(f"rhs has {b_arr.shape[0]} rows, A has "
                         f"{a.shape[0]}")
    _check_divisible(b_arr.shape[0], r, mesh.shape[r], "rhs row")
    k = a.shape[1]
    if maxiter is None:
        maxiter = max(2 * k, 16)
    maxiter = int(maxiter)

    fn = _entry("lstsq", mesh, (r, c, float(l2), float(tol), maxiter),
                lambda: _build_lstsq(mesh, r, c, l2, tol, maxiter))
    bs = jax.device_put(b_arr, NamedSharding(mesh, P(r, None)))
    x, iters, rnorm, conv = fn(a.jax(), bs)
    if vector_rhs:
        x = x[:, 0]
    return CGResult(x, iters, rnorm, conv)


def _warm_lstsq(mesh, m, k, dtype, row_axis=ROW_AXIS):
    """AOT warm start for the lstsq entry (distributed.precompile)."""
    maxiter = max(2 * int(k), 16)
    fn = _entry("lstsq", mesh, (row_axis, None, 0.0, 1e-6, maxiter),
                lambda: _build_lstsq(mesh, row_axis, None, 0.0, 1e-6,
                                     maxiter))
    if not hasattr(fn, "warm"):
        return {"lstsq": ("uncached", 0.0)}
    sds = jax.ShapeDtypeStruct
    rs = NamedSharding(mesh, P(row_axis, None))
    _, status, secs = fn.warm(sds((m, k), dtype, sharding=rs),
                              sds((m, 1), dtype, sharding=rs))
    return {"lstsq": (status, round(secs, 3))}
