"""Mesh-sharded dense linear algebra: `DistributedMatrix` + SUMMA GEMM.

Reference: org.nd4j.linalg's BLAS layer (gemm/mmul on libnd4j) is
single-device; the TPU rebuild follows "Large Scale Distributed Linear
Algebra With Tensor Processing Units" (PAPERS.md, arXiv:2112.09017):
operands too big for one chip's HBM live block-sharded over the mesh
and every routine is ONE shard_map program — the collectives
(all_gather / ppermute / psum) are explicit and named, so the PAR04
analyzer can statically check them and PAR06 can bill per-chip bytes
(linalg/plan.py) before a pod slot is claimed.

Layouts (axis names are the canonical parallel.mesh axes, so the plans
stay PAR04-clean on the dp4xtp2 trainer mesh):

  row-sharded      P(row, None)  [m/R, k]   tall data matrices
  block-sharded    P(row, col)   [m/R, k/C] operands over a 2-D mesh
  replicated       P()           small factors (Gram, SVD bases, CG x)

Sharding NEVER pads: an indivisible dimension raises the same PAR03
contract error `parallel.sharding.shard_batch` uses — a silently
padded trailing block would corrupt the reduction, exactly the failure
the runtime boundary refuses everywhere else in this repo.

GEMM is SUMMA-shaped (Van De Geijn & Watts; the paper's Sec. II
algorithm): the stationary operand stays put, k-panels of the moving
operand rotate around the mesh ring via ppermute while each chip
accumulates its C block — per-chip memory stays O(block), never
O(global). Transpose-fused variants (`transpose_a` / `transpose_b`)
reduce over the SHARDED row axis with one psum / all_gather instead of
materialising a transposed global operand.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

#: canonical linalg placement axes — rows of a data matrix shard over
#: the data-parallel axis, columns over the model axis (PAR04: both are
#: axes of the canonical dp4xtp2 mesh)
ROW_AXIS = DATA_AXIS
COL_AXIS = MODEL_AXIS

__all__ = ["DistributedMatrix", "ROW_AXIS", "COL_AXIS", "matmul", "gram",
           "covariance", "pairwise_sq_dists", "sq_dists",
           "collective_counts", "install_retrace_sentinel", "precompile"]


def _unwrap2d(data, what="operand"):
    """INDArray / numpy / jax -> jax 2-D array (never copies a device
    buffer)."""
    arr = getattr(data, "_jx", None)
    if arr is None:
        arr = jnp.asarray(getattr(data, "toNumpy", lambda: data)())
    if arr.ndim != 2:
        raise ValueError(f"{what} must be a 2-D matrix, got shape "
                         f"{tuple(arr.shape)}")
    return arr


def _check_divisible(dim, axis, width, what):
    """The never-pad contract (PAR03), shared wording with
    parallel.sharding.shard_batch: uneven tiling would pad the trailing
    shard with garbage rows that would silently enter the reduction."""
    if dim % width != 0:
        raise ValueError(
            f"{what} dim {dim} not divisible by mesh axis '{axis}' "
            f"(size {width}): refusing to silently pad; use a dimension "
            f"that is a multiple of {width} or replicate the operand "
            "(PAR03)")


def sq_dists(a, b):
    """[n,d]x[m,d] -> [n,m] squared euclidean distances via the
    quadratic form (matmul-shaped for the MXU). fp32 precision of this
    form degrades with the data's distance from the origin, so callers
    mean-center their data first (distances are translation-invariant).
    Safe inside shard_map bodies — no collectives."""
    return jnp.maximum(
        jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :]
        - 2.0 * (a @ b.T), 0.0)


class DistributedMatrix:
    """A 2-D matrix block-sharded over a mesh.

    `row_axis` / `col_axis` name the mesh axes dims 0 / 1 shard over
    (None = that dim replicated). The wrapper is placement + metadata
    only — the payload is one jax.Array whose NamedSharding the XLA
    partitioner reads; all math goes through the module-level routines
    (matmul/gram/...), each ONE compiled executable.
    """

    __slots__ = ("_jx", "mesh", "row_axis", "col_axis")

    def __init__(self, data, mesh, row_axis=ROW_AXIS, col_axis=None,
                 _placed=False):
        arr = _unwrap2d(data, "DistributedMatrix data")
        for role, axis in (("row_axis", row_axis), ("col_axis", col_axis)):
            if axis is not None and axis not in mesh.shape:
                raise ValueError(
                    f"mesh has no axis '{axis}' (axes: "
                    f"{list(mesh.shape)}); build the mesh with it or "
                    f"pass {role}=None (PAR01)")
        if row_axis is not None and row_axis == col_axis:
            raise ValueError(
                f"row_axis and col_axis are both '{row_axis}': a mesh "
                "axis can shard at most one dim (PAR01)")
        if row_axis is not None:
            _check_divisible(arr.shape[0], row_axis,
                             mesh.shape[row_axis], "row")
        if col_axis is not None:
            _check_divisible(arr.shape[1], col_axis,
                             mesh.shape[col_axis], "column")
        self.mesh = mesh
        self.row_axis = row_axis
        self.col_axis = col_axis
        self._jx = arr if _placed else jax.device_put(
            arr, NamedSharding(mesh, P(row_axis, col_axis)))

    # ----- metadata ---------------------------------------------------
    @property
    def shape(self):
        return tuple(self._jx.shape)

    @property
    def dtype(self):
        return self._jx.dtype

    @property
    def spec(self):
        return P(self.row_axis, self.col_axis)

    def block_shape(self):
        """Per-chip block shape under this placement."""
        r = self.mesh.shape[self.row_axis] if self.row_axis else 1
        c = self.mesh.shape[self.col_axis] if self.col_axis else 1
        return (self.shape[0] // r, self.shape[1] // c)

    def per_chip_bytes(self):
        """Resident bytes of ONE chip's block — the operand term the
        static PAR06 bill (linalg.plan) predicts."""
        b = self.block_shape()
        return int(b[0]) * int(b[1]) * self._jx.dtype.itemsize

    def is_replicated(self):
        return self.row_axis is None and self.col_axis is None

    # ----- conversion -------------------------------------------------
    def jax(self):
        return self._jx

    def toNumpy(self):
        """Gather the GLOBAL matrix to the host (defeats the point at
        real scale — for small factors and test oracles)."""
        return np.asarray(self._jx)

    def toINDArray(self):
        from deeplearning4j_tpu.ndarray.ndarray import INDArray

        return INDArray(self._jx)

    def replicate(self):
        """-> replicated DistributedMatrix (one all-gather at dispatch)."""
        if self.is_replicated():
            return self
        return DistributedMatrix(self._jx, self.mesh, row_axis=None,
                                 col_axis=None)

    def __repr__(self):
        return (f"DistributedMatrix{self.shape} {self.dtype} "
                f"spec={self.spec} mesh={dict(self.mesh.shape)}")


# ----------------------------------------------------------------------
# jitted-entry plumbing: one executable per (op, mesh, axes) x shape,
# AOT-cached (PR 7) and RetraceSentinel-hookable
# ----------------------------------------------------------------------

#: test hook (analysis.retrace.RetraceSentinel): when set, entries are
#: rebuilt as plain jit around sentinel.wrap so every trace is counted
_WRAP_HOOK = None
_JIT_CACHE = {}


def install_retrace_sentinel(sentinel):
    """Route every linalg entry compiled FROM NOW ON through `sentinel`
    (analysis.RetraceSentinel) — the one-compile-per-shape proof. Pass
    None to restore the AOT-cached entries. Clears the entry cache
    either way so counting starts fresh."""
    global _WRAP_HOOK
    _WRAP_HOOK = None if sentinel is None else sentinel.wrap
    _JIT_CACHE.clear()


def _mesh_fingerprint(mesh):
    return "x".join(f"{k}{v}" for k, v in mesh.shape.items())


def _entry(op, mesh, axes, build):
    """Memoised jitted entry for (op, mesh, axes). `build()` returns the
    traceable function; the wrapper is aot.cached_jit (persistent-cache
    warm start, docs/COMPILE.md) unless a RetraceSentinel hook is
    installed, in which case a counting plain jit."""
    key = (op, mesh, axes, _WRAP_HOOK is not None)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        body = build()
        if _WRAP_HOOK is not None:
            fn = jax.jit(_WRAP_HOOK(body, op))
        else:
            from deeplearning4j_tpu.runtime import aot

            fn = aot.cached_jit(
                body, entry=f"linalg_{op}",
                fingerprint=f"linalg:{op}:{_mesh_fingerprint(mesh)}:"
                            f"{axes}")
        _JIT_CACHE[key] = fn
    return fn


# ----------------------------------------------------------------------
# shard_map bodies
# ----------------------------------------------------------------------

def _ring_steps(n):
    """Static neighbour-rotation permutation of an n-chip ring."""
    return [(j, (j + 1) % n) for j in range(n)]


def _summa_2d_body(al, bl, row_axis, col_axis, n_cols):
    """C block [m/R, n/C] for A P(r,c) x B P(r,c): B's k-blocks gathered
    over rows once (one all_gather), A's k-panels rotate around the col
    ring (ppermute) — at step s the held panel originated at col
    (my - s) % C, selecting the matching k-rows of the gathered B."""
    my = lax.axis_index(col_axis)
    bk = lax.all_gather(bl, row_axis, axis=0, tiled=True)   # [k, n/C]
    kc = al.shape[1]

    def step(s, carry):
        acc, ah = carry
        src = (my - s) % n_cols
        panel = lax.dynamic_slice_in_dim(bk, src * kc, kc, 0)
        acc = acc + ah @ panel
        ah = lax.ppermute(ah, col_axis, _ring_steps(n_cols))
        return acc, ah

    acc0 = jnp.zeros((al.shape[0], bk.shape[1]),
                     jnp.promote_types(al.dtype, bl.dtype))
    acc, _ = lax.fori_loop(0, n_cols, step, (acc0, al))
    return acc


def _summa_1d_body(al, bl, row_axis, n_rows):
    """C block [m/R, n] for A P(r) x B P(r): B's k-blocks rotate around
    the row ring; each step multiplies the matching local k-panel of A."""
    my = lax.axis_index(row_axis)
    kr = bl.shape[0]

    def step(s, carry):
        acc, bh = carry
        src = (my - s) % n_rows
        panel = lax.dynamic_slice_in_dim(al, src * kr, kr, 1)
        acc = acc + panel @ bh
        bh = lax.ppermute(bh, row_axis, _ring_steps(n_rows))
        return acc, bh

    acc0 = jnp.zeros((al.shape[0], bl.shape[1]),
                     jnp.promote_types(al.dtype, bl.dtype))
    acc, _ = lax.fori_loop(0, n_rows, step, (acc0, bl))
    return acc


def _gather_cols(al, col_axis):
    """[m_l, k/C] -> [m_l, k]: undo a column sharding inside a body."""
    if col_axis is None:
        return al
    return lax.all_gather(al, col_axis, axis=1, tiled=True)


# ----------------------------------------------------------------------
# public routines
# ----------------------------------------------------------------------

def _require_same_mesh(a, b):
    if a.mesh is not b.mesh and a.mesh != b.mesh:
        raise ValueError("operands live on different meshes")


def matmul(a: DistributedMatrix, b, transpose_a=False, transpose_b=False):
    """Distributed C = op(A) @ op(B), SUMMA-style. -> DistributedMatrix.

    Supported layouts (R = row-axis size, C = col-axis size):

      plain        A P(r,c) x B P(r,c)  -> C P(r,c)   2-D ring SUMMA
                   A P(r)   x B P(r)    -> C P(r)     1-D ring SUMMA
                   A P(r[,c]) x B replicated array -> C P(r) (k-panel
                   partials psum over the col axis when A is col-sharded)
      transpose_a  A [n,k] P(r[,c]) x B [n,m] P(r[,c]) -> A^T B
                   replicated (psum over the sharded row axis — the
                   Gram reduction; no global transpose is materialised)
      transpose_b  A [n,d] P(r) x B [m,d] P(r) -> A B^T P(r) (one
                   all_gather of B over the row axis)

    Dimensions that a layout would shard unevenly raise the PAR03
    never-pad error at placement/dispatch time, not inside XLA.
    """
    if transpose_a and transpose_b:
        raise ValueError("transpose_a and transpose_b together are not "
                         "supported; transpose the small operand on host")
    if not isinstance(a, DistributedMatrix):
        raise TypeError("matmul's first operand must be a "
                        "DistributedMatrix")
    mesh, r, c = a.mesh, a.row_axis, a.col_axis

    if transpose_a:
        return _matmul_ta(a, b)
    if transpose_b:
        return _matmul_tb(a, b)

    if r is None and c is not None:
        # A's k dim sharded with no row sharding has no SUMMA layout
        # here (B's n would shard over the same axis) — refusing beats
        # the silent fall-through to the replicated branch, which would
        # mislabel a sharded result as replicated
        raise ValueError(
            f"matmul does not support column-only sharding {a.spec}; "
            "row-shard the operand (row_axis=) or replicate() it")

    if not isinstance(b, DistributedMatrix):
        return _matmul_repl_b(a, _unwrap2d(b, "matmul rhs"))
    if b.is_replicated() and not a.is_replicated():
        # a replicated rhs has its own kernel — the layout-mismatch
        # error below would send b.replicate() callers in a circle
        return _matmul_repl_b(a, b.jax())

    _require_same_mesh(a, b)
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"matmul shape mismatch: {a.shape} @ {b.shape}")
    if (b.row_axis, b.col_axis) != (r, c):
        raise ValueError(
            f"matmul needs both operands on the same layout, got "
            f"A {a.spec} vs B {b.spec}; replicate() or re-place one")
    k = a.shape[1]
    if r is not None:
        _check_divisible(k, r, mesh.shape[r], "contraction (k)")
    if c is not None:
        _check_divisible(k, c, mesh.shape[c], "contraction (k)")

    if c is not None and r is not None:
        nc = int(mesh.shape[c])
        fn = _entry(
            "matmul2d", mesh, (r, c), lambda: shard_map(
                functools.partial(_summa_2d_body, row_axis=r, col_axis=c,
                                  n_cols=nc),
                mesh=mesh, in_specs=(P(r, c), P(r, c)), out_specs=P(r, c),
                check_vma=False))
        out_axes = (r, c)
    elif r is not None:
        nr = int(mesh.shape[r])
        fn = _entry(
            "matmul1d", mesh, (r,), lambda: shard_map(
                functools.partial(_summa_1d_body, row_axis=r, n_rows=nr),
                mesh=mesh, in_specs=(P(r, None), P(r, None)),
                out_specs=P(r, None), check_vma=False))
        out_axes = (r, None)
    else:  # both replicated: plain local product
        fn = _entry("matmul_repl", mesh, (), lambda: (lambda x, y: x @ y))
        out_axes = (None, None)
    return DistributedMatrix(fn(a.jax(), b.jax()), mesh,
                             row_axis=out_axes[0], col_axis=out_axes[1],
                             _placed=True)


def _matmul_repl_b(a, b_arr):
    """A P(r[,c]) @ replicated B: local product per row block; when A's
    k dim is col-sharded each chip multiplies its k-panel against the
    matching B rows and the partials psum over the col axis."""
    mesh, r, c = a.mesh, a.row_axis, a.col_axis
    if a.shape[1] != b_arr.shape[0]:
        raise ValueError(
            f"matmul shape mismatch: {a.shape} @ {tuple(b_arr.shape)}")

    if c is None:
        def build():
            def body(al, b):
                return al @ b

            return shard_map(body, mesh=mesh,
                             in_specs=(P(r, None), P(None, None)),
                             out_specs=P(r, None), check_vma=False)

        fn = _entry("matmul_replb", mesh, (r,), build)
    else:
        def build():
            def body(al, b):
                kc = al.shape[1]
                my = lax.axis_index(c)
                panel = lax.dynamic_slice_in_dim(b, my * kc, kc, 0)
                return lax.psum(al @ panel, c)

            return shard_map(body, mesh=mesh,
                             in_specs=(P(r, c), P(None, None)),
                             out_specs=P(r, None), check_vma=False)

        fn = _entry("matmul_replb_psum", mesh, (r, c), build)
    return DistributedMatrix(fn(a.jax(), jnp.asarray(b_arr)), mesh,
                             row_axis=r, col_axis=None, _placed=True)


def _build_matmul_ta(mesh, r, ca, cb):
    """The ONE builder behind the "matmul_ta" entry — shared by
    _matmul_ta and precompile so a warm-started executable can never
    disagree with the dispatch-path program (they share the cache key,
    so they must share the body)."""
    def body(al, bl):
        af = _gather_cols(al, ca)
        bf = _gather_cols(bl, cb)
        return lax.psum(af.T @ bf, r)

    return shard_map(body, mesh=mesh, in_specs=(P(r, ca), P(r, cb)),
                     out_specs=P(None, None), check_vma=False)


def _matmul_ta(a, b):
    """A^T @ B with both operands sharded over the same row axis: the
    contraction dim IS the sharded dim, so each chip's partial product
    reduces with ONE psum; column shards are gathered first (the result
    is a small factor, replicated by contract)."""
    if not isinstance(b, DistributedMatrix):
        b = DistributedMatrix(b, a.mesh, row_axis=a.row_axis,
                              col_axis=None)
    _require_same_mesh(a, b)
    if a.shape[0] != b.shape[0]:
        raise ValueError(
            f"matmul(transpose_a) shape mismatch: {a.shape}^T @ {b.shape}")
    if a.row_axis is None or a.row_axis != b.row_axis:
        raise ValueError(
            "matmul(transpose_a) reduces over the sharded row axis: both "
            f"operands must be row-sharded over the same axis, got "
            f"A {a.spec} vs B {b.spec}")
    mesh, r = a.mesh, a.row_axis
    ca, cb = a.col_axis, b.col_axis

    fn = _entry("matmul_ta", mesh, (r, ca, cb),
                lambda: _build_matmul_ta(mesh, r, ca, cb))
    return DistributedMatrix(fn(a.jax(), b.jax()), mesh, row_axis=None,
                             col_axis=None, _placed=True)


def _matmul_tb(a, b):
    """A @ B^T with both row-sharded: one all_gather of B over the row
    axis, then a local product — the all-pairs (similarity-matrix)
    pattern; the [n, m] result stays row-sharded."""
    if not isinstance(b, DistributedMatrix):
        b = DistributedMatrix(b, a.mesh, row_axis=a.row_axis,
                              col_axis=None)
    _require_same_mesh(a, b)
    if a.shape[1] != b.shape[1]:
        raise ValueError(
            f"matmul(transpose_b) shape mismatch: {a.shape} @ {b.shape}^T")
    if a.col_axis is not None or b.col_axis is not None:
        raise ValueError(
            "matmul(transpose_b) supports row-sharded operands only "
            f"(col_axis=None), got A {a.spec} vs B {b.spec}")
    if a.row_axis is None or a.row_axis != b.row_axis:
        raise ValueError(
            "matmul(transpose_b) needs both operands row-sharded over "
            f"the same axis, got A {a.spec} vs B {b.spec}")
    mesh, r = a.mesh, a.row_axis

    def build():
        def body(al, bl):
            bf = lax.all_gather(bl, r, axis=0, tiled=True)
            return al @ bf.T

        return shard_map(body, mesh=mesh,
                         in_specs=(P(r, None), P(r, None)),
                         out_specs=P(r, None), check_vma=False)

    fn = _entry("matmul_tb", mesh, (r,), build)
    return DistributedMatrix(fn(a.jax(), b.jax()), mesh, row_axis=r,
                             col_axis=None, _placed=True)


def _build_gram(mesh, r, c):
    """The ONE builder behind the "gram" entry — a single-input body,
    NOT matmul_ta's two-parameter one: distinct shard_map parameters
    gather the same buffer twice (XLA cannot CSE across them), which
    would double the gathered-panel peak gram_plan bills."""
    def body(al):
        af = _gather_cols(al, c)
        return lax.psum(af.T @ af, r)

    return shard_map(body, mesh=mesh, in_specs=(P(r, c),),
                     out_specs=P(None, None), check_vma=False)


def gram(a: DistributedMatrix):
    """A^T A [k, k] replicated — the reduction over the sharded row
    axis (one psum; column shards gathered once). The canonical
    building block of covariance/PCA and the CG normal equations."""
    if not isinstance(a, DistributedMatrix) or a.row_axis is None:
        raise ValueError("gram needs a row-sharded DistributedMatrix "
                         "(the reduction is over the sharded row axis)")
    mesh, r, c = a.mesh, a.row_axis, a.col_axis
    fn = _entry("gram", mesh, (r, c), lambda: _build_gram(mesh, r, c))
    return DistributedMatrix(fn(a.jax()), mesh, row_axis=None,
                             col_axis=None, _placed=True)


def covariance(a: DistributedMatrix, ddof=1):
    """Column covariance [k, k] of a row-sharded data matrix, computed
    distributed: column means by psum of local sums, then the centered
    Gram — one executable, two psums, no global gather."""
    if a.row_axis is None:
        raise ValueError("covariance needs a row-sharded matrix (the "
                         "reduction is over the sharded row axis)")
    mesh, r, c = a.mesh, a.row_axis, a.col_axis
    n = a.shape[0]
    if n - ddof <= 0:
        raise ValueError(f"covariance of {n} rows with ddof={ddof}")

    def build():
        def body(al):
            af = _gather_cols(al, c)
            mu = lax.psum(jnp.sum(af, 0), r) / n
            ac = af - mu[None, :]
            return lax.psum(ac.T @ ac, r) / (n - ddof)

        return shard_map(body, mesh=mesh, in_specs=(P(r, c),),
                         out_specs=P(None, None), check_vma=False)

    fn = _entry("covariance", mesh, (r, c, int(ddof), n), build)
    return DistributedMatrix(fn(a.jax()), mesh, row_axis=None,
                             col_axis=None, _placed=True)


def pairwise_sq_dists(a: DistributedMatrix, b):
    """[n, d] row-sharded x [m, d] replicated -> [n, m] row-sharded
    squared euclidean distances — the clustering/LSH distance kernel at
    sharded scale (no collectives: the small operand is replicated)."""
    if a.col_axis is not None:
        raise ValueError("pairwise_sq_dists needs a row-sharded matrix "
                         "(col_axis=None); gather columns first")
    b_arr = b.jax() if isinstance(b, DistributedMatrix) else \
        _unwrap2d(b, "pairwise_sq_dists rhs")
    if a.shape[1] != b_arr.shape[1]:
        raise ValueError(f"feature dims differ: {a.shape} vs "
                         f"{tuple(b_arr.shape)}")
    mesh, r = a.mesh, a.row_axis

    def build():
        def body(al, b):
            return sq_dists(al, b)

        return shard_map(body, mesh=mesh,
                         in_specs=(P(r, None), P(None, None)),
                         out_specs=P(r, None), check_vma=False)

    fn = _entry("pairwise_sq_dists", mesh, (r,), build)
    return DistributedMatrix(fn(a.jax(), jnp.asarray(b_arr)), mesh,
                             row_axis=r, col_axis=None, _placed=True)


# ----------------------------------------------------------------------
# collective accounting + warm start
# ----------------------------------------------------------------------

# Hoisted to the shared analysis tier (PR 14): the collective-site
# walker grew into the full pass-7 signature verifier
# (analysis/collectives.py — ordered signatures, COL01-06 checks,
# CollectiveContract). Re-exported here unchanged so every existing
# `linalg.collective_counts` call site keeps working.
from deeplearning4j_tpu.analysis.collectives import (  # noqa: E402,F401
    COLLECTIVE_PRIMS as _COLLECTIVE_PRIMS, collective_counts,
)


def precompile(mesh, m, k, n, dtype=np.float32, row_axis=ROW_AXIS,
               col_axis=None):
    """Warm the AOT executable cache (runtime/aot, PR 7) for the public
    entry points at one GEMM problem size: matmul (layout chosen from
    the axes), gram, and the lstsq normal-equation step. Returns
    {entry: (status, seconds)} — "warm" means served from the
    persistent cache, the sub-second second-process start."""
    from deeplearning4j_tpu.linalg.solvers import _warm_lstsq

    dt = np.dtype(dtype)
    # the same never-pad contract placement enforces, checked up front:
    # an indivisible warm size must fail with the PAR03 error, not a
    # cryptic shard_map lowering error mid-compile
    nr = int(mesh.shape[row_axis])
    _check_divisible(m, row_axis, nr, "row (m)")
    _check_divisible(k, row_axis, nr, "contraction (k)")
    if col_axis is not None:
        nc = int(mesh.shape[col_axis])
        _check_divisible(k, col_axis, nc, "contraction (k)")
        _check_divisible(n, col_axis, nc, "column (n)")
    sds = jax.ShapeDtypeStruct
    rc = NamedSharding(mesh, P(row_axis, col_axis))
    out = {}

    def warm(op, axes, build, *args):
        fn = _entry(op, mesh, axes, build)
        if hasattr(fn, "warm"):
            key, status, secs = fn.warm(*args)
            out[op] = (status, round(secs, 3))
        else:  # sentinel-hooked plain jit: trace once, no cache
            out[op] = ("uncached", 0.0)

    a = sds((m, k), dt, sharding=rc)
    if col_axis is not None:
        b = sds((k, n), dt, sharding=rc)
        nc = int(mesh.shape[col_axis])
        warm("matmul2d", (row_axis, col_axis), lambda: shard_map(
            functools.partial(_summa_2d_body, row_axis=row_axis,
                              col_axis=col_axis, n_cols=nc),
            mesh=mesh, in_specs=(P(row_axis, col_axis),) * 2,
            out_specs=P(row_axis, col_axis), check_vma=False), a, b)
    else:
        b = sds((k, n), dt, sharding=rc)
        nr = int(mesh.shape[row_axis])
        warm("matmul1d", (row_axis,), lambda: shard_map(
            functools.partial(_summa_1d_body, row_axis=row_axis,
                              n_rows=nr),
            mesh=mesh, in_specs=(P(row_axis, None),) * 2,
            out_specs=P(row_axis, None), check_vma=False), a, b)

    warm("matmul_ta", (row_axis, col_axis, col_axis),
         lambda: _build_matmul_ta(mesh, row_axis, col_axis, col_axis),
         a, a)
    warm("gram", (row_axis, col_axis),
         lambda: _build_gram(mesh, row_axis, col_axis), a)
    out.update(_warm_lstsq(mesh, m, k, dt, row_axis=row_axis))
    return out
