"""KMeans + exact nearest neighbors.

Reference: org.deeplearning4j.clustering.kmeans.KMeansClustering
(setup(clusterCount, maxIterationCount, distanceFunction) →
applyTo(points) → ClusterSet) and the VPTree behind
NearestNeighborsServer. The JVM needs a vantage-point tree because
brute-force distance scans are slow there; on TPU the brute-force
distance matrix IS a matmul on the MXU, so NearestNeighbors is exact
brute force and KMeans runs Lloyd iterations as one jitted fori_loop
(k-means++ style farthest-point seeding, empty clusters re-seeded to
the farthest point).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# the quadratic-form distance kernel lives in the distributed-linalg
# tier now (linalg.sq_dists); imported under the old private name for
# this module's own uses (and any out-of-tree code bound to it)
from deeplearning4j_tpu.linalg.distributed import sq_dists as _sq_dists


class ClusterSet:
    """Fitted result (reference: clustering.cluster.ClusterSet)."""

    def __init__(self, centers, assignments, inertia):
        self._centers = np.asarray(centers)
        self._assign = np.asarray(assignments)
        self.inertia = float(inertia)

    def getClusterCount(self):
        return self._centers.shape[0]

    def getCenters(self):
        return self._centers

    def getAssignments(self):
        return self._assign

    def classifyPoint(self, point):
        d = np.sum((self._centers - np.asarray(point)) ** 2, 1)
        return int(np.argmin(d))


class KMeansClustering:
    """Reference: KMeansClustering.setup(...).applyTo(points)."""

    def __init__(self, clusterCount, maxIterationCount=100,
                 distanceFunction="euclidean", seed=42, mesh=None):
        if str(distanceFunction).lower() not in ("euclidean", "sqeuclidean"):
            raise ValueError(
                f"distanceFunction {distanceFunction!r} unsupported "
                "(euclidean)")
        self.k = int(clusterCount)
        if self.k < 1:
            raise ValueError(f"clusterCount must be >= 1, got {clusterCount}")
        self.maxIter = int(maxIterationCount)
        self.seed = int(seed)
        # mesh-sharded Lloyd (linalg tier): points row-shard over the
        # data axis, centers replicate, every reduction is a psum —
        # k-means at corpus sizes one chip's HBM can't hold
        self.mesh = mesh

    @staticmethod
    def setup(clusterCount, maxIterationCount=100,
              distanceFunction="euclidean", seed=42, mesh=None):
        return KMeansClustering(clusterCount, maxIterationCount,
                                distanceFunction, seed, mesh=mesh)

    def applyTo(self, points) -> ClusterSet:
        Xh = np.asarray(getattr(points, "toNumpy", lambda: points)(),
                        np.float32)
        n, d = Xh.shape
        if n < self.k:
            raise ValueError(f"{n} points cannot form {self.k} clusters")
        # mean-center: keeps the fp32 quadratic distance form accurate
        # for data far from the origin (translation-invariant)
        mean = Xh.mean(0, keepdims=True)
        key = jax.random.key(self.seed)
        first = int(jax.random.randint(key, (), 0, n))

        if self.mesh is not None:
            # sharded path: seeding AND Lloyd run inside one sharded
            # program — the centered corpus is placed row-sharded and
            # the full matrix never touches a single device
            C, a, inertia = _lloyd_sharded(Xh - mean, first, self.k,
                                           self.maxIter, self.mesh)
            return ClusterSet(np.asarray(C) + mean, a, inertia)

        X = jnp.asarray(Xh - mean)
        # farthest-point seeding with a running min-distance vector:
        # O(k*n*d) total, one distance column per new center
        idxs = [first]
        dmin = _sq_dists(X, X[first][None, :])[:, 0]
        for _ in range(self.k - 1):
            nxt = int(jnp.argmax(dmin))
            idxs.append(nxt)
            dmin = jnp.minimum(dmin, _sq_dists(X, X[nxt][None, :])[:, 0])
        C0 = X[jnp.asarray(idxs)]

        C, a, inertia = _lloyd(X, C0, self.k, self.maxIter)
        return ClusterSet(np.asarray(C) + mean, a, inertia)


@partial(jax.jit, static_argnums=(2, 3))
def _lloyd(X, C0, k, maxIter):
    """Module-level jit (repeat fits hit the compile cache). Iterates
    until assignments stop changing, bounded by maxIter — the reference
    terminates on convergence too; a fixed-trip loop would pay full
    O(n*k*d) matmuls for every wasted iteration."""

    def step(C):
        D = _sq_dists(X, C)
        a = jnp.argmin(D, 1)
        onehot = jax.nn.one_hot(a, k, dtype=X.dtype)
        counts = jnp.sum(onehot, 0)
        newC = (onehot.T @ X) / jnp.maximum(counts, 1.0)[:, None]
        # empty clusters re-seed to DISTINCT farthest points (slot i
        # takes the i-th farthest) — one shared point would leave
        # duplicate centers when several clusters empty at once
        far_idx = jax.lax.top_k(jnp.min(D, 1), k)[1]
        return (jnp.where((counts > 0)[:, None], newC, X[far_idx]),
                a.astype(jnp.int32))  # pinned: x64 mode must not widen

    def cond(carry):
        _, a_prev, a, i = carry
        return (i < maxIter) & jnp.any(a_prev != a)

    def body(carry):
        C, _, a, i = carry
        C2, a2 = step(C)
        return C2, a, a2, i + jnp.asarray(1, jnp.int32)

    a0 = jnp.full((X.shape[0],), -1, jnp.int32)
    C1, a1 = step(C0)
    C, _, a, _ = jax.lax.while_loop(
        cond, body, (C1, a0, a1, jnp.asarray(1, jnp.int32)))
    D = _sq_dists(X, C)
    a = jnp.argmin(D, 1)
    return C, a, jnp.sum(jnp.min(D, 1))


def _lloyd_sharded(Xc, first_idx, k, maxIter, mesh):
    """Farthest-point seeding + Lloyd iterations with the points
    row-sharded over the mesh's data axis (linalg tier), in ONE sharded
    program — the full corpus never materialises on a single device.
    Seeding: the first center is extracted from its owning shard
    (psum-masked dynamic slice), then each farthest point is the global
    argmax of the running min-distance vector (local argmax candidates
    all-gathered, re-argmaxed — same first-occurrence tie-break as the
    single-device path, so the two paths seed identically). Lloyd:
    distances are the same quadratic-form kernel per shard, center
    sums/counts and the convergence flag reduce with psums, and empty
    clusters re-seed to the GLOBAL farthest points (local top-k
    candidates all-gathered, then re-topped)."""
    from deeplearning4j_tpu.linalg import DistributedMatrix, ROW_AXIS
    from deeplearning4j_tpu.linalg.distributed import _entry
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    dX = DistributedMatrix(np.asarray(Xc, np.float32), mesh,
                           row_axis=ROW_AXIS)  # never-pad (PAR03)
    r = ROW_AXIS
    n_local = dX.block_shape()[0]
    if n_local < k:
        raise ValueError(
            f"{dX.shape[0]} points over mesh axis '{r}' "
            f"(size {mesh.shape[r]}) leave {n_local} rows per chip — "
            f"fewer than k={k}; the distributed farthest-point re-seed "
            "needs k candidates per shard")

    def build():
        def body(xl, first):
            nl = xl.shape[0]
            my = lax.axis_index(r)

            # -- seeding (distributed farthest-point) ---------------
            owner = first // nl
            local = first % nl
            pt0 = lax.psum(
                jnp.where(owner == my,
                          lax.dynamic_slice_in_dim(xl, local, 1, 0)[0],
                          jnp.zeros((xl.shape[1],), xl.dtype)), r)
            C0 = jnp.zeros((k, xl.shape[1]), xl.dtype).at[0].set(pt0)
            dmin0 = _sq_dists(xl, pt0[None, :])[:, 0]

            def seed_step(i, carry):
                dmin, C = carry
                li = jnp.argmax(dmin)
                gv = lax.all_gather(dmin[li], r)          # [R]
                gp = lax.all_gather(xl[li], r)            # [R, d]
                pt = gp[jnp.argmax(gv)]
                C = C.at[i].set(pt)
                dmin = jnp.minimum(dmin,
                                   _sq_dists(xl, pt[None, :])[:, 0])
                return dmin, C

            _, C0 = lax.fori_loop(1, k, seed_step, (dmin0, C0))

            # -- Lloyd ----------------------------------------------
            def step(C):
                D = _sq_dists(xl, C)
                a = jnp.argmin(D, 1)
                onehot = jax.nn.one_hot(a, k, dtype=xl.dtype)
                counts = lax.psum(jnp.sum(onehot, 0), r)
                sums = lax.psum(onehot.T @ xl, r)
                newC = sums / jnp.maximum(counts, 1.0)[:, None]
                # global farthest points for empty-cluster re-seed:
                # k local candidates, all-gathered, re-topped
                lv, li = lax.top_k(jnp.min(D, 1), k)
                gv = lax.all_gather(lv, r, axis=0, tiled=True)
                gp = lax.all_gather(xl[li], r, axis=0, tiled=True)
                far = gp[lax.top_k(gv, k)[1]]
                return (jnp.where((counts > 0)[:, None], newC, far),
                        a.astype(jnp.int32))

            def cond(carry):
                C, a_prev, a, changed, i = carry
                return (i < maxIter) & changed

            def loop(carry):
                C, _, a, _, i = carry
                C2, a2 = step(C)
                changed = lax.psum(
                    jnp.any(a != a2).astype(jnp.int32), r) > 0
                return C2, a, a2, changed, i + jnp.asarray(1, jnp.int32)

            a0 = jnp.full((xl.shape[0],), -1, jnp.int32)
            C1, a1 = step(C0)
            C, _, a, _, _ = lax.while_loop(
                cond, loop,
                (C1, a0, a1, jnp.asarray(True), jnp.asarray(1, jnp.int32)))
            D = _sq_dists(xl, C)
            a = jnp.argmin(D, 1).astype(jnp.int32)
            inertia = lax.psum(jnp.sum(jnp.min(D, 1)), r)
            return C, a, inertia

        return shard_map(body, mesh=mesh,
                         in_specs=(P(r, None), P()),
                         out_specs=(P(None, None), P(r), P()),
                         check_vma=False)

    fn = _entry("kmeans_lloyd", mesh, (r, k, int(maxIter)), build)
    C, a, inertia = fn(dX.jax(), jnp.asarray(int(first_idx), jnp.int32))
    return C, np.asarray(a), inertia


class NearestNeighbors:
    """Exact k-NN (reference: the VPTree/NearestNeighborsServer stack;
    brute force is the TPU-native choice — one matmul per query batch)."""

    def __init__(self, points):
        Xh = np.asarray(getattr(points, "toNumpy", lambda: points)(),
                        np.float32)
        if Xh.ndim != 2 or Xh.shape[0] == 0:
            raise ValueError("points must be a non-empty [n, d] matrix")
        # mean-center (see _sq_dists): fp32 quadratic distances stay
        # accurate for corpora far from the origin
        self._mean = Xh.mean(0, keepdims=True)
        self._X = jnp.asarray(Xh - self._mean)

    def search(self, query, k):
        """-> (indices [q, k], distances [q, k]) for a [q, d] (or [d])
        query batch; euclidean, exact."""
        q = np.asarray(getattr(query, "toNumpy", lambda: query)(),
                       np.float32)
        single = q.ndim == 1
        if single:
            q = q[None, :]
        q = jnp.asarray(q - self._mean)
        k = int(k)
        if not (1 <= k <= self._X.shape[0]):
            raise ValueError(f"k={k} outside [1, {self._X.shape[0]}]")
        D = _sq_dists(q, self._X)
        negd, idx = jax.lax.top_k(-D, k)
        dist = np.sqrt(np.asarray(-negd))
        idx = np.asarray(idx)
        return (idx[0], dist[0]) if single else (idx, dist)
