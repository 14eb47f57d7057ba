"""KMeans + NearestNeighbors (reference: deeplearning4j clustering /
nearestneighbors modules) — numpy oracles and blob recovery."""

import numpy as np
import pytest

from deeplearning4j_tpu.clustering import (KMeansClustering, ClusterSet,
                                           NearestNeighbors)


def _blobs(n_per=40, k=3, d=5, seed=0, spread=6.0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(k, d) * spread
    X = np.concatenate([centers[i] + rng.randn(n_per, d)
                        for i in range(k)]).astype("float32")
    y = np.repeat(np.arange(k), n_per)
    return X, y, centers


class TestKMeans:
    def test_recovers_blobs(self):
        X, y, _ = _blobs()
        cs = KMeansClustering.setup(3, 50, "euclidean", seed=1).applyTo(X)
        assert cs.getClusterCount() == 3
        a = cs.getAssignments()
        # each true blob maps (almost) entirely to one found cluster
        for i in range(3):
            counts = np.bincount(a[y == i], minlength=3)
            assert counts.max() / counts.sum() > 0.95
        # the three dominant labels are distinct
        dom = [np.bincount(a[y == i], minlength=3).argmax() for i in range(3)]
        assert len(set(dom)) == 3

    def test_classify_point_and_inertia(self):
        X, y, centers = _blobs()
        cs = KMeansClustering.setup(3, 50).applyTo(X)
        assert np.isfinite(cs.inertia) and cs.inertia > 0
        # a point at a true center classifies with its blob's majority
        i = cs.classifyPoint(centers[0])
        dom = np.bincount(cs.getAssignments()[y == 0], minlength=3).argmax()
        assert i == dom

    def test_validation(self):
        with pytest.raises(ValueError, match="unsupported"):
            KMeansClustering(2, distanceFunction="cosine")
        with pytest.raises(ValueError, match="clusters"):
            KMeansClustering(10).applyTo(np.zeros((3, 2), "float32"))

    def test_more_clusters_never_increase_inertia(self):
        X, _, _ = _blobs()
        i2 = KMeansClustering.setup(2, 50, seed=3).applyTo(X).inertia
        i6 = KMeansClustering.setup(6, 50, seed=3).applyTo(X).inertia
        assert i6 <= i2


class TestNearestNeighbors:
    def test_exact_vs_numpy_oracle(self):
        rng = np.random.RandomState(0)
        X = rng.randn(50, 7).astype("float32")
        q = rng.randn(4, 7).astype("float32")
        nn = NearestNeighbors(X)
        idx, dist = nn.search(q, 5)
        assert idx.shape == (4, 5) and dist.shape == (4, 5)
        D = np.linalg.norm(q[:, None, :] - X[None, :, :], axis=-1)
        ref = np.argsort(D, axis=1)[:, :5]
        np.testing.assert_array_equal(np.sort(idx, 1), np.sort(ref, 1))
        np.testing.assert_allclose(np.sort(dist, 1),
                                   np.sort(D, axis=1)[:, :5], rtol=1e-4,
                                   atol=1e-4)

    def test_single_query_and_validation(self):
        X = np.eye(4, dtype="float32")
        nn = NearestNeighbors(X)
        idx, dist = nn.search(X[2], 1)
        assert idx[0] == 2 and dist[0] < 1e-4
        with pytest.raises(ValueError, match="k="):
            nn.search(X[0], 9)
        with pytest.raises(ValueError, match="non-empty"):
            NearestNeighbors(np.zeros((0, 3), "float32"))


class TestKMeansEdgeCases:
    def test_k_zero_rejected(self):
        with pytest.raises(ValueError, match="clusterCount"):
            KMeansClustering(0)

    def test_simultaneous_empty_clusters_get_distinct_centers(self):
        """Force 3 empty clusters in one Lloyd step: the reseed must
        place DISTINCT points, not one shared farthest point."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.clustering.kmeans import _lloyd

        rng = np.random.RandomState(0)
        X = jnp.asarray(rng.randn(20, 2).astype("float32"))
        # one center near the data, three absurdly far: everything
        # assigns to slot 0, slots 1-3 are empty simultaneously
        C0 = jnp.asarray(np.array(
            [[0.0, 0.0], [1e3, 1e3], [2e3, 2e3], [-1e3, 1e3]], "float32"))
        C, a, _ = _lloyd(X, C0, 4, 1)
        C = np.asarray(C)
        d = np.linalg.norm(C[:, None, :] - C[None, :, :], axis=-1)
        np.fill_diagonal(d, np.inf)
        assert d.min() > 1e-6, C  # all four centers distinct

    def test_offset_data_precision(self):
        """fp32 quadratic-form distances degrade far from the origin;
        mean-centering must keep neighbors exact at large offsets."""
        rng = np.random.RandomState(0)
        X = (rng.randn(30, 4) * 0.01 + 1e4).astype("float32")
        nn = NearestNeighbors(X)
        idx, dist = nn.search(X[7], 2)
        assert idx[0] == 7 and dist[0] < 1e-4
        assert dist[1] > 0  # second neighbor is NOT collapsed to zero
        cs = KMeansClustering.setup(2, 30, seed=1).applyTo(
            np.concatenate([X, X + 0.5]))
        assert len(set(cs.getAssignments()[:30])) == 1


class TestVPTree:
    """VPTree vs the brute-force oracle (exact structure — must match)."""

    def test_matches_brute_force(self):
        rng = np.random.RandomState(3)
        X = rng.randn(400, 8).astype("float32")
        from deeplearning4j_tpu.clustering import VPTree
        tree = VPTree(X, seed=1)
        nn = NearestNeighbors(X)
        for qi in range(10):
            q = rng.randn(8).astype("float32")
            ti, td = tree.search(q, 5)
            bi, bd = nn.search(q, 5)
            assert list(ti) == list(bi)
            np.testing.assert_allclose(td, bd, rtol=1e-4, atol=1e-4)

    def test_prunes(self):
        # on clustered data the triangle-inequality prune must visit far
        # fewer points than a full scan
        X, _, _ = _blobs(n_per=300, k=4, d=3, seed=5, spread=30.0)
        from deeplearning4j_tpu.clustering import VPTree
        tree = VPTree(X, seed=0)
        tree.search(X[7] + 0.01, 3)
        assert tree._scanned < X.shape[0] * 0.5

    def test_k_1_and_k_n(self):
        rng = np.random.RandomState(0)
        X = rng.randn(20, 4)
        from deeplearning4j_tpu.clustering import VPTree
        tree = VPTree(X)
        i1, d1 = tree.search(X[11], 1)
        assert i1[0] == 11 and d1[0] < 1e-6
        iN, dN = tree.search(X[0], 20)
        assert sorted(iN) == list(range(20))
        assert np.all(np.diff(dN) >= -1e-12)

    def test_errors(self):
        from deeplearning4j_tpu.clustering import VPTree
        with pytest.raises(ValueError):
            VPTree(np.zeros((0, 3)))
        tree = VPTree(np.random.RandomState(0).randn(5, 3))
        with pytest.raises(ValueError):
            tree.search(np.zeros(3), 6)
        with pytest.raises(ValueError):
            tree.search(np.zeros(4), 1)
        with pytest.raises(ValueError):
            VPTree(np.zeros((4, 2)), distance="manhattan")


class TestKDTree:
    def test_nn_matches_brute_force(self):
        rng = np.random.RandomState(7)
        X = rng.randn(200, 5)
        from deeplearning4j_tpu.clustering import KDTree
        tree = KDTree(5)
        for p in X:
            tree.insert(p)
        assert tree.size() == 200
        for _ in range(10):
            q = rng.randn(5)
            idx, dist = tree.nn(q)
            d_all = np.linalg.norm(X - q, axis=1)
            assert idx == int(np.argmin(d_all))
            assert abs(dist - d_all.min()) < 1e-10

    def test_knn_radius(self):
        rng = np.random.RandomState(1)
        X = rng.randn(150, 3)
        from deeplearning4j_tpu.clustering import KDTree
        tree = KDTree(3)
        for p in X:
            tree.insert(p)
        q = X[42]
        idx, dist = tree.knn(q, 1.2)
        d_all = np.linalg.norm(X - q, axis=1)
        expect = set(np.nonzero(d_all <= 1.2)[0])
        assert set(idx) == expect
        assert np.all(np.diff(dist) >= -1e-12)
        assert idx[0] == 42  # the point itself, at distance 0

    def test_empty_and_dims_errors(self):
        from deeplearning4j_tpu.clustering import KDTree
        with pytest.raises(ValueError):
            KDTree(0)
        tree = KDTree(3)
        with pytest.raises(ValueError):
            tree.nn(np.zeros(3))
        tree.insert(np.zeros(3))
        with pytest.raises(ValueError):
            tree.insert(np.zeros(2))


class TestRandomProjectionLSH:
    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_recall_on_clustered_data(self):
        X, _, _ = _blobs(n_per=200, k=5, d=16, seed=2, spread=10.0)
        from deeplearning4j_tpu.clustering import RandomProjectionLSH
        lsh = RandomProjectionLSH(hashLength=10, numTables=8,
                                  inDimension=16, seed=0).index(X)
        nn = NearestNeighbors(X)
        hits = total = 0
        rng = np.random.RandomState(0)
        for qi in rng.choice(X.shape[0], 20, replace=False):
            q = X[qi] + rng.randn(16).astype("float32") * 0.05
            li, _ = lsh.search(q, 10)
            bi, _ = nn.search(q, 10)
            hits += len(set(li.tolist()) & set(bi.tolist()))
            total += 10
        assert hits / total > 0.8  # sign-LSH recall on well-separated blobs

    def test_bucket_contains_near_duplicates(self):
        rng = np.random.RandomState(4)
        X = rng.randn(300, 12).astype("float32")
        from deeplearning4j_tpu.clustering import RandomProjectionLSH
        lsh = RandomProjectionLSH(6, 12, 12, seed=3).index(X)
        cand = lsh.bucket(X[17] * 1.0001)  # same direction -> same signs
        assert 17 in cand
        assert cand.size < X.shape[0]  # it's a bucket, not the corpus

    def test_exact_rerank_ordering(self):
        rng = np.random.RandomState(9)
        X = rng.randn(100, 8).astype("float32")
        from deeplearning4j_tpu.clustering import RandomProjectionLSH
        lsh = RandomProjectionLSH(4, 6, 8, seed=1).index(X)
        idx, dist = lsh.search(X[3], 5)
        assert idx[0] == 3 and dist[0] < 1e-3
        assert np.all(np.diff(dist) >= -1e-5)
        # reported distances are TRUE euclidean distances, not hash stats
        for i, d in zip(idx, dist):
            assert abs(np.linalg.norm(X[i] - X[3]) - d) < 1e-3

    def test_errors(self):
        from deeplearning4j_tpu.clustering import RandomProjectionLSH
        with pytest.raises(ValueError):
            RandomProjectionLSH(0, 1, 4)
        with pytest.raises(ValueError):
            RandomProjectionLSH(63, 1, 4)
        lsh = RandomProjectionLSH(4, 2, 4)
        with pytest.raises(ValueError):
            lsh.bucket(np.zeros(4))
        lsh.index(np.random.RandomState(0).randn(10, 4))
        with pytest.raises(ValueError):
            lsh.bucket(np.zeros(5))
        with pytest.raises(ValueError):
            lsh.search(np.zeros(4), 0)


class TestDegenerateCorpora:
    """Regression: tie-heavy/duplicate corpora must not blow the
    recursion limit (build and query are iterative)."""

    def test_vptree_all_duplicates(self):
        from deeplearning4j_tpu.clustering import VPTree
        X = np.zeros((3000, 4), np.float32)
        tree = VPTree(X)
        idx, dist = tree.search(np.zeros(4), 3)
        assert len(idx) == 3 and np.all(dist == 0)

    def test_kdtree_duplicate_chain(self):
        from deeplearning4j_tpu.clustering import KDTree
        tree = KDTree(3)
        for _ in range(2000):
            tree.insert(np.ones(3))
        idx, dist = tree.nn(np.ones(3) + 0.01)
        assert dist < 0.02
        ri, _ = tree.knn(np.ones(3), 0.1)
        assert len(ri) == 2000

    def test_kdtree_sorted_inserts(self):
        from deeplearning4j_tpu.clustering import KDTree
        tree = KDTree(2)
        pts = np.stack([np.arange(2000.0), np.arange(2000.0)], 1)
        for p in pts:
            tree.insert(p)
        idx, dist = tree.nn(np.array([1000.2, 1000.2]))
        assert idx == 1000 and abs(dist - np.sqrt(2 * 0.04)) < 1e-6

    def test_vptree_rejects_sqeuclidean(self):
        from deeplearning4j_tpu.clustering import VPTree
        with pytest.raises(ValueError):
            VPTree(np.zeros((4, 2)), distance="sqeuclidean")

    def test_lsh_rejects_empty_corpus(self):
        from deeplearning4j_tpu.clustering import RandomProjectionLSH
        with pytest.raises(ValueError):
            RandomProjectionLSH(4, 2, 4).index(np.zeros((0, 4)))

    def test_kdtree_knn_empty_raises(self):
        from deeplearning4j_tpu.clustering import KDTree
        with pytest.raises(ValueError):
            KDTree(3).knn(np.zeros(3), 1.0)

    def test_lsh_short_return(self):
        # fewer candidates than k -> result length is the candidate
        # count, not k (documented bucket-limited semantics)
        rng = np.random.RandomState(2)
        X = rng.randn(50, 6).astype("float32") * 10
        from deeplearning4j_tpu.clustering import RandomProjectionLSH
        lsh = RandomProjectionLSH(16, 1, 6, seed=0).index(X)
        idx, dist = lsh.search(X[0], 20)
        assert 1 <= len(idx) <= 20 and len(idx) == len(dist)
        assert idx[0] == 0
