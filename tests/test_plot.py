"""t-SNE tests (deeplearning4j_tpu.plot; reference:
org.deeplearning4j.plot.BarnesHutTsne)."""

import numpy as np
import pytest


class TestTsne:
    """BarnesHutTsne (reference: org.deeplearning4j.plot) — exact t-SNE;
    well-separated high-dimensional clusters must stay separated in 2D."""

    def _clusters(self, n_per=25, d=10, k=3, seed=0):
        rng = np.random.RandomState(seed)
        centers = rng.randn(k, d) * 8.0
        X = np.concatenate([centers[i] + rng.randn(n_per, d)
                            for i in range(k)])
        y = np.repeat(np.arange(k), n_per)
        return X.astype("float32"), y

    def test_clusters_stay_separated(self):
        from deeplearning4j_tpu.plot import BarnesHutTsne

        X, y = self._clusters()
        t = (BarnesHutTsne.Builder().setMaxIter(400).perplexity(12)
             .learningRate(100.0).seed(3).build())
        Y = t.fit(X).getData()
        assert Y.shape == (75, 2)
        cent = np.stack([Y[y == i].mean(0) for i in range(3)])
        intra = max(np.linalg.norm(Y[y == i] - cent[i], axis=1).mean()
                    for i in range(3))
        inter = min(np.linalg.norm(cent[i] - cent[j])
                    for i in range(3) for j in range(i + 1, 3))
        assert inter > 2.0 * intra, (intra, inter)

    def test_validation_and_save(self, tmp_path):
        from deeplearning4j_tpu.plot import BarnesHutTsne

        X, y = self._clusters(n_per=4)  # 12 points
        with pytest.raises(ValueError, match="perplexity"):
            BarnesHutTsne.Builder().perplexity(30).build().fit(X)
        t = (BarnesHutTsne.Builder().setMaxIter(50).perplexity(3)
             .seed(1).build().fit(X))
        p = str(tmp_path / "tsne.csv")
        t.saveAsFile(y, p)
        lines = open(p).read().strip().splitlines()
        assert len(lines) == 12 and lines[0].count(",") == 2
        with pytest.raises(RuntimeError, match="fit"):
            BarnesHutTsne.Builder().build().getData()


class TestTiledTsne:
    """Tiled (block-pairwise) mode: same mathematics as exact with
    O(tile*N) memory; exact mode is the oracle."""

    _clusters = TestTsne._clusters

    def test_sparse_p_with_full_k_matches_dense_p(self):
        from deeplearning4j_tpu.plot.tsne import _p_conditional, _p_sparse

        X, _ = self._clusters(n_per=20)
        n = X.shape[0]
        dense = _p_conditional(X, 12.0)
        rows, cols, vals = _p_sparse(X, 12.0, k=n - 1)
        sp = np.zeros((n, n))
        sp[rows, cols] = vals
        np.testing.assert_allclose(sp, dense, atol=1e-5)

    def test_short_trajectory_matches_exact(self):
        from deeplearning4j_tpu.plot import BarnesHutTsne

        X, _ = self._clusters(n_per=20)
        kw = dict()
        a = (BarnesHutTsne.Builder().setMaxIter(5).perplexity(10)
             .learningRate(100.0).seed(5).method("exact").build())
        b = (BarnesHutTsne.Builder().setMaxIter(5).perplexity(10)
             .learningRate(100.0).seed(5).method("tiled")
             .knnK(59).tileSize(16).build())  # k=N-1: identical P; tile
        # size forces padding (60 -> 64) and multi-block streaming
        Ya = a.fit(X).getData()
        Yb = b.fit(X).getData()
        assert a.usedMethod == "exact" and b.usedMethod == "tiled"
        np.testing.assert_allclose(Ya, Yb, atol=1e-4)

    def test_tiled_clusters_stay_separated(self):
        from deeplearning4j_tpu.plot import BarnesHutTsne

        X, y = self._clusters()
        t = (BarnesHutTsne.Builder().setMaxIter(400).perplexity(12)
             .learningRate(100.0).seed(3).method("tiled")
             .tileSize(32).build())
        Y = t.fit(X).getData()
        assert Y.shape == (75, 2)
        cent = np.stack([Y[y == i].mean(0) for i in range(3)])
        intra = max(np.linalg.norm(Y[y == i] - cent[i], axis=1).mean()
                    for i in range(3))
        inter = min(np.linalg.norm(cent[i] - cent[j])
                    for i in range(3) for j in range(i + 1, 3))
        assert inter > 2.0 * intra, (intra, inter)

    def test_method_validation_and_auto(self):
        from deeplearning4j_tpu.plot import BarnesHutTsne

        with pytest.raises(ValueError, match="method"):
            BarnesHutTsne(method="barneshut")
        X, _ = self._clusters(n_per=15)
        t = (BarnesHutTsne.Builder().setMaxIter(5).perplexity(5)
             .build())
        t.fit(X)
        assert t.usedMethod == "exact"  # auto: small n
