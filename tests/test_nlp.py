"""Word2Vec SGNS (reference: deeplearning4j-nlp Word2Vec): vocab rules,
semantic clustering on a structured synthetic corpus, API parity, serde.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.nlp import (Word2Vec, ParagraphVectors,
                                    DefaultTokenizerFactory,
                                    CollectionSentenceIterator)


def _corpus(n=300, seed=0):
    """Two 'topics' whose words co-occur only within their topic; an
    embedding that captures co-occurrence must cluster them."""
    rng = np.random.RandomState(seed)
    animals = ["cat", "dog", "horse", "sheep", "cow"]
    tech = ["cpu", "gpu", "ram", "disk", "cache"]
    sents = []
    for _ in range(n):
        topic = animals if rng.rand() < 0.5 else tech
        sents.append(" ".join(rng.choice(topic, 6)))
    return sents


class TestWord2Vec:
    def _fit(self):
        return (Word2Vec.Builder()
                .minWordFrequency(2).layerSize(16).windowSize(3)
                .negativeSample(4).seed(7).iterations(40)
                .learningRate(0.5)
                .iterate(CollectionSentenceIterator(_corpus()))
                .tokenizerFactory(DefaultTokenizerFactory())
                .build().fit())

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_topic_words_cluster(self):
        m = self._fit()
        intra = m.similarity("cat", "dog")
        inter = m.similarity("cat", "gpu")
        assert intra > inter + 0.2, (intra, inter)
        near = m.wordsNearest("cpu", 4)
        assert set(near) <= {"gpu", "ram", "disk", "cache"}, near

    def test_vocab_rules_and_vector_shape(self):
        m = self._fit()
        assert m.hasWord("cat") and not m.hasWord("zebra")
        assert m.getWordVector("cat").shape == (16,)
        with pytest.raises(ValueError, match="empty vocabulary"):
            (Word2Vec.Builder().minWordFrequency(10_000)
             .iterate(CollectionSentenceIterator(_corpus(20)))
             .build().fit())

    def test_save_load_roundtrip(self, tmp_path):
        m = self._fit()
        p = str(tmp_path / "w2v.npz")
        m.save(p)
        m2 = Word2Vec.load(p)
        np.testing.assert_array_equal(m2.getWordVector("dog"),
                                      m.getWordVector("dog"))
        assert m2.wordsNearest("cat", 3) == m.wordsNearest("cat", 3)

    def test_requires_fit(self):
        m = (Word2Vec.Builder()
             .iterate(CollectionSentenceIterator(_corpus(10))).build())
        with pytest.raises(RuntimeError, match="fit"):
            m.getWordVector("cat")

    def test_save_without_extension_roundtrips(self, tmp_path):
        m = self._fit()
        p = str(tmp_path / "vectors")  # no .npz: np.savez appends it
        m.save(p)
        np.testing.assert_array_equal(Word2Vec.load(p).getWordVector("dog"),
                                      m.getWordVector("dog"))


class TestParagraphVectors:
    """PV-DBOW (reference: ParagraphVectors, dm=0): doc vectors cluster
    by topic and inferVector lands near same-topic documents."""

    def _fit(self):
        from deeplearning4j_tpu.nlp import ParagraphVectors

        return (ParagraphVectors.Builder()
                .minWordFrequency(2).layerSize(16).windowSize(3)
                .negativeSample(4).seed(7).iterations(40).learningRate(0.5)
                .iterate(CollectionSentenceIterator(_corpus(100)))
                .build().fit())

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_doc_vectors_cluster_by_topic(self):
        m = self._fit()
        # reconstruct each doc's topic from the corpus generator
        docs = _corpus(100)
        animal = [i for i, d in enumerate(docs) if "cat" in d or "dog" in d
                  or "horse" in d or "sheep" in d or "cow" in d]
        tech = [i for i, d in enumerate(docs) if i not in animal]
        # center first: SGNS embeddings share a large mean component that
        # masks topic structure under raw cosine
        mu = np.stack([m.getParagraphVector(i)
                       for i in range(len(docs))]).mean(0)
        va = np.stack([m.getParagraphVector(i) for i in animal[:20]]) - mu
        vt = np.stack([m.getParagraphVector(i) for i in tech[:20]]) - mu

        def cos(a, b):
            return (a @ b.T / (np.linalg.norm(a, axis=1)[:, None]
                               * np.linalg.norm(b, axis=1)[None, :] + 1e-12))

        intra = (cos(va, va).mean() + cos(vt, vt).mean()) / 2
        inter = cos(va, vt).mean()
        assert intra > inter + 0.3, (intra, inter)

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_infer_vector_matches_topic(self):
        m = self._fit()
        s_animal = m.similarityToDoc("the cat and the dog and the cow", 0)
        docs = _corpus(100)
        # find one doc per topic
        ai = next(i for i, d in enumerate(docs) if "cat" in d or "dog" in d)
        ti = next(i for i, d in enumerate(docs) if "cpu" in d or "gpu" in d)
        # centered cosine (the shared SGNS mean component masks topics)
        mu = np.stack([m.getParagraphVector(i)
                       for i in range(len(docs))]).mean(0)
        v = m.inferVector("the cat and the dog and the cow") - mu
        pa = m.getParagraphVector(ai) - mu
        pt = m.getParagraphVector(ti) - mu
        sa = v @ pa / (np.linalg.norm(v) * np.linalg.norm(pa) + 1e-12)
        st = v @ pt / (np.linalg.norm(v) * np.linalg.norm(pt) + 1e-12)
        assert sa > st + 0.2, (sa, st)
        assert np.isfinite(s_animal)

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_no_vocab_text_rejected(self):
        m = self._fit()
        with pytest.raises(ValueError, match="no in-vocabulary"):
            m.inferVector("zzz qqq")

    @pytest.mark.slow  # tier-1 budget (PR 21): 6 s on 8 CPU cores
    def test_pv_save_load_roundtrip_and_untrained_doc(self, tmp_path):
        from deeplearning4j_tpu.nlp import ParagraphVectors

        m = self._fit()
        p = str(tmp_path / "pv")
        m.save(p)
        m2 = ParagraphVectors.load(p)
        np.testing.assert_array_equal(m2.getParagraphVector(3),
                                      m.getParagraphVector(3))
        v1 = m.inferVector("cat dog cow")
        v2 = m2.inferVector("cat dog cow")
        np.testing.assert_allclose(v1, v2, rtol=1e-6)
        # OOV-only doc: trained-row guard
        from deeplearning4j_tpu.nlp import CollectionSentenceIterator
        docs = _corpus(50) + ["zzz qqq xxx"]
        pv = (ParagraphVectors.Builder().minWordFrequency(2).layerSize(8)
              .windowSize(2).negativeSample(2).seed(1).iterations(2)
              .learningRate(0.3)
              .iterate(CollectionSentenceIterator(docs)).build().fit())
        with pytest.raises(ValueError, match="no in-vocabulary tokens"):
            pv.getParagraphVector(50)
        pv.getParagraphVector(0)  # trained docs still fine


class TestDeepWalk:
    """DeepWalk (reference: deeplearning4j-graph): vertex embeddings from
    truncated random walks. Two densely-connected clusters joined by a
    single bridge edge must embed as two clusters."""

    def _two_cluster_graph(self):
        from deeplearning4j_tpu.graph import Graph

        g = Graph(12)
        for c in (range(0, 6), range(6, 12)):
            c = list(c)
            for i in c:
                for j in c:
                    if i < j:
                        g.addEdge(i, j)
        g.addEdge(5, 6)  # bridge
        return g

    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_clusters_separate(self):
        from deeplearning4j_tpu.graph import DeepWalk

        dw = (DeepWalk.Builder().windowSize(4).vectorSize(16)
              .learningRate(0.5).seed(7).build())
        dw.fit(self._two_cluster_graph(), walkLength=20, walksPerVertex=8,
               iterations=25)
        intra = dw.similarity(0, 3)
        inter = dw.similarity(0, 9)
        assert intra > inter + 0.1, (intra, inter)
        near = dw.verticesNearest(1, 4)
        assert sum(1 for v in near if v < 6) >= 3, near

    def test_api_guards(self):
        from deeplearning4j_tpu.graph import Graph, DeepWalk

        with pytest.raises(ValueError, match="positive"):
            Graph(0)
        g = Graph(3)
        with pytest.raises(ValueError, match="outside"):
            g.addEdge(0, 5)
        with pytest.raises(RuntimeError, match="fit"):
            DeepWalk.Builder().build().getVertexVector(0)

    def test_dead_end_truncates(self):
        from deeplearning4j_tpu.graph import Graph, DeepWalk

        g = Graph(4)
        g.addEdge(0, 1, directed=True)  # 1 is a sink for walks from 0
        g.addEdge(2, 3)
        dw = DeepWalk.Builder().windowSize(2).vectorSize(8).seed(1).build()
        dw.fit(g, walkLength=10, walksPerVertex=3, iterations=2)
        assert dw.getVertexVector(0).shape == (8,)


class TestNode2VecBias:
    """node2vec p/q-biased walks (reference: upstream's weighted/biased
    walk support; Grover & Leskovec 2016 parameterisation). The bias must
    change walk statistics in the documented direction, and biased
    embeddings must still capture community structure."""

    _two_cluster_graph = TestDeepWalk._two_cluster_graph

    def _backtrack_fraction(self, p):
        from deeplearning4j_tpu.graph import Graph, DeepWalk
        import numpy as np

        g = Graph(10)
        for i in range(9):
            g.addEdge(i, i + 1)  # path graph
        dw = DeepWalk(returnParam=p, seed=3)
        rng = np.random.RandomState(3)
        walks = dw._walks(g, 30, 5, rng)
        back = total = 0
        for w in walks:
            ids = [int(t) for t in w.split()]
            for t in range(2, len(ids)):
                total += 1
                back += ids[t] == ids[t - 2]
        return back / total

    def test_small_p_backtracks_more(self):
        lo = self._backtrack_fraction(0.05)
        hi = self._backtrack_fraction(20.0)
        assert lo > hi + 0.3, (lo, hi)

    def _escape_fraction(self, q):
        # barbell: fraction of walk steps that leave the start clique.
        # q > 1 keeps walks local; q < 1 pushes them outward.
        from deeplearning4j_tpu.graph import DeepWalk
        import numpy as np

        g = self._two_cluster_graph()
        dw = DeepWalk(inOutParam=q, seed=5)
        rng = np.random.RandomState(5)
        walks = dw._walks(g, 12, 6, rng)
        out = total = 0
        for w in walks:
            ids = [int(t) for t in w.split()]
            if ids[0] >= 6:
                continue  # start in cluster A only
            total += 1
            out += any(v >= 6 for v in ids)
        return out / total

    def test_large_q_stays_local(self):
        local = self._escape_fraction(8.0)
        explore = self._escape_fraction(0.125)
        assert local < explore - 0.1, (local, explore)

    def test_biased_embeddings_cluster(self):
        from deeplearning4j_tpu.graph import DeepWalk

        dw = (DeepWalk.Builder().windowSize(4).vectorSize(16)
              .learningRate(0.5).seed(7).returnParam(2.0).inOutParam(4.0)
              .build())
        dw.fit(self._two_cluster_graph(), walkLength=20, walksPerVertex=8,
               iterations=25)
        intra = dw.similarity(0, 3)
        inter = dw.similarity(0, 9)
        assert intra > inter + 0.1, (intra, inter)

    def test_invalid_params_rejected(self):
        from deeplearning4j_tpu.graph import DeepWalk

        with pytest.raises(ValueError, match="returnParam"):
            DeepWalk(returnParam=0.0)
        with pytest.raises(ValueError, match="returnParam"):
            DeepWalk(inOutParam=-1.0)


class TestGraphLoaderAndWeights:
    """GraphLoader edge-list files + weighted walks (reference:
    org.deeplearning4j.graph.data.GraphLoader, WeightedWalkIterator)."""

    def test_load_edge_list(self, tmp_path):
        from deeplearning4j_tpu.graph import GraphLoader

        p = tmp_path / "edges.txt"
        p.write_text("# comment\n0 1\n1 2\n\n2 3\n")
        g = GraphLoader.loadUndirectedGraphEdgeListFile(p)
        assert g.numVertices() == 4
        assert sorted(g.getConnectedVertices(1)) == [0, 2]
        g2 = GraphLoader.loadUndirectedGraphEdgeListFile(p, numVertices=10)
        assert g2.numVertices() == 10

    def test_load_weighted_csv(self, tmp_path):
        from deeplearning4j_tpu.graph import GraphLoader

        p = tmp_path / "w.csv"
        p.write_text("0,1,2.5\n1,2,0.5\n")
        g = GraphLoader.loadWeightedEdgeListFile(p, delimiter=",")
        assert g.getEdgeWeights(0) == [2.5]
        assert sorted(g.getEdgeWeights(1)) == [0.5, 2.5]
        d = GraphLoader.loadWeightedEdgeListFile(p, delimiter=",",
                                                 directed=True)
        assert d.getConnectedVertices(1) == [2]  # 0->1 not mirrored

    def test_load_errors(self, tmp_path):
        from deeplearning4j_tpu.graph import GraphLoader

        bad = tmp_path / "bad.txt"
        bad.write_text("0 1 2 3\n")
        with pytest.raises(ValueError, match="expected"):
            GraphLoader.loadUndirectedGraphEdgeListFile(bad)
        empty = tmp_path / "empty.txt"
        empty.write_text("# only comments\n")
        with pytest.raises(ValueError, match="no edges"):
            GraphLoader.loadUndirectedGraphEdgeListFile(empty)

    def test_weighted_walks_follow_weights(self):
        from deeplearning4j_tpu.graph import Graph, DeepWalk

        # star: 0 connects to 1 (weight 1000) and 2..5 (weight 1);
        # first-order transitions from 0 should overwhelmingly pick 1
        g = Graph(6)
        g.addEdge(0, 1, weight=1000.0)
        for v in range(2, 6):
            g.addEdge(0, v, weight=1.0)
        dw = DeepWalk.Builder().vectorSize(8).build()
        rng = np.random.RandomState(0)
        walks = dw._walks(g, walkLength=2, walksPerVertex=200, rng=rng)
        from_zero = [w.split()[1] for w in walks if w.split()[0] == "0"]
        frac_to_1 = sum(1 for t in from_zero if t == "1") / len(from_zero)
        assert frac_to_1 > 0.95, frac_to_1

    def test_zero_weight_rejected(self):
        from deeplearning4j_tpu.graph import Graph

        with pytest.raises(ValueError, match="weight"):
            Graph(2).addEdge(0, 1, weight=0.0)


class TestParagraphVectorsDM:
    """PV-DM mode (reference: ParagraphVectors.Builder
    .sequenceLearningAlgorithm(new DM<>()) — joint doc+word training)."""

    def _docs(self):
        rng = np.random.RandomState(3)
        animals = ["cat", "dog", "horse", "sheep", "cow"]
        tech = ["cpu", "gpu", "ram", "disk", "cache"]
        docs, topics = [], []
        for i in range(40):
            topic = animals if i % 2 == 0 else tech
            docs.append(" ".join(rng.choice(topic, 8)))
            topics.append(i % 2)
        return docs, topics

    def _fit(self, **kw):
        docs, topics = self._docs()
        # DM splits each window's signal across words + doc + output
        # table (h is a 7-way mean here), so per-table steps are ~1/7
        # of skip-gram's at the same lr — a hotter schedule and more
        # full-batch epochs compensate on this tiny corpus
        pv = (ParagraphVectors.Builder()
              .minWordFrequency(1).layerSize(16).windowSize(3)
              .negativeSample(4).seed(7).iterations(120).learningRate(1.0)
              .sequenceLearningAlgorithm("DM")
              .iterate(CollectionSentenceIterator(docs))
              .build().fit())
        return pv, topics

    def test_doc_vectors_cluster_by_topic(self):
        pv, topics = self._fit()
        assert pv.sequenceAlgorithm == "DM"
        vecs = np.stack([pv.getParagraphVector(i) for i in range(40)])
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True) + 1e-12
        sims = vecs @ vecs.T
        same = np.asarray([[t1 == t2 for t2 in topics] for t1 in topics])
        off = ~np.eye(40, dtype=bool)
        intra = sims[same & off].mean()
        inter = sims[~same].mean()
        assert intra > inter + 0.15, (intra, inter)

    def test_word_vectors_trained_jointly(self):
        pv, _ = self._fit()
        # DM trains words too — topic words must cluster
        assert pv.similarity("cat", "dog") > pv.similarity("cat", "gpu")

    def test_infer_vector_lands_near_topic(self):
        pv, topics = self._fit()
        v = pv.inferVector("cat dog sheep horse cow cat dog")
        v = v / (np.linalg.norm(v) + 1e-12)
        def mean_sim(t):
            idx = [i for i in range(40) if topics[i] == t]
            vecs = np.stack([pv.getParagraphVector(i) for i in idx])
            vecs /= np.linalg.norm(vecs, axis=1, keepdims=True) + 1e-12
            return float((vecs @ v).mean())
        assert mean_sim(0) > mean_sim(1), (mean_sim(0), mean_sim(1))

    def test_serde_roundtrip_preserves_dm(self, tmp_path):
        pv, _ = self._fit()
        p = tmp_path / "pv_dm"
        pv.save(p)
        pv2 = ParagraphVectors.load(p)
        assert pv2.sequenceAlgorithm == "DM"
        np.testing.assert_allclose(pv2.getParagraphVector(3),
                                   pv.getParagraphVector(3), rtol=1e-6)
        # inference works on the restored model (needs windowSize back)
        v = pv2.inferVector("cat dog cat dog cat")
        assert np.isfinite(v).all()

    def test_dm_rejects_hierarchical_softmax(self):
        with pytest.raises(ValueError, match="negative sampling"):
            ParagraphVectors(sequenceLearningAlgorithm="DM",
                             useHierarchicSoftmax=True)

    def test_unknown_algorithm_rejected(self):
        with pytest.raises(ValueError, match="sequenceLearningAlgorithm"):
            ParagraphVectors(sequenceLearningAlgorithm="skip-thought")

    def test_infer_cache_does_not_collide_across_texts(self):
        # two different same-token-count texts must get DIFFERENT
        # inferred vectors (the jit cache keys on length, so windows
        # must be traced arguments, not baked constants)
        pv, _ = self._fit()
        va = np.array(pv.inferVector("cat dog horse sheep cow"))
        vb = np.array(pv.inferVector("gpu ram disk cache cpu"))
        va /= np.linalg.norm(va) + 1e-12
        vb /= np.linalg.norm(vb) + 1e-12
        assert float(va @ vb) < 0.9, float(va @ vb)
