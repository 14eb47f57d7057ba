"""CBOW / GloVe / vectorizers (reference: deeplearning4j-nlp CBOW.java,
glove/Glove.java, bagofwords.vectorizer.{BagOfWords,Tfidf}Vectorizer).
Convergence tests mirror test_nlp.py's topic-clustering pattern; the
vectorizers get exact hand-computed oracles.
"""

import math

import numpy as np
import pytest

from deeplearning4j_tpu.nlp import (
    Word2Vec, Glove, BagOfWordsVectorizer, TfidfVectorizer,
    LabelAwareCollectionIterator, CollectionSentenceIterator,
    DefaultTokenizerFactory,
)


def _corpus(n=300, seed=0):
    rng = np.random.RandomState(seed)
    animals = ["cat", "dog", "horse", "sheep", "cow"]
    tech = ["cpu", "gpu", "ram", "disk", "cache"]
    sents = []
    for _ in range(n):
        topic = animals if rng.rand() < 0.5 else tech
        sents.append(" ".join(rng.choice(topic, 6)))
    return sents


class TestCBOW:
    def _fit(self):
        # lr is higher than the skip-gram test's 0.5: CBOW averages the
        # window's input vectors, so each word's per-step gradient is
        # ~1/(2w) of skip-gram's and needs a hotter schedule to separate
        return (Word2Vec.Builder()
                .minWordFrequency(2).layerSize(16).windowSize(3)
                .negativeSample(4).seed(7).iterations(40)
                .learningRate(1.0)
                .elementsLearningAlgorithm("CBOW")
                .iterate(CollectionSentenceIterator(_corpus()))
                .tokenizerFactory(DefaultTokenizerFactory())
                .build().fit())

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_topic_words_cluster(self):
        m = self._fit()
        assert m.algorithm == "cbow"
        intra = m.similarity("cat", "dog")
        inter = m.similarity("cat", "gpu")
        assert intra > inter + 0.2, (intra, inter)
        near = m.wordsNearest("cpu", 4)
        assert set(near) <= {"gpu", "ram", "disk", "cache"}, near

    def test_upstream_class_name_accepted(self):
        m = Word2Vec(elementsLearningAlgorithm="CBOW<VocabWord>")
        assert m.algorithm == "cbow"
        with pytest.raises(ValueError, match="elementsLearningAlgorithm"):
            Word2Vec(elementsLearningAlgorithm="hogwild")


class TestGlove:
    def _fit(self, **kw):
        b = (Glove.Builder()
             .minWordFrequency(2).layerSize(16).windowSize(3)
             .seed(11).epochs(60).learningRate(0.05)
             .iterate(CollectionSentenceIterator(_corpus()))
             .tokenizerFactory(DefaultTokenizerFactory()))
        for k, v in kw.items():
            getattr(b, k)(v)
        return b.build().fit()

    def test_topic_words_cluster(self):
        m = self._fit()
        intra = m.similarity("cat", "dog")
        inter = m.similarity("cat", "gpu")
        assert intra > inter + 0.2, (intra, inter)
        near = m.wordsNearest("ram", 4)
        assert set(near) <= {"cpu", "gpu", "disk", "cache"}, near

    def test_cooccurrence_symmetry_and_distance_weighting(self):
        g = (Glove.Builder().minWordFrequency(1).windowSize(2)
             .iterate(CollectionSentenceIterator(["a b c"]))
             .build())
        ii, jj, xx = g._cooccurrences()
        X = {(int(i), int(j)): float(x) for i, j, x in zip(ii, jj, xx)}
        ia, ib, ic = g.vocab["a"], g.vocab["b"], g.vocab["c"]
        assert X[(ia, ib)] == X[(ib, ia)] == 1.0      # adjacent
        assert X[(ia, ic)] == X[(ic, ia)] == 0.5      # distance 2 -> 1/2
        assert (ia, ia) not in X

    def test_xmax_weights_clip_at_one(self):
        m = self._fit(xMax=0.5)  # every pair saturates f(x)=1
        assert np.isfinite(m._score)


class TestVectorizers:
    DOCS = ["the cat sat", "the dog sat on the cat", "cpu and gpu"]
    LABELS = ["pets", "pets", "tech"]

    def _bow(self):
        return (BagOfWordsVectorizer.Builder()
                .setIterator(LabelAwareCollectionIterator(self.DOCS,
                                                          self.LABELS))
                .setTokenizerFactory(DefaultTokenizerFactory())
                .setMinWordFrequency(1)
                .setStopWords(["the", "and", "on"])
                .build().fit())

    def test_bow_counts_oracle(self):
        v = self._bow()
        assert v.vocabSize() == 5  # cat, sat, cpu, dog, gpu
        row = np.asarray(v.transform("cat cat dog zebra").jax())[0]
        assert row[v.indexOf("cat")] == 2.0
        assert row[v.indexOf("dog")] == 1.0
        assert row.sum() == 3.0  # zebra OOV, stopwords removed
        assert v.indexOf("the") == -1 and v.indexOf("zebra") == -1

    def test_tfidf_oracle(self):
        v = (TfidfVectorizer.Builder()
             .setIterator(LabelAwareCollectionIterator(self.DOCS,
                                                       self.LABELS))
             .setTokenizerFactory(DefaultTokenizerFactory())
             .setMinWordFrequency(1)
             .setStopWords(["the", "and", "on"])
             .build().fit())
        # df: cat=2 docs, cpu=1 doc; N=3
        t = v.tfidfWord("cpu", "cpu cpu")
        assert t == pytest.approx(2 * math.log(3 / 1))
        assert v.tfidfWord("cat", "cat") == pytest.approx(math.log(3 / 2))
        assert v.tfidfWord("zebra", "zebra") == 0.0
        row = np.asarray(v.transform("cat cpu").jax())[0]
        assert row[v.indexOf("cpu")] == pytest.approx(math.log(3))
        assert row[v.indexOf("cat")] == pytest.approx(math.log(1.5))

    def test_vectorize_to_dataset_and_label_guard(self):
        v = self._bow()
        ds = v.vectorize("cat sat", "pets")
        assert ds.getFeatures().shape() == (1, 5)
        np.testing.assert_array_equal(
            np.asarray(ds.getLabels().jax()), [[1.0, 0.0]])
        with pytest.raises(ValueError, match="unknown label"):
            v.vectorize("cat", "sports")

    def test_corpus_iterator_trains_classifier(self):
        # the RecordReaderDataSetIterator-style bridge: vectorized corpus
        # -> DataSetIterator -> MultiLayerNetwork.fit
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork, DenseLayer,
                                           OutputLayer, Adam)

        rng = np.random.RandomState(3)
        pets = ["cat", "dog", "sheep"]
        tech = ["cpu", "gpu", "disk"]
        docs, labels = [], []
        for _ in range(60):
            src = pets if rng.rand() < 0.5 else tech
            docs.append(" ".join(rng.choice(src, 4)))
            labels.append("pets" if src is pets else "tech")
        v = (TfidfVectorizer.Builder()
             .setIterator(LabelAwareCollectionIterator(docs, labels))
             .setMinWordFrequency(1).build().fit())
        it = v.iterator_over_corpus(batchSize=16, shuffle=True)
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
                .list()
                .layer(DenseLayer(nOut=16, activation="relu"))
                .layer(OutputLayer(nOut=2, activation="softmax",
                                   lossFunction="mcxent"))
                .setInputType(InputType.feedForward(v.vocabSize()))
                .build())
        net = MultiLayerNetwork(conf).init()
        for _ in range(12):
            net.fit(it)
        from deeplearning4j_tpu.evaluation import Evaluation

        ev = Evaluation(2)
        it.reset()
        while it.hasNext():
            ds = it.next()
            ev.eval(np.asarray(ds.getLabels().jax()),
                    np.asarray(net.output(ds.getFeatures()).jax()))
        assert ev.accuracy() > 0.95, ev.accuracy()

    def test_unlabelled_corpus_guards(self):
        v = (BagOfWordsVectorizer.Builder()
             .setIterator(CollectionSentenceIterator(["a b", "b c"]))
             .setMinWordFrequency(1).build().fit())
        assert v.vocabSize() == 3
        with pytest.raises(ValueError, match="label"):
            v.iterator_over_corpus()
        with pytest.raises(RuntimeError, match="fit"):
            BagOfWordsVectorizer().transform("a")


class TestHierarchicSoftmax:
    """useHierarchicSoftmax (reference: Word2Vec.Builder
    .useHierarchicSoftmax): Huffman codes over the vocab, sigmoid path
    losses — the upstream default output layer, here as one jitted
    padded-path step."""

    def test_huffman_codes_are_optimal_prefix_code(self):
        counts = np.array([50, 20, 15, 10, 5])
        pts, sgn, msk = Word2Vec._build_huffman(counts)
        lens = msk.sum(1).astype(int)
        # Kraft equality: a COMPLETE binary prefix code
        assert sum(2.0 ** -l for l in lens) == pytest.approx(1.0)
        # more frequent -> never a longer code
        assert all(lens[i] <= lens[j]
                   for i in range(5) for j in range(5)
                   if counts[i] > counts[j])
        # inner node ids within [0, V-1)
        assert pts.min() >= 0 and pts.max() < 4
        # signs are +-1 on real path entries
        assert set(np.unique(sgn[msk > 0])) == {-1.0, 1.0}
        with pytest.raises(ValueError, match="at least 2"):
            Word2Vec._build_huffman([7])

    def _fit(self, algorithm):
        return (Word2Vec.Builder()
                .minWordFrequency(2).layerSize(16).windowSize(3)
                .seed(7).iterations(40)
                .learningRate(1.0 if algorithm == "cbow" else 0.5)
                .elementsLearningAlgorithm(algorithm)
                .useHierarchicSoftmax()
                .iterate(CollectionSentenceIterator(_corpus()))
                .tokenizerFactory(DefaultTokenizerFactory())
                .build().fit())

    @pytest.mark.parametrize("algorithm", ["skipgram", "cbow"])
    def test_topic_words_cluster(self, algorithm):
        m = self._fit(algorithm)
        intra = m.similarity("cat", "dog")
        inter = m.similarity("cat", "gpu")
        assert intra > inter + 0.2, (algorithm, intra, inter)

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_paragraph_vectors_hs_and_serde(self, tmp_path):
        from deeplearning4j_tpu.nlp import ParagraphVectors

        rng = np.random.RandomState(1)
        animals = ["cat", "dog", "horse", "sheep"]
        tech = ["cpu", "gpu", "ram", "disk"]
        docs = []
        for i in range(40):
            src = animals if i % 2 == 0 else tech
            docs.append(" ".join(rng.choice(src, 8)))
        pv = (ParagraphVectors.Builder()
              .minWordFrequency(2).layerSize(16).windowSize(3)
              .seed(5).iterations(30).learningRate(0.5)
              .useHierarchicSoftmax()
              .iterate(CollectionSentenceIterator(docs))
              .build().fit())

        def cos(a, b):
            return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)
                                  + 1e-12))

        same = cos(pv.getParagraphVector(0), pv.getParagraphVector(2))
        diff = cos(pv.getParagraphVector(0), pv.getParagraphVector(1))
        assert same > diff + 0.2, (same, diff)
        v = pv.inferVector("cat dog sheep")
        assert cos(v, pv.getParagraphVector(0)) > \
            cos(v, pv.getParagraphVector(1))
        p = str(tmp_path / "pv_hs.npz")
        pv.save(p)
        pv2 = ParagraphVectors.load(p)
        assert pv2.useHierarchicSoftmax
        np.testing.assert_array_equal(pv2.inferVector("cat dog sheep"),
                                      pv.inferVector("cat dog sheep"))

    @pytest.mark.slow  # tier-1 budget (PR 21): 5 s on 8 CPU cores
    def test_load_then_save_roundtrips_both_modes(self, tmp_path):
        # regression: save() writes counts unconditionally, so a LOADED
        # model (old files may lack counts) must survive re-saving
        from deeplearning4j_tpu.nlp import ParagraphVectors

        docs = ["cat dog cat sheep", "cpu gpu disk ram"] * 15
        for hs in (False, True):
            pv = (ParagraphVectors.Builder().minWordFrequency(2)
                  .layerSize(8).windowSize(2).iterations(3)
                  .useHierarchicSoftmax(hs)
                  .iterate(CollectionSentenceIterator(docs)).build().fit())
            p1 = str(tmp_path / f"a{hs}.npz")
            p2 = str(tmp_path / f"b{hs}.npz")
            pv.save(p1)
            loaded = ParagraphVectors.load(p1)
            loaded.save(p2)  # crashed before the _counts restore fix
            again = ParagraphVectors.load(p2)
            np.testing.assert_array_equal(again.inferVector("cat dog"),
                                          pv.inferVector("cat dog"))


class TestStopWords:
    def test_stopwords_excluded_from_vocab_and_training(self):
        m = (Word2Vec.Builder()
             .minWordFrequency(1).layerSize(8).windowSize(2).iterations(2)
             .stopWords(["the", "of"])
             .iterate(CollectionSentenceIterator(
                 ["the cat of the house", "the dog of the yard"] * 5))
             .build().fit())
        assert not m.hasWord("the") and not m.hasWord("of")
        assert m.hasWord("cat") and m.hasWord("yard")
