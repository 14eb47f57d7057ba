"""FastText (reference: deeplearning4j-nlp
org.deeplearning4j.models.fasttext.FastText — the JNI wrapper over the
C++ fastText library; Builder flags supervised/skipgram/bucket/minn/
maxn/wordNgrams, API fit/predict/predictProbability/getWordVector).
Covers: n-gram extraction oracle, skip-gram clustering, OOV vectors via
shared subwords, supervised classification, serde.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.nlp import (
    FastText, CollectionSentenceIterator, DefaultTokenizerFactory,
)
from deeplearning4j_tpu.nlp.fasttext import _fnv1a, _ngrams


def _corpus(n=300, seed=0):
    rng = np.random.RandomState(seed)
    animals = ["cat", "dog", "horse", "sheep", "cow"]
    tech = ["cpu", "gpu", "ram", "disk", "cache"]
    sents = []
    for _ in range(n):
        topic = animals if rng.rand() < 0.5 else tech
        sents.append(" ".join(rng.choice(topic, 6)))
    return sents


class TestSubwords:
    def test_ngram_extraction_oracle(self):
        # fastText brackets the word: <where> → 3-grams of "<where>"
        assert _ngrams("where", 3, 3) == [
            "<wh", "whe", "her", "ere", "re>"]
        # upstream computeSubwords parity: the full bracketed word is a
        # subword whenever its length is within [minn, maxn] (ADVICE r4)
        got = _ngrams("as", 3, 6)
        assert got == ["<as", "as>", "<as>"]

    def test_full_bracketed_word_in_range_only(self):
        assert "<cat>" in _ngrams("cat", 5, 5)  # len("<cat>") == 5
        for n in (3, 4, 6):
            assert "<cat>" not in _ngrams("cat", n, n)

    def test_fnv1a_reference_values(self):
        # FNV-1a 32-bit published test vectors
        assert _fnv1a("") == 2166136261
        assert _fnv1a("a") == 0xE40C292C
        assert _fnv1a("foobar") == 0xBF9CF968


class TestSkipgramSubwords:
    @pytest.fixture(scope="class")
    def model(self):
        return (FastText.Builder()
                .minCount(2).dim(16).contextWindow(3)
                .negativeSamples(4).bucket(500)
                .minNgramLength(2).maxNgramLength(3)
                .epochs(40).learningRate(0.5).seed(7)
                .iterate(CollectionSentenceIterator(_corpus()))
                .tokenizerFactory(DefaultTokenizerFactory())
                .build().fit())

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_topic_words_cluster(self, model):
        # subword sharing compresses cosine margins relative to plain
        # Word2Vec (every pair shares some hashed n-gram buckets), so
        # the discriminator here is the RANKING, not a wide margin
        intra = model.similarity("cat", "dog")
        inter = model.similarity("cat", "gpu")
        assert intra > inter, (intra, inter)
        near = model.wordsNearest("cpu", 4)
        assert set(near) <= {"gpu", "ram", "disk", "cache"}, near

    def test_oov_vector_from_subwords(self, model):
        # "cats" is OOV but shares <ca/cat/at with "cat": its subword
        # vector must be closer to cat than to an unrelated tech word
        assert not model.hasWord("cats")
        v = model.getWordVector("cats")
        assert v.shape == (16,)
        sim_cat = model.similarityOOV("cats", "cat")
        sim_gpu = model.similarityOOV("cats", "gpu")
        assert sim_cat > sim_gpu, (sim_cat, sim_gpu)

    def test_oov_no_ngrams_raises(self, model):
        # minn=2 → a 1-char word still yields "<a"/"a>"; raise only when
        # truly nothing matches — force with a big minn via fresh model
        m = FastText(minn=10, maxn=12)
        m.vocab, m._ivocab = {}, []
        m._G = model._G
        with pytest.raises(KeyError, match="n-grams"):
            m.getWordVector("ab")

    def test_serde_roundtrip_incl_oov(self, model, tmp_path):
        p = tmp_path / "ft"
        model.save(p)
        m2 = FastText.load(p)
        assert m2.vocab == model.vocab
        np.testing.assert_allclose(m2.getWordVector("cat"),
                                   model.getWordVector("cat"), rtol=1e-6)
        np.testing.assert_allclose(m2.getWordVector("cats"),
                                   model.getWordVector("cats"), rtol=1e-6)


class TestSupervised:
    def _labeled_corpus(self, n=200, seed=3):
        rng = np.random.RandomState(seed)
        animals = ["cat", "dog", "horse", "sheep", "cow"]
        tech = ["cpu", "gpu", "ram", "disk", "cache"]
        out = []
        for _ in range(n):
            if rng.rand() < 0.5:
                out.append("__label__animal " + " ".join(rng.choice(animals, 5)))
            else:
                out.append("__label__tech " + " ".join(rng.choice(tech, 5)))
        return out

    @pytest.fixture(scope="class")
    def model(self):
        return (FastText.Builder()
                .supervised().minCount(1).dim(12)
                .wordNgrams(2).bucket(300)
                .epochs(60).learningRate(0.5).seed(5)
                .iterate(CollectionSentenceIterator(self._labeled_corpus()))
                .build().fit())

    def test_labels_discovered(self, model):
        assert model.labels == ["animal", "tech"]

    def test_predict(self, model):
        assert model.predict("the cat and the dog") == "animal"
        assert model.predict("gpu ram cache") == "tech"

    def test_predict_probability(self, model):
        lab, p = model.predictProbability("sheep cow horse")
        assert lab == "animal"
        assert 0.5 < p <= 1.0

    def test_missing_label_raises(self):
        m = FastText(supervised=True,
                     iterator=CollectionSentenceIterator(["no label here"]))
        with pytest.raises(ValueError, match="__label__"):
            m.fit()

    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_unsupervised_model_predict_raises(self):
        m = (FastText.Builder().minCount(1).dim(4).epochs(1)
             .iterate(CollectionSentenceIterator(["a b c d e f g"] * 3))
             .build().fit())
        with pytest.raises(RuntimeError, match="supervised"):
            m.predict("a b")

    def test_serde_roundtrip(self, model, tmp_path):
        p = tmp_path / "ft_sup"
        model.save(p)
        m2 = FastText.load(p)
        assert m2.labels == model.labels
        assert m2.predict("cat dog") == model.predict("cat dog")
        lab, prob = model.predictProbability("cpu disk")
        lab2, prob2 = m2.predictProbability("cpu disk")
        assert lab == lab2 and abs(prob - prob2) < 1e-6
