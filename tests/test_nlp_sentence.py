"""Tokenizer factories, preprocessors, and CnnSentenceDataSetIterator
(reference: deeplearning4j-nlp text.tokenization + iterator.
CnnSentenceDataSetIterator tests)."""

import numpy as np
import pytest

from deeplearning4j_tpu.nlp import (
    Word2Vec, DefaultTokenizerFactory, CollectionSentenceIterator,
    CommonPreprocessor, LowCasePreProcessor, EndingPreProcessor,
    NGramTokenizerFactory, CnnSentenceDataSetIterator,
    CollectionLabeledSentenceProvider, UnknownWordHandling,
    WordVectorSerializer, StaticWordVectors,
)


class TestTokenization:
    def test_default_tokenizer_with_preprocessor(self):
        tf = DefaultTokenizerFactory()
        assert tf.create("Hello, World! 42") == ["hello", "world", "42"]
        tf.setTokenPreProcessor(CommonPreprocessor())
        # digits stripped by CommonPreprocessor -> token drops out
        assert tf.create("Hello, World! 42") == ["hello", "world"]

    def test_lowcase_and_ending(self):
        assert LowCasePreProcessor().preProcess("ABC") == "abc"
        e = EndingPreProcessor()
        assert e.preProcess("cats") == "cat"
        assert e.preProcess("running") == "runn"  # reference parity: not a stemmer
        assert e.preProcess("quickly") == "quick"
        assert e.preProcess("boss") == "boss"

    def test_ngram_factory(self):
        tf = NGramTokenizerFactory(DefaultTokenizerFactory(), 1, 2)
        toks = tf.create("the quick fox")
        assert toks == ["the", "quick", "fox", "the quick", "quick fox"]

    def test_ngram_bigram_only_and_errors(self):
        tf = NGramTokenizerFactory(DefaultTokenizerFactory(), 2, 2)
        assert tf.create("a b c") == ["a b", "b c"]
        assert tf.create("single") == []
        with pytest.raises(ValueError):
            NGramTokenizerFactory(DefaultTokenizerFactory(), 3, 2)
        with pytest.raises(ValueError):
            NGramTokenizerFactory(DefaultTokenizerFactory(), 0, 2)


def _corpus(n=80, seed=0):
    rng = np.random.RandomState(seed)
    pets = ["cat", "dog", "sheep", "horse"]
    tech = ["cpu", "gpu", "disk", "ram"]
    sents, labels = [], []
    for _ in range(n):
        src = pets if rng.rand() < 0.5 else tech
        sents.append(" ".join(rng.choice(src, 5)))
        labels.append("pets" if src is pets else "tech")
    return sents, labels


def _w2v(sents):
    return (Word2Vec.Builder()
            .minWordFrequency(1).layerSize(12).windowSize(3)
            .negativeSample(4).seed(3).iterations(30).learningRate(0.4)
            .iterate(CollectionSentenceIterator(sents))
            .tokenizerFactory(DefaultTokenizerFactory())
            .build().fit())


class TestCnnSentenceIterator:
    def test_shapes_masks_labels(self):
        sents, labels = _corpus(20)
        wv = _w2v(sents)
        it = (CnnSentenceDataSetIterator.Builder()
              .sentenceProvider(CollectionLabeledSentenceProvider(sents,
                                                                  labels))
              .wordVectors(wv).maxSentenceLength(8).minibatchSize(4)
              .build())
        assert it.getLabels() == ["pets", "tech"]
        ds = it.next()
        f = np.asarray(ds.getFeatures().jax())
        m = np.asarray(ds.getFeaturesMaskArray().jax())
        y = np.asarray(ds.getLabels().jax())
        assert f.shape == (4, 1, 8, 12)
        assert m.shape == (4, 8)
        assert y.shape == (4, 2)
        # sentences are 5 tokens -> mask has 5 ones, padding rows zero
        assert m.sum(1).tolist() == [5.0] * 4
        np.testing.assert_allclose(f[0, 0, 5:], 0.0)

    def test_formats(self):
        sents, labels = _corpus(8)
        wv = _w2v(sents)
        prov = CollectionLabeledSentenceProvider(sents, labels)
        for fmt, shape in [("CNN1D", (8, 12, 6)), ("RNN", (8, 12, 6))]:
            it = CnnSentenceDataSetIterator(
                provider=prov, wordVectors=wv, maxSentenceLength=6,
                minibatchSize=8, format=fmt)
            f = np.asarray(it.next().getFeatures().jax())
            assert f.shape == shape, (fmt, f.shape)

    def test_unknown_word_handling(self):
        sents, labels = _corpus(8)
        wv = _w2v(sents)
        prov = CollectionLabeledSentenceProvider(
            ["cat zzz dog", "zzz zzz zzz"], ["pets", "tech"])
        it = CnnSentenceDataSetIterator(
            provider=prov, wordVectors=wv, maxSentenceLength=4,
            minibatchSize=2, format="CNN")
        m = np.asarray(it.next().getFeaturesMaskArray().jax())
        # RemoveWord: zzz dropped -> lengths 2 and 1 (all-unknown keeps
        # one zero step)
        assert m.sum(1).tolist() == [2.0, 1.0]
        it2 = CnnSentenceDataSetIterator(
            provider=prov, wordVectors=wv, maxSentenceLength=4,
            minibatchSize=2,
            unknownWordHandling=UnknownWordHandling.UseUnknownVector)
        m2 = np.asarray(it2.next().getFeaturesMaskArray().jax())
        assert m2.sum(1).tolist() == [3.0, 3.0]

    def test_errors(self):
        sents, labels = _corpus(8)
        wv = _w2v(sents)
        with pytest.raises(ValueError):
            CollectionLabeledSentenceProvider(["a"], ["x", "y"])
        with pytest.raises(ValueError):
            CollectionLabeledSentenceProvider([], [])
        prov = CollectionLabeledSentenceProvider(sents, labels)
        with pytest.raises(ValueError):
            CnnSentenceDataSetIterator(provider=prov, wordVectors=wv,
                                       format="NHWC")
        with pytest.raises(ValueError):
            CnnSentenceDataSetIterator(provider=prov, wordVectors=wv,
                                       unknownWordHandling="Ignore")
        with pytest.raises(ValueError):
            CnnSentenceDataSetIterator(provider=None, wordVectors=wv)

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_end_to_end_cnn_classifier(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork,
                                           ConvolutionLayer,
                                           GlobalPoolingLayer, OutputLayer,
                                           Adam)
        from deeplearning4j_tpu.evaluation import Evaluation

        sents, labels = _corpus(60, seed=4)
        wv = _w2v(sents)
        it = (CnnSentenceDataSetIterator.Builder()
              .sentenceProvider(CollectionLabeledSentenceProvider(sents,
                                                                  labels))
              .wordVectors(wv).maxSentenceLength(8).minibatchSize(16)
              .format("CNN").build())
        conf = (NeuralNetConfiguration.Builder().seed(5).updater(Adam(3e-3))
                .list()
                .layer(ConvolutionLayer(nOut=8, kernelSize=(3, 12),
                                        stride=(1, 1), padding=(0, 0),
                                        activation="relu"))
                .layer(GlobalPoolingLayer(poolingType="MAX"))
                .layer(OutputLayer(nOut=2, activation="softmax",
                                   lossFunction="mcxent"))
                .setInputType(InputType.convolutional(8, 12, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        for _ in range(15):
            net.fit(it)
        ev = Evaluation(2)
        it.reset()
        while it.hasNext():
            ds = it.next()
            ev.eval(np.asarray(ds.getLabels().jax()),
                    np.asarray(net.output(ds.getFeatures()).jax()))
        assert ev.accuracy() > 0.9, ev.accuracy()


class TestWordVectorSerializer:
    """Text-format interop (reference: WordVectorSerializer —
    writeWordVectors / loadTxtVectors / readWord2VecModel)."""

    def test_roundtrip_trained_model(self, tmp_path):
        from deeplearning4j_tpu.nlp import (WordVectorSerializer,
                                            StaticWordVectors)
        sents, _ = _corpus(30)
        wv = _w2v(sents)
        p = tmp_path / "vecs.txt"
        WordVectorSerializer.writeWordVectors(wv, p)
        sv = WordVectorSerializer.loadTxtVectors(p)
        assert isinstance(sv, StaticWordVectors)
        assert set(sv.vocab) == set(wv.vocab)
        for w in list(wv.vocab)[:5]:
            np.testing.assert_allclose(sv.getWordVector(w),
                                       wv.getWordVector(w),
                                       rtol=1e-4, atol=1e-4)
        # nearest-neighbor structure survives the 6-sig-digit text trip
        w0 = list(wv.vocab)[0]
        assert sv.wordsNearest(w0, 3) == wv.wordsNearest(w0, 3)

    def test_headerless_glove_style(self, tmp_path):
        from deeplearning4j_tpu.nlp import WordVectorSerializer
        p = tmp_path / "glove.txt"
        p.write_text("the 0.1 0.2 0.3\ncat -1 0.5 2\n")
        sv = WordVectorSerializer.loadTxtVectors(p)
        assert sv.hasWord("cat") and not sv.hasWord("dog")
        np.testing.assert_allclose(sv.getWordVector("cat"), [-1, 0.5, 2])

    def test_static_vectors_feed_cnn_sentence_iterator(self, tmp_path):
        from deeplearning4j_tpu.nlp import WordVectorSerializer
        sents, labels = _corpus(12)
        wv = _w2v(sents)
        p = tmp_path / "v.txt"
        WordVectorSerializer.writeWordVectors(wv, p)
        sv = WordVectorSerializer.loadTxtVectors(p)
        it = CnnSentenceDataSetIterator(
            provider=CollectionLabeledSentenceProvider(sents, labels),
            wordVectors=sv, maxSentenceLength=6, minibatchSize=4)
        assert np.asarray(it.next().getFeatures().jax()).shape == (4, 1, 6, 12)

    def test_dispatch_and_errors(self, tmp_path):
        from deeplearning4j_tpu.nlp import WordVectorSerializer, Word2Vec
        sents, _ = _corpus(20)
        wv = _w2v(sents)
        npz = tmp_path / "m.npz"
        wv.save(str(npz))
        back = WordVectorSerializer.readWord2VecModel(str(npz))
        assert isinstance(back, Word2Vec)
        txt = tmp_path / "m.txt"
        WordVectorSerializer.writeWordVectors(wv, txt)
        assert WordVectorSerializer.readWord2VecModel(str(txt)).hasWord(
            list(wv.vocab)[0])
        bad = tmp_path / "bad.txt"
        bad.write_text("a 1 2\nb 1\n")
        with pytest.raises(ValueError, match="components"):
            WordVectorSerializer.loadTxtVectors(bad)
        with pytest.raises(ValueError, match="no vectors"):
            empty = tmp_path / "e.txt"
            empty.write_text("")
            WordVectorSerializer.loadTxtVectors(empty)

    def test_whitespace_word_rejected_before_any_write(self, tmp_path):
        # validation must happen BEFORE the file is opened: a mid-loop
        # failure would leave a truncated file whose header lies
        from deeplearning4j_tpu.nlp import (WordVectorSerializer,
                                            StaticWordVectors)
        W = np.eye(3, dtype=np.float32)
        sv = StaticWordVectors(
            {"ok": 0, "new york": 1, "zz": 2}, W)
        p = tmp_path / "bad_vocab.txt"
        with pytest.raises(ValueError, match="whitespace"):
            WordVectorSerializer.writeWordVectors(sv, p)
        assert not p.exists()

    def test_host_matrix_cached_across_lookups(self):
        # getWordVector must not re-materialize the [V, D] table per
        # call (device tables pay a full transfer each time); the cache
        # invalidates when _W is rebound (re-fit)
        from deeplearning4j_tpu.nlp import StaticWordVectors
        sv = StaticWordVectors({"a": 0, "b": 1},
                               np.eye(2, dtype=np.float32))
        m1 = sv._matrix()
        assert sv._matrix() is m1
        sv._W = np.ones((2, 2), np.float32)  # rebind -> invalidate
        m2 = sv._matrix()
        assert m2 is not m1 and m2[0, 0] == 1.0

    def test_static_vectors_honor_dict_indices(self):
        # {word: row} dicts (the shape of Word2Vec.vocab) must bind by
        # the GIVEN indices, not dict iteration order
        from deeplearning4j_tpu.nlp import StaticWordVectors
        W = np.asarray([[1., 0.], [0., 1.]], np.float32)
        sv = StaticWordVectors({"b": 1, "a": 0}, W)  # insertion != index
        np.testing.assert_array_equal(sv.getWordVector("a"), W[0])
        np.testing.assert_array_equal(sv.getWordVector("b"), W[1])
        with pytest.raises(ValueError, match="row indices"):
            StaticWordVectors({"a": 0, "b": 2}, W)

    def test_host_matrix_cached_on_trained_model(self):
        # Word2Vec._matrix overrides the mixin (fit gate) — it must
        # still delegate to the caching path, or every per-token
        # getWordVector pays a full [V, D] device transfer
        sents, _ = _corpus(12)
        wv = _w2v(sents)
        assert wv._matrix() is wv._matrix()

    def test_whitespace_robust_parsing(self, tmp_path):
        from deeplearning4j_tpu.nlp import WordVectorSerializer
        p = tmp_path / "messy.txt"
        p.write_text("the 0.1  0.2\t0.3 \n   \ncat\t-1 0.5 2  \n")
        sv = WordVectorSerializer.loadTxtVectors(p)
        assert set(sv.vocab) == {"the", "cat"}
        np.testing.assert_allclose(sv.getWordVector("cat"), [-1, 0.5, 2])

    def test_numeric_vocab_1d_not_eaten_as_header(self, tmp_path):
        from deeplearning4j_tpu.nlp import WordVectorSerializer
        p = tmp_path / "years.txt"
        p.write_text("1984 3\n1985 4\n1986 5\n")  # 3 != body count of 2
        sv = WordVectorSerializer.loadTxtVectors(p)
        assert sv.hasWord("1984") and len(sv.vocab) == 3

    def test_suffixless_native_load(self, tmp_path):
        from deeplearning4j_tpu.nlp import WordVectorSerializer, Word2Vec
        sents, _ = _corpus(20)
        wv = _w2v(sents)
        wv.save(str(tmp_path / "model"))  # writes model.npz
        back = WordVectorSerializer.readWord2VecModel(str(tmp_path / "model"))
        assert isinstance(back, Word2Vec)

    def test_get_word_vector_is_a_copy(self, tmp_path):
        from deeplearning4j_tpu.nlp import StaticWordVectors
        sv = StaticWordVectors(["a", "b"], np.eye(2, dtype="float32"))
        v = sv.getWordVector("a")
        v *= 100.0  # in-place caller mutation must not corrupt the table
        np.testing.assert_allclose(sv.getWordVector("a"), [1.0, 0.0])


class TestAnalogyQuery:
    """wordsNearest(positive, negative, n) analogy form (reference:
    WordVectorsImpl.wordsNearest(Collection, Collection, int))."""

    def test_analogy_on_constructed_vectors(self):
        from deeplearning4j_tpu.nlp import StaticWordVectors
        # geometry engineered so king - man + woman == queen exactly
        W = np.asarray([
            [1.0, 1.0, 0.0],   # king  = royal + male
            [0.0, 1.0, 0.0],   # man   = male
            [0.0, 0.0, 1.0],   # woman = female
            [1.0, 0.0, 1.0],   # queen = royal + female
            [0.0, 0.0, 0.0],   # filler
        ], np.float32)
        W[4] = [0.3, 0.3, 0.3]
        sv = StaticWordVectors(["king", "man", "woman", "queen", "x"], W)
        got = sv.wordsNearest(["king", "woman"], 1, negative=["man"])
        assert got == ["queen"]
        # single-word form unchanged
        assert sv.wordsNearest("king", 2)[0] in ("queen", "man", "x")
        with pytest.raises(KeyError, match="vocabulary"):
            sv.wordsNearest(["king", "nope"], 1)

    def test_string_positive_with_negative_honored(self):
        # a plain-string positive must not silently drop `negative`
        from deeplearning4j_tpu.nlp import StaticWordVectors
        W = np.asarray([
            [1.0, 1.0, 0.0],   # king
            [0.0, 1.0, 0.0],   # man
            [0.0, 0.0, 1.0],   # woman
            [1.0, 0.0, 1.0],   # queen
        ], np.float32)
        sv = StaticWordVectors(["king", "man", "woman", "queen"], W)
        got = sv.wordsNearest("king", 2, negative=["man"])
        assert "man" not in got          # negatives excluded from results
        assert got[0] == "queen"         # royal direction wins sans male

    def test_single_word_backcompat(self):
        from deeplearning4j_tpu.nlp import StaticWordVectors
        W = np.asarray([[1, 0], [0.9, 0.1], [0, 1]], np.float32)
        sv = StaticWordVectors(["a", "b", "c"], W)
        assert sv.wordsNearest("a", 1) == ["b"]
        assert "a" not in sv.wordsNearest("a", 3)


class TestBinaryWordVectors:
    """word2vec C binary format (reference: WordVectorSerializer's
    binary read path for Google News-style .bin files)."""

    def _vectors(self):
        words = ["alpha", "beta", "gamma"]
        mat = np.arange(9, dtype="float32").reshape(3, 3) / 7.0
        return StaticWordVectors(words, mat)

    def test_roundtrip(self, tmp_path):
        v = self._vectors()
        p = tmp_path / "vecs.bin"
        WordVectorSerializer.writeBinaryModel(v, p)
        r = WordVectorSerializer.readBinaryModel(p)
        assert r._ivocab == v._ivocab
        np.testing.assert_allclose(r._W, v._W, rtol=1e-7)

    def test_wire_format_oracle(self, tmp_path):
        # hand-assembled spec bytes: header, then word + ' ' + LE floats
        # + '\n' — what the original word2vec C tool emits
        import struct
        p = tmp_path / "hand.bin"
        with open(p, "wb") as f:
            f.write(b"2 2\n")
            f.write(b"cat " + struct.pack("<2f", 1.5, -2.25) + b"\n")
            f.write(b"dog " + struct.pack("<2f", 0.5, 4.0) + b"\n")
        r = WordVectorSerializer.readBinaryModel(p)
        assert r._ivocab == ["cat", "dog"]
        np.testing.assert_allclose(r.getWordVector("cat"), [1.5, -2.25])
        np.testing.assert_allclose(r.getWordVector("dog"), [0.5, 4.0])

    def test_written_bytes_match_spec(self, tmp_path):
        import struct
        v = StaticWordVectors(["x"], np.asarray([[1.0, 2.0]], "float32"))
        p = tmp_path / "out.bin"
        WordVectorSerializer.writeBinaryModel(v, p)
        assert open(p, "rb").read() == \
            b"1 2\nx " + struct.pack("<2f", 1.0, 2.0) + b"\n"

    def test_truncated_raises(self, tmp_path):
        import struct
        p = tmp_path / "trunc.bin"
        with open(p, "wb") as f:
            f.write(b"2 2\n")
            f.write(b"cat " + struct.pack("<2f", 1.0, 2.0) + b"\n")
            f.write(b"dog " + struct.pack("<f", 1.0))  # half a vector
        with pytest.raises(ValueError, match="truncated"):
            WordVectorSerializer.readBinaryModel(p)

    def test_read_word2vec_model_dispatches_binary(self, tmp_path):
        v = self._vectors()
        p = tmp_path / "auto.bin"
        WordVectorSerializer.writeBinaryModel(v, p)
        r = WordVectorSerializer.readWord2VecModel(p)
        np.testing.assert_allclose(r.getWordVector("beta"),
                                   v.getWordVector("beta"))
        # and a text file still goes down the text path
        pt = tmp_path / "auto.txt"
        WordVectorSerializer.writeWordVectors(v, pt)
        rt = WordVectorSerializer.readWord2VecModel(pt)
        np.testing.assert_allclose(rt.getWordVector("beta"),
                                   v.getWordVector("beta"), rtol=1e-5)

    def test_whitespace_word_rejected(self, tmp_path):
        v = StaticWordVectors(["ok", "bad word"],
                              np.zeros((2, 2), "float32"))
        with pytest.raises(ValueError, match="whitespace"):
            WordVectorSerializer.writeBinaryModel(v, tmp_path / "w.bin")

    def test_zero_vector_binary_still_dispatches(self, tmp_path):
        # all-zero float payloads are valid UTF-8, defeating the byte
        # sniff — the text-parse-fails -> clean-binary-parse fallback
        # must still route correctly
        v = StaticWordVectors(["pad", "ok"], np.zeros((2, 3), "float32"))
        p = tmp_path / "zeros.bin"
        WordVectorSerializer.writeBinaryModel(v, p)
        r = WordVectorSerializer.readWord2VecModel(p)
        assert r._ivocab == ["ok", "pad"] or r._ivocab == ["pad", "ok"]
        np.testing.assert_allclose(r.getWordVector("pad"), [0, 0, 0])

    def test_trailing_garbage_rejected(self, tmp_path):
        import struct
        p = tmp_path / "extra.bin"
        with open(p, "wb") as f:
            f.write(b"1 2\nw " + struct.pack("<2f", 1.0, 2.0) + b"\n")
            f.write(b"unexpected trailing bytes")
        with pytest.raises(ValueError, match="unexpected bytes"):
            WordVectorSerializer.readBinaryModel(p)

    def test_utf8_boundary_not_misread_as_binary(self, tmp_path):
        # a multibyte char straddling the 4096-byte sniff boundary must
        # not flip a text file to the binary path
        p = tmp_path / "boundary.txt"
        word = "café"  # 5 bytes utf-8, é = 2 bytes
        filler = "x" * (4095 - 1 - 4)  # word starts so é spans offset 4096
        with open(p, "w", encoding="utf-8") as f:
            f.write(filler + " 1.0\n")   # first "word" is the filler
            f.write(word + " 2.0\n")
        assert not WordVectorSerializer._looks_binary(p)
        r = WordVectorSerializer.readWord2VecModel(p)
        assert r.hasWord(word)

    def test_mid_float_truncation_diagnostic(self, tmp_path):
        import struct
        p = tmp_path / "midfloat.bin"
        with open(p, "wb") as f:
            f.write(b"1 2\nw " + struct.pack("<f", 1.0) + b"\x00\x01")
        with pytest.raises(ValueError, match="truncated vector for 'w'"):
            WordVectorSerializer.readBinaryModel(p)


class TestFastTextIntegration:
    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_fasttext_feeds_cnn_sentence_iterator(self):
        # FastText shares the WordVectors query surface, so it plugs
        # into CnnSentenceDataSetIterator exactly like Word2Vec
        from deeplearning4j_tpu.nlp import FastText
        sents, labels = _corpus(20)
        ft = (FastText.Builder().minCount(1).dim(12).epochs(10).seed(3)
              .iterate(CollectionSentenceIterator(sents)).build().fit())
        it = (CnnSentenceDataSetIterator.Builder()
              .sentenceProvider(CollectionLabeledSentenceProvider(sents,
                                                                  labels))
              .wordVectors(ft).maxSentenceLength(8).minibatchSize(4)
              .build())
        ds = it.next()
        f = np.asarray(ds.getFeatures().jax())
        assert f.shape == (4, 1, 8, 12)
        # the embedded rows are exactly FastText's baked vectors
        first_tokens = sents[0].split()
        np.testing.assert_allclose(
            f[0, 0, 0], ft.getWordVector(first_tokens[0]), rtol=1e-5)


class TestParagraphVectorsSerializer:
    @pytest.mark.slow  # tier-1 budget (PR 21): 3 s on 8 CPU cores
    def test_write_read_roundtrip(self, tmp_path):
        from deeplearning4j_tpu.nlp import ParagraphVectors
        sents, _ = _corpus(16)
        pv = (ParagraphVectors.Builder()
              .minWordFrequency(1).layerSize(8).windowSize(2)
              .iterations(3).seed(1)
              .iterate(CollectionSentenceIterator(sents))
              .build().fit())
        p = tmp_path / "pv"
        WordVectorSerializer.writeParagraphVectors(pv, p)
        pv2 = WordVectorSerializer.readParagraphVectors(p)
        np.testing.assert_allclose(pv2.getParagraphVector(0),
                                   pv.getParagraphVector(0), rtol=1e-6)

    def test_write_rejects_plain_word2vec(self, tmp_path):
        sents, _ = _corpus(8)
        w = _w2v(sents)
        with pytest.raises(TypeError, match="ParagraphVectors"):
            WordVectorSerializer.writeParagraphVectors(w, tmp_path / "x")

    @pytest.mark.slow  # tier-1 budget (PR 21): 4 s on 8 CPU cores
    def test_read_word2vec_model_returns_paragraph_vectors(self, tmp_path):
        from deeplearning4j_tpu.nlp import ParagraphVectors
        sents, _ = _corpus(12)
        pv = (ParagraphVectors.Builder()
              .minWordFrequency(1).layerSize(8).windowSize(2)
              .iterations(2).seed(1)
              .iterate(CollectionSentenceIterator(sents))
              .build().fit())
        p = tmp_path / "pvx"
        WordVectorSerializer.writeParagraphVectors(pv, p)
        m = WordVectorSerializer.readWord2VecModel(str(p) + ".npz")
        assert isinstance(m, ParagraphVectors)
        np.testing.assert_allclose(m.getParagraphVector(0),
                                   pv.getParagraphVector(0), rtol=1e-6)
