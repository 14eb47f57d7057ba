"""Pretrained embedding initialization (reference:
org.deeplearning4j.nn.weights.embeddings.WeightInitEmbedding /
ArrayEmbeddingInitializer + deeplearning4j-nlp's
WordVectorsEmbeddingInitializer): seed EmbeddingLayer /
EmbeddingSequenceLayer tables from a trained word-vector model or a raw
array, then fine-tune."""

import numpy as np
import pytest

from deeplearning4j_tpu.nn import (
    NeuralNetConfiguration, EmbeddingLayer, OutputLayer, GlobalPoolingLayer,
    MultiLayerNetwork, Adam, WeightInitEmbedding, InputType,
)
from deeplearning4j_tpu.nn.conf.layers import EmbeddingSequenceLayer
from deeplearning4j_tpu.nlp import (
    Word2Vec, CollectionSentenceIterator, DefaultTokenizerFactory,
)


def _corpus(n=200, seed=0):
    rng = np.random.RandomState(seed)
    animals = ["cat", "dog", "horse", "sheep", "cow"]
    tech = ["cpu", "gpu", "ram", "disk", "cache"]
    return [" ".join(rng.choice(animals if rng.rand() < 0.5 else tech, 6))
            for _ in range(n)]


@pytest.fixture(scope="module")
def w2v():
    return (Word2Vec.Builder()
            .minWordFrequency(2).layerSize(12).windowSize(3)
            .negativeSample(4).seed(7).iterations(25).learningRate(0.5)
            .iterate(CollectionSentenceIterator(_corpus()))
            .tokenizerFactory(DefaultTokenizerFactory())
            .build().fit())


class TestWeightInitEmbedding:
    @pytest.mark.slow  # tier-1 budget (PR 21): 2 s on 8 CPU cores
    def test_rows_match_vocab_order(self, w2v):
        V, D = len(w2v.vocab), w2v.layerSize
        conf = (NeuralNetConfiguration.Builder().seed(1).list()
                .layer(EmbeddingLayer(nIn=V, nOut=D,
                                      weightInit=WeightInitEmbedding(w2v)))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(1)).build())
        net = MultiLayerNetwork(conf).init()
        W = np.asarray(net.getParam("0_W"))
        assert W.shape == (V, D)
        for word, idx in w2v.vocab.items():
            np.testing.assert_allclose(W[idx], w2v.getWordVector(word),
                                       rtol=1e-6)

    def test_raw_array_source(self):
        table = np.random.RandomState(3).randn(7, 5).astype("float32")
        conf = (NeuralNetConfiguration.Builder().seed(1).list()
                .layer(EmbeddingLayer(nIn=7, nOut=5,
                                      weightInit=WeightInitEmbedding(table)))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(1)).build())
        net = MultiLayerNetwork(conf).init()
        np.testing.assert_allclose(np.asarray(net.getParam("0_W")), table,
                                   rtol=1e-6)

    def test_shape_mismatch_raises(self, w2v):
        V = len(w2v.vocab)
        conf = (NeuralNetConfiguration.Builder().seed(1).list()
                .layer(EmbeddingLayer(nIn=V + 3, nOut=99,
                                      weightInit=WeightInitEmbedding(w2v)))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.feedForward(1)).build())
        with pytest.raises(ValueError, match="does not match"):
            MultiLayerNetwork(conf).init()

    def test_sequence_layer_finetunes_from_pretrained(self, w2v):
        """EmbeddingSequenceLayer seeded from Word2Vec, mean-pooled into
        a topic classifier: the pretrained start must already separate
        the two topics better than chance after a short fine-tune, and
        training must move the loss down."""
        V, D = len(w2v.vocab), w2v.layerSize
        rng = np.random.RandomState(5)
        sents = _corpus(120, seed=9)
        T = 6
        X = np.zeros((len(sents), T), "float32")
        y = np.zeros((len(sents),), int)
        animals = {"cat", "dog", "horse", "sheep", "cow"}
        for i, s in enumerate(sents):
            toks = [t for t in s.split() if t in w2v.vocab][:T]
            X[i, :len(toks)] = [w2v.vocab[t] for t in toks]
            y[i] = 0 if toks and toks[0] in animals else 1
        Y = np.eye(2, dtype="float32")[y]
        conf = (NeuralNetConfiguration.Builder().seed(2).updater(Adam(1e-2))
                .list()
                .layer(EmbeddingSequenceLayer(
                    nIn=V, nOut=D, inputLength=T,
                    weightInit=WeightInitEmbedding(w2v)))
                .layer(GlobalPoolingLayer(poolingType="AVG"))
                .layer(OutputLayer(nOut=2, activation="softmax"))
                .setInputType(InputType.recurrent(1, T)).build())
        net = MultiLayerNetwork(conf).init()
        first = None
        for _ in range(25):
            net.fit(X, Y)
            if first is None:
                first = net.score()
        assert net.score() < first, (first, net.score())
        acc = (np.asarray(net.output(X).toNumpy()).argmax(1) == y).mean()
        assert acc > 0.9, acc
