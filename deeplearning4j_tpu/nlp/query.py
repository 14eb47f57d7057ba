"""Shared word-vector query surface.

Reference: the WordVectors/WordVectorsImpl interface in
deeplearning4j-nlp (hasWord / getWordVector / similarity /
wordsNearest) — one implementation serving both trained models
(Word2Vec and subclasses) and loaded static tables
(StaticWordVectors). Cosine scans are one [V, D] @ [D] product.
"""

from __future__ import annotations

import numpy as np


class WordVectorQuery:
    """Mixin over (self.vocab, self._ivocab, self._W). Subclasses may
    override _matrix() to gate access (e.g. require fit())."""

    def _host(self, attr):
        """Host copy of the device table bound at self.<attr>, cached on
        the table's identity — np.asarray per lookup would copy the
        whole table off the device on every query; a re-fit
        (which rebinds the attribute) invalidates the cache."""
        arr = getattr(self, attr)
        cache = getattr(self, "_host_cache", None)
        if cache is None:
            cache = self._host_cache = {}
        hit = cache.get(attr)
        if hit is None or hit[0] is not arr:
            hit = cache[attr] = (arr, np.asarray(arr))
        return hit[1]

    def _matrix(self):
        return self._host("_W")

    def hasWord(self, word):
        return word in self.vocab

    def getWordVector(self, word):
        # a COPY: callers normalize in place; a live view would corrupt
        # the shared table
        return np.array(self._matrix()[self.vocab[word]])

    def similarity(self, w1, w2):
        W = self._matrix()
        a, b = W[self.vocab[w1]], W[self.vocab[w2]]
        return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12))

    def wordsNearest(self, word, n=10, negative=None):
        """Nearest words by cosine. Two forms (reference: WordVectorsImpl
        .wordsNearest):

        - wordsNearest("king", 10) — neighbors of one word
        - wordsNearest(["king", "woman"], 5, negative=["man"]) — the
          classic analogy query: unit vectors of the positives summed,
          negatives subtracted, scaled by 1/(len(pos)+len(neg)) (the
          word2vec/gensim convention)
        """
        W = self._matrix()
        positive = [word] if isinstance(word, str) else list(word)
        neg = list(negative or [])
        if not positive and not neg:
            raise ValueError("wordsNearest needs at least one query word")
        missing = [w for w in positive + neg if w not in self.vocab]
        if missing:
            raise KeyError(f"words not in vocabulary: {missing}")
        # mean of normalized vectors, the word2vec convention: each query
        # word contributes direction, not magnitude
        def unit(w):
            v = W[self.vocab[w]]
            return v / (np.linalg.norm(v) + 1e-12)

        v = (sum(unit(w) for w in positive)
             - (sum(unit(w) for w in neg) if neg else 0.0)) / max(
            len(positive) + len(neg), 1)
        sims = W @ v / (np.linalg.norm(W, axis=1)
                        * (np.linalg.norm(v) + 1e-12) + 1e-12)
        order = np.argsort(-sims)
        query = set(positive) | set(neg)
        out = [self._ivocab[i] for i in order
               if self._ivocab[i] not in query]
        return out[:n]
