"""Decode-feedback samplers with per-slot seeded RNG streams.

The PR 15 remainder: generation feedback beyond greedy one-hot. A
sampler is a host-side callable ``sample(logits_row, rng) -> token``
over a decode step's fp32 logits; the rng is a per-request
``numpy.random.Generator`` the scheduler seeds as ``default_rng((seed,
stream_id))`` with stream ids assigned in submit order — so sampling
is DETERMINISTIC per (seed, stream): the bitwise-vs-serial gate holds
with temperature sampling exactly as it does with greedy, because the
serial oracle replays the same stream (tests/test_paged_serving.py).

``greedy_sampler`` ignores its rng (argmax — the default, mirroring
``greedy_onehot_feedback`` on the RNN path, which stays) and says so
of itself: the callable it returns carries ``picks_argmax = True``,
and the paged scheduler steps a request whose sampler is so marked on
the argmax its decode step computed on the device, without waiting for
the row (serving/sequence.py). Every other callable — a temperature
draw, a user's function, a wrapper around the greedy one — is called
on the host with its float32 row and its rng, as the serial oracle
calls all of them. The RNN path's one-hot twin of a sampler is
``sampled_onehot_feedback``.
"""

from __future__ import annotations

import numpy as np

__all__ = ["greedy_sampler", "temperature_sampler", "stream_rng",
           "sampled_onehot_feedback"]


def stream_rng(seed, stream_id):
    """The per-slot RNG stream: deterministic in (seed, stream_id),
    independent across streams (numpy SeedSequence spawning under
    ``default_rng`` key tuples)."""
    return np.random.default_rng((int(seed), int(stream_id)))


def greedy_sampler():
    """argmax over the logits row — deterministic, rng unused. Marked
    ``picks_argmax`` (module docstring)."""

    def sample(logits, rng):
        return int(np.argmax(logits))

    sample.picks_argmax = True
    return sample


def temperature_sampler(temperature=1.0, top_k=None):
    """Softmax sampling at ``temperature``, optionally truncated to
    the ``top_k`` highest-logit tokens. temperature -> 0 degenerates
    to greedy (and temperature=0 is accepted as exactly that). The
    draw comes from the caller-provided per-slot rng stream, so equal
    (seed, stream) always yields the same token for the same logits."""
    temperature = float(temperature)
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k is not None and int(top_k) < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    top_k = None if top_k is None else int(top_k)
    if temperature == 0:
        return greedy_sampler()

    def sample(logits, rng):
        z = np.asarray(logits, np.float64) / temperature
        if top_k is not None and top_k < z.shape[0]:
            # keep the k largest; ties break by index like argpartition
            cut = np.argpartition(z, -top_k)[:-top_k]
            z = z.copy()
            z[cut] = -np.inf
        z = z - np.max(z)
        p = np.exp(z)
        p /= p.sum()
        return int(rng.choice(p.shape[0], p=p))

    return sample


def sampled_onehot_feedback(vocab, sampler, rng):
    """RNN-path twin: wrap a token sampler as a one-hot feedback
    closure for ``SequenceScheduler`` (the sampled token's one-hot row
    is the next input). Deterministic per the sampler's rng stream."""
    eye = np.eye(int(vocab), dtype=np.float32)

    def feedback(out_row):
        return eye[sampler(np.asarray(out_row, np.float32), rng)]

    return feedback
