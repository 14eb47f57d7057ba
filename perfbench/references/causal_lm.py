"""Plain float32 reference of the decoder-only LM the GPT cells serve.

The published model is GPT-2 family (Cerebras-GPT, arXiv:2304.03208):
learned positions, pre-norm blocks, full multi-head attention, a GELU
(tanh form) MLP of 4x, output embedding tied to the input one. Two
departures, the program's and noted in the configuration file: RMSNorm
(eps 1e-6, gain 1) where GPT-2 has LayerNorm, and no biases. The
reference follows the program's equations.

It is one full causal forward pass over a prompt with its served tokens:
no cache, no pages, no chunks, float32 at "highest" matmul precision.
It imports nothing of the program and takes nothing the program made.
The weights are drawn here from the seed by the model's stated rule:
N(0, 0.02) from one numpy `default_rng(seed)` stream in float64, per
layer wq wk wv wo w1 w2, then the embedding, then the positions; stored
in bfloat16, the type the configuration serves them in, so the model's
weights ARE the rounded values and the reference holds those in float32.

`precision="fp8"` is the control of "How correct is decided": every
weight matmul, the logits' among them, with both operands held in
float8 (e4m3, one scale per tensor) and accumulated in float32, the
nearest precision below the bfloat16 the configuration states and the
same control as the training configuration's. (int8 with a scale per
row and per column read 2.7-2.8 times the program's gap on the chip,
short of the three times a control needs: PERF.md.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
RMS_EPS = 1e-6
INIT_STD = 0.02
LAYER_KEYS = ("wq", "wk", "wv", "wo", "w1", "w2")


@jax.jit
def _to_model_dtype(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def make_weights(seed, m):
    """{"wq": [L, d, d], ..., "w1": [L, d, ff], "w2": [L, ff, d],
    "emb": [V, d], "pos": [T, d]} float32 device arrays holding the
    model's bfloat16 values."""
    if m["dtype"] != "bfloat16":
        raise ValueError("the reference rounds to bfloat16 only")
    rng = np.random.default_rng(seed)
    d, ff = m["n_embd"], m["n_inner"]
    shapes = {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
              "w1": (d, ff), "w2": (ff, d)}

    def draw(shape):
        return _to_model_dtype(
            (rng.standard_normal(shape) * INIT_STD).astype(np.float32))

    per_layer = {k: [] for k in LAYER_KEYS}
    for _ in range(m["n_layer"]):
        for k in LAYER_KEYS:
            per_layer[k].append(draw(shapes[k]))
    w = {k: jnp.stack(v) for k, v in per_layer.items()}
    w["emb"] = draw((m["vocab_size"], d))
    w["pos"] = draw((m["n_positions"], d))
    return w


def _rms(x):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS)


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, precision):
    """x [..., k] @ w [k, n]."""
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HI)


@functools.partial(jax.jit, static_argnames=("n_head", "precision"))
def logits_at(w, tokens, positions, *, n_head, precision="float32"):
    """Logits [P, V] of the next token after each of `positions` [P] of
    one sequence `tokens` [T] (zero-padded past its end: causal, so the
    padding changes nothing before it)."""
    T = tokens.shape[0]
    d = w["emb"].shape[1]
    dh = d // n_head
    h = w["emb"][tokens] + w["pos"][:T]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(h, lw):
        x = _rms(h)
        q = _mm(x, lw["wq"], precision).reshape(T, n_head, dh)
        k = _mm(x, lw["wk"], precision).reshape(T, n_head, dh)
        v = _mm(x, lw["wv"], precision).reshape(T, n_head, dh)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / (dh ** 0.5)
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                       precision=HI).reshape(T, d)
        h = h + _mm(a, lw["wo"], precision)
        x = _rms(h)
        h = h + _mm(jax.nn.gelu(_mm(x, lw["w1"], precision)),
                    lw["w2"], precision)
        return h, None

    h, _ = lax.scan(layer, h, {k: w[k] for k in LAYER_KEYS})
    return _mm(_rms(h)[positions], w["emb"].T, precision)


def served_logits(w, prompt, served, *, n_head, pad_to, precision="float32"):
    """Reference logits [len(served), V] for the positions at which the
    served tokens were sampled: the prompt's last position, then each
    served token but the last, in one forward pass over
    prompt + served[:-1], padded to a multiple of `pad_to`."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    T = -(-len(seq) // pad_to) * pad_to
    tokens = np.zeros((T,), np.int32)
    tokens[:len(seq)] = seq
    positions = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    return logits_at(w, tokens, positions, n_head=n_head,
                     precision=precision)
