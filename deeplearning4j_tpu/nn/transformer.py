"""Functional causal-transformer step twin for the paged serving tier.

PR 15's sequence scheduler serves the RNN h/c carry twin
(``MultiLayerNetwork.rnnStepBatched``); the transformer-class path
carries KV instead of a fixed-width hidden state, so its serving twin
is a pair of PURE step functions over an external paged KV cache
(serving/kvcache.py):

* ``prefill`` — append ONE prompt chunk's K/V into the slot's freshly
  allocated pages and attend the chunk's queries over the block table
  so far (causal in-chunk). A chunk is a whole number of KV pages of
  one slot, one of ``PREFILL_CHUNK_PAGES``: a pass reads every weight
  once, which costs the same whatever the pass carries until it holds
  a couple of hundred tokens, so ``prefill_plan`` keeps a prompt's
  passes longer than a page. Bounded work per call: a long prompt is
  consumed one chunk per scheduler iteration and can never stall the
  running decode batch.
* ``decode`` — one token per live slot: append each slot's K/V row at
  its block table's (page, offset), then one block-table attention
  step over every slot (one executable per slot bucket, exactly the
  rnnStepBatched discipline — warm every bucket, zero steady-state
  compiles).

Attention goes through ``ops.pallas_attention.paged_attention``, which
chooses from the backend and the shapes alone (``attend_impl()`` says
which): on the TPU, at shapes ``_paged_kernel_fits`` admits, the pallas
block-table kernels, which fetch each live page of the pool into VMEM
once and no other page; everywhere else ``paged_attend``, their
page-sequential online-softmax twin on the gathered tables (the CPU
path and the reference). Both take page_size as the block size,
whatever the chunk's length, and accumulate per head in the dense
flash kernel's block order (tests/test_paged_attention.py gates the
kernels in interpret mode).

A DENSE-cache twin (``decode_dense``/``prefill_dense``: contiguous
``[L, S, max_context, H, Dh]`` slabs, the pre-paged shape) rides along
as the bench A/B baseline and the serial-trajectory oracle: it views
its slab as a pool under an identity block table and goes through the
SAME dispatcher, so paged-vs-dense generation is bitwise comparable on
one backend (``dense_serial_trajectory``).

This is a serving twin, not a trainer: parameters are seeded at
construction (pure ``numpy.random.default_rng``), there is no fit
path, and every step function is cached through runtime/aot with an
explicit config fingerprint.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CausalTransformerLM", "PREFILL_CHUNK_PAGES",
           "dense_serial_trajectory", "prefill_plan"]

#: Lengths of a prefill chunk in KV pages, ascending: one executable of
#: ``_prefill_paged`` each (warmed by the scheduler). On the v5e at
#: bfloat16 a pass of one 128-token page is bound by the weights (30 us
#: a token) and from two pages on by the matrix products (19-20 us a
#: token whatever the length), so what pays is never to run a pass of
#: one page and never to pad one; twos and threes do that for every
#: prompt of two pages or more. PERF.md section 6 (PR 31) has the
#: device time of each length and the lengths that were tried and
#: dropped.
PREFILL_CHUNK_PAGES = (1, 2, 3)


def prefill_plan(n_tokens, n_live, page_size, max_pages):
    """The prefill passes of one prompt: ``[(t0, n_valid, C), ...]``.

    ``n_tokens`` prompt tokens of which the first ``n_live`` are in KV
    already (whole adopted pages), pages of ``page_size`` tokens, a
    block table of ``max_pages`` entries. Each pass takes ``n_valid``
    prompt tokens from position ``t0`` in a chunk of ``C`` tokens, C a
    length of ``PREFILL_CHUNK_PAGES``: the largest that the pages still
    to fill hold and that does not leave a single page behind (four
    pages are two and two, not three and one). Largest first, so a
    prompt pays for the weights as few times as the lengths allow; no
    chunk holds a page without a prompt token, so none runs past the
    table's end (the step's ``dynamic_slice`` of the table would clamp
    there and write other pages). The scheduler, its warm-up and the
    dense oracle all take their chunks from here."""
    if n_live % page_size and n_live < n_tokens:
        raise ValueError(
            f"prefill resumes on a page boundary, got {n_live} live "
            f"tokens at page {page_size}")
    passes = []
    t0 = n_live
    while t0 < n_tokens:
        left = -(-(n_tokens - t0) // page_size)
        n = max((c for c in PREFILL_CHUNK_PAGES
                 if c <= left and left - c != 1), default=1)
        n_valid = min(n * page_size, n_tokens - t0)
        assert t0 // page_size + n <= max_pages      # the table's end
        passes.append((t0, n_valid, n * page_size))
        t0 += n_valid
    return passes


def _rmsnorm(x, g):
    xf = x.astype(jnp.float32)
    inv = jnp.reciprocal(
        jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6))
    return (xf * inv).astype(x.dtype) * g


class CausalTransformerLM:
    """Decoder-only causal LM with paged-KV serving step functions
    (module docstring).

    vocab/d_model/n_heads/n_layers/d_ff: the usual dims (d_ff defaults
    to 4*d_model). max_context bounds positions; page_size is the KV
    page (max_context % page_size == 0) and the unit of a prefill
    chunk, which is PREFILL_CHUNK_PAGES pages long (prefill_plan).
    dtype is the compute/storage dtype (params, KV pools, residual
    stream); logits always come back fp32, and the decode step hands
    back their argmax beside them, so a greedy request's next token
    is 4 bytes to fetch and its row can follow later, and can take its
    tokens from an earlier step's ids still on the device.
    """

    #: duck-type marker the serving host dispatches on
    kind = "paged_lm"

    def __init__(self, *, vocab, d_model=32, n_heads=2, n_layers=2,
                 d_ff=None, max_context=64, page_size=8,
                 dtype="float32", seed=0):
        if int(d_model) % int(n_heads):
            raise ValueError(
                f"d_model {d_model} must divide by n_heads {n_heads}")
        if int(max_context) % int(page_size):
            raise ValueError(
                f"max_context {max_context} must be a multiple of "
                f"page_size {page_size}")
        self.vocab = int(vocab)
        self.d_model = int(d_model)
        self.n_heads = int(n_heads)
        self.n_layers = int(n_layers)
        self.d_ff = int(d_ff) if d_ff else 4 * self.d_model
        self.max_context = int(max_context)
        self.page_size = int(page_size)
        self.max_pages_per_slot = self.max_context // self.page_size
        self.head_dim = self.d_model // self.n_heads
        self.seed = int(seed)
        self._compute_dtype = jnp.dtype(dtype)
        from deeplearning4j_tpu.runtime import aot, telemetry

        with telemetry.phase("weights_init"):
            self._params = self._init_params()

        fp = self.fingerprint()
        # the pool/slab buffers are donated on every backend: the step
        # updates them in place and the caller's old handles are dead.
        # This is what the chip executes — chip_smoke.py asserts on the
        # TPU that the pool handed to the first step is deleted and its
        # successor alive
        dec_don = (2, 3)
        pre_don = (4, 5)
        self._jit_decode = aot.cached_jit(
            self._decode_paged, entry="paged_decode", fingerprint=fp,
            donate_argnums=dec_don)
        self._jit_prefill = aot.cached_jit(
            self._prefill_paged, entry="paged_prefill", fingerprint=fp,
            donate_argnums=pre_don)
        # an inner jit with the layer index as an operand: the layers
        # of a prefill step are one trace and one lowering, which is
        # most of what an executable a chunk length costs set-up (XLA
        # inlines the calls: the compiled step is the same)
        self._prefill_layer = jax.jit(self._prefill_layer)
        self._jit_decode_dense = aot.cached_jit(
            self._decode_dense, entry="dense_decode", fingerprint=fp,
            donate_argnums=dec_don)
        self._jit_prefill_dense = aot.cached_jit(
            self._prefill_dense, entry="dense_prefill", fingerprint=fp,
            donate_argnums=pre_don)

    def attend_impl(self):
        """'pallas' or 'reference': which attention the step functions
        trace on this backend (ops.pallas_attention.paged_attention)."""
        from deeplearning4j_tpu.ops.pallas_attention import \
            paged_attention_impl

        return paged_attention_impl(self.page_size, self.n_heads,
                                    self.head_dim, self._compute_dtype)

    def fingerprint(self):
        """Config hash for the AOT cache key (explicit: this twin has
        no conf JSON for network_fingerprint to derive from)."""
        return ("causal-lm:"
                f"v{self.vocab}:d{self.d_model}:h{self.n_heads}:"
                f"L{self.n_layers}:ff{self.d_ff}:T{self.max_context}:"
                f"p{self.page_size}:{self._compute_dtype.name}:"
                f"s{self.seed}")

    def _init_params(self):
        rng = np.random.default_rng(self.seed)
        dt = self._compute_dtype

        def w(*shape):
            return jnp.asarray(
                (rng.standard_normal(shape) * 0.02).astype(np.float32),
                dt)

        layers = []
        for _ in range(self.n_layers):
            layers.append({
                "ln1": jnp.ones((self.d_model,), dt),
                "wq": w(self.d_model, self.d_model),
                "wk": w(self.d_model, self.d_model),
                "wv": w(self.d_model, self.d_model),
                "wo": w(self.d_model, self.d_model),
                "ln2": jnp.ones((self.d_model,), dt),
                "w1": w(self.d_model, self.d_ff),
                "w2": w(self.d_ff, self.d_model),
            })
        return {"emb": w(self.vocab, self.d_model),
                "pos": w(self.max_context, self.d_model),
                "lnf": jnp.ones((self.d_model,), dt),
                "layers": layers}

    # -- shared block pieces (traced inside the step functions) ----------
    def _qkv(self, lp, x):
        S = x.shape[0]
        q = (x @ lp["wq"]).reshape(S, self.n_heads, self.head_dim)
        k = (x @ lp["wk"]).reshape(S, self.n_heads, self.head_dim)
        v = (x @ lp["wv"]).reshape(S, self.n_heads, self.head_dim)
        return q, k, v

    def _mlp(self, lp, h):
        x = _rmsnorm(h, lp["ln2"])
        return h + jax.nn.gelu(x @ lp["w1"]) @ lp["w2"]

    def _logits(self, params, h):
        hn = _rmsnorm(h, params["lnf"])
        return jnp.dot(hn, params["emb"].T,
                       preferred_element_type=jnp.float32)

    # -- paged step functions (pure; jitted via runtime/aot) -------------
    def _decode_paged(self, params, tokens, kps, vps, bts, sls, prev_ids,
                      src):
        """One decode token per slot. tokens [S] i32 (last sampled),
        kps/vps [L, P, page, H, Dh] pools, bts [S, MP] block tables,
        sls [S] live KV length per slot (the new token's position).
        prev_ids [S] i32 are the ids an earlier step returned and src
        [S] i32 says where each slot's token comes from: ``prev_ids[j]``
        for src j >= 0, ``tokens`` for -1, so the scheduler can queue a
        step on the ids of one whose ids have not reached the host yet.
        Returns ((ids [S] i32, logits [S, V] fp32), kps', vps'): ids is
        the argmax of each logits row, the first of equal maxima as
        ``np.argmax`` takes it, so the scheduler can step a greedy slot
        on 4 bytes and fetch the rows behind the next step. Padded
        slots (sl=0, block table all null-page) write their garbage row
        into the null page — identical values for every padded row,
        never attended by a live slot — and their ids and logits rows
        are ignored by the scheduler's scatter."""
        S = tokens.shape[0]
        tokens = jnp.where(src >= 0, prev_ids[jnp.maximum(src, 0)], tokens)
        h = params["emb"][tokens] + params["pos"][sls]
        pages = bts[jnp.arange(S), sls // self.page_size]
        offs = sls % self.page_size
        from deeplearning4j_tpu.ops.pallas_attention import paged_attention

        for li, lp in enumerate(params["layers"]):
            x = _rmsnorm(h, lp["ln1"])
            q, k, v = self._qkv(lp, x)
            kps = kps.at[li, pages, offs].set(k)
            vps = vps.at[li, pages, offs].set(v)
            att = paged_attention(q[:, None], kps, vps, li, bts, sls + 1,
                                  sls)[:, 0]
            h = h + att.reshape(S, self.d_model) @ lp["wo"]
            h = self._mlp(lp, h)
        logits = self._logits(params, h)
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (ids, logits), kps, vps

    def _prefill_paged(self, params, tokens, t0, n_valid, kps, vps, bt):
        """One prompt chunk of n whole pages for ONE slot. tokens
        [C = n * page] i32 (zero-padded past n_valid), t0 = chunk offset
        (multiple of page_size), bt [MP] the slot's block table with the
        chunk's fresh pages already installed from t0//page on. Writes
        the chunk's K/V into those n pages (padded rows too — decode
        overwrites them before they are ever unmasked; a page the
        scheduler left at the null page would take its rows there, as
        padded decode slots' rows go) and attends the chunk causally
        over the table. t0//page + n never passes the table's end
        (prefill_plan). Returns (logits of row n_valid-1 [V] fp32,
        kps', vps')."""
        C = tokens.shape[0]
        zero = jnp.zeros((), t0.dtype)      # x64 mode: indices must
        pos = jax.lax.dynamic_slice(params["pos"], (t0, zero),
                                    (C, self.d_model))
        h = params["emb"][tokens] + pos
        page_ids = jax.lax.dynamic_slice(
            bt, (t0 // self.page_size,), (C // self.page_size,))
        L = jnp.reshape(t0 + n_valid, (1,))
        t0v = jnp.reshape(t0, (1,))
        for li, lp in enumerate(params["layers"]):
            h, kps, vps = self._prefill_layer(
                lp, jnp.asarray(li, jnp.int32), h, kps, vps, page_ids, bt,
                L, t0v)
        h_last = jax.lax.dynamic_index_in_dim(h, n_valid - 1, 0,
                                              keepdims=True)
        return self._logits(params, h_last)[0], kps, vps

    def _prefill_layer(self, lp, li, h, kps, vps, page_ids, bt, L, t0v):
        """Layer `li` (a traced int32) of a prefill chunk: the chunk's
        K/V into its pages `page_ids`, attention over the table `bt`,
        the block's two matmul halves. Returns (h', kps', vps')."""
        from deeplearning4j_tpu.ops.pallas_attention import paged_attention

        C, page = h.shape[0], self.page_size
        x = _rmsnorm(h, lp["ln1"])
        q, k, v = self._qkv(lp, x)
        # one in-place update a page: a scatter over the page ids could
        # not alias the donated pool
        for j in range(C // page):
            rows = slice(j * page, (j + 1) * page)
            kps = kps.at[li, page_ids[j]].set(k[rows])
            vps = vps.at[li, page_ids[j]].set(v[rows])
        att = paged_attention(q[None], kps, vps, li, bt[None], L, t0v)[0]
        h = h + att.reshape(C, self.d_model) @ lp["wo"]
        return self._mlp(lp, h), kps, vps

    # -- dense-cache twins (bench baseline + serial oracle) --------------
    def _as_pool(self, slab):
        """A dense slab [L, S, max_context, H, Dh] seen as a pool
        [L, S*MP, page, H, Dh]: slot s's page j is pool page s*MP + j
        (`_identity_tables`)."""
        L, S = slab.shape[:2]
        return slab.reshape(L, S * self.max_pages_per_slot, self.page_size,
                            self.n_heads, self.head_dim)

    def _identity_tables(self, S):
        MP = self.max_pages_per_slot
        return jnp.arange(S * MP, dtype=jnp.int32).reshape(S, MP)

    def _decode_dense(self, params, tokens, kcs, vcs, sls):
        """Dense-slab decode: kcs/vcs [L, S, max_context, H, Dh].
        Views the slab as pages and runs the SAME attention core, so
        a dense trajectory is bitwise comparable to the paged one."""
        S = tokens.shape[0]
        h = params["emb"][tokens] + params["pos"][sls]
        rows = jnp.arange(S)
        bts = self._identity_tables(S)
        from deeplearning4j_tpu.ops.pallas_attention import paged_attention

        for li, lp in enumerate(params["layers"]):
            x = _rmsnorm(h, lp["ln1"])
            q, k, v = self._qkv(lp, x)
            kcs = kcs.at[li, rows, sls].set(k)
            vcs = vcs.at[li, rows, sls].set(v)
            att = paged_attention(q[:, None], self._as_pool(kcs),
                                  self._as_pool(vcs), li, bts, sls + 1,
                                  sls)[:, 0]
            h = h + att.reshape(S, self.d_model) @ lp["wo"]
            h = self._mlp(lp, h)
        return self._logits(params, h), kcs, vcs

    def _prefill_dense(self, params, tokens, t0, n_valid, kcs, vcs,
                       slot):
        """Dense-slab chunked prefill for ONE slot (same chunking as
        the paged path, any length of prefill_plan — the oracle must
        take the same block steps)."""
        C = tokens.shape[0]
        zero = jnp.zeros((), t0.dtype)      # x64 mode: indices must
        pos = jax.lax.dynamic_slice(params["pos"], (t0, zero),
                                    (C, self.d_model))
        h = params["emb"][tokens] + pos
        L = jnp.reshape(t0 + n_valid, (1,))
        t0v = jnp.reshape(t0, (1,))
        bt = jax.lax.dynamic_index_in_dim(
            self._identity_tables(kcs.shape[1]), slot, 0, keepdims=True)
        from deeplearning4j_tpu.ops.pallas_attention import paged_attention

        for li, lp in enumerate(params["layers"]):
            x = _rmsnorm(h, lp["ln1"])
            q, k, v = self._qkv(lp, x)
            liv = jnp.asarray(li, t0.dtype)
            kcs = jax.lax.dynamic_update_slice(
                kcs, k[None, None], (liv, slot, t0, zero, zero))
            vcs = jax.lax.dynamic_update_slice(
                vcs, v[None, None], (liv, slot, t0, zero, zero))
            att = paged_attention(q[None], self._as_pool(kcs),
                                  self._as_pool(vcs), li, bt, L, t0v)[0]
            h = h + att.reshape(C, self.d_model) @ lp["wo"]
            h = self._mlp(lp, h)
        h_last = jax.lax.dynamic_index_in_dim(h, n_valid - 1, 0,
                                              keepdims=True)
        return self._logits(params, h_last)[0], kcs, vcs

    # -- cache builders ---------------------------------------------------
    def dense_cache(self, S):
        """Zeroed dense KV slabs for S slots — the residency baseline:
        S x max_context rows live on HBM regardless of load."""
        shape = (self.n_layers, int(S), self.max_context, self.n_heads,
                 self.head_dim)
        return (jnp.zeros(shape, self._compute_dtype),
                jnp.zeros(shape, self._compute_dtype))

    def dense_cache_bytes(self, S):
        """HBM the dense twin reserves for S slots (K and V)."""
        return (2 * self.n_layers * int(S) * self.max_context
                * self.n_heads * self.head_dim
                * self._compute_dtype.itemsize)


def dense_serial_trajectory(model, prompt, n_new, sampler, rng,
                            bucket=1):
    """The serial oracle: ONE sequence generated through the DENSE
    twin at a fixed slot bucket (live row 0, padding rows dead) —
    the prefill chunks of ``prefill_plan``, then one decode step per
    generated token, sampling with the caller's rng stream. Returns
    (tokens [n_new] int list, logits [n_new, V] fp32) — what the paged
    scheduler must reproduce bitwise for the same (seed, stream)
    within the same bucket."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    S = int(bucket)
    kcs, vcs = model.dense_cache(S)
    last = None
    for t0, n_valid, C in prefill_plan(prompt.shape[0], 0, model.page_size,
                                       model.max_pages_per_slot):
        chunk = np.zeros((C,), np.int32)
        chunk[:n_valid] = prompt[t0:t0 + n_valid]
        last, kcs, vcs = model._jit_prefill_dense(
            model._params, chunk, jnp.asarray(t0, jnp.int32),
            jnp.asarray(n_valid, jnp.int32), kcs, vcs,
            jnp.asarray(0, jnp.int32))
    tokens, logits = [], []
    logits.append(np.asarray(last))
    tokens.append(int(sampler(logits[-1], rng)))
    seq_len = int(prompt.shape[0])
    for _ in range(int(n_new) - 1):
        tok = np.zeros((S,), np.int32)
        tok[0] = tokens[-1]
        sls = np.zeros((S,), np.int32)
        sls[0] = seq_len
        out, kcs, vcs = model._jit_decode_dense(
            model._params, tok, kcs, vcs, sls)
        seq_len += 1
        logits.append(np.asarray(out)[0])
        tokens.append(int(sampler(logits[-1], rng)))
    return tokens, np.stack(logits, axis=0)
