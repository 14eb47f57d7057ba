"""Paged KV cache: fixed-size KV blocks on bounded HBM.

The dense serving cache reserves ``slots x max_context`` KV rows up
front, so HBM residency is paid for context nobody is using; the
vLLM/PagedAttention shape bounds it by the tokens actually alive:
the pool is ``num_pages`` fixed-size pages per layer, device-resident
(``[L, P, page, H, Dh]`` for K and V), and each slot maps logical KV
block j -> physical page through its **block table** row. The serving
step functions (nn/transformer.py) attend through
``ops.pallas_attention.paged_attention``: on the TPU, at shapes its
rule admits, the pallas kernels (``paged_flash_decode`` /
``paged_flash_prefill``) take the whole pools and fetch each live page
of a slot's table into VMEM once, and no page past its last live one;
elsewhere ``paged_attend`` runs on the tables gathered whole (the CPU
path and the reference; chip_smoke.py checks the kernels against it).
``page_size`` doubles as the kernels' block_k so paged attention
accumulates per head in the dense flash kernel's block order.

``PagedKVCache`` is the HOST-side manager plus the device pools:

* **allocation/free at step boundaries**: a free list over page ids
  (page 0 is the reserved null page padded slots point at — never
  allocated, never read: a zero-length slot masks every key).
  Exhaustion raises the typed ``KVCacheFullError`` (429 at the HTTP
  tier) — admission control, never a swallowed except or a hang.
* **copy-on-write prefix sharing**: ``register_prefix`` publishes a
  finished prompt's pages into an LRU registry (one refcount each);
  ``match_prefix`` lets a later request with the same prompt prefix
  adopt the full pages outright — full prompt pages are immutable
  after prefill, so sharing them is free — and an exact-prompt match
  also shares the partial tail page, which the first generated-token
  append then forks (``ensure_private``: device page copy + block-
  table rewrite). Registry entries are evicted LRU when the free list
  runs dry, BEFORE admission fails.
* the pools cross the jit boundary functionally: the model step
  functions take the pool arrays and return the updated ones (append
  is an in-graph ``.at[].set``); the cache just holds the live
  reference between steps.

Telemetry: ``dl4j_kv_pages_in_use{model}`` and
``dl4j_kv_prefix_shared_pages{model}`` gauges (docs/OBSERVABILITY.md).
Thread safety: guarded by the owning scheduler's step lock (the same
single-driver contract as the slot table) — not internally locked.

See docs/SERVING.md "Paged KV cache".
"""

from __future__ import annotations

from collections import OrderedDict, deque

import numpy as np

from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.runtime.chaos import fault_point, register_seam

__all__ = ["KVCacheFullError", "PagedKVCache"]

#: page-allocation chaos seam: fired on every alloc (and on the CoW
#: fork's copy-target alloc), so a ChaosPlan can exhaust/fail paging
#: exactly where production would (runtime/chaos.py)
PAGE_ALLOC_SEAM = register_seam("kv.page_alloc")


class KVCacheFullError(RuntimeError):
    """KV page pool exhausted: the request cannot be admitted (or a
    mid-generation append cannot be served) without evicting live
    state. Surfaces as HTTP 429 — backpressure, never a hang."""


class PagedKVCache:
    """Device-resident paged KV pool + host-side block-table manager
    (module docstring). One instance per PagedSequenceScheduler."""

    def __init__(self, *, n_layers, n_heads, head_dim, page_size,
                 num_pages, dtype=np.float32, model="kv"):
        import jax.numpy as jnp

        if int(num_pages) < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is the reserved null "
                f"page), got {num_pages}")
        if int(page_size) < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.dtype = jnp.dtype(dtype)
        self.model = str(model)
        shape = (self.n_layers, self.num_pages, self.page_size,
                 self.n_heads, self.head_dim)
        #: the live pool arrays; the model's jitted step functions
        #: consume and REPLACE these (functional update, optionally
        #: donated on TPU)
        self.k_pools = jnp.zeros(shape, self.dtype)
        self.v_pools = jnp.zeros(shape, self.dtype)
        self._free = deque(range(1, self.num_pages))
        self._peak = 0                    # most pages in use, see below
        self._ref = np.zeros((self.num_pages,), np.int32)
        self._ref[0] = 1                  # the null page, pinned
        #: prompt-token tuple -> list of page ids, LRU order
        self._prefixes = OrderedDict()
        reg = telemetry.get_registry()
        self._registry = reg
        lab = {"model": self.model}
        self._g_in_use = reg.gauge(
            "dl4j_kv_pages_in_use",
            "KV pool pages allocated (live slots + prefix registry)",
            labels=("model",)).labels(**lab)
        self._g_shared = reg.gauge(
            "dl4j_kv_prefix_shared_pages",
            "KV pool pages held by the copy-on-write prefix registry",
            labels=("model",)).labels(**lab)
        self._g_in_use.set(0)
        self._g_shared.set(0)

    # -- accounting ------------------------------------------------------
    @property
    def pages_in_use(self):
        """Allocated pages (null page excluded)."""
        return self.num_pages - 1 - len(self._free)

    def take_pages_peak(self):
        """Most pages in use since the last call (pages only rise in
        alloc(), which counts it) — the scheduler reads it once an
        iteration, so a peak between two outside polls of the gauge is
        not lost."""
        peak = self._peak
        self._peak = self.pages_in_use    # where the next count starts
        return peak

    @property
    def capacity(self):
        """Allocatable pages (null page excluded)."""
        return self.num_pages - 1

    def page_bytes(self):
        """HBM bytes one page costs across every layer, K and V."""
        return (2 * self.n_layers * self.page_size * self.n_heads
                * self.head_dim * self.dtype.itemsize)

    def bytes_in_use(self):
        """HBM attributable to live tokens: allocated pages x page
        cost — the paged side of the bench residency A/B (the pool
        arrays themselves are num_pages x that, but num_pages is the
        operator's bound, sized to live load, not slots x
        max_context)."""
        return self.pages_in_use * self.page_bytes()

    def pages_for(self, n_tokens):
        """Pages a sequence of n_tokens occupies."""
        return -(-int(n_tokens) // self.page_size)

    # -- allocation ------------------------------------------------------
    def alloc(self, n=1):
        """Take n pages off the free list (refcount 1 each). Evicts
        LRU prefix-registry entries first when short; raises the typed
        KVCacheFullError when live slots alone hold the pool."""
        n = int(n)
        fault_point("kv.page_alloc", n)
        while len(self._free) < n and self._prefixes:
            self._evict_lru_prefix()
        if len(self._free) < n:
            raise KVCacheFullError(
                f"KV pool exhausted: {n} page(s) requested, "
                f"{len(self._free)} free of {self.capacity} "
                f"(page_size={self.page_size})")
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        self._peak = max(self._peak, self.pages_in_use)
        self._g_in_use.set(self.pages_in_use)
        return pages

    def retain(self, pages):
        """Add one reference to each page (prefix adoption)."""
        for p in pages:
            self._ref[p] += 1

    def release(self, pages):
        """Drop one reference per page; pages at refcount 0 return to
        the free list (slot teardown / registry eviction)."""
        for p in pages:
            if p == 0:
                continue
            self._ref[p] -= 1
            if self._ref[p] <= 0:
                self._ref[p] = 0
                self._free.append(p)
        self._g_in_use.set(self.pages_in_use)

    def is_shared(self, page):
        return self._ref[page] > 1

    def ensure_private(self, page):
        """The copy-on-write fork: return a page safe to append into.
        Unshared pages come back unchanged; a shared page is copied
        into a fresh page on device (one .at[].set per pool) and the
        shared original keeps its other holders."""
        if not self.is_shared(page):
            return page
        new = self.alloc(1)[0]
        self.k_pools = self.k_pools.at[:, new].set(self.k_pools[:, page])
        self.v_pools = self.v_pools.at[:, new].set(self.v_pools[:, page])
        self.release([page])
        return new

    # -- copy-on-write prefix registry -----------------------------------
    def _shared_pages_total(self):
        return sum(len(e[0]) for e in self._prefixes.values())

    def _evict_lru_prefix(self):
        _, (pages, _) = self._prefixes.popitem(last=False)
        self.release(pages)
        self._g_shared.set(self._shared_pages_total())

    def register_prefix(self, tokens, pages, last_logits=None):
        """Publish a fully-prefilled prompt's pages for sharing. The
        registry holds one reference per page, so a finished slot's
        release never frees them; pages under the registry are COW-
        protected for the owner's own decode appends too (the tail
        page is forked on the first generated token). ``last_logits``
        (the prompt's final-position logits row) lets an EXACT-prompt
        adopter skip prefill entirely and still sample its first
        token."""
        key = tuple(int(t) for t in tokens)
        if not key or key in self._prefixes:
            return
        pages = list(pages)
        self.retain(pages)
        logits = None if last_logits is None else np.asarray(last_logits)
        self._prefixes[key] = (pages, logits)
        self._g_shared.set(self._shared_pages_total())

    def match_prefix(self, tokens):
        """Longest registered prompt that prefixes `tokens` ->
        (pages_to_adopt, shared_token_count, last_logits_or_None) with
        one reference taken per adopted page, or ([], 0, None). Full
        pages of the match are always adoptable (immutable after
        prefill); the partial tail page — and the stored last-position
        logits — only on an EXACT prompt match, where the adopter's
        appends land in the tail page: exactly the CoW fork case. The
        remainder of the prompt always starts on a page boundary, so
        chunked prefill resumes cleanly."""
        key = tuple(int(t) for t in tokens)
        best = None
        for rk in self._prefixes:
            if len(rk) <= len(key) and key[:len(rk)] == rk:
                if best is None or len(rk) > len(best):
                    best = rk
        if best is None:
            return [], 0, None
        pages, logits = self._prefixes[best]
        self._prefixes.move_to_end(best)          # LRU touch
        exact = len(best) == len(key)
        n_full = len(best) // self.page_size
        if exact and logits is not None:
            shared = list(pages)
            n_tokens = len(best)
        else:
            # no stored logits -> treat an exact match like a partial
            # one (re-prefill the tail) so the first token is sampleable
            shared = list(pages[:n_full])
            n_tokens = n_full * self.page_size
            logits = None
            if n_tokens >= len(key):
                # the whole prompt would be adopted with no logits to
                # sample from: hold back the last page so prefill has
                # >= 1 token left to run
                shared = shared[:-1]
                n_tokens -= self.page_size
        if not shared:
            return [], 0, None
        self.retain(shared)
        return shared, n_tokens, logits

    # -- lifecycle -------------------------------------------------------
    def close(self):
        """Release the registry and this instance's gauge series."""
        while self._prefixes:
            self._evict_lru_prefix()
        for metric in ("dl4j_kv_pages_in_use",
                       "dl4j_kv_prefix_shared_pages"):
            fam = self._registry.get(metric)
            if fam is not None:
                fam.remove(model=self.model)
        return self
