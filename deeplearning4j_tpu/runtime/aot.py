"""AOT compilation + in-process executable cache.

Every train/inference program is ONE XLA executable; this module keeps
those executables addressable inside a process, in the spirit of
whole-program compilation (arXiv:1810.09868 — compile the WHOLE step
once, then reuse the executable). What survives the process is JAX's
own persistent compilation cache, placed by
``runtime/compile_cache.configure()``: it round-trips DONATED
executables on jaxlib 0.9.0 (CPU tier-1 and the TPU v5e smoke both
exercise it), so this layer keeps nothing on disk and cached
executables are the donated ones.

* ``ExecutableCache`` — an in-memory store of compiled XLA executables
  keyed by a content hash of everything that shapes the traced
  program: the network configuration JSON, the entry point, the
  abstract call signature (shapes/dtypes/shardings), the dtype-policy
  toggles, the weight-update/sharding mode, and the jax/jaxlib/package
  versions. Two networks with equal configs share ONE executable.

* ``cached_jit`` — a drop-in ``jax.jit`` replacement the network
  classes build their train/forward/loss steps with. With no cache
  enabled it IS the plain donated jit; with a session cache enabled
  every first call per signature goes key-lookup → hit-or-compile
  (``jit.lower().compile()``, donation included) and later calls
  dispatch straight to the compiled executable.

* ``precompile`` warm-start — ``network.precompile(...)`` (all three
  network types), ``ParallelWrapper.precompile(...)`` and
  ``ParallelInference.precompile(...)`` drive ``CachedJit.warm`` with
  example abstract arguments so serving processes and trainers hit the
  first real batch with a hot executable; ``CompileWatch`` proves a
  window compiled nothing.

* shape-bucket canonicalization — ``bucket_batch`` rounds request
  batch sizes up to a small fixed set of buckets so a serving tier
  compiles one executable per bucket, never one per request size; the
  bucket count is the retrace budget to hand RetraceSentinel
  (``sentinel_budget``).

Scope: single-process jax only (``jax.process_count() > 1`` disables
the cache).
"""

from __future__ import annotations

import hashlib
import os
import threading
import time

import jax
import numpy as np

from deeplearning4j_tpu.runtime import telemetry

__all__ = [
    "ExecutableCache", "CachedJit", "cached_jit", "compile_lowered",
    "enable", "disable", "session_cache", "ambient_fingerprint",
    "network_fingerprint", "samediff_fingerprint", "abstract_signature",
    "bucket_batch", "pad_batch", "sentinel_budget",
    "DEFAULT_BATCH_BUCKETS", "CompileWatch",
]

#: kill switch: DL4J_TPU_AOT=off ignores enable() entirely
AOT_ENV = "DL4J_TPU_AOT"


def _package_version():
    from deeplearning4j_tpu import __version__

    return __version__


_TM = None


def _tm():
    """Lazily-resolved AOT telemetry handles (runtime.telemetry): hits,
    misses and compile wall as registry instruments + trace spans, on
    top of the per-cache ``stats``/``seconds`` dicts the CLI reports."""
    global _TM
    if _TM is None:
        reg = telemetry.get_registry()
        _TM = {
            "reg": reg,
            "hits_mem": reg.counter(
                "dl4j_aot_cache_hits_total",
                "executable-cache hits by tier",
                labels=("tier",)).labels(tier="memory"),
            "misses": reg.counter(
                "dl4j_aot_cache_misses_total",
                "executable-cache misses (XLA compiles paid)"),
            "compile_s": reg.histogram(
                "dl4j_aot_compile_seconds",
                "XLA compile wall on a cache miss"),
        }
    return _TM


def _tm_compile(t0, key=None, entry=None):
    """Record one cache-miss compile that started at perf_counter t0."""
    tm = _tm()
    dt = time.perf_counter() - t0
    tm["misses"].inc()
    tm["compile_s"].observe(dt)
    # a compile paid inside a span() block (setup.warm) is its child
    tm["reg"].trace.add(
        "aot.compile", "compile", t0, dt,
        {"key": (key or "")[:16], "entry": entry or ""},
        parent=tm["reg"].current_span_id())
    return dt


# ----------------------------------------------------------------------
# fingerprints: everything that shapes the traced program
# ----------------------------------------------------------------------

def ambient_fingerprint():
    """Process-level facts that change the compiled program without
    appearing in any argument: versions, backend, device count, x64
    mode, and the module-global A/B toggles
    (loss/BN tail modes, pooling backward impl, attention windows) the
    bench flips — a cache hit across two of THESE states would replay
    the wrong program."""
    from deeplearning4j_tpu.nn import losses as _losses
    from deeplearning4j_tpu.nn import multilayer as _ml
    from deeplearning4j_tpu.ops import norm as _norm
    from deeplearning4j_tpu.ops import pallas_attention as _pattn
    from deeplearning4j_tpu.ops import pooling as _pooling

    return {
        "package": _package_version(),
        "jax": jax.__version__,
        "jaxlib": __import__("jaxlib").__version__,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "x64": bool(jax.config.jax_enable_x64),
        # the autotune-arbiter knobs (runtime/autotune.py): every value
        # the arbiter can flip lives in the key, so a tuned run and a
        # stock run can NEVER share an executable — flipping a knob is
        # a different program, not a warm hit
        "loss_tail": _losses._TAIL_MODE,
        "bn_tail": _norm._TAIL_MODE,
        "bn_epilogue": _norm._EPILOGUE,
        "maxpool_bwd": _pooling._BACKWARD_IMPL,
        "global_maxpool_bwd": _pooling._GLOBAL_MAXPOOL_BWD,
        "flash_bwd": _pattn._BWD_IMPL,
        "canon_staging": _ml._CANON_STAGING,
        "argmax_bwd_win": _pooling._ARGMAX_BWD_MAX_WINDOW,
        "flash_window": (_pattn._MIN_FLASH_SEQ, _pattn._BLOCKWISE_WINDOW,
                         _pattn._INTERPRET),
    }


def _sha(text):
    return hashlib.sha256(text.encode("utf-8")).hexdigest()


def network_fingerprint(net):
    """Stable content hash of a MultiLayerNetwork/ComputationGraph's
    traced-program identity: the config JSON (layers, updaters,
    frozen flags, remat policy, dtype — everything serde serializes)
    plus the pieces that live OUTSIDE the conf: the weight-update hook
    (ZeRO sharding changes the program and its mesh is not conf state)
    and the solver algo. Raises if the conf cannot serialize — callers
    treat that as "not cacheable", never as an error."""
    impl = getattr(net, "_update_impl", None)
    impl_desc = "none" if impl is None else (
        f"{type(impl).__name__}:{getattr(impl, 'axis', None)}:"
        f"{getattr(impl, 'min_shard_size', None)}:"
        f"{tuple(sorted(dict(getattr(impl, 'mesh', None).shape).items())) if getattr(impl, 'mesh', None) is not None else None}")
    return _sha("|".join([
        type(net).__name__,
        net.conf.toJson(),
        impl_desc,
        "solver" if getattr(net, "_solver", None) is not None else "sgd",
    ]))


def samediff_fingerprint(sd):
    """Structural hash of a SameDiff graph + its TrainingConfig: op
    list (names/inputs/outputs/attrs), variable table (name, type,
    dtype/shape of stored arrays — values ride as runtime arguments and
    do not bake into the program), loss variables, and the training
    config (updater + regularization) when set."""
    parts = [f"{o.opName}({','.join(o.inputs)})->"
             f"({','.join(o.outputs)}){sorted(o.kwargs.items())!r}"
             for o in sd._ops]
    for n in sorted(sd._vars):
        v = sd._vars[n]
        a = sd._arrays.get(n)
        parts.append(
            f"{n}:{v.variableType}:"
            f"{None if a is None else (tuple(a.shape), str(a.dtype))}:"
            f"{getattr(v, '_ph_shape', None)}:{getattr(v, '_ph_dtype', None)}")
    parts.append(f"loss={sd._loss_vars}")
    tc = sd._tc
    if tc is not None:
        from deeplearning4j_tpu.util import serde

        try:
            upd = serde.to_json(tc.updater)
        except Exception:  # fault-ok[FLT01]: the repr fallback IS the handling — any stable string works as a cache-key component, a serde failure only changes the key, never correctness
            upd = repr(vars(tc.updater)) if hasattr(tc.updater, "__dict__") \
                else repr(tc.updater)
        parts.append(f"tc:{upd}:{tc.l1}:{tc.l2}:{tc.weightDecay}:"
                     f"{tc.dataSetFeatureMapping}:{tc.dataSetLabelMapping}:"
                     f"{tc.lossVariables}")
    impl = getattr(sd, "_update_impl", None)
    parts.append("zero" if impl is not None else "dense")
    return _sha("|".join(parts))


def _leaf_sig(leaf):
    """Hashable per-leaf signature — (aval, sharding) OBJECT pairs for
    jax arrays (both hash/compare by value; no string building on the
    per-call hot path — stringification happens once per first-seen
    signature in _sig_repr). np/python leaves carry no sharding."""
    if isinstance(leaf, jax.Array):
        return (leaf.aval, leaf.sharding)
    if isinstance(leaf, np.ndarray):
        return (tuple(leaf.shape), str(leaf.dtype), None)
    if isinstance(leaf, jax.ShapeDtypeStruct):
        # normalize to the signature an equivalent CONCRETE array would
        # produce, so warm(ShapeDtypeStruct(...)) primes the same table/
        # cache entry the real call looks up (an SDS without an explicit
        # sharding matches the default single-device placement)
        from jax.core import ShapedArray
        from jax.sharding import SingleDeviceSharding

        sh = getattr(leaf, "sharding", None)
        if sh is None:
            sh = SingleDeviceSharding(jax.devices()[0])
        return (ShapedArray(leaf.shape, leaf.dtype), sh)
    # python scalar: jit would trace it weak-typed; keep the type in
    # the key so int/float streams don't collide
    return ("py", type(leaf).__name__)


def abstract_signature(args):
    """Hashable signature of a call's positional args: pytree structure
    + per-leaf (aval, sharding). The same function at the same
    signature lowers to the same program."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(_leaf_sig(leaf) for leaf in leaves))


def _sig_repr(sig):
    """Stable string form of a signature for the sha256 cache key —
    computed once per first-seen signature, never on the dispatch hot
    path."""
    if isinstance(sig, str):
        return sig
    treedef, leaf_sigs = sig
    parts = []
    for ls in leaf_sigs:
        parts.append(",".join(repr(c) for c in ls))
    return f"{treedef}|{';'.join(parts)}"


def cache_key(base_fp, entry, sig, ambient=None):
    """The cache key: sha256 over (ambient fingerprint, program
    fingerprint, entry-point name, abstract signature)."""
    amb = ambient if ambient is not None else ambient_fingerprint()
    return _sha("|".join([repr(sorted(amb.items())), base_fp, entry,
                          _sig_repr(sig)]))


# ----------------------------------------------------------------------
# the cache
# ----------------------------------------------------------------------

class ExecutableCache:
    """In-memory executable store: key -> jax.stages.Compiled, shared by
    every network in the process (N identical configs, 1 compile)."""

    def __init__(self):
        # serving threads drive get/put concurrently (every BATCHED
        # dispatch and every handler-thread first request lands here);
        # the stats counters are read-modify-write and the store is
        # check-then-insert, so both live under one lock (the THR01
        # audit, ISSUE 14). Reentrant: note_miss can fire under get.
        self._lock = threading.RLock()
        self._mem = {}
        self.stats = {"mem_hits": 0, "misses": 0, "puts": 0}
        #: key -> seconds of the compile; the CLI --precompile report
        #: reads this
        self.seconds = {}

    def note_miss(self, key=None, seconds=None):
        """Count one compile-path miss (and optionally its wall) — the
        lock-safe increment every caller that pays a compile uses
        (CachedJit, compile_lowered); bare `stats["misses"] += 1` from
        another thread would lose counts and CompileWatch proofs with
        them."""
        with self._lock:
            self.stats["misses"] += 1
            if key is not None and seconds is not None:
                self.seconds[key] = float(seconds)

    def get(self, key):
        """-> Compiled or None."""
        with self._lock:
            hit = self._mem.get(key)
            if hit is not None:
                self.stats["mem_hits"] += 1
        if hit is not None:
            _tm()["hits_mem"].inc()
        return hit

    def put(self, key, compiled):
        with self._lock:
            self._mem[key] = compiled
            self.stats["puts"] += 1


# ----------------------------------------------------------------------
# session cache
# ----------------------------------------------------------------------

_SESSION = None


def enable():
    """Turn on the process-wide session cache. Idempotent — re-enabling
    keeps the existing cache. Returns the ExecutableCache."""
    global _SESSION
    if _SESSION is None:
        _SESSION = ExecutableCache()
    return _SESSION


def disable():
    """Turn the session cache off (networks fall back to plain jit)."""
    global _SESSION
    _SESSION = None


def session_cache():
    """The active session cache or None. DL4J_TPU_AOT=off vetoes
    everything; multihost always disables (one process cannot speak
    for the others' executables)."""
    if os.environ.get(AOT_ENV, "").lower() in ("off", "0", "false"):
        return None
    if _SESSION is not None and jax.process_count() > 1:
        return None
    return _SESSION


def compile_lowered(lowered, key=None, cache=None, entry=None):
    """Compile a jax.stages.Lowered through a cache: a hit returns the
    stored executable, a miss pays lowered.compile() and stores it.
    With no cache this is exactly ``lowered.compile()``."""
    cache = cache if cache is not None else session_cache()
    if cache is None or key is None:
        return lowered.compile()
    compiled = cache.get(key)
    if compiled is None:
        t0 = time.perf_counter()
        compiled = lowered.compile()
        cache.note_miss(key, _tm_compile(t0, key, entry))
        cache.put(key, compiled)
    return compiled


# ----------------------------------------------------------------------
# CachedJit — the drop-in jit the network classes build steps with
# ----------------------------------------------------------------------

#: table sentinel: this signature failed through the AOT path once —
#: the plain jit owns it permanently (see CachedJit.__call__)
_BAD_ENTRY = object()


class CachedJit:
    """jit wrapper with an AOT fast path.

    Call behavior per invocation:
      * no session/pinned cache, or keyword args (static-arg paths), or
        an unfingerprintable owner -> the plain fallback jit, donation
        and all (exactly the pre-AOT behavior);
      * cache active -> signature lookup in the per-instance table; a
        first-seen signature computes the content key and goes through
        the cache (hit, or lower+compile of the same donated jit +
        store), then dispatches to the compiled executable.

    ``owner`` supplies the program fingerprint lazily (the conf JSON
    hash); ``extra`` folds caller context the fingerprint cannot see
    (e.g. a ParallelWrapper's mesh/compression mode) into the key.
    """

    def __init__(self, fn, owner=None, entry="step", extra="",
                 donate_argnums=(), fingerprint=None, **jit_kwargs):
        self._fn = fn
        self._owner = owner
        self._entry = entry
        self._extra = extra
        self._donate = tuple(donate_argnums or ())
        self._jit_kwargs = dict(jit_kwargs)
        self._fallback = jax.jit(fn, donate_argnums=self._donate,
                                 **jit_kwargs)
        self._table = {}
        self._fingerprint = fingerprint  # explicit > owner-derived
        self._fp_failed = False
        self._pinned_cache = None
        # identity of the owner's weight-update hook when the
        # fingerprint was derived: installing/removing the ZeRO hook
        # changes the traced program, so a change invalidates the
        # derived fingerprint + table (checked per call, id() cheap)
        self._seen_impl = object()
        # serving handler threads dispatch through ONE CachedJit
        # concurrently; the signature table is check-then-insert and a
        # first-seen signature pays an XLA compile, so the entry path
        # is single-flight PER SIGNATURE (the THR01/THR04 audit,
        # ISSUE 14): the table holds a threading.Event while a
        # signature's compile is in flight — a racing thread with the
        # SAME signature waits on it instead of duplicating the
        # compile, while warm traffic for other signatures keeps
        # flowing (the lock itself only guards table metadata, never
        # the compile). RLock: invalidate() may fire inside the locked
        # metadata path via the impl-change check.
        self._lock = threading.RLock()

    # -- key plumbing ----------------------------------------------------
    def pin_cache(self, cache):
        """Use this cache regardless of the session cache (precompile
        with an explicit cache pins it so later fit() calls keep
        hitting the same store)."""
        self._pinned_cache = cache
        return self

    def _cache(self):
        return self._pinned_cache if self._pinned_cache is not None \
            else session_cache()

    def _base_fp_locked(self):
        if self._fp_failed:
            return None
        if self._fingerprint is None:
            if self._owner is None:
                self._fp_failed = True
                return None
            try:
                self._fingerprint = network_fingerprint(self._owner)
            except Exception:  # fault-ok[FLT01]: _fp_failed IS the classification — dispatch consults it and routes every call to the plain-jit fallback instead of the cache
                self._fp_failed = True
                return None
        return self._fingerprint

    def invalidate(self):
        """Forget the derived fingerprint + signature table (the owner's
        program identity changed, e.g. a weight-update hook was
        installed)."""
        with self._lock:
            self._invalidate_locked()
        return self

    def _invalidate_locked(self):
        if self._owner is not None:
            self._fingerprint = None
        self._fp_failed = False
        self._table.clear()

    def _check_impl_locked(self):
        if self._owner is None:
            return
        cur = id(getattr(self._owner, "_update_impl", None))
        if cur != self._seen_impl:
            self._seen_impl = cur
            self._invalidate_locked()

    # -- dispatch --------------------------------------------------------
    def _entry_for(self, args, cache):
        """(table entry, served): the entry is (compiled, key), (None,
        None) where the call is not cacheable, or (_BAD_ENTRY, None);
        `served` says the signature was in the table already, so
        that nothing was compiled or looked up in the cache for it."""
        sig = abstract_signature(args)
        while True:
            with self._lock:
                self._check_impl_locked()
                ent = self._table.get(sig)
                if ent is None:
                    fp = self._base_fp_locked()
                    if fp is None:
                        return (None, None), False
                    marker = threading.Event()
                    self._table[sig] = marker   # we own this compile
                    break
                if not isinstance(ent, threading.Event):
                    return ent, True
                in_flight = ent
            # another thread is compiling THIS signature: wait outside
            # the lock, then re-read (its entry, or ownership if it
            # failed / the table was invalidated mid-compile). Bounded:
            # the owner's finally guarantees marker.set(), but a 1s
            # cap means a thread killed mid-compile (or a marker that
            # leaked through invalidate) degrades to a slow re-read
            # loop instead of a permanent wedge
            in_flight.wait(1.0)
        try:
            # the compile runs outside the lock — warm dispatches of
            # OTHER signatures are never stalled behind it
            key = cache_key(fp, self._entry + self._extra, sig)
            compiled = cache.get(key)
            if compiled is None:
                t0 = time.perf_counter()
                compiled = self._fallback.lower(*args).compile()
                cache.note_miss(key, _tm_compile(t0, key, self._entry))
                cache.put(key, compiled)
            ent = (compiled, key)
            with self._lock:
                if self._table.get(sig) is marker:
                    self._table[sig] = ent
            return ent, False
        except BaseException:
            with self._lock:
                if self._table.get(sig) is marker:
                    del self._table[sig]
            raise
        finally:
            marker.set()   # wake waiters either way; they re-read

    def __call__(self, *args, **kwargs):
        """Dispatch one call. A call served from the signature table
        records two spans on the registry's clock, both with ``entry``:
        ``aot.sign`` (the signature and the table lookup) and then
        ``aot.call`` (the executable's call, until it returns its
        futures). A first-seen signature records its ``aot.compile``
        where it pays one, and neither; with telemetry off no clock is
        read."""
        cache = self._cache()
        if cache is None or kwargs:
            return self._fallback(*args, **kwargs)
        reg = telemetry.get_registry() if telemetry.enabled() else None
        t0 = reg.clock() if reg is not None else None
        (ent, _key), served = self._entry_for(args, cache)
        if ent is None or ent is _BAD_ENTRY:
            return self._fallback(*args)
        t1 = reg.clock() if reg is not None else None
        try:
            out = ent(*args)
        except TypeError:
            # aval disagreement the signature didn't capture —
            # blacklist the entry so the plain jit owns this call
            # pattern from here on (no retry-per-call)
            with self._lock:
                self._table[abstract_signature(args)] = (_BAD_ENTRY, None)
            return self._fallback(*args)
        if reg is not None and served:
            t2 = reg.clock()
            parent = reg.current_span_id()
            reg.add_span("aot.sign", "compile", t0, t1 - t0,
                         parent=parent, entry=self._entry)
            reg.add_span("aot.call", "compile", t1, t2 - t1,
                         parent=parent, entry=self._entry)
        return out

    def warm(self, *args, cache=None):
        """Populate the cache + dispatch table for this signature
        WITHOUT executing (args may be ShapeDtypeStructs). Returns
        (key, status, seconds): status "warm" = served from cache,
        "cold" = compiled now, None = not cacheable."""
        if cache is not None:
            self.pin_cache(cache)
        c = self._cache()
        if c is None:
            c = self.pin_cache(enable())._cache()
        before = dict(c.stats)
        (ent, key), _ = self._entry_for(args, c)
        if ent is None or ent is _BAD_ENTRY:
            return None, None, 0.0
        status = "cold" if c.stats["misses"] > before["misses"] else "warm"
        return key, status, c.seconds.get(key, 0.0)

    # -- jit API passthrough --------------------------------------------
    def lower(self, *args, **kwargs):
        return self._fallback.lower(*args, **kwargs)

    def eval_shape(self, *args, **kwargs):
        return self._fallback.eval_shape(*args, **kwargs)

    @property
    def __wrapped__(self):
        return self._fn


def cached_jit(fn, owner=None, entry="step", extra="", donate_argnums=(),
               fingerprint=None, **jit_kwargs):
    """Build a CachedJit (see class docstring). Drop-in for
    ``jax.jit(fn, donate_argnums=..., **jit_kwargs)``."""
    return CachedJit(fn, owner=owner, entry=entry, extra=extra,
                     donate_argnums=donate_argnums,
                     fingerprint=fingerprint, **jit_kwargs)


# ----------------------------------------------------------------------
# warm-path proof
# ----------------------------------------------------------------------

class CompileWatch:
    """Context manager proving a region of code compiled nothing.

    Snapshots the cache's miss counter on entry and exposes the delta
    as ``.misses`` on exit — the warm-swap / serving-soak gate is built
    on it: after ``precompile()``, "zero request-path compiles" is
    ``CompileWatch().misses == 0`` over the whole serving window.
    Counts CACHE misses, i.e. every compile the AOT layer paid; code
    running outside the cache (fallback jit) is the RetraceSentinel's
    jurisdiction — use both for a complete proof (docs/SERVING.md).
    """

    def __init__(self, cache=None):
        self._explicit = cache
        self.misses = None

    def __enter__(self):
        self._cache = self._explicit if self._explicit is not None \
            else session_cache()
        if self._cache is None:
            raise RuntimeError(
                "CompileWatch needs an active executable cache "
                "(aot.enable() or an explicit cache) — with no cache "
                "there is no miss counter to prove warmth against")
        self._before = self._cache.stats["misses"]
        return self

    def __exit__(self, *exc):
        self.misses = self._cache.stats["misses"] - self._before
        return False

    def assert_no_compiles(self, context="watched region"):
        if self.misses is None:
            raise RuntimeError("assert_no_compiles before __exit__")
        if self.misses:
            raise RuntimeError(
                f"{context} paid {self.misses} compile(s) that a warm "
                "cache should have served — a cold executable reached "
                "the hot path (precompile the signature, or the key "
                "changed: see docs/COMPILE.md key anatomy)")
        return self


# ----------------------------------------------------------------------
# shape buckets
# ----------------------------------------------------------------------

#: serving-tier batch buckets: one executable per bucket, never one
#: per request size
DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)


def bucket_batch(n, buckets=DEFAULT_BATCH_BUCKETS):
    """Smallest bucket >= n; past the largest bucket, the next multiple
    of it (so compiles stay bounded: len(buckets) + overflow sizes)."""
    n = int(n)
    if n < 1:
        raise ValueError(f"batch size must be >= 1, got {n}")
    for b in buckets:
        if n <= b:
            return b
    top = max(buckets)
    return ((n + top - 1) // top) * top


def pad_batch(arr, bucket):
    """Zero-pad arr's leading (batch) axis up to `bucket` (host-side,
    numpy). Caller slices the surplus rows off the output."""
    arr = np.asarray(arr)
    pad = bucket - arr.shape[0]
    if pad < 0:
        raise ValueError(
            f"batch {arr.shape[0]} exceeds bucket {bucket}")
    if pad == 0:
        return arr
    return np.concatenate(
        [arr, np.zeros((pad,) + arr.shape[1:], arr.dtype)], axis=0)


def sentinel_budget(buckets=DEFAULT_BATCH_BUCKETS, entries=1):
    """The retrace budget a bucketized call site is allowed: one
    compile per bucket per entry point — hand to
    RetraceSentinel(max_compiles=...)."""
    return len(tuple(buckets)) * int(entries)
