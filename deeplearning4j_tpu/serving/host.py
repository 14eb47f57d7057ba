"""Multi-model host: name -> served version, with rolling swap.

Each registered model is a ``ServedModel``: the network, its dtype /
quantization policy, its batch buckets, and a BATCHED-mode
``ParallelInference`` (bounded queue + micro-batcher + per-bucket AOT
executable cache). Registration precompiles every bucket, so the first
real request of a model's life is served by a hot executable.

Rolling swap (``swap``): the replacement version is built and its
executables are WARMED while the current version keeps serving; only
then is the routing entry replaced (an atomic assignment under the
host lock), and the old version drains its already-queued requests
through its own hot executables. The request path never sees a cold
compile and never sees a gap — the /healthz the HTTP tier reports
stays ready throughout (docs/SERVING.md "Rolling swap").
"""

from __future__ import annotations

import threading
import time

import numpy as np

__all__ = ["ServedModel", "ServedSequenceModel", "ModelHost"]


class ServedModel:
    """One (name, version) entry: network + policy + its BATCHED-mode
    ParallelInference. Build through ModelHost.register/swap."""

    def __init__(self, name, version, network, mesh=None,
                 batchBuckets=None, int8=False, queueLimit=64,
                 maxWaitMs=2.0, clock=None):
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        self.name = str(name)
        self.version = int(version)
        self.network = network
        self.int8 = bool(int8)
        self.pi = ParallelInference(
            network, mesh=mesh, batchBuckets=batchBuckets,
            inferenceMode="BATCHED", queueLimit=queueLimit,
            maxWaitMs=maxWaitMs, int8=int8, clock=clock,
            metricsName=f"{self.name}:v{self.version}")

    @property
    def batcher(self):
        return self.pi._ensure_batcher()

    def warm(self, cache=None):
        """Precompile every batch bucket (hits are free). Returns the
        per-bucket {key, status, seconds} report."""
        return self.pi.precompile(cache=cache)

    def submit(self, features, deadline_s=None, wait=True):
        """Queue one request (features [rows, ...]) and block for its
        sliced result. deadline_s bounds the WHOLE request (queue wait
        + dispatch): expiry raises DeadlineExceededError whether the
        request was still queued or the dispatcher is busy. May raise
        QueueFullError (backpressure). wait=False returns the
        InferenceRequest at enqueue — the fleet's hedged-dispatch
        handle (serving/fleet.py)."""
        from deeplearning4j_tpu.runtime.chaos import fault_point

        b = self.batcher
        features = fault_point("host.submit", features)
        deadline = None if deadline_s is None else \
            b.clock() + float(deadline_s)
        return b.submit(features, deadline=deadline, wait=wait,
                        timeout=deadline_s)

    def policy(self):
        """The policy row the multi-model table reports."""
        import jax.numpy as jnp

        return {
            "model": self.name,
            "version": self.version,
            "dtype": jnp.dtype(self.network._compute_dtype).name,
            "int8": self.int8,
            "batchBuckets": list(self.pi.batchBuckets or ()),
            "queueLimit": self.pi.queueLimit,
            "maxWaitMs": self.pi.maxWaitMs,
            "exampleShape": list(self.pi.example_shape() or ()),
            "mesh": dict(
                (k, int(v)) for k, v in self.pi.mesh.shape.items()),
        }

    def close(self, drain=True):
        self.pi.close(drain=drain)
        return self


class ServedSequenceModel:
    """One (name, version) SEQUENCE entry: network + its iteration-
    level slot scheduler (serving/sequence.py). A network with
    ``kind == "paged_lm"`` (nn/transformer.py) is served behind the
    KV-slot ``PagedSequenceScheduler`` instead of the h/c carry
    scheduler — token prompts in, sampled tokens out, KV on a bounded
    paged pool. Build through ModelHost.register_sequence/
    swap_sequence."""

    def __init__(self, name, version, network, slotBuckets=None,
                 queueLimit=64, feedback=None, clock=None,
                 numPages=64, sampler=None, samplerSeed=0,
                 prefixSharing=True):
        from deeplearning4j_tpu.serving.sequence import (
            PagedSequenceScheduler, SequenceScheduler,
        )

        self.name = str(name)
        self.version = int(version)
        self.network = network
        self.paged = getattr(network, "kind", None) == "paged_lm"
        if self.paged:
            self.scheduler = PagedSequenceScheduler(
                network, num_pages=numPages, slot_buckets=slotBuckets,
                queue_limit=queueLimit, sampler=sampler,
                sampler_seed=samplerSeed, prefix_sharing=prefixSharing,
                clock=clock, start_thread=clock is None,
                name=f"{self.name}:v{self.version}")
        else:
            self.scheduler = SequenceScheduler(
                network, slot_buckets=slotBuckets,
                queue_limit=queueLimit, feedback=feedback, clock=clock,
                start_thread=clock is None,
                name=f"{self.name}:v{self.version}")

    def warm(self, cache=None):
        """Precompile the decode step for every slot bucket."""
        return self.scheduler.warm(cache=cache)

    def _submit(self, payload, deadline_s, wait, timeout, **kw):
        """Both submit paths' way into the scheduler: the host's fault
        point, a deadline relative to now on the scheduler's clock, and
        a caller's wait of `timeout`, by default the deadline's span."""
        from deeplearning4j_tpu.runtime.chaos import fault_point

        sched = self.scheduler
        payload = fault_point("host.submit_sequence", payload)
        deadline = None if deadline_s is None else \
            sched.clock() + float(deadline_s)
        return sched.submit(payload, deadline=deadline, wait=wait,
                            timeout=deadline_s if timeout is None
                            else timeout, **kw)

    def submit(self, features, deadline_s=None, extra_steps=0,
               wait=True, timeout=None):
        if self.paged:
            raise ValueError(
                f"model {self.name!r} is a paged token model — use "
                "generate()/submit_tokens() with a token prompt")
        return self._submit(features, deadline_s, wait, timeout,
                            extra_steps=extra_steps)

    def submit_tokens(self, tokens, deadline_s=None, max_new_tokens=1,
                      wait=True, timeout=None):
        """Queue one token prompt on the paged scheduler (the
        :generate token path). Same deadline/wait contract as
        submit()."""
        if not self.paged:
            raise ValueError(
                f"model {self.name!r} serves per-step features, not "
                "token prompts — use submit()")
        return self._submit(tokens, deadline_s, wait, timeout,
                            max_new_tokens=max_new_tokens)

    def policy(self):
        import jax.numpy as jnp

        pol = {
            "model": self.name,
            "version": self.version,
            "kind": "sequence",
            "dtype": jnp.dtype(self.network._compute_dtype).name,
            "slotBuckets": list(self.scheduler.slot_buckets),
            "queueLimit": self.scheduler.queue_limit,
        }
        if self.paged:
            cache = self.scheduler.cache
            pol.update({
                "paged": True,
                "vocab": self.scheduler.vocab,
                "maxContext": self.network.max_context,
                "pageSize": cache.page_size,
                "numPages": cache.num_pages,
                "prefixSharing": self.scheduler.prefix_sharing,
            })
        else:
            pol["featureSize"] = self.scheduler.feature_size
        return pol

    def close(self, drain=True):
        self.scheduler.close(drain=drain)
        return self


class ModelHost:
    """name -> ServedModel routing table (module docstring), plus a
    parallel table of sequence (iteration-level) models — one host =
    one serving process's worth of models; serving/fleet.py stacks N
    hosts behind a router."""

    def __init__(self, mesh=None, clock=None):
        self._mesh = mesh
        self._clock = clock
        self._models = {}
        self._sequences = {}        # name -> ServedSequenceModel
        self._registering = set()   # names reserved mid-register
        self._lock = threading.Lock()

    # -- registration / swap --------------------------------------------
    def register(self, name, network, *, batchBuckets=None, int8=False,
                 queueLimit=64, maxWaitMs=2.0, precompile=True):
        """Serve `network` as `name` (version 1). precompile=True (the
        production default) warms every bucket executable before the
        model is routable."""
        with self._lock:
            if name in self._models or name in self._sequences \
                    or name in self._registering:
                raise ValueError(
                    f"model {name!r} is already registered — use "
                    "swap() to roll a new version")
            # reserved so a concurrent register() of the same name
            # raises instead of silently overwriting the loser
            self._registering.add(name)
        try:
            sm = ServedModel(name, 1, network, mesh=self._mesh,
                             batchBuckets=batchBuckets, int8=int8,
                             queueLimit=queueLimit, maxWaitMs=maxWaitMs,
                             clock=self._clock)
            report = sm.warm() if precompile else None
            with self._lock:
                self._models[name] = sm
        finally:
            with self._lock:
                self._registering.discard(name)
        return {"model": name, "version": sm.version, "warm": report}

    def swap(self, name, network, **overrides):
        """Rolling swap to a new version of `name`.

        Sequence: (1) build the replacement with the current policy
        (override any knob by keyword), (2) WARM its bucket executables
        while the current version keeps serving, (3) install it
        atomically, (4) drain the old version — requests already queued
        complete on the version they were enqueued against, through its
        own hot executables. No cold compile ever lands on the request
        path and no request is dropped.
        """
        with self._lock:
            old = self._models.get(name)
            if old is None:
                raise KeyError(
                    f"unknown model {name!r}: register() it first "
                    f"(registered: {sorted(self._models)})")
        pol = old.policy()
        kw = {"batchBuckets": tuple(pol["batchBuckets"]) or None,
              "int8": pol["int8"], "queueLimit": pol["queueLimit"],
              "maxWaitMs": pol["maxWaitMs"]}
        kw.update(overrides)
        new = ServedModel(name, old.version + 1, network,
                          mesh=self._mesh, clock=self._clock, **kw)
        t0 = time.perf_counter()
        report = new.warm()          # old version is still serving
        warm_s = time.perf_counter() - t0
        with self._lock:
            self._models[name] = new  # atomic routing flip
        old.close(drain=True)         # queued requests finish on OLD
        return {"model": name, "version": new.version,
                "warm": report, "warm_s": round(warm_s, 3)}

    # -- sequence (iteration-level) models -------------------------------
    def register_sequence(self, name, network, *, slotBuckets=None,
                          queueLimit=64, feedback=None, precompile=True,
                          numPages=64, sampler=None, samplerSeed=0,
                          prefixSharing=True):
        """Serve a recurrent `network` as the SEQUENCE model `name`
        (version 1) behind an iteration-level slot scheduler
        (serving/sequence.py) — or, for a ``kind == "paged_lm"``
        network, the KV-slot paged scheduler (numPages/sampler/
        samplerSeed/prefixSharing apply there; feedback applies only to
        the carry path). precompile=True warms the decode-step
        executable for every slot bucket before the model is
        routable."""
        with self._lock:
            if name in self._models or name in self._sequences \
                    or name in self._registering:
                raise ValueError(
                    f"model {name!r} is already registered — use "
                    "swap_sequence() to roll a new version")
            self._registering.add(name)
        try:
            sm = ServedSequenceModel(name, 1, network,
                                     slotBuckets=slotBuckets,
                                     queueLimit=queueLimit,
                                     feedback=feedback,
                                     clock=self._clock,
                                     numPages=numPages, sampler=sampler,
                                     samplerSeed=samplerSeed,
                                     prefixSharing=prefixSharing)
            try:
                report = sm.warm() if precompile else None
            except Exception:
                # the ctor already started the scheduler thread and
                # registered telemetry series — a failed warm must not
                # leak either
                sm.close(drain=False)
                raise
            with self._lock:
                self._sequences[name] = sm
        finally:
            with self._lock:
                self._registering.discard(name)
        return {"model": name, "version": sm.version, "warm": report}

    def swap_sequence(self, name, network, **overrides):
        """Rolling swap of a sequence model: build + WARM the new
        version's slot-bucket executables while the current one keeps
        stepping, flip atomically, drain the old scheduler (sequences
        already admitted or queued finish on the version they were
        enqueued against)."""
        with self._lock:
            old = self._sequences.get(name)
            if old is None:
                raise KeyError(
                    f"unknown sequence model {name!r}: "
                    "register_sequence() it first (registered: "
                    f"{sorted(self._sequences)})")
        pol = old.policy()
        kw = {"slotBuckets": tuple(pol["slotBuckets"]) or None,
              "queueLimit": pol["queueLimit"]}
        if old.paged:
            kw.update({"numPages": pol["numPages"],
                       "sampler": old.scheduler.sampler,
                       "samplerSeed": old.scheduler.sampler_seed,
                       "prefixSharing": pol["prefixSharing"]})
        else:
            kw["feedback"] = old.scheduler.feedback
        kw.update(overrides)
        new = ServedSequenceModel(name, old.version + 1, network,
                                  clock=self._clock, **kw)
        t0 = time.perf_counter()
        try:
            report = new.warm()       # old version keeps stepping
        except Exception:
            new.close(drain=False)    # old version stays routed
            raise
        warm_s = time.perf_counter() - t0
        with self._lock:
            self._sequences[name] = new   # atomic routing flip
        old.close(drain=True)
        return {"model": name, "version": new.version,
                "warm": report, "warm_s": round(warm_s, 3)}

    def sequence_model(self, name):
        with self._lock:
            sm = self._sequences.get(name)
            registered = sorted(self._sequences)
        if sm is None:
            raise KeyError(
                f"unknown sequence model {name!r} (registered: "
                f"{registered})")
        return sm

    def submit_sequence(self, name, features, deadline_s=None,
                        extra_steps=0, wait=True, timeout=None):
        """Route one sequence ([T, F] per-step features) to `name`'s
        slot scheduler. Same swap re-route contract as submit(): a
        request losing the resolve/enqueue race against a
        swap_sequence lands on the new version, never a 5xx."""
        from deeplearning4j_tpu.serving.queue import ServingClosedError

        feats = np.asarray(features)
        try:
            return self.sequence_model(name).submit(
                feats, deadline_s=deadline_s, extra_steps=extra_steps,
                wait=wait, timeout=timeout)
        except ServingClosedError:
            return self.sequence_model(name).submit(
                feats, deadline_s=deadline_s, extra_steps=extra_steps,
                wait=wait, timeout=timeout)

    def generate(self, name, tokens, deadline_s=None, max_new_tokens=1,
                 wait=True, timeout=None):
        """Route one token prompt to `name`'s PAGED sequence scheduler
        (:generate with a "tokens" body). Same swap re-route contract
        as submit_sequence."""
        from deeplearning4j_tpu.serving.queue import ServingClosedError

        toks = np.asarray(tokens)
        try:
            return self.sequence_model(name).submit_tokens(
                toks, deadline_s=deadline_s,
                max_new_tokens=max_new_tokens, wait=wait,
                timeout=timeout)
        except ServingClosedError:
            return self.sequence_model(name).submit_tokens(
                toks, deadline_s=deadline_s,
                max_new_tokens=max_new_tokens, wait=wait,
                timeout=timeout)

    def queued_work(self, name):
        """Outstanding work this host holds for `name` — one-shot
        requests queued OR inside a running dispatch (a wedged batch
        must read as load, not idleness), or queue depth + live slots
        for a sequence model; None when the model is not served here.
        The fleet router's least-loaded ranking key (a point-in-time
        read)."""
        with self._lock:
            sm = self._models.get(name)
            seq = self._sequences.get(name)
        if sm is not None:
            b = sm.pi._batcher  # thread-ok[THR01]: atomic reference read — an idle model (no batcher yet) just reports 0
            return 0 if b is None else b.outstanding
        if seq is not None:
            return seq.scheduler.depth + seq.scheduler.active_slots
        return None

    def kind(self, name):
        """'oneshot' | 'sequence' | None when `name` is not served
        here — the fleet's swap_all dispatch key."""
        with self._lock:
            if name in self._models:
                return "oneshot"
            if name in self._sequences:
                return "sequence"
        return None

    # -- request path ---------------------------------------------------
    def model(self, name):
        with self._lock:
            sm = self._models.get(name)
        if sm is None:
            raise KeyError(
                f"unknown model {name!r} (registered: "
                f"{sorted(self.names())})")
        return sm

    def submit(self, name, features, deadline_s=None, wait=True):
        """Route one request. Once ENQUEUED, a request completes on the
        version it was enqueued against even if a swap lands mid-flight
        (the drain contract). A request that instead loses the
        resolve/enqueue race against a swap — the old version closed
        between routing and enqueue — is transparently re-routed to the
        new version: a rolling swap must never surface as a 5xx.
        wait=False returns the InferenceRequest at enqueue (the swap
        re-route still covers the ENQUEUE race; the returned handle
        then completes on its version)."""
        from deeplearning4j_tpu.serving.queue import ServingClosedError

        feats = np.asarray(features)
        try:
            return self.model(name).submit(feats, deadline_s=deadline_s,
                                           wait=wait)
        except ServingClosedError:
            return self.model(name).submit(feats, deadline_s=deadline_s,
                                           wait=wait)

    # -- introspection / lifecycle --------------------------------------
    def names(self):
        with self._lock:
            return sorted(self._models) + sorted(self._sequences)

    def __contains__(self, name):
        with self._lock:
            return name in self._models or name in self._sequences

    def describe(self):
        """The multi-model policy table (docs/SERVING.md); sequence
        models ride along with ``"kind": "sequence"`` rows."""
        with self._lock:
            models = list(self._models.values())
            seqs = list(self._sequences.values())
        table = {sm.name: sm.policy() for sm in models}
        table.update({sm.name: sm.policy() for sm in seqs})
        return table

    def metrics_snapshot(self):
        """One JSON-safe observability snapshot: the process-wide
        registry (training + serving + AOT instruments, the same data
        /metrics exposes) plus a per-served-model serving view (queue
        stats, depth, occupancy). The programmatic twin of
        ``GET /metrics`` (docs/OBSERVABILITY.md).

        Schema: the PR 13 keys (``registry``, ``models``) are stable —
        bench.py consumes them unchanged; the fleet view is ADDITIVE:
        ``sequences`` (per sequence model: queue depth + live slots +
        slot-occupancy summary, the per-replica row
        serving/fleet.py aggregates)."""
        from deeplearning4j_tpu.runtime import telemetry

        with self._lock:
            models = list(self._models.values())
            seqs = list(self._sequences.values())
        per_model = {}
        for sm in models:
            # a snapshot is a READ: never build the lazy batcher (that
            # would spawn its scheduler thread, or raise on a closed
            # instance racing a swap) — an idle model reports as such
            b = sm.pi._batcher
            if b is None:
                per_model[sm.name] = {"version": sm.version,
                                      "stats": None, "queue_depth": 0,
                                      "occupancy": {"dispatches": 0,
                                                    "mean_occupancy":
                                                        None,
                                                    "histogram": {}}}
                continue
            per_model[sm.name] = {
                "version": sm.version,
                "stats": dict(b.stats),
                "queue_depth": b.depth,
                "occupancy": b.occupancy_summary(),
            }
        per_seq = {}
        for sm in seqs:
            sched = sm.scheduler
            per_seq[sm.name] = {
                "version": sm.version,
                "stats": dict(sched.stats),
                "queue_depth": sched.depth,
                "active_slots": sched.active_slots,
                "slot_occupancy": sched.occupancy_summary(),
            }
        return {"registry": telemetry.get_registry().snapshot(),
                "models": per_model,
                "sequences": per_seq}

    def warm_all(self):
        """(Re)warm every registered model (one-shot AND sequence) —
        the HTTP tier's /healthz warmup hook: cache hits are cheap, so
        gating readiness on this is safe even when registration
        already precompiled."""
        with self._lock:
            models = list(self._models.values())
            seqs = list(self._sequences.values())
        out = {sm.name: sm.warm() for sm in models}
        out.update({sm.name: sm.warm() for sm in seqs})
        return out

    def close(self, drain=True):
        with self._lock:
            models = list(self._models.values())
            seqs = list(self._sequences.values())
            self._models.clear()
            self._sequences.clear()
        for sm in models:
            sm.close(drain=drain)
        for sm in seqs:
            sm.close(drain=drain)
