"""Resilient training runtime: retry, resume, and non-finite guards.

Reference: production TPU-pod training treats host preemption, flaky
data sources and numeric blow-ups as ROUTINE (TensorFlow's distributed
runtime is built around recoverable checkpointed workers — Abadi et
al.; the reference stack's analogues are CheckpointListener,
EarlyStoppingTrainer's exception hooks and FailureTestingListener).
This module is that layer for the jax_graft build, three cooperating
pieces:

* RetryPolicy / retry() — capped exponential backoff with DETERMINISTIC
  seeded jitter, shared by the data path (RetryingDataSetIterator,
  ResilientFit's batch fetch) and checkpoint I/O.
* ResilientFit — wraps MultiLayerNetwork / ParallelWrapper training
  with periodic ATOMIC checkpoints (util.sharded_checkpoint), automatic
  resume-from-latest on restart, and an on-device non-finite step guard:
  a step whose loss or updated parameters contain NaN/Inf is SKIPPED
  (params/updater/state keep their pre-step values — selected inside
  the jitted step, so donation stays safe and no host-side rewind copy
  is ever made) and training aborts with a clear error after K
  consecutive bad steps.
* FaultInjector — a deterministic, seedable fault-injection harness
  (raise-on-Nth-batch IOError, poison-NaN step, kill-after-step
  preemption) that tests and bench.py thread through the data iterators
  and the train step.

The guard's skip decision costs one extra all-finite reduction per
step and rides the loss fetch the training loop already pays — no
additional host sync.
"""

from __future__ import annotations

import os
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.util import sharded_checkpoint as _ckpt

_TM = None


def _tm():
    """Lazily-resolved resilience telemetry handles (runtime.telemetry;
    see docs/OBSERVABILITY.md). Event COUNTS (skips, saves, restores)
    are the MetricsListener's job — the direct wiring here carries only
    what the listener chain cannot see: retry fire counts and
    checkpoint I/O durations."""
    global _TM
    if _TM is None:
        from deeplearning4j_tpu.runtime import telemetry

        reg = telemetry.get_registry()
        _TM = {
            "reg": reg,
            "retries": reg.counter(
                "dl4j_retries_total",
                "transient failures retried with backoff (data fetch, "
                "checkpoint I/O)"),
            "ckpt_save_s": reg.histogram(
                "dl4j_checkpoint_save_seconds",
                "atomic checkpoint write wall (ResilientFit._save)"),
            "ckpt_restore_s": reg.histogram(
                "dl4j_checkpoint_restore_seconds",
                "checkpoint restore wall (resume-after-preemption)"),
        }
    return _TM


# ----------------------------------------------------------------------
# retry with capped exponential backoff + deterministic jitter
# ----------------------------------------------------------------------
class RetryPolicy:
    """Capped exponential backoff. attempt k (1-based) sleeps

        base_k = min(maxDelay, initialDelay * multiplier**(k-1))
        delay_k in [base_k * (1 - jitter), base_k]

    with the jitter fraction drawn from random.Random(seed) — the SAME
    seed replays the SAME delay sequence, so backoff behavior is exactly
    testable (no wall-clock flakiness in the fault matrix).
    """

    def __init__(self, maxRetries: int = 3, initialDelay: float = 0.05,
                 maxDelay: float = 2.0, multiplier: float = 2.0,
                 jitter: float = 0.5, seed: int = 0,
                 retryOn=(IOError, OSError, TimeoutError), sleep=time.sleep):
        if not 0.0 <= jitter <= 1.0:
            raise ValueError(f"jitter must be in [0, 1], got {jitter}")
        self.maxRetries = int(maxRetries)
        self.initialDelay = float(initialDelay)
        self.maxDelay = float(maxDelay)
        self.multiplier = float(multiplier)
        self.jitter = float(jitter)
        self.seed = int(seed)
        self.retryOn = tuple(retryOn)
        self.sleep = sleep

    def delay(self, attempt: int, rng: random.Random) -> float:
        base = min(self.maxDelay,
                   self.initialDelay * self.multiplier ** (attempt - 1))
        return base * (1.0 - self.jitter * rng.random())

    def delays(self):
        """The full deterministic delay sequence this policy would sleep
        (one fresh rng, as retry() uses) — for tests and capacity math."""
        rng = random.Random(self.seed)
        return [self.delay(k, rng) for k in range(1, self.maxRetries + 1)]


def retry(fn, policy: RetryPolicy = None, on_retry=None):
    """Call fn(); on an exception in policy.retryOn, back off and retry
    up to policy.maxRetries times, then re-raise the last error.
    on_retry(attempt, exc, delay) observes each backoff (listener /
    logging hook)."""
    policy = policy or RetryPolicy()
    rng = random.Random(policy.seed)
    attempt = 0
    while True:
        try:
            return fn()
        except policy.retryOn as e:
            attempt += 1
            if attempt > policy.maxRetries:
                raise
            d = policy.delay(attempt, rng)
            _tm()["retries"].inc()
            if on_retry is not None:
                on_retry(attempt, e, d)
            policy.sleep(d)


# ----------------------------------------------------------------------
# deterministic fault injection
# ----------------------------------------------------------------------
class Preemption(Exception):
    """Simulated host preemption: the 'process' dies here. Emitted by
    FaultInjector.killAfterStep so tests can kill training mid-epoch and
    restart through ResilientFit's resume-from-latest path."""


class FaultInjector:
    """Deterministic, seedable fault schedule threaded through the data
    iterators (wrapIterator) and the train step (ResilientFit hooks).

    Faults are scheduled explicitly — failOnBatch / poisonStep /
    killAfterStep — or drawn reproducibly from the seed
    (randomIOFaults). Every injection is recorded in .events as
    (kind, position) tuples so tests assert on exactly what fired.

    Scope: the TRAINING data path only. The process-wide
    generalization — seeded fault schedules against named seams at
    every SERVING dispatch boundary (and this module's checkpoint
    write/restore) — is ``runtime.chaos.ChaosPlan``
    (docs/RESILIENCE.md "Chaos harness").
    """

    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self.events = []
        self._io_faults = {}     # global batch index -> [times left, exc]
        self._poison_steps = set()
        self._kill_after = None
        self._killed = False

    # ----- scheduling -------------------------------------------------
    def failOnBatch(self, n: int, times: int = 1, exc=None):
        """Raise `exc` (default IOError) from the wrapped iterator's
        next() for the n-th batch (0-based, counted across epochs),
        `times` consecutive attempts before that fetch succeeds."""
        self._io_faults[int(n)] = [int(times),
                                   exc if exc is not None
                                   else IOError(f"injected data fault at "
                                                f"batch {n}")]
        return self

    def randomIOFaults(self, nBatches: int, rate: float, times: int = 1):
        """Schedule IOErrors on a seed-deterministic subset of the first
        nBatches fetches (~rate of them)."""
        rng = random.Random(self.seed)
        for b in range(int(nBatches)):
            if rng.random() < rate:
                self.failOnBatch(b, times=times)
        return self

    def poisonStep(self, *steps: int):
        """Poison the features feeding the given global iterations with
        NaN — the loss and every gradient of that step go non-finite,
        which is what the step guard must catch and skip."""
        self._poison_steps.update(int(s) for s in steps)
        return self

    def killAfterStep(self, step: int):
        """Raise Preemption once, right after the global iteration
        counter reaches `step` (i.e. after `step` completed steps) —
        after any checkpoint scheduled at that step, like a real
        preemption landing between steps."""
        self._kill_after = int(step)
        return self

    # ----- hooks (called by the training loop / iterator wrapper) -----
    def maybe_poison(self, iteration: int, x):
        if iteration in self._poison_steps:
            self.events.append(("poison", iteration))
            return jnp.full_like(jnp.asarray(x), jnp.nan)
        return x

    def maybe_kill(self, iteration: int):
        if (self._kill_after is not None and not self._killed
                and iteration >= self._kill_after):
            self._killed = True
            self.events.append(("preempt", iteration))
            raise Preemption(f"injected preemption after step {iteration}")

    def wrapIterator(self, iterator):
        """DataSetIterator wrapper raising the scheduled data faults.
        The fault fires BEFORE the underlying fetch, so a retry consumes
        the same batch the failed attempt would have."""
        return _FaultyIterator(iterator, self)

    def _check_fetch(self, global_batch: int):
        fault = self._io_faults.get(global_batch)
        if fault and fault[0] > 0:
            fault[0] -= 1
            self.events.append(("data_fault", global_batch))
            raise fault[1]


class _FaultyIterator:
    """FaultInjector's data-path shim: counts successful fetches across
    epochs (reset() does NOT replay faults) and raises the scheduled
    exception before consuming the underlying batch."""

    def __init__(self, base, injector: FaultInjector):
        self._base = base
        self._injector = injector
        self._fetched = 0

    def reset(self):
        self._base.reset()

    def hasNext(self):
        return self._base.hasNext()

    def next(self, num=None):
        self._injector._check_fetch(self._fetched)
        ds = self._base.next() if num is None else self._base.next(num)
        self._fetched += 1
        return ds

    def __iter__(self):
        self.reset()
        while self.hasNext():
            yield self.next()

    def __getattr__(self, name):  # batch()/totalExamples()/preprocessors
        return getattr(self._base, name)


# ----------------------------------------------------------------------
# non-finite step guard
# ----------------------------------------------------------------------
class NonFiniteStepError(FloatingPointError):
    """K consecutive steps produced non-finite loss/params — the run has
    diverged and skipping more steps would only burn accelerator time."""


def non_finite_guard(step_fn):
    """Wrap a `(params, upd, states, it, x, y, key, fm, lm) ->
    (params', upd', states', loss)` train step so that a step whose loss
    or updated parameters contain NaN/Inf returns the UNCHANGED inputs
    instead (plus an `ok` flag). The select happens inside the jitted
    computation, so the wrapped step stays donation-safe and the skip
    costs no host round-trip beyond the loss fetch the loop already
    pays. NaN gradients surface as NaN updated params, so checking loss
    + params covers the whole backward path."""

    def guarded(params, upd_states, states, iteration, x, y, key,
                fmask, lmask):
        new_p, new_u, new_s, loss = step_fn(
            params, upd_states, states, iteration, x, y, key, fmask, lmask)
        ok = jnp.all(jnp.isfinite(loss))
        for leaf in jax.tree_util.tree_leaves(new_p):
            if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
                ok = ok & jnp.all(jnp.isfinite(leaf))

        def sel(old, new):
            return jax.tree_util.tree_map(
                lambda o, n: jnp.where(ok, n, o), old, new)

        return (sel(params, new_p), sel(upd_states, new_u),
                sel(states, new_s), loss, ok)

    return guarded


# ----------------------------------------------------------------------
# the resilient training harness
# ----------------------------------------------------------------------
class ResilientFit:
    """Preemption-safe fit() for MultiLayerNetwork / ParallelWrapper.

    * periodic atomic checkpoints every `saveEveryNIterations` steps via
      util.sharded_checkpoint (keep-last-N rotation, resume metadata in
      the manifest so the mid-epoch position commits with the state),
    * automatic resume-from-latest: if `checkpointDir` already holds a
      complete checkpoint, fit() restores it, replays the data iterator
      to the saved batch position and continues — a run killed mid-epoch
      and restarted lands on the BITWISE-identical trajectory (same
      iteration-keyed dropout stream, same updater moments),
    * the non-finite step guard (see non_finite_guard),
    * retry with backoff on the batch fetch and the checkpoint write.

    Usage:
        rf = ResilientFit(net, ckpt_dir, saveEveryNIterations=50)
        rf.fit(iterator, epochs=10)        # crash it; run again: resumes

    Listener events (optimize.listeners.TrainingListener hooks):
    onStepSkipped, onCheckpointSaved, onCheckpointRestored, plus the
    usual iterationDone/onEpochStart/onEpochEnd with fit() parity.
    """

    def __init__(self, net, checkpointDir=None, *,
                 saveEveryNIterations: int = 0, keepLast: int = 2,
                 saveUpdater: bool = True,
                 maxConsecutiveBadSteps: int = 3,
                 retryPolicy: RetryPolicy = None,
                 injector: FaultInjector = None):
        from deeplearning4j_tpu.parallel.trainer import ParallelWrapper

        if isinstance(net, ParallelWrapper):
            self.wrapper, self.net = net, net.net
        else:
            self.wrapper, self.net = None, net
        if getattr(self.net, "_solver", None) is not None:
            raise ValueError(
                "ResilientFit requires optimizationAlgo="
                "STOCHASTIC_GRADIENT_DESCENT: the non-finite guard's "
                "skip semantics are undefined under a line search, whose "
                "internal state already encodes the rejected step")
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        if not isinstance(self.net, MultiLayerNetwork):
            raise TypeError(
                f"ResilientFit wraps MultiLayerNetwork (directly or via "
                f"ParallelWrapper); got {type(self.net).__name__}")
        from deeplearning4j_tpu.nn.conf.builder import BackpropType

        if self.net.conf.backpropType == BackpropType.TruncatedBPTT:
            raise ValueError(
                "ResilientFit does not support truncated BPTT yet: a "
                "mid-sequence skip would desynchronize the carry stream")
        self.checkpointDir = None if checkpointDir is None \
            else os.path.abspath(str(checkpointDir))
        self.saveEvery = int(saveEveryNIterations)
        if self.saveEvery > 0 and self.checkpointDir is None:
            raise ValueError(
                "saveEveryNIterations > 0 needs a checkpointDir")
        self.keepLast = int(keepLast)
        self.saveUpdater = bool(saveUpdater)
        self.maxBad = int(maxConsecutiveBadSteps)
        self.retryPolicy = retryPolicy or RetryPolicy()
        self.injector = injector
        self._jit = None
        self._guarded = None
        self._bad = 0
        self.skippedSteps = 0

    # ----- step construction ------------------------------------------
    def _build_jit(self):
        if self._jit is not None:
            return
        if self.wrapper is not None:
            self.wrapper._place_replicated()
            step = non_finite_guard(self.wrapper.trainStep())
        else:
            step = non_finite_guard(self.net._train_step)
        self._guarded = step
        self._jit = jax.jit(step, donate_argnums=(0, 1, 2))

    def _loop_jit(self, k):
        """Guarded k-block loop for fit(stepsPerSync=k): the non-finite
        guard wraps EVERY step inside the on-device loop (a bad step's
        params/updater/state are rolled back in place, exactly the k=1
        semantics), and the loop returns k-vectors of losses and ok
        flags that the host-side guard accounting consumes at the sync
        boundary. max_bad freezes the carry on device from the step
        where the consecutive-bad count reaches the abort threshold —
        the k=1 path raises before training the next batch, so an
        aborting block's params must not contain later steps either."""
        from deeplearning4j_tpu.nn.multilayer import fit_dataset_jit

        return fit_dataset_jit(self.net, k, step_fn=self._guarded,
                               guarded=True, owner=self,
                               max_bad=self.maxBad)

    # ----- checkpoint / resume ----------------------------------------
    def _fire(self, hook, *args):
        for lst in self.net._listeners:
            getattr(lst, hook, lambda *a: None)(self.net, *args)

    def _save(self, batch_in_epoch: int):
        from deeplearning4j_tpu.util.sharded_checkpoint import \
            ShardedModelSerializer

        net = self.net
        tm = _tm()
        t0 = tm["reg"].clock()
        path = _ckpt.step_path(self.checkpointDir, net._iteration)
        # trainer-owned step state (threshold compression's error-
        # feedback residual + live tau) rides the checkpoint as its own
        # item so a mid-epoch resume replays the exact trajectory; the
        # NET state stays canonical and restores into any mode
        trainer_state = None
        if self.wrapper is not None:
            get = getattr(self.wrapper, "_ckpt_trainer_state", None)
            trainer_state = get() if get is not None else None
        from deeplearning4j_tpu.runtime.chaos import fault_point

        def _write():
            # chaos seam INSIDE the retry lambda: an injected write
            # fault is retried like any transient I/O failure
            # (runtime/chaos.py, seam checkpoint.write)
            fault_point("checkpoint.write")
            return ShardedModelSerializer.writeModel(
                net, path, saveUpdater=self.saveUpdater,
                extra={"iteration": net._iteration, "epoch": net._epoch,
                       "batch_in_epoch": int(batch_in_epoch)},
                trainer_state=trainer_state)

        retry(_write, self.retryPolicy)
        _ckpt.gc_checkpoints(self.checkpointDir, self.keepLast)
        dt = tm["reg"].clock() - t0
        tm["ckpt_save_s"].observe(dt)
        tm["reg"].trace.add("resilience.checkpoint_save", "resilience",
                            t0, dt, {"iteration": net._iteration})
        self._fire("onCheckpointSaved", path, net._iteration)

    def _maybe_resume(self) -> int:
        """Restore the latest complete checkpoint into the wrapped net,
        returning the batch-within-epoch to replay past (0 = fresh or
        epoch-aligned resume). A checkpoint that fails its content
        digest (CheckpointDigestError, util/sharded_checkpoint.py) is
        treated as ABSENT: the walk falls back to the previous
        snapshot instead of restoring silently-corrupt state."""
        if self.checkpointDir is None:
            return 0
        steps = _ckpt.complete_steps(self.checkpointDir)
        if not steps:
            return 0
        from deeplearning4j_tpu.runtime.chaos import fault_point
        from deeplearning4j_tpu.util.sharded_checkpoint import (
            CheckpointDigestError, ShardedModelSerializer,
        )

        tm = _tm()
        t0 = tm["reg"].clock()
        restored = path = None
        for step in reversed(steps):
            path = _ckpt.step_path(self.checkpointDir, step)

            def _restore(p=path):
                # chaos seam INSIDE the retry lambda (runtime/chaos.py,
                # seam checkpoint.restore)
                fault_point("checkpoint.restore")
                return ShardedModelSerializer.restore(p)

            try:
                restored = retry(_restore, self.retryPolicy)
                break
            except CheckpointDigestError:
                tm["reg"].event("resilience.checkpoint_corrupt",
                                "resilience", step=step, path=path)
                continue
        if restored is None:
            return 0    # every snapshot failed its digest: fresh start
        net = self.net
        net._params = restored._params
        net._states = restored._states
        net._upd_states = restored._upd_states
        net._iteration = restored._iteration
        net._epoch = restored._epoch
        manifest = _ckpt.read_manifest(path)
        extra = manifest.get("extra", {})
        if self.wrapper is not None:
            # re-place the restored state onto the mesh: checkpoints
            # hold the CANONICAL full-shape updater-state layout, and
            # under the ZeRO sharded update (weight_update='sharded')
            # the live carry is the 1/dp flat-shard view — re-placement
            # is bitwise (the view is a reshape). Under threshold
            # compression this also re-packs the residual carry (fresh
            # zeros), which the saved trainer state then overwrites.
            self.wrapper._place_replicated()
            if manifest.get("trainerState"):
                tmpl = self.wrapper._ckpt_trainer_state()
                if tmpl is not None:
                    abstract = jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(
                            a.shape, a.dtype, sharding=a.sharding),
                        tmpl)
                    ts = retry(
                        lambda: _ckpt.restore_trainer_state(path,
                                                            abstract),
                        self.retryPolicy)
                    self.wrapper._restore_trainer_state(ts)
        dt = tm["reg"].clock() - t0
        tm["ckpt_restore_s"].observe(dt)
        tm["reg"].trace.add("resilience.checkpoint_restore",
                            "resilience", t0, dt,
                            {"iteration": net._iteration})
        self._fire("onCheckpointRestored", path, net._iteration)
        return int(extra.get("batch_in_epoch", 0))

    # ----- the loop ----------------------------------------------------
    def fit(self, data, epochs: int = 1, stepsPerSync: int = 1):
        """Train until `epochs` epochs are complete, resuming from the
        latest checkpoint when one exists. `data` is a DataSetIterator;
        its order must be replayable (deterministic/seeded) for resumed
        runs to match uninterrupted ones.

        stepsPerSync=k > 1 runs the device-staged k-batch block loop
        (MultiLayerNetwork.fitDataSet mechanics) with the non-finite
        guard inside the loop: one host sync per k fresh batches, the
        guard consuming the block's k-vector of losses/ok flags, and
        checkpoint + injected-preemption points at the k-step sync
        boundaries (a save cadence that lands mid-block commits at the
        block's end). The parameter trajectory — including which steps
        are skipped — is identical to stepsPerSync=1; the ragged final
        block runs through the per-batch guarded step."""
        net = self.net
        net._require_init()
        k = int(stepsPerSync)
        if k < 1:
            raise ValueError(f"stepsPerSync must be >= 1, got {k}")
        replay = self._maybe_resume()
        self._build_jit()
        jloop = self._loop_jit(k) if k > 1 else None
        self._bad = 0
        while net._epoch < int(epochs):
            data.reset()
            skip, replay = replay, 0
            if skip == 0:
                self._fire("onEpochStart")
            b = 0
            buf = []
            while self._has_next(data):
                ds = retry(data.next, self.retryPolicy)
                b += 1
                if b <= skip:
                    continue  # replayed: already folded into the params
                if k == 1:
                    self._step(ds)
                    self._boundary(b, 1)
                else:
                    buf.append(ds)
                    if len(buf) == k:
                        self._block_step(buf, jloop)
                        buf = []
                        self._boundary(b, k)
            for i, ds in enumerate(buf):
                # ragged tail: per-batch guarded step, no k-loop retrace
                self._step(ds)
                self._boundary(b - len(buf) + i + 1, 1)
            self._fire("onEpochEnd")
            net._epoch += 1
        return net

    def _boundary(self, b, steps):
        """Checkpoint/injected-preemption hooks at a sync boundary that
        just advanced the iteration counter by `steps`. A saveEvery
        cadence that fires anywhere inside the block saves once, at the
        block's end (the first host-visible state)."""
        net = self.net
        if self.saveEvery > 0 and \
                net._iteration // self.saveEvery > \
                (net._iteration - steps) // self.saveEvery:
            self._save(b)
        if self.injector is not None:
            self.injector.maybe_kill(net._iteration)

    def _has_next(self, data) -> bool:
        """hasNext with the same backoff as next() — a record-reader-
        backed iterator probes the remote source here. If an error WAS
        retried and the iterator then reports exhausted, the 'end of
        epoch' is really the iterator dying (e.g. an async wrapper that
        latches exhausted after a producer error): re-raise the original
        error instead of silently recording a truncated epoch."""
        errs = []

        def probe():
            try:
                return data.hasNext()
            except self.retryPolicy.retryOn as e:
                errs.append(e)
                raise

        more = retry(probe, self.retryPolicy)
        if not more and errs:
            raise errs[-1]
        return more

    def _step(self, ds):
        from deeplearning4j_tpu.nn.multilayer import _unwrap

        net = self.net
        x = _unwrap(ds.getFeatures())
        y = _unwrap(ds.getLabels())
        fmask = _unwrap(ds.getFeaturesMaskArray())
        lmask = _unwrap(ds.getLabelsMaskArray())
        if self.injector is not None:
            x = self.injector.maybe_poison(net._iteration, x)
        if self.wrapper is not None:
            w = self.wrapper
            # divisibility-checked placement (rejects, never pads)
            x = w._shard_batch(x)
            y = w._shard_batch(y)
            fmask = w._shard_batch(fmask)
            lmask = w._shard_batch(lmask)
        # the exact key stream of MultiLayerNetwork._fit_batch — resumed
        # and uninterrupted runs fold the same iteration into the same
        # seed, which is what makes the trajectories bitwise-identical
        key = jax.random.fold_in(
            jax.random.key(net.conf.seed ^ 0x5EED), net._iteration)
        from deeplearning4j_tpu.nn.multilayer import _tm as _train_tm

        tm = _train_tm()
        t0 = tm["reg"].clock()
        net._params, net._upd_states, net._states, loss, ok = self._jit(
            net._params, net._upd_states, net._states,
            jnp.asarray(net._iteration, jnp.int32), x, y, key, fmask, lmask)
        ok = bool(ok)   # the guarded step's host sync
        dt = tm["reg"].clock() - t0
        tm["step_s"].observe(dt)
        tm["reg"].trace.add("train.step", "train", t0, dt,
                            {"iteration": net._iteration, "ok": ok})
        self._account_step(loss, ok)

    def _account_step(self, loss, ok):
        """Per-step guard accounting, shared by the k=1 path and the
        k-vector replay at a block's sync boundary: score/iteration
        advance, skip events, the consecutive-bad abort. The two paths
        MUST fire identically — tests assert the same skip-event stream
        for stepsPerSync=1 and k>1 on the same faults."""
        net = self.net
        net._score = float(loss)
        net._iteration += 1
        # counted HERE so the k=1 path and the k-vector block replay
        # bill dl4j_train_steps_total identically
        from deeplearning4j_tpu.nn.multilayer import _tm as _train_tm

        _train_tm()["steps"].inc()
        if ok:
            self._bad = 0
        else:
            self._bad += 1
            self.skippedSteps += 1
            self._fire("onStepSkipped", net._iteration, net._epoch,
                       net._score)
        for lst in net._listeners:
            lst.iterationDone(net, net._iteration, net._epoch)
        if not ok and self._bad >= self.maxBad:
            raise NonFiniteStepError(
                f"{self._bad} consecutive non-finite steps (last loss "
                f"{net._score}) at iteration {net._iteration} — aborting "
                f"instead of skipping forever; lower the learning rate "
                f"or enable gradient clipping")

    def _block_step(self, batches, jloop):
        """One stepsPerSync block: stage k batches as a stacked device
        buffer (sharded over the wrapper's mesh when present), run the
        guarded on-device k-loop, then consume the k-vector of
        losses/ok flags in ONE host sync — per-step guard accounting
        (skip events, consecutive-bad abort) replays host-side exactly
        as the k=1 path fires it."""
        from deeplearning4j_tpu.data.iterators import stack_datasets

        net = self.net
        k = len(batches)
        start = net._iteration
        xs, ys, fms, lms = stack_datasets(batches)
        if self.injector is not None:
            for i in range(k):
                xs[i] = np.asarray(
                    self.injector.maybe_poison(start + i, xs[i]))
        staged = (xs, ys, fms, lms)
        if self.wrapper is not None:
            from deeplearning4j_tpu.parallel.sharding import \
                shard_batch_stack

            staged = shard_batch_stack(staged, self.wrapper.mesh,
                                       self.wrapper.batch_axis)
        else:
            staged = jax.device_put(staged)
        xs, ys, fms, lms = staged
        (net._params, net._upd_states, net._states, losses, oks, _bad) = \
            jloop(net._params, net._upd_states, net._states,
                  jnp.asarray(start, jnp.int32), xs, ys, fms, lms,
                  jnp.asarray(self._bad, jnp.int32))
        losses = np.asarray(losses)  # the block's one host sync
        oks = np.asarray(oks)
        for i in range(k):
            # raises at the same step k=1 would; the device loop froze
            # the carry from that step on, so params match bitwise
            self._account_step(losses[i], bool(oks[i]))
        for lst in net._listeners:
            getattr(lst, "onSyncBoundary", lambda *a: None)(
                net, net._iteration, losses)
