"""MultiLayerNetwork — the sequential network executor.

Reference: org.deeplearning4j.nn.multilayer.MultiLayerNetwork. The
reference executes layers one-by-one through mutable Layer objects with
workspace-managed activations, then a Solver/StochasticGradientDescent
optimize step and a BaseMultiLayerUpdater over a flattened gradient view.

TPU design: the whole training step — forward, loss (+regularization),
backward (jax.grad), gradient normalization, per-layer updater, parameter
update — is ONE jitted function compiled by XLA into a single fused
computation. Parameters, updater state and layer state (BN running stats)
are donated device buffers: XLA reuses their memory in-place, which is the
role the reference's workspaces play. fit()/output()/score() keep the
reference's signatures.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ndarray import INDArray, Nd4j
from deeplearning4j_tpu.nn import losses as _losses
from deeplearning4j_tpu.nn import updaters as _upd
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.builder import BackpropType, GradientNormalization
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.runtime import telemetry


def _unwrap(x):
    if isinstance(x, INDArray):
        return x.jax()
    if x is None:
        return None
    return jnp.asarray(x)


_TM = None


def _tm():
    """Lazily-resolved training telemetry handles (runtime.telemetry).
    The registry's identity is process-stable, so the handles are
    resolved once and the per-step cost is one histogram observe + one
    ring append — host-side, between dispatches, never inside a traced
    function (zero added device syncs / compiles; CI-gated)."""
    global _TM
    if _TM is None:
        reg = telemetry.get_registry()
        _TM = {
            "reg": reg,
            "step_s": reg.histogram(
                "dl4j_train_step_seconds",
                "train-step wall: dispatch + loss fetch, host-observed "
                "at the jit boundary"),
            "steps": reg.counter(
                "dl4j_train_steps_total", "optimizer steps applied"),
            "staging_s": reg.histogram(
                "dl4j_fit_dataset_staging_seconds",
                "fitDataSet k-block host stack + device placement"),
            "sync_wait_s": reg.histogram(
                "dl4j_fit_dataset_sync_wait_seconds",
                "fitDataSet block on the in-flight k-block's losses "
                "(the one host sync per block)"),
            "data_wait_s": reg.histogram(
                "dl4j_fit_dataset_data_wait_seconds",
                "fitDataSet wait on the data iterator per k-stack"),
            "syncs": reg.counter(
                "dl4j_fit_dataset_syncs_total",
                "fitDataSet host syncs (one per k-block)"),
        }
    return _TM


def traced_train_step(net, x, y, fmasks, lmasks):
    """One optimizer step of fit() with its host work under spans — the
    ONE body MultiLayerNetwork._fit_batch and ComputationGraph._step
    share, so the two cannot drift. All spans are cat ``train``, carry
    ``iteration`` and lie on the registry's clock, outside every traced
    function (docs/OBSERVABILITY.md):

    train.prepare    the dropout key's fold_in
    train.step       dispatch + loss fetch (feeds dl4j_train_step_seconds)
      train.dispatch   the iteration scalar + the jitted call, until it
                       returns its futures
      train.sync       float(loss): the host waits out the device step
    train.listeners  the iterationDone loop
    """
    tm = _tm()
    reg = tm["reg"]
    clock, add = reg.clock, reg.trace.add
    args = {"iteration": net._iteration}
    t0 = clock()
    key = jax.random.fold_in(jax.random.key(net.conf.seed ^ 0x5EED),
                             net._iteration)
    t1 = clock()
    net._params, net._upd_states, net._states, loss = net._jit_train(
        net._params, net._upd_states, net._states,
        jnp.asarray(net._iteration, jnp.int32), x, y, key, fmasks, lmasks)
    t2 = clock()
    # recorded while the device works: what follows the loss fetch is
    # on the step's critical path, this is not
    step_id = reg.new_span_id()
    add("train.prepare", "train", t0, t1 - t0, args)
    add("train.dispatch", "train", t1, t2 - t1, args, parent=step_id)
    net._score = float(loss)
    t3 = clock()
    tm["step_s"].observe(t3 - t1)
    tm["steps"].inc()
    add("train.sync", "train", t2, t3 - t2, args, parent=step_id)
    add("train.step", "train", t1, t3 - t1, args, span_id=step_id)
    net._iteration += 1
    t4 = clock()
    for lst in net._listeners:
        lst.iterationDone(net, net._iteration, net._epoch)
    add("train.listeners", "train", t4, clock() - t4, args)


def fit_iterator_epoch(net, data, fit_one):
    """The batches of one epoch of fit(iterator): `fit_one(batch)` for
    every batch `data` yields, with the iterator's share of the step
    (hasNext + next + the unwrapping into arrays that `net._extract_ds`
    does) recorded as ``train.data_wait`` — a slow source shows up
    there, not as a slow-looking step."""
    reg = _tm()["reg"]
    while True:
        t0 = reg.clock()
        if not data.hasNext():
            return
        batch = net._extract_ds(data.next())
        reg.trace.add("train.data_wait", "train", t0, reg.clock() - t0,
                      {"iteration": net._iteration})
        fit_one(*batch)


def checkpointed_forward(layer, l_train):
    """layer.forward wrapped in jax.checkpoint (activation remat); layer
    and the static train flag ride as closures, array args (params,
    state, x, key, mask — Nones allowed) cross the remat boundary.
    Shared by MultiLayerNetwork._run_layers and ComputationGraph."""
    return jax.checkpoint(
        lambda p_, s_, x_, k_, m_: layer.forward(p_, s_, x_, l_train, k_, m_))


def strip_carries(states):
    """Drop transient rnn carries (h/c) from a state container (list or
    dict of per-layer state dicts); keep persistent state like BN stats."""

    def strip(s):
        if isinstance(s, dict):
            return {k: strip(v) for k, v in s.items() if k not in ("h", "c")}
        return s

    if isinstance(states, dict):
        return {n: strip(s) for n, s in states.items()}
    return [strip(s) for s in states]


def cast_params(p, compute_dtype, param_dtype):
    """fp32 master params -> compute dtype (bf16/fp16) for the forward."""
    if compute_dtype == param_dtype:
        return p
    return jax.tree_util.tree_map(
        lambda a: a.astype(compute_dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, p)


def run_tbptt(net, T, L, jit_call):
    """Shared truncated-BPTT chunk driver for MultiLayerNetwork and
    ComputationGraph (reference: doTruncatedBPTT in both classes).

    jit_call(sl, key, iteration, use_carries) must run the network's
    donating jit step, REASSIGN the net's params/states in the same
    statement (listeners fire right after and may read them — the old
    buffers are already invalidated by donation), and return the loss.
    """
    for c in range(math.ceil(T / L)):
        sl = slice(c * L, min((c + 1) * L, T))
        key = jax.random.fold_in(jax.random.key(net.conf.seed ^ 0x5EED),
                                 net._iteration)
        loss = jit_call(sl, key, jnp.asarray(net._iteration, jnp.int32), c > 0)
        net._score = float(loss)
        net._iteration += 1
        for lst in net._listeners:
            lst.iterationDone(net, net._iteration, net._epoch)
    net._states = net._strip_carries(net._states)


def pick_batch(i, tree):
    """Batch i of a stacked [k, ...] pytree (None components pass
    through): the per-step slice of fitDataSet's staged device buffer."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


#: fitDataSet staging layout policy (round 6, the layout-hygiene fix):
#: "host" (default) canonicalises the staged feature stack on the HOST —
#: API layout -> internal NHWC/NDHWC and fp32 -> compute dtype BEFORE
#: device_put — so the compiled k-loop never carries the per-step entry
#: transpose+convert the HBM attribution names in its layout_copies /
#: dtype_widening bins, and the H2D transfer itself halves under bf16.
#: "device" keeps the legacy in-program conversion (the A/B leg in
#: bench.py and the attribution tests flip this). Read at fitDataSet
#: call time, so a test/bench can toggle the module global directly.
_CANON_STAGING = os.environ.get("DL4J_TPU_CANON_STAGING", "host")


def canon_staging_on():
    return _CANON_STAGING != "device"


def host_to_nhwc(x, stacked=False):
    """numpy NCHW -> NHWC, optionally under a leading [k] staging dim —
    the ONE definition of the stacked-axis transpose arithmetic shared
    by MultiLayerNetwork._canon_host and ComputationGraph._canon_host
    (each dispatches on the input KINDS its own _entry handles; the
    axis math must not fork)."""
    o = 1 if stacked else 0
    return np.transpose(x, (*range(o), o, o + 2, o + 3, o + 1))


def host_to_ndhwc(x, stacked=False):
    """numpy NCDHW -> NDHWC, optionally under a leading [k] staging
    dim (see host_to_nhwc)."""
    o = 1 if stacked else 0
    return np.transpose(x, (*range(o), o, o + 2, o + 3, o + 4, o + 1))


def make_fit_dataset_loop(net, k, step_fn=None, guarded=False,
                          max_bad=None, canonical=False):
    """The on-device k-fresh-batch training loop shared by
    MultiLayerNetwork, ComputationGraph, ParallelWrapper and
    ResilientFit: a lax.fori_loop whose step i ``dynamic_index_in_dim``s
    batch i out of the stacked [k, B, ...] buffers and runs the
    canonical train step with the donated params/updater/state carry —
    the whole epoch block is ONE executable with ONE host sync
    (vs fitSteps, which runs k steps on one batch: this is the
    fresh-data generalisation).

    step_fn defaults to net._train_step; a distributed wrapper passes
    its own (e.g. the int8-allreduce step). guarded=True expects the
    non_finite_guard signature (returns an extra ok flag) and the loop
    then also carries a k-vector of per-step ok flags, so the host can
    replay exactly which steps were skipped; it takes one extra runtime
    arg `bad0` (the consecutive-bad count entering the block) and, with
    `max_bad`, FREEZES the params/updater/state carry from the step
    where the count reaches `max_bad` — the k=1 path raises
    NonFiniteStepError before ever training the next batch, so later
    steps of an aborting block must not train either (the host replays
    the flags and raises at the same step, params bitwise-matching).

    Returns (params, upd, states, losses[k][, oks[k], bad]) — the loss
    k-vector is replayed host-side through the TrainingListener chain.
    """
    seed_key = jax.random.key(net.conf.seed ^ 0x5EED)
    if step_fn is not None:
        step = step_fn
    elif canonical:
        # the staged stack is already in the internal layout + compute
        # dtype (host canonicalisation, see _CANON_STAGING): the step
        # must not emit the entry transpose/convert again
        step = lambda *a, **kw: net._train_step(
            *a, canonical_inputs=True, **kw)
    else:
        step = net._train_step

    def loop(params, upd, states, it0, xs, ys, fms, lms, bad0=None):
        def body(i, carry):
            if guarded:
                p0, u0, s0, losses, oks, bad = carry
                p, u, s = p0, u0, s0
            else:
                p, u, s, losses = carry
            it = it0 + i
            key = jax.random.fold_in(seed_key, it)
            out = step(p, u, s, it, pick_batch(i, xs), pick_batch(i, ys),
                       key, pick_batch(i, fms), pick_batch(i, lms))
            if guarded:
                p, u, s, loss, ok = out
                if max_bad is not None:
                    # an earlier step of THIS block hit the abort
                    # threshold: k=1 raised there, so this step must
                    # not train — keep the pre-step carry
                    alive = bad < max_bad
                    p, u, s = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(alive, n, o),
                        (p, u, s), (p0, u0, s0))
                    bad = jnp.where(alive,
                                    jnp.where(ok, 0, bad + 1), bad)
                else:
                    bad = jnp.where(ok, 0, bad + 1)
            else:
                p, u, s, loss = out
            # strip the transient h/c entries the step may add: the fori
            # carry must be structure-stable (persistent state like BN
            # stats survives; same rule as fitSteps)
            res = (p, u, net._strip_carries(s),
                   losses.at[i].set(loss.astype(jnp.float32)))
            if guarded:
                res = res + (oks.at[i].set(ok), bad)
            return res

        init = (params, upd, net._strip_carries(states),
                jnp.zeros((k,), jnp.float32))
        if guarded:
            b0 = jnp.int32(0) if bad0 is None else bad0.astype(jnp.int32)
            init = init + (jnp.ones((k,), bool), b0)
        return jax.lax.fori_loop(0, k, body, init)

    return loop


def fit_dataset_jit(net, k, step_fn=None, guarded=False, owner=None,
                    max_bad=None, canonical=False, aot_extra=None):
    """Cached jit of make_fit_dataset_loop (one compile per k across an
    epoch — RetraceSentinel-provable via install_fit_dataset, which
    routes the loop through net._fit_dataset_wrap before jitting).

    `owner` holds the cache when a harness (ParallelWrapper,
    ResilientFit) builds loops around its own step for someone else's
    net — the wrap hook is still read from the net, where
    install_fit_dataset sets it for both. Solver (optax) states alias
    the param buffers, so params/upd donation follows net._solver
    exactly as _make_jit_train does.

    AOT routing: the loop compiles through the runtime.aot executable
    cache when a session cache is enabled AND the program's provenance
    is fully describable — the net's own step (step_fn None), or a
    caller-passed step whose identity the caller encodes in
    `aot_extra` (ParallelWrapper passes its mesh/compression mode). A
    wrapped loop (RetraceSentinel counting traces) or an anonymous
    step_fn stays on the plain jit."""
    cache_owner = owner if owner is not None else net
    cache = getattr(cache_owner, "_fit_dataset_cache", None)
    if cache is None:
        cache = cache_owner._fit_dataset_cache = {}
    # canonical staging changes the traced program (no entry transpose/
    # convert), so it must key the cache alongside k
    jloop = cache.get((k, bool(canonical)))
    if jloop is None:
        loop = make_fit_dataset_loop(net, k, step_fn=step_fn,
                                     guarded=guarded, max_bad=max_bad,
                                     canonical=canonical)
        wrap = getattr(net, "_fit_dataset_wrap", None)
        donate = (0, 1, 2) if getattr(net, "_solver", None) is None \
            else (2,)
        if wrap is not None:
            jloop = jax.jit(wrap(loop), donate_argnums=donate)
        elif step_fn is not None and aot_extra is None:
            jloop = jax.jit(loop, donate_argnums=donate)
        else:
            from deeplearning4j_tpu.runtime import aot

            entry = (f"fit_dataset[k={k},canonical={bool(canonical)},"
                     f"guarded={bool(guarded)},max_bad={max_bad}]"
                     + (aot_extra or ""))
            jloop = aot.cached_jit(loop, owner=net, entry=entry,
                                   donate_argnums=donate)
        cache[(k, bool(canonical))] = jloop
    return jloop


#: precompile()'s per-entry example-argument builders live beside the
#: call sites they must mirror — a drifted example would warm a program
#: the real fit/output never runs
def shape_for_input_type(it, batchSize):
    """API-layout feature shape for one InputType (None → caller must
    pass featuresShape explicitly; raises naming the gap)."""
    from deeplearning4j_tpu.nn.conf.inputs import InputType as IT

    B = int(batchSize)
    if it is None:
        raise ValueError(
            "precompile needs featuresShape=... for a conf with no "
            "declared InputType")
    if it.kind == IT.FF:
        return (B, it.size)
    if it.kind == IT.CNN_FLAT:
        # convolutionalFlat accepts flat [B, h*w*c] or NCHW; the NCHW
        # feed is what the zoo/bench paths use — precompile warms that
        # form (pass featuresShape=(B, h*w*c) for flat-fed pipelines)
        return (B, it.channels, it.height, it.width)
    if it.kind == IT.CNN:
        return (B, it.height, it.width, it.channels) \
            if getattr(it, "format", "NCHW") == "NHWC" \
            else (B, it.channels, it.height, it.width)
    if it.kind == IT.CNN3D:
        return (B, it.channels, it.depth, it.height, it.width)
    if it.kind == IT.RNN:
        T = it.dims.get("timeSeriesLength")
        if not T:
            raise ValueError(
                "precompile needs featuresShape=(B, size, T) for a "
                "recurrent InputType with no timeSeriesLength")
        return (B, it.size, T)
    raise ValueError(f"unsupported InputType {it!r}; pass "
                     "featuresShape explicitly")


def shape_for_output_type(ot, batchSize, api_nhwc=False, t_fallback=None):
    """API-layout labels shape for one output-layer InputType."""
    from deeplearning4j_tpu.nn.conf.inputs import InputType as IT

    B = int(batchSize)
    if ot.kind == IT.FF:
        return (B, ot.size)
    if ot.kind == IT.RNN:
        T = ot.dims.get("timeSeriesLength") or t_fallback
        if not T:
            raise ValueError(
                "precompile needs labelsShape=(B, size, T) for a "
                "recurrent output with no timeSeriesLength")
        return (B, ot.size, T)
    if ot.kind == IT.CNN:
        # _loss_from_preact expects API labels NCHW unless the net
        # declares NHWC end-to-end
        return (B, ot.height, ot.width, ot.channels) if api_nhwc \
            else (B, ot.channels, ot.height, ot.width)
    raise ValueError(f"unsupported output type {ot!r}; pass "
                     "labelsShape explicitly")


def example_batch(net, batchSize, featuresShape=None, labelsShape=None):
    """(x, y) example arrays for one training batch of `net` in the API
    layout/dtype fit() receives. Shapes are derived from the conf's
    InputType and the last layer's output type; recurrent inputs with
    no declared timeSeriesLength (and composite heads with bespoke
    label layouts) need explicit shapes."""
    if featuresShape is None:
        featuresShape = shape_for_input_type(net.conf.inputType,
                                             batchSize)
    if labelsShape is None:
        last = net.layers[-1]
        if hasattr(last, "computeLoss"):
            raise ValueError(
                f"precompile needs labelsShape=... for composite head "
                f"{type(last).__name__} (bespoke label layout)")
        ot = last.getOutputType(net.conf.layerInputTypes[-1])
        labelsShape = shape_for_output_type(
            ot, batchSize, api_nhwc=net._api_nhwc,
            t_fallback=featuresShape[-1] if len(featuresShape) == 3
            else None)
    return (np.zeros(featuresShape, np.float32),
            np.zeros(labelsShape, np.float32))


@telemetry.phase("warm")
def precompile_network(net, batchSize=32, featuresShape=None,
                       labelsShape=None, entries=("train", "infer"),
                       stepsPerSync=None, cache=None, wrap_args=None,
                       autotune=False):
    """Shared MultiLayerNetwork/ComputationGraph precompile driver:
    warm (or AOT-compile + persist) the selected entry points at one
    batch signature. wrap_args adapts (x, y) into the network-type call
    convention (ComputationGraph's inputs-dict/labels-list).
    autotune=True first installs this network's persisted tuned knobs
    (runtime.autotune.warm_start — a no-op when no record exists), so
    the warmed executables are the TUNED programs, in any process."""
    net._require_init()
    if autotune:
        from deeplearning4j_tpu.runtime import autotune as _autotune

        _autotune.warm_start(net)
    x, y = example_batch(net, batchSize, featuresShape, labelsShape)
    key = jax.random.fold_in(jax.random.key(net.conf.seed ^ 0x5EED), 0)
    it0 = jnp.asarray(0, jnp.int32)
    adapt = wrap_args or (lambda xx, yy: (xx, yy))
    report = {}

    def record(name, res):
        k_, status, secs = res
        if status is not None:
            report[name] = {"key": k_, "status": status,
                            "seconds": round(secs, 3)}

    if "train" in entries:
        xx, yy = adapt(jnp.asarray(x), jnp.asarray(y))
        record("train_step", net._jit_train.warm(
            net._params, net._upd_states, net._states, it0, xx, yy,
            key, None, None, cache=cache))
    if "infer" in entries:
        xx, _ = adapt(jnp.asarray(x), jnp.asarray(y))
        record("forward_infer", net._jit_forward.warm(
            net._params, net._states, xx, cache=cache))
    if stepsPerSync and int(stepsPerSync) > 1:
        k = int(stepsPerSync)
        canon = canon_staging_on()
        from deeplearning4j_tpu.data.dataset import DataSet

        batches = [DataSet(x, y) for _ in range(k)]
        if hasattr(net, "_stack_batches"):  # ComputationGraph
            stack = (net._stack_batches_canonical if canon
                     else net._stack_batches)(batches)
        else:
            from deeplearning4j_tpu.data.iterators import stack_datasets

            stack = net._stack_canonical(batches) if canon \
                else stack_datasets(batches)
        staged = jax.device_put(stack)
        jloop = fit_dataset_jit(net, k, canonical=canon)
        if hasattr(jloop, "warm"):
            record(f"fit_dataset[k={k}]", jloop.warm(
                net._params, net._upd_states, net._states, it0, *staged,
                cache=cache))
    return report


def run_staged_blocks(iterator, k, dispatch, consume):
    """The double-buffered block driver shared by every fitDataSet
    implementation (MultiLayerNetwork/ComputationGraph via
    run_fit_dataset_epoch, SameDiff directly). For each FULL stack of k
    fresh batches, `dispatch(batches)` stages and launches the jitted
    k-loop and returns the block's (device-resident) losses; `consume`
    blocks on them one block BEHIND the launch — the transfer of stack
    n+1 and its dispatch are already in flight while the host blocks on
    stack n's losses, so H2D overlaps compute.

    Returns the ragged final stack (< k batches, possibly empty) for
    the caller to run through its plain per-batch fit — never through
    the k-loop, which therefore never retraces on a ragged shape."""
    from deeplearning4j_tpu.data.iterators import iter_stacks

    tm = _tm()
    pending = None     # (losses device array) of the in-flight block
    tail = []
    stacks = iter_stacks(iterator, k)
    _end = object()
    try:
        while True:
            # data-wait vs staging split (docs/OBSERVABILITY.md): this
            # is the iterator's share of the block cadence — a slow
            # data source shows up HERE, not as a slow-looking step
            t0 = tm["reg"].clock()
            batches = next(stacks, _end)
            dt = tm["reg"].clock() - t0
            tm["data_wait_s"].observe(dt)
            tm["reg"].trace.add("fit_dataset.data_wait", "train", t0, dt,
                                {"k": k})
            if batches is _end:
                break
            if len(batches) < k:
                tail = batches
                break
            out = dispatch(batches)
            if pending is not None:
                consume(pending)
            pending = out
    finally:
        # drain in a finally: a mid-epoch error (ragged stack, shard
        # rejection) lands AFTER a block was dispatched and the model's
        # params reassigned — consuming the in-flight block here keeps
        # the iteration counter (the RNG/saveEvery/resume key) in step
        # with the params instead of up to k steps behind them
        if pending is not None:
            consume(pending)
    return tail


def run_fit_dataset_epoch(net, iterator, k, stack_fn, fit_one, jloop,
                          place=None):
    """One epoch of device-staged k-step blocks with double-buffered
    transfer overlap (run_staged_blocks above drives the
    stage → launch → lagged-consume cadence).

    The loss k-vector is replayed per-step through the listener chain
    (score/iteration advance exactly as per-batch fit() would), then
    onSyncBoundary fires once per block. The ragged final stack
    (< k batches) runs through `fit_one` — plain per-batch fit.

    Returns the number of host syncs performed: one per full k-block
    plus one per ragged-tail batch — ⌈n/k⌉ for n batches whenever k
    divides n (or the tail is a single batch); a longer tail pays the
    ordinary per-batch sync for each of its batches."""
    syncs = 0
    it_next = net._iteration   # dispatch-side iteration cursor
    tm = _tm()

    def consume(losses):
        nonlocal syncs
        syncs += 1
        t0 = tm["reg"].clock()
        vals = np.asarray(losses)   # THE host sync for this block
        dt = tm["reg"].clock() - t0
        tm["sync_wait_s"].observe(dt)
        tm["syncs"].inc()
        # the k on-device steps count here (per-step WALL is only
        # observable at a jit boundary, so the step histogram stays
        # per-dispatch — the block's wall is staging + sync_wait)
        tm["steps"].inc(len(vals))
        tm["reg"].trace.add("fit_dataset.sync_wait", "train", t0, dt,
                            {"k": k, "iteration": net._iteration})
        for v in vals:
            net._score = float(v)
            net._iteration += 1
            for lst in net._listeners:
                lst.iterationDone(net, net._iteration, net._epoch)
        for lst in net._listeners:
            getattr(lst, "onSyncBoundary", lambda *a: None)(
                net, net._iteration, vals)

    def dispatch(batches):
        nonlocal it_next
        t0 = tm["reg"].clock()
        staged = stack_fn(batches)
        staged = jax.device_put(staged) if place is None \
            else place(staged)
        dt = tm["reg"].clock() - t0
        tm["staging_s"].observe(dt)
        tm["reg"].trace.add("fit_dataset.staging", "train", t0, dt,
                            {"k": k, "iteration": it_next})
        xs, ys, fms, lms = staged
        t1 = tm["reg"].clock()
        net._params, net._upd_states, net._states, losses = jloop(
            net._params, net._upd_states, net._states,
            jnp.asarray(it_next, jnp.int32), xs, ys, fms, lms)
        tm["reg"].trace.add("fit_dataset.dispatch", "train", t1,
                            tm["reg"].clock() - t1,
                            {"k": k, "iteration": it_next})
        it_next += k
        return losses

    tail = run_staged_blocks(iterator, k, dispatch, consume)
    for ds in tail:
        fit_one(ds)
        syncs += 1
    return syncs


def default_param_update(updater, grads, upd_state, iteration, params):
    """The canonical apply-and-subtract for one trainable unit (a layer's
    params dict, or SameDiff's whole variable dict) — the default
    `_update_impl` every network type shares. A distributed trainer may
    swap in parallel.sharding.ZeroShardedUpdate (same signature) for the
    cross-replica sharded weight update."""
    upd, us = updater.apply(grads, upd_state, iteration, params=params)
    # cast keeps param dtype stable (python-float hyperparams would
    # otherwise promote under x64)
    return jax.tree_util.tree_map(
        lambda p, u: (p - u).astype(p.dtype), params, upd), us


def _grad_normalize(grads_per_layer, mode, threshold):
    """Gradient clipping/normalization (reference:
    org.deeplearning4j.nn.conf.GradientNormalization, applied in
    BaseLayer.backpropGradient)."""
    if mode is None:
        return grads_per_layer
    out = []
    for g in grads_per_layer:
        if not g:
            out.append(g)
            continue
        if mode == GradientNormalization.ClipElementWiseAbsoluteValue:
            g = jax.tree_util.tree_map(lambda a: jnp.clip(a, -threshold, threshold), g)
        elif mode in (GradientNormalization.ClipL2PerLayer,
                      GradientNormalization.RenormalizeL2PerLayer):
            leaves = jax.tree_util.tree_leaves(g)
            l2 = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in leaves) + 1e-12)
            if mode == GradientNormalization.ClipL2PerLayer:
                scale = jnp.minimum(1.0, threshold / l2)
            else:
                scale = 1.0 / l2
            g = jax.tree_util.tree_map(lambda a: a * scale, g)
        elif mode in (GradientNormalization.ClipL2PerParamType,
                      GradientNormalization.RenormalizeL2PerParamType):
            def per_param(a):
                l2 = jnp.sqrt(jnp.sum(jnp.square(a)) + 1e-12)
                if mode == GradientNormalization.ClipL2PerParamType:
                    return a * jnp.minimum(1.0, threshold / l2)
                return a / l2
            g = jax.tree_util.tree_map(per_param, g)
        out.append(g)
    return out


class MultiLayerNetwork:
    def __init__(self, conf):
        self.conf = conf
        self.layers = conf.layers
        self._params = None        # list[dict] per layer
        self._states = None        # list[dict] per layer
        self._upd_states = None    # list per layer
        self._updaters = None
        self._iteration = 0
        self._epoch = 0
        self._listeners = []
        self._rnn_state = None     # stateful rnnTimeStep carries
        self._compute_dtype = conf.dataType.np_dtype
        # params kept fp32 for stable updates even when compute is bf16/fp16;
        # fp64 dataType (gradient checks) promotes params too
        self._param_dtype = jnp.float64 if self._compute_dtype == jnp.float64 else jnp.float32
        algo = getattr(conf, "optimizationAlgo",
                       "STOCHASTIC_GRADIENT_DESCENT")
        if algo != "STOCHASTIC_GRADIENT_DESCENT":
            from deeplearning4j_tpu.nn import solvers as _solvers

            self._solver = _solvers.build_solver(
                algo, getattr(conf, "maxNumLineSearchIterations", 20))
            if conf.gradientNormalization is not None:
                import warnings

                warnings.warn(
                    f"gradientNormalization={conf.gradientNormalization} is "
                    f"IGNORED under optimizationAlgo={algo}: the line search "
                    "needs the true gradient of the loss for its "
                    "Wolfe/Armijo conditions, so clipping/renorm is not "
                    "applied (ADVICE r4). Use SGD-family updaters for "
                    "gradient clipping.", stacklevel=2)
        else:
            self._solver = None
        from deeplearning4j_tpu.runtime import aot

        self._jit_train = self._make_jit_train()
        self._jit_forward = aot.cached_jit(self._forward_infer, owner=self,
                                           entry="forward_infer")
        self._jit_loss = aot.cached_jit(self._loss_only, owner=self,
                                        entry="loss_only")
        # functional slot-batched decode step (rnnStepBatched): its own
        # AOT entry so the sequence-serving tier compiles one executable
        # per slot bucket, shared across equal-config models
        self._jit_rnn_step = aot.cached_jit(self._rnn_step, owner=self,
                                            entry="rnn_step")

    def _make_jit_train(self, step_fn=None):
        """The canonical jit of the train step. Factored out so
        instrumentation (analysis.retrace.RetraceSentinel.install) can
        re-jit a wrapped step under the SAME options — static args and
        donation must match or the counter would measure a different
        program. The unwrapped form routes through the AOT executable
        cache (runtime.aot) when a session cache is enabled: equal
        configs at equal signatures share ONE compile, and precompile()
        can warm-start it from disk; a WRAPPED step (sentinel counting
        traces) always gets the plain jit — a cache hit would hide the
        trace the wrapper exists to count."""
        # solver (optax) states alias the param buffers (L-BFGS
        # keeps previous params/updates); donating both would be
        # `f(donate(a), donate(a))` — donate states only there
        donate = (0, 1, 2) if self._solver is None else (2,)
        if step_fn is not None:
            return jax.jit(step_fn, static_argnames=("use_carries",),
                           donate_argnums=donate)
        from deeplearning4j_tpu.runtime import aot

        return aot.cached_jit(
            self._train_step, owner=self, entry="train_step",
            static_argnames=("use_carries",), donate_argnums=donate)

    # ------------------------------------------------------------------
    # initialization
    # ------------------------------------------------------------------
    def init(self, validate=False, mesh=None, hbm_gb=None, plan=None,
             batchSize=32):
        """Initialize parameters. validate=True runs the static
        shape/dtype analyzer first (analysis.validate_model) and raises
        ConfigValidationError with every finding — catching config
        mistakes eagerly instead of at trace time, where the XLA error
        would name a lowered op instead of the offending layer.

        Plan-aware form: passing `mesh` (axis->size dict, Mesh, or
        "data=4,model=2") extends the eager check with the
        partition-plan analyzer (analysis.validate_plan): sharding-spec
        sanity, collective axis consistency, pipeline balance and —
        with `hbm_gb` — the per-chip HBM fit prediction, all before any
        trace. Pass `batchSize` as the GLOBAL batch you will fit() with
        — the PAR03 divisibility check and the PAR06 residency
        prediction are statements about that batch, not the default."""
        if validate or mesh is not None:
            from deeplearning4j_tpu.analysis import validate_or_raise

            validate_or_raise(self.conf, batchSize=batchSize, mesh=mesh,
                              hbm_gb=hbm_gb, plan=plan)
        key = jax.random.key(self.conf.seed)
        params, states, upds, upd_states = [], [], [], []
        with telemetry.phase("weights_init"):
            for i, layer in enumerate(self.layers):
                k = jax.random.fold_in(key, i)
                p, s = layer.initialize(k, self.conf.layerInputTypes[i], self._param_dtype)
                params.append(p)
                states.append(s)
                u = _upd.resolve(layer.updater) if layer.updater is not None else _upd.Sgd()
                upds.append(u)
                upd_states.append(u.init(p) if p else ())
        self._params, self._states = params, states
        self._updaters, self._upd_states = upds, upd_states
        if self._solver is not None:
            # whole-pytree optimizer state replaces the per-layer list
            self._upd_states = self._solver.init(params)
        self._iteration = 0
        return self

    def initFrom(self, params, states, upd_states=None):
        """Initialize from existing state (ModelSerializer restore path) —
        skips the random weight init that init() would immediately discard."""
        self._params, self._states = params, states
        self._updaters = [
            _upd.resolve(l.updater) if l.updater is not None else _upd.Sgd()
            for l in self.layers]
        if self._solver is not None:
            # solver memory (L-BFGS curvature pairs, CG direction) is
            # batch-local and not serialized — fresh state on restore,
            # like the reference's solvers which rebuild per fit call
            self._upd_states = self._solver.init(params)
        elif upd_states is not None:
            self._upd_states = upd_states
        else:
            self._upd_states = [u.init(p) if p else ()
                                for u, p in zip(self._updaters, params)]
        self._iteration = 0
        return self

    def precompile(self, batchSize=32, featuresShape=None,
                   labelsShape=None, entries=("train", "infer"),
                   stepsPerSync=None, cache=None, autotune=False):
        """AOT warm-start: compile (or load from the persistent
        executable cache) the train-step / inference / fitDataSet
        programs for one batch signature BEFORE the first real batch,
        so a fresh process starts training/serving in milliseconds
        instead of paying XLA compile seconds (docs/COMPILE.md).

        entries: any of "train", "infer"; stepsPerSync=k additionally
        warms the fitDataSet k-loop. cache: an aot.ExecutableCache (or
        None for the session cache, enabling a memory one if none is
        active). autotune=True installs this network's persisted
        autotuned knobs first (docs/AUTOTUNE.md), so the process warms
        the TUNED executables. Returns
        {entry: {key, status cold|warm, seconds}}."""
        return precompile_network(
            self, batchSize=batchSize, featuresShape=featuresShape,
            labelsShape=labelsShape, entries=entries,
            stepsPerSync=stepsPerSync, cache=cache, autotune=autotune)

    # ------------------------------------------------------------------
    # pure functions (traced under jit)
    # ------------------------------------------------------------------
    @property
    def _api_nhwc(self):
        """True when the declared input format is NHWC: then ALL 4-d arrays
        at the API boundary (features, labels, outputs) are NHWC and no
        layout transposes happen anywhere (reference: CNN2DFormat.NHWC)."""
        it = self.conf.inputType
        return (it is not None and it.kind == InputType.CNN
                and getattr(it, "format", "NCHW") == "NHWC")

    def _entry(self, x, already_internal=False):
        """API-format input -> internal format (one transpose at entry).
        already_internal=True: the caller staged the input in the
        internal layout + compute dtype on the HOST (fitDataSet
        canonical staging) — no transpose/convert HLO is emitted, which
        is exactly the layout_copies/dtype_widening traffic the HBM
        attribution charged to this entry."""
        if already_internal:
            return x.astype(self._compute_dtype)  # no-op when staged
        # cast BEFORE the transpose: the relayout then moves compute-
        # dtype bytes, not fp32 — the audit caught the old order as a
        # wide activation-scale transpose
        x = x.astype(self._compute_dtype)
        it = self.conf.inputType
        if it.kind == InputType.CNN and x.ndim == 4:
            if getattr(it, "format", "NCHW") != "NHWC":
                x = jnp.transpose(x, (0, 2, 3, 1))  # NCHW -> NHWC
        elif it.kind == InputType.CNN3D and x.ndim == 5:
            x = jnp.transpose(x, (0, 2, 3, 4, 1))  # NCDHW -> NDHWC
        return x

    def _canon_host(self, x, stacked=False):
        """HOST-side equivalent of _entry: numpy transpose to the
        internal NHWC/NDHWC layout + cast to the compute dtype
        (ml_dtypes bf16 casts round-to-nearest-even exactly like XLA's
        convert, so the staged trajectory is bitwise the legacy one).
        stacked=True shifts every axis by the leading [k] staging dim."""
        x = np.asarray(x)
        it = self.conf.inputType
        o = 1 if stacked else 0
        if it is not None and it.kind == InputType.CNN \
                and x.ndim == 4 + o:
            if getattr(it, "format", "NCHW") != "NHWC":
                x = host_to_nhwc(x, stacked)
        elif it is not None and it.kind == InputType.CNN3D \
                and x.ndim == 5 + o:
            x = host_to_ndhwc(x, stacked)
        return np.ascontiguousarray(
            x.astype(np.dtype(self._compute_dtype), copy=False))

    def _stack_canonical(self, batches):
        """stack_datasets with the feature stack canonicalised on host
        (labels/masks stack unchanged — their layout work is loss-tail
        business and they are batch-scale, not the 46.8 GB bill)."""
        from deeplearning4j_tpu.data.iterators import stack_datasets

        xs, ys, fms, lms = stack_datasets(batches)
        return self._canon_host(xs, stacked=True), ys, fms, lms

    def _cast_params(self, p):
        return cast_params(p, self._compute_dtype, self._param_dtype)

    def _run_layers(self, params, states, x, train, key, fmask,
                    entry_done=False):
        h = self._entry(x, already_internal=entry_done)
        new_states = []
        for i, layer in enumerate(self.layers):
            pp = self.conf.preprocessors.get(i)
            if pp is not None:
                if hasattr(pp, "batch"):
                    pp.batch = x.shape[0]
                h = pp.preProcess(h, fmask)
            # frozen layers (transfer learning) run in inference mode: no
            # dropout, and BN uses+preserves its stored running stats — the
            # reference's FrozenLayer forces the wrapped layer into inference
            # the same way, so the frozen feature extractor cannot drift
            l_train = train and (not getattr(layer, "frozen", False)
                                 or getattr(layer, "frozenKeepTraining",
                                            False))
            lk = None if (key is None or not l_train) else jax.random.fold_in(key, i)
            p = self._cast_params(params[i])
            wn = getattr(layer, "weightNoise", None)
            if wn is not None and lk is not None:
                # train-time weight perturbation (reference: IWeightNoise);
                # pure function of the step key — inference stays clean
                p = wn.apply(p, jax.random.fold_in(lk, 0x5EED))
            if i == len(self.layers) - 1 and isinstance(layer, (L.BaseOutputLayer, L.LossLayer)):
                # dropout applies to the output layer's input too
                h = layer._dropout_input(h, l_train, lk)
                preact = layer.preoutput(p, h)
                new_states.append(states[i])
                return preact, new_states
            if train and getattr(self.conf, "activationCheckpointing", False):
                # rematerialize this layer's activations in the backward
                # pass (jax.checkpoint): l_train/layer are static closures,
                # array args flow through the checkpointed boundary
                h, s = checkpointed_forward(layer, l_train)(
                    p, states[i], h, lk, fmask)
            else:
                h, s = layer.forward(p, states[i], h, l_train, lk, fmask)
            if getattr(self.conf, "checkpointPolicy", None) == \
                    "save_conv_outputs" and isinstance(
                        layer, (L.ConvolutionLayer, L.DenseLayer)):
                # name MXU outputs as the ONLY residuals the train step's
                # jax.checkpoint policy saves (_ckpt_loss_fn) — see
                # nn/graph.py for the policy contract
                from jax.ad_checkpoint import checkpoint_name
                h = checkpoint_name(h, "dl4j_mxu_out")
            new_states.append(s)
        return h, new_states

    def _ckpt_loss_fn(self, use_carries, canonical=False):
        """_loss_fn under the conf's named-residual remat policy when one
        is set (see ComputationGraph._ckpt_loss_fn — same contract)."""
        def base(p, s, x, y, k, fm, lm):
            return self._loss_fn(p, s, x, y, k, fm, lm, use_carries,
                                 canonical)

        if getattr(self.conf, "checkpointPolicy", None) != \
                "save_conv_outputs":
            return base
        policy = jax.checkpoint_policies.save_only_these_names(
            "dl4j_mxu_out")
        return jax.checkpoint(base, policy=policy)

    def _loss_from_preact(self, preact, labels, lmask):
        last = self.layers[-1]
        if hasattr(last, "computeLoss"):
            # composite-loss heads (e.g. objdetect.Yolo2OutputLayer) own
            # their full loss computation and expect the reference's NCHW
            # label layout — restore it for NHWC-format networks. Their
            # multi-term math is not covered by the losses.py fp32-
            # accumulator policy, so they always run wide regardless of
            # the tail mode (activation-scale, but one head tensor).
            wdt = jnp.promote_types(preact.dtype, jnp.float32)
            preact, labels = preact.astype(wdt), labels.astype(wdt)
            if self._api_nhwc and labels.ndim == 4:
                labels = jnp.transpose(labels, (0, 3, 1, 2))
            return last.computeLoss(preact, labels, lmask)
        if isinstance(last, (L.BaseOutputLayer, L.LossLayer)):
            if preact.ndim == 3:  # RnnOutputLayer: [B,O,T] -> loss over [B,T,O]
                pre = jnp.transpose(preact, (0, 2, 1))
                lab = jnp.transpose(labels, (0, 2, 1))
                return _losses.compute(last.lossFunction, lab, pre,
                                       last.activation, lmask)
            if preact.ndim == 4:  # CnnLossLayer: NHWC preact; labels are
                # NCHW from the API unless the net declares NHWC
                lab = labels if self._api_nhwc else \
                    jnp.transpose(labels, (0, 2, 3, 1))
                return _losses.compute(last.lossFunction, lab, preact,
                                       last.activation, lmask)
            return _losses.compute(last.lossFunction, labels, preact,
                                   last.activation, lmask)
        raise ValueError("Final layer must be an OutputLayer/LossLayer to compute loss")

    def _regularization(self, params):
        reg = 0.0
        for layer, p in zip(self.layers, params):
            if p and not getattr(layer, "frozen", False):
                reg = reg + layer.regularization(p)
        return reg

    def _tail_cast(self, preact, y):
        """(preact, labels) cast for the loss tail: both to tail_dtype,
        EXCEPT labels of a composite head (computeLoss) — those heads
        re-widen to fp32 in _loss_from_preact, so downcasting their
        fp32 labels (box coordinates etc.) here would round them for
        nothing."""
        ldt = _losses.tail_dtype(preact.dtype)
        labels = _unwrap(y)
        if not hasattr(self.layers[-1], "computeLoss"):
            labels = labels.astype(ldt)
        return preact.astype(ldt), labels

    def _loss_fn(self, params, states, x, y, key, fmask, lmask, use_carries,
                 canonical=False):
        # frozen layers (transfer learning): structurally zero grads — XLA
        # dead-code-eliminates their whole backward pass, which is the TPU
        # equivalent of the reference's FrozenLayer wrapper skipping backprop
        params = [jax.tree_util.tree_map(jax.lax.stop_gradient, p)
                  if getattr(l, "frozen", False) else p
                  for l, p in zip(self.layers, params)]
        run_states = states if use_carries else self._strip_carries(states)
        preact, new_states = self._run_layers(params, run_states, x, True,
                                              key, fmask,
                                              entry_done=canonical)
        # loss-tail dtype policy (round 6): under bf16 compute the
        # activation-scale loss math stays bf16 — fp32 appears only in
        # the fused reduce accumulators inside nn/losses (tail_dtype
        # returns fp32 in "wide" mode and for fp32/fp64 nets, where the
        # old promote-to-fp32 behaviour is unchanged)
        loss = self._loss_from_preact(*self._tail_cast(preact, y), lmask)
        loss = loss + self._regularization(params)
        return loss, new_states

    def _train_step(self, params, upd_states, states, iteration, x, y, key,
                    fmask, lmask, use_carries=False, grad_transform=None,
                    loss_transform=None, state_transform=None,
                    canonical_inputs=False):
        """The fused step. The *_transform hooks let distributed wrappers
        (parallel.trainer) splice in an explicit cross-shard allreduce /
        pmean without duplicating the updater loop. canonical_inputs=True
        asserts x is already in the internal layout + compute dtype
        (fitDataSet host staging) and skips the entry transpose/convert."""
        (loss, new_states), grads = jax.value_and_grad(
            self._ckpt_loss_fn(use_carries, canonical_inputs),
            has_aux=True)(
            params, states, x, y, key, fmask, lmask)
        if grad_transform is not None:
            grads = grad_transform(grads)
        if loss_transform is not None:
            loss = loss_transform(loss)
        if state_transform is not None:
            new_states = state_transform(new_states)
        if self._solver is not None:
            # LBFGS / CG / line search: one whole-pytree step; the line
            # search re-evaluates THIS batch's loss (same dropout key),
            # so grads stay un-normalized — they must be the true
            # gradient of value_fn for the Wolfe/Armijo conditions
            from deeplearning4j_tpu.nn import solvers as _solvers

            def value_fn(ps):
                return self._ckpt_loss_fn(use_carries, canonical_inputs)(
                    ps, states, x, y, key, fmask, lmask)[0]

            new_params, new_upd = _solvers.solver_update(
                self._solver, grads, upd_states, params, loss, value_fn)
            for i, layer in enumerate(self.layers):
                if getattr(layer, "frozen", False):
                    # safety net, normally a no-op: frozen grads enter the
                    # solver structurally zero (_loss_fn stop_gradient),
                    # and zero-grad coordinates of a fresh L-BFGS/CG state
                    # stay zero inductively (direction, s/y pairs), so the
                    # recorded step already matches the applied step —
                    # invariant pinned by test_solvers.py::TestFrozenUnderSolver
                    new_params[i] = params[i]
                cs = getattr(layer, "constraints", None)
                if cs and new_params[i]:
                    from deeplearning4j_tpu.nn.conf.constraint import \
                        apply_constraints
                    new_params[i] = apply_constraints(cs, new_params[i])
            return new_params, new_upd, new_states, loss
        grads = _grad_normalize(grads, self.conf.gradientNormalization,
                                self.conf.gradientNormalizationThreshold)
        # the weight-update hook: a distributed trainer may install
        # parallel.sharding.ZeroShardedUpdate here to run the optimizer
        # on 1/dp shards (reduce-scatter -> shard update -> all-gather);
        # default is the plain apply-and-subtract below. Read at trace
        # time; the hook changes the updater-state SHAPES, so a stale
        # jit cache cannot silently keep the old program.
        update_impl = getattr(self, "_update_impl", None) \
            or default_param_update
        new_params, new_upd_states = [], []
        for i in range(len(self.layers)):
            if not params[i] or getattr(self.layers[i], "frozen", False):
                new_params.append(params[i])
                new_upd_states.append(upd_states[i])
                continue
            np_i, us = update_impl(self._updaters[i], grads[i],
                                   upd_states[i], iteration, params[i])
            cs = getattr(self.layers[i], "constraints", None)
            if cs:
                from deeplearning4j_tpu.nn.conf.constraint import apply_constraints
                np_i = apply_constraints(cs, np_i)
            new_params.append(np_i)
            new_upd_states.append(us)
        return new_params, new_upd_states, new_states, loss

    @staticmethod
    def _out_act(layer, pre):
        """Apply the output activation over the CLASS axis. NCW [B,O,T]
        recurrent output needs softmax over O, not the trailing time axis."""
        from deeplearning4j_tpu.nn import activations as _act

        if hasattr(layer, "outputFromPreact"):
            # composite heads (CenterLossOutputLayer) carry extra channels
            # in the preact that the user-visible output must drop
            return layer.outputFromPreact(pre)
        act = _act.get(layer.activation)
        if pre.ndim == 3:
            return jnp.transpose(act(jnp.transpose(pre, (0, 2, 1))), (0, 2, 1))
        return act(pre)

    def _forward_infer(self, params, states, x, fmask=None):
        last = self.layers[-1]
        preact_or_h, _ = self._run_layers(params, self._strip_carries(states),
                                          x, False, None, fmask)
        if isinstance(last, (L.BaseOutputLayer, L.LossLayer)):
            return self._out_act(last, preact_or_h)
        return preact_or_h

    def _loss_only(self, params, states, x, y, fmask=None, lmask=None):
        preact, _ = self._run_layers(params, self._strip_carries(states),
                                     x, False, None, fmask)
        loss = self._loss_from_preact(*self._tail_cast(preact, y), lmask)
        return loss + self._regularization(params)

    @staticmethod
    def _strip_carries(states):
        return strip_carries(states)

    # ------------------------------------------------------------------
    # public API (reference signatures)
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, epochs=None):
        """fit(x, y) | fit(DataSet) | fit(DataSetIterator[, epochs])."""
        from deeplearning4j_tpu.data.dataset import DataSet

        if labels is not None:
            ds = DataSet(data, labels)
            self._fit_batch(ds)
            return self
        if isinstance(data, DataSet):
            self._fit_batch(data)
            return self
        # iterator
        n_epochs = epochs or 1
        for _ in range(n_epochs):
            data.reset()
            for lst in self._listeners:
                getattr(lst, "onEpochStart", lambda m: None)(self)
            fit_iterator_epoch(self, data, self._step)
            for lst in self._listeners:
                getattr(lst, "onEpochEnd", lambda m: None)(self)
            self._epoch += 1
        return self

    def _require_init(self):
        if self._params is None:
            raise RuntimeError(
                "Network is not initialized — call net.init() before "
                "fit/output/score (reference: MultiLayerNetwork.init())")

    @staticmethod
    def _extract_ds(ds):
        """(features, labels, features mask, labels mask) of a DataSet
        as arrays."""
        return (_unwrap(ds.getFeatures()), _unwrap(ds.getLabels()),
                _unwrap(ds.getFeaturesMaskArray()),
                _unwrap(ds.getLabelsMaskArray()))

    def _fit_batch(self, ds):
        self._step(*self._extract_ds(ds))

    def _step(self, x, y, fmask, lmask):
        self._require_init()
        if self.conf.backpropType == BackpropType.TruncatedBPTT and x.ndim == 3:
            self._fit_tbptt(x, y, fmask, lmask)
            return
        traced_train_step(self, x, y, fmask, lmask)

    def _fit_tbptt(self, x, y, fmask, lmask):
        """Truncated BPTT: split time into tbpttFwdLength chunks, carrying
        h/c across chunks (reference: MultiLayerNetwork.doTruncatedBPTT)."""

        def jit_call(sl, key, it, use_carries):
            self._params, self._upd_states, self._states, loss = self._jit_train(
                self._params, self._upd_states, self._states, it,
                x[:, :, sl], y[:, :, sl], key,
                None if fmask is None else fmask[:, sl],
                None if lmask is None else lmask[:, sl],
                use_carries=use_carries)
            return loss

        run_tbptt(self, x.shape[2], self.conf.tbpttFwdLength, jit_call)

    def fitSteps(self, data, labels=None, numSteps=1):
        """TPU-native k-step fit: run `numSteps` optimizer steps on ONE
        batch entirely on device (lax.fori_loop) and sync the loss to the
        host once per call.

        No upstream analog — upstream fit() pays a host round-trip per
        iteration, which is correct fit() semantics but lets the
        per-step host sync dominate small models. This is the
        framework-native loop for that regime. Semantics match numSteps
        consecutive fit() calls on the same batch: the dropout/noise key
        advances per step from the same fold_in stream, the iteration
        counter feeds the updater schedules, and tBPTT nets run their
        full window sweep (carries reset per sequence) per step.
        Listeners fire once at the end with the final loss.
        """
        from deeplearning4j_tpu.data.dataset import DataSet

        self._require_init()
        ds = DataSet(data, labels) if labels is not None else data
        x = _unwrap(ds.getFeatures())
        y = _unwrap(ds.getLabels())
        fmask = _unwrap(ds.getFeaturesMaskArray())
        lmask = _unwrap(ds.getLabelsMaskArray())
        tbptt = (self.conf.backpropType == BackpropType.TruncatedBPTT
                 and x.ndim == 3)
        if tbptt:
            T, L = x.shape[2], self.conf.tbpttFwdLength
            if T % L != 0:
                raise ValueError(
                    f"fitSteps tBPTT needs seq len divisible by "
                    f"tbpttFwdLength (got T={T}, L={L}): the on-device "
                    "window sweep uses fixed-size dynamic slices. Use "
                    "fit() for ragged tails.")
            n_win = T // L
        else:
            n_win = 1
        cache = getattr(self, "_fit_steps_cache", None)
        if cache is None:
            cache = self._fit_steps_cache = {}
        # n_win is baked into the traced loop body, so it must key the
        # cache alongside numSteps (jit's own shape retrace would reuse
        # the wrong closure constant)
        jloop = cache.get((numSteps, n_win))
        if jloop is None:
            seed_key = jax.random.key(self.conf.seed ^ 0x5EED)

            def loop(params, upd, states, it0, x, y, fmask, lmask):
                def window(carry, step_i, win_i, use_carries):
                    p, u, s, _ = carry
                    it = it0 + step_i * n_win + win_i
                    key = jax.random.fold_in(seed_key, it)
                    if n_win == 1:
                        xs, ys, fs, ls = x, y, fmask, lmask
                    else:
                        L = self.conf.tbpttFwdLength
                        sl = lambda a, ax: None if a is None else \
                            jax.lax.dynamic_slice_in_dim(a, win_i * L, L, ax)
                        xs, ys, fs, ls = sl(x, 2), sl(y, 2), \
                            sl(fmask, 1), sl(lmask, 1)
                    p, u, s, loss = self._train_step(
                        p, u, s, it, xs, ys, key, fs, ls,
                        use_carries=use_carries)
                    return (p, u, s, loss.astype(jnp.float32))

                def body(i, carry):
                    # window 0 strips carries (fresh sequence); later
                    # tbptt windows carry h/c across the chunk boundary
                    carry = window(carry, i, 0, False)
                    if n_win > 1:
                        carry = jax.lax.fori_loop(
                            1, n_win,
                            lambda w, c: window(c, i, w, True), carry)
                    # fori_loop needs a structure-stable carry: the step
                    # ADDS h/c entries to states; strip them at sequence
                    # end (use_carries=False re-strips inside the step
                    # anyway, and persistent state like BN stats survives)
                    p, u, s, loss = carry
                    return (p, u, self._strip_carries(s), loss)

                return jax.lax.fori_loop(
                    0, numSteps, body,
                    (params, upd, self._strip_carries(states),
                     jnp.float32(0)))

            jloop = jax.jit(
                loop,
                donate_argnums=(0, 1, 2) if self._solver is None else (2,))
            cache[(numSteps, n_win)] = jloop
        self._params, self._upd_states, self._states, loss = jloop(
            self._params, self._upd_states, self._states,
            jnp.asarray(self._iteration, jnp.int32), x, y, fmask, lmask)
        self._score = float(loss)
        self._iteration += numSteps * n_win
        # no post-loop carry strip needed: the loop body strips at the
        # end of every step to keep the fori carry structure stable
        for lst in self._listeners:
            lst.iterationDone(self, self._iteration, self._epoch)
        return self

    def fitDataSet(self, iterator, stepsPerSync=1, epochs=None):
        """Epoch training with ONE host sync and ONE device transfer per
        `stepsPerSync` fresh batches: pull k batches from the iterator,
        stage them as a stacked [k, B, ...] device buffer, and run a
        single jitted lax.fori_loop that indexes batch i per step with
        the donated param/updater carry — fit(iterator) semantics
        (same trajectory, RNG stream, iteration counters, listener
        replay) without the per-batch dispatch+fetch tax fitSteps only
        removed for repeated batches. Staging is double-buffered: stack
        n+1's async device_put is in flight before the host blocks on
        stack n's losses. The ragged final stack (< k batches) runs
        through plain fit(), so the k-loop compiles exactly once.

        stepsPerSync=1 is exactly fit(iterator). The total host-sync
        count of the call (one per k-block + one per tail batch) is
        recorded on `self._fit_dataset_syncs`.
        """
        from deeplearning4j_tpu.data.iterators import stack_datasets

        self._require_init()
        k = int(stepsPerSync)
        if k < 1:
            raise ValueError(f"stepsPerSync must be >= 1, got {k}")
        if k == 1:
            it0 = self._iteration
            self.fit(iterator, epochs=epochs)
            self._fit_dataset_syncs = self._iteration - it0  # 1/batch
            return self
        if self.conf.backpropType == BackpropType.TruncatedBPTT:
            raise ValueError(
                "fitDataSet does not support truncated BPTT: the k-batch "
                "stack would need a second on-device window sweep per "
                "step; use fit() (per-batch windows) or fitSteps()")
        # layout hygiene (round 6): canonicalise the staged stack on the
        # host (internal layout + compute dtype) so the k-loop program
        # carries no per-step entry transpose/convert — see
        # _CANON_STAGING for the A/B toggle
        canon = canon_staging_on()
        jloop = fit_dataset_jit(self, k, canonical=canon)
        stack = self._stack_canonical if canon else stack_datasets
        self._fit_dataset_syncs = 0
        for _ in range(epochs or 1):
            iterator.reset()
            for lst in self._listeners:
                getattr(lst, "onEpochStart", lambda m: None)(self)
            self._fit_dataset_syncs += run_fit_dataset_epoch(
                self, iterator, k, stack, self._fit_batch, jloop)
            for lst in self._listeners:
                getattr(lst, "onEpochEnd", lambda m: None)(self)
            self._epoch += 1
        return self

    # ----- unsupervised layerwise pretraining (VAE etc.) --------------
    def _frozen_feed(self, layerIdx, x, params=None, states=None):
        """The input layers[layerIdx] would receive: frozen inference
        forward of the preceding layers with every input preprocessor
        applied — INCLUDING layerIdx's own (shared by pretrainLayer and
        reconstructionLogProbability). params/states may be passed
        explicitly so a jitted caller traces them as ARGUMENTS — read
        through self they would bake in as compile-time constants and
        go stale after further training."""
        params = self._params if params is None else params
        states = (self._strip_carries(self._states) if states is None
                  else states)
        h = self._entry(x)
        for j in range(layerIdx + 1):
            pp = self.conf.preprocessors.get(j)
            if pp is not None:
                if hasattr(pp, "batch"):
                    pp.batch = x.shape[0]
                h = pp.preProcess(h, None)
            if j < layerIdx:
                h, _ = self.layers[j].forward(
                    self._cast_params(params[j]), states[j], h,
                    False, None, None)
        return h

    def reconstructionLogProbability(self, data, numSamples=5, layerIdx=0):
        """Per-example log p(x) estimate from a VariationalAutoencoder
        layer (reference: the upstream anomaly-detection workflow —
        net.getLayer(0).reconstructionLogProbability(data, K)). Higher
        is more in-distribution. The frozen forward of preceding layers
        + the VAE estimate compile into ONE cached jitted program per
        (layerIdx, numSamples)."""
        self._require_init()
        layer = self.layers[layerIdx]
        if not hasattr(layer, "reconstructionLogProbability"):
            raise ValueError(
                f"Layer {layerIdx} ({type(layer).__name__}) is not a "
                "VariationalAutoencoder")
        if not hasattr(self, "_rlp_jit"):
            self._rlp_jit = {}
        fn = self._rlp_jit.get((layerIdx, int(numSamples)))
        if fn is None:
            fn = jax.jit(
                lambda ps, sts, x, k: layer.reconstructionLogProbability(
                    self._cast_params(ps[layerIdx]),
                    self._frozen_feed(layerIdx, x, ps, sts),
                    int(numSamples), k))
            self._rlp_jit[(layerIdx, int(numSamples))] = fn
        return INDArray(fn(self._params,
                           self._strip_carries(self._states),
                           _unwrap(data), jax.random.key(0)))

    def pretrain(self, iterator, epochs=1):
        """Layerwise unsupervised pretraining of every pretrainable layer
        (reference: MultiLayerNetwork.pretrain(DataSetIterator) — upstream
        this is how VariationalAutoencoder layers train)."""
        for i, layer in enumerate(self.layers):
            if getattr(layer, "pretrainable", False):
                self.pretrainLayer(i, iterator, epochs)
        return self

    def pretrainLayer(self, layerIdx, data, epochs=1):
        """Unsupervised pretraining of one layer: its input is the frozen
        forward of the preceding layers; its params train against the
        layer's own pretrain_loss (negative ELBO for VAE) in a donated
        jitted step (reference: MultiLayerNetwork.pretrainLayer)."""
        self._require_init()
        if self._solver is not None:
            raise ValueError(
                "layerwise pretraining uses the per-layer updater path; "
                "it is not defined under a whole-pytree "
                f"optimizationAlgo ({self.conf.optimizationAlgo}) — "
                "pretrain with STOCHASTIC_GRADIENT_DESCENT, then fine-"
                "tune with the solver")
        layer = self.layers[layerIdx]
        if not getattr(layer, "pretrainable", False):
            raise ValueError(f"Layer {layerIdx} "
                             f"({type(layer).__name__}) is not pretrainable")

        def feed(x):
            return self._frozen_feed(layerIdx, x)

        upd = self._updaters[layerIdx]

        @jax.jit
        def pre_step(p, us, it, x, key):
            loss, g = jax.value_and_grad(
                lambda p_: layer.pretrain_loss(self._cast_params(p_),
                                               feed(x), key))(p)
            d, us = upd.apply(g, us, it, params=p)
            p = jax.tree_util.tree_map(
                lambda a, b: (a - b).astype(a.dtype), p, d)
            return p, us, loss

        from deeplearning4j_tpu.data.dataset import DataSet

        batches = None
        if isinstance(data, DataSet):
            batches = [data]
        elif hasattr(data, "hasNext"):
            pass  # iterator: re-drawn per epoch below
        else:
            batches = [DataSet(data, None)]
        p, us = self._params[layerIdx], self._upd_states[layerIdx]
        loss = float("nan")

        def one(ds, p, us):
            x = _unwrap(ds.getFeatures())
            key = jax.random.fold_in(
                jax.random.key(self.conf.seed ^ 0xE1B0), self._iteration)
            p, us, loss = pre_step(
                p, us, jnp.asarray(self._iteration, jnp.int32), x, key)
            self._iteration += 1
            return p, us, loss

        for _ in range(epochs):
            if batches is None:
                data.reset()
                while data.hasNext():
                    p, us, loss = one(data.next(), p, us)
            else:
                for ds in batches:
                    p, us, loss = one(ds, p, us)
        self._params[layerIdx], self._upd_states[layerIdx] = p, us
        self._score = float(loss)
        return self

    def output(self, x, train=False) -> INDArray:
        self._require_init()
        out = self._jit_forward(self._params, self._states, _unwrap(x))
        return INDArray(out)

    def feedForward(self, x) -> list:
        """All layer activations (eager; reference returns the list)."""
        x = _unwrap(x)
        h = self._entry(x)
        acts = [INDArray(h)]
        states = self._strip_carries(self._states)
        for i, layer in enumerate(self.layers):
            pp = self.conf.preprocessors.get(i)
            if pp is not None:
                if hasattr(pp, "batch"):
                    pp.batch = x.shape[0]
                h = pp.preProcess(h, None)
            h, _ = layer.forward(self._cast_params(self._params[i]), states[i],
                                 h, False, None, None)
            acts.append(INDArray(h))
        return acts

    def score(self, dataset=None) -> float:
        if dataset is None:
            return getattr(self, "_score", float("nan"))
        x = _unwrap(dataset.getFeatures())
        y = _unwrap(dataset.getLabels())
        return float(self._jit_loss(self._params, self._states, x, y,
                                    _unwrap(dataset.getFeaturesMaskArray()),
                                    _unwrap(dataset.getLabelsMaskArray())))

    def computeGradientAndScore(self, x, y):
        """(grads, score) for gradient checks (reference:
        Model.computeGradientAndScore)."""
        (loss, _), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
            self._params, self._states, _unwrap(x), _unwrap(y), None, None, None, False)
        return grads, float(loss)

    def doEvaluation(self, iterator, *evaluations):
        """Stream the iterator through output() into any number of
        IEvaluation instances (reference: MultiLayerNetwork.doEvaluation)."""
        if not evaluations:
            raise ValueError("doEvaluation needs at least one IEvaluation")
        iterator.reset()
        while iterator.hasNext():
            ds = iterator.next()
            out = self.output(ds.getFeatures())
            for e in evaluations:
                e.eval(ds.getLabels(), out, mask=ds.getLabelsMaskArray())
        return evaluations if len(evaluations) > 1 else evaluations[0]

    def evaluate(self, iterator):
        from deeplearning4j_tpu.evaluation.evaluation import Evaluation

        return self.doEvaluation(iterator, Evaluation())

    def evaluateRegression(self, iterator):
        from deeplearning4j_tpu.evaluation.regression import RegressionEvaluation

        return self.doEvaluation(iterator, RegressionEvaluation())

    def evaluateROC(self, iterator, thresholdSteps=0):
        from deeplearning4j_tpu.evaluation.roc import ROC

        return self.doEvaluation(iterator, ROC(thresholdSteps))

    def evaluateROCMultiClass(self, iterator, thresholdSteps=0):
        from deeplearning4j_tpu.evaluation.roc import ROCMultiClass

        return self.doEvaluation(iterator, ROCMultiClass(thresholdSteps))

    # ----- rnn stateful inference -------------------------------------
    def rnnTimeStep(self, x) -> INDArray:
        """Stateful single/multi-step inference for generation
        (reference: MultiLayerNetwork.rnnTimeStep)."""
        x = _unwrap(x)
        squeeze_out = x.ndim == 2
        if squeeze_out:
            x = x[:, :, None]
        states = self._rnn_state if self._rnn_state is not None \
            else self._strip_carries(self._states)
        h = self._entry(x)
        new_states = []
        for i, layer in enumerate(self.layers):
            pp = self.conf.preprocessors.get(i)
            if pp is not None:
                if hasattr(pp, "batch"):
                    pp.batch = x.shape[0]
                h = pp.preProcess(h, None)
            if i == len(self.layers) - 1 and isinstance(layer, (L.BaseOutputLayer, L.LossLayer)):
                pre = layer.preoutput(self._cast_params(self._params[i]), h)
                h = self._out_act(layer, pre)
                new_states.append(states[i])
                break
            h, s = layer.forward(self._cast_params(self._params[i]), states[i],
                                 h, False, None, None)
            new_states.append(s)
        self._rnn_state = new_states
        if squeeze_out and h.ndim == 3:
            h = h[:, :, 0]  # 2d in -> 2d out, like the reference
        return INDArray(h)

    def rnnClearPreviousState(self):
        self._rnn_state = None

    # ----- functional slot-batched rnn step (serving/sequence.py) -----
    def rnnCarrySpec(self):
        """Per-layer carry-key tuples for the functional stepwise path:
        ``("h", "c")`` for LSTM-family layers, ``("h",)`` for
        SimpleRnn/GRU, ``()`` for everything else. Raises for nets whose
        recurrent state is not a flat per-layer h/c carry (Bidirectional
        needs the whole sequence, so stepwise decode is ill-defined for
        it — same limit the stateful ``rnnTimeStep`` has, made loud)."""
        from deeplearning4j_tpu.nn.conf import recurrent as R

        spec = []
        for i, layer in enumerate(self.layers):
            if isinstance(layer, (R.Bidirectional, R.LastTimeStep)):
                raise ValueError(
                    f"layer {i} ({type(layer).__name__}) wraps its "
                    "recurrent state: stepwise decode needs causal "
                    "flat h/c carries (LSTM/GravesLSTM/SimpleRnn/GRU)")
            if isinstance(layer, R.LSTM):
                spec.append(("h", "c"))
            elif isinstance(layer, R.BaseRecurrentLayer):
                spec.append(("h",))
            else:
                spec.append(())
        if not any(spec):
            raise ValueError(
                "no recurrent layers: nothing carries state between "
                "steps — serve this net through the one-shot tier")
        return tuple(spec)

    def rnnCarryZeros(self, batch):
        """Materialized zero carries (``[batch, nOut]`` per recurrent
        layer, compute dtype) — the state a fresh sequence starts from.
        Bitwise the state the scans synthesize from ``h0=None``, but as
        explicit arrays so the slot scheduler can gather/scatter them
        and the stepwise jit signature stays fixed per slot bucket."""
        out = []
        for keys, layer in zip(self.rnnCarrySpec(), self.layers):
            H = getattr(layer, "nOut", None)
            out.append({k: jnp.zeros((int(batch), int(H)),
                                     self._compute_dtype) for k in keys})
        return out

    def _rnn_step(self, params, states, carries, x):
        """PURE single-timestep forward: x [S, F] (one step per slot
        row), carries = rnnCarrySpec-shaped h/c arrays [S, H]. Returns
        (out [S, O], new_carries). The functional twin of rnnTimeStep —
        no ``self._rnn_state`` mutation, so one jitted executable per
        slot-count bucket serves ANY occupancy: rows are independent
        (per-row matmuls + elementwise cells), a zero-padded slot can
        never perturb a live one."""
        h = self._entry(x[:, :, None])
        spec = self.rnnCarrySpec()
        new_carries = []
        for i, layer in enumerate(self.layers):
            pp = self.conf.preprocessors.get(i)
            if pp is not None:
                if hasattr(pp, "batch"):
                    pp.batch = x.shape[0]
                h = pp.preProcess(h, None)
            if i == len(self.layers) - 1 \
                    and isinstance(layer, (L.BaseOutputLayer, L.LossLayer)):
                pre = layer.preoutput(self._cast_params(params[i]), h)
                h = self._out_act(layer, pre)
                new_carries.append({})
                break
            st = {**states[i], **carries[i]}
            h, s = layer.forward(self._cast_params(params[i]), st, h,
                                 False, None, None)
            new_carries.append({k: s[k] for k in spec[i]})
        if h.ndim == 3:
            h = h[:, :, 0]
        return h, new_carries

    def rnnStepBatched(self, x, carries):
        """One decode step for a slot batch: x [S, F] (slot-count-
        bucketed), carries from rnnCarryZeros/previous steps. Returns
        (out [S, O] jax array, new_carries). Jitted through the AOT
        executable cache (entry ``rnn_step``) — one compile per slot
        bucket, warmable via ``CachedJit.warm`` before traffic
        (serving/sequence.py does exactly that)."""
        return self._jit_rnn_step(self._params,
                                  self._strip_carries(self._states),
                                  carries, jnp.asarray(x))

    # ----- introspection ----------------------------------------------
    def params(self) -> INDArray:
        leaves = jax.tree_util.tree_leaves(self._params)
        if not leaves:
            return Nd4j.empty()
        return INDArray(jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves]))

    def numParams(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self._params))

    def paramTable(self) -> dict:
        out = {}
        for i, p in enumerate(self._params):
            for k, v in p.items():
                out[f"{i}_{k}"] = INDArray(v)
        return out

    def setParams(self, flat):
        """Inverse of params(): set all parameters from one flat vector
        (reference: Model.setParams). Leaf order matches params()."""
        leaves, treedef = jax.tree_util.tree_flatten(self._params)
        vec = np.asarray(_unwrap(flat)).reshape(-1)
        if vec.size != sum(int(np.prod(l.shape)) for l in leaves):
            raise ValueError(
                f"setParams: got {vec.size} values for "
                f"{self.numParams()} parameters")
        new, off = [], 0
        for l in leaves:
            n = int(np.prod(l.shape))
            new.append(jnp.asarray(vec[off:off + n], l.dtype).reshape(l.shape))
            off += n
        self._params = jax.tree_util.tree_unflatten(treedef, new)
        return self

    def getParam(self, key: str):
        """One parameter by "layerIndex_name" key (reference:
        Model.getParam, e.g. "0_W")."""
        i, _, name = key.partition("_")
        return INDArray(self._params[int(i)][name])

    def setParamTable(self, table: dict):
        """Assign parameters by "layerIndex_name" keys (reference:
        Model.setParamTable). Shapes must match the existing table."""
        for key, v in table.items():
            i, _, name = key.partition("_")
            i = int(i)
            cur = self._params[i][name]
            arr = jnp.asarray(_unwrap(v), cur.dtype)
            if arr.shape != cur.shape:
                raise ValueError(
                    f"setParamTable: {key} has shape {arr.shape}, "
                    f"expected {cur.shape}")
            self._params[i] = {**self._params[i], name: arr}
        return self

    def clone(self):
        """Independent copy with the same configuration and parameters
        (reference: MultiLayerNetwork.clone). Buffers are COPIED —
        fit() donates the original's arrays to XLA, so a buffer-sharing
        clone would die on the original's next train step."""
        # initFrom, not init(): a full random re-initialization would
        # be computed and immediately overwritten
        copy = lambda x: jnp.copy(x) if hasattr(x, "shape") else x
        net = MultiLayerNetwork(self.conf).initFrom(
            jax.tree_util.tree_map(copy, self._params),
            jax.tree_util.tree_map(copy, self._states),
            jax.tree_util.tree_map(copy, self._upd_states))
        # training position travels with the updater moments: a clone
        # resuming at iteration 0 would restart LR schedules and repeat
        # the dropout key stream
        net._iteration = self._iteration
        net._epoch = self._epoch
        return net

    def getLayers(self):
        return self.layers

    def getnLayers(self) -> int:
        return len(self.layers)

    def setListeners(self, *listeners):
        self._listeners = list(listeners)
        return self

    def addListeners(self, *listeners):
        self._listeners.extend(listeners)
        return self

    def getIterationCount(self) -> int:
        return self._iteration

    def getEpochCount(self) -> int:
        return self._epoch

    def save(self, path, saveUpdater: bool = True):
        """Reference: MultiLayerNetwork.save(File, saveUpdater)."""
        from deeplearning4j_tpu.util.serializer import ModelSerializer

        ModelSerializer.writeModel(self, path, saveUpdater)
        return self

    @staticmethod
    def load(path, loadUpdater: bool = True) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.util.serializer import ModelSerializer

        return ModelSerializer.restoreMultiLayerNetwork(path, loadUpdater)

    def summary(self) -> str:
        lines = [f"{'idx':<4}{'type':<28}{'out shape':<24}{'params':<12}"]
        total = 0
        for i, layer in enumerate(self.layers):
            n = sum(int(np.prod(v.shape)) for v in self._params[i].values()) if self._params else 0
            total += n
            ot = layer.getOutputType(self.conf.layerInputTypes[i])
            lines.append(f"{i:<4}{type(layer).__name__:<28}{str(ot):<24}{n:<12}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)
