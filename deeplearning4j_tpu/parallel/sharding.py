"""Parameter sharding rules for model (tensor) parallelism.

Reference: none — the reference is data-parallel only (its multi-GPU and
Spark paths replicate the full model). Tensor parallelism is a TPU-first
capability: parameters are annotated with PartitionSpecs over the mesh
"model" axis and XLA's SPMD partitioner (GSPMD; see PAPERS.md sharding
papers) propagates shardings through the computation and inserts the
all-gather / reduce-scatter collectives over ICI.

Rules follow the Megatron layout:
  dense W [in, out]      -> P(None, "model")   (column parallel)
  conv  W [kh,kw,ci,co]  -> P(None,None,None,"model")
  lstm  W/RW [in, 4H]    -> P(None, "model")
  biases/gains [out]     -> P("model") when their dim is sharded
Small params (< min_shard_size) stay replicated — collective latency beats
the memory win.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, GROUP_AXIS, INTRA_AXIS, MODEL_AXIS,
)


def shard_batch(arr, mesh: Mesh, batch_axis=DATA_AXIS, dim=0):
    """Place one batch array with dim `dim` sharded over `batch_axis`
    (one axis name, or a tuple of axis names for a factored data axis —
    the hierarchical trainer shards the batch over ("group", "intra")).

    REJECTS indivisible batches with an error naming the axis instead
    of letting the placement silently pad (uneven GSPMD tiling pads the
    trailing shard with garbage rows that would train): the same check
    the partition-plan analyzer reports statically as PAR03, enforced
    at the runtime boundary every trainer shares."""
    axes = batch_axis if isinstance(batch_axis, tuple) else (batch_axis,)
    width = 1
    for ax in axes:
        if ax not in mesh.shape:
            raise ValueError(
                f"mesh has no axis '{ax}' (axes: "
                f"{list(mesh.shape)}); build the mesh with a "
                "data-parallel axis or pass batch_axis=")
        width *= mesh.shape[ax]
    if arr.shape[dim] % width != 0:
        raise ValueError(
            f"Global batch {arr.shape[dim]} not divisible by "
            f"data-parallel mesh axis '{batch_axis}' (size {width}): "
            "refusing to silently pad; use a batch size that is a "
            f"multiple of {width} (PAR03)")
    spec = [None] * arr.ndim
    spec[dim] = batch_axis
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


def shard_batch_stack(tree, mesh: Mesh, batch_axis=DATA_AXIS):
    """Place a fitDataSet staging stack — a pytree of [k, B, ...] arrays
    (None components pass through) — with the BATCH dim (dim 1) sharded
    over `batch_axis` and the k staging dim replicated, through the same
    divisibility-checked shard_batch every trainer uses. Each of the k
    steps of the on-device loop then indexes a correctly-sharded global
    batch."""
    import jax.tree_util as jtu

    return jtu.tree_map(
        lambda a: shard_batch(a, mesh, batch_axis=batch_axis, dim=1), tree)


def spec_for_param(name: str, shape, model_axis=MODEL_AXIS, min_shard_size=2 ** 16):
    """PartitionSpec for one parameter array by name/shape convention."""
    if int(np.prod(shape)) < min_shard_size:
        return P()
    if len(shape) == 2:
        # dense / recurrent / embedding weights: shard the output dim
        return P(None, model_axis)
    if len(shape) == 4:
        # conv HWIO: shard output channels
        return P(None, None, None, model_axis)
    if len(shape) == 1:
        return P(model_axis)
    return P()


def shard_params(params, mesh: Mesh, model_axis=MODEL_AXIS,
                 min_shard_size=2 ** 16, on_indivisible="replicate"):
    """Annotate+place a params pytree (list/dict of per-layer dicts) onto
    the mesh with tensor-parallel shardings; returns the placed pytree.

    on_indivisible: what to do when a selected dim does not divide by
    the model-axis size — "replicate" (default; GSPMD requires even
    tiling, and replication is always correct) or "error" to fail
    loudly naming the axis (the strict mode a validated plan uses)."""
    if on_indivisible not in ("replicate", "error"):
        raise ValueError("on_indivisible must be 'replicate' or 'error'")

    def place(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        # shard only when divisible; otherwise replicate (GSPMD requires
        # even tiling for the annotated dim)
        spec = spec_for_param(name, leaf.shape, model_axis, min_shard_size)
        width = mesh.shape[model_axis]
        ok = True
        for dim, axis in zip(leaf.shape, tuple(spec) + (None,) * (leaf.ndim - len(spec))):
            if axis == model_axis and dim % width != 0:
                if on_indivisible == "error":
                    raise ValueError(
                        f"param {jax.tree_util.keystr(path)} dim {dim} "
                        f"is not divisible by mesh axis "
                        f"'{model_axis}' (size {width}) (PAR03)")
                ok = False
        if not ok:
            spec = P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, params)


def replicate_params(params, mesh: Mesh):
    return jax.device_put(params, NamedSharding(mesh, P()))


# ----------------------------------------------------------------------
# quantized gradient collectives (EQuARX-style block int8, PAPERS.md
# arXiv:2506.17615) — the shard_map building blocks the compressed
# trainer steps share
# ----------------------------------------------------------------------

#: per-block scale granularity of gradient_compression="block_int8"
DEFAULT_COMPRESSION_BLOCK = 256


def _quant_scales(flat, axis, mode, block):
    """Per-ELEMENT f32 dequant scale, shared across replicas: per-tensor
    absmax ("int8") or per-block absmax ("block_int8"), pmax'd over the
    data axis so every replica quantizes against the same grid (the
    scale exchange is the small side channel EQuARX pays)."""
    if mode == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(flat)), 1e-12)
        return jax.lax.pmax(scale, axis)
    n = flat.size
    pad = (-n) % block
    mag = jnp.abs(jnp.pad(flat, (0, pad))) if pad else jnp.abs(flat)
    s = jnp.maximum(jnp.max(mag.reshape(-1, block), axis=1), 1e-12)
    s = jax.lax.pmax(s, axis)
    return jnp.repeat(s, block)[:n]


def _quantize(g, axis, dp, mode, block):
    """The shared quantize front-end of both compressed collectives:
    flatten to f32, build the replica-shared scale grid, snap to the
    int8 grid in the integer accumulation dtype. Returns
    (q, per-element scales, f32 flat) — ONE definition, so the
    replicated psum and the composed psum_scatter can never drift off
    the grid that their bitwise-parity gate relies on."""
    flat = g.reshape(-1).astype(jnp.float32)
    sc = _quant_scales(flat, axis, mode, block)
    q = jnp.clip(jnp.round(flat / sc * 127.0), -127, 127) \
        .astype(_acc_dtype(dp))
    return q, sc, flat


def _acc_dtype(dp):
    # the sum of dp int8 lanes needs headroom: 127*dp <= 32512 fits
    # int16 through dp=256; past that accumulate in int32
    return jnp.int16 if dp <= 256 else jnp.int32


def quantized_psum_mean(g, axis, dp, mode="int8", block=None):
    """Compressed gradient all-reduce of one leaf inside shard_map:
    int8 quantize on a replica-shared scale grid, integer psum,
    dequantized MEAN in the leaf's dtype."""
    block = DEFAULT_COMPRESSION_BLOCK if block is None else int(block)
    q, sc, _ = _quantize(g, axis, dp, mode, block)
    summed = jax.lax.psum(q, axis)
    mean = summed.astype(jnp.float32) * (sc / 127.0) / dp
    return mean.reshape(g.shape).astype(g.dtype)


def quantized_psum_scatter_mean(flat, axis, dp, mode="int8", block=None):
    """Compressed gradient REDUCE-SCATTER of one flat leaf (n % dp == 0)
    inside shard_map: quantize as above, psum_scatter the integer
    lanes, dequantize only the local 1/dp shard of the mean — the
    compressed half of the ZeRO composition (reduce-scatter -> local
    shard update -> all-gather)."""
    block = DEFAULT_COMPRESSION_BLOCK if block is None else int(block)
    n = flat.size
    q, sc, _ = _quantize(flat, axis, dp, mode, block)
    shard = jax.lax.psum_scatter(q, axis, scatter_dimension=0, tiled=True)
    if mode != "int8":
        i = jax.lax.axis_index(axis)
        sc = jax.lax.dynamic_slice_in_dim(sc, i * (n // dp), n // dp)
    mean = shard.astype(jnp.float32) * (sc / 127.0) / dp
    return mean.astype(flat.dtype)


# ----------------------------------------------------------------------
# hierarchical 2-hop sparse gradient exchange (ROADMAP item 4): dense or
# block_int8 reduce-scatter inside a node group, Strom threshold-sparse
# exchange between group leaders, all-gather fan-back — wire bytes scale
# with capacity x groups instead of capacity x dp, which is what moves
# the sparse-vs-dense crossover past dp128
# ----------------------------------------------------------------------

#: default node-group size of gradient_compression="hierarchical" (the
#: intra-group reduce-scatter hop spans this many contiguous chips)
DEFAULT_COMPRESSION_GROUP = 8


def default_compression_group(dp):
    """The node-group size "hierarchical" picks when none is given: the
    largest divisor of dp that is <= DEFAULT_COMPRESSION_GROUP,
    and leaves >= 2 groups (so the sparse leader hop actually
    exchanges something). A dp with no such divisor (dp < 4, or a
    prime dp) has no 2-hop factorization at all — that raises, naming
    the flat modes as the fallback, rather than silently degenerating
    to one group whose leader exchange would be a no-op."""
    dp = int(dp)
    for g in range(min(dp // 2, DEFAULT_COMPRESSION_GROUP), 1, -1):
        if dp % g == 0:
            return g
    raise ValueError(
        f"data-parallel degree {dp} has no hierarchical factorization: "
        f"the 2-hop exchange needs a group size g with 2 <= g <= dp/2 "
        f"(>= 2 chips per group AND >= 2 groups), which requires a "
        f"composite dp >= 4; use gradient_compression='threshold' or "
        f"'block_int8' on this mesh instead")


def hierarchical_shard_elems(n, group_size):
    """Per-chip shard length of one n-element leaf under the
    hierarchical exchange: leaves are zero-padded up to a multiple of
    the group size before the intra-group reduce-scatter (padding zeros
    quantize to 0 and never cross the threshold, so the padding is
    mathematically invisible on the wire)."""
    n, g = int(n), int(group_size)
    return (n + (-n) % g) // g


def hierarchical_mesh(mesh: Mesh, group_size, batch_axis=DATA_AXIS):
    """Factor a 1-D pure data-parallel mesh into the 2-D
    (GROUP_AXIS, INTRA_AXIS) mesh the hierarchical exchange shard_maps
    over. The device ORDER is preserved — intra is innermost, so one
    group's chips stay contiguous (fastest ICI links) and replicated
    placements on either mesh are interchangeable. Rejects meshes with
    extra axes and indivisible/degenerate group sizes loudly, naming
    the constraint."""
    names = tuple(mesh.axis_names)
    if names != (batch_axis,):
        raise ValueError(
            f"gradient_compression='hierarchical' needs a 1-D pure "
            f"data-parallel mesh over '{batch_axis}', got axes "
            f"{list(names)}: the 2-hop exchange re-factors the data "
            "axis itself and cannot coexist with other mesh axes")
    dp = int(mesh.shape[batch_axis])
    g = int(group_size)
    if g < 2:
        raise ValueError(
            f"compressionGroupSize must be >= 2, got {g}: a 1-chip "
            "group has no intra-group reduction — that is the flat "
            "gradient_compression='threshold' mode; use it directly")
    if g > dp:
        raise ValueError(
            f"compressionGroupSize {g} exceeds the data-parallel "
            f"degree {dp}: a group cannot span more chips than the "
            "mesh has")
    if g == dp:
        raise ValueError(
            f"compressionGroupSize {g} equals the data-parallel degree "
            f"{dp}, leaving a single node group — hop 2's sparse "
            "leader exchange would have no peer to exchange with; use "
            "gradient_compression='block_int8' for pure in-group "
            f"quantization, or a divisor of {dp} that is <= {dp // 2}")
    if dp % g != 0:
        raise ValueError(
            f"data-parallel degree {dp} is not divisible by "
            f"compressionGroupSize {g}: node groups must tile the "
            f"data axis exactly (pick a divisor of {dp})")
    devices = np.asarray(mesh.devices).reshape(-1).reshape(dp // g, g)
    return Mesh(devices, (GROUP_AXIS, INTRA_AXIS))


def hierarchical_grad_exchange(g, res, tau, *, group_size, n_groups,
                               capacity, group_axis=GROUP_AXIS,
                               intra_axis=INTRA_AXIS,
                               intra_mode="block_int8", block=None):
    """The 2-hop exchange of ONE gradient leaf inside shard_map over the
    (group, intra) mesh:

      hop 1  dense (intra_mode=None) or block_int8 psum_scatter over
             the intra axis, divided by group_size — each chip ends
             with the GROUP MEAN of its 1/group_size shard of the leaf
             (the group now acts as ONE virtual Strom replica, so the
             transmitted +-tau has the same effective magnitude as the
             flat threshold mode's — without the /group_size the final
             /dp would shrink every update by group_size and the mode
             would train group_size-times slower than flat),
      hop 2  fixed-capacity Strom threshold exchange of that shard over
             the group axis (each intra position is the leader for its
             own shard): error feedback in, threshold_encode_fixed,
             (idx, +-tau) all-gathers, scatter-add, /n_groups,
      hop 3  all-gather fan-back over the intra axis to the full leaf.

    `res` is this chip's 1-D residual shard (hierarchical_shard_elems
    long). Returns (mean in g's shape/dtype, new residual shard f32,
    transmitted-entry count) — residual clipping and the adaptive tau
    stay with the caller, exactly as in the flat threshold step."""
    from deeplearning4j_tpu.ndarray.compression import (
        threshold_cap, threshold_encode_fixed,
    )

    block = DEFAULT_COMPRESSION_BLOCK if block is None else int(block)
    gsz = int(group_size)
    ng = int(n_groups)
    flat = g.reshape(-1).astype(jnp.float32)
    n = flat.size
    pad = (-n) % gsz
    if pad:
        flat = jnp.pad(flat, (0, pad))
    m = flat.size // gsz
    # hop 1: group-sum reduce-scatter inside the node group
    if intra_mode == "block_int8":
        q, sc, _ = _quantize(flat, intra_axis, gsz, "block_int8", block)
        shard_q = jax.lax.psum_scatter(q, intra_axis,
                                       scatter_dimension=0, tiled=True)
        i = jax.lax.axis_index(intra_axis)
        sc = jax.lax.dynamic_slice_in_dim(sc, i * m, m)
        shard = shard_q.astype(jnp.float32) * (sc / (127.0 * gsz))
    else:
        shard = jax.lax.psum_scatter(flat, intra_axis,
                                     scatter_dimension=0, tiled=True) / gsz
    # hop 2: sparse leader exchange of this shard across groups
    acc = shard + res.astype(shard.dtype)
    cap = threshold_cap(acc.size, capacity)
    idx, val, _, new_res = threshold_encode_fixed(acc, tau, cap)
    gi = jax.lax.all_gather(idx, group_axis, tiled=True)
    gv = jax.lax.all_gather(val, group_axis, tiled=True)
    mean_shard = jnp.zeros_like(acc).at[gi].add(gv) / ng
    # hop 3: fan the mean shard back out to the full leaf
    full = jax.lax.all_gather(mean_shard, intra_axis, tiled=True)
    if pad:
        full = full[:n]
    sent = jnp.sum(jnp.abs(val) > 0)
    return full.reshape(g.shape).astype(g.dtype), new_res, sent


class ZeroShardedUpdate:
    """ZeRO-style cross-replica weight-update sharding (Xu et al.,
    "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
    Training", arXiv:2004.13336).

    Installed as a network's ``_update_impl`` hook (MultiLayerNetwork /
    ComputationGraph per-layer update, SameDiff whole-dict update). The
    forward/backward is UNTOUCHED — same GSPMD program, same global-batch
    loss/BN semantics as the replicated path. Only the weight update is
    re-annotated, exactly the paper's transformation:

      * each eligible gradient leaf is viewed flat and constrained to
        1/dp shards over the data axis — the SPMD partitioner lowers the
        gradient reduction feeding it as a reduce-scatter (TPU; XLA:CPU
        lacks the ReduceScatterCreator pass and emits the equivalent
        all-reduce + dynamic-slice, see dp_weight_update_bytes),
      * the optimizer applies to ONLY the local shard of params and
        updater state (updater state is ALLOCATED sharded from init —
        each chip ever holds 1/dp of the fp32 moments, which is where
        the HBM win for big optimizers comes from),
      * the fresh flat params are constrained back to replicated — one
        all-gather — and reshaped for the next forward.

    Eligibility is per LEAF on the total element count n: a leaf shards
    when ``n >= min_shard_size and n % dp == 0``; anything else —
    scalar/vector leaves (biases, BN gamma/beta) below min_shard_size,
    or sizes dp does not divide — stays REPLICATED (the explicit
    pad-or-replicate policy: never pad; the partition-plan analyzer
    reports the same fallback statically as PAR03). Because the view is
    a reshape and replicated-leaf math is byte-for-byte the default
    update, a model with no eligible leaves trains bitwise-identically
    to the replicated path.
    """

    def __init__(self, mesh: Mesh, axis=DATA_AXIS, min_shard_size=2 ** 16):
        if axis not in mesh.shape:
            raise ValueError(
                f"mesh has no axis '{axis}' (axes: {list(mesh.shape)}); "
                "build the mesh with a data-parallel axis or pass axis=")
        self.mesh = mesh
        self.axis = axis
        self.dp = int(mesh.shape[axis])
        self.min_shard_size = int(min_shard_size)
        self._sharded = NamedSharding(mesh, P(axis))
        self._repl = NamedSharding(mesh, P())

    # ----- eligibility / views ----------------------------------------
    def eligible(self, leaf) -> bool:
        """Shard-or-replicate decision for one array/abstract leaf (by
        total element count — the flat view shards dim 0 of the
        flattened vector, so leading-dim divisibility is irrelevant)."""
        n = int(np.prod(leaf.shape)) if hasattr(leaf, "shape") else int(leaf)
        return n > 0 and n >= self.min_shard_size and n % self.dp == 0

    def _tmap(self, f, *trees):
        return jax.tree_util.tree_map(f, *trees)

    def view(self, tree):
        """Traced: eligible leaves -> flat 1-D views constrained to 1/dp
        shards over the data axis; ineligible leaves pass through."""
        wsc = jax.lax.with_sharding_constraint
        return self._tmap(
            lambda a: wsc(a.reshape(-1), self._sharded)
            if self.eligible(a) else a, tree)

    def constrain_state(self, state):
        """Traced: pin eligible (already-flat) state leaves to the
        sharded layout so the carry cannot silently replicate."""
        wsc = jax.lax.with_sharding_constraint
        return self._tmap(
            lambda a: wsc(a, self._sharded) if self.eligible(a) else a,
            state)

    # ----- the update hook --------------------------------------------
    def __call__(self, updater, grads, upd_state, iteration, params):
        """reduce-scatter(grads) -> local 1/dp shard update -> all-gather
        (params). Drop-in for the default apply-and-subtract: returns
        (new_params at full shape, new updater state in the sharded view
        layout)."""
        wsc = jax.lax.with_sharding_constraint
        gv = self.view(grads)
        pv = self.view(params)
        upd, new_state = updater.apply(gv, upd_state, iteration, params=pv)
        new_state = self.constrain_state(new_state)
        new_pv = self._tmap(
            lambda p, u: (p - u).astype(p.dtype), pv, upd)
        # pin the POST-cast result sharded before replicating: without
        # this the partitioner may sink the param-dtype convert past the
        # all-gather and move a wider intermediate (x64 promotes updater
        # scalar math to f64) — the gather must carry param-dtype bytes
        new_pv = self.constrain_state(new_pv)
        # all-gather the fresh shards back to the replicated full-shape
        # params the next forward reads
        return self._tmap(
            lambda full, flat: wsc(flat, self._repl).reshape(full.shape)
            if self.eligible(full) else flat,
            params, new_pv), new_state

    # ----- state allocation / (un)view --------------------------------
    def init_state(self, updater, params):
        """Fresh updater state ALLOCATED in the sharded layout: init runs
        under jit with sharded out_shardings, so each chip materialises
        only its 1/dp shard — no full-size state buffer ever exists
        (ISSUE: 'allocated sharded from init, not sliced from a
        replicated copy')."""
        views = self._tmap(
            lambda a: a.reshape(-1) if self.eligible(a) else a, params)
        shapes = jax.eval_shape(updater.init, views)
        if not jax.tree_util.tree_leaves(shapes):
            return updater.init(views)  # stateless (Sgd/NoOp): ()/empty
        shardings = self._tmap(
            lambda s: self._sharded if self.eligible(s) else self._repl,
            shapes)
        return jax.jit(updater.init, out_shardings=shardings)(views)

    def place_state(self, state):
        """Re-place an EXISTING state tree (full-shape or already
        viewed) into the sharded layout — the mid-training switch and
        checkpoint-restore path; values are preserved bitwise (the view
        is a reshape)."""
        def place(a):
            a = jnp.asarray(a)
            if self.eligible(a):
                return jax.device_put(a.reshape(-1), self._sharded)
            return jax.device_put(a, self._repl)

        return self._tmap(place, state)

    def unview_state(self, state, updater, params):
        """Sharded view layout -> the canonical full-shape state layout
        (checkpoints save THIS form, so a sharded-mode save restores
        into any mode bitwise; reshape is lossless)."""
        template = jax.eval_shape(updater.init, params)
        return self._tmap(
            lambda s, t: jnp.reshape(s, t.shape), state, template)

    def per_chip_state_bytes(self, state) -> int:
        """Measured per-chip resident bytes of one state tree (device
        0's addressable shards) — the number the analytic
        dp_weight_update_bytes(sharded=True) opt_state_resident_bytes
        bill is judged against."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(state):
            if not hasattr(leaf, "addressable_shards"):
                total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                continue
            dev0 = leaf.addressable_shards[0]
            total += int(np.prod(dev0.data.shape)) * leaf.dtype.itemsize
        return total


class ManualZeroUpdate:
    """ZeroShardedUpdate's shard_map twin: the compressed-collective
    composition of compression and ZeRO (ISSUE 11). The compressed
    trainer steps run inside an EXPLICIT shard_map, where the GSPMD
    sharding annotations ZeroShardedUpdate relies on cannot apply — so
    this hook spells the same transformation out with manual
    collectives:

      * eligible gradient leaves take a QUANTIZED reduce-scatter
        (quantized_psum_scatter_mean: int8/block_int8 lanes through
        psum_scatter) — each replica receives only its 1/dp shard of
        the reduced gradient, at compressed wire cost,
      * ineligible leaves take the compressed all-reduce
        (quantized_psum_mean) and update replicated, exactly the
        GSPMD path's replicate fallback,
      * the optimizer applies to the LOCAL 1/dp shard of params and
        updater state (state layout identical to ZeroShardedUpdate's:
        flat leaves sharded over the data axis — allocation,
        checkpoint unview and per-chip byte accounting are all shared
        with the GSPMD implementation),
      * the fresh local param shards are all-gathered (param dtype)
        back to the full shapes the next forward reads.

    Installed as the net's `_update_impl` by
    ParallelWrapper._place_sharded_update when gradient_compression is
    "int8"/"block_int8" and weight_update="sharded"."""

    def __init__(self, zero: ZeroShardedUpdate, compression: str,
                 block=None):
        if compression not in ("int8", "block_int8"):
            raise ValueError(
                "ManualZeroUpdate composes the sharded weight update "
                "with gradient_compression 'int8'/'block_int8', got "
                f"{compression!r} (the 'threshold' step's per-replica "
                "error-feedback residual has no per-parameter "
                "reduce-scatter form)")
        self.zero = zero
        self.axis = zero.axis
        self.dp = zero.dp
        self.compression = compression
        self.block = DEFAULT_COMPRESSION_BLOCK if block is None \
            else int(block)

    def __call__(self, updater, grads, upd_state, iteration, params):
        z, ax, dp = self.zero, self.axis, self.dp
        i = jax.lax.axis_index(ax)
        tmap = jax.tree_util.tree_map

        def reduce_leaf(g, p):
            if z.eligible(p):
                return quantized_psum_scatter_mean(
                    g.reshape(-1), ax, dp, self.compression, self.block)
            return quantized_psum_mean(g, ax, dp, self.compression,
                                       self.block)

        def pview(p):
            if z.eligible(p):
                flat = p.reshape(-1)
                return jax.lax.dynamic_slice_in_dim(
                    flat, i * (flat.size // dp), flat.size // dp)
            return p

        gv = tmap(reduce_leaf, grads, params)
        pv = tmap(pview, params)
        upd, new_state = updater.apply(gv, upd_state, iteration,
                                       params=pv)
        new_pv = tmap(lambda p, u: (p - u).astype(p.dtype), pv, upd)

        def unview(full, flat):
            if z.eligible(full):
                return jax.lax.all_gather(
                    flat, ax, tiled=True).reshape(full.shape)
            return flat

        return tmap(unview, params, new_pv), new_state


# ----------------------------------------------------------------------
# the bytes-on-wire bill per compression mode
# ----------------------------------------------------------------------

#: selectable gradient_compression modes (None = dense psum)
COMPRESSION_MODES = (None, "int8", "block_int8", "threshold",
                     "hierarchical")

#: default fraction of a leaf's elements the fixed-capacity threshold
#: encoder may transmit per step (ParallelWrapper encodingCapacity)
DEFAULT_ENCODING_CAPACITY = 0.125


def compressed_wire_bytes(grad_bytes, dp, compression=None, block=None,
                          capacity=None, itemsize=4, group_size=None,
                          intra_mode="block_int8"):
    """LOGICAL per-replica bytes-on-wire of ONE gradient reduction under
    a compression mode — the bill PAR06 reports, bench records and the
    tier-1 ceiling gate holds block_int8 under 30% of dense against.
    Ring-collective convention (what each replica sends):

      dense       2*(dp-1)/dp * G            (reduce-scatter + all-gather
                                             halves of the all-reduce)
      int8        2*(dp-1)/dp * (N + 4)      one byte per element + one
                                             fp32 scale
      block_int8  2*(dp-1)/dp * (N + 4*ceil(N/block))
                                             one byte per element + one
                                             fp32 scale per block
                                             (EQuARX-style)
      threshold   (dp-1) * cap * 5           ring all-gather of each
                                             replica's cap (int32 index,
                                             sign byte) pairs;
                                             cap = ceil(N*capacity)
                                             (Strom's sparse messages
                                             are gathered, not reduced)
      hierarchical  two honest terms over the (groups x group_size)
                    factorization (Np = N padded to the group size,
                    Ns = Np/group_size the per-chip shard):
                    intra   (I-1)/I * (Np + 4*ceil(Np/block))  quantized
                            reduce-scatter (or (I-1)/I * Np*itemsize
                            dense when intra_mode=None) PLUS the
                            (I-1)/I * Np*itemsize fan-back all-gather
                    leader  (groups-1) * cap(Ns) * 5 sparse ring
                            exchange of the shard between group leaders
                    — capacity bytes scale with GROUPS, not dp, which
                    is what moves the sparse crossover past dp128

    N = grad elements (grad_bytes / itemsize). Returns
    {wire_bytes, dense_wire_bytes, ratio, mode}; the hierarchical mode
    adds {intra_wire_bytes, leader_wire_bytes, group_size, groups,
    intra_mode, flat_threshold_wire_bytes, vs_flat_threshold}."""
    if compression not in COMPRESSION_MODES:
        raise ValueError(
            f"unknown gradient_compression {compression!r}; pick one of "
            f"{COMPRESSION_MODES}")
    if group_size is not None and compression != "hierarchical":
        raise ValueError(
            f"group_size only applies to "
            f"gradient_compression='hierarchical', got group_size="
            f"{group_size} with {compression!r}")
    block = DEFAULT_COMPRESSION_BLOCK if block is None else int(block)
    capacity = DEFAULT_ENCODING_CAPACITY if capacity is None \
        else float(capacity)
    G = int(grad_bytes)
    N = G // int(itemsize)
    dense = 2 * (dp - 1) * G // dp
    extra = {}
    if compression is None:
        wire = dense
    elif compression == "int8":
        wire = 2 * (dp - 1) * (N + 4) // dp
    elif compression == "block_int8":
        wire = 2 * (dp - 1) * (N + 4 * _ceil_div(N, block)) // dp
    elif compression == "threshold":
        from deeplearning4j_tpu.ndarray.compression import threshold_cap

        wire = (dp - 1) * threshold_cap(N, capacity) * 5
    else:  # hierarchical
        from deeplearning4j_tpu.ndarray.compression import threshold_cap

        gsz = default_compression_group(dp) if group_size is None \
            else int(group_size)
        if gsz < 2 or gsz >= dp or dp % gsz != 0:
            raise ValueError(
                f"hierarchical group_size {gsz} must be a divisor of "
                f"dp={dp} with 2 <= group_size <= dp/2 (node groups "
                "tile the data axis exactly and the leader exchange "
                "needs >= 2 groups)")
        if intra_mode not in (None, "block_int8"):
            raise ValueError(
                f"hierarchical intra_mode must be None (dense) or "
                f"'block_int8', got {intra_mode!r}")
        groups = dp // gsz
        Ns = hierarchical_shard_elems(N, gsz)
        Np = Ns * gsz
        if intra_mode == "block_int8":
            hop1 = (gsz - 1) * (Np + 4 * _ceil_div(Np, block)) // gsz
        else:
            hop1 = (gsz - 1) * Np * int(itemsize) // gsz
        hop3 = (gsz - 1) * Np * int(itemsize) // gsz
        leader = (groups - 1) * threshold_cap(Ns, capacity) * 5
        wire = hop1 + hop3 + leader
        flat_thr = (dp - 1) * threshold_cap(N, capacity) * 5
        extra = {
            "intra_wire_bytes": int(hop1 + hop3),
            "leader_wire_bytes": int(leader),
            "group_size": gsz,
            "groups": groups,
            "intra_mode": intra_mode or "dense",
            "flat_threshold_wire_bytes": int(flat_thr),
            "vs_flat_threshold": round(wire / flat_thr, 4)
            if flat_thr else 1.0,
        }
    # publish the static bill as gauges: a scrape of /metrics shows the
    # per-replica bytes-on-wire the current config is billed for
    # (host-side analytic math — never inside a traced function)
    from deeplearning4j_tpu.runtime import telemetry

    _g = telemetry.get_registry().gauge(
        "dl4j_compressed_wire_bytes",
        "analytic per-replica gradient bytes-on-wire per step",
        labels=("mode",))
    _g.labels(mode=compression or "dense").set(int(wire))
    _g.labels(mode="dense").set(int(dense))
    rec = {
        "wire_bytes": int(wire),
        "dense_wire_bytes": int(dense),
        "ratio": round(wire / dense, 4) if dense else 1.0,
        "mode": compression or "dense",
    }
    rec.update(extra)
    return rec


def _ceil_div(a, b):
    return -(-int(a) // int(b))


def compressed_hlo_collective_bytes(leaf_elems, dp, compression,
                                    block=None, capacity=None,
                                    sharded=False, eligible=None,
                                    itemsize=4, group_size=None,
                                    intra_mode="block_int8"):
    """Per-replica HBM bytes the hbm_ledger charges the COLLECTIVE rows
    of the compressed dp step AS LOWERED on this backend — the analytic
    twin the tier-1 measured-bytes gate holds the dp8 CPU compile
    within 10% of. Convention (hbm_ledger._instruction_bytes): an op
    charges its output bytes plus its distinct-operand input bytes.

    `leaf_elems`: per-leaf element counts (the quantizer/encoder runs
    per leaf, so scale/capacity rounding is per leaf). Emitted ops per
    leaf of n elements, acc = int16 for dp <= 256 else int32:

      int8        scale pmax (all-reduce f32 scalar: 8 B) +
                  integer psum (all-reduce acc[n]: 2 * n * acc_bytes)
      block_int8  scale pmax (all-reduce f32 [ceil(n/block)]) +
                  integer psum as above
      threshold   all-gather idx int32 [cap]->[dp*cap] + all-gather val
                  [cap]->[dp*cap] in the residual dtype: each charges
                  (dp+1) * cap * itemsize_of_part
      hierarchical (pass group_size; acc from _acc_dtype(group_size) —
                  the integer sum spans only the group's lanes):
                  per leaf with np = n padded to group_size, ns =
                  np/group_size, groups = dp/group_size:
                  scale pmax (all-reduce f32 [ceil(np/block)], quantized
                  hop 1 only) + intra reduce-scatter (in np + out ns, at
                  acc bytes quantized / f32 dense) + the two leader
                  all-gathers ((groups+1) * cap(ns) * {4, itemsize}) +
                  the f32 fan-back all-gather (in ns + out np)

    sharded=True (int8/block_int8 only): leaves for which
    `eligible(n)` is True take the quantized reduce-scatter
    (in acc[n] + out acc[n/dp]) plus the param-dtype all-gather of the
    fresh shards (in n/dp + out n, at `itemsize`); ineligible leaves
    keep the compressed all-reduce."""
    from deeplearning4j_tpu.ndarray.compression import threshold_cap

    block = DEFAULT_COMPRESSION_BLOCK if block is None else int(block)
    capacity = DEFAULT_ENCODING_CAPACITY if capacity is None \
        else float(capacity)
    # the bill and the lowering share ONE accumulator-width definition
    # (_acc_dtype) so they cannot drift apart; the analyzer's COL03
    # check (analysis.collectives.check_acc_dtype) cross-checks both
    # against the dp<=256 int16 bound independently
    acc = jnp.dtype(_acc_dtype(dp)).itemsize
    if compression == "hierarchical":
        gsz = default_compression_group(dp) if group_size is None \
            else int(group_size)
        groups = dp // gsz
        # hop 1 sums int8 lanes across the GROUP only — the
        # accumulator width tracks the group size, not dp
        acc = jnp.dtype(_acc_dtype(gsz)).itemsize
    total = 0
    for n in leaf_elems:
        n = int(n)
        if compression == "threshold":
            cap = threshold_cap(n, capacity)     # the encoder's rule
            total += (dp + 1) * cap * 4          # idx int32 gather
            total += (dp + 1) * cap * itemsize   # value gather
            continue
        if compression == "hierarchical":
            ns = hierarchical_shard_elems(n, gsz)
            np_ = ns * gsz
            cap = threshold_cap(ns, capacity)
            if intra_mode == "block_int8":
                total += 2 * _ceil_div(np_, block) * 4  # scale pmax
                total += np_ * acc + ns * acc    # int reduce-scatter
            else:
                total += (np_ + ns) * 4          # f32 reduce-scatter
            total += (groups + 1) * cap * 4      # leader idx gather
            total += (groups + 1) * cap * itemsize  # leader val gather
            total += (ns + np_) * 4              # f32 fan-back gather
            continue
        nb = _ceil_div(n, block) if compression == "block_int8" else 1
        scale = 2 * nb * 4                       # pmax all-reduce
        if sharded and eligible is not None and eligible(n):
            rs = n * acc + (n // dp) * acc       # reduce-scatter
            ag = n * itemsize + (n // dp) * itemsize  # param all-gather
            total += scale + rs + ag
        else:
            total += scale + 2 * n * acc         # integer all-reduce
    return int(total)


def dp_weight_update_bytes(grad_bytes, dp, master_bytes=None,
                           opt_state_bytes=None, sharded=False,
                           compression=None, compression_block=None,
                           encoding_capacity=None,
                           compression_group=None):
    """Analytic per-replica HBM bytes of the data-parallel weight-update
    path — the model the hbm_ledger attribution's `collective` bin
    (weight_update rows) is judged against, and the bill cross-replica
    weight-update sharding (Xu et al., "Automatic Cross-Replica
    Sharding of Weight Update in Data-Parallel Training") removes.

    Terms per replica, dp = data-parallel degree:
      allreduce:       ring all-reduce of the gradients moves
                       2*(dp-1)/dp * G bytes through each replica's HBM
                       (reduce-scatter + all-gather halves)
      update_replicated: every replica redundantly reads+writes the full
                       fp32 master params and updater state and re-reads
                       the full reduced gradient — identical work dp
                       times over
      update_sharded:  the same update with cross-replica sharding: each
                       replica touches only its 1/dp slice (plus the
                       all-gather of updated params, already counted in
                       the allreduce-equivalent traffic of that scheme)

    master/opt default to fp32 buffers the same element count as the
    (fp32) grads. Returns the terms plus `sharding_saves_bytes` — the
    per-replica HBM cut the sharded update offers; compare it against
    the attribution's measured weight_update collective rows before
    spending chip time on the rewrite.

    sharded=True returns the ZeRO bill of the IMPLEMENTED scheme
    (ZeroShardedUpdate) — the analytic yardstick its measured
    weight_update collective bin and per-chip updater-state bytes are
    CI-gated against. Terms per replica:

      reduce_scatter_bytes  (dp-1)/dp * G on the wire (the gradient
                            reduction, scattered instead of replicated)
      all_gather_bytes      (dp-1)/dp * M on the wire (the fresh params)
      update_bytes          (2M + 2S + G)/dp — the optimizer touches
                            only the local shard
      opt_state_resident_bytes  S/dp per chip (state allocated sharded)
      hlo_collective_bytes  the per-replica HBM bytes the hbm_ledger
                            charges the COLLECTIVE rows of the
                            PARTITIONED step, by lowering:
                              reduce_scatter:    rs (out G/dp + in G)
                                                 + ag (out M + in M/dp)
                                                 — what TPU emits;
                              all_reduce_gather: XLA:CPU lacks the
                                                 ReduceScatterCreator
                                                 pass and lowers the
                                                 scattered reduction as
                                                 all-reduce (2G) + a
                                                 local dynamic-slice
                                                 (not a collective),
                                                 plus the same param
                                                 all-gather — the form
                                                 the tier-1 CPU gate
                                                 prices.
                            Both models cover the ELIGIBLE (actually
                            sharded) bytes; leaves the replicate
                            fallback keeps pay the plain 2G all-reduce
                            on top (the caller adds that term).

    compression (None / "int8" / "block_int8" / "threshold") bills the
    compressed gradient reduction on top of either mode (the ISSUE 11
    composition): `compressed_wire` carries the compressed_wire_bytes
    record for the gradient half, and under sharded=True
    `compressed_reduce_scatter_bytes` + `collective_wire_bytes_compressed`
    replace the gradient reduce-scatter's wire cost with its quantized
    form (the param all-gather stays dense — params are not quantized).
    "threshold" does not compose with sharded=True (no per-parameter
    reduce-scatter form) and raises.
    """
    if compression not in COMPRESSION_MODES:
        raise ValueError(
            f"unknown gradient_compression {compression!r}; pick one of "
            f"{COMPRESSION_MODES}")
    if sharded and compression in ("threshold", "hierarchical"):
        raise ValueError(
            f"weight_update sharding does not compose with "
            f"gradient_compression={compression!r}: the Strom step "
            "carries per-replica error-feedback residuals and "
            "transmits sparse messages, which have no per-parameter "
            "reduce-scatter form; bill 'int8'/'block_int8' (compressed "
            "reduce-scatter) or the dense sharded path")
    G = int(grad_bytes)
    M = G if master_bytes is None else int(master_bytes)
    S = G if opt_state_bytes is None else int(opt_state_bytes)
    if dp < 1:
        raise ValueError(f"dp must be >= 1, got {dp}")
    allreduce = 2 * (dp - 1) * G // dp
    update_repl = 2 * M + 2 * S + G
    update_shard = (2 * M + 2 * S + G) // dp
    rec = {
        "allreduce_bytes": allreduce,
        "update_replicated_bytes": update_repl,
        "update_sharded_bytes": update_shard,
        "sharding_saves_bytes": update_repl - update_shard,
        "dp": int(dp),
        "mode": "sharded" if sharded else "replicated",
        "gradient_compression": compression,
    }
    if compression is not None:
        rec["compressed_wire"] = compressed_wire_bytes(
            G, dp, compression, block=compression_block,
            capacity=encoding_capacity,
            group_size=compression_group
            if compression == "hierarchical" else None)
    if not sharded:
        rec["update_bytes"] = update_repl
        rec["opt_state_resident_bytes"] = S
        return rec
    rs = (dp - 1) * G // dp
    ag = (dp - 1) * M // dp
    rec.update({
        "reduce_scatter_bytes": rs,
        "all_gather_bytes": ag,
        "collective_wire_bytes": rs + ag,
        "update_bytes": update_shard,
        "opt_state_resident_bytes": S // dp,
        "hlo_collective_bytes": {
            "reduce_scatter": (G + G // dp) + (M + M // dp),
            "all_reduce_gather": 2 * G + (M + M // dp),
        },
    })
    if compression is not None:
        # the gradient half of the compressed wire bill IS the
        # compressed reduce-scatter (one of the all-reduce's two
        # halves); the param all-gather stays dense
        rs_c = rec["compressed_wire"]["wire_bytes"] // 2
        rec["compressed_reduce_scatter_bytes"] = rs_c
        rec["collective_wire_bytes_compressed"] = rs_c + ag
    return rec
