"""Distributed training wrappers.

Reference: two reference subsystems collapse into this module —
  * org.deeplearning4j.parallelism.ParallelWrapper (single-host multi-GPU:
    replicate model per device, average gradients),
  * the Spark gradient-sharing stack (SharedTrainingMaster /
    SharedTrainingWrapper + Aeron UDP threshold-encoded allreduce,
    Strom 2015).

TPU design: data parallelism is a SHARDING, not a worker framework. The
network's existing jitted train step is re-jitted with parameter/optimizer
shardings = replicated and batch shardings = split over the mesh "data"
axis; XLA's SPMD partitioner inserts the bf16 gradient all-reduce over ICI
(the role of NCCL/Aeron). Threshold encoding existed because Ethernet
allreduce was the bottleneck; dense bf16 over ICI is faster than any
host-side sparse encode/decode, so the default is dense. For DCN-limited
deployments three compressed modes are selectable per config, each an
explicit shard_map program with a statically billed bytes-on-wire
contract (parallel.sharding.compressed_wire_bytes):

  gradient_compression="int8"        per-tensor-scale quantized allreduce
  gradient_compression="block_int8"  per-BLOCK-scale quantized allreduce
                                     (EQuARX-style, PAPERS.md
                                     arXiv:2506.17615) — tighter scales,
                                     same wire bytes + a small scale
                                     side channel
  gradient_compression="threshold"   Strom-2015 sparse sign encoding
                                     with per-replica error-feedback
                                     residuals, fixed-capacity top-|g|
                                     encoding so shapes stay static and
                                     the step remains ONE jitted
                                     executable; the residual rides the
                                     donated updater-state carry (and
                                     therefore fitDataSet's k-loop and
                                     ResilientFit checkpoints)

"int8"/"block_int8" compose with weight_update="sharded": the gradient
reduction becomes a QUANTIZED reduce-scatter and the optimizer runs on
the local 1/dp shard (parallel.sharding.ManualZeroUpdate).

Determinism: batch stats (BN) and losses are computed over the GLOBAL
batch (GSPMD reduces across shards), so DP training at any width produces
the same result as single-device training on the combined batch — the
property the reference's parameter-averaging mode only approximates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel import mesh as _mesh
from deeplearning4j_tpu.nn.multilayer import _unwrap


# ----------------------------------------------------------------------
# threshold-algorithm configs (reference: org.nd4j.parameterserver
# ThresholdAlgorithm implementations) — SharedTrainingMaster maps these
# to real trainer config instead of passing an opaque kwarg through
# ----------------------------------------------------------------------

class FixedThresholdAlgorithm:
    """A constant Strom threshold tau (reference:
    FixedThresholdAlgorithm)."""

    def __init__(self, threshold):
        self.threshold = float(threshold)


class AdaptiveThresholdAlgorithm:
    """Adapt tau multiplicatively so the mean transmitted fraction
    tracks `sparsityTarget` (reference: AdaptiveThresholdAlgorithm)."""

    def __init__(self, initialThreshold=1e-3, sparsityTarget=1e-2):
        self.threshold = float(initialThreshold)
        self.sparsityTarget = float(sparsityTarget)


class TargetSparsityThresholdAlgorithm(AdaptiveThresholdAlgorithm):
    """Alias of the adaptive algorithm with the target spelled first
    (reference: TargetSparsityThresholdAlgorithm)."""

    def __init__(self, sparsityTarget=1e-2, initialThreshold=1e-3):
        super().__init__(initialThreshold, sparsityTarget)


class ResidualClippingPostProcessor:
    """Clip the error-feedback residual to +-(clipValue * tau) every
    `frequency` iterations (reference:
    ResidualClippingPostProcessor) — bounds how much stale gradient a
    slow-moving coordinate can accumulate."""

    def __init__(self, clipValue=5.0, frequency=1):
        self.clipValue = float(clipValue)
        self.frequency = int(frequency)
        if self.clipValue <= 0:
            raise ValueError(
                f"clipValue must be > 0, got {clipValue}")
        if self.frequency < 1:
            raise ValueError(
                f"frequency must be >= 1, got {frequency}")


#: the named threshold algorithms SharedTrainingMaster accepts (a bare
#: number is shorthand for FixedThresholdAlgorithm)
THRESHOLD_ALGORITHMS = (FixedThresholdAlgorithm,
                        AdaptiveThresholdAlgorithm,
                        TargetSparsityThresholdAlgorithm)

#: the packed updater-state carry of the threshold step: the canonical
#: (params, upd, states, it, ...) signature is preserved by riding the
#: error-feedback residual and the live tau INSIDE the donated upd slot
_PACK_KEYS = frozenset({"upd", "ef", "tau"})


def _is_packed(upd):
    return isinstance(upd, dict) and set(upd.keys()) == _PACK_KEYS


class ParallelWrapper:
    """Data-parallel trainer over a device mesh.

    Usage (reference ParallelWrapper.Builder parity):
        pw = ParallelWrapper(net)              # all local devices
        pw = ParallelWrapper(net, mesh=mesh)   # explicit mesh
        pw.fit(iterator)
    """

    def __init__(self, net, mesh=None, gradient_compression=None,
                 batch_axis=_mesh.DATA_AXIS, threshold=1e-3,
                 targetSparsity=None, weight_update="replicated",
                 min_shard_size=2 ** 16, encodingCapacity=None,
                 residualClip=None, residualClipFrequency=1,
                 compressionBlock=None, compressionGroupSize=None,
                 intraGroupCompression="block_int8"):
        from deeplearning4j_tpu.parallel.sharding import (
            COMPRESSION_MODES, DEFAULT_COMPRESSION_BLOCK,
            DEFAULT_ENCODING_CAPACITY, default_compression_group,
            hierarchical_mesh,
        )

        if getattr(net, "_solver", None) is not None:
            raise ValueError(
                "distributed trainers require "
                "optimizationAlgo=STOCHASTIC_GRADIENT_DESCENT: a shard-"
                "local line search (LBFGS/CG) would accept a different "
                "step size on every replica and silently desynchronize "
                "the supposedly-replicated parameters")
        self.net = net
        self.mesh = mesh or _mesh.data_parallel_mesh()
        self.batch_axis = batch_axis
        self.gradient_compression = gradient_compression
        self.threshold = float(threshold)
        if gradient_compression in ("threshold", "hierarchical") \
                and self.threshold <= 0:
            raise ValueError(
                f"threshold (tau) must be > 0, got {threshold}: the "
                "Strom encoder transmits sign(g)*tau, so a non-positive "
                "tau would negate (or zero) every transmitted update")
        # reference: AdaptiveThresholdAlgorithm — adapt the threshold so
        # the transmitted fraction tracks this target (None = fixed)
        self.targetSparsity = None if targetSparsity is None \
            else float(targetSparsity)
        # fixed-capacity encoding: the threshold step may transmit at
        # most ceil(capacity * n) entries per leaf per step (static
        # shapes — one executable). Auto (None) leaves headroom over an
        # adaptive sparsity target.
        if encodingCapacity is None:
            cap = DEFAULT_ENCODING_CAPACITY if self.targetSparsity is None \
                else max(DEFAULT_ENCODING_CAPACITY,
                         min(1.0, 2.0 * self.targetSparsity))
        else:
            cap = float(encodingCapacity)
            if not 0.0 < cap <= 1.0:
                raise ValueError(
                    f"encodingCapacity must be in (0, 1], got {cap}")
            if self.targetSparsity is not None \
                    and self.targetSparsity > cap:
                raise ValueError(
                    f"targetSparsity {self.targetSparsity} exceeds "
                    f"encodingCapacity {cap}: the fixed-capacity "
                    "encoder can never transmit more than the capacity "
                    "fraction, so the adaptive threshold could not "
                    "reach its target")
        self.encoding_capacity = cap
        self.residual_clip = None if residualClip is None \
            else float(residualClip)
        self.residual_clip_frequency = int(residualClipFrequency)
        if self.residual_clip is not None and self.residual_clip <= 0:
            raise ValueError(
                f"residualClip must be > 0, got {residualClip}")
        if self.residual_clip_frequency < 1:
            raise ValueError(
                "residualClipFrequency must be >= 1, got "
                f"{residualClipFrequency}")
        self.compression_block = DEFAULT_COMPRESSION_BLOCK \
            if compressionBlock is None else int(compressionBlock)
        if self.compression_block < 1:
            raise ValueError(
                f"compressionBlock must be >= 1, got {compressionBlock}")
        self._repl = NamedSharding(self.mesh, P())
        self._jit = None
        if gradient_compression not in COMPRESSION_MODES:
            raise ValueError(
                "gradient_compression must be one of "
                f"{COMPRESSION_MODES}, got {gradient_compression!r}")
        if intraGroupCompression not in (None, "block_int8"):
            raise ValueError(
                "intraGroupCompression must be None (dense hop-1 "
                "reduce-scatter) or 'block_int8', got "
                f"{intraGroupCompression!r}")
        self.intra_compression = intraGroupCompression
        self._hmesh = None
        self._n_groups = None
        self.compression_group = None
        if gradient_compression == "hierarchical":
            dp = self.mesh.shape.get(self.batch_axis, 0)
            gsz = default_compression_group(dp) \
                if compressionGroupSize is None else int(compressionGroupSize)
            # hierarchical_mesh does the loud validation (divisibility,
            # 1-D pure-data mesh, g >= 2)
            self._hmesh = hierarchical_mesh(
                self.mesh, gsz, batch_axis=self.batch_axis)
            self._n_groups = dp // gsz
            self.compression_group = gsz
            # ONE mesh everywhere in hierarchical mode: placements and
            # the shard_map step must agree on the (group, intra) mesh,
            # or every step would reshard through a mesh change
            self._repl = NamedSharding(self._hmesh, P())
        elif compressionGroupSize is not None:
            raise ValueError(
                f"compressionGroupSize given together with "
                f"gradient_compression={gradient_compression!r}: the "
                "node-group size only applies to the 'hierarchical' "
                "2-hop exchange; drop one of the two arguments")
        if weight_update not in ("replicated", "sharded"):
            raise ValueError(
                "weight_update must be 'replicated' or 'sharded', got "
                f"{weight_update!r}")
        if weight_update == "sharded" \
                and gradient_compression in ("threshold", "hierarchical"):
            raise ValueError(
                "weight_update='sharded' composes with "
                "gradient_compression None/'int8'/'block_int8' "
                "(compressed reduce-scatter -> 1/dp shard update -> "
                "all-gather), but not "
                f"{gradient_compression!r}: the Strom exchange's "
                "per-replica error-feedback residual transmits sparse "
                "all-gathered messages, which have no per-parameter "
                "reduce-scatter form. Pick 'int8'/'block_int8', or "
                "keep the update replicated.")
        if gradient_compression in ("int8", "block_int8") \
                and weight_update == "sharded" \
                and getattr(net.conf, "gradientNormalization", None) \
                is not None:
            raise ValueError(
                "gradient normalization is applied to the REDUCED "
                "gradient, but the compressed sharded update "
                "reduce-scatters inside the weight-update hook — the "
                "normalization would see per-replica gradients and "
                "silently change semantics. Drop gradientNormalization "
                "or use weight_update='replicated'.")
        self.weight_update = weight_update
        self.min_shard_size = int(min_shard_size)
        self._zero = None
        if weight_update == "sharded":
            from deeplearning4j_tpu.parallel.sharding import \
                ZeroShardedUpdate

            self._zero = ZeroShardedUpdate(
                self.mesh, axis=self.batch_axis,
                min_shard_size=self.min_shard_size)

    @property
    def _residual(self):
        """Threshold mode's (error-feedback tree, live tau) — carried
        INSIDE the packed updater state (the donated step carry), so
        fitDataSet's k-loop and ResilientFit checkpoints see it for
        free. None outside threshold mode / before placement."""
        u = getattr(self.net, "_upd_states", None)
        if _is_packed(u):
            return (u["ef"], u["tau"])
        return None

    # ------------------------------------------------------------------
    def _shard_batch(self, arr):
        """Divisibility-checked batch placement (sharding.shard_batch:
        rejects indivisible batches naming the axis, never pads).
        Hierarchical mode shards over BOTH factor axes of the 2-D
        (group, intra) mesh — same device order, same per-chip rows as
        the flat data mesh, but placed on the mesh the step runs on."""
        from deeplearning4j_tpu.parallel.sharding import shard_batch

        if arr is None:
            return None
        if self._hmesh is not None:
            return shard_batch(
                arr, self._hmesh,
                batch_axis=(_mesh.GROUP_AXIS, _mesh.INTRA_AXIS))
        return shard_batch(arr, self.mesh, batch_axis=self.batch_axis)

    def _place_replicated(self):
        """Move the net's params/opt/layer state onto the mesh: params
        and layer state replicated always; the updater state replicated
        (default) or in the ZeRO 1/dp-shard layout when
        weight_update='sharded' (the hook + sharded allocation live in
        _place_sharded_update). Idempotent — ResilientFit re-runs it
        after every checkpoint restore."""
        n = self.net
        n._params = jax.device_put(n._params, self._repl)
        n._states = jax.device_put(n._states, self._repl)
        if self.gradient_compression == "threshold":
            self._uninstall_sharded_update()
            self._pack_threshold_state()
            return
        if self.gradient_compression == "hierarchical":
            self._uninstall_sharded_update()
            self._pack_hier_state()
            return
        self._unpack_threshold_state()
        if self._zero is not None:
            self._place_sharded_update()
        else:
            self._uninstall_sharded_update()
            n._upd_states = jax.device_put(n._upd_states, self._repl)

    # ----- threshold mode: the packed residual carry -------------------
    def _pack_threshold_state(self):
        """Wrap the net's updater state as {'upd', 'ef', 'tau'}: the
        per-replica error-feedback residual (leading [dp] device axis,
        sharded over the data axis) and the LIVE tau ride the donated
        updater-state slot, so the step keeps the canonical
        (params, upd, states, ...) signature — one jitted executable,
        k-loop carry and ResilientFit guard/checkpoints all for free.
        Re-placement of an already-packed state (checkpoint restore,
        repeated _place_replicated) is bitwise."""
        n = self.net
        ndev = self.mesh.shape[self.batch_axis]
        ef_sh = NamedSharding(self.mesh, P(self.batch_axis))
        if _is_packed(n._upd_states):
            pack = n._upd_states
            self._check_carry_layout(
                pack, lambda p: (ndev,) + p.shape, "threshold")
            upd = jax.device_put(pack["upd"], self._repl)
            ef = jax.device_put(pack["ef"], ef_sh)
            tau = jax.device_put(jnp.asarray(pack["tau"], jnp.float32),
                                 self._repl)
        else:
            upd = jax.device_put(n._upd_states, self._repl)
            ef = jax.device_put(
                jax.tree_util.tree_map(
                    lambda p: jnp.zeros((ndev,) + p.shape, p.dtype),
                    n._params), ef_sh)
            tau = jax.device_put(jnp.asarray(self.threshold, jnp.float32),
                                 self._repl)
        n._upd_states = {"upd": upd, "ef": ef, "tau": tau}
        # checkpoints save the CANONICAL plain updater state here; the
        # residual itself is saved separately (writeModel trainer_state
        # — see _ckpt_trainer_state) so a threshold-mode save still
        # restores into any mode
        n._upd_state_unview = (
            lambda packed: packed["upd"] if _is_packed(packed) else packed)

    def _check_carry_layout(self, pack, expect_shape, mode):
        """Refuse to re-place a packed {upd, ef, tau} carry whose
        residual layout belongs to the OTHER sparse mode: flat threshold
        carries per-replica full-shape residuals [dp, *p.shape],
        hierarchical carries per-chip shard residuals [groups, group,
        m]. Silently re-placing one as the other would device_put
        garbage into the step."""
        ef_leaves = jax.tree_util.tree_leaves(pack["ef"])
        p_leaves = jax.tree_util.tree_leaves(self.net._params)
        for e, p in zip(ef_leaves, p_leaves):
            want = tuple(expect_shape(p))
            if tuple(e.shape) != want:
                raise ValueError(
                    f"packed residual carry has leaf shape {tuple(e.shape)} "
                    f"where gradient_compression={mode!r} expects {want}: "
                    "the carry was packed by the other sparse mode "
                    "(flat 'threshold' vs 'hierarchical' residual "
                    "layouts are incompatible). Re-fit from a plain "
                    "updater state, or restore a checkpoint taken in "
                    "the same mode.")

    def _pack_hier_state(self):
        """Hierarchical-mode packed carry: same {'upd', 'ef', 'tau'}
        shape as the flat threshold mode, but the error-feedback
        residual lives where hop 2 encodes — the per-chip 1/group_size
        shard of each (zero-padded) leaf, laid out [n_groups,
        group_size, shard_elems] and sharded over BOTH mesh axes, so the
        shard_map step sees exactly its local f32 residual row."""
        from deeplearning4j_tpu.parallel.sharding import \
            hierarchical_shard_elems

        n = self.net
        gsz, ng = self.compression_group, self._n_groups
        ef_sh = NamedSharding(
            self._hmesh, P(_mesh.GROUP_AXIS, _mesh.INTRA_AXIS))
        if _is_packed(n._upd_states):
            pack = n._upd_states
            self._check_carry_layout(
                pack,
                lambda p: (ng, gsz, hierarchical_shard_elems(p.size, gsz)),
                "hierarchical")
            upd = jax.device_put(pack["upd"], self._repl)
            ef = jax.device_put(pack["ef"], ef_sh)
            tau = jax.device_put(jnp.asarray(pack["tau"], jnp.float32),
                                 self._repl)
        else:
            upd = jax.device_put(n._upd_states, self._repl)
            ef = jax.device_put(
                jax.tree_util.tree_map(
                    lambda p: jnp.zeros(
                        (ng, gsz, hierarchical_shard_elems(p.size, gsz)),
                        jnp.float32),
                    n._params), ef_sh)
            tau = jax.device_put(jnp.asarray(self.threshold, jnp.float32),
                                 self._repl)
        n._upd_states = {"upd": upd, "ef": ef, "tau": tau}
        n._upd_state_unview = (
            lambda packed: packed["upd"] if _is_packed(packed) else packed)

    def _unpack_threshold_state(self):
        """Drop a PREVIOUS threshold-mode wrapper's packed carry: restore
        the plain updater state and clear the unview hook, so dense/int8
        wrappers (and the net's own fit) see the canonical layout."""
        n = self.net
        if not _is_packed(getattr(n, "_upd_states", None)):
            return
        n._upd_states = n._upd_states["upd"]
        n._upd_state_unview = None

    def _ckpt_trainer_state(self):
        """The trainer-owned step state a checkpoint must persist for a
        bitwise resume — threshold mode's error-feedback residual and
        live tau (util.sharded_checkpoint writeModel trainer_state=...).
        None when the mode carries no such state."""
        u = getattr(self.net, "_upd_states", None)
        if _is_packed(u):
            return {"ef": u["ef"], "tau": u["tau"]}
        return None

    def _restore_trainer_state(self, state):
        """Install a checkpoint's trainer state into the packed carry
        (call after _place_replicated has packed fresh zeros)."""
        if state is None:
            return
        n = self.net
        if not _is_packed(n._upd_states):
            raise ValueError(
                "restoring sparse-exchange trainer state needs "
                "gradient_compression='threshold' or 'hierarchical' "
                "(the packed carry is not installed)")
        if self._hmesh is not None:
            from deeplearning4j_tpu.parallel.sharding import \
                hierarchical_shard_elems

            gsz, ng = self.compression_group, self._n_groups
            self._check_carry_layout(
                state,
                lambda p: (ng, gsz, hierarchical_shard_elems(p.size, gsz)),
                "hierarchical")
            ef_sh = NamedSharding(
                self._hmesh, P(_mesh.GROUP_AXIS, _mesh.INTRA_AXIS))
        else:
            ndev = self.mesh.shape[self.batch_axis]
            self._check_carry_layout(
                state, lambda p: (ndev,) + p.shape, "threshold")
            ef_sh = NamedSharding(self.mesh, P(self.batch_axis))
        n._upd_states = {
            "upd": n._upd_states["upd"],
            "ef": jax.device_put(state["ef"], ef_sh),
            "tau": jax.device_put(jnp.asarray(state["tau"], jnp.float32),
                                  self._repl),
        }

    def _uninstall_sharded_update(self):
        """Remove a PREVIOUS sharded-mode wrapper's ZeRO hook from the
        net and restore the canonical full-shape updater state: a stale
        `_update_impl` would keep running the sharded update against
        the old wrapper's mesh (and ParameterAveragingTrainingMaster's
        shard_map step would die deep in tracing on the flat-view
        state — exactly the failure its construction check exists to
        prevent)."""
        n = self.net
        if getattr(n, "_update_impl", None) is None:
            return
        unview = getattr(n, "_upd_state_unview", None)
        if unview is not None:
            n._upd_states = unview(n._upd_states)
        n._update_impl = None
        n._upd_state_unview = None

    def _update_units(self):
        """(key, updater, params) per trainable unit, both net types."""
        n = self.net
        if self._is_graph():
            return [(name, n._updaters[name], n._params[name])
                    for name in n._layer_names]
        return [(i, n._updaters[i], n._params[i])
                for i in range(len(n.layers))]

    def _place_sharded_update(self):
        """Install the ZeRO update hook and put the updater state into
        the sharded layout: a fresh net (iteration 0) ALLOCATES the
        state sharded — each chip only ever materialises its 1/dp shard
        of the fp32 moments — while mid-training state (including a
        restored checkpoint's canonical full-shape layout) is re-placed
        bitwise (the view is a reshape)."""
        n, z = self.net, self._zero
        if self.gradient_compression is None:
            n._update_impl = z
        else:
            # compressed modes trace inside an explicit shard_map where
            # GSPMD annotations cannot apply: the manual twin runs the
            # QUANTIZED reduce-scatter -> local 1/dp shard update ->
            # all-gather with the same eligibility and state layout
            from deeplearning4j_tpu.parallel.sharding import \
                ManualZeroUpdate

            n._update_impl = ManualZeroUpdate(
                z, self.gradient_compression, self.compression_block)
        n._upd_state_unview = self._unview_upd_states
        fresh = n._iteration == 0
        new = dict(n._upd_states) if self._is_graph() \
            else list(n._upd_states)
        for key, u, p in self._update_units():
            if not p:
                continue
            new[key] = z.init_state(u, p) if fresh \
                else z.place_state(n._upd_states[key])
        n._upd_states = new

    def _unview_upd_states(self, upd_states):
        """Sharded view layout -> the canonical full-shape updater-state
        layout (installed as net._upd_state_unview; checkpoints save the
        canonical form so a sharded-mode save restores into any mode
        bitwise — see util.sharded_checkpoint._net_state)."""
        z = self._zero
        new = dict(upd_states) if self._is_graph() else list(upd_states)
        for key, u, p in self._update_units():
            if not p:
                continue
            new[key] = z.unview_state(upd_states[key], u, p)
        return new

    def _aot_extra(self):
        """Key suffix describing program context the net's config hash
        cannot see: the mesh, the compression mode (and its static
        knobs — block size, encoding capacity, adaptive target,
        residual clipping; the tau VALUE rides as a runtime array) and
        the weight-update mode all change the traced program."""
        return (f"|pw[mesh={sorted(dict(self.mesh.shape).items())},"
                f"axis={self.batch_axis},"
                f"comp={self.gradient_compression},"
                f"blk={self.compression_block},"
                f"cap={self.encoding_capacity},"
                f"tgt={self.targetSparsity},"
                f"clip={self.residual_clip}"
                f"@{self.residual_clip_frequency},"
                f"grp={self.compression_group},"
                f"imode={self.intra_compression},"
                f"wu={self.weight_update}]")

    def _build_jit(self):
        n = self.net
        if self.gradient_compression is None:
            step = n._train_step
        elif self.gradient_compression == "threshold":
            step = self._threshold_step
        elif self.gradient_compression == "hierarchical":
            step = self._hierarchical_step
        else:
            step = self._compressed_step
        # params/opt/state replicated; batch args sharded over the data
        # axis. Routed through the AOT executable cache (runtime.aot):
        # the extra key part carries the mesh/compression/update mode.
        # The threshold step qualifies too now that its residual rides
        # the donated updater-state carry (tau is a runtime array, not
        # a trace-baked constant).
        from deeplearning4j_tpu.runtime import aot

        self._jit = aot.cached_jit(step, owner=n, entry="pw_train_step",
                                   extra=self._aot_extra(),
                                   donate_argnums=(0, 1, 2))

    def _upd_specs(self):
        """shard_map partition specs for the updater-state argument:
        replicated by default; under the compressed sharded update the
        eligible leaves live as flat 1/dp shards over the data axis —
        read off the PLACED state's actual shardings so the spec tree
        can never drift from the layout."""
        if self._zero is None:
            return P()
        return jax.tree_util.tree_map(
            lambda l: l.sharding.spec if hasattr(l, "sharding") else P(),
            self.net._upd_states)

    def _compressed_step(self, params, upd_states, states, iteration, x, y,
                         key, fmask, lmask):
        """Train step with an explicit quantized gradient all-reduce:
        per-tensor scale ("int8") or per-block scale ("block_int8",
        EQuARX-style). shard_map over the data axis expresses the
        quantize → integer psum → dequantize pipeline directly
        (parallel.sharding.quantized_psum_mean). With
        weight_update='sharded' the gradient reduction instead happens
        INSIDE the weight-update hook (ManualZeroUpdate): a QUANTIZED
        reduce-scatter feeds the local 1/dp shard update and the fresh
        shards are all-gathered — compression and ZeRO stack."""
        from jax import shard_map
        from deeplearning4j_tpu.parallel.sharding import \
            quantized_psum_mean

        n = self.net
        mesh, ax = self.mesh, self.batch_axis
        dp = int(self.mesh.shape[ax])
        mode, blk = self.gradient_compression, self.compression_block
        sharded = self._zero is not None

        def qall_tree(grads):
            return jax.tree_util.tree_map(
                lambda g: quantized_psum_mean(g, ax, dp, mode, blk),
                grads)

        def sync_states(states):
            # Per-shard batch stats (BN running mean/var) diverge across the
            # mesh; pmean keeps the returned "replicated" state consistent on
            # every device (cross-replica BN, mean-of-shard-stats).
            return jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, ax)
                if jnp.issubdtype(a.dtype, jnp.inexact) else a, states)

        def shard_step(params_r, upd_r, states_r, it_r, x_s, y_s, key_r, fm_s, lm_s):
            # sharded: grads reach the update hook UNREDUCED — the
            # ManualZeroUpdate hook performs the compressed
            # reduce-scatter (eligible leaves) / all-reduce (fallback)
            return n._train_step(
                params_r, upd_r, states_r, it_r, x_s, y_s, key_r, fm_s, lm_s,
                grad_transform=None if sharded else qall_tree,
                loss_transform=lambda l: jax.lax.pmean(l, ax),
                state_transform=sync_states)

        spec_b = P(ax)
        upd_specs = self._upd_specs()
        return shard_map(
            shard_step, mesh=mesh,
            in_specs=(P(), upd_specs, P(), P(), spec_b, spec_b, P(),
                      spec_b if fmask is not None else P(),
                      spec_b if lmask is not None else P()),
            out_specs=(P(), upd_specs, P(), P()),
            check_vma=False,
        )(params, upd_states, states, iteration, x, y, key, fmask, lmask)

    def _threshold_step(self, params, upd_states, states, iteration, x, y,
                        key, fmask, lmask):
        """Train step with threshold-encoded gradient sharing (reference:
        Strom 2015, the algorithm behind upstream SharedTrainingMaster's
        sparse updates). Each replica adds its error-feedback residual
        to the fresh gradient and transmits at most
        ceil(encodingCapacity * n) entries per leaf — the top-|.|
        candidates with |value| >= tau, encoded as +-tau (sign
        encoding); the remainder is next step's residual. The fixed
        capacity keeps every shape static, so the whole step is ONE
        jitted executable whose carry (residual + live tau) rides the
        donated updater-state slot with the canonical signature.

        The wire format is genuinely sparse: each replica all-gathers
        its (index, +-tau) pairs and scatter-adds the dp messages into
        the dense mean — bytes-on-wire scale with the capacity, not the
        model (parallel.sharding.compressed_wire_bytes bills it)."""
        from jax import shard_map
        from deeplearning4j_tpu.ndarray.compression import (
            threshold_cap, threshold_encode_fixed,
        )

        n = self.net
        mesh, ax = self.mesh, self.batch_axis
        target = self.targetSparsity
        capacity = self.encoding_capacity
        clip, clip_freq = self.residual_clip, self.residual_clip_frequency

        def sync_states(states):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, ax)
                if jnp.issubdtype(a.dtype, jnp.inexact) else a, states)

        def shard_step(params_r, pack, states_r, it_r, x_s, y_s,
                       key_r, fm_s, lm_s):
            upd_r, res_s, t = pack["upd"], pack["ef"], pack["tau"]
            new_pack_cell = []

            def encode_all(grads):
                g_leaves, treedef = jax.tree_util.tree_flatten(grads)
                r_leaves = jax.tree_util.tree_flatten(res_s)[0]
                means, new_rs = [], []
                sent = 0.0
                total = 0
                dp = jax.lax.psum(1, ax)
                for g, r in zip(g_leaves, r_leaves):
                    acc = (g + r[0].astype(g.dtype)).reshape(-1)
                    cap = threshold_cap(acc.size, capacity)
                    idx, val, dense, res = threshold_encode_fixed(
                        acc, t, cap)
                    # the sparse transmission: every replica broadcasts
                    # its cap (index, +-tau) pairs; scatter-add
                    # reassembles the dense sum locally
                    gi = jax.lax.all_gather(idx, ax, tiled=True)
                    gv = jax.lax.all_gather(val, ax, tiled=True)
                    summed = jnp.zeros_like(acc).at[gi].add(gv)
                    means.append((summed / dp).reshape(g.shape)
                                 .astype(g.dtype))
                    if clip is not None:
                        # ResidualClippingPostProcessor: bound stale
                        # accumulation to +-(clip * tau) every clip_freq
                        # iterations
                        lim = (clip * t).astype(res.dtype)
                        clipped = jnp.clip(res, -lim, lim)
                        res = jnp.where((it_r % clip_freq) == 0,
                                        clipped, res) \
                            if clip_freq > 1 else clipped
                    new_rs.append(res.reshape(g.shape)[None]
                                  .astype(r.dtype))
                    sent = sent + jnp.sum(jnp.abs(val) > 0)
                    total += acc.size
                if target is None:
                    new_t = t
                else:
                    # adaptive threshold (reference:
                    # AdaptiveThresholdAlgorithm): multiplicative steps
                    # keep the mean transmitted fraction near the target
                    frac = jax.lax.pmean(sent / total, ax)
                    new_t = jnp.where(
                        frac > 1.25 * target, t * 1.1,
                        jnp.where(frac < 0.8 * target, t / 1.1, t))
                new_pack_cell.append(
                    (jax.tree_util.tree_unflatten(treedef, new_rs),
                     new_t.astype(jnp.float32)))
                return jax.tree_util.tree_unflatten(treedef, means)

            p, u, s, loss = n._train_step(
                params_r, upd_r, states_r, it_r, x_s, y_s, key_r, fm_s, lm_s,
                grad_transform=encode_all,
                loss_transform=lambda l: jax.lax.pmean(l, ax),
                state_transform=sync_states)
            new_res, new_t = new_pack_cell[0]
            return p, {"upd": u, "ef": new_res, "tau": new_t}, s, loss

        spec_b = P(ax)
        ef_specs = jax.tree_util.tree_map(lambda _: P(ax),
                                          self.net._upd_states["ef"])
        pack_specs = {"upd": P(), "ef": ef_specs, "tau": P()}
        return shard_map(
            shard_step, mesh=mesh,
            in_specs=(P(), pack_specs, P(), P(), spec_b, spec_b, P(),
                      spec_b if fmask is not None else P(),
                      spec_b if lmask is not None else P()),
            out_specs=(P(), pack_specs, P(), P()),
            check_vma=False,
        )(params, upd_states, states, iteration, x, y, key, fmask, lmask)

    def _hierarchical_step(self, params, upd_states, states, iteration,
                           x, y, key, fmask, lmask):
        """Train step with the 2-hop hierarchical sparse exchange
        (ROADMAP item 4): hop 1 is a dense-or-block_int8 psum_scatter
        reduce over the INTRA axis (each chip ends up owning the group
        sum of a 1/group_size shard), hop 2 is the fixed-capacity Strom
        threshold exchange over the GROUP axis — every chip encodes its
        shard's above-tau entries and all-gathers the (index, +-tau)
        pairs with the n_groups-1 peer chips holding the SAME shard in
        the other groups — then the dense mean shard is all-gathered
        back over the intra axis. Error feedback lives on the per-chip
        shard (where hop 2 truncates), so the carry {upd, ef, tau}
        rides the donated updater-state slot exactly as the flat
        threshold mode's does: one jitted executable, bitwise k-loop
        and ResilientFit resume. Wire bytes scale with
        capacity x n_groups (not capacity x dp) — bills in
        parallel.sharding.compressed_wire_bytes."""
        from jax import shard_map
        from deeplearning4j_tpu.parallel.sharding import \
            hierarchical_grad_exchange

        n = self.net
        hmesh = self._hmesh
        gax, iax = _mesh.GROUP_AXIS, _mesh.INTRA_AXIS
        gsz, ng = self.compression_group, self._n_groups
        target = self.targetSparsity
        capacity = self.encoding_capacity
        clip, clip_freq = self.residual_clip, self.residual_clip_frequency
        imode, blk = self.intra_compression, self.compression_block

        def sync_states(states):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, (gax, iax))
                if jnp.issubdtype(a.dtype, jnp.inexact) else a, states)

        def shard_step(params_r, pack, states_r, it_r, x_s, y_s,
                       key_r, fm_s, lm_s):
            upd_r, res_s, t = pack["upd"], pack["ef"], pack["tau"]
            new_pack_cell = []

            def encode_all(grads):
                g_leaves, treedef = jax.tree_util.tree_flatten(grads)
                r_leaves = jax.tree_util.tree_flatten(res_s)[0]
                means, new_rs = [], []
                sent = 0.0
                total = 0
                for g, r in zip(g_leaves, r_leaves):
                    mean, res, nsent = hierarchical_grad_exchange(
                        g, r[0, 0], t, group_size=gsz, n_groups=ng,
                        capacity=capacity, group_axis=gax,
                        intra_axis=iax, intra_mode=imode, block=blk)
                    if clip is not None:
                        lim = (clip * t).astype(res.dtype)
                        clipped = jnp.clip(res, -lim, lim)
                        res = jnp.where((it_r % clip_freq) == 0,
                                        clipped, res) \
                            if clip_freq > 1 else clipped
                    means.append(mean)
                    new_rs.append(res[None, None].astype(r.dtype))
                    sent = sent + nsent
                    total += res.size
                if target is None:
                    new_t = t
                else:
                    # adaptive tau tracks the mean TRANSMITTED fraction
                    # of the per-chip shards (the quantity hop 2 pays
                    # wire for), averaged over the whole 2-D mesh
                    frac = jax.lax.pmean(sent / total, (gax, iax))
                    new_t = jnp.where(
                        frac > 1.25 * target, t * 1.1,
                        jnp.where(frac < 0.8 * target, t / 1.1, t))
                new_pack_cell.append(
                    (jax.tree_util.tree_unflatten(treedef, new_rs),
                     new_t.astype(jnp.float32)))
                return jax.tree_util.tree_unflatten(treedef, means)

            p, u, s, loss = n._train_step(
                params_r, upd_r, states_r, it_r, x_s, y_s, key_r, fm_s, lm_s,
                grad_transform=encode_all,
                loss_transform=lambda l: jax.lax.pmean(l, (gax, iax)),
                state_transform=sync_states)
            new_res, new_t = new_pack_cell[0]
            return p, {"upd": u, "ef": new_res, "tau": new_t}, s, loss

        spec_b = P((gax, iax))
        ef_specs = jax.tree_util.tree_map(lambda _: P(gax, iax),
                                          self.net._upd_states["ef"])
        pack_specs = {"upd": P(), "ef": ef_specs, "tau": P()}
        return shard_map(
            shard_step, mesh=hmesh,
            in_specs=(P(), pack_specs, P(), P(), spec_b, spec_b, P(),
                      spec_b if fmask is not None else P(),
                      spec_b if lmask is not None else P()),
            out_specs=(P(), pack_specs, P(), P()),
            check_vma=False,
        )(params, upd_states, states, iteration, x, y, key, fmask, lmask)

    # ------------------------------------------------------------------
    def fit(self, data, labels=None, epochs=None):
        from deeplearning4j_tpu.data.dataset import DataSet

        n = self.net
        n._require_init()
        if self._jit is None:
            self._place_replicated()
            self._build_jit()
        if labels is not None:
            self._fit_batch(DataSet(data, labels))
            return self
        if isinstance(data, DataSet):
            self._fit_batch(data)
            return self
        for _ in range(epochs or 1):
            data.reset()
            while data.hasNext():
                self._fit_batch(data.next())
            n._epoch += 1
        return self

    def _is_graph(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        return isinstance(self.net, ComputationGraph)

    def _fit_batch(self, ds):
        n = self.net
        x = _unwrap(ds.getFeatures())
        y = _unwrap(ds.getLabels())
        fmask = _unwrap(ds.getFeaturesMaskArray())
        lmask = _unwrap(ds.getLabelsMaskArray())
        x = self._shard_batch(x)
        y = self._shard_batch(y)
        fmask = self._shard_batch(fmask)
        lmask = self._shard_batch(lmask)
        if self._is_graph():
            # ComputationGraph._train_step takes an inputs dict + labels
            # list (single-input/-output graphs through this wrapper)
            if len(n.conf.networkInputs) != 1 or len(n.conf.networkOutputs) != 1:
                raise ValueError(
                    "ParallelWrapper supports single-input/single-output "
                    "ComputationGraphs; use MultiDataSet-aware training "
                    "directly for multi-IO graphs")
            x = {n.conf.networkInputs[0]: x}
            y = [y]
            fmask = None if fmask is None else {n.conf.networkInputs[0]: fmask}
            lmask = None if lmask is None else [lmask]
        key = jax.random.fold_in(jax.random.key(n.conf.seed ^ 0x5EED), n._iteration)
        n._params, n._upd_states, n._states, loss = self._jit(
            n._params, n._upd_states, n._states,
            jnp.asarray(n._iteration, jnp.int32), x, y, key, fmask, lmask)
        n._score = float(loss)
        n._iteration += 1
        for lst in n._listeners:
            lst.iterationDone(n, n._iteration, n._epoch)

    def fitDataSet(self, iterator, stepsPerSync=1, epochs=None):
        """Sharded form of MultiLayerNetwork.fitDataSet: k fresh batches
        are staged as ONE [k, B, ...] stack per component, placed with
        the batch dim sharded over the data axis (sharding.
        shard_batch_stack — the same divisibility-checked shard_batch
        every trainer uses, never padding), and trained by one jitted
        lax.fori_loop whose step i indexes a correctly-sharded global
        batch — GSPMD inserts the gradient collectives inside the loop.
        One host sync and one transfer per k batches; double-buffered
        staging; ragged tail through the per-batch sharded fit path.
        Supports every gradient_compression mode — the threshold step's
        residual + tau ride the donated updater-state carry, so the
        k-loop threads them like any other state."""
        from deeplearning4j_tpu.data.iterators import stack_datasets
        from deeplearning4j_tpu.nn.multilayer import (
            fit_dataset_jit, run_fit_dataset_epoch)
        from deeplearning4j_tpu.parallel.sharding import shard_batch_stack

        n = self.net
        n._require_init()
        k = int(stepsPerSync)
        if k < 1:
            raise ValueError(f"stepsPerSync must be >= 1, got {k}")
        if k == 1:
            it0 = n._iteration
            self.fit(iterator, epochs=epochs)
            self._fit_dataset_syncs = n._iteration - it0  # 1/batch
            return self
        bp = getattr(n.conf, "backpropType", None)
        if bp == "tbptt" or str(getattr(bp, "name", bp)) == "TruncatedBPTT":
            raise ValueError(
                "fitDataSet does not support truncated BPTT; use fit()")
        if self._is_graph() and (len(n.conf.networkInputs) != 1
                                 or len(n.conf.networkOutputs) != 1):
            raise ValueError(
                "ParallelWrapper supports single-input/single-output "
                "ComputationGraphs")
        step = self.trainStep()
        if self._jit is None:
            self._place_replicated()
            self._build_jit()
        jloop = fit_dataset_jit(n, k, step_fn=step, owner=self,
                                aot_extra=self._aot_extra())

        if self._is_graph():
            name = n.conf.networkInputs[0]

            def stack_fn(batches):
                x, y, fm, lm = stack_datasets(batches)
                return ({name: x}, [y],
                        None if fm is None else {name: fm},
                        None if lm is None else [lm])
        else:
            stack_fn = stack_datasets

        def place(staged):
            if self._hmesh is not None:
                return shard_batch_stack(
                    staged, self._hmesh,
                    (_mesh.GROUP_AXIS, _mesh.INTRA_AXIS))
            return shard_batch_stack(staged, self.mesh, self.batch_axis)

        self._fit_dataset_syncs = 0
        for _ in range(epochs or 1):
            iterator.reset()
            self._fit_dataset_syncs += run_fit_dataset_epoch(
                n, iterator, k, stack_fn, self._fit_batch, jloop,
                place=place)
            n._epoch += 1
        return self

    def precompile(self, batchSize=32, featuresShape=None,
                   labelsShape=None, cache=None):
        """AOT warm-start of the sharded train step (see
        MultiLayerNetwork.precompile): places the model on the mesh,
        builds the distributed step and compiles (or loads from the
        persistent cache) its executable for one GLOBAL batch
        signature. Composes with weight_update='sharded' — the ZeRO
        layout is part of the cache key, and the updater state is
        allocated sharded before the warm lowering, exactly as fit()
        would — and with every compression mode (the threshold carry
        is part of the warmed signature since it rides the updater
        state)."""
        from deeplearning4j_tpu.nn.multilayer import example_batch

        n = self.net
        n._require_init()
        if self._jit is None:
            self._place_replicated()
            self._build_jit()
        if not hasattr(self._jit, "warm"):
            return {}
        if self._is_graph():
            featuresShape, labelsShape = n._example_shapes(
                batchSize, featuresShape, labelsShape)
            x = np.zeros(featuresShape, np.float32)
            y = np.zeros(labelsShape, np.float32)
        else:
            x, y = example_batch(n, batchSize, featuresShape,
                                 labelsShape)
        x = self._shard_batch(jnp.asarray(x))
        y = self._shard_batch(jnp.asarray(y))
        if self._is_graph():
            x = {n.conf.networkInputs[0]: x}
            y = [y]
        key = jax.random.fold_in(
            jax.random.key(n.conf.seed ^ 0x5EED), n._iteration)
        res = self._jit.warm(
            n._params, n._upd_states, n._states,
            jnp.asarray(n._iteration, jnp.int32), x, y, key, None, None,
            cache=cache)
        k_, status, secs = res
        return {} if status is None else {
            "pw_train_step": {"key": k_, "status": status,
                              "seconds": round(secs, 3)}}

    def trainStep(self):
        """The un-jitted per-batch step function with the canonical
        `(params, upd, states, it, x, y, key, fmask, lmask) ->
        (params', upd', states', loss)` signature, for harnesses that
        splice logic around it before jitting — runtime.resilience
        wraps it in the non-finite guard. Every compression mode is
        wrappable: the threshold step's residual + tau ride inside the
        updater-state slot, so a guarded skip rolls them back with the
        rest of the carry (exactly the error-feedback semantics a
        skipped step needs)."""
        if self.gradient_compression is None:
            return self.net._train_step
        if self.gradient_compression == "threshold":
            return self._threshold_step
        if self.gradient_compression == "hierarchical":
            return self._hierarchical_step
        return self._compressed_step

    def averagingFrequency(self, *_):
        # synchronous psum makes per-step averaging exact already; the
        # reference's periodic-averaging semantics live in
        # ParameterAveragingTrainingMaster below
        return self

    def workers(self, *_):
        return self


class SharedTrainingMaster(ParallelWrapper):
    """Gradient-sharing distributed trainer (reference: Spark
    SharedTrainingMaster). Alias of ParallelWrapper with the quantized
    all-reduce enabled by default — the ICI-native analog of the
    reference's threshold-encoded sparse updates. Pass
    ``gradient_compression=None`` for the dense bf16 psum,
    ``"block_int8"`` for EQuARX-style per-block scales, or
    ``"threshold"`` / a ``thresholdAlgorithm`` for the reference's
    actual Strom-2015 algorithm (fixed-capacity sparsified +-tau
    updates with per-replica error feedback — see
    ParallelWrapper._threshold_step).

    ``thresholdAlgorithm`` maps to REAL trainer config, not an opaque
    kwarg: a bare number or FixedThresholdAlgorithm pins tau;
    AdaptiveThresholdAlgorithm / TargetSparsityThresholdAlgorithm set
    the initial tau plus targetSparsity (the adaptive loop);
    ``residualPostProcessor=ResidualClippingPostProcessor(...)`` wires
    residual clipping. Unknown algorithm objects raise naming the
    supported set.

    ``compressionGroupSize=g`` selects the hierarchical 2-hop exchange
    (``gradient_compression="hierarchical"``) with node groups of g
    chips: dense/block_int8 reduce-scatter inside each group, Strom
    threshold exchange between group leaders — wire bytes scale with
    capacity x n_groups instead of capacity x dp (see
    ParallelWrapper._hierarchical_step). Composes with
    thresholdAlgorithm / residualPostProcessor, which configure the
    leader hop's encoder."""

    def __init__(self, net, mesh=None, thresholdAlgorithm=None,
                 residualPostProcessor=None, compressionGroupSize=None,
                 **kw):
        if compressionGroupSize is not None:
            # process FIRST so a bare compressionGroupSize= selects the
            # hierarchical mode before the threshold-algorithm mapping
            # defaults gradient_compression (the algorithm then
            # configures hop 2's tau, which IS the Strom encoder)
            gc = kw.get("gradient_compression", "hierarchical")
            if gc != "hierarchical":
                raise ValueError(
                    f"compressionGroupSize given together with "
                    f"gradient_compression={gc!r}: the node-group size "
                    "only applies to the 'hierarchical' 2-hop exchange; "
                    "drop one of the two arguments")
            kw.setdefault("gradient_compression", "hierarchical")
            kw["compressionGroupSize"] = compressionGroupSize
        if thresholdAlgorithm is not None:
            gc = kw.get("gradient_compression", "threshold")
            if gc not in ("threshold", "hierarchical"):
                raise ValueError(
                    f"thresholdAlgorithm given together with "
                    f"gradient_compression={gc!r}: the threshold algorithm "
                    "only applies to the 'threshold' (Strom-2015) encoding "
                    "or the 'hierarchical' 2-hop exchange (whose leader "
                    "hop is the same encoder); drop one of the two "
                    "arguments")
            kw.setdefault("gradient_compression", "threshold")
            algo = thresholdAlgorithm
            if isinstance(algo, (int, float)) \
                    and not isinstance(algo, bool):
                algo = FixedThresholdAlgorithm(algo)
            if isinstance(algo, AdaptiveThresholdAlgorithm):
                kw.setdefault("threshold", algo.threshold)
                kw.setdefault("targetSparsity", algo.sparsityTarget)
            elif isinstance(algo, FixedThresholdAlgorithm) \
                    or hasattr(algo, "threshold"):
                # any object carrying .threshold duck-types as fixed
                kw.setdefault("threshold", float(algo.threshold))
            else:
                names = [c.__name__ for c in THRESHOLD_ALGORITHMS]
                raise ValueError(
                    f"unknown thresholdAlgorithm {thresholdAlgorithm!r}; "
                    f"pass a number (fixed tau) or one of {names}")
        if residualPostProcessor is not None:
            if kw.get("gradient_compression",
                      "threshold") not in ("threshold", "hierarchical") \
                    and thresholdAlgorithm is None:
                raise ValueError(
                    "residualPostProcessor only applies to the "
                    "'threshold' and 'hierarchical' encodings (there "
                    "is no residual elsewhere)")
            rpp = residualPostProcessor
            if not isinstance(rpp, ResidualClippingPostProcessor):
                raise ValueError(
                    f"unknown residualPostProcessor {rpp!r}; supported: "
                    "ResidualClippingPostProcessor")
            kw.setdefault("gradient_compression", "threshold")
            kw.setdefault("residualClip", rpp.clipValue)
            kw.setdefault("residualClipFrequency", rpp.frequency)
        # ISSUE 11: compression and the ZeRO sharded update now STACK
        # (compressed reduce-scatter) — asking for weight_update=
        # "sharded" keeps this master's int8 default instead of
        # silently opting out; only "threshold" cannot compose (the
        # ParallelWrapper constructor rejects that pair loudly)
        kw.setdefault("gradient_compression", "int8")
        super().__init__(net, mesh=mesh, **kw)


class ParameterAveragingTrainingMaster(ParallelWrapper):
    """Parameter-averaging distributed trainer (reference: Spark
    ParameterAveragingTrainingMaster.java). Each data-shard replica takes
    LOCAL updater steps on its own copy of the parameters — no per-step
    gradient allreduce — and every ``averagingFrequency`` iterations the
    parameters, updater state and layer state are averaged across the mesh
    (``pmean`` over ICI plays the role of the Spark driver's aggregate).

    With ``averagingFrequency=1`` and plain SGD this is mathematically
    identical to synchronous gradient sharing; larger frequencies trade
    fidelity for fewer collectives, exactly the reference's knob.
    """

    def __init__(self, net, mesh=None, averagingFrequency=5,
                 batch_axis=_mesh.DATA_AXIS, weight_update="replicated"):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        if isinstance(net, ComputationGraph):
            raise ValueError(
                "ParameterAveragingTrainingMaster supports "
                "MultiLayerNetwork; for ComputationGraph data-parallel "
                "training use ParallelWrapper/SharedTrainingMaster "
                "(single-input/-output graphs)")
        if weight_update == "sharded":
            # reject HERE, not deep in jit tracing: this master keeps a
            # PER-REPLICA stacked copy of params+updater state (local
            # steps, periodic pmean) — there is no single cross-replica
            # update to shard, and the stacked state's leading replica
            # axis would collide with the ZeRO flat-shard views
            raise ValueError(
                "ParameterAveragingTrainingMaster does not support "
                "weight_update='sharded': its replicas take LOCAL "
                "updater steps on per-replica state, so there is no "
                "cross-replica weight update to shard. The ZeRO-style "
                "sharded update is supported by ParallelWrapper and "
                "SharedTrainingMaster(gradient_compression=None).")
        super().__init__(net, mesh=mesh, batch_axis=batch_axis,
                         weight_update=weight_update)
        if int(averagingFrequency) < 1:
            raise ValueError("averagingFrequency must be >= 1")
        self._avg_freq = int(averagingFrequency)
        self._stacked = None  # (params, upd_states, states) + replica axis

    def trainStep(self):
        raise ValueError(
            "ParameterAveragingTrainingMaster's step is not expressible "
            "as one wrappable train step: it takes LOCAL per-replica "
            "steps on stacked state with a periodic pmean, all inside "
            "its own _fit_batch. Wrap ParallelWrapper/"
            "SharedTrainingMaster in ResilientFit instead, or run this "
            "master without the non-finite guard")

    def fitDataSet(self, iterator, stepsPerSync=1, epochs=None):
        if int(stepsPerSync) == 1:
            return self.fit(iterator, epochs=epochs)
        raise ValueError(
            "ParameterAveragingTrainingMaster does not support "
            "stepsPerSync > 1: it picks a different executable per "
            "iteration host-side (averaging vs local step), which a "
            "single traced k-loop cannot express without paying the "
            "full-state pmean every step; use ParallelWrapper/"
            "SharedTrainingMaster for the k-stack loop")

    def averagingFrequency(self, k):
        if self._jit is not None:
            raise RuntimeError("set averagingFrequency before the first fit()")
        if int(k) < 1:
            raise ValueError("averagingFrequency must be >= 1")
        self._avg_freq = int(k)
        return self

    # ------------------------------------------------------------------
    def _place_replicated(self):
        """Give every replica its own (initially identical) copy: stack each
        leaf along a leading replica axis sharded over the data axis."""
        # a net previously trained under a sharded-update or threshold
        # wrapper must shed the ZeRO hook / packed residual carry before
        # stacking
        self._uninstall_sharded_update()
        self._unpack_threshold_state()
        n, dp = self.net, self.mesh.shape[self.batch_axis]

        def stack(tree):
            def one(a):
                a = jnp.asarray(a)
                sh = NamedSharding(self.mesh,
                                   P(self.batch_axis, *([None] * a.ndim)))
                return jax.device_put(jnp.stack([a] * dp), sh)
            return jax.tree_util.tree_map(one, tree)

        self._stacked = (stack(n._params), stack(n._upd_states),
                         stack(n._states))

    def _build_jit(self):
        from jax import shard_map

        n, mesh, ax = self.net, self.mesh, self.batch_axis

        def make_step(do_avg):
            # two step variants chosen HOST-side by the iteration counter:
            # the averaging collective only exists in the executable that
            # runs at averaging points — a traced jnp.where would make XLA
            # pay the full pmean of params+opt+state every single step,
            # which is exactly the traffic this mode exists to avoid
            def shard_step(params, upd, states, it, x, y, key, fm, lm):
                sq = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
                params, upd, states = sq(params), sq(upd), sq(states)
                # decorrelate per-replica dropout like distinct Spark workers
                key = jax.random.fold_in(key, jax.lax.axis_index(ax))
                p, u, s, loss = n._train_step(params, upd, states, it, x, y,
                                              key, fm, lm)
                if do_avg:
                    avg = lambda t: jax.tree_util.tree_map(
                        lambda a: jax.lax.pmean(a, ax)
                        if jnp.issubdtype(a.dtype, jnp.inexact) else a, t)
                    p, u, s = avg(p), avg(u), avg(s)
                loss = jax.lax.pmean(loss, ax)
                ex = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
                return ex(p), ex(u), ex(s), loss

            def step(params, upd, states, it, x, y, key, fm, lm):
                spec_b = P(ax)
                return shard_map(
                    shard_step, mesh=mesh,
                    in_specs=(spec_b, spec_b, spec_b, P(), spec_b, spec_b, P(),
                              spec_b if fm is not None else P(),
                              spec_b if lm is not None else P()),
                    out_specs=(spec_b, spec_b, spec_b, P()),
                    check_vma=False,
                )(params, upd, states, it, x, y, key, fm, lm)

            return jax.jit(step, donate_argnums=(0, 1, 2))

        self._jit = make_step(False)
        self._jit_avg = make_step(True)

    def _fit_batch(self, ds):
        from deeplearning4j_tpu.nn.multilayer import _unwrap as unw

        n = self.net
        x, y = unw(ds.getFeatures()), unw(ds.getLabels())
        fmask, lmask = unw(ds.getFeaturesMaskArray()), unw(ds.getLabelsMaskArray())
        x = self._shard_batch(x)
        y = self._shard_batch(y)
        fmask = self._shard_batch(fmask)
        lmask = self._shard_batch(lmask)
        key = jax.random.fold_in(jax.random.key(n.conf.seed ^ 0x5EED), n._iteration)
        p, u, s = self._stacked
        step = self._jit_avg if (n._iteration + 1) % self._avg_freq == 0 \
            else self._jit
        p, u, s, loss = step(p, u, s, jnp.asarray(n._iteration, jnp.int32),
                             x, y, key, fmask, lmask)
        self._stacked = (p, u, s)
        n._score = float(loss)
        n._iteration += 1
        for lst in n._listeners:
            lst.iterationDone(n, n._iteration, n._epoch)

    def fit(self, data, labels=None, epochs=None):
        super().fit(data, labels, epochs)
        self._sync_to_net()
        return self

    def _sync_to_net(self):
        """Expose the replica-average as the net's canonical model (the
        reference's driver-side aggregated model)."""
        if self._stacked is None:
            return

        def collapse(tree):
            return jax.tree_util.tree_map(
                lambda a: a.mean(0) if jnp.issubdtype(a.dtype, jnp.inexact)
                else a[0], tree)

        n = self.net
        p, u, s = self._stacked
        n._params, n._upd_states, n._states = collapse(p), collapse(u), collapse(s)
