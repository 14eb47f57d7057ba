"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

Reference: no upstream analog (DL4J scales out data-parallel only); this
is the TPU-first addition SURVEY.md row 43 commits to. Design follows the
standard SPMD pipeline formulation (GPipe, Huang et al. 2019; the
jax-ml scaling-book pipelining recipe):

  * the network's layer list is partitioned into a replicated PROLOGUE,
    S structurally identical BODY STAGES (params stacked leaf-wise along a
    leading axis sharded over the mesh "pipe" axis), and a replicated
    EPILOGUE (output head + loss);
  * one training step runs `n_microbatches + S - 1` ticks inside a
    `lax.scan`; every tick each device applies ITS stage to its resident
    activation and hands the result to the next stage with
    `lax.ppermute` over ICI. The first S-1 ticks fill the pipe, the last
    S-1 drain it (the usual GPipe bubble);
  * the whole schedule lives inside ONE `shard_map`-ped, jitted train
    step, so `jax.grad` differentiates straight through the scan and the
    ppermutes — the backward pipeline (reverse ppermutes) is generated by
    AD, not hand-scheduled;
  * composes with data parallelism: batch sharded over "data", stage
    params sharded over "pipe", gradients pmean'd over "data" by AD of
    the pmean'd loss.

Constraints (checked loudly): body stages must be structurally identical
(same layer types + param shapes — the transformer/MLP-stack case pipeline
parallelism exists for), stage boundaries must carry one fixed activation
shape, and ALL layers must be stateless (forward-state updates like BN
running stats don't survive the pipelined step; BN-bearing nets belong
in dp/tp regimes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel import mesh as _mesh
from deeplearning4j_tpu.nn.multilayer import _unwrap


def _cfg_repr(v):
    """Value-based repr for config objects: two separately-constructed
    Dropout(0.5) instances must compare equal (default object repr carries
    a memory address and would reject valid homogeneous stacks)."""
    if v is None or isinstance(v, (int, float, str, bool)):
        return v
    try:
        return (type(v).__name__,
                tuple(sorted((k, _cfg_repr(x)) for k, x in vars(v).items())))
    except TypeError:
        return type(v).__name__


def _layer_signature(layer, params):
    """Stages must match on this: type, param shapes AND the behavioral
    config (activation/dropout/updater) — shape-equal layers with
    different activations are NOT interchangeable stages."""
    return (type(layer).__name__,
            str(getattr(layer, "activation", None)),
            _cfg_repr(getattr(layer, "dropOut", None)),
            _cfg_repr(getattr(layer, "updater", None)),
            tuple(sorted((k, tuple(v.shape)) for k, v in params.items())))


def stage_input_sizes(conf):
    """Per-layer boundary feature sizes for partition_stages'
    `input_sizes` (index len(layers) = network output), read from the
    config's recorded layerInputTypes. ONE source of truth shared by
    PipelineParallel._organize and the static PAR05 balance pass
    (analysis.partitioning.pipeline_balance) — the analyzer's stage
    assignment is only a valid prediction while both feed
    partition_stages identical inputs. None when the config carries no
    per-layer types (un-built builder)."""
    def size_of(it):
        try:
            return it.size
        except Exception:
            return None

    sizes = [size_of(it)
             for it in getattr(conf, "layerInputTypes", [])] + [None]
    if len(sizes) != len(conf.layers) + 1:
        return None
    return sizes


def partition_stages(layers, params, n_stages, input_sizes=None):
    """Split a layer list into (prologue_idx, body_idx, epilogue_idx) where
    body is the longest run of identical-signature layers, trimmed to a
    multiple of n_stages. When `input_sizes` (per-layer input feature size,
    index len(layers) = network output) is given, run members must also
    preserve the boundary size (in == out) so single-layer runs like a
    shape-changing entry Dense stay in the prologue. Raises if no run is
    long enough."""
    sigs = [_layer_signature(l, p) for l, p in zip(layers, params)]
    if input_sizes is not None:
        # mark shape-changing layers with a unique signature so they can't
        # join (or form) a body run
        for i in range(len(sigs)):
            nxt = input_sizes[i + 1] if i + 1 < len(input_sizes) else None
            if input_sizes[i] is None or nxt is None or input_sizes[i] != nxt:
                sigs[i] = ("__boundary_changing__", i)
    best_start, best_len = 0, 0
    i = 0
    while i < len(sigs):
        j = i
        while j < len(sigs) and sigs[j] == sigs[i]:
            j += 1
        if j - i > best_len and not (isinstance(sigs[i][0], str)
                                     and sigs[i][0] == "__boundary_changing__"):
            best_start, best_len = i, j - i
        i = j
    if best_len < n_stages:
        raise ValueError(
            f"Pipeline needs >= {n_stages} structurally identical "
            f"consecutive layers; longest homogeneous run is {best_len}. "
            "Pipeline parallelism targets repeated-block architectures "
            "(transformer/MLP stacks).")
    use = (best_len // n_stages) * n_stages
    # keep the TRAILING `use` layers of the run in the body so the
    # shape-changing first instance (e.g. Dense nIn->H) stays in the prologue
    body_start = best_start + (best_len - use)
    body_end = body_start + use
    return (list(range(0, body_start)),
            list(range(body_start, body_end)),
            list(range(body_end, len(layers))))


class PipelineParallel:
    """Pipeline(+data)-parallel trainer for a MultiLayerNetwork.

    Usage:
        mesh = build_mesh({DATA_AXIS: 2, PIPE_AXIS: 4})
        pp = PipelineParallel(net, mesh=mesh, n_microbatches=8)
        pp.fit(x, y)

    The wrapped net's params/updater state are re-organized into
    (prologue, stacked-body [S, ...], epilogue) on first fit and synced
    back (unstacked) by sync_to_net().
    """

    def __init__(self, net, mesh, n_microbatches=4,
                 pipe_axis=_mesh.PIPE_AXIS, batch_axis=_mesh.DATA_AXIS):
        if pipe_axis not in mesh.shape:
            raise ValueError(f"mesh has no '{pipe_axis}' axis")
        self.net = net
        self.mesh = mesh
        self.pipe_axis = pipe_axis
        self.batch_axis = batch_axis if batch_axis in mesh.shape else None
        self.S = mesh.shape[pipe_axis]
        self.M = int(n_microbatches)
        if self.M < 1:
            raise ValueError("n_microbatches must be >= 1")
        self._jit = None
        self._org = None  # (pro_idx, body_idx, epi_idx, k_per_stage)

    # ------------------------------------------------------------------
    def _organize(self):
        """Partition the net's layers and build the sharded param groups."""
        n = self.net
        n._require_init()
        pro_i, body_i, epi_i = partition_stages(
            n.layers, n._params, self.S,
            input_sizes=stage_input_sizes(n.conf))
        k = len(body_i) // self.S
        # stateless check — EVERYWHERE, not just the body: the pipelined
        # step discards forward-state updates (BN running stats would stay
        # frozen at init), so fail loudly instead of silently mis-training
        for i in range(len(n.layers)):
            st = n._states[i]
            if st and any(kk not in ("h", "c") for kk in st):
                raise ValueError(
                    f"Layer {i} ({type(n.layers[i]).__name__}) carries "
                    "persistent state (e.g. BN running stats); "
                    "PipelineParallel supports stateless layers only — "
                    "train BN-bearing nets with dp/tp instead")
        self._org = (pro_i, body_i, epi_i, k)

        repl = NamedSharding(self.mesh, P())
        pick = lambda idxs: [n._params[i] for i in idxs]
        pro = jax.device_put(pick(pro_i), repl)
        epi = jax.device_put(pick(epi_i), repl)
        # stack stage s's k layer-param-dicts leaf-wise over a leading axis
        stages = [pick(body_i[s * k:(s + 1) * k]) for s in range(self.S)]
        body = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *stages)
        body = jax.device_put(body, NamedSharding(
            self.mesh, P(self.pipe_axis)))

        pro_u = jax.device_put([n._upd_states[i] for i in pro_i], repl)
        epi_u = jax.device_put([n._upd_states[i] for i in epi_i], repl)
        ust = [[n._upd_states[i] for i in body_i[s * k:(s + 1) * k]]
               for s in range(self.S)]
        body_u = jax.tree_util.tree_map(lambda *l: jnp.stack(l), *ust)
        body_u = jax.device_put(body_u, NamedSharding(
            self.mesh, P(self.pipe_axis)))
        self._groups = (pro, body, epi)
        self._upd_groups = (pro_u, body_u, epi_u)

    # ------------------------------------------------------------------
    def _pipe_loss(self, pro, body_local, epi, x_mb, y_mb, key):
        """Per-device microbatch pipeline (runs inside shard_map).
        body_local: this stage's params, leading axis 1. Returns mean loss."""
        n = self.net
        pro_i, body_i, epi_i, k = self._org
        S, M = self.S, self.M
        s_idx = lax.axis_index(self.pipe_axis)
        body_p = jax.tree_util.tree_map(lambda a: a[0], body_local)
        tmpl = [n.layers[i] for i in body_i[:k]]  # stage layer templates

        def prologue(x, kb):
            h = n._entry(x)
            for j, i in enumerate(pro_i):
                lk = None if kb is None else jax.random.fold_in(kb, i)
                h, _ = n.layers[i].forward(n._cast_params(pro[j]), n._states[i],
                                           h, True, lk, None)
            return h

        def stage(p_list, h, kb):
            for j, layer in enumerate(tmpl):
                lk = None if kb is None else jax.random.fold_in(kb, 1000 + j)
                h, _ = layer.forward(n._cast_params(p_list[j]), {}, h,
                                     True, lk, None)
            return h

        def epilogue_loss(h, y, kb):
            from deeplearning4j_tpu.nn.conf import layers as L

            for j, i in enumerate(epi_i[:-1]):
                lk = None if kb is None else jax.random.fold_in(kb, i)
                h, _ = n.layers[i].forward(n._cast_params(epi[j]),
                                           n._states[i], h, True, lk, None)
            last = n.layers[epi_i[-1]]
            lp = n._cast_params(epi[len(epi_i) - 1])
            lk = None if kb is None else jax.random.fold_in(kb, epi_i[-1])
            h = last._dropout_input(h, True, lk)
            pre = last.preoutput(lp, h)
            return n._loss_from_preact(*n._tail_cast(pre, y), None)

        # boundary buffer shape/dtype from a traced prologue+stage probe
        x0 = jax.tree_util.tree_map(lambda a: a[0], x_mb)
        bshape = jax.eval_shape(lambda x: prologue(x, None), x0)
        sshape = jax.eval_shape(
            lambda h: stage(body_p, h, None),
            jax.ShapeDtypeStruct(bshape.shape, bshape.dtype))
        if sshape.shape != bshape.shape:
            raise ValueError(
                f"Pipeline stages must preserve the boundary activation "
                f"shape; stage maps {bshape.shape} -> {sshape.shape}")

        def tick(carry, t):
            buf, loss_acc = carry
            kb = jax.random.fold_in(key, t)
            xt = lax.dynamic_index_in_dim(x_mb, jnp.clip(t, 0, M - 1), 0,
                                          keepdims=False)
            entry = prologue(xt, kb)
            h = jnp.where(s_idx == 0, entry, buf)
            h = stage(body_p, h, kb)
            out_t = t - (S - 1)
            yt = lax.dynamic_index_in_dim(y_mb, jnp.clip(out_t, 0, M - 1), 0,
                                          keepdims=False)
            l = epilogue_loss(h, yt, kb)
            valid = (s_idx == S - 1) & (out_t >= 0)
            loss_acc = loss_acc + jnp.where(valid, l, 0.0)
            nxt = lax.ppermute(h, self.pipe_axis,
                               [(i, i + 1) for i in range(S - 1)])
            return (nxt.astype(buf.dtype), loss_acc), None

        buf0 = jnp.zeros(bshape.shape, bshape.dtype)
        (_, loss_acc), _ = lax.scan(tick, (buf0, jnp.float32(0.0)),
                                    jnp.arange(M + S - 1))
        total = lax.psum(loss_acc, self.pipe_axis) / M  # only last stage adds
        if self.batch_axis:
            total = lax.pmean(total, self.batch_axis)
        return total

    def _build_jit(self):
        n = self.net
        pro_i, body_i, epi_i, k = self._org
        S, M = self.S, self.M
        mesh = self.mesh
        spec_pipe = P(self.pipe_axis)
        spec_b = P(None, self.batch_axis) if self.batch_axis else P()

        def step(pro, body, epi, pro_u, body_u, epi_u, it, x_mb, y_mb, key):
            def loss_fn(pro, body, epi):
                from jax import shard_map

                return shard_map(
                    self._pipe_loss, mesh=mesh,
                    in_specs=(P(), spec_pipe, P(), spec_b, spec_b, P()),
                    out_specs=P(), check_vma=False,
                )(pro, body, epi, x_mb, y_mb, key)

            loss, (g_pro, g_body, g_epi) = jax.value_and_grad(
                loss_fn, argnums=(0, 1, 2))(pro, body, epi)

            from deeplearning4j_tpu.nn.conf.constraint import apply_constraints

            def upd_group(idxs, plist, ulist, glist):
                newp, newu = [], []
                for j, i in enumerate(idxs):
                    if not plist[j]:
                        newp.append(plist[j])
                        newu.append(ulist[j])
                        continue
                    u, us = n._updaters[i].apply(glist[j], ulist[j], it,
                                                 params=plist[j])
                    np_j = jax.tree_util.tree_map(
                        lambda p, d: (p - d).astype(p.dtype), plist[j], u)
                    cs = getattr(n.layers[i], "constraints", None)
                    if cs:
                        np_j = apply_constraints(cs, np_j)
                    newp.append(np_j)
                    newu.append(us)
                return newp, newu

            pro, pro_u = upd_group(pro_i, pro, pro_u, g_pro)
            epi, epi_u = upd_group(epi_i, epi, epi_u, g_epi)
            # body: one updater (stage-uniform) over the stacked leaves
            u, body_u = n._updaters[body_i[0]].apply(g_body, body_u, it,
                                                     params=body)
            body = jax.tree_util.tree_map(
                lambda p, d: (p - d).astype(p.dtype), body, u)
            b_cs = getattr(n.layers[body_i[0]], "constraints", None)
            if b_cs:
                # vmap over the leading stage axis so constraint norms see
                # the same axes as the unstacked layer params
                body = jax.vmap(
                    lambda stage: [apply_constraints(b_cs, d) for d in stage]
                )(body)
            return pro, body, epi, pro_u, body_u, epi_u, loss

        self._jit = jax.jit(step, donate_argnums=(0, 1, 2, 3, 4, 5))

    # ------------------------------------------------------------------
    def fit(self, data, labels=None, epochs=None):
        from deeplearning4j_tpu.data.dataset import DataSet

        n = self.net
        if self._org is None:
            self._organize()
            self._build_jit()
        if labels is not None:
            self._fit_batch(DataSet(data, labels))
        elif isinstance(data, DataSet):
            self._fit_batch(data)
        else:
            for _ in range(epochs or 1):
                data.reset()
                while data.hasNext():
                    self._fit_batch(data.next())
                n._epoch += 1
        self.sync_to_net()
        return self

    def _fit_batch(self, ds):
        n = self.net
        x = _unwrap(ds.getFeatures())
        y = _unwrap(ds.getLabels())
        B = x.shape[0]
        dp = self.mesh.shape[self.batch_axis] if self.batch_axis else 1
        if B % (self.M * dp) != 0:
            raise ValueError(
                f"Batch {B} not divisible by n_microbatches*dp = "
                f"{self.M}*{dp}")
        mb = B // self.M
        x_mb = x.reshape(self.M, mb, *x.shape[1:])
        y_mb = y.reshape(self.M, mb, *y.shape[1:])
        if self.batch_axis:
            # divisibility-checked placement over the data axis (dim 1:
            # microbatch index leads) — rejects rather than pads
            from deeplearning4j_tpu.parallel.sharding import shard_batch

            x_mb = shard_batch(x_mb, self.mesh, self.batch_axis, dim=1)
            y_mb = shard_batch(y_mb, self.mesh, self.batch_axis, dim=1)
        else:
            x_mb = jax.device_put(x_mb, NamedSharding(self.mesh, P()))
            y_mb = jax.device_put(y_mb, NamedSharding(self.mesh, P()))
        key = jax.random.fold_in(jax.random.key(n.conf.seed ^ 0x9199),
                                 n._iteration)
        pro, body, epi = self._groups
        pro_u, body_u, epi_u = self._upd_groups
        pro, body, epi, pro_u, body_u, epi_u, loss = self._jit(
            pro, body, epi, pro_u, body_u, epi_u,
            jnp.asarray(n._iteration, jnp.int32), x_mb, y_mb, key)
        self._groups = (pro, body, epi)
        self._upd_groups = (pro_u, body_u, epi_u)
        n._score = float(loss)
        n._iteration += 1
        for lst in n._listeners:
            lst.iterationDone(n, n._iteration, n._epoch)

    def sync_to_net(self):
        """Write the (unstacked) pipeline params back onto the wrapped net."""
        if self._org is None:
            return
        n = self.net
        pro_i, body_i, epi_i, k = self._org
        pro, body, epi = self._groups
        pro_u, body_u, epi_u = self._upd_groups
        for j, i in enumerate(pro_i):
            n._params[i] = pro[j]
            n._upd_states[i] = pro_u[j]
        for j, i in enumerate(epi_i):
            n._params[i] = epi[j]
            n._upd_states[i] = epi_u[j]
        for s in range(self.S):
            sp = jax.tree_util.tree_map(lambda a: a[s], body)
            su = jax.tree_util.tree_map(lambda a: a[s], body_u)
            for j, i in enumerate(body_i[s * k:(s + 1) * k]):
                n._params[i] = sp[j]
                n._upd_states[i] = su[j]
