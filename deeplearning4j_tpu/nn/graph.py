"""ComputationGraph — the DAG network executor.

Reference: org.deeplearning4j.nn.graph.ComputationGraph. Same TPU design
as MultiLayerNetwork (see nn/multilayer.py): the full train step over the
DAG — all vertices, losses on every output layer, backward, updaters —
compiles to one donated-buffer XLA computation. Supports multiple inputs
and outputs via MultiDataSet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ndarray import INDArray
from deeplearning4j_tpu.nn import losses as _losses
from deeplearning4j_tpu.nn import updaters as _upd
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.multilayer import (_grad_normalize, _unwrap,
                                               cast_params,
                                               default_param_update,
                                               strip_carries,
                                               checkpointed_forward,
                                               fit_iterator_epoch,
                                               traced_train_step)
from deeplearning4j_tpu.runtime import telemetry


class ComputationGraph:
    def __init__(self, conf):
        self.conf = conf
        self._layer_names = [n for n in conf.topoOrder
                             if conf.nodes[n].kind == "layer"]
        # stable per-layer rng stream ids (python hash() is process-salted)
        self._layer_idx = {n: i for i, n in enumerate(self._layer_names)}
        self._params = None    # {layer_name: dict}
        self._states = None
        self._upd_states = None
        self._updaters = None
        self._iteration = 0
        self._epoch = 0
        self._listeners = []
        self._compute_dtype = conf.dataType.np_dtype
        self._param_dtype = jnp.float64 if self._compute_dtype == jnp.float64 else jnp.float32
        algo = getattr(conf, "optimizationAlgo",
                       "STOCHASTIC_GRADIENT_DESCENT")
        if algo != "STOCHASTIC_GRADIENT_DESCENT":
            from deeplearning4j_tpu.nn import solvers as _solvers

            self._solver = _solvers.build_solver(
                algo, getattr(conf, "maxNumLineSearchIterations", 20))
            if getattr(conf, "gradientNormalization", None) is not None:
                import warnings

                warnings.warn(
                    f"gradientNormalization={conf.gradientNormalization} is "
                    f"IGNORED under optimizationAlgo={algo}: the line search "
                    "needs the true gradient for its Wolfe/Armijo "
                    "conditions (ADVICE r4). Use SGD-family updaters for "
                    "gradient clipping.", stacklevel=2)
        else:
            self._solver = None
        from deeplearning4j_tpu.runtime import aot

        self._jit_train = self._make_jit_train()
        self._jit_forward = aot.cached_jit(self._forward_infer, owner=self,
                                           entry="forward_infer")
        self._jit_loss = aot.cached_jit(self._loss_only, owner=self,
                                        entry="loss_only")

    def _make_jit_train(self, step_fn=None):
        """Canonical train-step jit; see MultiLayerNetwork._make_jit_train
        (RetraceSentinel.install re-jits a wrapped step through this;
        the unwrapped form routes through the AOT executable cache)."""
        # optax solver states alias the param buffers (see
        # MultiLayerNetwork)
        donate = (0, 1, 2) if self._solver is None else (2,)
        if step_fn is not None:
            return jax.jit(step_fn, static_argnames=("use_carries",),
                           donate_argnums=donate)
        from deeplearning4j_tpu.runtime import aot

        return aot.cached_jit(
            self._train_step, owner=self, entry="train_step",
            static_argnames=("use_carries",), donate_argnums=donate)

    # ------------------------------------------------------------------
    def init(self, validate=False, mesh=None, hbm_gb=None, plan=None,
             batchSize=32):
        """Initialize parameters. validate=True runs the static
        shape/dtype analyzer first; a `mesh` extends it with the
        partition-plan passes, with `batchSize` the global batch you
        will fit() with (see MultiLayerNetwork.init)."""
        if validate or mesh is not None:
            from deeplearning4j_tpu.analysis import validate_or_raise

            validate_or_raise(self.conf, batchSize=batchSize, mesh=mesh,
                              hbm_gb=hbm_gb, plan=plan)
        key = jax.random.key(self.conf.seed)
        params, states, upds, upd_states = {}, {}, {}, {}
        with telemetry.phase("weights_init"):
            for i, name in enumerate(self._layer_names):
                node = self.conf.nodes[name]
                k = jax.random.fold_in(key, i)
                p, s = node.payload.initialize(k, node.layerInputType, self._param_dtype)
                params[name] = p
                states[name] = s
                u = _upd.resolve(node.payload.updater) if node.payload.updater is not None else _upd.Sgd()
                upds[name] = u
                upd_states[name] = u.init(p) if p else ()
        self._params, self._states = params, states
        self._updaters, self._upd_states = upds, upd_states
        if self._solver is not None:
            self._upd_states = self._solver.init(params)
        return self

    def initFrom(self, params, states, upd_states=None):
        """Initialize from existing state (ModelSerializer restore path) —
        skips the random weight init that init() would immediately discard."""
        self._params, self._states = params, states
        self._updaters = {}
        for name in self._layer_names:
            payload = self.conf.nodes[name].payload
            self._updaters[name] = (_upd.resolve(payload.updater)
                                    if payload.updater is not None else _upd.Sgd())
        if self._solver is not None:
            # solver memory is batch-local and not serialized — fresh
            # state on restore (see MultiLayerNetwork.initFrom)
            self._upd_states = self._solver.init(params)
        elif upd_states is not None:
            self._upd_states = upd_states
        else:
            self._upd_states = {
                name: (self._updaters[name].init(params[name])
                       if params[name] else ())
                for name in self._layer_names}
        return self

    def _require_init(self):
        if self._params is None:
            raise RuntimeError("Call net.init() before fit/output/score")

    def _example_shapes(self, batchSize, featuresShape=None,
                        labelsShape=None):
        """(featuresShape, labelsShape) for a precompile example batch —
        the ONE derivation shared by ComputationGraph.precompile and
        ParallelWrapper.precompile (single-input/single-output graphs;
        vertex outputs and composite-loss heads need explicit
        labelsShape)."""
        from deeplearning4j_tpu.nn.multilayer import (
            shape_for_input_type, shape_for_output_type)

        if len(self.conf.networkInputs) != 1 \
                or len(self.conf.networkOutputs) != 1:
            raise ValueError(
                "precompile supports single-input/single-output "
                "ComputationGraphs; warm a multi-IO graph by fitting "
                "one real (or zero) MultiDataSet")
        if featuresShape is None:
            featuresShape = shape_for_input_type(
                self.conf.inputTypes.get(self.conf.networkInputs[0]),
                batchSize)
        if labelsShape is None:
            out_node = self.conf.nodes[self.conf.networkOutputs[0]]
            if out_node.kind != "layer" \
                    or hasattr(out_node.payload, "computeLoss"):
                raise ValueError(
                    "precompile needs labelsShape=... for this output "
                    "(vertex output or composite-loss head)")
            ot = out_node.payload.getOutputType(out_node.layerInputType)
            labelsShape = shape_for_output_type(
                ot, batchSize, api_nhwc=self._api_nhwc,
                t_fallback=featuresShape[-1]
                if len(featuresShape) == 3 else None)
        return featuresShape, labelsShape

    def precompile(self, batchSize=32, featuresShape=None,
                   labelsShape=None, entries=("train", "infer"),
                   stepsPerSync=None, cache=None, autotune=False):
        """AOT warm-start for single-input/single-output graphs: see
        MultiLayerNetwork.precompile. Multi-IO graphs have no canonical
        example batch — warm those by running one real batch."""
        from deeplearning4j_tpu.nn.multilayer import precompile_network

        featuresShape, labelsShape = self._example_shapes(
            batchSize, featuresShape, labelsShape)
        in_name = self.conf.networkInputs[0]
        return precompile_network(
            self, batchSize=batchSize, featuresShape=featuresShape,
            labelsShape=labelsShape, entries=entries,
            stepsPerSync=stepsPerSync, cache=cache,
            wrap_args=lambda x, y: ({in_name: x}, [y]),
            autotune=autotune)

    # ------------------------------------------------------------------
    def _cast_params(self, p):
        return cast_params(p, self._compute_dtype, self._param_dtype)

    @property
    def _api_nhwc(self):
        """True when every declared CNN input is NHWC-format: then ALL 4-d
        arrays at the API boundary (features, labels, outputs, feedForward
        activations) are NHWC and no layout transposes happen anywhere
        (reference: CNN2DFormat.NHWC)."""
        its = [it for it in self.conf.inputTypes.values()
               if it is not None and it.kind == InputType.CNN]
        return bool(its) and all(
            getattr(it, "format", "NCHW") == "NHWC" for it in its)

    def _entry(self, name, x, already_internal=False):
        if already_internal:
            # staged on host in internal layout + compute dtype
            # (fitDataSet canonical staging): no transpose/convert HLO
            return x.astype(self._compute_dtype)
        # cast BEFORE the relayout so the transpose moves compute-dtype
        # bytes, not fp32 (see MultiLayerNetwork._entry)
        x = x.astype(self._compute_dtype)
        it = self.conf.inputTypes.get(name)
        if it is not None and it.kind == InputType.CNN and x.ndim == 4:
            if getattr(it, "format", "NCHW") != "NHWC":
                x = jnp.transpose(x, (0, 2, 3, 1))
        if it is not None and it.kind == InputType.CNN_FLAT and x.ndim == 2:
            x = x.reshape(x.shape[0], it.channels, it.height, it.width)
            x = jnp.transpose(x, (0, 2, 3, 1))
        return x

    def _canon_host(self, name, x, stacked=False):
        """HOST-side equivalent of _entry for one input (see
        MultiLayerNetwork._canon_host): numpy layout + dtype
        canonicalisation of a staged [k, B, ...] stack."""
        from deeplearning4j_tpu.nn.multilayer import host_to_nhwc

        x = np.asarray(x)
        it = self.conf.inputTypes.get(name)
        o = 1 if stacked else 0
        if it is not None and it.kind == InputType.CNN \
                and x.ndim == 4 + o:
            if getattr(it, "format", "NCHW") != "NHWC":
                x = host_to_nhwc(x, stacked)
        elif it is not None and it.kind == InputType.CNN_FLAT \
                and x.ndim == 2 + o:
            x = x.reshape(*x.shape[:o + 1], it.channels, it.height,
                          it.width)
            x = host_to_nhwc(x, stacked)
        return np.ascontiguousarray(
            x.astype(np.dtype(self._compute_dtype), copy=False))

    def _run_graph(self, params, states, inputs, train, key, fmasks,
                   canonical=False):
        """inputs: dict name->array. Returns (activations dict, preacts of
        output layers, new states). Masks propagate node-to-node: a node's
        mask is its first input's mask (reference:
        ComputationGraph.feedForwardMaskArrays)."""
        acts = {}
        masks = {}
        new_states = {}
        preacts = {}
        B = None
        for idx, name in enumerate(self.conf.networkInputs):
            x = self._entry(name, inputs[name], already_internal=canonical)
            B = x.shape[0] if B is None else B
            acts[name] = x
            masks[name] = None if fmasks is None else fmasks.get(name)
        for name in self.conf.topoOrder:
            node = self.conf.nodes[name]
            if node.kind == "input":
                continue
            if node.kind == "vertex":
                pp = getattr(node.payload, "pp", None)
                if pp is not None and hasattr(pp, "batch"):
                    pp.batch = B  # FeedForwardToRnn needs B to un-flatten
                vert = node.payload
                ins = [acts[i] for i in node.inputs]
                if getattr(vert, "maskAware", False):
                    # time-semantic vertices (reverse/last-step) must see
                    # and may rewrite the masks of their inputs
                    acts[name], masks[name] = vert.applyMasked(
                        ins, [masks.get(i) for i in node.inputs])
                else:
                    acts[name] = vert.apply(ins)
                    masks[name] = masks.get(node.inputs[0])
                continue
            layer = node.payload
            out_mask = masks.get(node.inputs[0])
            if getattr(layer, "multiInput", False):
                h = [acts[i] for i in node.inputs]
                # the KEYS' mask governs score masking (2nd input if distinct,
                # else the single self-attention input); the node's OUTPUT is
                # aligned to the query axis, so out_mask stays the first
                # input's mask
                fmask = masks.get(node.inputs[1 if len(node.inputs) > 1 else 0])
            else:
                h = acts[node.inputs[0]]
                fmask = out_mask
            if node.preprocessor is not None:
                if hasattr(node.preprocessor, "batch"):
                    node.preprocessor.batch = B
                h = node.preprocessor.preProcess(h)
            # frozen layers run in inference mode (no dropout, BN keeps its
            # running stats) — mirrors MultiLayerNetwork._run_layers and the
            # reference's FrozenLayer/FrozenVertex
            l_train = train and (not getattr(layer, "frozen", False)
                                 or getattr(layer, "frozenKeepTraining",
                                            False))
            lk = None if (key is None or not l_train) else \
                jax.random.fold_in(key, self._layer_idx[name])
            p = self._cast_params(params[name])
            wn = getattr(layer, "weightNoise", None)
            if wn is not None and lk is not None:
                # train-time weight perturbation (reference: IWeightNoise)
                p = wn.apply(p, jax.random.fold_in(lk, 0x5EED))
            if name in self.conf.networkOutputs and isinstance(
                    layer, (L.BaseOutputLayer, L.LossLayer)):
                h = layer._dropout_input(h, l_train, lk)
                pre = layer.preoutput(p, h)
                preacts[name] = pre
                from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
                out = MultiLayerNetwork._out_act(layer, pre)
                if out.ndim == 4 and not self._api_nhwc:
                    # NHWC internal -> NCHW at the API boundary
                    out = jnp.transpose(out, (0, 3, 1, 2))
                acts[name] = out
                new_states[name] = states[name]
                continue
            if train and not getattr(layer, "multiInput", False) and \
                    getattr(self.conf, "activationCheckpointing", False):
                # rematerialize in backward (jax.checkpoint); multi-input
                # layers (attention) keep the plain path — their inputs
                # list is heterogeneous and they are few per graph
                h, s = checkpointed_forward(layer, l_train)(
                    p, states[name], h, lk, fmask)
            else:
                h, s = layer.forward(p, states[name], h, l_train, lk, fmask)
            if getattr(self.conf, "checkpointPolicy", None) == \
                    "save_conv_outputs" and isinstance(
                        layer, (L.ConvolutionLayer, L.DenseLayer)):
                # name MXU outputs as the ONLY residuals the train step's
                # jax.checkpoint policy saves (_ckpt_loss_fn); everything
                # else (BN, activations, adds, pools) is recomputed from
                # them in the backward — outside that wrapper the name
                # primitive is an identity
                from jax.ad_checkpoint import checkpoint_name
                h = checkpoint_name(h, "dl4j_mxu_out")
            acts[name] = h
            masks[name] = out_mask
            new_states[name] = s
        return acts, preacts, new_states

    def _loss(self, preacts, labels, lmasks):
        total = 0.0
        for i, name in enumerate(self.conf.networkOutputs):
            layer = self.conf.nodes[name].payload
            pre = preacts[name]
            y = labels[i]
            lmask = None if lmasks is None else lmasks[i]
            # round-6 loss-tail policy: activation-scale loss math in
            # the compute dtype, fp32 only inside the losses.py reduce
            # accumulators (see nn/losses.tail_dtype); composite heads
            # below keep the wide tail — their multi-term math is not
            # covered by the fp32-accumulator policy
            ldt = _losses.tail_dtype(pre.dtype)
            pre = pre.astype(ldt)
            if hasattr(layer, "computeLoss"):
                # composite-loss heads (e.g. objdetect.Yolo2OutputLayer) own
                # their full loss computation and expect the reference's
                # NCHW label layout — restore it for NHWC-format networks.
                # Their labels skip the ldt downcast: the head runs wide,
                # and rounding fp32 box coordinates to bf16 first would
                # lose label precision for nothing.
                wdt = jnp.promote_types(pre.dtype, jnp.float32)
                pre, y = pre.astype(wdt), y.astype(wdt)
                if self._api_nhwc and y.ndim == 4:
                    y = jnp.transpose(y, (0, 3, 1, 2))
                total = total + layer.computeLoss(pre, y, lmask)
                continue
            y = y.astype(ldt)
            if pre.ndim == 3:  # NCW preact: loss over [B,T,O]
                pre = jnp.transpose(pre, (0, 2, 1))
                y = jnp.transpose(y, (0, 2, 1))
            elif pre.ndim == 4:  # NHWC preact; labels are NCHW from the
                # API unless the net declares NHWC
                if not self._api_nhwc:
                    y = jnp.transpose(y, (0, 2, 3, 1))
            total = total + _losses.compute(layer.lossFunction, y, pre,
                                            layer.activation, lmask)
        return total

    def _regularization(self, params):
        reg = 0.0
        for name in self._layer_names:
            p = params[name]
            if p and not getattr(self.conf.nodes[name].payload, "frozen", False):
                reg = reg + self.conf.nodes[name].payload.regularization(p)
        return reg

    def _loss_fn(self, params, states, inputs, labels, key, fmasks, lmasks,
                 use_carries=False, canonical=False):
        # frozen layers: structurally zero grads so XLA eliminates their
        # backward pass (see MultiLayerNetwork._loss_fn)
        params = {n: jax.tree_util.tree_map(jax.lax.stop_gradient, p)
                  if getattr(self.conf.nodes[n].payload, "frozen", False) else p
                  for n, p in params.items()}
        run_states = states if use_carries else self._strip_carries(states)
        _, preacts, new_states = self._run_graph(
            params, run_states, inputs, True, key, fmasks,
            canonical=canonical)
        loss = self._loss(preacts, labels, lmasks) + self._regularization(params)
        return loss, new_states

    def _train_step(self, params, upd_states, states, iteration, inputs, labels,
                    key, fmasks, lmasks, use_carries=False,
                    grad_transform=None, loss_transform=None,
                    state_transform=None, canonical_inputs=False):
        """The *_transform hooks mirror MultiLayerNetwork._train_step:
        distributed wrappers (parallel.trainer) splice in cross-shard
        allreduce/pmean without duplicating the updater loop.
        canonical_inputs=True: inputs staged host-side in the internal
        layout + compute dtype (fitDataSet canonical staging)."""
        (loss, new_states), grads = jax.value_and_grad(
            self._ckpt_loss_fn(use_carries, canonical_inputs),
            has_aux=True)(
            params, states, inputs, labels, key, fmasks, lmasks)
        if grad_transform is not None:
            grads = grad_transform(grads)
        if loss_transform is not None:
            loss = loss_transform(loss)
        if state_transform is not None:
            new_states = state_transform(new_states)
        if self._solver is not None:
            from deeplearning4j_tpu.nn import solvers as _solvers

            def value_fn(ps):
                return self._ckpt_loss_fn(use_carries, canonical_inputs)(
                    ps, states, inputs, labels, key, fmasks, lmasks)[0]

            new_params, new_upd = _solvers.solver_update(
                self._solver, grads, upd_states, params, loss, value_fn)
            for name in self._layer_names:
                payload = self.conf.nodes[name].payload
                if getattr(payload, "frozen", False):
                    new_params[name] = params[name]
                cs = getattr(payload, "constraints", None)
                if cs and new_params[name]:
                    from deeplearning4j_tpu.nn.conf.constraint import \
                        apply_constraints
                    new_params[name] = apply_constraints(
                        cs, new_params[name])
            return new_params, new_upd, new_states, loss
        glist = _grad_normalize([grads[n] for n in self._layer_names],
                                self.conf.gradientNormalization,
                                self.conf.gradientNormalizationThreshold)
        # the weight-update hook (see MultiLayerNetwork._train_step):
        # ZeroShardedUpdate runs the optimizer on 1/dp shards here
        update_impl = getattr(self, "_update_impl", None) \
            or default_param_update
        new_params, new_upd = dict(params), dict(upd_states)
        for name, g in zip(self._layer_names, glist):
            if not params[name] or getattr(self.conf.nodes[name].payload,
                                           "frozen", False):
                continue
            np_n, us = update_impl(self._updaters[name], g,
                                   upd_states[name], iteration,
                                   params[name])
            cs = getattr(self.conf.nodes[name].payload, "constraints", None)
            if cs:
                from deeplearning4j_tpu.nn.conf.constraint import apply_constraints
                np_n = apply_constraints(cs, np_n)
            new_params[name] = np_n
            new_upd[name] = us
        return new_params, new_upd, new_states, loss

    def _ckpt_loss_fn(self, use_carries, canonical=False):
        """_loss_fn, under the conf's named-residual remat policy when
        one is set. With checkpointPolicy="save_conv_outputs" the whole
        loss is a jax.checkpoint region whose policy saves ONLY tensors
        tagged "dl4j_mxu_out" in _run_graph (conv/dense outputs, plus
        the region's own inputs, which are free); BN/activation/add/pool
        intermediates are recomputed during the backward. On
        bandwidth-bound steps that removes the write+read of every
        elementwise intermediate at the cost of re-reading the saved
        conv outputs."""
        def base(p, s, i, l, k, fm, lm):
            return self._loss_fn(p, s, i, l, k, fm, lm, use_carries,
                                 canonical)

        if getattr(self.conf, "checkpointPolicy", None) != \
                "save_conv_outputs":
            return base
        policy = jax.checkpoint_policies.save_only_these_names(
            "dl4j_mxu_out")
        return jax.checkpoint(base, policy=policy)

    def _forward_infer(self, params, states, inputs):
        acts, _, _ = self._run_graph(params, self._strip_carries(states),
                                     inputs, False, None, None)
        return [acts[n] for n in self.conf.networkOutputs]

    def _loss_only(self, params, states, inputs, labels, fmasks=None, lmasks=None):
        _, preacts, _ = self._run_graph(params, self._strip_carries(states),
                                        inputs, False, None, fmasks)
        return self._loss(preacts, labels, lmasks) + self._regularization(params)

    @staticmethod
    def _strip_carries(states):
        return strip_carries(states)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def _coerce_inputs(self, features):
        if isinstance(features, (list, tuple)):
            arrs = [_unwrap(f) for f in features]
        else:
            arrs = [_unwrap(features)]
        return {n: a for n, a in zip(self.conf.networkInputs, arrs)}

    def fit(self, data, labels=None, epochs=None):
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.data.multidataset import MultiDataSet

        self._require_init()
        if labels is not None:
            self._fit_arrays(data, labels)
            return self
        if isinstance(data, (DataSet, MultiDataSet)):
            self._fit_ds(data)
            return self
        for _ in range(epochs or 1):
            data.reset()
            for lst in self._listeners:
                getattr(lst, "onEpochStart", lambda m: None)(self)
            fit_iterator_epoch(self, data, self._step)
            for lst in self._listeners:
                getattr(lst, "onEpochEnd", lambda m: None)(self)
            self._epoch += 1
        return self

    def _fit_arrays(self, features, labels):
        inputs = self._coerce_inputs(features)
        labs = [_unwrap(l) for l in (labels if isinstance(labels, (list, tuple)) else [labels])]
        self._step(inputs, labs, None, None)

    def _extract_ds(self, ds):
        """(inputs dict, labels list, fmasks, lmasks) from a DataSet or
        MultiDataSet — shared by fit() and fitSteps()."""
        from deeplearning4j_tpu.data.multidataset import MultiDataSet

        if isinstance(ds, MultiDataSet):
            inputs = {n: _unwrap(f) for n, f in zip(self.conf.networkInputs, ds.getFeatures())}
            labs = [_unwrap(l) for l in ds.getLabels()]
            fmasks = None
            fm = ds.getFeaturesMaskArrays()
            if fm is not None:
                fmasks = {n: _unwrap(m) for n, m in zip(self.conf.networkInputs, fm)}
            lm = ds.getLabelsMaskArrays()
            lmasks = None if lm is None else [_unwrap(m) for m in lm]
        else:
            inputs = {self.conf.networkInputs[0]: _unwrap(ds.getFeatures())}
            labs = [_unwrap(ds.getLabels())]
            fm = ds.getFeaturesMaskArray()
            fmasks = None if fm is None else {self.conf.networkInputs[0]: _unwrap(fm)}
            lm = ds.getLabelsMaskArray()
            lmasks = None if lm is None else [_unwrap(lm)]
        return inputs, labs, fmasks, lmasks

    def _fit_ds(self, ds):
        self._step(*self._extract_ds(ds))

    def _step(self, inputs, labels, fmasks, lmasks):
        if self.conf.backpropType == "tbptt" and any(
                v.ndim == 3 for v in inputs.values()):
            self._fit_tbptt(inputs, labels, fmasks, lmasks)
            return
        traced_train_step(self, inputs, labels, fmasks, lmasks)

    def fitSteps(self, data, labels=None, numSteps=1):
        """TPU-native k-step fit for graphs — numSteps optimizer steps
        on one batch in a single on-device lax.fori_loop, one host sync.
        Same trajectory/RNG/iteration semantics as numSteps fit() calls;
        see MultiLayerNetwork.fitSteps for the rationale. tBPTT graphs
        run their full window sweep per step (seq len must divide
        tbpttFwdLength; mixed static+sequence inputs slice only the
        [B,C,T] entries, like fit())."""
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.data.multidataset import MultiDataSet

        self._require_init()
        if labels is not None:
            inputs = self._coerce_inputs(data)
            labs = [_unwrap(l) for l in
                    (labels if isinstance(labels, (list, tuple))
                     else [labels])]
            fmasks = lmasks = None
        elif isinstance(data, (DataSet, MultiDataSet)):
            inputs, labs, fmasks, lmasks = self._extract_ds(data)
        else:
            raise ValueError("fitSteps takes (x, y) arrays or one "
                             "DataSet/MultiDataSet batch, not an iterator")
        tbptt = self.conf.backpropType == "tbptt" and any(
            v.ndim == 3 for v in inputs.values())
        if tbptt:
            T = max(v.shape[2] for v in inputs.values() if v.ndim == 3)
            L = self.conf.tbpttFwdLength
            if T % L != 0:
                raise ValueError(
                    f"fitSteps tBPTT needs seq len divisible by "
                    f"tbpttFwdLength (got T={T}, L={L}); use fit() for "
                    "ragged tails")
            n_win = T // L
        else:
            n_win = 1
        cache = getattr(self, "_fit_steps_cache", None)
        if cache is None:
            cache = self._fit_steps_cache = {}
        jloop = cache.get((numSteps, n_win))
        if jloop is None:
            seed_key = jax.random.key(self.conf.seed ^ 0x5EED)

            def loop(params, upd, states, it0, inputs, labels, fmasks,
                     lmasks):
                L = getattr(self.conf, "tbpttFwdLength", 1)

                def window(carry, step_i, win_i, use_carries):
                    p, u, s, _ = carry
                    it = it0 + step_i * n_win + win_i
                    key = jax.random.fold_in(seed_key, it)
                    if n_win == 1:
                        ic, lc, fc, mc = inputs, labels, fmasks, lmasks
                    else:
                        sl3 = lambda a: a if a is None or a.ndim != 3 \
                            else jax.lax.dynamic_slice_in_dim(
                                a, win_i * L, L, 2)
                        slm = lambda m: None if m is None else \
                            jax.lax.dynamic_slice_in_dim(m, win_i * L, L, 1)
                        ic = {n: sl3(v) for n, v in inputs.items()}
                        lc = [sl3(l) for l in labels]
                        fc = None if fmasks is None else \
                            {n: slm(m) for n, m in fmasks.items()}
                        mc = None if lmasks is None else \
                            [slm(m) for m in lmasks]
                    p, u, s, loss = self._train_step(
                        p, u, s, it, ic, lc, key, fc, mc,
                        use_carries=use_carries)
                    return (p, u, s, loss.astype(jnp.float32))

                def body(i, carry):
                    carry = window(carry, i, 0, False)
                    if n_win > 1:
                        carry = jax.lax.fori_loop(
                            1, n_win,
                            lambda w, c: window(c, i, w, True), carry)
                    # structure-stable carry: strip the h/c entries the
                    # step adds (see MultiLayerNetwork.fitSteps)
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
            jnp.asarray(self._iteration, jnp.int32), inputs, labs,
            fmasks, lmasks)
        self._score = float(loss)
        self._iteration += numSteps * n_win
        for lst in self._listeners:
            lst.iterationDone(self, self._iteration, self._epoch)
        return self

    def _stack_batches(self, batches):
        """k DataSets/MultiDataSets -> stacked [k, ...] host arrays in
        the train step's (inputs dict, labels list, fmasks, lmasks)
        structure — fitDataSet's one-transfer staging unit."""
        from deeplearning4j_tpu.data.iterators import stack_mask_group

        ex = [self._extract_ds(ds) for ds in batches]
        inputs_l = [e[0] for e in ex]
        labs_l = [e[1] for e in ex]
        fms_l = [e[2] for e in ex]
        lms_l = [e[3] for e in ex]
        X = {n: np.stack([np.asarray(d[n]) for d in inputs_l])
             for n in self.conf.networkInputs}
        Y = [np.stack([np.asarray(ls[j]) for ls in labs_l])
             for j in range(len(labs_l[0]))]
        if all(f is None for f in fms_l):
            FM = None
        else:
            # per-input None entries (a masked sequence input alongside a
            # static one) synthesize all-ones exactly like whole-batch
            # Nones — same guard shape as the labels-mask branch below
            names = list(next(f for f in fms_l if f is not None))
            FM = {n: stack_mask_group(
                [None if f is None or f.get(n) is None
                 else np.asarray(f[n]) for f in fms_l],
                f"features-mask[{n}]") for n in names}
        if all(m is None for m in lms_l):
            LM = None
        else:
            LM = [stack_mask_group(
                [None if m is None or m[j] is None else np.asarray(m[j])
                 for m in lms_l], f"labels-mask[{j}]")
                for j in range(len(labs_l[0]))]
        return X, Y, FM, LM

    def _stack_batches_canonical(self, batches):
        """_stack_batches with every input stack canonicalised on host
        (internal layout + compute dtype — see _canon_host); pairs with
        fit_dataset_jit(canonical=True)."""
        X, Y, FM, LM = self._stack_batches(batches)
        X = {n: self._canon_host(n, x, stacked=True) for n, x in X.items()}
        return X, Y, FM, LM

    def fitDataSet(self, iterator, stepsPerSync=1, epochs=None):
        """Epoch training with one host sync and one transfer per
        `stepsPerSync` fresh batches — the ComputationGraph form of
        MultiLayerNetwork.fitDataSet (see there for the staging and
        double-buffering contract). The iterator may yield DataSets or
        MultiDataSets (multi-input/-output graphs stack every component);
        the ragged final stack runs through plain fit()."""
        from deeplearning4j_tpu.nn.multilayer import (fit_dataset_jit,
                                                      run_fit_dataset_epoch)

        self._require_init()
        k = int(stepsPerSync)
        if k < 1:
            raise ValueError(f"stepsPerSync must be >= 1, got {k}")
        if k == 1:
            it0 = self._iteration
            self.fit(iterator, epochs=epochs)
            self._fit_dataset_syncs = self._iteration - it0  # 1/batch
            return self
        if self.conf.backpropType == "tbptt":
            raise ValueError(
                "fitDataSet does not support truncated BPTT: use fit() "
                "(per-batch windows) or fitSteps()")
        # layout hygiene (round 6): host-canonical staging, same A/B
        # toggle as MultiLayerNetwork.fitDataSet
        from deeplearning4j_tpu.nn.multilayer import canon_staging_on

        canon = canon_staging_on()
        jloop = fit_dataset_jit(self, k, canonical=canon)
        stack = (self._stack_batches_canonical if canon
                 else self._stack_batches)
        self._fit_dataset_syncs = 0
        for _ in range(epochs or 1):
            iterator.reset()
            for lst in self._listeners:
                getattr(lst, "onEpochStart", lambda m: None)(self)
            self._fit_dataset_syncs += run_fit_dataset_epoch(
                self, iterator, k, stack, self._fit_ds, jloop)
            for lst in self._listeners:
                getattr(lst, "onEpochEnd", lambda m: None)(self)
            self._epoch += 1
        return self

    def _fit_tbptt(self, inputs, labels, fmasks, lmasks):
        """Truncated BPTT over the DAG: split time ([B,C,T] axis 2) into
        tbpttFwdLength windows, carrying recurrent h/c across windows
        (reference: ComputationGraph.doTruncatedBPTT). The chunk loop is
        the shared run_tbptt driver."""
        from deeplearning4j_tpu.nn.multilayer import run_tbptt

        T = max(v.shape[2] for v in inputs.values() if v.ndim == 3)

        def tseq(a, sl):
            # only sequence ([B,C,T]) arrays are time-sliced; feedforward
            # inputs/labels in a mixed graph pass through whole
            return a[:, :, sl] if (a is not None and a.ndim == 3) else a

        def tmask(m, sl):
            return None if m is None else m[:, sl]

        def jit_call(sl, key, it, use_carries):
            ic = {n: tseq(v, sl) for n, v in inputs.items()}
            lc = [tseq(l, sl) for l in labels]
            fc = None if fmasks is None else {n: tmask(m, sl)
                                              for n, m in fmasks.items()}
            mc = None if lmasks is None else [tmask(m, sl) for m in lmasks]
            self._params, self._upd_states, self._states, loss = self._jit_train(
                self._params, self._upd_states, self._states, it, ic, lc, key,
                fc, mc, use_carries=use_carries)
            return loss

        run_tbptt(self, T, self.conf.tbpttFwdLength, jit_call)

    # ----- unsupervised layerwise pretraining (VAE etc.) --------------
    def pretrain(self, iterator, epochs=1):
        """Layerwise unsupervised pretraining of every pretrainable layer
        (reference: ComputationGraph.pretrain(DataSetIterator))."""
        for name in self._layer_names:
            if getattr(self.conf.nodes[name].payload, "pretrainable", False):
                self.pretrainLayer(name, iterator, epochs)
        return self

    def pretrainLayer(self, layerName, data, epochs=1):
        """Unsupervised pretraining of one named layer against its own
        pretrain_loss, fed by the frozen forward of its ancestors
        (reference: ComputationGraph.pretrainLayer)."""
        self._require_init()
        node = self.conf.nodes[layerName]
        layer = node.payload
        if not getattr(layer, "pretrainable", False):
            raise ValueError(f"Layer '{layerName}' "
                             f"({type(layer).__name__}) is not pretrainable")
        src = node.inputs[0]
        upd = self._updaters[layerName]

        def feed(inputs):
            acts, _, _ = self._run_graph(
                self._params, self._strip_carries(self._states), inputs,
                False, None, None)
            h = acts[src]
            if node.preprocessor is not None:
                h = node.preprocessor.preProcess(h)
            return h

        @jax.jit
        def pre_step(p, us, it, inputs, key):
            loss, g = jax.value_and_grad(
                lambda p_: layer.pretrain_loss(self._cast_params(p_),
                                               feed(inputs), key))(p)
            d, us = upd.apply(g, us, it, params=p)
            p = jax.tree_util.tree_map(
                lambda a, b: (a - b).astype(a.dtype), p, d)
            return p, us, loss

        from deeplearning4j_tpu.data.dataset import DataSet

        p, us = self._params[layerName], self._upd_states[layerName]
        loss = float("nan")

        def one(features, p, us):
            inputs = self._coerce_inputs(features)
            key = jax.random.fold_in(
                jax.random.key(self.conf.seed ^ 0xE1B0), self._iteration)
            p, us, loss = pre_step(p, us,
                                   jnp.asarray(self._iteration, jnp.int32),
                                   inputs, key)
            self._iteration += 1
            return p, us, loss

        for _ in range(epochs):
            if isinstance(data, DataSet):
                p, us, loss = one(data.getFeatures(), p, us)
            elif hasattr(data, "hasNext"):
                data.reset()
                while data.hasNext():
                    p, us, loss = one(data.next().getFeatures(), p, us)
            else:
                p, us, loss = one(data, p, us)
        self._params[layerName], self._upd_states[layerName] = p, us
        self._score = float(loss)
        return self

    def output(self, *features):
        self._require_init()
        inputs = self._coerce_inputs(features if len(features) > 1 else features[0])
        outs = self._jit_forward(self._params, self._states, inputs)
        outs = [INDArray(o) for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def outputSingle(self, *features) -> INDArray:
        out = self.output(*features)
        return out if isinstance(out, INDArray) else out[0]

    def feedForward(self, *features, train=False):
        """Every vertex/layer activation by name (reference:
        ComputationGraph.feedForward() -> Map<String,INDArray>). CNN
        activations come back in the API's NCHW layout. Inspection API:
        runs the graph eagerly (outside the jitted inference path)."""
        self._require_init()
        inputs = self._coerce_inputs(
            features if len(features) > 1 else features[0])
        key = jax.random.key(self.conf.seed ^ 0xFEED) if train else None
        acts, _, _ = self._run_graph(
            self._params, self._strip_carries(self._states), inputs,
            train, key, None)
        out = {}
        nhwc = self._api_nhwc
        for name, a in acts.items():
            if hasattr(a, "ndim") and a.ndim == 4 and not nhwc and \
                    name not in self.conf.networkOutputs:
                a = jnp.transpose(a, (0, 3, 1, 2))
            out[name] = INDArray(a)
        return out

    def score(self, ds=None) -> float:
        if ds is None:
            return getattr(self, "_score", float("nan"))
        from deeplearning4j_tpu.data.multidataset import MultiDataSet

        self._require_init()
        if isinstance(ds, MultiDataSet):
            inputs = {n: _unwrap(f) for n, f in zip(self.conf.networkInputs, ds.getFeatures())}
            labs = [_unwrap(l) for l in ds.getLabels()]
            fm = ds.getFeaturesMaskArrays()
            fmasks = None if fm is None else {
                n: _unwrap(m) for n, m in zip(self.conf.networkInputs, fm)}
            lm = ds.getLabelsMaskArrays()
            lmasks = None if lm is None else [_unwrap(m) for m in lm]
        else:
            inputs = {self.conf.networkInputs[0]: _unwrap(ds.getFeatures())}
            labs = [_unwrap(ds.getLabels())]
            fm = ds.getFeaturesMaskArray()
            fmasks = None if fm is None else {self.conf.networkInputs[0]: _unwrap(fm)}
            lm = ds.getLabelsMaskArray()
            lmasks = None if lm is None else [_unwrap(lm)]
        return float(self._jit_loss(self._params, self._states, inputs, labs,
                                    fmasks, lmasks))

    def doEvaluation(self, iterator, *evaluations):
        """Stream the iterator through outputSingle() into any number of
        IEvaluation instances (reference: ComputationGraph.doEvaluation)."""
        from deeplearning4j_tpu.data.multidataset import MultiDataSet

        if not evaluations:
            raise ValueError("doEvaluation needs at least one IEvaluation")
        if len(self.conf.networkOutputs) > 1:
            raise ValueError(
                "doEvaluation evaluates a single-output graph; score "
                "multi-output graphs per-output via output() directly "
                "(reference throws here too)")
        iterator.reset()
        while iterator.hasNext():
            ds = iterator.next()
            out = self.outputSingle(ds.getFeatures())
            if isinstance(ds, MultiDataSet):
                lm = ds.getLabelsMaskArrays()
                lab, m = ds.getLabels(0), None if lm is None else lm[0]
            else:
                lab, m = ds.getLabels(), ds.getLabelsMaskArray()
            for e in evaluations:
                e.eval(lab, out, mask=m)
        return evaluations if len(evaluations) > 1 else evaluations[0]

    def evaluateRegression(self, iterator):
        from deeplearning4j_tpu.evaluation.regression import RegressionEvaluation

        return self.doEvaluation(iterator, RegressionEvaluation())

    def evaluateROC(self, iterator, thresholdSteps=0):
        from deeplearning4j_tpu.evaluation.roc import ROC

        return self.doEvaluation(iterator, ROC(thresholdSteps))

    def evaluateROCMultiClass(self, iterator, thresholdSteps=0):
        from deeplearning4j_tpu.evaluation.roc import ROCMultiClass

        return self.doEvaluation(iterator, ROCMultiClass(thresholdSteps))

    def evaluate(self, iterator):
        from deeplearning4j_tpu.evaluation.evaluation import Evaluation

        return self.doEvaluation(iterator, Evaluation())


    def params(self) -> INDArray:
        leaves = jax.tree_util.tree_leaves(self._params)
        return INDArray(jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves]))

    def numParams(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self._params))

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

    def paramTable(self) -> dict:
        """"vertexName_paramName" -> INDArray (reference:
        ComputationGraph.paramTable)."""
        out = {}
        for name in self._layer_names:
            for k, v in self._params[name].items():
                out[f"{name}_{k}"] = INDArray(v)
        return out

    def getParam(self, key: str):
        """One parameter by "vertexName_paramName" key (reference:
        Model.getParam). Vertex names may contain underscores, so the
        split is on the LAST one."""
        name, _, pname = key.rpartition("_")
        return INDArray(self._params[name][pname])

    def setParamTable(self, table: dict):
        """Assign parameters by "vertexName_paramName" keys (reference:
        Model.setParamTable). Shapes must match the existing table."""
        for key, v in table.items():
            name, _, pname = key.rpartition("_")
            cur = self._params[name][pname]
            arr = jnp.asarray(_unwrap(v), cur.dtype)
            if arr.shape != cur.shape:
                raise ValueError(
                    f"setParamTable: {key} has shape {arr.shape}, "
                    f"expected {cur.shape}")
            self._params[name] = {**self._params[name], pname: arr}
        return self

    def computeGradientAndScore(self, inputs, labels):
        """(grads, score) for gradient checks (reference:
        Model.computeGradientAndScore). `inputs`/`labels` follow fit()'s
        conventions (single array or list)."""
        ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        labs = labels if isinstance(labels, (list, tuple)) else [labels]
        feed = {n: _unwrap(v) for n, v in
                zip(self.conf.networkInputs, ins)}
        (loss, _), grads = jax.value_and_grad(
            self._loss_fn, has_aux=True)(
            self._params, self._states, feed,
            [_unwrap(y) for y in labs], None, None, None, False)
        return grads, float(loss)

    def clone(self):
        """Independent copy with the same configuration and parameters
        (reference: ComputationGraph.clone). Buffers are COPIED —
        fit() donates the original's arrays to XLA, so a buffer-sharing
        clone would die on the original's next train step."""
        # initFrom, not init(): a full random re-initialization would
        # be computed and immediately overwritten
        copy = lambda x: jnp.copy(x) if hasattr(x, "shape") else x
        net = ComputationGraph(self.conf).initFrom(
            jax.tree_util.tree_map(copy, self._params),
            jax.tree_util.tree_map(copy, self._states),
            jax.tree_util.tree_map(copy, self._upd_states))
        # training position travels with the updater moments (see
        # MultiLayerNetwork.clone)
        net._iteration = self._iteration
        net._epoch = self._epoch
        return net

    def setListeners(self, *listeners):
        self._listeners = list(listeners)
        return self

    def addListeners(self, *listeners):
        self._listeners.extend(listeners)
        return self

    def getIterationCount(self):
        return self._iteration

    def getEpochCount(self):
        return self._epoch

    def save(self, path, saveUpdater: bool = True):
        """Reference: ComputationGraph.save(File, saveUpdater)."""
        from deeplearning4j_tpu.util.serializer import ModelSerializer

        ModelSerializer.writeModel(self, path, saveUpdater)
        return self

    @staticmethod
    def load(path, loadUpdater: bool = True) -> "ComputationGraph":
        from deeplearning4j_tpu.util.serializer import ModelSerializer

        return ModelSerializer.restoreComputationGraph(path, loadUpdater)

    def summary(self) -> str:
        lines = [f"{'name':<24}{'type':<26}{'inputs':<30}{'params':<10}"]
        total = 0
        for name in self.conf.topoOrder:
            node = self.conf.nodes[name]
            n = 0
            if node.kind == "layer" and self._params:
                n = sum(int(np.prod(v.shape)) for v in self._params[name].values())
            total += n
            kind = type(node.payload).__name__ if node.payload is not None else "Input"
            lines.append(f"{name:<24}{kind:<26}{','.join(node.inputs):<30}{n:<10}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)
