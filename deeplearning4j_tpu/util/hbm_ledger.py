"""Per-op HBM traffic ledger + train-step roofline floor.

A diagnosis that stops at "bandwidth-bound, N GB/step" does not say
WHICH fusions carry those bytes or what the unavoidable floor is. This
module supplies both:

- `ledger(hlo_text)` walks the compiled module and charges each
  instruction the bytes it moves, following XLA's own HloCostAnalysis
  conventions so the total reproduces
  ``compiled.cost_analysis()["bytes accessed"]`` (validated exact to
  <0.1% on XLA:CPU by tests/test_hbm_ledger.py):

  * a plain instruction is charged its output buffer plus every operand
    buffer (resolved through a module-wide symbol table);
  * a TUPLE-shaped result is priced as its pointer table (8 bytes per
    top-level element, the backend's ShapeSizeBytes convention) — the
    leaf buffers are charged at the get-tuple-element consumers that
    actually read them, never twice;
  * ``call`` / ``while`` / ``conditional`` recurse into their attached
    computations (body + condition once for a while, matching
    HandleWhile's single-iteration convention) instead of being charged
    at the call site;
  * ``dynamic-slice`` / ``dynamic-update-slice`` are in-place: only the
    slice region is charged (2x the update/output plus the scalar
    indices), not the full aliased buffer;
  * ``fusion`` is call-site-priced (parameters + root) with XLA's
    utilization scaling: a fusion whose ROOT is a dynamic-update-slice
    writes only the update region (the aliased operand reads likewise),
    and a parameter consumed exclusively through dynamic-slice is
    charged the slice size, not the full buffer — the in-place loop
    patterns XLA emits for scan/select_and_scatter bodies. Everything
    else inside a fusion stays in registers/VMEM and is free.

- `train_step_floor(net, x_shape)` computes the analytic lower bound on
  HBM bytes for one training step from the MODEL, not the compiler:
  master params + optimizer state + grads at fp32, compute-dtype weight
  copies, the input batch, and the minimal activation traffic of a
  conv net's forward+backward. Measured bytes / floor says how close
  XLA's lowering is to the memory roofline — "within N% of floor" is a
  result; "bandwidth-bound" alone is a stopping excuse.

- `static_memory_terms(...)` is the RESIDENCY (capacity) counterpart of
  the floor's traffic model: per-chip HBM bytes a train step must hold
  live at its high-water mark. The partition-plan analyzer's PAR06 pass
  (analysis/partitioning.py) builds on it to predict OOM before any
  compile.

The floor's activation model, stated so the number is auditable: every
layer boundary activation A is (1) written by the forward, (2) read by
the backward to form the weight gradient, and its gradient G (same
size) is (3) written and (4) read by the adjacent backward step —
4 touches of each boundary buffer at compute dtype. Rematerialisation
can trade (1)/(2) for recompute; XLA fusion can eliminate boundaries
between elementwise neighbours, which is why the floor uses ONLY
conv/dense/pool boundaries (fusable chains of BN/relu/add don't count).
"""

from __future__ import annotations

import re

import numpy as np

from deeplearning4j_tpu.parallel.overlap import _DTYPE_BITS, _SHAPE_RE

# '%name = <result types> opcode(...operands...)'
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([a-z][\w\-]*)\((.*)$")
_OPERAND_RE = re.compile(r"%?([\w.\-]+)")

# 'name {' / 'ENTRY name {' / '%name (params) -> result {'
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*)?\{\s*$")

# attached-computation attributes, parsed per key so a comma-list like
# branch_computations={%a, %b} cannot bleed into the next attribute
_ATTACH_RE = re.compile(
    r"(?:calls|to_apply|condition|body|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_ATTACH_LIST_RE = re.compile(r"branch_computations=\{([^}]*)\}")

# opcodes that don't move HBM bytes themselves (metadata / control flow
# / aliasing views); their operands are charged where actually consumed
_FREE_OPS = {"parameter", "get-tuple-element", "bitcast",
             "constant", "after-all", "partition-id", "replica-id"}

# opcodes charged by recursing into their attached computations
# (HloCostAnalysis HandleCall/HandleWhile/HandleConditional)
_SUBCOMP_OPS = {"call", "while", "conditional"}

_POINTER_SIZE = 8  # bytes per tuple-table entry (CPU/TPU ShapeSizeBytes)


_ANY_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]{0,14})\[[0-9,]*\]")


def _tuple_arity(result_text):
    """Top-level element count of a tuple-shaped result text like
    '(f32[2]{0}, (s32[3]{0}, s32[]))' -> 2; 0 for non-tuple results."""
    s = result_text.strip()
    if not s.startswith("("):
        return 0
    depth = 0
    arity = 1
    for ch in s:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        elif ch == "," and depth == 1:
            arity += 1
    return arity


def _result_bytes(result_text):
    # an unrecognized dtype must FAIL, not silently rank as 0 bytes —
    # the whole point is an accurate table on the TPU backend
    for tok in _ANY_SHAPE_RE.findall(result_text):
        if tok not in _DTYPE_BITS and tok != "token":
            raise ValueError(
                f"unknown HLO dtype {tok!r} in {result_text[:80]!r} — "
                "add it to parallel/overlap.py _DTYPE_BITS")
    arity = _tuple_arity(result_text)
    if arity:
        # tuple shape = pointer table; the element buffers are charged
        # at the GTE consumers that read them
        return arity * _POINTER_SIZE
    total = 0
    for dt, dims in _SHAPE_RE.findall(result_text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += (n * _DTYPE_BITS[dt] + 7) // 8
    return total


def _result_meta(result_text):
    """(dtype_str, elems) of a single-shape non-tuple result; None for
    tuples, tokens and anything else the classifier cannot reason
    about (attribution then treats the buffer as opaque)."""
    s = result_text.strip()
    if s.startswith("("):
        return None
    found = _SHAPE_RE.findall(result_text)
    if len(found) != 1:
        return None
    dt, dims = found[0]
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return dt, n


def _parse_module(hlo_text):
    """-> (sizes, comp_sizes, computations, entry_name, meta, comp_meta)
    where computations maps name -> [(name, op, out_bytes,
    operand_names, attached_comps, is_root)] and meta/comp_meta carry
    (dtype, elems) per instruction for the attribution classifier.

    HLO instruction names are only guaranteed unique PER COMPUTATION —
    a name reused inside a fusion/called computation must not overwrite
    an ENTRY buffer's size (ADVICE r5 #1) — so sizes are recorded both
    per computation (`comp_sizes`, the authoritative scope for operand
    resolution) and module-wide (`sizes`, the fallback for names a
    computation references but does not define, e.g. cross-computation
    references in synthetic test modules)."""
    sizes = {}
    comp_sizes = {}
    meta = {}
    comp_meta = {}
    comps = {}
    cur = None
    entry = None
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _DEF_RE.match(line)
        if m is None:
            # not an instruction: computation header or closing brace
            cm = _COMP_RE.match(s)
            if cm:
                cur = cm.group(2)
                comps[cur] = []
                comp_sizes[cur] = {}
                comp_meta[cur] = {}
                if cm.group(1):
                    entry = cur
            elif s == "}":
                cur = None
            continue
        name, result, op, rest = m.groups()
        nbytes = _result_bytes(result)
        rmeta = _result_meta(result)
        # module-wide fallback keeps the FIRST definition: a later
        # fusion-internal reuse of an entry name cannot reprice it
        sizes.setdefault(name, nbytes)
        if rmeta is not None:
            meta.setdefault(name, rmeta)
        if cur is not None:
            comp_sizes[cur][name] = nbytes
            if rmeta is not None:
                comp_meta[cur][name] = rmeta
        # operands = instruction names before the first metadata key;
        # stop there to avoid charging called-computation names
        arg_text = rest.split("), ")[0] if "), " in rest else rest
        operands = _OPERAND_RE.findall(arg_text)
        attached = _ATTACH_RE.findall(rest)
        for lst in _ATTACH_LIST_RE.findall(rest):
            attached.extend(t.strip().lstrip("%")
                            for t in lst.split(",") if t.strip())
        if cur is not None:
            comps[cur].append((name, op, nbytes, operands, attached,
                               s.startswith("ROOT ")))
    return sizes, comp_sizes, comps, entry, meta, comp_meta


def _fusion_bytes(fname, callsite_operands, out_bytes, caller_sizes,
                  inner_sizes, comps):
    """(bytes, out, in) of one fusion call site with XLA's utilization
    scaling: an in-place DUS root writes only the update region, and a
    parameter consumed exclusively via dynamic-slice is charged the
    slice size (HloCostAnalysis fusion handling). Falls back to the
    plain parameters+root charge when the fused computation is
    unavailable.

    Two size scopes (HLO names are unique per computation only):
    `caller_sizes` resolves the CALLSITE operands, `inner_sizes` the
    fusion-internal instructions — a shared name must never cross."""
    insts = comps.get(fname)
    known = [t for t in callsite_operands if t in caller_sizes]
    if not insts:
        seen, in_bytes = set(), 0
        for t in known:
            if t not in seen:
                seen.add(t)
                in_bytes += caller_sizes[t]
        return out_bytes + in_bytes, out_bytes, in_bytes

    param_of = {}     # inner parameter name -> callsite operand name
    consumers = {}    # inner name -> [(op, operands)]
    root = None
    for name, op, _, operands, _, is_root in insts:
        if op == "parameter":
            idx = next((int(t) for t in operands if t.isdigit()), None)
            if idx is not None and idx < len(known):
                param_of[name] = known[idx]
        else:
            for t in operands:
                consumers.setdefault(t, []).append((op, operands))
        if is_root:
            root = (name, op, operands)
    if root is None and insts:
        root = (insts[-1][0], insts[-1][1], insts[-1][3])

    dus_aliased = None   # inner name feeding the in-place DUS operand 0
    out_eff = out_bytes
    if root is not None and root[1] == "dynamic-update-slice":
        r_ops = [t for t in root[2] if t in inner_sizes]
        if len(r_ops) >= 2:
            out_eff = inner_sizes[r_ops[1]]    # update region only
            dus_aliased = r_ops[0]

    def data_operand(operands):
        """First operand that names an instruction (the token list also
        carries dtype/dim text, which never resolves in the scope)."""
        return next((t for t in operands if t in inner_sizes), None)

    in_bytes = 0
    for pname, site_name in param_of.items():
        uses = consumers.get(pname, [])
        if pname == dus_aliased:
            in_bytes += out_eff          # aliased: reads the update region
        elif uses and all(op == "dynamic-slice"
                          and data_operand(ops) == pname
                          for op, ops in uses):
            # sliced access only: charge each slice's output, not the
            # full buffer
            in_bytes += sum(b for _n, o, b, ops2, _a, _r in insts
                            if o == "dynamic-slice"
                            and data_operand(ops2) == pname)
        else:
            in_bytes += caller_sizes[site_name]
    return out_eff + in_bytes, out_eff, in_bytes


def _instruction_bytes(op, out_bytes, operands, sizes):
    """(bytes, out, in) for one non-recursive instruction, following the
    HloCostAnalysis special cases for in-place slicing ops."""
    known = [t for t in operands if t in sizes]
    if op == "dynamic-update-slice":
        # operand 0 aliases the output: only the update region moves
        upd = sizes[known[1]] if len(known) > 1 else 0
        idx = sum(sizes[t] for t in known[2:])
        return 2 * upd + idx, upd, upd + idx
    if op == "dynamic-slice":
        idx = sum(sizes[t] for t in known[1:])
        return 2 * out_bytes + idx, out_bytes, out_bytes + idx
    if op == "tuple":
        # gathers pointers only; element buffers charged at consumers
        return out_bytes, out_bytes, 0
    in_bytes = 0
    seen = set()
    for t in known:
        if t not in seen:
            seen.add(t)
            in_bytes += sizes[t]
    return out_bytes + in_bytes, out_bytes, in_bytes


def ledger(hlo_text, top=15):
    """Rank ENTRY instructions by HBM bytes touched.

    Returns {"total_bytes", "by_opcode": {op: bytes}, "top": [
    {"name", "op", "bytes", "out_bytes", "in_bytes"}, ...]}.
    by_opcode attributes bytes to the opcode that actually moves them —
    instructions inside call/while/conditional bodies count under their
    own opcodes, not under the call site's.
    """
    sizes, comp_sizes, comps, entry, _meta, _comp_meta = \
        _parse_module(hlo_text)
    if entry is None:
        # single anonymous/first computation (inline test modules)
        entry = next(iter(comps)) if comps else None

    by_op = {}
    visiting = set()
    scopes = {}

    def scoped(cname):
        """Operand-size scope for one computation: its OWN definitions
        first (HLO names are unique per computation, so a fusion-
        internal name reuse can't misprice an entry instruction —
        ADVICE r5 #1), module-wide first-definition fallback for names
        it references but does not define. ChainMap: two-level lookup
        without copying the module-wide table per computation."""
        from collections import ChainMap

        sc = scopes.get(cname)
        if sc is None:
            sc = ChainMap(comp_sizes.get(cname, {}), sizes)
            scopes[cname] = sc
        return sc

    def inst_bytes(op, out_bytes, operands, attached, sc):
        if op == "fusion" and attached:
            return _fusion_bytes(attached[0], operands, out_bytes, sc,
                                 scoped(attached[0]), comps)
        return _instruction_bytes(op, out_bytes, operands, sc)

    def comp_cost(cname):
        """Total bytes of one computation, recursing through
        call/while/conditional (processed per call site, as
        HloCostAnalysis does); free ops and fusion interiors are never
        charged."""
        if cname in visiting or cname not in comps:
            return 0
        visiting.add(cname)
        sc = scoped(cname)
        total = 0
        for name, op, out_bytes, operands, attached, _root in comps[cname]:
            if op in _FREE_OPS:
                continue
            if op in _SUBCOMP_OPS:
                total += sum(comp_cost(a) for a in attached)
                continue
            nbytes, _, _ = inst_bytes(op, out_bytes, operands, attached, sc)
            total += nbytes
            by_op[op] = by_op.get(op, 0) + nbytes
        visiting.discard(cname)
        return total

    rows = []
    total = 0
    entry_scope = scoped(entry) if entry is not None else dict(sizes)
    for name, op, out_bytes, operands, attached, _root in comps.get(entry, []):
        if op in _FREE_OPS:
            continue
        if op in _SUBCOMP_OPS:
            sub = sum(comp_cost(a) for a in attached)
            total += sub
            rows.append({"name": name, "op": op, "bytes": sub,
                         "out_bytes": 0, "in_bytes": sub})
            continue
        nbytes, ob, ib = inst_bytes(op, out_bytes, operands, attached,
                                    entry_scope)
        total += nbytes
        by_op[op] = by_op.get(op, 0) + nbytes
        rows.append({"name": name, "op": op, "bytes": nbytes,
                     "out_bytes": ob, "in_bytes": ib})
    rows.sort(key=lambda r: -r["bytes"])
    return {"total_bytes": total,
            "by_opcode": dict(sorted(by_op.items(), key=lambda kv: -kv[1])),
            "top": rows[:top]}


def ledger_for_compiled(compiled, top=15):
    return ledger(compiled.as_text(), top=top)


# ---------------------------------------------------------------------
# analytic roofline floor
# ---------------------------------------------------------------------

_BOUNDARY_LAYERS = ("ConvolutionLayer", "Convolution2D", "DenseLayer",
                    "SubsamplingLayer", "SeparableConvolution2D",
                    "DepthwiseConvolution2D", "Deconvolution2D",
                    "OutputLayer")


def _boundary_layer_objects(net):
    if hasattr(net, "layers"):  # MultiLayerNetwork
        layers = list(net.layers)
    else:  # ComputationGraph
        layers = [n.payload for n in net.conf.nodes.values()
                  if getattr(n, "payload", None) is not None]
    return [l for l in layers if type(l).__name__ in _BOUNDARY_LAYERS]


def _input_shapes(net, x_shape):
    """Normalize `x_shape` into {input_name: shape} for a
    ComputationGraph (ADVICE r5 #3: multi-input graphs pass a dict of
    input shapes; a bare tuple keeps working for single-input graphs),
    or return the tuple unchanged for a MultiLayerNetwork."""
    if hasattr(net, "layers"):  # MultiLayerNetwork: one positional input
        if isinstance(x_shape, dict):
            raise ValueError(
                "MultiLayerNetwork takes one input shape tuple, not a "
                "dict")
        return tuple(x_shape)
    names = list(net.conf.networkInputs)
    if isinstance(x_shape, dict):
        missing = [n for n in names if n not in x_shape]
        if missing:
            raise ValueError(
                f"x_shape dict is missing graph input(s) {missing} "
                f"(graph inputs: {names})")
        return {n: tuple(x_shape[n]) for n in names}
    if len(names) == 1:
        return {names[0]: tuple(x_shape)}
    raise ValueError(
        f"graph has {len(names)} inputs ({names}); pass x_shape as a "
        "dict of input shapes, e.g. {name: (B, ...), ...}")


def boundary_activation_elems(net, x_shape):
    """Per-layer boundary activation element counts via jax.eval_shape
    (abstract — nothing executes). Only conv/dense/pool boundaries
    count; elementwise chains between them are fusable and carry no
    unavoidable HBM traffic. Works for MultiLayerNetwork AND
    ComputationGraph by recording each boundary layer's forward output
    shape during the abstract trace; multi-input graphs pass `x_shape`
    as a {input_name: shape} dict."""
    import jax

    shapes = _input_shapes(net, x_shape)
    recorded = []
    wrapped = []
    for layer in _boundary_layer_objects(net):
        orig = layer.forward  # bound method

        def mk(orig):
            def spy(*a, **kw):
                out = orig(*a, **kw)
                h = out[0] if isinstance(out, tuple) else out
                recorded.append(int(np.prod(h.shape)))
                return out
            return spy

        layer.forward = mk(orig)  # instance attr shadows the class method
        wrapped.append(layer)
    try:
        dt = np.dtype(net._compute_dtype)
        if hasattr(net, "layers"):
            x = jax.ShapeDtypeStruct(shapes, dt)
            jax.eval_shape(
                lambda xx: net._forward_infer(net._params, net._states, xx),
                x)
        else:
            xs = {n: jax.ShapeDtypeStruct(s, dt) for n, s in shapes.items()}
            jax.eval_shape(
                lambda inputs: net._forward_infer(net._params, net._states,
                                                  inputs), xs)
    finally:
        for layer in wrapped:
            del layer.__dict__["forward"]
    return recorded


def train_step_floor(net, x_shape, optimizer_slots=1):
    """Analytic lower bound on HBM bytes for one train step.

    optimizer_slots: per-param fp32 state buffers the updater holds
    (1 = momentum/Nesterovs, 2 = Adam).
    Terms, each at its dtype (see module docstring for the activation
    model):
      params:   fp32 master read + write, compute-dtype copy written
                once and read by fwd and bwd (3 touches at compute)
      optimizer: fp32 state read + write per slot
      grads:    fp32 write + read
      input:    batch read once at compute dtype
      acts:     4 touches of every conv/dense/pool boundary buffer
    """
    cb = np.dtype(net._compute_dtype).itemsize
    pb = np.dtype(net._param_dtype).itemsize
    P = int(sum(a.size for a in _tree_leaves(net._params)))
    A = int(sum(boundary_activation_elems(net, x_shape)))
    shapes = _input_shapes(net, x_shape)
    if isinstance(shapes, dict):  # multi-input graph: every batch reads
        Bx = int(sum(np.prod(s) for s in shapes.values()))
    else:
        Bx = int(np.prod(shapes))
    # when compute dtype == param dtype there IS no separate cast copy:
    # fwd+bwd read the master buffers directly (2 reads) — charging the
    # 3-touch copy there would push the "floor" ABOVE real programs
    copy_bytes = 3 * P * cb if cb != pb else 2 * P * pb
    terms = {
        "params_master_rw": 2 * P * pb,
        "params_compute_copy": copy_bytes,
        "optimizer_state_rw": 2 * optimizer_slots * P * pb,
        "grads_wr": 2 * P * pb,
        "input_read": Bx * cb,
        "activations_4touch": 4 * A * cb,
    }
    return {"floor_bytes": int(sum(terms.values())), "terms": terms,
            "param_count": P, "boundary_activation_elems": A}


# ---------------------------------------------------------------------
# static residency (capacity) model — the PAR06 building block
# ---------------------------------------------------------------------

def static_memory_terms(param_elems, opt_state_elems, boundary_act_bytes,
                        compute_itemsize, param_itemsize, input_bytes=0,
                        grad_itemsize=None, weight_update_sharding=1.0):
    """Per-chip HBM RESIDENCY at the train step's high-water mark,
    computed from already-placed (per-chip) element counts — the caller
    (analysis/partitioning.py) applies the sharding plan's division
    first. This is capacity, not traffic: what must fit, vs what the
    floor says must move.

      params:      fp32 master copies
      grads:       one gradient buffer per param (fp32 — the updaters
                   consume fp32 grads)
      optimizer:   the updater's state leaves (exact count, not slots x
                   params — Sgd holds nothing, Adam holds 2x), divided
                   by `weight_update_sharding`
      cast copy:   a compute-dtype copy of the params, only when the
                   compute dtype differs from the param dtype
      activations: every conv/dense/pool boundary buffer simultaneously
                   live at the start of the backward pass (the
                   high-water mark without rematerialisation)
      input:       the device-resident batch

    weight_update_sharding is the ZeRO cross-replica weight-update
    sharding factor (parallel.sharding.ZeroShardedUpdate): under
    weight_update='sharded' each chip holds only 1/dp of the updater
    state — params stay replicated (the forward needs them) and the
    gradient buffer is still materialised whole before its
    reduce-scatter, so ONLY the optimizer term divides. Pass the
    EFFECTIVE factor (opt_state_elems-layout bytes / actual per-chip
    bytes): leaves below min_shard_size or indivisible by dp stay
    replicated, so the effective factor is <= dp (the partition-plan
    analyzer's PAR06 pass computes it exactly from the per-leaf
    eligibility rule). The factor may be BELOW 1: when
    `opt_state_elems` already reflects a tensor-parallel division finer
    than dp (tp > dp), the ZeRO flat view's 1/dp-over-the-data-axis
    layout genuinely holds MORE per chip than the tp layout would — the
    residency model must report that, not clamp it away.
    """
    gb = param_itemsize if grad_itemsize is None else grad_itemsize
    wf = float(weight_update_sharding)
    if wf <= 0.0:
        raise ValueError(
            f"weight_update_sharding must be > 0, got {wf}")
    terms = {
        "params_bytes": int(param_elems * param_itemsize),
        "grads_bytes": int(param_elems * gb),
        "optimizer_state_bytes": int(opt_state_elems * param_itemsize
                                     / wf),
        "params_cast_copy_bytes": (int(param_elems * compute_itemsize)
                                   if compute_itemsize != param_itemsize
                                   else 0),
        "activations_bytes": int(boundary_act_bytes),
        "input_bytes": int(input_bytes),
    }
    terms["total_bytes"] = int(sum(terms.values()))
    terms["weight_update_sharding"] = round(wf, 4)
    return terms


def _tree_leaves(t):
    import jax

    return jax.tree_util.tree_leaves(t)


# ---------------------------------------------------------------------
# attribution engine: name the gap between ledger total and floor
# ---------------------------------------------------------------------
#
# The round-5 ledger proved the flagship moves ~3.95x the analytic floor
# and stopped there. attribute_ledger() finishes the sentence: every
# charged byte is classified into the floor (the bytes the MODEL needs)
# or a named overhead bin (the bytes the LOWERING added), so "35 GB of
# lowering overhead" becomes a per-category bill the next fix can be
# measured against.
#
# Bin conventions (chosen so no charged byte lands in two bins and the
# invariant floor + bins + uncategorized == ledger total holds exactly):
#
#   layout_copies     full bytes of relayout instructions — copy /
#                     copy-start/-done / transpose / pad / reshape /
#                     slice / concatenate / reverse / broadcast — and of
#                     fusions whose ROOT is one (XLA's copy/transpose
#                     fusions). The floor contains no relayouts, so the
#                     whole row is overhead.
#   dtype_widening    the WIDENING EXCESS of buffers wider than the
#                     compute dtype at activation scale: a f32 buffer in
#                     a bf16-policy step is half excess — the floor
#                     already prices the bf16-equivalent touch. Charged
#                     on writes and on every read.
#   grad_double_touch reads BEYOND THE FIRST of compute-dtype
#                     activation-scale buffers (the dX-conv + dW-conv
#                     both re-reading a boundary activation is the
#                     canonical case). The floor's 4-touch model allows
#                     one backward read per buffer; extra reads are
#                     overhead.
#   collective        full bytes of cross-replica traffic (all-reduce /
#                     all-gather / reduce-scatter / collective-permute /
#                     all-to-all) — the data-parallel weight-update bill
#                     (cf. Xu et al., cross-replica sharding of weight
#                     update); the single-chip floor has none.
#
# "Activation scale" = more elements than the largest parameter leaf:
# master params, grads and updater state are at most param-sized, so
# anything bigger must be batch/spatial data. uncategorized is the
# remainder; it holds the floor itself (params/grads/updater/input/
# activation traffic is not re-identified buffer-by-buffer) plus
# whatever the bins cannot name — a large POSITIVE uncategorized on a
# gap-heavy program means the bins missed something and is reported,
# never hidden.

#: cross-replica traffic (async start/done forms included)
_COLLECTIVE_OPS = {
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "all-reduce-start", "all-reduce-done",
    "all-gather-start", "all-gather-done", "collective-permute-start",
    "collective-permute-done", "reduce-scatter-start",
    "reduce-scatter-done",
}

#: pure-relayout opcodes: they move bytes without computing anything the
#: floor model recognises
_LAYOUT_OPS = {"copy", "copy-start", "copy-done", "transpose", "pad",
               "reshape", "slice", "concatenate", "reverse", "broadcast"}

_FLOAT_DTYPES = frozenset(d for d in _DTYPE_BITS
                          if d[0] == "f" or d.startswith("bf"))


def _walk_charged_rows(mod):
    """Every charged instruction as a flat row list, recursing through
    call/while/conditional per call site exactly as ledger() does —
    sum(row bytes) == ledger()['total_bytes'] by construction. Fusions
    are one call-site-priced row annotated with their root opcode (the
    relayout-fusion marker); free ops never appear.

    Row: (scope, name, op, bytes, out_bytes, in_bytes, out_meta,
    reads, root_op) with reads = [(operand, bytes, meta), ...] over the
    distinct resolved operands."""
    from collections import ChainMap

    sizes, comp_sizes, comps, entry, meta, comp_meta = mod
    if entry is None:
        entry = next(iter(comps)) if comps else None

    size_scopes, meta_scopes = {}, {}

    def scoped(cname):
        sc = size_scopes.get(cname)
        if sc is None:
            sc = ChainMap(comp_sizes.get(cname, {}), sizes)
            size_scopes[cname] = sc
            meta_scopes[cname] = ChainMap(comp_meta.get(cname, {}), meta)
        return sc, meta_scopes[cname]

    rows = []
    visiting = set()

    def walk(cname):
        if cname in visiting or cname not in comps:
            return
        visiting.add(cname)
        sc, mc = scoped(cname)
        for name, op, out_bytes, operands, attached, _root in comps[cname]:
            if op in _FREE_OPS:
                continue
            if op in _SUBCOMP_OPS:
                for a in attached:
                    walk(a)
                continue
            root_op = None
            if op == "fusion" and attached:
                insts = comps.get(attached[0]) or ()
                for iname, iop, _b, _o, _a, is_root in insts:
                    if is_root:
                        root_op = iop
                nbytes, ob, ib = _fusion_bytes(
                    attached[0], operands, out_bytes, sc,
                    scoped(attached[0])[0], comps)
            else:
                nbytes, ob, ib = _instruction_bytes(op, out_bytes,
                                                    operands, sc)
            reads, seen = [], set()
            for t in operands:
                if t in sc and t not in seen:
                    seen.add(t)
                    reads.append((t, sc[t], mc.get(t)))
            rows.append((cname, name, op, nbytes, ob, ib,
                         mc.get(name), reads, root_op))
        visiting.discard(cname)

    if entry is not None:
        walk(entry)
    return rows


def _is_scale(m, threshold_elems):
    return m is not None and m[1] > threshold_elems


def attribute_ledger(compiled, net=None, x_shape=None, optimizer_slots=1,
                     compute_dtype=None, act_threshold_elems=None, top=6):
    """Classify every charged byte of a compiled train step into the
    analytic floor vs named lowering-overhead bins (see the bin table
    above). `compiled` is a compiled executable or raw HLO text.

    With `net` (+ `x_shape`) the floor, the compute dtype and the
    activation-scale threshold all come from the model; without a net,
    pass `compute_dtype` and `act_threshold_elems` explicitly and the
    report is bins-only (floor 0). Invariant, exact by construction:

        floor_bytes + sum(bins) + uncategorized_bytes == ledger total
    """
    hlo = compiled if isinstance(compiled, str) else compiled.as_text()
    mod = _parse_module(hlo)
    rows = _walk_charged_rows(mod)
    total = sum(r[3] for r in rows)

    if net is not None:
        if compute_dtype is None:
            compute_dtype = net._compute_dtype
        if act_threshold_elems is None:
            act_threshold_elems = max(
                (int(a.size) for a in _tree_leaves(net._params)), default=0)
    if compute_dtype is None or act_threshold_elems is None:
        raise ValueError(
            "attribute_ledger needs a net (for the compute dtype and the "
            "activation-scale threshold) or explicit compute_dtype= and "
            "act_threshold_elems=")
    cbits = np.dtype(compute_dtype).itemsize * 8
    thr = int(act_threshold_elems)

    floor = None
    if net is not None and x_shape is not None:
        floor = train_step_floor(net, x_shape,
                                 optimizer_slots=optimizer_slots)

    bins = {"layout_copies": 0, "dtype_widening": 0,
            "grad_double_touch": 0, "collective": 0}
    contrib = {k: [] for k in bins}

    def wide_excess(m, nbytes):
        """Excess bytes of one wide-float activation-scale touch."""
        dt = m[0]
        if dt not in _FLOAT_DTYPES or _DTYPE_BITS[dt] <= cbits:
            return 0
        return int(round(nbytes * (1.0 - cbits / _DTYPE_BITS[dt])))

    read_counts = {}  # (scope, operand) -> [count, bytes, meta]
    for scope, name, op, nbytes, ob, ib, out_meta, reads, root_op in rows:
        if op in _COLLECTIVE_OPS or root_op in _COLLECTIVE_OPS:
            bins["collective"] += nbytes
            # param-scale collectives are the dp weight-update bill
            # (gradient all-reduce — Xu et al.); activation-scale ones
            # are tensor/sequence-parallel traffic. The split names
            # which fix applies (cross-replica update sharding vs
            # layout/sharding of activations).
            kind = ("activation" if _is_scale(out_meta, thr)
                    else "weight_update")
            contrib["collective"].append((f"{name} [{kind}]", op, nbytes))
            continue
        if op in _LAYOUT_OPS or (op == "fusion"
                                 and root_op in _LAYOUT_OPS):
            bins["layout_copies"] += nbytes
            contrib["layout_copies"].append((name, op, nbytes))
            continue
        wid = 0
        if _is_scale(out_meta, thr):
            wid += wide_excess(out_meta, ob)
        for t, b, m in reads:
            if _is_scale(m, thr):
                wid += wide_excess(m, b)
        wid = min(wid, nbytes)
        if wid:
            bins["dtype_widening"] += wid
            contrib["dtype_widening"].append((name, op, wid))
        for t, b, m in reads:
            rc = read_counts.get((scope, t))
            if rc is None:
                read_counts[(scope, t)] = [1, b, m]
            else:
                rc[0] += 1

    for (scope, t), (count, b, m) in read_counts.items():
        if count < 2 or not _is_scale(m, thr):
            continue
        dt = m[0]
        if dt in _FLOAT_DTYPES and _DTYPE_BITS[dt] <= cbits:
            extra = (count - 1) * b
            bins["grad_double_touch"] += extra
            contrib["grad_double_touch"].append((t, f"{count} reads",
                                                 extra))

    floor_bytes = floor["floor_bytes"] if floor else 0
    binsum = sum(bins.values())
    gap = total - floor_bytes if floor else None
    rec = {
        "ledger_total_bytes": int(total),
        "floor_bytes": int(floor_bytes),
        "floor_terms": dict(floor["terms"]) if floor else {},
        "bins": {k: int(v) for k, v in bins.items()},
        "bin_top": {
            k: [{"name": n, "op": o, "bytes": int(b)}
                for n, o, b in sorted(v, key=lambda r: -r[2])[:top]]
            for k, v in contrib.items()},
        "uncategorized_bytes": int(total - floor_bytes - binsum),
        "compute_dtype": str(np.dtype(compute_dtype)),
        "act_threshold_elems": thr,
    }
    if gap is not None:
        rec["gap_bytes"] = int(gap)
        rec["named_gap_frac"] = round(binsum / gap, 4) if gap > 0 else None
    # publish the attribution totals as gauges (host-side static
    # analysis): the /metrics view of what the last attributed compile
    # was billed — total, floor and each named overhead bin
    from deeplearning4j_tpu.runtime import telemetry

    _g = telemetry.get_registry().gauge(
        "dl4j_hbm_attributed_bytes",
        "last attribute_ledger bill: charged bytes by bin",
        labels=("bin",))
    _g.labels(bin="total").set(rec["ledger_total_bytes"])
    _g.labels(bin="floor").set(rec["floor_bytes"])
    _g.labels(bin="uncategorized").set(rec["uncategorized_bytes"])
    for b, v in rec["bins"].items():
        _g.labels(bin=b).set(v)
    return rec


def pre_opt_hlo(lowered):
    """Pre-optimization HLO text of a jax Lowered — the MODEL's dtype
    request, before backend passes rewrite it. The dtype-policy audit
    must read THIS form: backend optimization adds widenings the model
    never asked for (XLA:CPU promotes bf16 convolutions to f32 wholesale
    because its conv kernels are fp32-only; TPU does not), and a policy
    gate that flags backend artifacts would be red forever on CI
    hosts."""
    try:
        return lowered.as_text(dialect="hlo")
    except Exception:
        return lowered.compiler_ir(dialect="hlo").as_hlo_text()


def audit_activation_dtypes(compiled, net=None, compute_dtype=None,
                            act_threshold_elems=None):
    """HLO dtype-policy audit: every charged buffer of the step that is
    a FLOAT WIDER than the compute dtype at activation scale — the
    buffers the dtype_widening bin prices. A bf16-policy step that
    honours the round-6 tail policy (fp32 only in vector-scale
    statistics and fused reduce accumulators) returns [].

    `compiled` may be a compiled executable, a raw HLO string, or —
    the form a MODEL-policy CI gate should use — the pre_opt_hlo() text
    of the unoptimized lowering, which excludes backend-forced
    widenings (see pre_opt_hlo).

    Walks the same charged rows as the ledger (entry computation,
    recursing through call/while/conditional; fusion interiors stay in
    registers/VMEM and are exempt — only buffers that reach HBM can
    leak). Returns [{"scope", "name", "op", "dtype", "elems", "bytes"}]
    sorted largest first; assert_activation_dtype_clean raises with the
    offender table so a CI gate reads the leak, not just the failure."""
    hlo = compiled if isinstance(compiled, str) else compiled.as_text()
    if net is not None:
        if compute_dtype is None:
            compute_dtype = net._compute_dtype
        if act_threshold_elems is None:
            act_threshold_elems = max(
                (int(a.size) for a in _tree_leaves(net._params)), default=0)
    if compute_dtype is None or act_threshold_elems is None:
        raise ValueError(
            "audit_activation_dtypes needs a net or explicit "
            "compute_dtype= and act_threshold_elems=")
    cbits = np.dtype(compute_dtype).itemsize * 8
    thr = int(act_threshold_elems)
    mod = _parse_module(hlo)
    _sizes, _csizes, comps, _entry_name, _m, _cm = mod

    consumer_ops = {}  # scope -> {producer: {consumer ops}}

    def consumers(scope, name):
        sc = consumer_ops.get(scope)
        if sc is None:
            sc = {}
            for cn, cop, _b, operands, _a, _r in comps.get(scope, ()):
                for t in operands:
                    sc.setdefault(t, set()).add(cop)
            consumer_ops[scope] = sc
        return sc.get(name, set())

    offenders = []
    for scope, name, op, nbytes, ob, _ib, out_meta, _reads, _root in \
            _walk_charged_rows(mod):
        if not _is_scale(out_meta, thr):
            continue
        dt, elems = out_meta
        if dt not in _FLOAT_DTYPES or _DTYPE_BITS[dt] <= cbits:
            continue
        if op == "convert":
            # the SANCTIONED wide idiom: a widening convert consumed
            # ONLY by reductions is the `jnp.sum(..., dtype=f32)`
            # fused accumulator — backend fusion folds it into the
            # reduce and nothing wide reaches HBM. Any other consumer
            # makes it a real materialisation.
            cons = consumers(scope, name)
            if cons and cons <= {"reduce", "reduce-window"}:
                continue
        offenders.append({"scope": scope, "name": name, "op": op,
                          "dtype": dt, "elems": int(elems),
                          "bytes": int(ob)})
    offenders.sort(key=lambda r: -r["bytes"])
    return offenders


def assert_activation_dtype_clean(compiled, net=None, compute_dtype=None,
                                  act_threshold_elems=None):
    """Raise AssertionError naming every wide-float activation-scale
    buffer in the compiled step (audit_activation_dtypes); the CI form
    of the round-6 acceptance bar 'zero ENTRY-scope f32 activation-
    scale buffers in the bf16 flagship step'."""
    off = audit_activation_dtypes(compiled, net=net,
                                  compute_dtype=compute_dtype,
                                  act_threshold_elems=act_threshold_elems)
    if off:
        lines = [f"  {r['name'][:48]:<50} {r['op']:<16} {r['dtype']:<5} "
                 f"{r['elems']:>12} elems  {r['bytes']:>12} B"
                 for r in off[:12]]
        raise AssertionError(
            f"{len(off)} wide-float activation-scale buffer(s) in a "
            "step whose compute dtype should bound activation widths "
            "(dtype_widening leak):\n" + "\n".join(lines))


def format_attribution(rec, gb=True):
    """Human-readable attribution table (the analysis CLI surface)."""
    unit, div = ("GB", 1e9) if gb else ("MB", 1e6)

    def f(b):
        return f"{b / div:10.3f} {unit}"

    lines = [f"ledger total     {f(rec['ledger_total_bytes'])}",
             f"analytic floor   {f(rec['floor_bytes'])}"]
    for term, b in rec["floor_terms"].items():
        lines.append(f"  floor.{term:<22} {f(b)}")
    if "gap_bytes" in rec:
        lines.append(f"gap (total-floor){f(rec['gap_bytes'])}")
    for name, b in sorted(rec["bins"].items(), key=lambda kv: -kv[1]):
        lines.append(f"  bin.{name:<24} {f(b)}")
        for t in rec["bin_top"].get(name, [])[:3]:
            lines.append(f"      {t['name'][:40]:<42} {t['op'][:16]:<17}"
                         f"{f(t['bytes'])}")
    lines.append(f"uncategorized    {f(rec['uncategorized_bytes'])}")
    if rec.get("named_gap_frac") is not None:
        lines.append(f"named gap fraction  {rec['named_gap_frac']:.1%}")
    return "\n".join(lines)
