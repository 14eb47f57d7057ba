"""Benchmark suite: all five BASELINE.json configs + kernel/ETL probes.

Headline (the ONE required JSON line, printed last): ResNet-50 training
throughput, images/sec/chip, vs the reference's cuDNN fp16 V100 number
(~800 img/s at batch 128-256; fp32 is ~400). The line also carries, under
"configs", one record per secondary benchmark:

  lenet_mnist      LeNet MultiLayerNetwork (BASELINE config 1)
  samediff_mlp     SameDiff MLP whole-graph-XLA train steps (config 2)
  lstm_tbptt       GravesLSTM char-RNN truncated-BPTT (config 3)

  (configs 1-3 measure BOTH fit() — per-iteration host loss fetch, the
  reference's semantics — and the TPU-native fitSteps() k-step
  on-device loop; the faster variant is each record's headline, the
  other rides underneath)
  resnet50         the headline itself (config 4) + mfu/compile split
  grad_sharing     data-parallel psum trainer on the virtual 8-device CPU
                   mesh (config 5 — labeled: 1 physical chip, so this
                   measures the sharded-step path, not real ICI)
  attention        pallas flash vs fused-XLA vs blockwise scan, ms/call
                   at T in {512, 2048, 8192}
  prefetch         C++ ring-buffer ETL overlap: ResNet-50 fit() wall time
                   async vs sync feeding (runtime/prefetch.cpp)

Method notes: headline steps are the donated jitted train step chained
back-to-back; every timed window ends in block_until_ready. MFU uses
XLA's own cost_analysis() flop count over the chip's bf16 peak
(perfbench/peaks.py, the one table of peaks). fit()-based configs include the per-iteration
host loss fetch — the reference's fit() semantics pay the same sync.

Process model: the parent never imports jax, so it never holds the
chip; every leg runs in a child process, one at a time. Children place
JAX's persistent compilation cache with runtime/compile_cache.configure()
before their first compile.

On failure: prints a JSON line with an "error" key and exits nonzero.
A run in which any leg failed (or was skipped at the deadline) still
prints the full record, then exits nonzero.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_IMG_PER_SEC = 800.0  # nd4j-cuda + cuDNN fp16, V100, batch 128+

# DL4J_BENCH_SMOKE=1: tiny-shape CPU rehearsal of the ENTIRE bench
# pipeline (headline A/B legs, ledger wiring, partial banking,
# secondaries, final JSON) — integration bugs in bench plumbing have
# cost driver budgets in past rounds; this catches them without a TPU.
# The numbers it produces are MEANINGLESS and the output is watermarked.
SMOKE = os.environ.get("DL4J_BENCH_SMOKE") not in (None, "", "0")
if SMOKE:
    # inherited by every child, and read by jax when a child (which
    # imports this module first) starts its backend
    os.environ["JAX_PLATFORMS"] = "cpu"

#: first lines of every child process: place the persistent compilation
#: cache before the first compile (runtime/compile_cache.py)
_CHILD_PRELUDE = ("from deeplearning4j_tpu.runtime import compile_cache\n"
                  "compile_cache.configure()\n")

_DEADLINE = None  # set by __main__: absolute deadline (epoch s)
_HEADLINE = None  # banked resnet50 record: reported even if a later config hangs
_CONFIGS = {}     # banked secondary records, reported even on a hard stop


def bench_resnet50():
    """Measures the standard stem, then the space-to-depth stem (exact
    same function — MLPerf conv1 rewrite, parity-tested in
    tests/test_zoo.py::TestSpaceToDepthStem) and reports the faster of
    the two as the headline configuration.

    First runs the maxpool-backward A/B (seconds) and selects the faster
    implementation for the headline: the argmax rewrite targets TPU's
    select-and-scatter problem, but on backends where the stock path wins
    (CPU does: its scatter rewrite vectorizes) the headline must not
    carry a self-inflicted regression. Gradient parity between the two
    is pinned by tests/test_pooling_backward.py either way."""
    from deeplearning4j_tpu.ops import pooling as _pooling

    try:
        ab = bench_maxpool_backward()
        # explicit both ways: the library default (stock, measured best
        # on CPU and TPU v5e) must not silently stick if this backend's
        # A/B lands the other way
        _pooling._BACKWARD_IMPL = "argmax" if ab["speedup"] > 1.0 else "stock"
    except Exception as e:
        # the flagship number must survive an A/B failure: fall back to
        # whatever impl is configured and record the error
        ab = {"error": f"{type(e).__name__}: {e}"[:200]}
    ab["headline_uses"] = _pooling._BACKWARD_IMPL
    rec = _measure_resnet50("standard")
    rec["maxpool_backward_ab"] = ab
    # bank the standard-stem record across the process boundary NOW: if
    # the space-to-depth leg stalls and the parent kills this process,
    # the flagship measurement must survive (TimeoutExpired carries the
    # captured stdout-so-far)
    rec["stem"] = "standard"
    print("\nBENCHREC-PARTIAL " + json.dumps(rec), flush=True)
    try:
        s2d = _measure_resnet50("space_to_depth")
        if s2d["images_per_sec"] > rec["images_per_sec"]:
            s2d["stem_standard"] = {k: rec[k] for k in
                                    ("images_per_sec", "step_ms", "mfu")}
            s2d["stem"] = "space_to_depth"
            # the A/B verdict and the ledger (computed on the standard
            # leg) must survive the stem swap — the smoke rehearsal
            # caught both being dropped here
            s2d["maxpool_backward_ab"] = rec.get("maxpool_backward_ab")
            if "hbm_ledger" in rec:
                s2d["hbm_ledger"] = dict(rec["hbm_ledger"],
                                         note="computed on the "
                                              "standard-stem program")
            if "hbm_attribution" in rec:
                s2d["hbm_attribution"] = dict(
                    rec["hbm_attribution"],
                    note="computed on the standard-stem program")
            rec = s2d
        else:
            rec["stem_space_to_depth"] = {k: s2d[k] for k in
                                          ("images_per_sec", "step_ms",
                                           "mfu")}
    except Exception as e:
        rec["stem_space_to_depth"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    print("\nBENCHREC-PARTIAL " + json.dumps(rec), flush=True)
    # Third A/B: the round-6 dtype-tail policy. The library default
    # ("compute") keeps activation-scale BN/loss math in bf16 with fp32
    # only in fused reduce accumulators; the "wide" leg recompiles with
    # the legacy fp32 tails. cost_analysis bytes/step of both legs are
    # recorded — the byte cut is provable on CPU/SMOKE, the rate decides
    # the headline exactly like the other A/Bs.
    if os.environ.get("DL4J_TPU_TAIL_AB", "") != "off":
        try:
            wd = _measure_resnet50(rec["stem"], tail_mode="wide")
            sub = {k: wd[k] for k in ("images_per_sec", "step_ms", "mfu",
                                      "hbm_bytes_per_step")}
            rec["dtype_tail_ab"] = {
                "wide": sub,
                "compute": {k: rec[k] for k in
                            ("images_per_sec", "step_ms", "mfu",
                             "hbm_bytes_per_step")},
                "bytes_cut": round(wd["hbm_bytes_per_step"]
                                   - rec["hbm_bytes_per_step"], 1),
                "headline_uses": "compute",
            }
            if wd["images_per_sec"] > rec["images_per_sec"]:
                # self-protection: if the wide tail measures FASTER on
                # this backend the headline must not carry a
                # self-inflicted regression — flip, carry the banked
                # analyses, and say so
                for carry in ("maxpool_backward_ab", "stem",
                              "stem_space_to_depth", "stem_standard",
                              "hbm_ledger", "hbm_attribution",
                              "dtype_tail_ab"):
                    if carry in rec:
                        wd[carry] = rec[carry]
                wd["dtype_tail_ab"]["headline_uses"] = "wide"
                rec = wd
        except Exception as e:
            rec["dtype_tail_ab"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        print("\nBENCHREC-PARTIAL " + json.dumps(rec), flush=True)
    # Fourth A/B: checkpointPolicy="save_conv_outputs" (named-residual
    # remat — recompute BN/relu/add tails in the backward instead of
    # storing them; trades recompute FLOPs for HBM traffic). Same
    # self-protection as the maxpool A/B: the headline flips only if
    # the remat leg measures faster here.
    if os.environ.get("DL4J_TPU_REMAT", "") != "off":
        try:
            rm = _measure_resnet50(rec["stem"], remat=True)
            sub = {k: rm[k] for k in ("images_per_sec", "step_ms", "mfu",
                                      "hbm_bytes_per_step")}
            if rm["images_per_sec"] > rec["images_per_sec"]:
                rm["remat_off"] = {k: rec[k] for k in
                                   ("images_per_sec", "step_ms", "mfu",
                                    "hbm_bytes_per_step")}
                for carry in ("maxpool_backward_ab", "stem",
                              "stem_space_to_depth", "stem_standard",
                              "hbm_ledger", "hbm_attribution",
                              "dtype_tail_ab"):
                    if carry in rec:
                        rm[carry] = rec[carry]
                rm["headline_uses_remat"] = True
                return rm
            rec["remat_ab"] = sub
            rec["headline_uses_remat"] = False
        except Exception as e:
            rec["remat_ab"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    return rec


class _tail_mode:
    """Trace-time dtype-tail override for the BN/loss tails (ops/norm
    and nn/losses _TAIL_MODE): the round-6 dtype-policy A/B flips both
    to "wide" (the pre-round-6 fp32 activation-scale lowering) around
    one leg's lower+compile, then restores."""

    def __init__(self, mode):
        self.mode = mode

    def __enter__(self):
        from deeplearning4j_tpu.nn import losses as _lo
        from deeplearning4j_tpu.ops import norm as _no

        self._mods = (_lo, _no)
        self._old = (_lo._TAIL_MODE, _no._TAIL_MODE)
        if self.mode is not None:
            _lo._TAIL_MODE = _no._TAIL_MODE = self.mode
        return self

    def __exit__(self, *exc):
        self._mods[0]._TAIL_MODE, self._mods[1]._TAIL_MODE = self._old
        return False


def _mfu(flops_per_step, step_time_s):
    """Achieved FLOP/s over the chip's bf16 peak; 0.0 on the CPU
    platform, an error for an accelerator perfbench/peaks.py lacks."""
    import jax

    from perfbench.peaks import peaks_for

    dev = jax.devices()[0]
    if dev.platform == "cpu" or step_time_s <= 0:
        return 0.0
    return flops_per_step / step_time_s \
        / peaks_for(dev.device_kind)["flops_bf16"]


def _measure_resnet50(stem, remat=False, tail_mode=None):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.zoo import ResNet50
    from deeplearning4j_tpu.ndarray import DataType
    from deeplearning4j_tpu.nn import Nesterovs
    from deeplearning4j_tpu.util import profiler

    B, image, classes = (4, 32, 8) if SMOKE else (128, 224, 1000)
    net = ResNet50(numClasses=classes, inputShape=(3, image, image),
                   updater=Nesterovs(0.1, 0.9), stemMode=stem,
                   dataType=DataType.BFLOAT16, dataFormat="NHWC",
                   checkpointPolicy="save_conv_outputs" if remat
                   else None).init()
    rng = np.random.RandomState(0)
    # NHWC bf16 from the host: binds directly to the internal conv layout —
    # no 77 MB NCHW fp32 input param, no entry transpose+cast HLOs
    x = jax.device_put(jnp.asarray(rng.rand(B, image, image, 3),
                                   jnp.bfloat16))
    y = jax.device_put(jnp.asarray(
        np.eye(classes, dtype="float32")[rng.randint(0, classes, B)]))
    inputs = {"input": x}
    key = jax.random.key(0)
    it0 = jnp.asarray(0, jnp.int32)
    step = jax.jit(net._train_step, donate_argnums=(0, 1, 2))

    # ONE compile: the AOT executable serves cost_analysis AND the timing
    # loop (lower().compile() does not populate the jit dispatch cache, so
    # calling `step` afterwards would compile ResNet-50 a second time).
    # tail_mode (the dtype-policy A/B) is a trace-time switch, so it
    # wraps exactly the lower().
    t0 = time.perf_counter()
    with _tail_mode(tail_mode):
        lowered = step.lower(net._params, net._upd_states, net._states,
                             it0, inputs, [y], key, None, None)
        compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    cost = {"flops": float((ca or {}).get("flops", 0.0)),
            "bytes_accessed": float((ca or {}).get("bytes accessed", 0.0))}

    ledger_rec = None
    attribution_rec = None
    if stem == "standard" and not remat and tail_mode is None:
        # per-op HBM table + analytic roofline floor:
        # pure host-side HLO text parsing + abstract shape eval, cheap
        try:
            from deeplearning4j_tpu.util import hbm_ledger
            led = hbm_ledger.ledger_for_compiled(compiled, top=10)
            fl = hbm_ledger.train_step_floor(net, (B, image, image, 3),
                                             optimizer_slots=1)
            ledger_rec = {
                "ledger_total_bytes": led["total_bytes"],
                "by_opcode": {k: v for k, v in
                              list(led["by_opcode"].items())[:8]},
                "top": [{k: r[k] for k in ("name", "op", "bytes")}
                        for r in led["top"]],
                "floor_bytes": fl["floor_bytes"],
                "floor_terms": fl["terms"],
                "measured_over_floor": round(
                    cost["bytes_accessed"] / max(fl["floor_bytes"], 1), 3),
            }
        except Exception as e:
            ledger_rec = {"error": f"{type(e).__name__}: {e}"[:200]}
        # round-6 attribution: the per-category bill of the ledger-vs-
        # floor gap (hbm_ledger.attribute_ledger), plus the dtype-policy
        # audit — zero wide-float activation-scale buffers is the
        # acceptance bar for the bf16 tail fix
        try:
            from deeplearning4j_tpu.util import hbm_ledger
            att = hbm_ledger.attribute_ledger(
                compiled, net=net, x_shape=(B, image, image, 3),
                optimizer_slots=1, top=3)
            # model-policy audit on the PRE-OPT lowering (backend
            # passes widen things the model never asked for — see
            # hbm_ledger.pre_opt_hlo)
            att["wide_activation_buffers"] = len(
                hbm_ledger.audit_activation_dtypes(
                    hbm_ledger.pre_opt_hlo(lowered), net=net))
            attribution_rec = att
        except Exception as e:
            attribution_rec = {"error": f"{type(e).__name__}: {e}"[:200]}

    p, u, s = net._params, net._upd_states, net._states
    for it in range(1 if SMOKE else 2):  # warmup (compiled-step runs)
        p, u, s, loss = compiled(p, u, s, jnp.asarray(it, jnp.int32),
                                 inputs, [y], key, None, None)
    jax.block_until_ready((p, u, s, loss))

    iters = 2 if SMOKE else 20
    t0 = time.perf_counter()
    for it in range(iters):
        p, u, s, loss = compiled(p, u, s, jnp.asarray(2 + it, jnp.int32),
                                 inputs, [y], key, None, None)
    jax.block_until_ready((p, u, s, loss))
    dt = (time.perf_counter() - t0) / iters
    assert np.isfinite(float(loss))

    rec = {
        "images_per_sec": round(B / dt, 1),
        "step_ms": round(dt * 1e3, 2),
        "batch": B,
        "compile_s": round(compile_s, 1),
        "flops_per_step": cost["flops"],
        "hbm_bytes_per_step": cost["bytes_accessed"],
        "mfu": round(_mfu(cost["flops"], dt), 3),
    }
    if ledger_rec is not None:
        rec["hbm_ledger"] = ledger_rec
    if attribution_rec is not None:
        rec["hbm_attribution"] = attribution_rec
    return rec


def bench_lenet():
    import jax

    from deeplearning4j_tpu.zoo import LeNet
    from deeplearning4j_tpu.ndarray import DataType
    from deeplearning4j_tpu.data.iterators import MnistDataSetIterator
    from deeplearning4j_tpu.util import profiler

    B = 64
    net = LeNet(numClasses=10, inputShape=(1, 28, 28),
                dataType=DataType.BFLOAT16).init()
    it = MnistDataSetIterator(B, train=True)
    ds = it.next()
    net.fit(ds)  # compile
    t0 = time.perf_counter()
    n = 3 if SMOKE else 30
    for _ in range(n):
        net.fit(ds)
    dt = (time.perf_counter() - t0) / n
    import jax.numpy as jnp
    cost = profiler.compiled_cost(
        net._jit_train, net._params, net._upd_states, net._states,
        jnp.asarray(0, jnp.int32), ds.getFeatures().jax(),
        ds.getLabels().jax(), jax.random.key(0), None, None)
    # framework-native variant: fitSteps() k-step on-device loop, loss
    # fetched once per k — on small models the fit() number is mostly
    # the per-step host sync.
    # Same self-protection as the maxpool A/B: the faster variant is the
    # headline (XLA:CPU runs convs inside while-loops on a slow path, so
    # the loop must EARN the slot per backend).
    K = 3 if SMOKE else 30
    net.fitSteps(ds, numSteps=K)  # compile+warm the K-step loop
    t0 = time.perf_counter()
    net.fitSteps(ds, numSteps=K)
    dt_loop = (time.perf_counter() - t0) / K
    return _pick_faster(
        "images_per_sec",
        {"images_per_sec": round(B / dt_loop, 1),
         "step_ms": round(dt_loop * 1e3, 3), "batch": B,
         "mfu": round(_mfu(cost["flops"], dt_loop), 4),
         "loop_steps": K,
         "note": f"fitSteps(k={K}) on-device loop, one loss fetch per k"},
        {"images_per_sec": round(B / dt, 1),
         "step_ms": round(dt * 1e3, 3), "batch": B,
         "mfu": round(_mfu(cost["flops"], dt), 4),
         "note": "fit() incl. per-iteration loss fetch"})


def _pick_faster(rate_key, loop_rec, fit_rec):
    """Headline = the faster of the fitSteps()-loop and fit() variants;
    the other rides underneath, always both banked."""
    if loop_rec[rate_key] >= fit_rec[rate_key]:
        loop_rec["fit_semantics"] = fit_rec
        return loop_rec
    fit_rec["fitsteps_loop"] = loop_rec
    return fit_rec


def bench_samediff_mlp():
    import jax.numpy as jnp

    from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
    from deeplearning4j_tpu.nn import Adam

    rs = np.random.RandomState(7)
    B, F, H, O = 256, 784, 256, 10
    X = rs.rand(B, F).astype("float32")
    Yi = rs.randint(0, O, B)
    Y = np.eye(O, dtype="float32")[Yi]

    sd = SameDiff.create()
    x = sd.placeHolder("x", jnp.float32, B, F)
    y = sd.placeHolder("y", jnp.float32, B, O)
    w1 = sd.var("w1", (rs.randn(F, H) * 0.05).astype("float32"))
    b1 = sd.var("b1", np.zeros(H, dtype="float32"))
    w2 = sd.var("w2", (rs.randn(H, O) * 0.05).astype("float32"))
    b2 = sd.var("b2", np.zeros(O, dtype="float32"))
    h = sd.nn.relu(sd.nn.linear(x, w1, b1), name="h")
    logits = sd.nn.linear(h, w2, b2, name="logits")
    sd.loss.softmaxCrossEntropy(y, logits, name="loss")
    sd.setTrainingConfig(TrainingConfig.Builder()
                         .updater(Adam(learningRate=1e-3))
                         .dataSetFeatureMapping("x")
                         .dataSetLabelMapping("y").build())
    sd.fit(features=X, labels=Y, epochs=2)  # compile + warm
    n = 5 if SMOKE else 100
    t0 = time.perf_counter()
    hist = sd.fit(features=X, labels=Y, epochs=n)
    dt = (time.perf_counter() - t0) / n
    assert np.isfinite(hist[-1])
    # framework-native variant: the on-device k-step loop (one loss
    # fetch per k) — see bench_lenet for the selection rule
    K = 5 if SMOKE else 100
    sd.fitSteps(features=X, labels=Y, numSteps=K)  # compile+warm
    t0 = time.perf_counter()
    loss = sd.fitSteps(features=X, labels=Y, numSteps=K)
    dt_loop = (time.perf_counter() - t0) / K
    assert np.isfinite(loss)
    return _pick_faster(
        "steps_per_sec",
        {"steps_per_sec": round(1.0 / dt_loop, 1), "batch": B,
         "loop_steps": K,
         "note": f"fitSteps(k={K}) whole-graph on-device loop"},
        {"steps_per_sec": round(1.0 / dt, 1), "batch": B,
         "note": "fit() incl. per-iteration loss fetch"})


def bench_lstm_tbptt():
    from deeplearning4j_tpu.nn import (
        NeuralNetConfiguration, InputType, MultiLayerNetwork, GravesLSTM,
        RnnOutputLayer, Adam,
    )
    from deeplearning4j_tpu.nn.conf.builder import BackpropType
    from deeplearning4j_tpu.ndarray import DataType

    # vocab, batch, seq len, tbptt window
    V, B, T, L = (20, 4, 40, 20) if SMOKE else (77, 32, 80, 20)
    conf = (NeuralNetConfiguration.Builder()
            .seed(12).updater(Adam(2e-3)).dataType(DataType.BFLOAT16)
            .list()
            .layer(GravesLSTM(nOut=256))
            .layer(GravesLSTM(nOut=256))
            .layer(RnnOutputLayer(nOut=V, activation="softmax",
                                  lossFunction="mcxent"))
            .setInputType(InputType.recurrent(V, T))
            .backpropType(BackpropType.TruncatedBPTT).tBPTTLength(L)
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, V, (B, T))
    x = np.eye(V, dtype="float32")[ids].transpose(0, 2, 1)  # [B,V,T]
    y = np.eye(V, dtype="float32")[np.roll(ids, -1, 1)].transpose(0, 2, 1)
    net.fit(x, y)  # compile (4 tbptt windows)
    n = 2 if SMOKE else 10
    t0 = time.perf_counter()
    for _ in range(n):
        net.fit(x, y)
    dt = (time.perf_counter() - t0) / n
    assert np.isfinite(net.score())
    # framework-native variant: fitSteps runs the whole 4-window tbptt
    # sweep per step INSIDE one on-device loop — fit() pays a host loss
    # fetch per window; selection rule in bench_lenet
    K = 2 if SMOKE else 10
    net.fitSteps(x, y, numSteps=K)  # compile+warm
    t0 = time.perf_counter()
    net.fitSteps(x, y, numSteps=K)
    dt_loop = (time.perf_counter() - t0) / K
    assert np.isfinite(net.score())
    return _pick_faster(
        "chars_per_sec",
        {"chars_per_sec": round(B * T / dt_loop, 1),
         "seq_ms": round(dt_loop * 1e3, 2), "batch": B, "seq_len": T,
         "tbptt_len": L, "loop_steps": K,
         "note": f"fitSteps(k={K}): {T // L} tbptt windows/seq "
                 "on-device, one loss fetch per k seqs"},
        {"chars_per_sec": round(B * T / dt, 1),
         "seq_ms": round(dt * 1e3, 2), "batch": B, "seq_len": T,
         "tbptt_len": L, "note": "fit() incl. per-window loss fetch"})


def bench_attention():
    """Pallas flash vs fused XLA vs blockwise scan. Each timed as an
    on-device fori_loop (output fed back as q) so per-call dispatch
    doesn't mask kernel time."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_attention as _pa
    from deeplearning4j_tpu.ops.pallas_attention import _flash
    from deeplearning4j_tpu.ops.attention import (blockwise_attention,
                                                  dot_product_attention)

    if SMOKE:
        _pa._INTERPRET = True  # the CPU rehearsal has no Mosaic
    B, H, D = 4, 8, 64
    N = 2 if SMOKE else 8
    out = {}
    for T in ((64,) if SMOKE else (512, 2048, 8192)):
        rng = np.random.RandomState(0)
        mk = lambda: jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
        q, k, v = mk(), mk(), mk()

        def timed(fn):
            def loop(q, k, v):
                return jax.lax.fori_loop(
                    0, N, lambda i, qc: fn(qc, k, v).astype(qc.dtype), q)
            j = jax.jit(loop)
            jax.block_until_ready(j(q, k, v))  # compile+warm
            t0 = time.perf_counter()
            jax.block_until_ready(j(q, k, v))
            return (time.perf_counter() - t0) / N * 1e3

        def t_or_err(fn):
            # one leg failing (e.g. a pallas lowering error) must not
            # erase the other legs' numbers at this T; _failed() below
            # turns any such entry into the config's "error"
            try:
                return round(timed(fn), 3)
            except Exception as e:
                return f"{type(e).__name__}: {e}"[:200]

        rec = {
            "flash_ms": t_or_err(
                lambda q, k, v: _flash(q, k, v, True, 512, 512)),
            "fused_ms": t_or_err(
                lambda q, k, v: dot_product_attention(q, k, v, causal=True)),
            "blockwise_ms": t_or_err(
                lambda q, k, v: blockwise_attention(q, k, v, block_size=512,
                                                    causal=True)),
        }
        # dispatch audit: what the library would pick at this T, so the
        # banked table and _choose_impl can be cross-checked in one record
        from deeplearning4j_tpu.ops.pallas_attention import (_choose_impl,
                                                             _on_tpu)
        rec["dispatcher_picks"] = _choose_impl(T, on_tpu=_on_tpu())
        out[f"T{T}"] = rec
        # bank the table incrementally: the streaming parser overwrites
        # the config on each line, so a stall later in this function
        # still keeps every T measured so far
        print("\nBENCHREC-CONFIG " + json.dumps(
            {"name": "attention", "rec": dict(out, partial=True)}),
            flush=True)

    def _failed(out):
        # a kernel that raised is a failed leg: the record keeps every
        # number, "error" makes the run exit nonzero
        bad = [f"{t}.{k}: {v}" for t, rec in out.items()
               for k, v in rec.items() if isinstance(v, str)
               and k.endswith("_ms")]
        bad += [f"T2048.sweep.{k}: {v}" for k, v in
                out.get("T2048", {}).get("flash_block_sweep", {}).items()
                if isinstance(v, str)]
        if bad:
            out["error"] = "; ".join(bad)[:600]
        return out

    if SMOKE:  # sweep needs the pallas kernel; plumbing already covered
        return _failed(out)
    # block-size sweep at the T where flash measured SLOWER than the
    # blockwise scan — AFTER the three-T table so a failed sweep
    # cannot cost the main measurement: either a
    # tuned block pairing wins at 2048 and _BLOCKWISE_WINDOW can shrink,
    # or the window stands on a denser measurement
    T = 2048
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    sweep = {}
    for bq, bk in ((256, 256), (512, 256), (256, 512),
                   (1024, 512), (512, 1024)):
        try:
            sweep[f"bq{bq}_bk{bk}"] = round(timed(
                lambda q, k, v, bq=bq, bk=bk:
                _flash(q, k, v, True, bq, bk)), 3)
        except Exception as e:
            sweep[f"bq{bq}_bk{bk}"] = f"{type(e).__name__}: {e}"[:200]
        # incremental banking; partial=True so a line-grabbing reader
        # can't mistake an early cumulative record for the finished sweep
        print("\nBENCHREC-SWEEP " + json.dumps(
            {"T": T, "partial": True, "sweep": sweep}), flush=True)
    print("\nBENCHREC-SWEEP " + json.dumps({"T": T, "sweep": sweep}),
          flush=True)
    out["T2048"]["flash_block_sweep"] = sweep
    ms = [x for x in sweep.values() if isinstance(x, float)]
    if ms:
        out["T2048"]["flash_best_tuned_ms"] = min(ms)
    return _failed(out)


def bench_maxpool_backward():
    """Argmax-routed maxpool backward vs the stock select-and-scatter
    path, at the ResNet-50 stem-pool shape (a 206 MB consumer in the
    compiled step). Each timed as an on-device fori_loop so per-call
    dispatch doesn't mask kernel time."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pooling

    B, H, W, C = (4, 16, 16, 8) if SMOKE else (128, 112, 112, 64)
    N = 2 if SMOKE else 10
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(B, H, W, C), jnp.bfloat16)

    # bypass the DL4J_TPU_MAXPOOL_BWD dispatch: each leg must measure
    # ITS OWN implementation even when the env override is set (a
    # stock-vs-stock comparison recorded as an A/B would be worse than
    # no record)
    def argmax_pool(x, k, s, pad):
        return pooling._max_pool2d_argmax(
            x, pooling._pair(k), pooling._pair(s),
            (tuple(pad[0]), tuple(pad[1])))

    def timed(pool_fn):
        def g(x):
            return jax.grad(
                lambda t: jnp.sum(pool_fn(
                    t, (3, 3), (2, 2), ((1, 1), (1, 1))).astype(jnp.float32)
                ))(x)

        def loop(x):
            return jax.lax.fori_loop(0, N, lambda i, c: g(c).astype(c.dtype), x)

        j = jax.jit(loop)
        o = j(x)
        float(jnp.sum(o.astype(jnp.float32)))  # compile + warm, sync
        t0 = time.perf_counter()
        o = j(x)
        float(jnp.sum(o.astype(jnp.float32)))
        return (time.perf_counter() - t0) / N * 1e3

    argmax_ms = timed(argmax_pool)
    stock_ms = timed(pooling.max_pool2d_reference)
    return {"argmax_bwd_ms": round(argmax_ms, 3),
            "select_and_scatter_bwd_ms": round(stock_ms, 3),
            "speedup": round(stock_ms / argmax_ms, 3),
            "shape": [B, H, W, C],
            "note": "fwd+bwd of the ResNet stem pool (3x3/2 pad 1), bf16"}


class _HostETLIterator:
    """Host-side synthetic ETL: numpy generation + repeated
    normalization/augmentation passes, modelling the record-reader +
    transform work DataVec does on the JVM side upstream.
    (data/iterators.RandomDataSetIterator generates on-device, which is
    the wrong side of the bus for an ETL-overlap benchmark.)"""

    def __init__(self, numBatches, B, shape=(1, 28, 28), nOut=10,
                 etl_passes=4):
        self.nb, self.B = numBatches, B
        self.shape, self.nOut, self.passes = shape, nOut, etl_passes
        self.rng = np.random.RandomState(0)
        self.i = 0

    def reset(self):
        self.i = 0

    def hasNext(self):
        return self.i < self.nb

    def next(self, num=None):
        from deeplearning4j_tpu.data.dataset import DataSet

        self.i += 1
        x = self.rng.rand(self.B, *self.shape).astype("float32")
        # transform = a few LARGE BLAS matmuls (whole-image mixing): one
        # long GIL-released gemm per pass, as C++/JNI record readers
        # behave — chains of tiny numpy ufunc calls hold the GIL and
        # cannot overlap with the consumer thread no matter the queue
        D = int(np.prod(self.shape))
        if not hasattr(self, "_mix"):
            self._mix = (np.eye(D, dtype="float32") * 0.99
                         + (0.01 / D) * np.ones((D, D), dtype="float32"))
        flat = x.reshape(self.B, D)
        for _ in range(self.passes):
            flat = flat @ self._mix
        x = np.clip(flat.reshape(x.shape), -3.0, 3.0)
        y = np.eye(self.nOut, dtype="float32")[
            self.rng.randint(0, self.nOut, self.B)]
        return DataSet(np.ascontiguousarray(x), y)


def bench_prefetch():
    """LeNet fit() fed by the C++ ring-buffer prefetcher vs the same
    host-ETL iterator consumed synchronously — the ETL-overlap claim,
    measured where ETL is the bottleneck (its domain). Batches are kept
    small (800KB) so the host->device copy does not swamp the A/B."""
    from deeplearning4j_tpu.zoo import LeNet
    from deeplearning4j_tpu.ndarray import DataType
    from deeplearning4j_tpu.runtime.async_iterator import AsyncDataSetIterator

    B, NB = (64, 3) if SMOKE else (256, 20)
    net = LeNet(numClasses=10, inputShape=(1, 28, 28),
                dataType=DataType.BFLOAT16).init()

    etl = _HostETLIterator(2, B)
    t0 = time.perf_counter()
    while etl.hasNext():
        ds = etl.next()
    etl_s = (time.perf_counter() - t0) / 2
    net.fit(ds)  # compile/warm this batch shape

    def run(wrap):
        it = _HostETLIterator(NB, B)
        if wrap:
            it = AsyncDataSetIterator(it, queueSize=4)
        t0 = time.perf_counter()
        net.fit(it)
        return time.perf_counter() - t0

    sync_s = run(False)
    async_s = run(True)
    cores = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    # the loader drops to the pure-Python ring when the C++ one cannot
    # be built (no g++): the record says which one this rate is for
    from deeplearning4j_tpu.runtime.ringbuffer import native_lib

    ring = ("native C++ (runtime/prefetch.cpp)" if native_lib() is not None
            else "pure-Python fallback (native build unavailable)")
    if cores == 1:
        note = (f"ring prefetch, {ring}. This host has ONE core: producer "
                "thread and training loop cannot run concurrently, so the "
                "delta is pure queue overhead")
    else:
        note = (f"ring prefetch, {ring}, overlapping host ETL with LeNet "
                f"device steps on a {cores}-core host")
    return {"sync_s": round(sync_s, 2), "async_s": round(async_s, 2),
            "speedup": round(sync_s / async_s, 3), "ring_impl": ring,
            "host_etl_s_per_batch": round(etl_s, 3),
            "batches": NB, "batch": B, "host_cores": cores, "note": note}


def bench_fit_dataset():
    """fitDataSet(iterator, stepsPerSync=k) vs per-batch fit() over the
    SAME fresh-batch stream — the on-device multi-batch epoch loop:
    k batches staged as one stacked device buffer,
    one jitted fori_loop, one host sync per k batches, double-buffered
    H2D. Same self-protection as the fitSteps A/B: the faster variant is
    each record's headline, the other rides underneath — on backends
    where XLA's while-loop lowering loses (CPU convs), the loop must
    EARN the slot."""
    from deeplearning4j_tpu.zoo import LeNet
    from deeplearning4j_tpu.ndarray import DataType
    from deeplearning4j_tpu.data.iterators import RandomDataSetIterator

    B = 64
    NB = 4 if SMOKE else 32     # fresh batches per epoch
    K = 2 if SMOKE else 8       # stepsPerSync
    net = LeNet(numClasses=10, inputShape=(1, 28, 28),
                dataType=DataType.BFLOAT16).init()
    it = RandomDataSetIterator(NB, (B, 1, 28, 28), (B, 10))

    net.fit(it)                  # compile + warm the per-batch program
    t0 = time.perf_counter()
    net.fit(it)
    fit_s = time.perf_counter() - t0

    net.fitDataSet(it, stepsPerSync=K)   # compile + warm the k-loop
    t0 = time.perf_counter()
    net.fitDataSet(it, stepsPerSync=K)
    loop_s = time.perf_counter() - t0
    syncs = net._fit_dataset_syncs

    # round-6 layout A/B: host-canonical staging (library default —
    # the staged stack arrives NHWC + compute dtype, no per-step entry
    # transpose/convert in the loop program) vs the legacy "device"
    # staging. cost_analysis bytes of both loop executables are the
    # CPU-provable half; wall time picks the loop leg's headline.
    from deeplearning4j_tpu.nn import multilayer as _ml
    canon_rec = None
    try:
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.data.iterators import (iter_stacks,
                                                       stack_datasets)

        # the primary loop above ran under the AMBIENT staging mode
        # (host by default, device if DL4J_TPU_CANON_STAGING=device) —
        # the counter-leg must time the OPPOSITE mode, not
        # unconditionally "device", or an env-overridden run would A/B
        # device against itself and label the noise "host"
        ambient_host = _ml.canon_staging_on()
        old = _ml._CANON_STAGING
        try:
            _ml._CANON_STAGING = "device" if ambient_host else "host"
            net.fitDataSet(it, stepsPerSync=K)  # compile+warm counter-leg
            t0 = time.perf_counter()
            net.fitDataSet(it, stepsPerSync=K)
            other_s = time.perf_counter() - t0
        finally:
            _ml._CANON_STAGING = old
        host_s, dev_s = ((loop_s, other_s) if ambient_host
                         else (other_s, loop_s))

        def loop_cost_bytes(canon):
            jl = _ml.fit_dataset_jit(net, K, canonical=canon)  # cached
            it.reset()
            batches = next(iter_stacks(it, K))
            xs, ys, fms, lms = (net._stack_canonical(batches) if canon
                                else stack_datasets(batches))
            ca = jl.lower(net._params, net._upd_states, net._states,
                          jnp.asarray(0, jnp.int32), xs, ys, fms,
                          lms).compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            # per k-block program; /K for the per-step bill
            return float((ca or {}).get("bytes accessed", 0.0)) / K

        host_b = loop_cost_bytes(True)
        dev_b = loop_cost_bytes(False)
        canon_rec = {
            "host_bytes_per_step": round(host_b, 1),
            "device_bytes_per_step": round(dev_b, 1),
            "bytes_cut_per_step": round(dev_b - host_b, 1),
            "host_epoch_s": round(host_s, 3),
            "device_epoch_s": round(dev_s, 3),
            "headline_uses": "host" if host_s <= dev_s else "device",
        }
        if other_s < loop_s:
            loop_s = other_s  # self-protection: faster leg is the number
    except Exception as e:
        canon_rec = {"error": f"{type(e).__name__}: {e}"[:200]}

    loop_rec = {
        "images_per_sec": round(NB * B / loop_s, 1),
        "epoch_s": round(loop_s, 3), "batch": B, "batches": NB,
        "steps_per_sync": K, "host_syncs": syncs,
        "note": f"fitDataSet(stepsPerSync={K}): k-stack on-device "
                "loop, double-buffered staging, one loss fetch per "
                f"{K} fresh batches"}
    if canon_rec is not None:
        loop_rec["canon_staging_ab"] = canon_rec
    return _pick_faster(
        "images_per_sec",
        loop_rec,
        {"images_per_sec": round(NB * B / fit_s, 1),
         "epoch_s": round(fit_s, 3), "batch": B, "batches": NB,
         "note": "fit(iterator): per-batch transfer + loss fetch"})


def bench_int8_inference():
    """ResNet-50 batch inference img/s: weight-only int8 (nn/quantize)
    vs bf16, both as one AOT executable serving cost_analysis AND the
    timing loop. The attribution story is the weight term: int8 halves
    the resident/streamed weight bytes vs bf16 (param_bytes reported
    both ways) — on a bandwidth-bound chip that is the inference
    speedup ceiling. Top-1 agreement between the two legs is recorded
    so a quantization-quality regression cannot hide in a throughput
    table. SMOKE runs the full plumbing at tiny shapes."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ndarray import DataType
    from deeplearning4j_tpu.nn import Nesterovs
    from deeplearning4j_tpu.nn import quantize as _q
    from deeplearning4j_tpu.zoo import ResNet50

    B, image, classes = (4, 32, 8) if SMOKE else (128, 224, 1000)
    iters = 2 if SMOKE else 20
    net = ResNet50(numClasses=classes, inputShape=(3, image, image),
                   updater=Nesterovs(0.1, 0.9),
                   dataType=DataType.BFLOAT16, dataFormat="NHWC").init()
    rng = np.random.RandomState(0)
    x = jax.device_put(jnp.asarray(rng.rand(B, image, image, 3),
                                   jnp.bfloat16))
    inputs = {"input": x}
    states = net._strip_carries(net._states)

    def first(out):
        return out[0] if isinstance(out, (list, tuple)) else out

    def measure(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        nbytes = float((ca or {}).get("bytes accessed", 0.0))
        out = compiled(*args)
        jnp.asarray(first(out)).block_until_ready()  # compile+warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = compiled(*args)
        o = jnp.asarray(first(out))
        o.block_until_ready()
        return (time.perf_counter() - t0) / iters, nbytes, o

    # bf16 leg: params pre-cast to bf16 on host — inference has no fp32
    # master to protect, and the cast copy would pollute the weight-
    # traffic comparison
    p16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, net._params)
    bf16_s, bf16_b, o16 = measure(
        lambda p, xx: net._forward_infer(p, states, xx), p16, inputs)

    qp, sc = _q.quantize_params_int8(net._params)
    int8_s, int8_b, o8 = measure(
        lambda q, s, xx: net._forward_infer(
            _q.dequantize_params(q, s, net._compute_dtype), states, xx),
        qp, sc, inputs)

    agree = float(jnp.mean((jnp.argmax(o16.astype(jnp.float32), -1)
                            == jnp.argmax(o8.astype(jnp.float32), -1))
                           .astype(jnp.float32)))
    return {
        "bf16_img_per_sec": round(B / bf16_s, 1),
        "int8_img_per_sec": round(B / int8_s, 1),
        "speedup": round(bf16_s / int8_s, 3),
        "bf16_bytes_per_step": bf16_b,
        "int8_bytes_per_step": int8_b,
        "weight_bytes_bf16": _q.param_bytes(p16),
        "weight_bytes_int8": _q.param_bytes(qp),
        "top1_agreement": round(agree, 4),
        "batch": B,
        "note": ("weight-only int8 (symmetric per-channel absmax, "
                 "nn/quantize) vs bf16 ResNet-50 batch inference; "
                 "weight_bytes_* is the resident/streamed weight cut "
                 "the attribution prices"),
    }


def bench_resilience():
    """Overhead of the resilient training runtime (runtime/resilience.py):
    (a) the non-finite step guard — an all-finite reduction over loss +
    updated params and an on-device select, fused into the jitted step —
    vs the plain fused step, and (b) the retrying data path with
    FaultInjector IOErrors threaded through the iterator (near-zero
    backoff so the number measures machinery, not sleeps)."""
    from deeplearning4j_tpu.nn import (
        NeuralNetConfiguration, DenseLayer, OutputLayer, MultiLayerNetwork,
        Adam,
    )
    from deeplearning4j_tpu.data.dataset import DataSetIterator
    from deeplearning4j_tpu.runtime.resilience import (
        FaultInjector, ResilientFit, RetryPolicy,
    )

    B, H, epochs = (32, 64, 2) if SMOKE else (256, 1024, 15)
    rng = np.random.RandomState(0)
    x = rng.randn(B * 4, 32).astype("float32")
    y = np.eye(10, dtype="float32")[rng.randint(0, 10, B * 4)]
    steps = 4 * epochs

    def make():
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-3))
                .activation("relu").list()
                .layer(DenseLayer(nIn=32, nOut=H))
                .layer(OutputLayer(nOut=10, activation="softmax"))
                .build())
        return MultiLayerNetwork(conf).init()

    policy = RetryPolicy(maxRetries=4, initialDelay=1e-4, maxDelay=1e-3)

    net = make()
    net.fit(DataSetIterator(x, y, B))  # compile the plain step
    t0 = time.perf_counter()
    net.fit(DataSetIterator(x, y, B), epochs=epochs)
    plain_s = time.perf_counter() - t0

    net = make()
    rf = ResilientFit(net, retryPolicy=policy)
    rf.fit(DataSetIterator(x, y, B), epochs=1)  # compile the guarded step
    t0 = time.perf_counter()
    rf.fit(DataSetIterator(x, y, B), epochs=1 + epochs)
    guarded_s = time.perf_counter() - t0

    inj = FaultInjector(seed=3).randomIOFaults(steps, rate=0.25)
    net = make()
    rf = ResilientFit(net, retryPolicy=policy, injector=inj)
    rf.fit(inj.wrapIterator(DataSetIterator(x, y, B)), epochs=1)  # compile
    t0 = time.perf_counter()
    rf.fit(inj.wrapIterator(DataSetIterator(x, y, B)), epochs=1 + epochs)
    faulty_s = time.perf_counter() - t0
    faults = len([e for e in inj.events if e[0] == "data_fault"])

    return {
        "plain_steps_per_s": round(steps / plain_s, 2),
        "guarded_steps_per_s": round(steps / guarded_s, 2),
        "guard_overhead_pct": round(100.0 * (guarded_s - plain_s)
                                    / max(plain_s, 1e-9), 2),
        "faulty_steps_per_s": round(steps / faulty_s, 2),
        "injected_io_faults": faults,
        "steps": steps, "batch": B, "hidden": H,
        "note": ("non-finite guard select + retrying data path "
                 "(runtime/resilience.py) on a Dense MLP"),
    }


def bench_analysis():
    """Static-analyzer wall time over the zoo config corpus
    (deeplearning4j_tpu/analysis): the shape/dtype inference pass —
    including the eval_shape forward-agreement deep check on every
    layer — is the cost a pre-flight `--zoo`/validate=True gate adds
    BEFORE any pod slot is claimed, so it must stay host-cheap. Also
    times the purity lint over the package source, the pass-8
    thread-safety lint over the threaded tier (--concurrency), the
    pass-9 failure-path lint over the same tier (--failpaths), and
    the pass-7 collective-contract sweep (one TRACE per
    gradient-compression mode, zero compiles) — ISSUE 14/18."""
    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu

    from deeplearning4j_tpu.analysis import lint_paths
    from deeplearning4j_tpu.analysis import collectives as colan
    from deeplearning4j_tpu.analysis.cli import run_zoo
    from deeplearning4j_tpu.analysis.faults import lint_fault_paths
    from deeplearning4j_tpu.analysis.threads import lint_thread_paths

    t0 = time.perf_counter()
    results = run_zoo(batch_size=32)
    zoo_s = time.perf_counter() - t0
    errors = {n: len(r.errors) for n, r, _ in results if r.errors}
    per_model = {n: round(w * 1e3, 1) for n, r, w in results}
    layers = sum(len(r.layers) for _, r, _ in results)

    pkg = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "deeplearning4j_tpu")
    t0 = time.perf_counter()
    lint_rep = lint_paths([pkg])
    lint_s = time.perf_counter() - t0

    # pass 8: the thread-safety lint over the canonical threaded tier
    t0 = time.perf_counter()
    thr_rep = lint_thread_paths()
    threads_s = time.perf_counter() - t0

    # pass 9: the failure-path lint over the same tier (pure AST,
    # host-only like every lint here)
    t0 = time.perf_counter()
    flt_rep = lint_fault_paths()
    failpaths_s = time.perf_counter() - t0

    # pass 7: trace + contract-check every gradient_compression mode's
    # train step on a dp mesh (make_jaxpr only — no XLA compile)
    from deeplearning4j_tpu.nn import (
        DenseLayer, InputType, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer, Sgd,
    )
    from deeplearning4j_tpu.parallel import (DATA_AXIS, ParallelWrapper,
                                             build_mesh)

    n_dev = len(jax.devices())
    col_errors = {}
    col_s = None
    if n_dev > 1:
        mesh = build_mesh({DATA_AXIS: n_dev})
        conf = (NeuralNetConfiguration.Builder()
                .seed(7).updater(Sgd(0.05)).activation("tanh").list()
                .layer(DenseLayer(nOut=16))
                .layer(OutputLayer(nOut=4, activation="softmax"))
                .setInputType(InputType.feedForward(8)).build())
        rng = np.random.RandomState(0)
        x = rng.randn(2 * n_dev, 8).astype("float32")
        y = np.eye(4, dtype="float32")[rng.randint(0, 4, 2 * n_dev)]
        t0 = time.perf_counter()
        for mode in (None, "int8", "block_int8", "threshold"):
            net = MultiLayerNetwork(conf).init()
            pw = ParallelWrapper(net, mesh=mesh,
                                 gradient_compression=mode)
            pw._place_replicated()
            rep = colan.verify_program(
                pw.trainStep(), net._params, net._upd_states,
                net._states, jnp.asarray(0, jnp.int32),
                pw._shard_batch(jnp.asarray(x)),
                pw._shard_batch(jnp.asarray(y)),
                jax.random.key(0), None, None,
                mesh=mesh, dp=n_dev,
                contract=colan.compression_contract(
                    mode, len(jtu.tree_leaves(net._params))))
            if not rep.ok:
                col_errors[mode or "dense"] = len(rep.errors)
        col_s = round(time.perf_counter() - t0, 3)

    return {
        "zoo_models": len(results),
        "zoo_layers_checked": layers,
        "zoo_wall_s": round(zoo_s, 3),
        "zoo_ms_per_model": per_model,
        "zoo_errors": errors,  # must be {} — the corpus gate
        "lint_wall_s": round(lint_s, 3),
        "lint_violations": len(lint_rep.errors),
        "threads_wall_s": round(threads_s, 3),
        "threads_violations": len(thr_rep.errors),   # must be 0
        "threads_suppressed": len(thr_rep.suppressed),
        "failpaths_wall_s": round(failpaths_s, 3),
        "failpaths_violations": len(flt_rep.errors),   # must be 0
        "failpaths_suppressed": len(flt_rep.suppressed),
        "collectives_wall_s": col_s,   # None on a 1-device host
        "collectives_errors": col_errors,  # must be {} — contract gate
        "note": ("config shape/dtype validation (incl. eval_shape "
                 "forward-agreement deep check) over the 16-model zoo "
                 "corpus + purity lint of the package source + "
                 "thread-safety and failure-path lints of the "
                 "threaded tier + one-trace collective-contract "
                 "sweep over the compression modes; host-only, "
                 "no TPU"),
    }


def bench_analysis_parallel():
    """Partition-plan analyzer wall time (deeplearning4j_tpu/analysis/
    partitioning): the zoo corpus validated on both canonical meshes
    (dp4xtp2 and dp2xpp4) — the pre-flight cost a `--parallel` gate
    adds before a pod slot is claimed — plus the RetraceSentinel proof
    that the benchmark training step compiles exactly ONCE across a
    multi-step fit (the acceptance obligation: a retrace loop would
    eat the TPU window in compiles)."""
    import jax

    from deeplearning4j_tpu.analysis import RetraceSentinel
    from deeplearning4j_tpu.analysis.cli import (
        CANONICAL_MESHES, run_zoo_parallel,
    )
    from deeplearning4j_tpu.data.dataset import DataSetIterator
    from deeplearning4j_tpu.ndarray import DataType
    from deeplearning4j_tpu.zoo import LeNet

    t0 = time.perf_counter()
    results = run_zoo_parallel(list(CANONICAL_MESHES), batch_size=32)
    zoo_s = time.perf_counter() - t0
    errors = {n: len(r.errors) for n, r, _ in results if r.errors}
    per_subject = {n: round(w * 1e3, 1) for n, r, w in results}
    warn_codes = sorted({d.code for _, r, _ in results
                         for d in r.warnings})

    # RetraceSentinel: the training step must compile exactly once
    net = LeNet(numClasses=10, inputShape=(1, 28, 28),
                dataType=DataType.BFLOAT16).init()
    sentinel = RetraceSentinel(max_compiles=1).install(net, "train_step")
    B, steps = 32, 6
    rng = np.random.RandomState(0)
    x = rng.randn(B * steps, 1, 28, 28).astype("float32")
    y = np.eye(10, dtype="float32")[rng.randint(0, 10, B * steps)]
    t0 = time.perf_counter()
    net.fit(DataSetIterator(x, y, B))
    fit_s = time.perf_counter() - t0
    compiles = sentinel.compiles("train_step")

    return {
        "zoo_subjects": len(results),
        "meshes": [dict(m) for m in CANONICAL_MESHES],
        "zoo_wall_s": round(zoo_s, 3),
        "zoo_ms_per_subject": per_subject,
        "zoo_errors": errors,      # must be {} — the corpus gate
        "zoo_warning_codes": warn_codes,
        "train_step_compiles": compiles,   # must be 1
        "train_steps_run": steps,
        "fit_wall_s": round(fit_s, 3),
        "note": ("partition-plan validation (PAR01-06) of the zoo on "
                 "dp4xtp2 + dp2xpp4 + RetraceSentinel single-compile "
                 "proof over a LeNet fit; host-only, no TPU"),
    }


def bench_linalg():
    """Distributed-linalg workload tier (linalg/, docs/LINALG.md;
    ROADMAP item 4): sharded-vs-single-device GEMM GFLOP/s (ring SUMMA
    over the dpxtp mesh vs one plain jitted matmul on one device) and
    randomized-PCA wall time on a row-sharded tall matrix, with the
    static per-chip byte bill (linalg.plan) attached so the record is
    self-describing. On a single chip the mesh degenerates to
    one device — like grad_sharing, the sharded leg then certifies the
    collective path, not ICI perf; the virtual 8-device CPU twin of
    this measurement is tier-1's test_linalg."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import linalg
    from deeplearning4j_tpu.parallel import (DATA_AXIS, MODEL_AXIS,
                                             build_mesh)

    devs = jax.devices()
    n_dev = len(devs)
    tp = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    dp = max(1, n_dev // tp)
    # dims derived from the mesh so every sharded dim divides its axis
    # (the never-pad contract) on ANY device count, like the dryrun leg
    blk = dp * tp
    base = 512 if SMOKE else 2048
    dim = max(1, base // blk) * blk
    reps = 3 if SMOKE else 10
    rng = np.random.RandomState(0)
    A = rng.randn(dim, dim).astype("float32")
    B = rng.randn(dim, dim).astype("float32")
    flops = 2.0 * dim ** 3

    # single device: plain jitted matmul on device 0
    a0 = jax.device_put(jnp.asarray(A), devs[0])
    b0 = jax.device_put(jnp.asarray(B), devs[0])
    mm = jax.jit(jnp.matmul)
    t0 = time.perf_counter()
    jax.block_until_ready(mm(a0, b0))
    single_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = mm(a0, b0)
    jax.block_until_ready(out)
    single_s = (time.perf_counter() - t0) / reps

    # sharded: ring SUMMA over the dpxtp mesh
    axes = {DATA_AXIS: dp}
    if tp > 1:
        axes[MODEL_AXIS] = tp
    mesh = build_mesh(axes, devs[: dp * tp])
    dA = linalg.DistributedMatrix(A, mesh, row_axis=DATA_AXIS,
                                  col_axis=MODEL_AXIS if tp > 1 else None)
    dB = linalg.DistributedMatrix(B, mesh, row_axis=DATA_AXIS,
                                  col_axis=MODEL_AXIS if tp > 1 else None)
    t0 = time.perf_counter()
    jax.block_until_ready(linalg.matmul(dA, dB).jax())
    sharded_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        outs = linalg.matmul(dA, dB)
    jax.block_until_ready(outs.jax())
    sharded_s = (time.perf_counter() - t0) / reps
    np.testing.assert_allclose(outs.toNumpy(), A @ B, rtol=2e-3,
                               atol=2e-2)

    # randomized PCA on a row-sharded tall matrix vs host numpy SVD
    n_rows = (256 if SMOKE else 2048) * blk
    d_cols = 128 if SMOKE else 256
    k = 16
    X = (rng.randn(n_rows, 8) @ rng.randn(8, d_cols)
         + 0.01 * rng.randn(n_rows, d_cols)).astype("float32")
    dX = linalg.DistributedMatrix(X, build_mesh({DATA_AXIS: dp * tp},
                                                devs[: dp * tp]),
                                  row_axis=DATA_AXIS)
    t0 = time.perf_counter()
    comps, ev, mu = linalg.pca(dX, k, n_iter=2)
    jax.block_until_ready(ev)
    pca_first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    comps, ev, mu = linalg.pca(dX, k, n_iter=2)
    jax.block_until_ready(ev)
    pca_warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.linalg.svd(X - X.mean(0), full_matrices=False)
    numpy_svd_s = time.perf_counter() - t0

    bill = linalg.matmul_plan(dim, dim, dim, dict(mesh.shape),
                              col_axis=MODEL_AXIS if tp > 1 else None)
    return {
        "devices": n_dev, "mesh": dict(mesh.shape), "dim": dim,
        "gemm_single_gflops": round(flops / single_s / 1e9, 2),
        "gemm_sharded_gflops": round(flops / sharded_s / 1e9, 2),
        "gemm_single_compile_s": round(single_compile_s, 3),
        "gemm_sharded_compile_s": round(sharded_compile_s, 3),
        "gemm_per_chip_bytes": bill["per_chip_bytes"],
        "pca": {"rows": n_rows, "cols": d_cols, "k": k,
                "first_call_s": round(pca_first_s, 3),
                "warm_call_s": round(pca_warm_s, 3),
                "numpy_svd_s": round(numpy_svd_s, 3)},
        "note": ("ring-SUMMA GEMM GFLOP/s sharded vs single device + "
                 "randomized-PCA wall (warm = executable cached); "
                 "sharded leg certifies the collective path when only "
                 "one chip is live (cf. grad_sharing)"),
    }


_COMPILE_CACHE_CHILD = _CHILD_PRELUDE + r"""
import json, time
import numpy as np
from deeplearning4j_tpu.nn.multilayer import example_batch
from deeplearning4j_tpu.zoo import LeNet, SimpleCNN
import jax
B = %d
out = {"platform": jax.devices()[0].platform,
       "cache_dir": jax.config.jax_compilation_cache_dir, "subjects": {}}
for name, net in (
        ("lenet", LeNet(numClasses=10, inputShape=(1, 28, 28)).init()),
        ("simplecnn", SimpleCNN(numClasses=5,
                                inputShape=(3, 32, 32)).init())):
    x, y = example_batch(net, B)
    with compile_cache.PersistentCacheWatch() as w:
        t0 = time.perf_counter()
        net.precompile(batchSize=B, entries=("train",))
        net.fit(x, y)
        wall = time.perf_counter() - t0
    assert np.isfinite(net.score())
    out["subjects"][name] = {
        "precompile_plus_first_step_s": round(wall, 3),
        "persistent_hits": w.hits, "persistent_misses": w.misses}
print("CCACHEREC " + json.dumps(out), flush=True)
"""


def bench_compile_cache(timeout_s=300):
    """Persistent compilation cache across processes (runtime/
    compile_cache.py, docs/COMPILE.md): two fresh interpreters, one
    after the other, each precompile + first optimizer step of zoo
    LeNet and SimpleCNN on the DEFAULT platform. The parent holds no
    chip, so each child can take it. The second child must load every
    executable the first one stored (zero persistent-cache misses);
    the first is cold only if the cache directory did not already hold
    these programs — its hit/miss counts say which."""
    here = os.path.dirname(os.path.abspath(__file__))
    code = _COMPILE_CACHE_CHILD % (8 if SMOKE else 32)
    rec = {}
    for leg in ("first_process", "second_process"):
        try:
            r = subprocess.run([sys.executable, "-c", code],
                               capture_output=True, text=True, cwd=here,
                               timeout=timeout_s)
        except subprocess.TimeoutExpired:
            return {"error": f"{leg} exceeded {timeout_s}s", **rec}
        line = next((ln for ln in (r.stdout or "").splitlines()
                     if ln.startswith("CCACHEREC ")), None)
        if line is None:
            return {"error": f"{leg}: " + (r.stderr or r.stdout or
                    f"exit {r.returncode}").strip()[-300:], **rec}
        rec[leg] = json.loads(line[len("CCACHEREC "):])
    cold = [n for n, v in rec["second_process"]["subjects"].items()
            if v["persistent_misses"]]
    if cold:
        rec["error"] = f"second process recompiled {cold}"
    return rec


_AUTOTUNE_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
from deeplearning4j_tpu.runtime import autotune as at
out = {}
for subject in ("lenet", "resnet_block"):
    res = at.autotune_subject(subject, force=True)
    B = {"lenet": 64, "resnet_block": 32}[subject]
    w = res.wall or {}
    base_s = w.get("baseline_s")
    tuned_s = w.get("tuned_s")
    out[subject] = {
        "baseline_bytes_per_step": res.baseline_bytes,
        "tuned_bytes_per_step": res.tuned_bytes,
        "bytes_cut_frac": round(1.0 - res.tuned_bytes
                                / max(res.baseline_bytes, 1), 4),
        "knobs_changed": {p["knob"]: p["to"] for p in res.per_knob
                          if p["verdict"] == "adopted"},
        "images_per_sec_stock": round(B / base_s, 1) if base_s else None,
        "images_per_sec_tuned": round(B / tuned_s, 1) if tuned_s else None,
        "per_knob": res.per_knob,
    }
print("AUTOTUNEREC " + json.dumps(out), flush=True)
"""


def bench_autotune(timeout_s=420):
    """Autotune arbiter A/B (runtime/autotune.py, docs/AUTOTUNE.md):
    sweep the lowering knobs for the two attribution subjects and
    record tuned-vs-stock attributed bytes/step plus the measured
    step-rate delta. CPU-pinned subprocess BY DESIGN (grad_sharing's
    pattern — never touches the chip); the scoring lever being
    measured is attributed HBM bytes of the XLA:CPU-compiled step, and
    its rates are CPU wall clocks, not device metrics. The same sweep
    runs on a device via
    `python -m deeplearning4j_tpu.analysis --autotune all`."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("DL4J_TPU_AUTOTUNE_CACHE", None)  # force a fresh sweep
    try:
        r = subprocess.run([sys.executable, "-c", _AUTOTUNE_CHILD],
                           capture_output=True, text=True, cwd=here,
                           env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"autotune sweep exceeded {timeout_s}s"}
    line = next((ln for ln in (r.stdout or "").splitlines()
                 if ln.startswith("AUTOTUNEREC ")), None)
    if line is None:
        return {"error": (r.stderr or r.stdout or
                          f"exit {r.returncode}").strip()[-300:]}
    rec = json.loads(line[len("AUTOTUNEREC "):])
    rec["note"] = ("coordinate-descent knob sweep, loss-parity-gated, "
                   "scored by hbm_ledger attributed bytes (wall time "
                   "joins the score on a live device); winners persist "
                   "keyed like the AOT cache so every later process "
                   "starts tuned")
    return rec


_SERVING_FLEET_CHILD = r"""
import json, os, threading, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
    MultiLayerNetwork, DenseLayer, OutputLayer, Nesterovs)
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.runtime import aot
from deeplearning4j_tpu.serving import ModelHost, FleetRouter, loadgen
from deeplearning4j_tpu.serving.fleet import (scenario_diurnal_ramp,
    scenario_hot_model_skew, scenario_slow_client_storm)

aot._SESSION = aot.ExecutableCache()   # cold, memory-only
aot._SESSION_INIT = True
rec = {}
rng = np.random.RandomState(0)
mesh = build_mesh({"data": 1})

def mlp_conf(seed):
    return (NeuralNetConfiguration.Builder().seed(seed)
            .updater(Nesterovs(0.1, 0.9)).list()
            .layer(DenseLayer(nOut=16, activation="relu"))
            .layer(OutputLayer(nOut=4, activation="softmax",
                               lossFunction="mcxent"))
            .setInputType(InputType.feedForward(8)).build())

hot = MultiLayerNetwork(mlp_conf(7)).init()
cold = MultiLayerNetwork(mlp_conf(11)).init()

def mk_host():
    h = ModelHost(mesh=mesh)
    h.register("hot", hot, batchBuckets=(16, 64), queueLimit=1024,
               maxWaitMs=2.0)
    h.register("cold", cold, batchBuckets=(16, 64), queueLimit=1024,
               maxWaitMs=2.0)
    return h

def one_row(i):
    return rng.randn(1, 8).astype(np.float32)

def drive(router, n, rate, seed):
    return loadgen.run_open_loop(
        lambda x: router.submit("hot", x), lambda i: one_row(i),
        rate=rate, n_requests=n, seed=seed, max_clients=24)

# ---- fleet vs single replica (same open-loop rate) ----
single = FleetRouter([mk_host()])
single.submit("hot", one_row(0))
t0 = time.perf_counter()
for i in range(24):
    single.submit("hot", one_row(i))
rate = round(max(200.0, 8.0 * 24 / (time.perf_counter() - t0)), 1)
rs = drive(single, 192, rate, seed=0)
single.close()
fleet = FleetRouter([mk_host() for _ in range(3)])
with aot.CompileWatch() as watch:
    rb = drive(fleet, 192, rate, seed=1)
rec["fleet_vs_single"] = {
    "open_loop_rate_rps": rate,
    "replicas": 3,
    "single_rps": rs["requests_per_sec"],
    "single_p99_ms": rs.get("p99_ms"),
    "fleet_rps": rb["requests_per_sec"],
    "fleet_p50_ms": rb.get("p50_ms"),
    "fleet_p99_ms": rb.get("p99_ms"),
    "single_errors": rs["errors"], "fleet_errors": rb["errors"],
    "speedup_vs_single": round(rb["requests_per_sec"]
                               / rs["requests_per_sec"], 2)
    if rb["requests_per_sec"] and rs["requests_per_sec"] else None,
    "request_path_compiles": watch.misses,
    "note": ("all replicas share ONE CPU device: the CPU fleet ratio "
             "measures routing+queue-capacity overhead, not compute "
             "scale-out — a live multi-host window measures the "
             "latter"),
}

# ---- load scenarios (fleet-level rps/p99 + error classes) ----
rec["scenarios"] = {}
r = scenario_diurnal_ramp(lambda x: fleet.submit("hot", x), one_row,
                          base_rate=rate / 4, peak_rate=rate,
                          phases=3, requests_per_phase=48, seed=2)
rec["scenarios"]["diurnal_ramp"] = {k: r[k] for k in
    ("requests_per_sec", "p99_ms", "completed", "errors")}
r = scenario_hot_model_skew(
    lambda n: (lambda x: fleet.submit(n, x)), one_row,
    models=["hot", "cold"], hot_fraction=0.8, rate=rate / 2,
    n_requests=96, seed=3)
rec["scenarios"]["hot_model_skew"] = {
    "per_model": r["per_model"], "completed": r["completed"],
    "errors": r["errors"], "p99_ms": r.get("p99_ms")}
hedge_armed = []
def hedged_submit(x):
    # arm lazily so the scenario's BASE storm runs unhedged and only
    # the internal rerun pays (and records) the hedging path
    if not hedge_armed:
        fleet.set_hedge("hot", after_s=None)   # live-p95 driven
        hedge_armed.append(1)
    return fleet.submit("hot", x)
r = scenario_slow_client_storm(
    lambda x: fleet.submit("hot", x), lambda c, i: one_row(i),
    n_clients=24, requests_per_client=4, think_time_s=0.005, seed=4,
    hedged_submit=hedged_submit,
    hedge_stats=lambda: fleet._m_hedges.labels(model="hot").value)
fleet.set_hedge("hot", enabled=False)
rec["scenarios"]["slow_client_storm"] = {k: r[k] for k in
    ("requests_per_sec", "p99_ms", "completed", "errors", "clients",
     "hedged") if k in r}
rec["fleet_metrics"] = {
    "replicas": {rid: v["queue_depth"]
                 for rid, v in fleet.metrics_snapshot()["replicas"]
                 .items()},
}
fleet.close()

print("FLEETREC " + json.dumps(rec), flush=True)
"""


def bench_serving_fleet(timeout_s=420):
    """Multi-host serving fleet (serving/fleet.py, docs/SERVING.md):
    fleet requests/sec + p99 vs a single replica under the same
    open-loop rate, and the three load scenarios (diurnal ramp,
    hot-model skew, slow-client storm) with per-error-class counts.
    CPU-pinned subprocess BY DESIGN
    (grad_sharing's pattern — never touches the chip): the levers
    measured are host-side scheduling ratios."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    try:
        r = subprocess.run([sys.executable, "-c", _SERVING_FLEET_CHILD],
                           capture_output=True, text=True, cwd=here,
                           env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"serving_fleet exceeded {timeout_s}s"}
    line = next((ln for ln in (r.stdout or "").splitlines()
                 if ln.startswith("FLEETREC ")), None)
    if line is None:
        return {"error": (r.stderr or r.stdout or
                          f"exit {r.returncode}").strip()[-300:]}
    rec = json.loads(line[len("FLEETREC "):])
    rec["note"] = (
        "CPU rehearsal of the fleet tier: least-loaded routing over 3 "
        "in-process ModelHost replicas + the Orca-style "
        "iteration-level scheduler vs run-to-completion batching "
        "(slot table, per-step rebatch, mid-sequence refill) — the "
        ">=2x decode-throughput gate's bench twin (docs/SERVING.md)")
    return rec


_SERVING_CHAOS_CHILD = r"""
import json, os, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
    MultiLayerNetwork, DenseLayer, OutputLayer, Nesterovs)
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.runtime import aot
from deeplearning4j_tpu.runtime.chaos import ChaosPlan
from deeplearning4j_tpu.serving import ModelHost, FleetRouter

aot._SESSION = aot.ExecutableCache()   # cold, memory-only
aot._SESSION_INIT = True
rec = {}
rng = np.random.RandomState(0)
mesh = build_mesh({"data": 1})

conf = (NeuralNetConfiguration.Builder().seed(7)
        .updater(Nesterovs(0.1, 0.9)).list()
        .layer(DenseLayer(nOut=16, activation="relu"))
        .layer(OutputLayer(nOut=4, activation="softmax",
                           lossFunction="mcxent"))
        .setInputType(InputType.feedForward(8)).build())
net = MultiLayerNetwork(conf).init()

def mk_host():
    h = ModelHost(mesh=mesh)
    h.register("m", net, batchBuckets=(8,), queueLimit=256,
               maxWaitMs=0.1)
    return h

fleet = FleetRouter([mk_host() for _ in range(2)])
feats = rng.randn(1, 8).astype(np.float32)
for _ in range(30):                 # warm executables + code paths
    fleet.submit("m", feats)

def run_leg(n):
    lat, errors = [], {}
    for _ in range(n):
        t0 = time.perf_counter()
        try:
            fleet.submit("m", feats)
            lat.append(time.perf_counter() - t0)
        except Exception as e:
            k = type(e).__name__
            errors[k] = errors.get(k, 0) + 1
    lat = np.asarray(lat)
    return {"completed": int(lat.size), "errors": errors,
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3)}

# ---- disarmed vs ARMED-with-faults: p99 + per-error-class counts ----
fo = fleet._m_failover.labels(model="m", error="ChaosError")
rec["disarmed"] = run_leg(150)
plan = ChaosPlan(seed=0)
for at in (5, 45, 85, 125):      # sparse raises: failover absorbs each
    plan.raise_n("fleet.dispatch", at=at)
plan.random_slows("queue.dispatch", rate=0.05, window=200,
                  seconds=0.002)
with plan:
    rec["armed"] = run_leg(150)
rec["armed"]["injected"] = {
    "fleet.dispatch_raises": plan.fired("fleet.dispatch"),
    "queue.dispatch_slows": plan.fired("queue.dispatch")}
rec["armed"]["failovers_ChaosError"] = fo.value

# ---- the fast-path gate: armed-but-quiet <= 1.03x disarmed ----
quiet = ChaosPlan().raise_n("checkpoint.write", times=10**6)
def trial(n=120):
    s = []
    for _ in range(n):
        t0 = time.perf_counter()
        fleet.submit("m", feats)
        s.append(time.perf_counter() - t0)
    return float(np.median(s))
dis, arm = [], []
for _ in range(4):               # interleave trials against drift
    dis.append(trial())
    with quiet:
        arm.append(trial())
ratio = round(min(arm) / min(dis), 4)
rec["overhead"] = {"disarmed_median_ms": round(min(dis) * 1e3, 4),
                   "armed_quiet_median_ms": round(min(arm) * 1e3, 4),
                   "ratio": ratio, "gate": 1.03,
                   "pass": bool(ratio <= 1.03)}
fleet.close()
print("CHAOSREC " + json.dumps(rec), flush=True)
"""


def bench_serving_chaos(timeout_s=300):
    """Chaos harness cost + behavior on the serving path (runtime/
    chaos.py + serving/breaker.py, docs/RESILIENCE.md "Chaos
    harness"): p99 and per-error-class counts with and without an
    armed fault plan (the injected dispatch raises must be absorbed by
    budget-capped failover, so the armed leg still reports zero
    client-visible errors), plus the fast-path overhead gate — an
    armed-but-quiet plan must cost <= 1.03x the disarmed path
    (best-of-trials medians). CPU-pinned subprocess BY DESIGN
    (grad_sharing's pattern — never touches the chip): every lever
    measured is host-side."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    try:
        r = subprocess.run([sys.executable, "-c", _SERVING_CHAOS_CHILD],
                           capture_output=True, text=True, cwd=here,
                           env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"serving_chaos exceeded {timeout_s}s"}
    line = next((ln for ln in (r.stdout or "").splitlines()
                 if ln.startswith("CHAOSREC ")), None)
    if line is None:
        return {"error": (r.stderr or r.stdout or
                          f"exit {r.returncode}").strip()[-300:]}
    rec = json.loads(line[len("CHAOSREC "):])
    rec["note"] = (
        "CPU rehearsal of the chaos-hardened fleet: seeded dispatch "
        "faults absorbed by breaker/budget-capped failover with zero "
        "client-visible errors, and the armed-but-quiet harness within "
        "1.03x of disarmed (docs/RESILIENCE.md, docs/SERVING.md)")
    return rec


_SERVING_PAGED_CHILD = r"""
import json, os, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from deeplearning4j_tpu.nn.transformer import (CausalTransformerLM,
    dense_serial_trajectory)
from deeplearning4j_tpu.runtime import aot
from deeplearning4j_tpu.serving import (PagedSequenceScheduler,
    greedy_sampler, stream_rng)

aot._SESSION = aot.ExecutableCache()   # cold, memory-only
aot._SESSION_INIT = True
rec = {}
rng = np.random.default_rng(0)

S = 8                                    # slot bucket
m = CausalTransformerLM(vocab=257, d_model=64, n_heads=4, n_layers=2,
                        max_context=160, page_size=16, seed=0)
lens = (18, 34, 50, 66, 90, 96)          # ragged: 6/8 slots = 75%
n_new = 48
prompts = [rng.integers(0, m.vocab, size=n).tolist() for n in lens]
n_tok = len(lens) * n_new

# ---- paged leg: concurrent ragged generate over the page pool ----
sched = PagedSequenceScheduler(m, num_pages=96, slot_buckets=(S,),
                               start_thread=False, name="bench-paged")
sched.warm()                             # decode buckets + prefill hot
reqs = [sched.submit(p, max_new_tokens=n_new, wait=False)
        for p in prompts]
peak = 0
t0 = time.perf_counter()
while sched.poll():
    peak = max(peak, sched.cache.bytes_in_use())
paged_s = time.perf_counter() - t0
assert all(r.done and r.error is None for r in reqs)
dense_bytes = m.dense_cache_bytes(S)
rec["residency"] = {
    "paged_peak_bytes": int(peak),
    "dense_reserved_bytes": int(dense_bytes),
    "ratio": round(peak / dense_bytes, 4),
    "gate": 0.6, "pass": bool(peak <= 0.6 * dense_bytes),
    "live_slots": len(lens), "bucket": S,
    "prompt_lens": list(lens), "new_tokens": n_new,
    "occupancy": sched.occupancy_summary()}
rec["paged"] = {
    "tokens": n_tok, "wall_s": round(paged_s, 3),
    "decode_tokens_per_s": round(n_tok / paged_s, 1)}
sched.close()

# ---- dense twin: same prompts through the dense-slab serial path
# (one live row in a bucket-S slab — the residency model the paged
# pool replaces, and the serial decode-throughput baseline) ----
dense_serial_trajectory(m, prompts[0][:4], 2, greedy_sampler(),
                        stream_rng(0, 0), bucket=S)   # warm compiles
t0 = time.perf_counter()
for i, p in enumerate(prompts):
    dense_serial_trajectory(m, p, n_new, greedy_sampler(),
                            stream_rng(0, i), bucket=S)
dense_s = time.perf_counter() - t0
rec["dense_serial"] = {
    "tokens": n_tok, "wall_s": round(dense_s, 3),
    "decode_tokens_per_s": round(n_tok / dense_s, 1)}
rec["throughput_paged_vs_dense_serial"] = round(dense_s / paged_s, 3)
print("PAGEDREC " + json.dumps(rec), flush=True)
"""


def bench_serving_paged(timeout_s=300):
    """Paged KV-cache serving A/B (ISSUE 19, docs/SERVING.md "Paged KV
    cache"): HBM residency of the block-table page pool vs the dense
    twin's S x max_context reservation at >= 75% ragged occupancy
    (gate: paged peak <= 0.6x dense), plus aggregate decode
    tokens/sec — the continuously-batched paged scheduler against the
    serial dense-slab trajectory on the same prompts. CPU-pinned
    subprocess BY DESIGN (grad_sharing's pattern — never touches the
    chip): residency is computed from the pool accounting and the
    lever measured is scheduler-side."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    try:
        r = subprocess.run([sys.executable, "-c", _SERVING_PAGED_CHILD],
                           capture_output=True, text=True, cwd=here,
                           env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"serving_paged exceeded {timeout_s}s"}
    line = next((ln for ln in (r.stdout or "").splitlines()
                 if ln.startswith("PAGEDREC ")), None)
    if line is None:
        return {"error": (r.stderr or r.stdout or
                          f"exit {r.returncode}").strip()[-300:]}
    rec = json.loads(line[len("PAGEDREC "):])
    rec["note"] = (
        "CPU rehearsal of the paged KV tier: ragged transformer "
        "prompts at 75% slot occupancy hold only live-token pages "
        "(gate <= 0.6x the dense S x max_context reservation) while "
        "the interleaved prefill+decode scheduler sustains the serial "
        "dense path's throughput (docs/SERVING.md)")
    return rec


def bench_serving():
    """Continuous-batching model server (ROADMAP item 3, docs/SERVING.md):
    open-loop Poisson load through the request queue + dynamic
    micro-batcher vs the serial one-dispatch-per-request baseline, on a
    zoo model. CPU rehearsal BY DESIGN (not a SMOKE shortcut): the
    serving lever being measured is host-side dispatch amortization —
    one padded dispatch per micro-batch instead of one per request —
    and that ratio is the product; the mesh is pinned to a CPU
    device, so its rates are CPU wall clocks, not device metrics.
    Records requests/sec, p50/p99 latency, the
    batch-occupancy histogram, cold-vs-warm first-request latency, and
    the request-path compile count (must be 0 — the PR-7 bucket cache
    doing its job under load)."""
    import threading

    import jax

    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.runtime import aot
    from deeplearning4j_tpu.serving import ModelHost, loadgen
    from deeplearning4j_tpu.zoo import LeNet

    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       NeuralNetConfiguration, Nesterovs,
                                       OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    n_requests = 32 if SMOKE else 256
    rng = np.random.RandomState(0)
    cpu = jax.devices("cpu")

    def open_loop_vs_serial(host, name, pi_serial, one_row, n,
                            max_clients):
        """Both disciplines under the SAME limited-open-loop harness
        (pooled clients, saturating Poisson rate derived from measured
        serial capacity), so client-side thread costs cancel and the
        ratio isolates the micro-batching lever."""
        lock = threading.Lock()

        def serial_submit(x):
            with lock:          # one dispatch per request, serialized
                return pi_serial.output(x)

        serial_submit(one_row(0))
        host.submit(name, one_row(0))
        t0 = time.perf_counter()
        for i in range(24):
            serial_submit(one_row(i))
        est = 24 / (time.perf_counter() - t0)
        rate = round(max(200.0, 8.0 * est), 1)
        rs = loadgen.run_open_loop(serial_submit, one_row, rate=rate,
                                   n_requests=n, seed=0,
                                   max_clients=max_clients)
        with aot.CompileWatch() as watch:
            rb = loadgen.run_open_loop(
                lambda x: host.submit(name, x), one_row, rate=rate,
                n_requests=n, seed=1, max_clients=max_clients)
        occ = host.model(name).batcher.occupancy_summary()
        return {
            "open_loop_rate_rps": rate,
            "serial_rps": rs["requests_per_sec"],
            "serial_p99_ms": rs.get("p99_ms"),
            "batched_rps": rb["requests_per_sec"],
            "p50_ms": rb.get("p50_ms"),
            "p99_ms": rb.get("p99_ms"),
            "serial_errors": rs["errors"],
            "errors": rb["errors"],
            "speedup_vs_serial": round(
                rb["requests_per_sec"] / rs["requests_per_sec"], 2)
            if rb["requests_per_sec"] and rs["requests_per_sec"]
            else None,
            "batch_occupancy": occ,
            "request_path_compiles": watch.misses,
        }

    prev_cache, prev_init = aot._SESSION, aot._SESSION_INIT
    rec = {}
    try:
        # cold, memory-only session cache; _SESSION_INIT pinned so a
        # developer's exported DL4J_TPU_AOT_CACHE cannot re-arm the
        # disk tier mid-leg through session_cache()'s lazy env probe
        aot._SESSION = aot.ExecutableCache()
        aot._SESSION_INIT = True

        # ---- leg 1: zoo model (LeNet), single-device CPU rehearsal.
        # Per-row conv compute dominates a CPU dispatch, so the
        # speedup here is modest BY NATURE — this leg's products are
        # the latency distribution, the occupancy histogram, the
        # cold-vs-warm first request, and compiles == 0 under load.
        net = LeNet(numClasses=10).init()
        mesh1 = build_mesh({"data": 1}, devices=cpu[:1])
        buckets = (16, 64)
        shape = ParallelInference(net, mesh=mesh1,
                                  batchBuckets=buckets).example_shape()

        def lenet_row(i):
            return rng.randn(1, *shape).astype(np.float32)

        host_cold = ModelHost(mesh=mesh1)
        host_cold.register("lenet", net, batchBuckets=buckets,
                           precompile=False)
        t0 = time.perf_counter()
        host_cold.submit("lenet", lenet_row(0))
        cold_s = round(time.perf_counter() - t0, 3)
        host_cold.close()

        host = ModelHost(mesh=mesh1)
        t0 = time.perf_counter()
        host.register("lenet", net, batchBuckets=buckets, queueLimit=1024,
                      maxWaitMs=2.0)                    # precompiles
        host.submit("lenet", lenet_row(0))
        warm_s = round(time.perf_counter() - t0, 3)
        pi_serial = ParallelInference(net, mesh=mesh1, batchBuckets=(1,))
        pi_serial.precompile()
        rec["zoo_lenet"] = open_loop_vs_serial(
            host, "lenet", pi_serial, lenet_row, n_requests,
            max_clients=16)
        rec["zoo_lenet"]["cold_first_request_s"] = cold_s
        rec["zoo_lenet"]["warm_register_plus_first_request_s"] = warm_s
        host.close()

        # ---- leg 2: dispatch-bound amortization on the batch-dim-
        # sharded mesh — the regime the serving tier exists for (the
        # CPU rehearsal of an expensive dispatch is the multi-device
        # sharded one). This is the leg the tier-1 >=3x gate mirrors.
        n_mesh = min(8, max(1, len(cpu)))
        meshN = build_mesh({"data": n_mesh}, devices=cpu[:n_mesh])
        conf = (NeuralNetConfiguration.Builder().seed(7)
                .updater(Nesterovs(0.1, 0.9)).list()
                .layer(DenseLayer(nOut=16, activation="relu"))
                .layer(OutputLayer(nOut=4, activation="softmax",
                                   lossFunction="mcxent"))
                .setInputType(InputType.feedForward(8)).build())
        mlp = MultiLayerNetwork(conf).init()

        def mlp_row(i):
            return rng.randn(1, 8).astype(np.float32)

        host = ModelHost(mesh=meshN)
        host.register("mlp", mlp, batchBuckets=(8 * n_mesh, 16 * n_mesh),
                      queueLimit=1024, maxWaitMs=3.0)
        pi_serial = ParallelInference(mlp, mesh=meshN,
                                      batchBuckets=(n_mesh,))
        pi_serial.precompile()
        rec["amortization"] = open_loop_vs_serial(
            host, "mlp", pi_serial, mlp_row, n_requests, max_clients=24)
        rec["amortization"]["mesh_devices"] = n_mesh
        # the serving window's own telemetry view (queue/occupancy/
        # latency instruments this leg just exercised) rides the record
        rec["metrics_snapshot"] = host.metrics_snapshot()
        host.close()
    finally:
        aot._SESSION, aot._SESSION_INIT = prev_cache, prev_init
    rec["note"] = (
        "open-loop Poisson load (pooled clients) vs serial one-"
        "dispatch-per-request baseline, CPU rehearsal by design (host "
        "dispatch amortization is the product): zoo_lenet = zoo-model "
        "latency/occupancy/cold-start record (per-row conv compute "
        "bounds its CPU speedup), amortization = dispatch-bound "
        "batch-dim-sharded leg, the tier-1 >=3x gate's twin; "
        "request_path_compiles must be 0 in both (serving/, "
        "docs/SERVING.md)")
    return rec


# child body for _run_secondaries_subprocess (module constant so tests
# can drive the streaming parse with a stand-in child)
_SECONDARIES_CODE = (_CHILD_PRELUDE
                     + "import bench\nbench.bench_tpu_secondaries()\n")

SECONDARY_CONFIGS = [("attention", "bench_attention"),
                     ("lenet_mnist", "bench_lenet"),
                     ("samediff_mlp", "bench_samediff_mlp"),
                     ("lstm_tbptt", "bench_lstm_tbptt"),
                     ("fit_dataset", "bench_fit_dataset"),
                     ("int8_inference", "bench_int8_inference"),
                     ("prefetch", "bench_prefetch"),
                     ("resilience", "bench_resilience"),
                     ("analysis", "bench_analysis"),
                     ("analysis_parallel", "bench_analysis_parallel"),
                     ("serving", "bench_serving"),
                     ("linalg", "bench_linalg")]
# attention runs FIRST: if the group is cut short, the flash-vs-fused
# table is already banked


def bench_tpu_secondaries():
    """Every secondary TPU config in ONE interpreter (one process holds
    the chip; backend start-up is paid once), each banked with a
    BENCHREC-CONFIG line the moment it lands. A config that raises is
    recorded and the rest still run, but the process then exits
    nonzero: a leg that was meant to run on the chip and failed is a
    failed run."""
    out = {}
    for name, fn_name in SECONDARY_CONFIGS:
        fn = globals()[fn_name]
        try:
            rec = fn()
        except Exception as e:  # recorded; the exit code carries it
            rec = {"error": f"{type(e).__name__}: {e}"[:300]}
        out[name] = rec
        print("\nBENCHREC-CONFIG " + json.dumps({"name": name, "rec": rec}),
              flush=True)
    if any("error" in rec for rec in out.values()):
        sys.exit(1)
    return out


def _run_secondaries_subprocess(budget, deadline_capped=False, sink=None):
    """-> configs dict parsed from BENCHREC-CONFIG lines. The child's
    stdout is STREAMED and each record lands in `sink` (default: the
    module-global _CONFIGS) the moment its line arrives — so a watchdog
    hard stop mid-group still reports every finished config in the
    error record. Configs the group never reached get an explanatory
    error entry (`deadline_capped` distinguishes a short
    deadline-driven budget from a hung group)."""
    import tempfile
    import threading

    names = [n for n, _ in SECONDARY_CONFIGS]
    sink = _CONFIGS if sink is None else sink
    here = os.path.dirname(os.path.abspath(__file__))
    code = _SECONDARIES_CODE
    out = {}

    def _drain(stream):
        for line in stream:  # EOF ends the thread
            if line.startswith("BENCHREC-CONFIG "):
                try:
                    rec = json.loads(line[len("BENCHREC-CONFIG "):])
                    name, new = rec["name"], rec["rec"]
                    prev = out.get(name)
                    # an error-only final record must not ERASE partial
                    # measurements this config already banked (e.g. the
                    # attention T-table lines) — attach, don't replace
                    if (isinstance(prev, dict) and prev
                            and "error" not in prev
                            and set(new) == {"error"}):
                        new = dict(prev, error_after_partial=new["error"])
                    out[name] = new
                    sink[name] = new
                except (json.JSONDecodeError, KeyError):
                    pass

    try:
        with tempfile.TemporaryFile(mode="w+") as errf:
            proc = subprocess.Popen([sys.executable, "-c", code],
                                    stdout=subprocess.PIPE, stderr=errf,
                                    text=True, cwd=here)
            reader = threading.Thread(target=_drain, args=(proc.stdout,),
                                      daemon=True)
            reader.start()
            try:
                rc = proc.wait(timeout=budget)
                reader.join(timeout=10)
                errf.seek(0)
                tail_err = errf.read().strip()[-200:]
                fallback = ({"error": f"group exited rc={rc}: {tail_err}"}
                            if rc != 0 else {"error": "no record emitted"})
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                reader.join(timeout=10)
                fallback = {"error": f"group timeout at {budget}s (killed; "
                            + ("bench deadline reached)" if deadline_capped
                               else "hung?)")}
    except Exception as e:
        fallback = {"error": f"{type(e).__name__}: {e}"[:300]}
    for n in names:
        out.setdefault(n, dict(fallback))
    return out


def bench_grad_sharing_virtual(timeout_s=600):
    """BASELINE config 5 on the virtual 8-device CPU mesh (one physical
    chip available — this certifies the sharded psum path, not ICI
    perf), plus the round-7 replicated-vs-ZeRO-sharded weight-update
    A/B: same model/updater/data through ParallelWrapper with
    weight_update='replicated' vs 'sharded' (reduce-scatter -> 1/dp
    shard update -> all-gather, Xu et al.), with trajectory parity and
    the measured per-chip updater-state bytes recorded. Wall-clock here
    is CPU time — the A/B certifies correctness + the state-bytes cut;
    the bandwidth win is priced by dp_weight_update_bytes and the
    hbm_ledger weight_update bin (tests/test_zero_sharding.py gates
    it)."""
    code = r"""
import json, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.tree_util as jtu
import numpy as np
from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
    MultiLayerNetwork, DenseLayer, OutputLayer, Adam)
from deeplearning4j_tpu.parallel import (SharedTrainingMaster,
    ParallelWrapper, data_parallel_mesh)
def make_conf():
    return (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
            .activation("relu").list()
            .layer(DenseLayer(nOut=512)).layer(DenseLayer(nOut=256))
            .layer(OutputLayer(nOut=10, activation="softmax"))
            .setInputType(InputType.feedForward(784)).build())
net = MultiLayerNetwork(make_conf()).init()
rng = np.random.RandomState(0)
x = rng.randn(512, 784).astype("float32")
y = np.eye(10, dtype="float32")[rng.randint(0, 10, 512)]
m = SharedTrainingMaster(net)
m.fit(x, y)
t0 = time.perf_counter(); n = 30
for _ in range(n):
    m.fit(x, y)
dt = (time.perf_counter() - t0) / n
rec = {"cpu_mesh_steps_per_sec": round(1/dt, 1),
       "global_batch": 512,
       "devices": len(jax.devices()),
       "compression": m.gradient_compression}
# analytic per-replica bytes-on-wire of this trainer's gradient
# reduction (ISSUE 11: the headline's bytes_on_wire field)
from deeplearning4j_tpu.parallel import compressed_wire_bytes
G = sum(int(np.prod(l.shape)) * 4
        for l in jtu.tree_leaves(net._params))
rec["bytes_on_wire"] = compressed_wire_bytes(
    G, len(jax.devices()), m.gradient_compression)
# ---- replicated-vs-sharded weight update A/B ----
ab = {}
nets = {}
for mode in ("replicated", "sharded"):
    wnet = MultiLayerNetwork(make_conf()).init()
    pw = ParallelWrapper(wnet, mesh=data_parallel_mesh(),
                         weight_update=mode)
    pw.fit(x, y)
    t0 = time.perf_counter(); n = 20
    for _ in range(n):
        pw.fit(x, y)
    sps = n / (time.perf_counter() - t0)
    entry = {"steps_per_sec": round(sps, 1)}
    if mode == "sharded":
        entry["opt_state_bytes_per_chip"] = \
            pw._zero.per_chip_state_bytes(wnet._upd_states)
    else:
        entry["opt_state_bytes_per_chip"] = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jtu.tree_leaves(wnet._upd_states))
    ab[mode] = entry
    nets[mode] = wnet
maxdiff = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
              for a, b in zip(jtu.tree_leaves(nets["replicated"]._params),
                              jtu.tree_leaves(nets["sharded"]._params)))
ab["parity_maxdiff"] = maxdiff
ab["state_bytes_cut"] = (ab["replicated"]["opt_state_bytes_per_chip"]
                         - ab["sharded"]["opt_state_bytes_per_chip"])
rec["weight_update_ab"] = ab
# house selection: the trajectory is parity-gated, so the mode is a
# pure perf/memory knob — report which one this backend would pick
rec["weight_update_mode"] = (
    "sharded" if ab["sharded"]["steps_per_sec"]
    >= ab["replicated"]["steps_per_sec"] else "replicated")
print(json.dumps(rec))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    # no persistent cache for the CPU-mesh leg: XLA:CPU AOT reloads emit
    # spurious machine-feature warnings that would pollute the stderr
    # tail this function reports on failure
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout_s, env=env,
                       cwd=os.path.dirname(os.path.abspath(__file__)))
    if r.returncode != 0:
        return {"error": (r.stderr or r.stdout)[-400:]}
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    rec["note"] = ("CORRECTNESS CERTIFICATION of the sharded psum path "
                   "on a virtual 8-device CPU mesh — wall-clock is CPU "
                   "time, NOT a TPU rate; int8 allreduce by default")
    # ISSUE 11: bytes-on-wire vs convergence parity per compression
    # mode, swept over virtual-mesh sizes (each size its own forced-
    # device-count subprocess); a size that times out records an error
    # without losing the banked 8-device record
    rec["compression_sweep"] = {
        str(nd): _grad_compression_sweep_one(nd, max(60, timeout_s // 4))
        for nd in (8, 32, 128, 512)}
    # ISSUE 20 headline: the 2-hop-vs-flat wire ratio at the dp128 wall
    # (min over the swept hierarchical group sizes)
    try:
        m128 = rec["compression_sweep"]["128"]["modes"]
        hier = min((v for k, v in m128.items()
                    if k.startswith("hierarchical")),
                   key=lambda v: v["wire_bytes_per_step"])
        rec["hier_vs_flat_wire_ratio_dp128"] = \
            hier["wire_ratio_vs_flat_threshold"]
    except (KeyError, ValueError):
        rec["hier_vs_flat_wire_ratio_dp128"] = None
    return rec


def _grad_compression_sweep_one(n_devices, timeout_s):
    """One virtual-mesh size of the grad_sharing compression sweep:
    train the same tiny MLP under every gradient_compression mode for a
    few steps and record final loss (parity vs dense), steps/sec and
    the analytic per-replica bytes-on-wire per step. Hierarchical 2-hop
    legs run at every group size in {4, 8} that divides the mesh with
    >= 2 groups, billing both hops and recording the ratio vs flat
    threshold (ISSUE 20: the crossover moves past dp128); at >= 512
    devices the dense-quantized modes are skipped (recorded in
    skipped_modes) to keep the compile budget bounded."""
    code = r"""
import json, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.tree_util as jtu
import numpy as np
from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
    MultiLayerNetwork, DenseLayer, OutputLayer, Sgd)
from deeplearning4j_tpu.parallel import (ParallelWrapper,
    data_parallel_mesh, compressed_wire_bytes)
ndev = len(jax.devices())
def make_conf():
    return (NeuralNetConfiguration.Builder().seed(3).updater(Sgd(0.1))
            .activation("tanh").list()
            .layer(DenseLayer(nOut=64)).layer(DenseLayer(nOut=32))
            .layer(OutputLayer(nOut=8, activation="softmax"))
            .setInputType(InputType.feedForward(32)).build())
rng = np.random.RandomState(0)
B = 2 * ndev
yi = rng.randint(0, 8, B)
x = (np.eye(8)[yi] @ rng.randn(8, 32) + 0.1 * rng.randn(B, 32)) \
    .astype("float32")
y = np.eye(8, dtype="float32")[yi]
mesh = data_parallel_mesh()
out = {"devices": ndev, "modes": {}}
# the sparse legs run the ADAPTIVE tau loop (threshold=1e-1 seed,
# targetSparsity=0.1): sign updates move at the tau scale, so a fixed
# tiny tau cannot hold the 25% parity gate in a 16-step run while the
# adaptive loop keeps tau at the live gradient scale (wire bytes are
# capacity-bound either way)
sparse_kw = {"threshold": 1e-1, "targetSparsity": 0.1}
legs = [(None, {}), ("int8", {}), ("block_int8", {}),
        ("threshold", dict(sparse_kw))]
if ndev >= 512:
    # bound the big-mesh leg: the dense-quantized modes carry no new
    # crossover information past dp128 and dominate compile time here
    out["skipped_modes"] = ["int8", "block_int8"]
    legs = [l for l in legs if l[0] not in ("int8", "block_int8")]
for gsz in (4, 8):
    if ndev % gsz == 0 and ndev // gsz >= 2:
        legs.append(("hierarchical_g%d" % gsz,
                     dict(sparse_kw, compressionGroupSize=gsz)))
dense_loss = None
flat_wire = None
for label, kw in legs:
    mode = ("hierarchical" if label and label.startswith("hierarchical")
            else label)
    net = MultiLayerNetwork(make_conf()).init()
    pw = ParallelWrapper(net, mesh=mesh, gradient_compression=mode, **kw)
    pw.fit(x, y)  # compile
    t0 = time.perf_counter(); steps = 16
    for _ in range(steps):
        pw.fit(x, y)
    sps = steps / (time.perf_counter() - t0)
    G = sum(int(np.prod(l.shape)) * 4
            for l in jtu.tree_leaves(net._params))
    wire = compressed_wire_bytes(
        G, ndev, mode, capacity=pw.encoding_capacity,
        group_size=pw.compression_group if mode == "hierarchical" else None,
        intra_mode=pw.intra_compression)
    loss = float(net.score())
    if mode is None:
        dense_loss = loss
    if mode == "threshold":
        flat_wire = wire["wire_bytes"]
    entry = {
        "final_loss": round(loss, 5),
        "loss_delta_vs_dense": None if dense_loss is None
        else round(loss - dense_loss, 5),
        "parity_25pct": None if dense_loss is None
        else bool(abs(loss - dense_loss) <= 0.25 * abs(dense_loss)),
        "steps_per_sec": round(sps, 2),
        "wire_bytes_per_step": wire["wire_bytes"],
        "wire_ratio_vs_dense": wire["ratio"],
    }
    if mode == "hierarchical":
        entry["hop_wire_bytes"] = {"intra": wire["intra_wire_bytes"],
                                   "leader": wire["leader_wire_bytes"]}
        entry["groups"] = wire["groups"]
        entry["wire_ratio_vs_flat_threshold"] = wire["vs_flat_threshold"]
        if flat_wire is not None:
            entry["beats_flat_threshold"] = bool(
                wire["wire_bytes"] < flat_wire)
        entry["beats_dense"] = bool(
            wire["wire_bytes"] < wire["dense_wire_bytes"])
    out["modes"][label or "dense"] = entry
print(json.dumps(out))
"""
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n_devices}"])
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=timeout_s, env=env,
                           cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"error": f"timeout at {timeout_s}s "
                         f"({n_devices} virtual devices)"}
    if r.returncode != 0:
        return {"error": (r.stderr or r.stdout)[-300:]}
    return json.loads(r.stdout.strip().splitlines()[-1])


def _run_config_subprocess(fn_name, budget):
    """Run one bench function in its own interpreter with a hard kill.

    Two reasons: (a) only a process kill bounds a leg that hangs inside
    a C call; (b) the parent process never initializes JAX, so
    sequential children don't contend for the chip (libtpu is
    process-exclusive — two processes can't hold it at once).
    """
    here = os.path.dirname(os.path.abspath(__file__))
    code = (_CHILD_PRELUDE + f"import json, bench\n"
            f"print('\\nBENCHREC ' + json.dumps(bench.{fn_name}()))")
    def _best_record(stdout, prefer_final=True):
        for tag in (["BENCHREC ", "BENCHREC-PARTIAL "] if prefer_final
                    else ["BENCHREC-PARTIAL "]):
            recs = [l for l in (stdout or "").splitlines()
                    if l.startswith(tag)]
            if recs:
                return json.loads(recs[-1][len(tag):])
        return None

    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=budget, cwd=here)
        rec = _best_record(r.stdout) if r.returncode == 0 else None
        if rec is not None:
            return rec
        return {"error": ((r.stderr or r.stdout or "")
                          .strip()[-300:] or "no output")}
    except subprocess.TimeoutExpired as e:
        out = e.stdout
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        rec = _best_record(out, prefer_final=False)
        if rec is not None:  # a banked partial survived the kill
            rec["note"] = (rec.get("note", "") +
                           f" [partial: killed at {budget}s]").strip()
            return rec
        return {"error": f"timeout: config exceeded {budget}s (killed)"}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"[:300]}


def _budget(cap):
    if _DEADLINE is None:
        return cap
    return min(cap, int(_DEADLINE - time.time()) - 30)


#: legs that run as their own child of the parent, after the chip
#: group: (record name, function, budget cap in seconds). grad_sharing,
#: autotune and the serving_* legs pin their child to the CPU by design
#: (virtual meshes, host-side scheduling ratios); compile_cache runs on
#: the default platform.
PARENT_LEGS = [("grad_sharing", "bench_grad_sharing_virtual", 600),
               ("autotune", "bench_autotune", 420),
               ("serving_fleet", "bench_serving_fleet", 420),
               ("serving_chaos", "bench_serving_chaos", 300),
               ("serving_paged", "bench_serving_paged", 300),
               ("compile_cache", "bench_compile_cache", 300)]


def main():
    # headline FIRST (own subprocess, like every TPU config): if a later
    # leg fails the flagship number is already banked and _error_line
    # reports it
    global _HEADLINE
    # 780 s: the headline carries THREE ResNet-50 compiles (standard
    # stem, space-to-depth stem, remat-policy A/B); the
    # BENCHREC-PARTIAL banking still protects earlier legs on a kill
    headline = _run_config_subprocess("bench_resnet50", _budget(780))
    if "error" in headline:
        raise RuntimeError(f"headline failed: {headline['error']}")
    _HEADLINE = headline

    configs = _CONFIGS  # module-global, shared with _error_line
    budget = _budget(600)
    if budget < 60:  # leave headroom to emit the final line
        for name, _ in SECONDARY_CONFIGS:
            configs[name] = {"error": "skipped: bench deadline reached"}
    else:
        configs.update(_run_secondaries_subprocess(
            budget, deadline_capped=budget < 600))
    for name, fn_name, cap in PARENT_LEGS:
        budget = _budget(cap + 30)
        if budget < 45:
            configs[name] = {"error": "skipped: bench deadline reached"}
            continue
        try:
            configs[name] = globals()[fn_name](min(budget, cap))
        except Exception as e:  # recorded; the exit code carries it
            configs[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
    img_per_sec = headline["images_per_sec"]
    line = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": img_per_sec,
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
        "mfu": headline["mfu"],
        # XLA compile seconds the headline's cold step paid (the
        # compile_cache leg measures what a warm-started process pays
        # instead)
        "compile_s": headline.get("compile_s"),
        # which weight-update path the dp trainers ran this round (the
        # round-7 ZeRO A/B lives in configs.grad_sharing.weight_update_ab;
        # the single-chip headline itself has no dp update to shard) —
        # recorded at top level so the record is attributable
        "weight_update_mode": configs.get("grad_sharing", {}).get(
            "weight_update_mode", "replicated"),
        # compressed gradient collectives (round 11, ISSUE 11): which
        # compression mode the gradient-sharing trainer ran and its
        # analytic per-replica bytes-on-wire per step — top level so
        # the record stays attributable; None/absent when the
        # grad_sharing leg errored
        "compression_mode": configs.get("grad_sharing", {}).get(
            "compression"),
        "bytes_on_wire": configs.get("grad_sharing", {}).get(
            "bytes_on_wire"),
        # the system's SECOND measured product surface (round 8): what
        # the continuous-batching model server sustains under open-loop
        # load, and its amortization factor over one-dispatch-per-
        # request — top level so the record is attributable
        "serving_rps": configs.get("serving", {}).get(
            "amortization", {}).get("batched_rps"),
        "serving_speedup_vs_serial": configs.get("serving", {}).get(
            "amortization", {}).get("speedup_vs_serial"),
        # the fleet (round 15, ISSUE 15): fleet-level requests/sec
        # over 3 replicas — top level so the record is attributable;
        # None when the CPU-pinned leg errored
        "fleet_rps": configs.get("serving_fleet", {}).get(
            "fleet_vs_single", {}).get("fleet_rps"),
        # chaos harness (round 16, ISSUE 16): armed-but-quiet fault
        # seams over the disarmed serving path (gate <= 1.03x) — top
        # level so the record is attributable; None when the
        # CPU-pinned leg errored
        "chaos_overhead_x": configs.get("serving_chaos", {}).get(
            "overhead", {}).get("ratio"),
        # paged KV cache (round 19, ISSUE 19): peak page-pool bytes
        # over the dense S x max_context reservation at 75% ragged
        # occupancy (gate <= 0.6x) and the paged scheduler's aggregate
        # decode tokens/sec — top level so the record is attributable;
        # None when the CPU-pinned leg errored
        "kv_paged_residency_x": configs.get("serving_paged", {}).get(
            "residency", {}).get("ratio"),
        "kv_paged_decode_tokens_per_s": configs.get(
            "serving_paged", {}).get("paged", {}).get(
            "decode_tokens_per_s"),
        # autotune arbiter (round 12, ISSUE 12): tuned-vs-stock
        # attributed bytes/step for the LeNet b64 attribution subject
        # (the ratcheted-ceiling gate's measurement) and the measured
        # step-rate delta — top level so the record is attributable;
        # None when the CPU-pinned leg errored
        "autotune_bytes_cut": configs.get("autotune", {}).get(
            "lenet", {}).get("bytes_cut_frac"),
        "autotune_imgs_per_sec_delta": (
            lambda a: round(a["images_per_sec_tuned"]
                            - a["images_per_sec_stock"], 1)
            if a.get("images_per_sec_tuned")
            and a.get("images_per_sec_stock") else None)(
            configs.get("autotune", {}).get("lenet", {})),
        "resnet50": headline,
        "configs": configs,
    }
    failed = sorted(n for n, rec in configs.items()
                    if isinstance(rec, dict) and "error" in rec)
    if failed:
        line["failed_legs"] = failed
    if SMOKE:  # watermark loudly: tiny-shape CPU rehearsal, not a result
        line.update(value=0.0, vs_baseline=0.0,
                    smoke="DL4J_BENCH_SMOKE tiny-shape CPU rehearsal — "
                          "plumbing check only, NOT a measurement")
    print(json.dumps(line))
    if failed:  # the record is complete, the run is not
        sys.exit(1)


def _error_line(msg):
    rec = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": 0.0, "unit": "images/sec", "vs_baseline": 0.0,
        "error": msg[:500],
    }
    if _HEADLINE is not None:  # the flagship number was banked before the failure
        rec["value"] = _HEADLINE["images_per_sec"]
        rec["vs_baseline"] = round(rec["value"] / BASELINE_IMG_PER_SEC, 3)
        rec["mfu"] = _HEADLINE.get("mfu")
        rec["resnet50"] = _HEADLINE
    if _CONFIGS:  # every secondary that finished before the failure
        rec["configs"] = _CONFIGS
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    # every leg is a child with its own hard timeout; the deadline only
    # decides how much budget the remaining legs get
    _DEADLINE = time.time() + 1500
    try:
        main()
    except Exception as e:
        _error_line(f"{type(e).__name__}: {e}")
        sys.exit(1)
