"""HBM gap attribution as a pre-flight diagnostic (CLI ``--attribution``).

The round-5 ledger said the flagship step moves 3.95x its analytic
floor; the round-6 attribution engine (util/hbm_ledger.attribute_ledger)
names the gap per category. This module is the HOST-ONLY diagnostic
surface: compile a known model's train step on the local backend (CPU in
CI — the classifier reads HLO text, no TPU needed), classify every
charged byte into floor vs overhead bins, and print the bill plus the
dtype-policy audit. Unlike the other analysis passes this one pays a
real XLA compile (seconds for LeNet, longer for deep subjects), which is
why it is a named subject list rather than the whole zoo corpus.

    python -m deeplearning4j_tpu.analysis --attribution lenet
    python -m deeplearning4j_tpu.analysis --attribution resnet_block --json
"""

from __future__ import annotations

import numpy as np

#: CLI subjects: name -> builder returning (net, x_shape). Kept small
#: and shallow on purpose — each costs a host XLA compile.
SUBJECTS = ("lenet", "resnet_block")


def build_subject(name, batch_size=32):
    """-> (net, x_shape, optimizer_slots) for one attribution subject,
    bf16 compute + NHWC (the flagship regime the bins are tuned for)."""
    from deeplearning4j_tpu.ndarray import DataType

    if name == "lenet":
        from deeplearning4j_tpu.zoo import LeNet

        net = LeNet(numClasses=10, inputShape=(1, 28, 28),
                    dataType=DataType.BFLOAT16).init()
        return net, (batch_size, 1, 28, 28), 1
    if name == "resnet_block":
        # one bottleneck-style residual stack: conv/BN/relu x3 + dense
        # head — the ResNet-50 traffic pattern at a CI-compilable size
        from deeplearning4j_tpu.nn import (
            BatchNormalization, ConvolutionLayer, GlobalPoolingLayer,
            InputType, MultiLayerNetwork, NeuralNetConfiguration,
            Nesterovs, OutputLayer,
        )

        # conv/BN/relu x2 + global pool + small head: the ResNet-50
        # traffic shape (activations >> any single param leaf, so the
        # activation-scale threshold bites exactly as on the flagship)
        # at a CI-compilable size
        conf = (NeuralNetConfiguration.Builder()
                .seed(12).updater(Nesterovs(0.1, 0.9))
                .dataType(DataType.BFLOAT16)
                .activation("relu").list()
                .layer(ConvolutionLayer(nOut=32, kernelSize=(3, 3),
                                        stride=(1, 1), padding=(1, 1)))
                .layer(BatchNormalization())
                .layer(ConvolutionLayer(nOut=32, kernelSize=(3, 3),
                                        stride=(1, 1), padding=(1, 1)))
                .layer(BatchNormalization())
                .layer(GlobalPoolingLayer())
                .layer(OutputLayer(nOut=10, activation="softmax",
                                   lossFunction="mcxent"))
                .setInputType(InputType.convolutional(16, 16, 3))
                .build())
        net = MultiLayerNetwork(conf).init()
        return net, (batch_size, 3, 16, 16), 1
    raise ValueError(
        f"unknown attribution subject {name!r}; pick one of {SUBJECTS}")


def lower_train_step(net, x_shape, n_classes=10):
    """Lower (not yet compile) one canonical train step of `net` on the
    HOST backend (shared by the CLI and tests/test_hbm_attribution.py —
    one definition of 'the step the bytes gate pins'). The Lowered
    serves both audiences: pre_opt_hlo(lowered) for the model-policy
    dtype audit, lowered.compile() for the ledger/attribution/cost
    oracle."""
    import jax
    import jax.numpy as jnp

    B = x_shape[0]
    x = jnp.ones(x_shape, jnp.float32)
    y = jnp.asarray(np.eye(n_classes, dtype="float32")[
        np.zeros(B, dtype=int)])
    key = jax.random.key(0)
    it0 = jnp.asarray(0, jnp.int32)
    # fresh-identity wrapper, NOT jax.jit(net._train_step): jax's
    # global trace cache keys on bound-method EQUALITY, so once this
    # net has fit() at this signature, a plain re-jit would serve the
    # cached jaxpr and silently ignore any module-global knob flipped
    # since (the autotune arbiter's whole sweep would read
    # "identical") — a fresh lambda per call can never alias and the
    # lowering always reflects the LIVE knob state
    step = lambda *a: net._train_step(*a)  # noqa: E731
    if hasattr(net, "layers"):  # MultiLayerNetwork
        return jax.jit(step).lower(
            net._params, net._upd_states, net._states, it0, x, y, key,
            None, None)
    inputs = {net.conf.networkInputs[0]: x}
    return jax.jit(step).lower(
        net._params, net._upd_states, net._states, it0, inputs, [y],
        key, None, None)


def _aot_key(net, x_shape, n_classes):
    """Cache key for one attribution subject's compiled step: the
    lowering is fully determined by (net config, example shapes,
    ambient toggles), all of which the key embeds."""
    from deeplearning4j_tpu.runtime import aot

    try:
        fp = aot.network_fingerprint(net)
    except Exception:
        return None
    return aot.cache_key(fp, "hbm_train_step",
                         f"x={tuple(x_shape)},n={int(n_classes)}")


def compile_train_step(net, x_shape, n_classes=10, cache=None,
                       lowered=None):
    """lower + compile one canonical train step, through the AOT
    executable cache when one is active (runtime.aot) — a second
    ``--attribution`` run (or the bytes-gate tests after the CLI) gets
    the executable warm instead of re-paying the subject's XLA compile.
    Pass `lowered` when the caller already
    lowered (e.g. for the pre-opt dtype audit) — this is the ONE
    definition of the subject key/entry, so every compile of a subject
    lands on the same cache slot."""
    from deeplearning4j_tpu.runtime import aot

    if lowered is None:
        lowered = lower_train_step(net, x_shape, n_classes)
    return aot.compile_lowered(lowered,
                               key=_aot_key(net, x_shape, n_classes),
                               cache=cache, entry="hbm_train_step")


def precompile_subject(subject, batch_size=32, cache=None):
    """CLI ``--precompile``: populate the AOT executable cache for one
    subject — the network's own train/inference entry points (what the
    trainers and the serving tier dispatch to) plus the attribution
    lowering — and report per-key compile-or-load seconds. Returns
    {entry: {key, status, seconds}}."""
    from deeplearning4j_tpu.runtime import aot

    cache = cache if cache is not None else \
        (aot.session_cache() or aot.enable())
    net, x_shape, _slots = build_subject(subject, batch_size)
    report = dict(net.precompile(batchSize=batch_size, cache=cache))
    key = _aot_key(net, x_shape, 10)
    before = cache.stats["misses"]
    import time as _time

    t0 = _time.perf_counter()
    compile_train_step(net, x_shape, cache=cache)
    status = "cold" if cache.stats["misses"] > before else "warm"
    report["hbm_train_step"] = {
        "key": key, "status": status,
        "seconds": round(cache.seconds.get(
            key, _time.perf_counter() - t0), 3)}
    return report


def run_attribution(subject="lenet", batch_size=32):
    """Compile + attribute one subject; -> (record, formatted_text).
    The record is attribute_ledger()'s dict plus the audit offender
    count and the XLA cost_analysis total for cross-checking."""
    from deeplearning4j_tpu.util import hbm_ledger

    net, x_shape, slots = build_subject(subject, batch_size)
    lowered = lower_train_step(net, x_shape)
    compiled = compile_train_step(net, x_shape, lowered=lowered)
    rec = hbm_ledger.attribute_ledger(compiled, net=net, x_shape=x_shape,
                                      optimizer_slots=slots)
    rec["subject"] = subject
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    rec["cost_analysis_bytes"] = float((ca or {}).get("bytes accessed",
                                                      0.0))
    # model-policy audit on the PRE-OPTIMIZATION lowering: backend
    # passes add widenings the model never asked for (XLA:CPU runs
    # convs in fp32) that must not fail a dtype-policy gate
    audit = hbm_ledger.audit_activation_dtypes(
        hbm_ledger.pre_opt_hlo(lowered), net=net)
    rec["wide_activation_buffers"] = len(audit)
    text = (f"subject: {subject} (batch {batch_size}, bf16, host "
            "backend)\n" + hbm_ledger.format_attribution(rec, gb=False)
            + f"\ndtype audit      {len(audit)} wide-float "
              "activation-scale buffer(s) in the model lowering")
    if audit:
        for r in audit[:5]:
            text += (f"\n    {r['name'][:40]:<42} {r['op'][:16]:<17}"
                     f"{r['dtype']:<6}{r['bytes']} B")
    return rec, text
