"""Traffic kind "train_fit": `net.fit(iterator)` over batches that already
live on the device.

Set-up builds ONE network object from the seed, drives it through its
first steps by the window's own call and feed (`fit()` over the same
iterator class, one batch a call so that the loss and the optimizer's
state can be read between steps) and hands that same object to the
window. The iterator's `next()` returns a prebuilt DataSet: no RNG, no
host-to-device copy, no numpy. After the window the program's state is
freed and the configuration's plain reference follows the same first
steps from the same seed on the same batches.
"""

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.harness import log


class ResidentIterator:
    """DL4J's DataSetIterator protocol over prebuilt device DataSets,
    cycled until `max_steps` or until `seconds` have passed since
    reset()."""

    def __init__(self, pool, spans, start=0, max_steps=None, seconds=None):
        self.pool, self.spans = pool, spans
        self.cursor, self.max_steps, self.seconds = start, max_steps, seconds
        self.served = 0
        self.t0 = self.deadline = None

    def reset(self):
        self.t0 = time.perf_counter()
        self.deadline = None if self.seconds is None \
            else self.t0 + self.seconds

    def hasNext(self):
        if self.max_steps is not None and self.served >= self.max_steps:
            return False
        return self.deadline is None or time.perf_counter() < self.deadline

    def next(self, num=None):
        t = time.perf_counter()
        ds = self.pool[self.cursor % len(self.pool)]
        self.cursor += 1
        self.served += 1
        self.spans.append(("bench.iterator_next", t,
                           time.perf_counter() - t))
        return ds


@functools.partial(jax.jit, static_argnames=("n", "batch", "image",
                                             "channels", "classes"))
def make_batches(key, *, n, batch, image, channels, classes):
    """n batches of seeded images (NHWC, bfloat16, rows all different) and
    one-hot labels, made on the device in one call."""
    out = []
    for i in range(n):
        kx, ky = jax.random.split(jax.random.fold_in(key, i))
        x = jax.random.normal(kx, (batch, image, image, channels),
                              jnp.bfloat16)
        y = jax.nn.one_hot(jax.random.randint(ky, (batch,), 0, classes),
                           classes, dtype=jnp.bfloat16)
        out.append((x, y))
    return out


def build_net(config, seed):
    from deeplearning4j_tpu import nn, zoo
    from deeplearning4j_tpu.ndarray import DataType

    m, t = config["model"], config["training"]
    updater = getattr(nn, t["updater"])(t["learning_rate"], t["momentum"])
    return getattr(zoo, m["zoo"])(
        numClasses=m["classes"],
        inputShape=(m["channels"], m["image"], m["image"]),
        updater=updater, dataType=getattr(DataType, m["dtype"]),
        dataFormat=m["data_format"], seed=seed).init()


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


def _flat(tree):
    return {f"{layer}_{k}": float(v) for layer, leaves in tree.items()
            if leaves for k, v in leaves.items()}


def first_steps(net, pool, spans, n, lr):
    """The program's first n steps through fit(iterator). Returns the
    losses, the first gradient's leaf norms as the optimizer got it
    (Nesterov from zero momentum: v1 = -lr g, so |g| = |v1| / lr) and the
    leaf norms of the parameters' change after n steps."""
    p0 = jax.tree.map(jnp.copy, net._params)
    losses, grad1 = [], None
    for i in range(n):
        net.fit(ResidentIterator(pool, spans, start=i, max_steps=1))
        losses.append(float(net.score()))
        if i == 0:
            grad1 = {k: v / lr for k, v in
                     _flat(_norms(net._upd_states)).items()}
    change = _flat(_diff_norms(net._params, p0))
    return {"loss": losses, "grad1": grad1, "change": change}


def setup(run):
    from deeplearning4j_tpu.data.dataset import DataSet

    m, t = run.config["model"], run.config["training"]
    tr = run.traffic
    seed = run.subseed("model")
    net = build_net(run.config, seed)
    raw = make_batches(
        jax.random.key(run.subseed("data")), n=tr["pool_batches"],
        batch=t["batch"], image=m["image"], channels=m["channels"],
        classes=m["classes"])
    pool = [DataSet(x, y) for x, y in raw]
    prog = first_steps(net, pool, run.spans, tr["first_steps"],
                       t["learning_rate"])
    log(f"first steps' losses {prog['loss']}")
    return {"net": net, "pool": pool, "raw": raw, "seed": seed,
            "program": prog}


def window(run, state, go):
    from deeplearning4j_tpu.runtime import telemetry

    net = state["net"]
    it = ResidentIterator(state["pool"], run.spans,
                          start=run.traffic["first_steps"],
                          seconds=run.seconds)
    telemetry.get_registry().trace.clear()
    del run.spans[:]
    go()
    net.fit(it)
    jax.block_until_ready(net._params)
    t1 = time.perf_counter()
    wall = t1 - it.t0
    batch = run.config["training"]["batch"]
    ok = np.isfinite(net.score())
    log(f"window: {it.served} steps of {batch} in {wall:.3f}s, last loss "
        f"{net.score():.4f}")
    return {"t0": it.t0, "t1": t1, "wall_s": wall, "steps": it.served,
            "attempted": it.served, "failed": 0 if ok else it.served,
            "metrics": {"train_samples_per_s_per_chip":
                        it.served * batch / wall / len(run.devices)}}


def compare(prog, ref, tiny_grad=1e-3):
    """The numbers of "How correct is decided" for a training cell:
    each step's loss, the first gradient's norm and the parameters'
    change, leaf by leaf: the gap between the program's norm and the
    reference's over the reference's norm of that leaf or of the median
    leaf, whichever is larger; its worst and its median over the leaves.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's are left out of the change."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        out[f"loss{i + 1}_rel"] = abs(a - b) / abs(b)
    gmed = float(np.median(list(ref["grad1"].values())))
    for key, skip_tiny in (("grad1", False), ("change", True)):
        med = float(np.median(list(ref[key].values())))
        gaps = {leaf: abs(prog[key][leaf] - r) / max(r, med)
                for leaf, r in ref[key].items()
                if not (skip_tiny and ref["grad1"][leaf] < tiny_grad * gmed)}
        worst_leaf = max(gaps, key=gaps.get)
        out[f"{key}_norm_gap_worst_leaf"] = gaps[worst_leaf]
        out[f"{key}_norm_gap_median_leaf"] = float(
            np.median(list(gaps.values())))
        out[f"{key}_worst_leaf"] = worst_leaf
    return out


def reference_steps(run, state, **kw):
    m, t = run.config["model"], run.config["training"]
    return run.reference.first_steps(
        state["seed"], m["classes"], state["raw"],
        lr=t["learning_rate"], mu=t["momentum"],
        steps=run.traffic["first_steps"], **kw)


def check(run, state):
    for k in ("net", "pool"):
        state.pop(k, None)
    state["raw"] = state["raw"][:run.traffic["first_steps"]]
    gc.collect()
    ref = reference_steps(run, state)
    got = compare(state["program"], ref)
    log(f"worst leaves: grad1 {got['grad1_worst_leaf']}, change "
        f"{got['change_worst_leaf']}; reference losses {ref['loss']}")
    limits = run.config["correct"]
    return [(name, got[name], limit) for name, limit in limits.items()]
