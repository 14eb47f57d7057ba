"""Plain float32 reference of ResNet-50 training (He et al. 2015,
arXiv:1512.03385, Table 1, 50-layer column; bottleneck v1, stride on the
first 1x1 of a stage as the program's zoo model has it).

Straight jax.numpy / lax in float32 at "highest" matmul precision:
forward, softmax cross-entropy (mean over the batch), jax.grad, Nesterov
momentum. It imports nothing of the program and takes nothing the program
made: the initial weights are drawn here from the seed by the same
published rule (He normal, std sqrt(2/fan_in), one PRNG stream per layer
index), images and labels are the benchmark's. Each bottleneck is
rematerialised in the backward pass so that a float32 batch of 256 fits
the chip; that changes no number.

`precision="fp8"` is the control of "How correct is decided": the same
mathematics with every tensor the configuration holds in bfloat16 (the
operands of every convolution and of the dense layer, and what every
convolution and normalisation writes back) held in float8 instead (e4m3
with a per-tensor scale in the forward pass, e5m2 for the cotangents),
accumulation in float32 as on the chip. It is the step that would tempt
a later PR: the train step is bound by memory, and float8 activations
halve its bytes. Rounding the operands alone does not separate from
bfloat16 (PERF.md: the products average the rounding away), so the
control rounds what is stored. `precision="bfloat16"` holds the same
tensors in bfloat16: a second witness of what the configuration's own
precision costs against float32, used when the limits were set and by
no run.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

STAGES = ((3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2),
          (3, 512, 2048, 2))
BN_EPS = 1e-5
HI = lax.Precision.HIGHEST


def layer_table(classes):
    """(name, kind, spec) in the order the layers are added, which is the
    index each layer's PRNG stream is folded with. Layers without
    parameters (pooling, relu) hold their place in the count."""
    t = [("conv1", "conv", (7, 3, 64, 2, 3)), ("bn1", "bn", 64),
         ("pool1", "none", None)]
    cin = 64
    for si, (blocks, mid, out, stride0) in enumerate(STAGES):
        for bi in range(blocks):
            n = f"s{si}b{bi}"
            s = stride0 if bi == 0 else 1
            t += [(f"{n}_c1", "conv", (1, cin, mid, s, 0)),
                  (f"{n}_b1", "bn", mid),
                  (f"{n}_c2", "conv", (3, mid, mid, 1, 1)),
                  (f"{n}_b2", "bn", mid),
                  (f"{n}_c3", "conv", (1, mid, out, 1, 0)),
                  (f"{n}_b3", "bn", out)]
            if bi == 0:
                t += [(f"{n}_proj", "conv", (1, cin, out, s, 0)),
                      (f"{n}_projbn", "bn", out)]
            t.append((f"{n}_relu", "none", None))
            cin = out
    t += [("gap", "none", None), ("fc", "dense", (cin, classes))]
    return t


def init_params(seed, classes):
    key = jax.random.key(seed)
    params = {}
    for i, (name, kind, spec) in enumerate(layer_table(classes)):
        k = jax.random.fold_in(key, i)
        if kind == "conv":
            ksz, cin, cout, _, _ = spec
            kw, _ = jax.random.split(k)
            std = jnp.sqrt(2.0 / (ksz * ksz * cin))
            params[name] = {"W": std * jax.random.normal(
                kw, (ksz, ksz, cin, cout), jnp.float32)}
        elif kind == "bn":
            params[name] = {"gamma": jnp.ones((spec,), jnp.float32),
                            "beta": jnp.zeros((spec,), jnp.float32)}
        elif kind == "dense":
            nin, nout = spec
            kw, _ = jax.random.split(k)
            std = jnp.sqrt(2.0 / nin)
            params[name] = {"W": std * jax.random.normal(
                kw, (nin, nout), jnp.float32),
                "b": jnp.zeros((nout,), jnp.float32)}
    return params


# -- the control's rounding ------------------------------------------------
def _round_to(x, dtype):
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round_to(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round_to(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


@jax.custom_vjp
def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_bf16.defvjp(lambda x: (_bf16(x), None), lambda _, g: (_bf16(g),))


def _operand(x, precision):
    """A tensor as `precision` holds it: operands and stored results."""
    if precision == "fp8":
        return _fp8(x)
    return _bf16(x) if precision == "bfloat16" else x


# -- layers ----------------------------------------------------------------
def conv(x, w, stride, pad, precision):
    return _operand(lax.conv_general_dilated(
        _operand(x, precision), _operand(w, precision), (stride, stride),
        ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI),
        precision)


def batch_norm(x, p, precision):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return _operand(
        (x - mean) * lax.rsqrt(var + BN_EPS) * p["gamma"] + p["beta"],
        precision)


def max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1),
                             ((0, 0), (1, 1), (1, 1), (0, 0)))


def bottleneck(params, x, name, stride, project, precision):
    h = conv(x, params[f"{name}_c1"]["W"], stride, 0, precision)
    h = jax.nn.relu(batch_norm(h, params[f"{name}_b1"], precision))
    h = conv(h, params[f"{name}_c2"]["W"], 1, 1, precision)
    h = jax.nn.relu(batch_norm(h, params[f"{name}_b2"], precision))
    h = conv(h, params[f"{name}_c3"]["W"], 1, 0, precision)
    h = batch_norm(h, params[f"{name}_b3"], precision)
    if project:
        s = conv(x, params[f"{name}_proj"]["W"], stride, 0, precision)
        s = batch_norm(s, params[f"{name}_projbn"], precision)
    else:
        s = x
    return _operand(jax.nn.relu(h + s), precision)


def loss_fn(params, x, y, precision="float32"):
    """Mean over the batch of -sum(y * log_softmax(logits)); x NHWC, y
    one-hot, both taken to float32."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    h = conv(x, params["conv1"]["W"], 2, 3, precision)
    h = jax.nn.relu(batch_norm(h, params["bn1"], precision))
    h = max_pool_3x3_s2(h)
    for si, (blocks, _, _, stride0) in enumerate(STAGES):
        for bi in range(blocks):
            blk = jax.checkpoint(functools.partial(
                bottleneck, name=f"s{si}b{bi}",
                stride=stride0 if bi == 0 else 1, project=bi == 0,
                precision=precision))
            h = blk(params, h)
    h = jnp.mean(h, axis=(1, 2))
    logits = jnp.dot(_operand(h, precision),
                     _operand(params["fc"]["W"], precision),
                     precision=HI) + params["fc"]["b"]
    return jnp.mean(-jnp.sum(y * jax.nn.log_softmax(logits), axis=-1))


@functools.partial(jax.jit, static_argnames=("lr", "mu", "precision"),
                   donate_argnums=(0, 1))
def train_step(params, vel, x, y, *, lr, mu, precision="float32"):
    """One step of SGD with Nesterov momentum as the source paper's §3.4
    trains (momentum 0.9): v' = mu v - lr g; p' = p + mu v' - lr g."""
    loss, g = jax.value_and_grad(loss_fn)(params, x, y, precision)
    vel = jax.tree.map(lambda v, gi: mu * v - lr * gi, vel, g)
    params = jax.tree.map(lambda p, v, gi: p + mu * v - lr * gi,
                          params, vel, g)
    gnorm = jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), g)
    return params, vel, loss, gnorm


@jax.jit
def change_norms(p_new, p_old):
    return jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p_new, p_old)


def first_steps(seed, classes, batches, *, lr, mu, steps=3,
                precision="float32", rows=None):
    """Follow the first `steps` steps from the seed's weights on
    `batches` [(x, y)]. Returns {"loss": [..], "grad1": {leaf: norm},
    "change": {leaf: norm}} with leaves named "<layer>_<param>".
    `rows` keeps only the first rows of each batch (the planted fault
    "half of the batch left out, the mean taken over the rest")."""
    p = init_params(seed, classes)
    p0 = jax.tree.map(jnp.copy, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad1 = [], None
    for i in range(steps):
        x, y = batches[i]
        if rows is not None:
            x, y = x[:rows], y[:rows]
        p, v, loss, gn = train_step(p, v, x, y, lr=lr, mu=mu,
                                    precision=precision)
        losses.append(float(loss))
        if i == 0:
            grad1 = gn
    change = change_norms(p, p0)

    def flat(tree):
        return {f"{layer}_{k}": float(val)
                for layer, leaves in tree.items()
                for k, val in leaves.items()}

    return {"loss": losses, "grad1": flat(grad1), "change": flat(change)}
