"""Operations and bytes a ResNet-50 training step needs, from its shapes
alone (the same whatever implements the step).

FLOPs: 2 per multiply-add of every convolution and of the dense layer;
a training step is forward + gradient by the input + gradient by the
weights, three times the forward, less the first convolution's gradient
by its input, which nothing needs. Batch norm, ReLU, pooling and the
loss are not counted (under 1% of the multiply-adds). Recomputation
never counts.

Bytes: the least traffic an ideal implementation keeps: every
convolution reads its input and writes its output once in the forward
pass, and in the backward pass reads input and output-gradient and
writes the input-gradient, all in the compute type; normalisation and
activation are taken as fused into those passes. Weights are read in
each of the three passes and the optimizer reads parameter, momentum and
gradient and writes parameter and momentum, in float32.
"""

from perfbench.references.resnet50 import layer_table


def _convs(image, classes):
    """(multiply-adds per image, input elements, output elements, weight
    elements, is_first) for every convolution and the dense layer."""
    size = {"conv1": image}
    out = []
    hw = image
    for name, kind, spec in layer_table(classes):
        if name == "pool1":
            hw //= 2
        if kind == "conv":
            ksz, cin, cout, stride, _ = spec
            hin = hw if not name.endswith("_proj") else size["proj_in"]
            if name.endswith("_c1"):
                size["proj_in"] = hw
            hout = hin // stride
            out.append((hout * hout * ksz * ksz * cin * cout,
                        hin * hin * cin, hout * hout * cout,
                        ksz * ksz * cin * cout, name == "conv1"))
            if not name.endswith("_proj"):
                hw = hout
        elif kind == "dense":
            nin, nout = spec
            out.append((nin * nout, nin, nout, nin * nout + nout, False))
    return out


def train_step_flops(config, batch):
    m = config["model"]
    total = 0
    for macs, _, _, _, first in _convs(m["image"], m["classes"]):
        total += 2 * macs * (2 if first else 3)
    return total * batch


def n_params(config):
    m = config["model"]
    convs = sum(w for _, _, _, w, _ in _convs(m["image"], m["classes"]))
    bn = sum(2 * spec for _, kind, spec in layer_table(m["classes"])
             if kind == "bn")
    return convs + bn


def train_step_min_bytes(config, batch, act_itemsize=2):
    m = config["model"]
    act = 0
    for _, xin, xout, _, first in _convs(m["image"], m["classes"]):
        fwd = xin + xout
        bwd = (xin + xout) + (0 if first else xin)
        act += fwd + bwd
    n = n_params(config)
    return batch * act * act_itemsize + n * (3 * act_itemsize + 5 * 4)
