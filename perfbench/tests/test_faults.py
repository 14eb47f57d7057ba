"""A run with the timed path broken underneath has to come out with
`correct` false: once for each fault a cell can have. The faults are
planted in the program's objects, here in the test and nowhere else."""

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness
from perfbench.tests.tiny_runs import run_tiny


class Wrapped:
    """A jitted entry with its call replaced; `warm` and the rest pass
    through."""

    def __init__(self, real, call):
        self._real, self._call = real, call

    def __call__(self, *a, **k):
        return self._call(self._real, *a, **k)

    def __getattr__(self, name):
        return getattr(self._real, name)


def state_unchanged(real, params, upd, states, *rest, **kw):
    copy = lambda t: jax.tree.map(jnp.copy, t)
    *_, loss = real(copy(params), copy(upd), copy(states), *rest, **kw)
    return params, upd, states, loss


def half_batch(real, params, upd, states, it, inputs, labels, *rest, **kw):
    half = lambda a: a[:a.shape[0] // 2]
    return real(params, upd, states, it,
                {k: half(v) for k, v in inputs.items()},
                [half(v) for v in labels], *rest, **kw)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_training_fault_is_not_correct(monkeypatch, fault):
    kind = harness.load_module("kinds", "train_fit")
    build = kind.build_net

    def broken(config, seed):
        net = build(config, seed)
        net._jit_train = Wrapped(net._jit_train, fault)
        return net

    monkeypatch.setattr(kind, "build_net", broken)
    r = run_tiny("resnet50-fit-resident", seed=3)
    assert r["correct"] is False, r["compared"]


def kv_dropped(real, *a, **k):
    out, kps, vps = real(*a, **k)
    return out, jnp.zeros_like(kps), jnp.zeros_like(vps)


@pytest.mark.parametrize("workload,entry", [
    ("gpt1.3b-generate-decode", "_jit_decode"),
    ("gpt1.3b-generate-prefill", "_jit_prefill")])
def test_serving_step_that_drops_its_state_is_not_correct(
        monkeypatch, workload, entry):
    kind = harness.load_module("kinds", "serve_generate")
    build = kind.build_model

    def broken(config, seed):
        model = build(config, seed)
        setattr(model, entry, Wrapped(getattr(model, entry), kv_dropped))
        return model

    monkeypatch.setattr(kind, "build_model", broken)
    r = run_tiny(workload, seed=2)
    assert r["correct"] is False, r["compared"]


@pytest.mark.parametrize("workload", ["gpt1.3b-generate-decode",
                                      "gpt1.3b-generate-prefill"])
def test_altered_token_is_not_correct(monkeypatch, workload):
    import deeplearning4j_tpu.serving as serving

    real = serving.greedy_sampler

    def altered():
        pick = real()
        return lambda row, rng: (int(pick(row, rng)) + 1) % row.shape[0]

    monkeypatch.setattr(serving, "greedy_sampler", altered)
    r = run_tiny(workload, seed=2)
    assert r["correct"] is False, r["compared"]
