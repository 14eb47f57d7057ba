"""The control of "How correct is decided", at a size a test run holds:
the reference in the nearest precision below the configuration's, put in
the program's place, has to come out as not correct by at least one of
the cell's numbers, while the program itself is correct."""

import pytest

from perfbench import harness
from perfbench.tests.tiny_runs import make_run


def verdict(got, limits):
    return all(got[name] <= lim for name, lim in limits.items())


def test_fp8_reference_is_not_correct_for_the_training_cell():
    run = make_run("resnet50-fit-resident", seed=3)
    kind = harness.load_module("kinds", "train_fit")
    state = kind.setup(run)
    ref = kind.reference_steps(run, state)
    limits = run.config["correct"]
    assert verdict(kind.compare(state["program"], ref), limits)
    control = kind.compare(
        kind.reference_steps(run, state, precision="fp8"), ref)
    assert not verdict(control, limits), control


@pytest.mark.parametrize("workload", ["gpt1.3b-generate-decode",
                                      "gpt1.3b-generate-prefill"])
def test_fp8_reference_is_not_correct_for_the_serving_cells(workload):
    run = make_run(workload, seed=2)
    kind = harness.load_module("kinds", "serve_generate")
    state = kind.setup(run)
    run.window = kind.window(run, state, lambda: None)
    limits = run.config["correct"]
    got = {n: v for n, v, _ in kind.check(run, state)}
    assert verdict(got, limits), got
    control = kind.compare_sample(run, state["weights"], state["sample"],
                                  control="fp8")
    assert not verdict(control, limits), control
