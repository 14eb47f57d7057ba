"""Test harness config.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU pod hardware (the driver separately dry-runs the
multichip path). Env must be set before jax initialises a backend, hence
module-level, before any framework import.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take the chip
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)  # fp64 oracles for gradchecks

# NOTE on the tier-1 time budget: the suite is COMPILE-dominated (the
# zoo-model tests alone pay minutes of XLA time per run). The session
# executable cache below (runtime/aot.py, docs/COMPILE.md) lets tests
# that build equal-config networks share one executable instead of
# recompiling per test. JAX's persistent compilation cache is NOT
# enabled for the suite: on jaxlib 0.9.0 it round-trips donated
# executables correctly (tests/test_aot_cache.py drives it across two
# child processes), but a suite whose pass/fail depended on what an
# earlier run left on disk would not be hermetic. The serving-tier
# tests install fresh ExecutableCache() instances so their miss counts
# start from zero.

from deeplearning4j_tpu.runtime import aot as _aot  # noqa: E402

_aot.enable()

import pytest  # noqa: E402


def pytest_configure(config):
    # registered here (no pytest.ini in this repo) so `-m 'not slow'`
    # and `-m faults` filter without unknown-marker warnings
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run")
    config.addinivalue_line(
        "markers",
        "faults: deterministic fault-injection tests "
        "(runtime.resilience.FaultInjector)")
    config.addinivalue_line(
        "markers",
        "lint: static-analysis self-checks (purity linter over the "
        "package source + zoo config corpus); tier-1 fails on new "
        "violations")


@pytest.fixture(autouse=True)
def _fixed_seed():
    from deeplearning4j_tpu.ndarray import random as r

    r.setSeed(12345)
    yield


def drop_jax_caches_fixture():
    """Factory for the module-teardown cache-drop hygiene fixture the
    trace-heavy modules install (`_drop_jax_caches_after_module =
    drop_jax_caches_fixture()` at module scope). Such modules churn many
    tiny single-use executables (interpret-mode pallas kernels, paged
    step twins); left in jax's global caches they stay live for the rest
    of the tier-1 process and starve the big zoo fits that run last —
    PR 19's full-suite YOLO2 segfault. One shared definition so the next
    trace-heavy module can't reintroduce it with a drifted copy."""

    @pytest.fixture(autouse=True, scope="module")
    def _drop_jax_caches_after_module():
        yield
        jax.clear_caches()

    return _drop_jax_caches_after_module


# ----------------------------------------------------------------------
# session-scoped compiled subjects: the attribution/bytes-gate tests all
# interrogate the SAME canonical train-step compiles (LeNet b64 and the
# resnet_block b32 from analysis.hbm) — one XLA compile per subject per
# RUN, not per module; fit-style tests share executables through the
# session AOT cache above instead (equal config + equal signature =
# same cache key).
# ----------------------------------------------------------------------

def _compiled_subject(name, batch_size):
    from deeplearning4j_tpu.analysis.hbm import (build_subject,
                                                 compile_train_step,
                                                 lower_train_step)

    net, x_shape, slots = build_subject(name, batch_size=batch_size)
    lowered = lower_train_step(net, x_shape)
    compiled = compile_train_step(net, x_shape, lowered=lowered)
    return net, x_shape, slots, lowered, compiled


@pytest.fixture(scope="session")
def lenet_compiled_subject():
    """(net, x_shape, optimizer_slots, lowered, compiled) for the LeNet
    b64 attribution subject."""
    return _compiled_subject("lenet", 64)


@pytest.fixture(scope="session")
def resnet_block_compiled_subject():
    """(net, x_shape, optimizer_slots, lowered, compiled) for the
    resnet_block b32 attribution subject."""
    return _compiled_subject("resnet_block", 32)
