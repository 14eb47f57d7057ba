"""The CPU rehearsal of the one command: every cell's kind at a tiny
size, marked as a rehearsal and naming no device metric; and the refusal
to run without a chip."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import harness
from perfbench.tests.tiny_runs import MANIFEST, run_tiny

ROOT = harness.ROOT
CELLS = [c["name"] for c in MANIFEST["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_is_correct_and_names_no_device_metric(workload):
    r = run_tiny(workload, seed=3)
    assert r["rehearsal"] is True and r["metrics"] == {}
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-2:] == ["compared", "rehearsal"]
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_manifest_is_served_by_files():
    """Every name in BENCHMARK.json has its file: the harness finds
    configurations, traffic, kinds, references, arithmetic and metric
    readers by name alone."""
    for cfg in MANIFEST["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as f:
            c = json.load(f)
        assert c["reduced"] == cfg["reduced"]
        harness.load_module("arith", c["arith"])
        harness.load_module("references", c["reference"])
    for cell in MANIFEST["workloads"]:
        tr = harness.load_json("traffic", cell["traffic"] + ".json")
        harness.load_module("kinds", tr["kind"])
    for m in MANIFEST["per_layer"]:
        assert hasattr(harness.load_module("metrics", m["name"]), "read")


@pytest.mark.parametrize("workload", CELLS)
def test_every_reader_of_the_cell_runs(workload, monkeypatch):
    """The per-layer readers over a tiny window on the CPU: those that
    read spans and counters find them; those that read the device trace
    find none and return nothing (never 0). No value is reported."""
    from perfbench import peaks
    from perfbench.tests.tiny_runs import make_run

    monkeypatch.setattr(peaks, "peaks_for",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    run = make_run(workload, seed=4)
    run.trace = True            # the window then samples what a traced run does
    kind = harness.load_module("kinds", run.traffic["kind"])
    state = kind.setup(run)
    run.window = kind.window(run, state, lambda: None)
    run.counters.setdefault("compiles_in_window", 0)
    run.counters.setdefault("setup_compile_s", 0.0)
    got = harness.per_layer_metrics(MANIFEST, run)
    traced = {m["name"] for m in MANIFEST["per_layer"]
              if m["source"] == "device_trace"}
    mine = {m["name"] for m in MANIFEST["per_layer"]
            if workload in m.get("workloads", [workload])}
    assert set(got) == mine - traced, sorted(mine - traced - set(got))
    assert all(v["value"] == v["value"] for v in got.values())
