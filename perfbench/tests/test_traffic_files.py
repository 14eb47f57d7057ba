"""Every open-loop traffic file, by plain arithmetic on the file and the
program's `prefill_plan` (no device work): a window holds whole cycles,
both percentiles of the time to first token lie inside a mass of
requests that take the same passes, and the file holds requests enough.
The file as it stood before PR 33 fails the second (rank 303 of 320
against the 1024-token prompts' 304-319)."""

import glob
import os

import pytest

from deeplearning4j_tpu.nn.transformer import prefill_plan
from perfbench import harness

MANIFEST = harness.load_manifest()
INSIDE = 10         # ranks between a percentile and its mass's edges


def open_loop_cells():
    cells = []
    for cell in MANIFEST["workloads"]:
        config, traffic = harness.cell_files(
            *harness.find_cell(MANIFEST, cell["name"]))
        if traffic.get("loop") == "open":
            cells.append(pytest.param(config, traffic, id=cell["name"]))
    return cells


def test_every_open_loop_file_belongs_to_a_cell():
    names = [os.path.basename(f) for f in glob.glob(
        os.path.join(harness.HERE, "traffic", "*.json"))]
    is_open = {n[:-5] for n in names
               if harness.load_json("traffic", n).get("loop") == "open"}
    assert is_open and is_open <= {c["traffic"]
                                   for c in MANIFEST["workloads"]}


@pytest.mark.parametrize("config,traffic", open_loop_cells())
def test_window_of_whole_cycles_and_percentiles_inside_a_mass(config,
                                                              traffic):
    m = config["model"]
    block = [n for n, count in traffic["prompt_multiset"]
             for _ in range(count)]
    cycle = len(block) * traffic["cycle_blocks"]
    window = traffic["rate_rps"] * MANIFEST["run_seconds"]
    assert window == int(window) and int(window) % cycle == 0
    window = int(window)
    assert traffic["max_requests"] >= window + traffic["warmup_requests"]

    # the chunk lengths of each prompt's passes
    passes = {n: tuple(c for _, _, c in prefill_plan(
        n, 0, m["page_size"], m["n_positions"] // m["page_size"]))
        for n in set(block)}
    ranked = sorted(block * (window // len(block)),
                    key=lambda n: (len(passes[n]), n))
    for q in (50, 95):
        pos = (window - 1) * q / 100
        lo, hi = int(pos), min(int(pos) + 1, window - 1)
        same = [i for i, n in enumerate(ranked)
                if passes[n] == passes[ranked[lo]]]
        assert passes[ranked[hi]] == passes[ranked[lo]]
        assert same == list(range(same[0], same[-1] + 1))
        assert lo - same[0] >= INSIDE and same[-1] - hi >= INSIDE, \
            (q, lo, same[0], same[-1])
