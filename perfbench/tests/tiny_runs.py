"""Shared by the tests: one run of a cell at the tiny size on the CPU,
through everything of a run but the harness's look for a chip."""

import jax

from perfbench import harness

MANIFEST = harness.load_manifest()


def run_tiny(workload, seed=1, seconds=1.5):
    return harness.run_cell(MANIFEST, workload, seed, seconds, False,
                            devices=jax.devices()[:1], rehearsal=True)


def make_run(workload, seed=1, seconds=1.5):
    cell, cfg_entry = harness.find_cell(MANIFEST, workload)
    config, traffic = harness.cell_files(cell, cfg_entry, tiny=True)
    return harness.Run(cell, config, traffic, seed, seconds, False,
                       jax.devices()[:1], rehearsal=True)
