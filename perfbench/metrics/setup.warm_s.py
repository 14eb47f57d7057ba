"""Layer: set-up. Source: program_counter
(`dl4j_setup_seconds{phase="warm"}`, fed by `telemetry.phase("warm")`
around the schedulers' warm() and the networks' precompile(); the
`aot.compile` spans are its children). Reads 0 in a cell that calls
neither: `fit()` compiles inside its first step, which
`cache.setup_compile_s` sees. None where the program has no such
counter, or the ring dropped spans. Moves: setup_s."""

from perfbench.harness import load_module


def read(run):
    return load_module("metrics", "setup.weights_init_s") \
        .phase_seconds("warm")
