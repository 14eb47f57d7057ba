"""Layer: compile caches. Source: program_counter (the program's
PersistentCacheWatch over set-up: seconds JAX spent obtaining executables,
compiling on a miss, loading on a hit). Moves: setup_s."""


def read(run):
    return run.counters.get("setup_compile_s")
