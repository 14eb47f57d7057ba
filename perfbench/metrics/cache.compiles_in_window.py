"""Layer: compile caches. Source: program_counter (executables JAX
compiled or loaded from its cache inside the measured window, counted by
the program's PersistentCacheWatch with every executable cached). It has
to read 0: a compile in the window is set-up that leaked. Moves:
setup_s."""


def read(run):
    return run.counters.get("compiles_in_window")
