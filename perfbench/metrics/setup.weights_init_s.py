"""Layer: set-up. Source: program_counter
(`dl4j_setup_seconds{phase="weights_init"}`, fed by
`telemetry.phase("weights_init")` around CausalTransformerLM's host
draw of its weights and around the init() of both network classes).
Seconds of the process so far, all of them set-up. None where the
program has no such counter, or the ring dropped spans. Moves:
setup_s."""

from deeplearning4j_tpu.runtime import telemetry


def phase_seconds(phase):
    """The counter's value for `phase`; 0.0 for a phase this process
    never entered; None where the program has no such counter."""
    reg = telemetry.get_registry()
    fam = reg.get("dl4j_setup_seconds")
    if fam is None or reg.trace.dropped:
        return None
    child = fam.labels_get(phase=phase)
    return 0.0 if child is None else child.value


def read(run):
    return phase_seconds("weights_init")
