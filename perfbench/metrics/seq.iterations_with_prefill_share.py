"""Layer: serving. Source: program_span. Percent of the window's
`sequence.iteration` spans that have a `sequence.prefill` child (by
`parent`): how often a prompt chunk rides on the decode batch and
lengthens its iteration. None where the ring dropped spans. Moves:
output_tokens_per_s."""

from deeplearning4j_tpu.runtime import telemetry


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    its = run.program_spans("sequence.iteration")
    if not its:
        return None
    with_chunk = {s["parent"] for s in run.program_spans("sequence.prefill")}
    return 100.0 * sum(s["id"] in with_chunk for s in its) / len(its)
