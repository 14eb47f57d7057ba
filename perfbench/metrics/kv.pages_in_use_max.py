"""Layer: serving. Source: program_span (`sequence.iteration` carries
`pages_in_use`: the most pages of the KV pool in use during that
iteration, counted by PagedKVCache.alloc() where pages are allotted).
Largest over the window's iterations; no peak falls between two polls,
as it can for `kv.pages_in_use_peak`. None where the ring dropped spans.
Moves: output_tokens_per_s (a pool that runs full refuses or evicts)."""

from deeplearning4j_tpu.runtime import telemetry


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    spans = run.program_spans("sequence.iteration")
    return max(s["args"]["pages_in_use"] for s in spans) if spans else None
