"""Layer: serving. Source: program_span (`sequence.prefill`,
serving/sequence.py, one span a prefill pass; `chunk` is the prompt
tokens the pass took, `bucket` the length of the chunk it ran in, which
a program from before the passes grew past a page does not record and
this reader does not need). Mean `chunk` over the window's passes: the
prompt tokens one read of the weights carries. None where the window
held no pass, where a span lacks `chunk`, and where the ring dropped
spans. Moves: ttft_p50_ms."""

from deeplearning4j_tpu.runtime import telemetry


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    chunks = [s["args"].get("chunk")
              for s in run.program_spans("sequence.prefill")]
    if not chunks or any(c is None for c in chunks):
        return None
    return sum(chunks) / len(chunks)
