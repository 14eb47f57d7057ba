"""Layer: serving. Source: program_span (`sequence.step`,
serving/sequence.py, carries `ahead`: 1 where the decode step took its
tokens from the previous step's ids on the device and was dispatched
before those ids reached the host, 0 where its tokens came from the
host). The window's sum(ahead) over its decode steps, in percent: how
often the host's round trip between two steps ran under the device's
step. None where the spans lack the number (a program from before it
was recorded) and where the ring dropped spans. Moves:
output_tokens_per_s."""

from deeplearning4j_tpu.runtime import telemetry


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    args = [s["args"] for s in run.program_spans("sequence.step")]
    if not args or any("ahead" not in a for a in args):
        return None
    return 100.0 * sum(a["ahead"] for a in args) / len(args)
