"""Layer: serving. Source: program_span (`sequence.step`,
serving/sequence.py, carries `device_picked`: the live slots of the
decode step whose token was the argmax the step computed on the device,
because their request's sampler marks itself the greedy pick; and
`slots`, the live slots). The window's sum(device_picked) / sum(slots)
in percent: how often the decode iteration goes on without waiting for
its logits rows. 100 where every request is greedy; a slot whose sampler
is any other callable is picked on the host from its row and counts
against it. None where the spans lack the number (a program from before
it was recorded) and where the ring dropped spans. Moves:
output_tokens_per_s."""

from deeplearning4j_tpu.runtime import telemetry


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    args = [s["args"] for s in run.program_spans("sequence.step")]
    if not args or any("device_picked" not in a for a in args):
        return None
    slots = sum(a["slots"] for a in args)
    return 100.0 * sum(a["device_picked"] for a in args) / slots \
        if slots else None
