"""Layer: trainers. Source: program_span. The host time of a step that
lies outside `train.step`: `train.prepare` (the dropout key's fold_in
and the iteration scalar, two small device programs), `train.listeners`
and `train.data_wait` (the iterator's hasNext/next and the unwrapping),
summed over the window and divided by its steps. None where the ring
dropped spans. Moves: train_samples_per_s_per_chip."""

from deeplearning4j_tpu.runtime import telemetry


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    steps = len(run.program_spans("train.step"))
    outside = [s["dur"] for name in ("train.prepare", "train.listeners",
                                     "train.data_wait")
               for s in run.program_spans(name)]
    return 1e3 * sum(outside) / steps if steps and outside else None
