"""Layer: trainers. Source: program_span (`train.sync`,
nn/multilayer.py::traced_train_step: `float(loss)`, in which the host
waits out the device step; child of `train.step`). Mean over the steps
of the window: what fetching the previous step's loss (ROADMAP S6) would
overlap. None where the ring dropped spans. Moves:
train_samples_per_s_per_chip."""

from deeplearning4j_tpu.runtime import telemetry
from perfbench.stats import mean


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    spans = run.program_spans("train.sync")
    return 1e3 * mean(s["dur"] for s in spans) if spans else None
