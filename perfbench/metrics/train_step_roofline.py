"""Layer: kernels. Source: device_trace. The least time the chip could
take for one train step, the larger of FLOPs over the bf16 peak and bytes
over the HBM peak (arith/<config>.py says what is counted), over the
step's median device time. Moves: train_samples_per_s_per_chip."""

from perfbench.harness import log
from perfbench.stats import percentile


def read(run):
    ms = run.entry_device_ms("train_step")
    if not ms:
        return None
    batch = run.config["training"]["batch"]
    t_flops = run.arith.train_step_flops(run.config, batch) \
        / run.peaks["flops_bf16"]
    t_bytes = run.arith.train_step_min_bytes(run.config, batch) \
        / run.peaks["hbm_bytes_per_s"]
    log(f"train step roofline: compute {1e3 * t_flops:.2f} ms, memory "
        f"{1e3 * t_bytes:.2f} ms; bound by "
        f"{'compute' if t_flops >= t_bytes else 'memory'}")
    return 100.0 * max(t_flops, t_bytes) / (1e-3 * percentile(ms, 50))
