"""Layer: model step. Source: host_clock (steps finished in the window
times the forward+backward FLOPs a step requires, over the window's wall
time times the chips' bf16 peak). The whole step's share of the peak:
idle time and host work count against it. Moves:
train_samples_per_s_per_chip."""


def read(run):
    w = run.window
    if not w or not w.get("steps"):
        return None
    flops = run.arith.train_step_flops(
        run.config, run.config["training"]["batch"]) * w["steps"]
    return 100.0 * flops / (w["wall_s"] * len(run.devices)
                            * run.peaks["flops_bf16"])
