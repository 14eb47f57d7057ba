"""Layer: model step. Source: host_clock (tokens emitted in the window
times the FLOPs a decoded token requires: 2 x matmul parameters, plus
attention over the mix's mean context; over the window's wall time times
the bf16 peak). The whole step's share of the peak. Moves:
output_tokens_per_s."""


def read(run):
    w = run.window
    if not w or not w.get("tokens"):
        return None
    m = run.config["model"]
    flops = run.arith.decode_flops(
        m, w["tokens"], w["tokens"] * run.traffic["mean_context"])
    return 100.0 * flops / (w["wall_s"] * len(run.devices)
                            * run.peaks["flops_bf16"])
