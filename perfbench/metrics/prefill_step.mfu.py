"""Layer: model step. Source: host_clock (the prompts finished in the
window times the FLOPs their tokens require: 2 x layer parameters a
token, the logits once a prompt, causal attention; over the window's wall
time times the bf16 peak). The whole step's share of the peak. Moves:
ttft_p95_ms."""


def read(run):
    w = run.window
    done = (w or {}).get("done")
    if not done:
        return None
    m = run.config["model"]
    lens = [len(r.prompt) for r in done]
    flops = run.arith.prefill_flops(
        m, sum(lens), len(lens), sum(map(run.arith.causal_pairs, lens)))
    return 100.0 * flops / (w["wall_s"] * len(run.devices)
                            * run.peaks["flops_bf16"])
