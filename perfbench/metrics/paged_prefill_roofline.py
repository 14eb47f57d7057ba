"""Layer: kernels. Source: device_trace. The least time the chip could
take for the window's average prompt chunk (weights read once, the keys
and values of the context so far, against its FLOPs) over a chunk's
median device time. Moves: ttft_p50_ms."""

from perfbench.harness import log
from perfbench.stats import percentile


def read(run):
    ms = run.entry_device_ms("prefill_paged")
    done = (run.window or {}).get("done")
    if not ms or not done:
        return None
    m = run.config["model"]
    page = m["page_size"]
    lens = [len(r.prompt) for r in done]
    chunks = sum(-(-n // page) for n in lens)
    kv = sum(min((c + 1) * page, n) for n in lens
             for c in range(-(-n // page))) / chunks
    flops = run.arith.prefill_flops(
        m, sum(lens), len(lens),
        sum(map(run.arith.causal_pairs, lens))) / chunks
    least, bound = run.arith.step_min_seconds(m, run.peaks, flops, kv)
    log(f"prefill chunk roofline: least {1e3 * least:.3f} ms, bound by {bound}")
    return 100.0 * least / (1e-3 * percentile(ms, 50))
