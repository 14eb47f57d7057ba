"""Layer: serving. Source: program_span (`sequence.request`, whose
`token_times` hold one clock read a sampled token). Median of the
differences of consecutive `token_times` over the requests enqueued and
ended in the window: the gap a reader of the stream sees between two
tokens. None where the ring dropped spans. Moves: output_tokens_per_s."""

from perfbench.harness import load_module
from perfbench.stats import percentile


def read_gap_ms(run, q):
    reqs = load_module("metrics", "seq.ttft_inside_p50_ms").requests(run)
    gaps = [b - a for r in reqs or ()
            for a, b in zip(r["token_times"], r["token_times"][1:])]
    return 1e3 * percentile(gaps, q) if gaps else None


def read(run):
    return read_gap_ms(run, 50)
