"""Layer: serving. Source: program_span (`sequence.step`). The scheduler
iteration as the host sees it: median time from one decode dispatch to the
next, which holds the dispatch, the logits' fetch, the host-side sampling
and any prefill chunk in between. Moves: output_tokens_per_s."""

from perfbench.stats import percentile


def read(run):
    ts = [s["ts"] for s in run.program_spans("sequence.step")]
    if len(ts) < 3:
        return None
    return 1e3 * percentile([b - a for a, b in zip(ts, ts[1:])], 50)
