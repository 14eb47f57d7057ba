"""Layer: compile caches. Source: program_span (`aot.call`,
runtime/aot.py: the call of a compiled executable served from
`CachedJit`'s table, until it returns its futures; matched to the
`sequence.prefill` span it ran inside as aot.prefill_sign_ms_p50 does).
Median over the window's prefill passes, in ms: the host's launch of a
pass. None where no pass holds one (a program without the span) and
where the ring dropped spans. Moves: ttft_p50_ms."""

from perfbench.harness import load_module


def read(run):
    return load_module("metrics", "aot.prefill_sign_ms_p50").read_inside(
        run, "aot.call")
