"""Layer: serving. Source: program_counter (the `dl4j_kv_pages_in_use`
gauge of PagedKVCache, sampled every 20 ms through the window by the
traced run). Moves: output_tokens_per_s (a pool that runs full refuses
or evicts)."""


def read(run):
    return run.counters.get("kv_pages_in_use_peak")
