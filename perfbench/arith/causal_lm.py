"""Operations and bytes the decoder-only LM needs, from its shapes alone.

FLOPs: 2 per multiply-add of every weight matmul a token passes through
(4 d^2 + 2 d ff per layer, and d V for the tied output embedding where
logits are taken), plus attention: 4 d per layer for each (query, live
key) pair (q.k and p.v). Norms, GELU and the softmax are not counted.

Bytes: the least traffic of one program execution: every matmul weight
read once in the served type, and the live keys and values of the
contexts attended read once (2 L d elements a token).
"""

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def layer_matmul_params(m):
    return m["n_layer"] * (4 * m["n_embd"] ** 2
                           + 2 * m["n_embd"] * m["n_inner"])


def logits_params(m):
    return m["n_embd"] * m["vocab_size"]


def n_params(m):
    return layer_matmul_params(m) + logits_params(m) \
        + m["n_positions"] * m["n_embd"]


def kv_bytes_per_token(m):
    return 2 * m["n_layer"] * m["n_embd"] * ITEMSIZE[m["dtype"]]


def weight_bytes(m):
    return (layer_matmul_params(m) + logits_params(m)) * ITEMSIZE[m["dtype"]]


def attention_flops(m, pairs):
    """`pairs`: the number of (query, live key) pairs attended."""
    return 4 * m["n_embd"] * m["n_layer"] * pairs


def decode_flops(m, tokens, pairs):
    """`tokens` decoded, each through every matmul and the logits."""
    return 2 * (layer_matmul_params(m) + logits_params(m)) * tokens \
        + attention_flops(m, pairs)


def prefill_flops(m, prompt_tokens, prompts, pairs):
    """Prompt tokens through the layers; logits once a prompt."""
    return 2 * layer_matmul_params(m) * prompt_tokens \
        + 2 * logits_params(m) * prompts + attention_flops(m, pairs)


def causal_pairs(length):
    return length * (length + 1) // 2


def step_min_seconds(m, peaks, flops, live_kv_tokens):
    """(least seconds of one execution, which bound binds)."""
    t_f = flops / peaks["flops_bf16"]
    t_b = (weight_bytes(m) + live_kv_tokens * kv_bytes_per_token(m)) \
        / peaks["hbm_bytes_per_s"]
    return max(t_f, t_b), "compute" if t_f >= t_b else "memory"
