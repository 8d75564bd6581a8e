"""Operations and bytes that serving a dense decoder needs, from its shapes.

`m` is a configuration's "model" block. Counts are what the algorithm
needs, not what a kernel happens to do: a decode step reads every stored
weight byte once, the bf16 tied head once, and each live slot's own KV; a
prefill does the matmuls of its real prompt tokens, causal attention, and
the head for the last token only.
"""
from __future__ import annotations

import formats

BF16 = 2


def linear_shapes(m: dict) -> list:
    """(K, N) of each FC weight of one layer."""
    d, f = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    mats = [(d, q), (d, kv), (d, kv), (q, d)]
    if m["mlp_act"] in ("swiglu", "geglu"):
        return mats + [(d, f), (d, f), (f, d)]
    return mats + [(d, f), (f, d)]


def linear_params(m: dict) -> int:
    return m["n_layers"] * sum(k * n for k, n in linear_shapes(m))


def plane_bytes(m: dict, weights: str) -> int:
    return m["n_layers"] * sum(
        formats.stored_bytes(weights, k, n) for k, n in linear_shapes(m)
    )


def head_bytes(m: dict) -> int:
    return m["vocab_size"] * m["d_model"] * BF16


def kv_bytes_per_token(m: dict) -> int:
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["d_head"] * BF16


def decode_flops(m: dict, tokens: int, kv_sum: int) -> float:
    """`tokens` decoded tokens whose contexts hold `kv_sum` KV tokens in all."""
    per_token = 2 * linear_params(m) + 2 * m["d_model"] * m["vocab_size"]
    attn = 4 * m["n_layers"] * m["n_heads"] * m["d_head"] * kv_sum
    return float(per_token * tokens + attn)


def decode_bytes(m: dict, weights: str, steps: int, kv_sum: int) -> float:
    """`steps` decode steps that held at least one live slot."""
    per_step = plane_bytes(m, weights) + head_bytes(m)
    return float(per_step * steps + kv_bytes_per_token(m) * kv_sum)


def prefill_flops(m: dict, prompt_len: int) -> float:
    p = prompt_len
    attn = 2 * m["n_layers"] * m["n_heads"] * m["d_head"] * p * (p + 1)
    head = 2 * m["d_model"] * m["vocab_size"]
    return float(2 * linear_params(m) * p + attn + head)
