"""Operations and bytes of the Qwen3 decoder's work, from shapes alone.

A multiply-add counts two operations. Model operations count the work the
algorithm needs for the tokens that were asked for: prompt tokens unpadded,
decode rows that produced a token, attention over the positions attended
(causal: position p attends p + 1 keys). Bytes count what a step must read
from HBM at least: each weight once, the embedding rows used, and the keys
and values attended. Sizes come from ``reference.qwen3.sizes``.
"""

from __future__ import annotations

BF16 = 2


def layer_matmul_params(s: dict) -> int:
    d, h, kv, hd, f = s["d"], s["h"], s["kv"], s["hd"], s["f"]
    return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f


def layer_norm_params(s: dict) -> int:
    return 2 * s["d"] + 2 * s["hd"]


def non_embedding_params(s: dict) -> int:
    """Every weight a decode step reads whole: the layers, the final norm
    and the untied LM head."""
    return (s["layers"] * (layer_matmul_params(s) + layer_norm_params(s))
            + s["d"] + s["vocab"] * s["d"])


def attn_flops(s: dict, keys: int) -> int:
    """Scores and weighted sum of one query over ``keys`` keys, every
    layer and head."""
    return 4 * s["layers"] * s["h"] * s["hd"] * keys


def prefill_flops(s: dict, prompt_lens) -> int:
    """Prefill of batch-1 prompts: every layer's products for each token,
    causal attention, and the LM head at the last position only."""
    total = 0
    for n in prompt_lens:
        n = int(n)
        total += 2 * s["layers"] * layer_matmul_params(s) * n
        total += attn_flops(s, n * (n + 1) // 2)
        total += 2 * s["vocab"] * s["d"]
    return total


def decode_flops(s: dict, rows: int, keys: int) -> int:
    """One decode step for ``rows`` rows attending ``keys`` keys in all."""
    per_row = 2 * (s["layers"] * layer_matmul_params(s) + s["vocab"] * s["d"])
    return rows * per_row + attn_flops(s, keys)


def kv_bytes_per_token(s: dict) -> int:
    return s["layers"] * 2 * s["kv"] * s["hd"] * BF16


def decode_bytes(s: dict, rows: int, keys: int) -> int:
    """What one decode step must read: the non-embedding weights once,
    one embedding row per row, the keys and values attended."""
    return (non_embedding_params(s) * BF16 + rows * s["d"] * BF16
            + keys * kv_bytes_per_token(s))


def paged_attn(s: dict, rows: int, keys: int) -> tuple[int, int]:
    """(operations, bytes) of the paged decode attention kernel over all
    layers: ``rows`` queries of all heads attending ``keys`` keys in all;
    bytes are the keys and values attended plus the queries and outputs."""
    flops = attn_flops(s, keys)
    qo = 2 * rows * s["layers"] * s["h"] * s["hd"] * BF16
    return flops, keys * kv_bytes_per_token(s) + qo


def topk_lse(s: dict, rows: int, k: int, itemsize: int) -> tuple[int, int]:
    """(operations, bytes) of the streaming top-k and log-sum-exp summary
    over ``rows`` rows of the vocabulary: each logit read once, an
    exponential, a compare and an add counted per logit; the outputs are
    k values, k indices and one lse per row."""
    v = s["vocab"]
    return 3 * rows * v, rows * v * itemsize + rows * (8 * k + 4)
