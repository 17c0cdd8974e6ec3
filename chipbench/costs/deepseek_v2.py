"""Operations and bytes of the DeepSeek-V2 decoder's work, from shapes alone.

A multiply-add counts two operations. Model operations count the work the
algorithm needs for the tokens that were asked for: prompt tokens unpadded,
decode rows that produced a token, each token's own k routed experts and
the shared experts (not all E), attention over the positions attended
(causal: position p attends p + 1 keys). Prefill attends un-absorbed
(expanded keys and values of nope + rope and v columns a head); decode
attends absorbed, in the latent space (R + rope columns a key for the
scores, R for the values). Bytes count what a step must read from HBM at
least: each weight once, of the routed experts only those hit, the
embedding rows used, and the latents attended (R + rope columns of bf16 a
token a layer). Sizes come from ``reference.deepseek_v2.sizes``.
"""

from __future__ import annotations

BF16 = 2


def attn_params(s: dict) -> int:
    """Products of one layer's attention: wq, wkv_a, wkv_b, wo."""
    d, h, r, pe, nope, vd = (s[k] for k in ("d", "h", "r", "pe", "nope",
                                            "vd"))
    return d * h * (nope + pe) + d * (r + pe) + r * h * (nope + vd) \
        + h * vd * d


def expert_params(s: dict) -> int:
    """One routed expert's SwiGLU (w1, w3, w2)."""
    return 3 * s["d"] * s["fe"]


def moe_layers(s: dict) -> int:
    return s["layers"] - s["dense"]


def active_matmul_params(s: dict) -> int:
    """Weights one token multiplies through, every layer: attention, the
    dense MLP or the router, its k routed experts and the shared ones."""
    d = s["d"]
    dense = s["dense"] * 3 * d * s["f"]
    moe = moe_layers(s) * (d * s["e"]
                           + (s["k"] + s["shared"]) * expert_params(s))
    return s["layers"] * attn_params(s) + dense + moe


def norm_params(s: dict) -> int:
    return s["layers"] * (2 * s["d"] + s["r"]) + s["d"]


def resident_params(s: dict) -> int:
    """Weights a decode step reads whole: every layer's attention, norms,
    dense MLP, router and shared experts, and the untied LM head (not the
    routed experts, nor the embedding)."""
    d = s["d"]
    return (s["layers"] * attn_params(s) + s["dense"] * 3 * d * s["f"]
            + moe_layers(s) * (d * s["e"] + s["shared"] * expert_params(s))
            + norm_params(s) + s["vocab"] * d)


def experts_hit(s: dict, rows: int) -> float:
    """Routed experts a step of ``rows`` tokens reads, every MoE layer:
    the expected count of distinct experts among rows x k draws spread
    evenly, E (1 - (1 - k/E)^rows) a layer."""
    e, k = s["e"], s["k"]
    return moe_layers(s) * e * (1.0 - (1.0 - k / e) ** rows)


def latent_bytes_per_token(s: dict) -> int:
    return s["layers"] * (s["r"] + s["pe"]) * BF16


def _latent_attn_flops(s: dict, keys: int) -> int:
    """Absorbed decode attention of ``keys`` keys in all, every layer and
    head: scores over R + rope columns, the weighted sum over R."""
    return 2 * s["layers"] * s["h"] * (2 * s["r"] + s["pe"]) * keys


def _absorb_flops(s: dict) -> int:
    """One decode row's absorption, every layer: q_nope into the latent
    (nope x R a head) and the latent sum out through wkv_b's V half (R x v
    a head)."""
    return 2 * s["layers"] * s["h"] * s["r"] * (s["nope"] + s["vd"])


def prefill_flops(s: dict, prompt_lens) -> int:
    """Prefill of batch-1 prompts: every layer's products for each token,
    causal un-absorbed attention, and the LM head at the last position
    only."""
    total = 0
    per_key = 2 * s["layers"] * s["h"] * (s["nope"] + s["pe"] + s["vd"])
    for n in prompt_lens:
        n = int(n)
        total += 2 * active_matmul_params(s) * n
        total += per_key * (n * (n + 1) // 2)
        total += 2 * s["vocab"] * s["d"]
    return total


def decode_flops(s: dict, rows: int, keys: int) -> int:
    """One decode step for ``rows`` rows attending ``keys`` keys in all."""
    per_row = 2 * (active_matmul_params(s) + s["vocab"] * s["d"]) \
        + _absorb_flops(s)
    return rows * per_row + _latent_attn_flops(s, keys)


def decode_bytes(s: dict, rows: int, keys: int) -> float:
    """What one decode step must read: the resident weights once, the
    routed experts its rows hit (:func:`experts_hit`), one embedding row
    per row, the latents attended."""
    return ((resident_params(s) + experts_hit(s, rows) * expert_params(s))
            * BF16 + rows * s["d"] * BF16
            + keys * latent_bytes_per_token(s))


def latent_attn(s: dict, rows: int, keys: int) -> tuple[int, int]:
    """(operations, bytes) of the paged latent attention kernel over all
    layers: ``rows`` queries of every head attending ``keys`` keys in all;
    bytes are the latents attended (R + rope columns) plus the queries (R
    + rope a head) and outputs (R a head)."""
    qo = rows * s["layers"] * s["h"] * (2 * s["r"] + s["pe"]) * BF16
    return _latent_attn_flops(s, keys), keys * latent_bytes_per_token(s) + qo


def moe(s: dict, routed: int, hit: int) -> tuple[int, int]:
    """(operations, bytes) of the grouped expert matmuls of ``routed``
    token-expert pairs that hit ``hit`` experts (summed over layers): 6 d
    Fe operations a pair; the weights of the experts hit, and per pair its
    activations (the gathered row read twice, the two hidden products
    written, their product read, the output written)."""
    d, fe = s["d"], s["fe"]
    act = routed * (2 * d + 3 * fe + d) * BF16
    return 6 * d * fe * routed, hit * expert_params(s) * BF16 + act


def topk_lse(s: dict, rows: int, k: int, itemsize: int) -> tuple[int, int]:
    """(operations, bytes) of the streaming top-k and log-sum-exp summary
    over ``rows`` rows of the vocabulary: each logit read once, an
    exponential, a compare and an add counted per logit; the outputs are
    k values, k indices and one lse per row."""
    v = s["vocab"]
    return 3 * rows * v, rows * v * itemsize + rows * (8 * k + 4)
