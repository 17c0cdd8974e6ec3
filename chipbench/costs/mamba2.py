"""Operations of the Mamba-2 model's training work, from shapes alone.

A multiply-add counts two operations. The forward of one token counts
every layer's ``in_proj`` and ``out_proj``, the depthwise convolution, the
chunked scan (with chunk ``Q``: the causal half of the chunk's C.B products
and their weighting of x, and the state written and read: ``Q*N + Q*H*P +
4*H*P*N``) and the LM head. The backward counts twice the forward;
recomputation does not count. Sizes come from ``reference.mamba2.sizes``.
"""

from __future__ import annotations


def forward_per_token(s: dict) -> int:
    d, di, n, h, p, k, q = (s[x] for x in
                            ("d", "di", "n", "h", "hd", "k", "chunk"))
    layer = (2 * d * (2 * di + 2 * n + h) + 2 * di * d + 2 * k * (di + 2 * n)
             + q * n + q * h * p + 4 * h * p * n)
    return s["layers"] * layer + 2 * s["vocab"] * s["d"]


def recycled_step(s: dict, kept_rows: int, seq_len: int) -> int:
    """A recycled OBFTF step: forward and backward on the kept rows only
    (the recorded losses stand in for the selection forward)."""
    return 3 * forward_per_token(s) * kept_rows * seq_len
