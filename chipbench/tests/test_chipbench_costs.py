"""The operation and byte counts against hand-computed values at the
published Qwen3-14B widths (8 of 40 layers, as the cell runs)."""

import json
import os

import pytest

from chipbench.costs import qwen3 as C
from chipbench.reference import qwen3 as R

CONF = os.path.join(os.path.dirname(__file__), "..", "configs",
                    "qwen3-14b-8layer.json")


@pytest.fixture(scope="module")
def s():
    with open(CONF) as f:
        return R.sizes(json.load(f))


def test_sizes(s):
    assert (s["d"], s["f"], s["h"], s["kv"], s["hd"], s["layers"],
            s["vocab"]) == (5120, 17408, 40, 8, 128, 8, 151936)


def test_params(s):
    # q, k, v: 5120 * (40 + 2 * 8) * 128 = 36,700,160
    # o: 40 * 128 * 5120 = 26,214,400; MLP: 3 * 5120 * 17408 = 267,386,880
    assert C.layer_matmul_params(s) == 330_301_440
    # 8 * (330,301,440 + 2 * 5120 + 2 * 128) + 5120 + 151936 * 5120
    assert C.non_embedding_params(s) == 3_420_412_928


def test_decode(s):
    # 2 * (8 * 330,301,440 + 151936 * 5120) per row, 4 * 8 * 40 * 128 per key
    assert C.decode_flops(s, 1, 1) == 6_840_811_520
    assert C.decode_flops(s, 64, 0) == 64 * 6_840_647_680
    # KV: 8 layers * 2 (k, v) * 8 heads * 128 * 2 B = 32 KiB a token
    assert C.kv_bytes_per_token(s) == 32768
    # the non-embedding weights once (bf16) and one embedding row a row
    assert C.decode_bytes(s, 64, 0) == 3_420_412_928 * 2 + 64 * 5120 * 2
    assert C.decode_bytes(s, 1, 100) - C.decode_bytes(s, 1, 0) == 3_276_800


def test_prefill(s):
    # 2 * 8 * 330,301,440 * 1024 + 163,840 * (1024 * 1025 / 2)
    #   + 2 * 151936 * 5120 (the LM head at the last position only)
    assert C.prefill_flops(s, [1024]) == (5_411_658_792_960
                                          + 85_983_232_000
                                          + 1_555_824_640)
    assert C.prefill_flops(s, [7, 9]) == C.prefill_flops(s, [7]) + \
        C.prefill_flops(s, [9])


def test_kernels(s):
    flops, nbytes = C.paged_attn(s, rows=2, keys=300)
    assert flops == 4 * 8 * 40 * 128 * 300
    assert nbytes == 300 * 32768 + 2 * 2 * 8 * 40 * 128 * 2
    flops, nbytes = C.topk_lse(s, rows=64, k=64, itemsize=2)
    assert nbytes == 64 * 151936 * 2 + 64 * (8 * 64 + 4)
    assert flops == 3 * 64 * 151936
