"""Kernel benchmark: XLA-path timing + Pallas VMEM/traffic accounting.

Pallas-TPU kernels cannot be timed on this CPU host (interpret mode runs
the kernel body in Python). What IS measurable and meaningful here:
  * the ref/XLA path wall time (the baseline the kernel replaces),
  * the analytic HBM-traffic model of both paths (the quantity the kernel
    optimizes; derived from shapes, reported as a ratio).

xent traffic model (T tokens, V vocab, f32):
  naive log-softmax path: read logits (2·TV: max+sub pass), write logsoftmax
  (TV), read for gather -> ~4·TV + backward re-reads ~2·TV
  fused kernel: read logits once fwd (TV) + once bwd (TV), save [T] LSE
decode_attn (T cache positions, bf16): XLA materializes [H, T] scores in
  HBM (+2 passes for softmax); flash keeps them in VMEM: traffic -> K/V
  read once (the optimum).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(f, *args, trials=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(trials):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / trials * 1e3


def xent_traffic_ratio(t: int, v: int) -> float:
    naive = 6 * t * v * 4  # materialized log-softmax fwd+bwd (f32)
    fused = 2 * t * v * 2 + 3 * t * 4  # logits bf16 read fwd+bwd + [T] lse
    return naive / fused


def decode_traffic_ratio(t: int, hq: int, hkv: int, d: int) -> float:
    kv = 2 * t * hkv * d * 2  # K/V bf16 read once (both paths)
    scores_hbm = 3 * hq * t * 4  # XLA: write+read+read [Hq, T] f32 scores
    return (kv + scores_hbm) / kv


def main(fast: bool = False) -> list[str]:
    out = ["table,kernel,shape,ms_ref_path,traffic_ratio_vs_naive"]
    shapes = [(2048, 8192)] if fast else [(2048, 8192), (4096, 32768)]
    for t, v in shapes:
        logits = jax.random.normal(jax.random.key(0), (t, v), jnp.float32)
        labels = jax.random.randint(jax.random.key(1), (t,), 0, v)
        f = jax.jit(lambda l, y: ops.xent_loss(l, y, "ref"))
        ms = _time(f, logits, labels)
        out.append(
            f"kernel,xent,T{t}xV{v},{ms:.2f},{xent_traffic_ratio(t, v):.2f}"
        )
    for t in ((4096,) if fast else (4096, 32768)):
        b, hq, hkv, d = 4, 32, 8, 128
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
        valid = jnp.ones((b, t), bool)
        f = jax.jit(lambda q, k, v, m: ops.decode_attn(q, k, v, m, "ref"))
        ms = _time(f, q, k, v, valid)
        out.append(
            f"kernel,decode_attn,T{t},{ms:.2f},{decode_traffic_ratio(t, hq, hkv, d):.2f}"
        )
    # paged decode_attn: the same flash reduction with K/V gathered
    # through a page table. ms times the ref/XLA path (gather pages to the
    # dense layout + attend) that the paged Pallas grid replaces; the
    # traffic model is the dense one — scores stay in VMEM either way and
    # the indirection adds only the [B, NP] int32 table, which is noise —
    # so the ratio column is shared. What paging buys is HBM capacity,
    # priced in selection_bench's kv[*] rows, not bandwidth.
    for t in ((4096,) if fast else (4096, 32768)):
        b, hq, hkv, d, ps = 4, 32, 8, 128, 256
        per = t // ps
        ks = jax.random.split(jax.random.key(1), 4)
        q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
        kp = jax.random.normal(ks[1], (1, b * per, hkv, ps, d), jnp.float32)
        vp = jax.random.normal(ks[2], (1, b * per, hkv, ps, d), jnp.float32)
        pt = jax.random.permutation(ks[3], b * per).reshape(b, per)
        pt = pt.astype(jnp.int32)
        pos = jnp.full((b,), t - 1, jnp.int32)
        f = jax.jit(
            lambda q, kp, vp, pt, pos: ops.paged_decode_attn(
                q, kp, vp, pt, pos, 0, "ref"
            )
        )
        ms = _time(f, q, kp, vp, pt, pos)
        out.append(
            f"kernel,paged_decode_attn,T{t}xP{ps},{ms:.2f},{decode_traffic_ratio(t, hq, hkv, d):.2f}"
        )
    # ledger scatter: the XLA/ref-path wall time the Pallas kernel replaces,
    # plus which scatter variant the batch-size dispatch picks and the
    # analytic per-item vector-work ratio of the block tiling (each item
    # touches one table tile instead of the whole [rows, 128] table).
    from repro.core.history import HistoryConfig
    from repro.core.device_ledger import init_state, record_priority
    from repro.kernels.ledger import BLOCK_TILES, LANES, resolve_variant
    from repro.kernels.ops import LEDGER_BLOCK_MIN_BATCH

    cap = 1 << 14
    lcfg = HistoryConfig(capacity=cap)
    rows = cap // LANES
    for b in ((64, 1024) if fast else (64, 1024, 4096)):
        ids = jax.random.randint(jax.random.key(b), (b,), 0, 4 * cap, jnp.int32)
        losses = jax.random.normal(jax.random.key(b + 1), (b,)) * 2 + 5
        f = jax.jit(
            lambda st, i, l: record_priority(lcfg, st, i, l, 3, impl="ref")
        )
        st = init_state(lcfg)
        ms = _time(lambda i, l: f(st, i, l)[1], ids, losses)
        var = resolve_variant(None, b, LEDGER_BLOCK_MIN_BATCH, rows)
        tiles = min(BLOCK_TILES, rows) if var == "block" else 1
        out.append(
            f"kernel,ledger_scatter,C{cap}xB{b},{ms:.2f},"
            f"{var}(tiles={tiles};work/item=1/{tiles})"
        )
    # ledger lookup: gather (VPU row-select) vs the one-hot MXU matmul
    # variant — bit-identical results, ratio >1 means the matmul wins
    # (expected on MXU hardware at small batch; on CPU the gather usually
    # does). Both paths jitted, same table/ids.
    from repro.core.device_ledger import lookup as led_lookup, record as led_record

    b = 256
    ids = jax.random.randint(jax.random.key(7), (b,), 0, 4 * cap, jnp.int32)
    st_l = jax.jit(
        lambda st, i, l: led_record(lcfg, st, i, l, 1)
    )(init_state(lcfg), ids, jnp.ones((b,)))
    f_g = jax.jit(lambda st, i: led_lookup(st, i, variant="gather")[0])
    f_o = jax.jit(lambda st, i: led_lookup(st, i, variant="onehot")[0])
    ms_g = _time(f_g, st_l, ids)
    ms_o = _time(f_o, st_l, ids)
    out.append(
        f"kernel,ledger_lookup_onehot,C{cap}xB{b},{ms_o:.2f},"
        f"{ms_g / max(ms_o, 1e-9):.2f}"
    )
    # ssd: XLA chunked vs sequential-recurrence cost
    bsz, s, h, p, g, n = 2, 2048, 8, 64, 1, 64
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (bsz, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (bsz, s, g, n)) * 0.5
    cm = jax.random.normal(ks[4], (bsz, s, g, n)) * 0.5
    f_chunk = jax.jit(lambda *args: ops.ssd_scan(*args, chunk=128, impl="ref"))
    f_seq = jax.jit(lambda *args: ref.ssd_ref(*args))
    ms_c = _time(f_chunk, x, dt, a, bm, cm)
    ms_s = _time(f_seq, x, dt, a, bm, cm)
    out.append(f"kernel,ssd_chunked_vs_sequential,S{s},{ms_c:.2f},{ms_s / max(ms_c, 1e-9):.2f}")
    return out


if __name__ == "__main__":
    print("\n".join(main()))
