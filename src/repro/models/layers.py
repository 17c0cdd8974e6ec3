"""Transformer layer primitives: norms, RoPE, GQA/MQA/SWA attention, MLA.

Conventions:
* activations bf16 (cfg.compute_dtype), reductions/softmax/norms in f32;
* matmuls pass preferred_element_type=f32 where accumulation matters;
* every attention entry point has train/prefill (full-sequence) and decode
  (single token + KV cache) forms; caches are per-layer dicts that the model
  stacks over layers via scan;
* sliding-window attention uses a rolling cache (slot = pos % window) so the
  long_500k cell is O(window) memory — the reason Mixtral runs that cell.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.params import ParamSpec

Array = jax.Array
F32 = jnp.float32

_MASK_VALUE = -1e30


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------


def rmsnorm(x: Array, w: Array, eps: float) -> Array:
    xf = x.astype(F32)
    rms = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (xf * rms).astype(x.dtype) * w.astype(x.dtype)


def rmsnorm_spec(d: int, axis: Optional[str] = "embed") -> ParamSpec:
    return ParamSpec((d,), (axis,), init="ones")


def rope(
    x: Array, positions: Array, theta: float, inv: Any = None,
    scale: float = 1.0,
) -> Array:
    """Rotary embedding over the last dim. x [..., S, H, D]; positions [S]
    (shared across the batch) or [B, S] (per-example positions — the
    continuous-batching decode path, where every slot sits at its own
    depth). ``inv`` [D/2] replaces the plain inverse frequencies of
    ``theta`` and ``scale`` multiplies cos and sin (YaRN, :func:`rope_cfg`).
    """
    d = x.shape[-1]
    if inv is None:
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[..., None] * inv  # [S, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    cos = cos[..., None, :]  # broadcast over heads: [S, 1, D/2]
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek's ``yarn_get_mscale``: 0.1 * mscale * ln(factor) + 1."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: ModelConfig, dim: int) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies ([dim/2] float32), as
    ``DeepseekV2YarnRotaryEmbedding`` computes them: rotary dims below the
    correction range of ``beta_fast`` / ``beta_slow`` keep the plain
    frequency, dims above it take the frequency over ``factor``, and a
    linear ramp joins the two."""
    base, f = cfg.rope_theta, cfg.yarn_factor
    ar = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    extra = (1.0 / (np.float32(base) ** ar)).astype(np.float32)
    inter = (1.0 / (np.float32(f) * np.float32(base) ** ar)).astype(
        np.float32
    )

    def corr_dim(rot: float) -> float:
        return (dim * math.log(cfg.yarn_original_max_pos
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1).astype(np.float32)
    keep = 1.0 - ramp  # 1 where the plain frequency is kept
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def rope_cfg(x: Array, positions: Array, cfg: ModelConfig) -> Array:
    """:func:`rope` as the config asks: plain at ``rope_theta``, or
    DeepSeek's YaRN when ``yarn_factor`` is set (cos and sin times
    mscale(factor, mscale) / mscale(factor, mscale_all_dim), 1 where both
    are equal)."""
    if not cfg.yarn_factor:
        return rope(x, positions, cfg.rope_theta)
    scale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return rope(x, positions, cfg.rope_theta,
                jnp.asarray(yarn_inv_freq(cfg, x.shape[-1])), scale)


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------


def causal_mask(
    q_pos: Array, k_pos: Array, window: Optional[int] = None
) -> Array:
    """[..., S_q, S_k] boolean keep-mask: causal, optionally windowed."""
    keep = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        keep &= k_pos[None, :] > (q_pos[:, None] - window)
    return keep


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def gqa_specs(cfg: ModelConfig) -> dict[str, ParamSpec]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = d**-0.5
    p = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim"), scale=s),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), scale=s),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), scale=s),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"), scale=(h * hd) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_spec(hd, "head_dim")
        p["k_norm"] = rmsnorm_spec(hd, "head_dim")
    return p


def _qkv(x: Array, p: dict, cfg: ModelConfig, positions: Array):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    # context parallelism: explicit full-seq K/V gather (RS backward);
    # no-op unless the active sharding rules set seq_axis
    from repro.distributed.sharding import cp_kv_gather

    k = cp_kv_gather(k, 1)
    v = cp_kv_gather(v, 1)
    return q, k, v


def _gqa_core(q: Array, k: Array, v: Array, keep: Array, n_q_heads: int) -> Array:
    """q [B,S,Hq,D]; k,v [B,T,Hkv,D]; keep [S,T] or [B,S,T] -> [B,S,Hq,D]."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    q = q.reshape(b, s, hkv, g, d)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", q, k, preferred_element_type=F32
    ) * (d**-0.5)
    keep_b = keep if keep.ndim == 3 else keep[None]
    scores = jnp.where(keep_b[:, None, None], scores, _MASK_VALUE)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(b, s, hq, d)


def _gqa_blocked(
    q: Array,
    k: Array,
    v: Array,
    positions: Array,
    window: Optional[int],
    block: int = 1024,
) -> Array:
    """Memory-bounded causal attention: 2-level blocking (Q outer, KV inner)
    with online softmax. Peak extra memory is one [B, Hkv, G, bq, bk] score
    tile (f32) + the per-Q-block accumulator — never anything O(S^2) or
    O(S x bk). This is the XLA-path analogue of a flash kernel; the Pallas
    kernels target the same math on TPU. Exact up to fp rounding.
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    nb = (s + block - 1) // block
    pad = nb * block - s
    if pad:
        q = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)])
        k = jnp.pad(k, [(0, 0), (0, pad), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, pad), (0, 0), (0, 0)])
        kpos = jnp.pad(positions, (0, pad), constant_values=-1)
        qpos = jnp.pad(positions, (0, pad), constant_values=-1)
    else:
        kpos = qpos = positions
    sp = s + pad
    qb = q.reshape(b, nb, block, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(b, nb, block, hkv, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nb, block, hkv, dv).transpose(1, 0, 3, 2, 4)
    pqb = qpos.reshape(nb, block)
    pkb = kpos.reshape(nb, block)
    scale = d**-0.5

    def q_block(args):
        qi, pq = args
        # qi [B, Hkv, G, bq, D]; inner online-softmax scan over KV blocks
        def body(carry, blk):
            m, l, acc = carry
            kj, vj, pk = blk  # [B,Hkv,bk,D], [B,Hkv,bk,Dv], [bk]
            s_ij = jnp.einsum(
                "bkgqd,bktd->bkgqt", qi, kj, preferred_element_type=F32
            ) * scale
            keep = (pk[None, :] <= pq[:, None]) & (pk[None, :] >= 0)
            if window is not None:
                keep &= pk[None, :] > (pq[:, None] - window)
            s_ij = jnp.where(keep[None, None, None], s_ij, _MASK_VALUE)
            m_new = jnp.maximum(m, jnp.max(s_ij, axis=-1))
            p_ij = jnp.exp(s_ij - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p_ij, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,bktd->bkgqd", p_ij.astype(vj.dtype), vj,
                preferred_element_type=F32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, block), _MASK_VALUE, F32)
        l0 = jnp.zeros((b, hkv, g, block), F32)
        acc0 = jnp.zeros((b, hkv, g, block, dv), F32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, pkb))
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    out = jax.lax.map(q_block, (qb, pqb))  # [nb, B, Hkv, G, bq, Dv]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sp, hq, dv)
    return out[:, :s]


# Sequences at or above this length take the blocked (flash-style) path.
BLOCKED_ATTN_MIN_SEQ = 8192


def gqa_attend(
    x: Array, p: dict, cfg: ModelConfig, positions: Array
) -> Array:
    """Training/prefill full-sequence attention. x [B,S,D] -> [B,S,D]."""
    q, k, v = _qkv(x, p, cfg, positions)
    if x.shape[1] >= cfg.blocked_attn_min:
        out = _gqa_blocked(q, k, v, positions, cfg.sliding_window)
    else:
        keep = causal_mask(positions, positions, cfg.sliding_window)
        out = _gqa_core(q, k, v, keep, cfg.num_heads)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def gqa_cache_len(cfg: ModelConfig, max_seq: int) -> int:
    return min(max_seq, cfg.sliding_window or max_seq)


def _kv_quant(x: Array) -> tuple[Array, Array]:
    """[..., hd] -> (int8 values, f32 scale over the head_dim)."""
    xf = x.astype(F32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xf / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequant(q: Array, scale: Array, dtype) -> Array:
    return (q.astype(F32) * scale[..., None].astype(F32)).astype(dtype)


def gqa_init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> dict:
    t = gqa_cache_len(cfg, max_seq)
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (batch, t, kv, hd)
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], F32),
            "v_scale": jnp.zeros(shape[:-1], F32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_fill_cache(
    x: Array, p: dict, cfg: ModelConfig, positions: Array, max_seq: int
) -> tuple[Array, dict]:
    """Prefill: returns (output, cache holding the last cache_len tokens)."""
    q, k, v = _qkv(x, p, cfg, positions)
    if x.shape[1] >= cfg.blocked_attn_min:
        out = _gqa_blocked(q, k, v, positions, cfg.sliding_window)
    else:
        keep = causal_mask(positions, positions, cfg.sliding_window)
        out = _gqa_core(q, k, v, keep, cfg.num_heads)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    t = gqa_cache_len(cfg, max_seq)
    s = x.shape[1]
    if t >= s:
        pad = [(0, 0), (0, t - s), (0, 0), (0, 0)]
        cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    else:
        # rolling window: slot j holds position p with p % t == j
        last = jax.lax.dynamic_slice_in_dim(k, s - t, t, axis=1)
        lastv = jax.lax.dynamic_slice_in_dim(v, s - t, t, axis=1)
        shift = s % t
        cache = {
            "k": jnp.roll(last, shift, axis=1),
            "v": jnp.roll(lastv, shift, axis=1),
        }
    if cfg.kv_cache_dtype == "int8":
        qk, sk = _kv_quant(cache["k"])
        qv, sv = _kv_quant(cache["v"])
        cache = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    return out, cache


def gqa_decode(
    x: Array, p: dict, cfg: ModelConfig, cache: dict, pos: Array, max_seq: int
) -> tuple[Array, dict]:
    """Single-token decode. x [B,1,D]; pos = tokens seen so far, a scalar
    (whole batch at one depth, the lockstep path) or a [B] vector (every
    slot at its own depth — the continuous-batching serving engine)."""
    t = gqa_cache_len(cfg, max_seq)
    per_slot = pos.ndim == 1 and pos.shape[0] == x.shape[0]
    rope_pos = pos[:, None] if per_slot else (
        pos[None] if pos.ndim == 0 else pos
    )
    q, k, v = _qkv(x, p, cfg, rope_pos)
    slot = pos % t
    if per_slot:
        bidx = jnp.arange(x.shape[0])

        def upd(c, n):  # batched one-row scatter: row `slot[b]` of example b
            return c.at[bidx, slot].set(n[:, 0])
    else:

        def upd(c, n):
            return jax.lax.dynamic_update_slice_in_dim(c, n, slot, axis=1)

    int8_cache = cfg.kv_cache_dtype == "int8"
    if int8_cache:
        qk, sk = _kv_quant(k)
        qv, sv = _kv_quant(v)
        new_cache = {
            "k": upd(cache["k"], qk),
            "v": upd(cache["v"], qv),
            "k_scale": upd(cache["k_scale"], sk),
            "v_scale": upd(cache["v_scale"], sv),
        }
        ck = _kv_dequant(new_cache["k"], new_cache["k_scale"], x.dtype)
        cv = _kv_dequant(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        ck = upd(cache["k"], k)
        cv = upd(cache["v"], v)
        new_cache = {"k": ck, "v": cv}
    # slot j holds position pos - ((pos - j) mod t); valid if within window
    j = jnp.arange(t)
    posq = pos[:, None] if per_slot else pos  # [B,1] or scalar
    slot_pos = posq - jnp.mod(posq - j, t)  # [B,T] or [T]
    valid = slot_pos >= 0
    if cfg.sliding_window is not None:
        valid &= slot_pos > posq - cfg.sliding_window
    keep = valid[:, None, :] if per_slot else valid[None, :]  # [B,1,T]/[1,T]
    out = _gqa_core(q, ck, cv, keep, cfg.num_heads)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, new_cache


def gqa_paged_init_cache(
    cfg: ModelConfig, num_pages: int, page_size: int, dtype
) -> dict:
    """One layer's slice of the global KV page pool: [P, kv, page, hd].

    Unlike ``gqa_init_cache`` there is no per-slot reservation — physical
    pages are a shared pool, and a per-slot page table (held by the
    serving engine's state, not the cache) maps logical block -> page.
    Head-major pages make one (page, kv-head) block the pool's last two
    dims, the tile the TPU kernel's BlockSpec can address."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (num_pages, kv, page_size, hd)
    return {"kp": jnp.zeros(shape, dtype), "vp": jnp.zeros(shape, dtype)}


def _paged_write(pool: Array, layer: Array, page: Array, off: Array,
                 rows: Array) -> Array:
    """Write row b of ``rows`` at page ``page[b]``, offset ``off[b]`` of
    layer ``layer`` of a stacked page pool: [L, P, kv, page, hd] at
    ``[layer, page, :, off]`` (rows [B, kv, hd]) or [L, P, page, W] at
    ``[layer, page, off]`` (rows [B, W]). A page of -1 (unallocated) drops
    the write, so a freed slot can never scribble on a page that was
    reallocated to someone else."""
    # -1 must become one-past-end before the scatter: negative indices
    # wrap numpy-style BEFORE mode="drop" filters, so a raw -1 would
    # scribble on the pool's last page instead of dropping
    page = jnp.where(page >= 0, page, pool.shape[1])
    if pool.ndim == 5:
        # one scatter index per (slot, kv head): each update is one
        # minor-dim row of hd, which XLA writes in the pool's own layout.
        # A [kv, hd] window across the page dim makes the TPU compiler lay
        # the whole stack out page-row-major for the scatter and copy it
        # back for the kernel, in every layer.
        heads = jnp.arange(pool.shape[2])[None]
        idx = (layer, page[:, None], heads, off[:, None])
    else:
        idx = (layer, page, off)
    return pool.at[idx].set(rows.astype(pool.dtype), mode="drop")


def gqa_paged_decode(
    x: Array, p: dict, cfg: ModelConfig, cache: dict, page_table: Array,
    pos: Array, layer: Array,
) -> tuple[Array, dict]:
    """Single-token decode of layer ``layer`` through the stacked paged KV
    pool.

    x [B,1,D]; cache {"kp","vp": [L, P, kv, page, hd]}, every layer's
    pool; page_table [B, NP] (physical page per logical block, -1 =
    unallocated: the write is dropped, see :func:`_paged_write`); pos [B]
    per-slot depth. Only the new token's row of layer ``layer`` is
    written, so a decode step carrying the pool through its layer scan
    keeps it in place.

    The ref path gathers the slot's pages back into the dense [B, T, ...]
    layout and runs the exact ``gqa_decode`` einsum chain (``_gqa_core``),
    so a paged engine at temperature 0 is BIT-identical to the dense one;
    the Pallas path (on a TPU, ``kernels.ops.default_impl``) streams pages
    of layer ``layer`` through ``kernels.ops.paged_decode_attn`` without
    materializing [B, T, ...].
    """
    from repro.kernels import ops as kops

    ps = cache["kp"].shape[3]
    npages = page_table.shape[1]
    t = npages * ps
    b = x.shape[0]
    q, k, v = _qkv(x, p, cfg, pos[:, None])
    page = page_table[jnp.arange(b), pos // ps]  # [B]; -1 when unallocated
    off = pos % ps
    new_cache = {
        "kp": _paged_write(cache["kp"], layer, page, off, k[:, 0]),
        "vp": _paged_write(cache["vp"], layer, page, off, v[:, 0]),
    }
    impl = kops.default_impl()
    if impl == "ref":
        # gather-to-dense + the dense path's own mask/einsum chain. Junk in
        # never-written or stale page offsets is masked to -1e30 before the
        # softmax, so its weight underflows to exactly 0.0 — same as the
        # dense cache's own stale rows.
        pt = jnp.maximum(page_table, 0)  # clamp -1: masked anyway
        kv_, hd = cache["kp"].shape[2], cache["kp"].shape[4]

        def dense(pool):  # [B, NP, kv, page, hd] -> [B, T, kv, hd]
            return pool[layer, pt].swapaxes(2, 3).reshape(b, t, kv_, hd)

        ck, cv = dense(new_cache["kp"]), dense(new_cache["vp"])
        keep = (jnp.arange(t)[None] <= pos[:, None])[:, None]  # [B,1,T]
        out = _gqa_core(q, ck, cv, keep, cfg.num_heads)
    else:
        o = kops.paged_decode_attn(
            q[:, 0], new_cache["kp"], new_cache["vp"], page_table, pos,
            layer, impl=impl,
        )
        out = o[:, None].astype(x.dtype)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_specs(cfg: ModelConfig) -> dict[str, ParamSpec]:
    d, h = cfg.d_model, cfg.num_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    nope, pe, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    s = d**-0.5
    if qr:  # q through a low-rank latent (DeepSeek-V2)
        q = {
            "wq_a": ParamSpec((d, qr), ("embed", "q_lora"), scale=s),
            "q_norm": rmsnorm_spec(qr, "q_lora"),
            "wq_b": ParamSpec((qr, h, nope + pe), ("q_lora", "heads", "head_dim"), scale=qr**-0.5),
        }
    else:  # q_lora_rank null (DeepSeek-V2-Lite): one [d, h, nope + pe]
        q = {"wq": ParamSpec((d, h, nope + pe), ("embed", "heads", "head_dim"), scale=s)}
    return {
        **q,
        "wkv_a": ParamSpec((d, r + pe), ("embed", "kv_lora"), scale=s),
        "kv_norm": rmsnorm_spec(r, "kv_lora"),
        "wkv_b": ParamSpec((r, h, nope + vd), ("kv_lora", "heads", "head_dim"), scale=r**-0.5),
        "wo": ParamSpec((h, vd, d), ("heads", "head_dim", "embed"), scale=(h * vd) ** -0.5),
    }


def _yarn_mscale2(cfg: ModelConfig) -> float:
    """mscale(factor, mscale_all_dim)^2 under DeepSeek's YaRN (~1.590 at
    factor 40, mscale_all_dim 0.707); 1 without it."""
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return 1.0


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """The MLA softmax scale: (nope + pe)^-0.5, times YaRN's mscale^2."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 \
        * _yarn_mscale2(cfg)


def _mla_q(x: Array, p: dict, cfg: ModelConfig, positions: Array):
    dt = x.dtype
    nope, pe = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if "wq" in p:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    else:
        cq = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(dt)), p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"].astype(dt))
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = rope_cfg(q_pe, positions, cfg)
    return q_nope, q_pe


def _mla_kv_latent(x: Array, p: dict, cfg: ModelConfig, positions: Array):
    dt = x.dtype
    r = cfg.kv_lora_rank
    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
    ckv, k_pe = kv_a[..., :r], kv_a[..., r:]
    ckv = rmsnorm(ckv, p["kv_norm"], cfg.norm_eps)
    k_pe = rope_cfg(k_pe[..., None, :], positions, cfg)[..., 0, :]
    return ckv, k_pe  # [B,S,R], [B,S,pe]


def mla_attend(x: Array, p: dict, cfg: ModelConfig, positions: Array) -> Array:
    """Full-sequence MLA (train/prefill): expand the latent into K/V.

    Long sequences route through the blocked helper by concatenating the
    nope and rope halves into one qk dim (k_pe broadcast across heads), so
    the [S, S] score matrix is never materialized at 32k.
    """
    dt = x.dtype
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    h = cfg.num_heads
    q_nope, q_pe = _mla_q(x, p, cfg, positions)
    ckv, k_pe = _mla_kv_latent(x, p, cfg, positions)
    kv = jnp.einsum("bsr,rhk->bshk", ckv, p["wkv_b"].astype(dt))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    # Ulysses resharding (no-op unless rules enable it): attention core
    # runs head-sharded over the full sequence; a2a in, a2a out. The
    # alternative — gathering the EXPANDED 128-head K/V across sequence
    # shards — moves ~70x more bytes than the q/k/v a2a set.
    from repro.distributed.sharding import ulysses_constraint as _ul

    q_nope = _ul(q_nope, "heads")
    q_pe = _ul(q_pe, "heads")
    k_nope = _ul(k_nope, "heads")
    v = _ul(v, "heads")
    scale_fix = mla_softmax_scale(cfg)
    if x.shape[1] >= cfg.blocked_attn_min:
        qcat = jnp.concatenate([q_nope, q_pe], axis=-1)
        if _yarn_mscale2(cfg) != 1.0:  # _gqa_blocked scales by d_qk^-0.5
            qcat = qcat * _yarn_mscale2(cfg)
        kcat = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], q_pe.shape[:1] + (k_pe.shape[1], h, k_pe.shape[-1]))],
            axis=-1,
        )
        out = _gqa_blocked(qcat, kcat, v, positions, None)
    else:
        scores = (
            jnp.einsum("bshk,bthk->bhst", q_nope, k_nope, preferred_element_type=F32)
            + jnp.einsum("bshk,btk->bhst", q_pe, k_pe, preferred_element_type=F32)
        ) * scale_fix
        keep = causal_mask(positions, positions)
        scores = jnp.where(keep[None, None], scores, _MASK_VALUE)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        out = jnp.einsum("bhst,bthv->bshv", w, v)
    out = _ul(out, "seq")  # a2a back: seq-sharded, full heads
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(dt))


def mla_init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> dict:
    return {
        "ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "kpe": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dtype),
    }


def mla_fill_cache(
    x: Array, p: dict, cfg: ModelConfig, positions: Array, max_seq: int
) -> tuple[Array, dict]:
    out = mla_attend(x, p, cfg, positions)
    ckv, k_pe = _mla_kv_latent(x, p, cfg, positions)
    s = x.shape[1]
    pad = [(0, 0), (0, max_seq - s), (0, 0)]
    return out, {"ckv": jnp.pad(ckv, pad), "kpe": jnp.pad(k_pe, pad)}


def mla_decode(
    x: Array, p: dict, cfg: ModelConfig, cache: dict, pos: Array, max_seq: int
) -> tuple[Array, dict]:
    """Absorbed-weight decode: attention runs entirely in the latent space.

    The compressed cache (R + pe floats per token — MLA's whole point) is
    queried by absorbing wkv_b's K-half into q and applying the V-half after
    the weighted latent sum. Nothing of size [T, H, head_dim] is ever built.
    """
    dt = x.dtype
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    per_slot = pos.ndim == 1 and pos.shape[0] == x.shape[0]
    rope_pos = pos[:, None] if per_slot else (
        pos[None] if pos.ndim == 0 else pos
    )
    q_nope, q_pe = _mla_q(x, p, cfg, rope_pos)
    ckv_new, kpe_new = _mla_kv_latent(x, p, cfg, rope_pos)
    if per_slot:
        bidx = jnp.arange(x.shape[0])
        ckv = cache["ckv"].at[bidx, pos].set(ckv_new[:, 0])
        kpe = cache["kpe"].at[bidx, pos].set(kpe_new[:, 0])
    else:
        ckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv_new, pos, axis=1
        )
        kpe = jax.lax.dynamic_update_slice_in_dim(
            cache["kpe"], kpe_new, pos, axis=1
        )

    wkv_k = p["wkv_b"][..., :nope].astype(dt)  # [R, H, nope]
    wkv_v = p["wkv_b"][..., nope:].astype(dt)  # [R, H, vd]
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wkv_k)
    scale = mla_softmax_scale(cfg)
    scores = (
        jnp.einsum("bshr,btr->bhst", q_lat, ckv, preferred_element_type=F32)
        + jnp.einsum("bshk,btk->bhst", q_pe, kpe, preferred_element_type=F32)
    ) * scale
    if per_slot:
        valid = jnp.arange(max_seq)[None, :] <= pos[:, None]  # [B, T]
        scores = jnp.where(valid[:, None, None], scores, _MASK_VALUE)
    else:
        valid = jnp.arange(max_seq)[None, :] <= pos  # [1, T]
        scores = jnp.where(valid[None, None], scores, _MASK_VALUE)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    ctx = jnp.einsum("bhst,btr->bshr", w, ckv)
    out = jnp.einsum("bshr,rhv->bshv", ctx, wkv_v)
    out = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(dt))
    return out, {"ckv": ckv, "kpe": kpe}


def mla_latent_width(cfg: ModelConfig) -> int:
    """Columns of a latent page row: R + pe rounded up to the 128 lanes
    of a TPU tile (576 -> 640 at DeepSeek-V2's widths), the width the
    chip's tiled layout allocates anyway and the kernel's DMA can slice."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def mla_paged_init_cache(
    cfg: ModelConfig, num_pages: int, page_size: int, dtype
) -> dict:
    """One layer's slice of the latent page pool: [P, page, W].

    A token holds one latent row: the normed ``ckv`` (R columns), the
    roped ``kpe`` (pe columns), then zeros up to :func:`mla_latent_width`.
    At DeepSeek-V2's widths in bf16 that is 576 x 2 B = 1,152 B of latent
    in 640 x 2 B = 1,280 B allocated a token a layer."""
    return {"lat": jnp.zeros((num_pages, page_size, mla_latent_width(cfg)),
                             dtype)}


def mla_paged_decode(
    x: Array, p: dict, cfg: ModelConfig, cache: dict, page_table: Array,
    pos: Array, layer: Array,
) -> tuple[Array, dict]:
    """Absorbed-weight decode of layer ``layer`` through the stacked latent
    page pool.

    x [B,1,D]; cache {"lat": [L, P, page, W]}; page_table [B, NP] (-1 =
    unallocated: the write is dropped, see :func:`_paged_write`); pos [B]
    per-slot depth. The new token's latent is written at ``pos`` in layer
    ``layer``; the query absorbs wkv_b's K half ([B, H, R] beside the
    roped [B, H, pe], zeros to W) and attends one latent stream for all
    heads, whose first R columns are the values
    (``kernels.ops.paged_latent_attn``); wkv_b's V half and wo then apply
    to the weighted latent sum, as in :func:`mla_decode`."""
    from repro.kernels import ops as kops

    dt = x.dtype
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    b = x.shape[0]
    q_nope, q_pe = _mla_q(x, p, cfg, pos[:, None])
    ckv, kpe = _mla_kv_latent(x, p, cfg, pos[:, None])
    pool = cache["lat"]
    ps, w = pool.shape[2], pool.shape[3]
    pad = [(0, 0), (0, 0), (0, w - r - cfg.qk_rope_head_dim)]
    page = page_table[jnp.arange(b), pos // ps]
    row = jnp.pad(jnp.concatenate([ckv, kpe], -1), pad)[:, 0]  # [B, W]
    lat = _paged_write(pool, layer, page, pos % ps, row)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope,
                       p["wkv_b"][..., :nope].astype(dt))
    q = jnp.pad(jnp.concatenate([q_lat, q_pe], -1)[:, 0], pad)  # [B, H, W]
    ctx = kops.paged_latent_attn(q, lat, page_table, pos, layer,
                                 scale=mla_softmax_scale(cfg), v_dim=r)
    out = jnp.einsum("bhr,rhv->bhv", ctx.astype(dt),
                     p["wkv_b"][..., nope:].astype(dt))
    out = jnp.einsum("bhv,hvd->bd", out, p["wo"].astype(dt))[:, None]
    return out, {"lat": lat}


# ---------------------------------------------------------------------------
# Dense FFN (SwiGLU)
# ---------------------------------------------------------------------------


def mlp_specs(d: int, f: int, gelu: bool = False) -> dict[str, ParamSpec]:
    p = {
        "w1": ParamSpec((d, f), ("embed", "mlp"), scale=d**-0.5),
        "w2": ParamSpec((f, d), ("mlp", "embed"), scale=f**-0.5),
    }
    if not gelu:  # SwiGLU gate
        p["w3"] = ParamSpec((d, f), ("embed", "mlp"), scale=d**-0.5)
    return p


def mlp(x: Array, p: dict) -> Array:
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w1"].astype(dt))
    if "w3" in p:  # SwiGLU
        h = jax.nn.silu(h) * jnp.einsum("bsd,df->bsf", x, p["w3"].astype(dt))
    else:  # GPTBigCode-style GELU (granite)
        h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w2"].astype(dt))


def swiglu_tokens(x: Array, w1: Array, w3: Array, w2: Array) -> Array:
    """SwiGLU over a flat token axis (used by MoE expert compute)."""
    h = jax.nn.silu(x @ w1) * (x @ w3)
    return h @ w2


Params = dict[str, Any]
