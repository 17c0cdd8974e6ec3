"""Flash decode attention for GQA serving (Pallas TPU).

serve_step's hot op: one query token per sequence against a KV cache of up
to 512k positions. The XLA path materializes [B, Hkv, G, T] scores in HBM;
this kernel streams KV blocks through VMEM with the online-softmax
recurrence, keeping only an [G, D] accumulator + [G, 1] (max, sumexp) per
(batch, kv-head) — O(T) HBM reads of K/V and O(1) writes, which is the
memory-roofline optimum for decode.

Grid: (B, Hkv, T/bt) — T minor, so the softmax state carries across KV
blocks in VMEM scratch. Query heads of one KV group (G = Hq/Hkv) ride the
sublane dim together. Validity (cache occupancy, sliding windows, rolling
slots) arrives as a precomputed [B, T] int8 mask, so one kernel serves all
cache layouts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_s, s_s, acc_s):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)
    g, d = q_ref.shape
    bt = k_ref.shape[0]

    @pl.when(ti == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        s_s[...] = jnp.zeros_like(s_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[...].astype(F32)  # [G, D]
    k = k_ref[...].astype(F32)  # [bt, D]
    v = v_ref[...].astype(F32)  # [bt, D]
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32
    ) * (d**-0.5)  # [G, bt]
    ok = valid_ref[...] > 0  # [1, bt]
    scores = jnp.where(ok, scores, NEG_INF)

    m_prev, s_prev = m_s[...], s_s[...]  # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)  # [G, bt]
    corr = jnp.exp(m_prev - m_new)
    s_s[...] = s_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )
    m_s[...] = m_new

    @pl.when(ti == nt - 1)
    def _emit():
        o_ref[...] = (acc_s[...] / jnp.maximum(s_s[...], 1e-30)).astype(
            o_ref.dtype
        )


def _paged_decode_kernel(
    pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_s, s_s, acc_s
):
    b = pl.program_id(0)
    pi = pl.program_id(2)
    npg = pl.num_programs(2)
    g, d = q_ref.shape
    page = k_ref.shape[0]

    @pl.when(pi == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        s_s[...] = jnp.zeros_like(s_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[...].astype(F32)  # [G, D]
    k = k_ref[...].astype(F32)  # [page, D] — the gathered physical page
    v = v_ref[...].astype(F32)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32
    ) * (d**-0.5)  # [G, page]
    # validity is computed in-kernel from (logical position, pos): page pi
    # covers logical positions [pi*page, (pi+1)*page); position pos itself
    # (the token just written) is attended. An unallocated table entry
    # (-1, DMA'd clamped to page 0) is masked wholesale.
    t = pi * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    ok = (t <= pos_ref[b]) & (pt_ref[b, pi] >= 0)
    scores = jnp.where(ok, scores, NEG_INF)

    m_prev, s_prev = m_s[...], s_s[...]  # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    corr = jnp.exp(m_prev - m_new)
    s_s[...] = s_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )
    m_s[...] = m_new

    @pl.when(pi == npg - 1)
    def _emit():
        o_ref[...] = (acc_s[...] / jnp.maximum(s_s[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attn(
    q: jax.Array,  # [B, Hq, D]
    kp: jax.Array,  # [P, Hkv, page, D] global page pool (head-major)
    vp: jax.Array,  # [P, Hkv, page, D]
    page_table: jax.Array,  # [B, NP] i32, -1 = unallocated
    pos: jax.Array,  # [B] i32 per-slot depth (position pos is attended)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Paged flash decode attention: the dense kernel's grid extended to
    gather K/V blocks *through the page table*. The table and positions
    ride in as scalar-prefetch operands (``PrefetchScalarGridSpec``), so
    the K/V BlockSpec index maps can address physical pages — each grid
    step DMAs exactly one page; no [B, T, ...] dense gather ever
    materializes. Grid (B, Hkv, NP), pages minor, online-softmax state in
    VMEM scratch exactly like :func:`decode_attn`. The pool is head-major
    so each K/V block (one page of one kv head) spans the array's last two
    dims whole, the block shape the TPU lowering accepts at any page size
    and head dim."""
    b, hq, d = q.shape
    p_, hkv, page, _ = kp.shape
    npg = page_table.shape[1]
    g = hq // hkv
    qr = q.reshape(b, hkv, g, d)
    pt = jnp.asarray(page_table, jnp.int32)
    posr = jnp.asarray(pos, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, npg),
        in_specs=[
            pl.BlockSpec(
                (None, None, g, d), lambda i, j, pi, pt, ps: (i, j, 0, 0)
            ),
            # physical page via the prefetched table; -1 clamps to page 0
            # for the DMA and the kernel masks the whole block
            pl.BlockSpec(
                (None, None, page, d),
                lambda i, j, pi, pt, ps: (jnp.maximum(pt[i, pi], 0), j, 0, 0),
            ),
            pl.BlockSpec(
                (None, None, page, d),
                lambda i, j, pi, pt, ps: (jnp.maximum(pt[i, pi], 0), j, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, g, d), lambda i, j, pi, pt, ps: (i, j, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 1), F32),
            pltpu.VMEM((g, 1), F32),
            pltpu.VMEM((g, d), F32),
        ],
    )
    out = pl.pallas_call(
        _paged_decode_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(pt, posr, qr, kp, vp)
    return out.reshape(b, hq, d)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def decode_attn(
    q: jax.Array,  # [B, Hq, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    valid: jax.Array,  # [B, T] bool
    *,
    bt: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bt = min(bt, max(128, -(-t // 128) * 128))
    pad_t = (-t) % bt
    if pad_t:
        k = jnp.pad(k, [(0, 0), (0, pad_t), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, pad_t), (0, 0), (0, 0)])
        valid = jnp.pad(valid, [(0, 0), (0, pad_t)])
    tp = t + pad_t

    qr = q.reshape(b, hkv, g, d)
    # [B, Hkv, T, D] layout so the kv-head grid dim indexes a leading axis
    kr = k.transpose(0, 2, 1, 3)
    vr = v.transpose(0, 2, 1, 3)
    val = valid.astype(jnp.int8)[:, None, :]  # [B, 1, T]

    out = pl.pallas_call(
        _decode_kernel,
        grid=(b, hkv, tp // bt),
        in_specs=[
            pl.BlockSpec((None, None, g, d), lambda i, j, ti: (i, j, 0, 0)),
            pl.BlockSpec((None, None, bt, d), lambda i, j, ti: (i, j, ti, 0)),
            pl.BlockSpec((None, None, bt, d), lambda i, j, ti: (i, j, ti, 0)),
            pl.BlockSpec((None, 1, bt), lambda i, j, ti: (i, 0, ti)),
        ],
        out_specs=pl.BlockSpec((None, None, g, d), lambda i, j, ti: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), F32),
            pltpu.VMEM((g, 1), F32),
            pltpu.VMEM((g, d), F32),
        ],
        interpret=interpret,
    )(qr, kr, vr, val)
    return out.reshape(b, hq, d)
