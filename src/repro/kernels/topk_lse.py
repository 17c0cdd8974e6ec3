"""Fused top-k + logsumexp summary over a blocked vocabulary (Pallas TPU).

The serving engine's retained-outcome buffer compresses each generated
position's [V] logits into ``(top-k values, top-k indices, exact lse)``
— constant size in V — so a late label can still be scored exactly when
it lands in the top-k set and with the tail floor ``lse - min(topk)``
when it misses (see ``repro.serving.recorder``). This kernel computes
the summary in ONE streaming pass over vocab blocks: the online-lse
machinery of ``kernels.xent`` plus a running top-k merge, both held in
VMEM scratch across vocab steps. Nothing of size [T, V] beyond the
logits themselves is ever materialized.

Grid: (T/bt, V/bv), vocab minor — TPU grids iterate the last axis
fastest and sequentially, so the running (max, sumexp, top-k values,
top-k indices) state persists in scratch across the vocab steps of one
token block. Per vocab block the merge concatenates
``[running kp | block bv]`` and runs k rounds of (row argmax, gather
the winner's vocab index by masked reduction, knock the winner out) —
O(k * (kp + bv)) vector work per block, no sort.

Tiling: bt multiple of 8 (sublane); bv and the padded top-k width kp
both multiples of 128 (lane). f32 accumulation throughout. Ties resolve
to the lowest vocab index, matching ``jax.lax.top_k``; outputs come
back value-descending.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
I32 = jnp.int32
NEG_INF = -1e30


def _topk_lse_kernel(
    logits_ref, vals_ref, idx_ref, lse_ref, m_s, s_s, tv_s, ti_s, *, k
):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)
    bt, bv = logits_ref.shape
    kp = tv_s.shape[1]

    @pl.when(vi == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        s_s[...] = jnp.zeros_like(s_s)
        tv_s[...] = jnp.full_like(tv_s, NEG_INF)
        ti_s[...] = jnp.full_like(ti_s, -1)

    block = logits_ref[...].astype(F32)  # [bt, bv]
    m_prev, s_prev = m_s[...], s_s[...]  # [bt, 1]
    bm = jnp.max(block, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, bm)
    s_new = s_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(block - m_new), axis=-1, keepdims=True
    )
    m_s[...] = m_new
    s_s[...] = s_new

    # merge this block into the running top-k: the running entries sit
    # BEFORE the block in the concat so argmax's first-occurrence tie
    # break keeps the lowest vocab index (running entries always came
    # from earlier blocks)
    comb_v = jnp.concatenate([tv_s[...], block], axis=1)  # [bt, kp+bv]
    col = jax.lax.broadcasted_iota(I32, (bt, bv), 1) + vi * bv
    comb_i = jnp.concatenate([ti_s[...], col], axis=1)
    cw = kp + bv
    cpos = jax.lax.broadcasted_iota(I32, (bt, cw), 1)
    opos = jax.lax.broadcasted_iota(I32, (bt, kp), 1)

    def pick(j, carry):
        cv, nvals, nidx = carry
        top = jnp.max(cv, axis=1, keepdims=True)  # [bt, 1]
        # the first position holding the max, by a min over positions: the
        # TPU's own arg-max reduction does not promise which of equal
        # values it returns
        am = jnp.min(jnp.where(cv == top, cpos, cw), axis=1, keepdims=True)
        winner = cpos == am  # [bt, cw] one-hot
        gi = jnp.sum(jnp.where(winner, comb_i, 0), axis=1, keepdims=True)
        write = opos == j
        nvals = jnp.where(write, top, nvals)
        nidx = jnp.where(write, gi, nidx)
        return jnp.where(winner, NEG_INF, cv), nvals, nidx

    _, new_tv, new_ti = jax.lax.fori_loop(
        0,
        k,
        pick,
        (
            comb_v,
            jnp.full((bt, kp), NEG_INF, F32),
            jnp.full((bt, kp), -1, I32),
        ),
    )
    tv_s[...] = new_tv
    ti_s[...] = new_ti

    @pl.when(vi == nv - 1)
    def _emit():
        lse_ref[...] = m_new + jnp.log(s_new)
        vals_ref[...] = new_tv
        idx_ref[...] = new_ti


def _pad_to(x, mult, axis, value):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("k", "bt", "bv", "interpret"))
def topk_lse(
    logits: jax.Array,
    k: int,
    *,
    bt: int = 256,
    bv: int = 2048,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """logits [T,V] -> (vals [T,k] f32 descending, idx [T,k] i32,
    lse [T] f32)."""
    t, v = logits.shape
    if not 0 < k <= v:
        raise ValueError(f"k={k} not in (0, {v}]")
    bt = min(bt, max(8, -(-t // 8) * 8))
    bv = min(bv, max(128, -(-v // 128) * 128))
    kp = max(128, -(-k // 128) * 128)
    lp = _pad_to(_pad_to(logits, bt, 0, 0.0), bv, 1, NEG_INF)
    tp, vp = lp.shape
    grid = (tp // bt, vp // bv)
    vals, idx, lse = pl.pallas_call(
        functools.partial(_topk_lse_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((bt, bv), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bt, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, kp), F32),
            jax.ShapeDtypeStruct((tp, kp), I32),
            jax.ShapeDtypeStruct((tp, 1), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, 1), F32),
            pltpu.VMEM((bt, 1), F32),
            pltpu.VMEM((bt, kp), F32),
            pltpu.VMEM((bt, kp), I32),
        ],
        interpret=interpret,
    )(lp)
    return vals[:t, :k], idx[:t, :k], lse[:t, 0]
