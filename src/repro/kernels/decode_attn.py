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

The paged kernel (``paged_decode_attn``) reads K/V through a page table
instead: grid (B, page blocks), each step gathering the attended pages of
one slot for every kv head by manual DMA. The paged latent kernel
(``paged_latent_attn``, DeepSeek-V2's absorbed MLA) shares that block
gather (:func:`_paged_blocks`): every query head of a slot attends one
latent stream whose first columns are also the values.
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


# K bytes (all kv heads) one grid step of the paged kernel gathers: large
# enough to amortise a grid step's fixed cost, small enough that K and V,
# double-buffered, take 4 MiB of the scoped VMEM
_PAGED_BLOCK_BYTES = 1 << 20


def _pages_per_block(hkv: int, page: int, d: int, itemsize: int,
                     npg: int) -> int:
    """Pages one grid step gathers, from the bytes of one physical page of
    all kv heads: 32 pages of 16 at Qwen3's 8 x 128 bf16 heads, 8 at an
    MHA's 32 heads."""
    return max(1, min(npg, _PAGED_BLOCK_BYTES // (hkv * page * d * itemsize)))


def _paged_blocks(pt_ref, pos_ref, copies, sems, cur_ref, init, compute, *,
                  npg, ppb, page):
    """The block gather of the paged kernels, on grid (slots, page blocks)
    with pages minor. ``copies`` pairs each pool in HBM ([P, ...], a page
    per leading index) with its VMEM buffer [2, ppb, ...]; a grid step
    gathers the attended pages of its block of one slot into buffer
    ``cur`` (one DMA per page and pool) while the next block that attends
    anything is in flight into the other one, then runs ``compute(cur)``.
    ``init`` runs at a slot's first block."""
    b, i = pl.program_id(0), pl.program_id(1)
    nslots, nblk = pl.num_programs(0), pl.num_programs(1)

    def attended(bb, ii):
        """Pages of block (bb, ii) the slot attends: logical pages up to
        pos // page; none when the slot holds no first page (a free
        slot)."""
        last = jnp.minimum(pos_ref[bb] // page, npg - 1)
        n = jnp.clip(last + 1 - ii * ppb, 0, ppb)
        return jnp.where(pt_ref[bb * npg] >= 0, n, 0)

    def each_copy(bb, ii, slot, op):
        """``op`` on the DMA of every attended, allocated page of block
        (bb, ii), one per pool (a page of the head-major K pool holds all
        kv heads contiguously, [Hkv, page, D])."""

        def body(j, carry):
            phys = pt_ref[bb * npg + ii * ppb + j]

            @pl.when(phys >= 0)
            def _():
                for n, (pool, buf) in enumerate(copies):
                    op(pltpu.make_async_copy(
                        pool.at[phys], buf.at[slot, j], sems.at[n, slot]))

            return carry

        jax.lax.fori_loop(0, attended(bb, ii), body, 0)

    def first_slot_from(bb):
        """The first slot >= bb that attends anything (nslots if none)."""
        return jax.lax.while_loop(
            lambda c: (c < nslots)
            & (attended(jnp.minimum(c, nslots - 1), 0) == 0),
            lambda c: c + 1,
            bb,
        )

    @pl.when((b == 0) & (i == 0))
    def _start_first():
        first = first_slot_from(0)
        cur_ref[0] = 0

        @pl.when(first < nslots)
        def _():
            each_copy(first, 0, 0, lambda c: c.start())

    pl.when(i == 0)(init)

    @pl.when(attended(b, i) > 0)
    def _block():
        # this block's pages are in flight into buffer `cur`; start the
        # next block that attends anything (this slot's, else the next
        # such slot's first) into the other buffer before computing
        cur = cur_ref[0]
        more = (i + 1 < nblk) & (
            attended(b, jnp.minimum(i + 1, nblk - 1)) > 0
        )
        nb, ni = jax.lax.cond(
            more,
            lambda: (b, i + 1),
            lambda: (first_slot_from(b + 1), jnp.int32(0)),
        )

        @pl.when(nb < nslots)
        def _():
            each_copy(nb, ni, 1 - cur, lambda c: c.start())
            cur_ref[0] = 1 - cur

        each_copy(b, i, cur, lambda c: c.wait())
        compute(cur)


def _init_softmax(m_s, s_s, acc_s):
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    s_s[...] = jnp.zeros_like(s_s)
    acc_s[...] = jnp.zeros_like(acc_s)


def _paged_decode_kernel(
    pt_ref, pos_ref, q_ref, kp_hbm, vp_hbm, o_ref,
    kbuf, vbuf, sems, cur_ref, m_s, s_s, acc_s, *, npg, ppb,
):
    b, i = pl.program_id(0), pl.program_id(1)
    nblk = pl.num_programs(1)
    _, _, hkv, page, d = kbuf.shape
    bt = ppb * page

    def compute(cur):
        # position pos itself (the token just written) is attended; pages
        # of the buffer not gathered this step hold stale data, masked out
        # of the scores and zeroed out of V
        t0 = i * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        ok = t0 <= pos_ref[b]  # [1, bt]
        ok_v = i * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
        ok_v = ok_v <= pos_ref[b]  # [bt, 1]
        dt = jnp.promote_types(q_ref.dtype, kbuf.dtype)
        for h in range(hkv):
            # bf16 x bf16 products are exact in f32: reading K in the pool's
            # dtype loses nothing against an f32 upcast
            q = q_ref[h].astype(dt)  # [G, D]
            k = kbuf[cur, :, h].reshape(bt, d).astype(dt)  # [bt, D]
            v = vbuf[cur, :, h].reshape(bt, d).astype(F32)
            v = jnp.where(ok_v, v, 0.0)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32
            ) * (d**-0.5)  # [G, bt]
            scores = jnp.where(ok, scores, NEG_INF)
            m_prev, s_prev = m_s[h], s_s[h]  # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1,
                                                keepdims=True))
            p = jnp.exp(scores - m_new)
            corr = jnp.exp(m_prev - m_new)
            s_s[h] = s_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_s[h] = acc_s[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
            )
            m_s[h] = m_new

    _paged_blocks(pt_ref, pos_ref, ((kp_hbm, kbuf), (vp_hbm, vbuf)), sems,
                  cur_ref, lambda: _init_softmax(m_s, s_s, acc_s), compute,
                  npg=npg, ppb=ppb, page=page)

    @pl.when(i == nblk - 1)
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
    """Paged flash decode attention over only the pages a slot attends.

    Grid (B, ceil(NP / ppb)), pages minor. A grid step gathers a block of
    up to ``ppb`` logical pages of one slot (:func:`_pages_per_block`,
    ~1 MiB of K) from the pool in HBM with one DMA per page for all kv
    heads, and loops over the kv heads: the G query heads of a group
    against that head's [ppb * page, D] keys, with the online-softmax
    state of every head in VMEM scratch. The table and positions ride in
    as scalar-prefetch operands. Only logical pages 0 .. pos // page with
    a table entry >= 0 are copied; a block past a slot's depth, and every
    block of a slot with no first page, does no work, and the next
    block that attends anything is prefetched while the current one
    computes (the pattern of JAX's TPU ``paged_attention`` kernel).
    Scores, max, sum and accumulator are f32; a slot that attends nothing
    reads zeros. The table is a prefix of allocated pages up to pos //
    page, as the engine keeps it."""
    b, hq, d = q.shape
    _, hkv, page, _ = kp.shape
    npg = page_table.shape[1]
    g = hq // hkv
    ppb = _pages_per_block(hkv, page, d, kp.dtype.itemsize, npg)
    qr = q.reshape(b, hkv, g, d)
    pt = jnp.asarray(page_table, jnp.int32).reshape(-1)  # 1-D in SMEM
    posr = jnp.asarray(pos, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pl.cdiv(npg, ppb)),
        in_specs=[
            pl.BlockSpec((None, hkv, g, d), lambda i, j, pt, ps: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (None, hkv, g, d), lambda i, j, pt, ps: (i, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, hkv, page, d), kp.dtype),
            pltpu.VMEM((2, ppb, hkv, page, d), vp.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # (K | V, buffer)
            pltpu.SMEM((1,), jnp.int32),  # the buffer in flight for this step
            pltpu.VMEM((hkv, g, 1), F32),
            pltpu.VMEM((hkv, g, 1), F32),
            pltpu.VMEM((hkv, g, d), F32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, npg=npg, ppb=ppb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        # the prefetch chain crosses slots: both axes run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(pt, posr, qr, kp, vp)
    return out.reshape(b, hq, d)


def _paged_latent_kernel(
    pt_ref, pos_ref, q_ref, lat_hbm, o_ref,
    buf, sems, cur_ref, m_s, s_s, acc_s, *, npg, ppb, scale,
):
    b, i = pl.program_id(0), pl.program_id(1)
    nblk = pl.num_programs(1)
    _, _, page, w = buf.shape
    bt = ppb * page
    vd = acc_s.shape[-1]

    def compute(cur):
        # as in _paged_decode_kernel: position pos is attended, pages not
        # gathered this step are masked out of the scores and zeroed out
        # of V
        t0 = i * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        ok = t0 <= pos_ref[b]  # [1, bt]
        ok_v = i * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
        ok_v = ok_v <= pos_ref[b]  # [bt, 1]
        lat = buf[cur].reshape(bt, w)  # [bt, W]
        # bf16 x bf16 products are exact in f32
        scores = jax.lax.dot_general(
            q_ref[...], lat, (((1,), (1,)), ((), ())),
            preferred_element_type=F32,
        ) * scale  # [H, bt]
        scores = jnp.where(ok, scores, NEG_INF)
        v = jnp.where(ok_v, lat[:, :vd].astype(F32), 0.0)  # [bt, R]
        m_prev, s_prev = m_s[...], s_s[...]  # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        s_s[...] = s_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
        )
        m_s[...] = m_new

    _paged_blocks(pt_ref, pos_ref, ((lat_hbm, buf),), sems, cur_ref,
                  lambda: _init_softmax(m_s, s_s, acc_s), compute,
                  npg=npg, ppb=ppb, page=page)

    @pl.when(i == nblk - 1)
    def _emit():
        o_ref[...] = (acc_s[...] / jnp.maximum(s_s[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(jax.jit, static_argnames=("scale", "v_dim", "interpret"))
def paged_latent_attn(
    q: jax.Array,  # [B, H, W]: absorbed query (R), roped query (pe), zeros
    lat: jax.Array,  # [P, page, W] latent page pool: ckv (R), kpe (pe), zeros
    page_table: jax.Array,  # [B, NP] i32, -1 = unallocated
    pos: jax.Array,  # [B] i32 per-slot depth (position pos is attended)
    *,
    scale: float,
    v_dim: int,
    interpret: bool = False,
) -> jax.Array:
    """Paged absorbed-MLA decode attention -> [B, H, v_dim].

    Every query head of a slot attends the slot's one latent stream:
    scores ``q . lat`` over all W columns times ``scale``, values the first
    ``v_dim`` columns of the same rows, so each attended page is read once
    for all heads. The grid, the block gather (``ppb`` pages of ~1 MiB a
    step, the next attended block prefetched) and the f32 online softmax
    are those of :func:`paged_decode_attn`."""
    b, h, w = q.shape
    _, page, _ = lat.shape
    npg = page_table.shape[1]
    ppb = max(1, min(npg, _PAGED_BLOCK_BYTES
                     // (page * w * lat.dtype.itemsize)))
    pt = jnp.asarray(page_table, jnp.int32).reshape(-1)  # 1-D in SMEM
    posr = jnp.asarray(pos, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pl.cdiv(npg, ppb)),
        in_specs=[
            pl.BlockSpec((None, h, w), lambda i, j, pt, ps: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (None, h, v_dim), lambda i, j, pt, ps: (i, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, w), lat.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),  # (pool, buffer)
            pltpu.SMEM((1,), jnp.int32),  # the buffer in flight for this step
            pltpu.VMEM((h, 1), F32),
            pltpu.VMEM((h, 1), F32),
            pltpu.VMEM((h, v_dim), F32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_latent_kernel, npg=npg, ppb=ppb,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, v_dim), q.dtype),
        # the prefetch chain crosses slots: both axes run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(pt, posr, q, lat)


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
