"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array
F32 = jnp.float32


def xent_ref(logits: Array, labels: Array) -> tuple[Array, Array]:
    """Per-token CE. logits [T,V], labels [T] -> (loss [T], lse [T]), f32.

    Negative labels (the recorder's -1 "unknown" sentinel) pick no
    logit: loss = lse, matching the kernel's no-hit path (where a -1
    column offset never equals the block iota) instead of numpy-wrapping
    to the last vocab column.
    """
    logits = logits.astype(F32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[:, None], axis=-1
    )[:, 0]
    return lse - jnp.where(labels >= 0, picked, 0.0), lse


def topk_lse_ref(logits: Array, k: int) -> tuple[Array, Array, Array]:
    """Retained-outcome summary: logits [T,V] -> (vals [T,k] f32
    descending, idx [T,k] i32, lse [T] f32). Ties resolve to the lowest
    vocab index (``jax.lax.top_k`` semantics)."""
    logits = logits.astype(F32)
    vals, idx = jax.lax.top_k(logits, k)
    return vals, idx.astype(jnp.int32), jax.nn.logsumexp(logits, axis=-1)


def xent_grad_ref(logits: Array, labels: Array, lse: Array, g: Array) -> Array:
    """d loss / d logits given saved lse. -> [T,V] in logits.dtype."""
    p = jnp.exp(logits.astype(F32) - lse[:, None])
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=F32)
    return ((p - onehot) * g[:, None]).astype(logits.dtype)


def decode_attn_ref(
    q: Array,  # [B, Hq, D]
    k: Array,  # [B, T, Hkv, D]
    v: Array,  # [B, T, Hkv, D]
    valid: Array,  # [B, T] bool
) -> Array:
    """Single-token GQA decode attention -> [B, Hq, D]."""
    b, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qr = q.reshape(b, hkv, g, d).astype(F32)
    scores = jnp.einsum("bkgd,btkd->bkgt", qr, k.astype(F32)) * (d**-0.5)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", w, v.astype(F32))
    return out.reshape(b, hq, d).astype(q.dtype)


def paged_decode_attn_ref(
    q: Array,  # [B, Hq, D]
    kp: Array,  # [P, Hkv, page, D] global page pool (head-major pages)
    vp: Array,  # [P, Hkv, page, D]
    page_table: Array,  # [B, NP] i32 physical page per logical block
    pos: Array,  # [B] i32 per-slot depth; position pos is attended
) -> Array:
    """Decode attention through a paged KV pool -> [B, Hq, D].

    Gathers each row's pages back into the dense [B, T, Hkv, D] layout
    (T = NP * page) and defers to :func:`decode_attn_ref` with the
    position-validity mask ``t <= pos``. Unallocated table entries (-1)
    are clamped to page 0 — whatever is read there is masked, and masked
    scores contribute exactly-zero softmax weight."""
    b = q.shape[0]
    p_, hkv, page, d = kp.shape
    t = page_table.shape[1] * page
    pt = jnp.maximum(page_table, 0)
    k = kp[pt].swapaxes(2, 3).reshape(b, t, hkv, d)
    v = vp[pt].swapaxes(2, 3).reshape(b, t, hkv, d)
    valid = jnp.arange(t)[None] <= pos[:, None]
    return decode_attn_ref(q, k, v, valid)


def paged_latent_attn_ref(
    q: Array,  # [B, H, W] absorbed query then roped query
    lat: Array,  # [P, page, W] latent page pool
    page_table: Array,  # [B, NP] i32
    pos: Array,  # [B] i32; position pos is attended
    scale: float,
    v_dim: int,
) -> Array:
    """Absorbed-MLA decode attention through the latent pool -> [B, H,
    v_dim]: each row's pages gathered back to [B, T, W], f32 scores of
    every head against all W columns, masked to ``t <= pos``, and the
    softmax-weighted sum of the first ``v_dim`` columns (the chain of
    ``models.layers.mla_decode``). Unallocated entries clamp to page 0,
    whose reads are masked."""
    b = q.shape[0]
    _, page, w = lat.shape
    t = page_table.shape[1] * page
    dense = lat[jnp.maximum(page_table, 0)].reshape(b, t, w)
    scores = jnp.einsum("bhw,btw->bht", q, dense,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(t)[None] <= pos[:, None]
    scores = jnp.where(valid[:, None], scores, -1e30)
    wts = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,btr->bhr", wts, dense[..., :v_dim])


def ledger_record_priority_ref(
    ema: Array,  # [capacity] f32
    count: Array,  # [capacity] i32
    last_seen: Array,  # [capacity] i32
    owner: Array,  # [capacity] i32
    ids: Array,  # [B] i32
    losses: Array,  # [B] f32
    step: Array,  # scalar i32
    decay: float,
    unseen_priority: float,
    staleness_half_life: float = float("inf"),
    valid: Optional[Array] = None,  # [B] bool, None = all valid
) -> tuple[Array, Array, Array, Array, Array]:
    """Fused ledger record+priority (repro.core.device_ledger semantics).

    Scatter-EMA write with deterministic numpy last-write-wins on intra-batch
    slot collisions, then the post-update priority of EVERY queried id
    against the updated table. Just-recorded ids have age 0 (score = fresh
    EMA); ``valid``-masked items skip the write but are still scored, with
    the staleness boost applied to whatever record they hit. Within-batch
    evictions read back as unseen. Hash must match
    repro.core.history.slot_for.
    """
    from repro.core.device_ledger import slot_for_jnp

    cap = ema.shape[0]
    i32 = jnp.int32
    ids = ids.astype(i32)
    losses = losses.astype(F32)
    step = jnp.asarray(step).astype(i32)
    slots = slot_for_jnp(ids, cap)

    fresh = owner[slots] != ids
    prev = jnp.where(fresh, losses, ema[slots])
    new_ema = decay * prev + (1.0 - decay) * losses
    new_count = jnp.where(fresh, 1, count[slots] + 1)
    order = jnp.arange(ids.shape[0], dtype=i32)
    wslots = slots if valid is None else jnp.where(valid, slots, cap)
    last = jnp.full((cap,), -1, i32).at[wslots].max(order, mode="drop")
    winner = (wslots < cap) & (last[slots] == order)
    tgt = jnp.where(winner, slots, cap)  # OOB -> dropped
    ema2 = ema.at[tgt].set(new_ema, mode="drop")
    count2 = count.at[tgt].set(new_count, mode="drop")
    last_seen2 = last_seen.at[tgt].set(
        jnp.broadcast_to(step, tgt.shape), mode="drop"
    )
    owner2 = owner.at[tgt].set(ids, mode="drop")
    seen = owner2[slots] == ids
    age = jnp.maximum(step - last_seen2[slots], 0).astype(F32)
    boost = jnp.exp2(age / staleness_half_life)
    pri = jnp.where(seen, ema2[slots] * boost, unseen_priority).astype(F32)
    return ema2, count2, last_seen2, owner2, pri


def ssd_ref(
    x: Array,  # [B, S, H, P]
    dt: Array,  # [B, S, H] positive
    a: Array,  # [H] negative
    b: Array,  # [B, S, G, N]
    c: Array,  # [B, S, G, N]
    h0: Optional[Array] = None,  # [B, H, P, N]
) -> tuple[Array, Array]:
    """Sequential SSD recurrence (the definitional oracle).

    h_t = exp(a*dt_t) h_{t-1} + dt_t * x_t B_t^T ;  y_t = h_t C_t
    """
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    bh = jnp.repeat(b.astype(F32), rep, axis=2)  # [B,S,H,N]
    ch = jnp.repeat(c.astype(F32), rep, axis=2)
    xf, dtf = x.astype(F32), dt.astype(F32)

    def step(hprev, inp):
        xt, dtt, bt, ct = inp  # [B,H,P],[B,H],[B,H,N],[B,H,N]
        decay = jnp.exp(dtt * a[None, :])[..., None, None]
        upd = (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        hnew = hprev * decay + upd
        y = jnp.einsum("bhpn,bhn->bhp", hnew, ct)
        return hnew, y

    init = jnp.zeros((bsz, h, p, n), F32) if h0 is None else h0.astype(F32)
    final, ys = jax.lax.scan(
        step,
        init,
        (
            xf.swapaxes(0, 1),
            dtf.swapaxes(0, 1),
            bh.swapaxes(0, 1),
            ch.swapaxes(0, 1),
        ),
    )
    return ys.swapaxes(0, 1).astype(x.dtype), final
