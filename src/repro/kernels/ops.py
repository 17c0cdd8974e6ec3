"""Public kernel entry points: backend dispatch + autodiff.

One rule picks each op's implementation (:func:`default_impl`): the
Pallas kernel when JAX's default backend is a TPU, the pure-jnp "ref"
oracle everywhere else. An explicit ``impl=`` overrides it — tests use it
to run a kernel under "interpret" on the CPU or to compare against "ref";
no path on the TPU falls back to either by default.

``xent_loss`` carries a custom_vjp: forward saves only the [T] LSE (never a
[T, V] softmax); backward recomputes grad blockwise from (logits, lse).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attn as _da
from repro.kernels import ledger as _ledger
from repro.kernels import ref as _ref
from repro.kernels import ssd as _ssd
from repro.kernels import topk_lse as _topk
from repro.kernels import xent as _xent

_VALID = ("ref", "pallas", "interpret")


def default_impl() -> str:
    """The platform's implementation: "pallas" on a TPU, "ref" elsewhere
    (Pallas-TPU kernels run on the CPU only under the interpreter, which
    is orders of magnitude slower than the jnp oracle)."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def resolve(impl: Optional[str]) -> str:
    """``impl`` if given, else :func:`default_impl`."""
    impl = impl or default_impl()
    if impl not in _VALID:
        raise ValueError(f"kernel impl {impl!r} not in {_VALID}")
    return impl


# ---------------------------------------------------------------------------
# fused cross-entropy
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def xent_loss(logits: jax.Array, labels: jax.Array, impl: Optional[str] = None):
    """Per-token CE: logits [T,V], labels [T] -> loss [T] f32."""
    loss, _ = _xent_fwd_impl(logits, labels, resolve(impl))
    return loss


def _xent_fwd_impl(logits, labels, impl):
    if impl == "ref":
        return _ref.xent_ref(logits, labels)
    return _xent.xent_fwd(logits, labels, interpret=(impl == "interpret"))


def _xent_fwd(logits, labels, impl):
    loss, lse = _xent_fwd_impl(logits, labels, resolve(impl))
    return loss, (logits, labels, lse)


def _xent_bwd(impl, res, g):
    logits, labels, lse = res
    impl = resolve(impl)
    if impl == "ref":
        grad = _ref.xent_grad_ref(logits, labels, lse, g)
    else:
        grad = _xent.xent_bwd(
            logits, labels, lse, g, interpret=(impl == "interpret")
        )
    return grad, None


xent_loss.defvjp(_xent_fwd, _xent_bwd)


# ---------------------------------------------------------------------------
# top-k + lse retained-outcome summary (inference only — no vjp needed)
# ---------------------------------------------------------------------------


def topk_lse(
    logits: jax.Array, k: int, impl: Optional[str] = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compress logits [T,V] into the retained-outcome summary:
    (top-k values [T,k] f32 descending, top-k indices [T,k] i32,
    exact lse [T] f32). One streaming pass on the Pallas path."""
    impl = resolve(impl)
    if impl == "ref":
        return _ref.topk_lse_ref(logits, k)
    return _topk.topk_lse(logits, k, interpret=(impl == "interpret"))


# ---------------------------------------------------------------------------
# decode attention (inference only — no vjp needed)
# ---------------------------------------------------------------------------


def decode_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    valid: jax.Array,
    impl: Optional[str] = None,
) -> jax.Array:
    impl = resolve(impl)
    if impl == "ref":
        return _ref.decode_attn_ref(q, k, v, valid)
    return _da.decode_attn(q, k, v, valid, interpret=(impl == "interpret"))


def paged_decode_attn(
    q: jax.Array,
    kp: jax.Array,
    vp: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
    layer: jax.Array,
    impl: Optional[str] = None,
) -> jax.Array:
    """Decode attention through layer ``layer`` of the stacked paged KV
    pool (see ``kernels.decode_attn.paged_decode_attn``): q [B,Hq,D], pool
    [L,P,Hkv,page,D], page_table [B,NP] (-1 = unallocated), pos [B]."""
    impl = resolve(impl)
    if impl == "ref":
        return _ref.paged_decode_attn_ref(q, kp[layer], vp[layer],
                                          page_table, pos)
    return _da.paged_decode_attn(
        q, kp, vp, page_table, pos, layer, interpret=(impl == "interpret")
    )


def paged_latent_attn(
    q: jax.Array,
    lat: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
    layer: jax.Array,
    *,
    scale: float,
    v_dim: int,
    impl: Optional[str] = None,
) -> jax.Array:
    """Absorbed-MLA decode attention through layer ``layer`` of the stacked
    latent page pool (see ``kernels.decode_attn.paged_latent_attn``): q
    [B,H,W], pool [L,P,page,W], page_table [B,NP] (-1 = unallocated), pos
    [B] -> [B,H,v_dim]."""
    impl = resolve(impl)
    if impl == "ref":
        return _ref.paged_latent_attn_ref(q, lat[layer], page_table, pos,
                                          scale, v_dim)
    return _da.paged_latent_attn(
        q, lat, page_table, pos, layer, scale=scale, v_dim=v_dim,
        interpret=(impl == "interpret"),
    )


# ---------------------------------------------------------------------------
# fused recycle-ledger record+priority (no vjp — the ledger is not a
# differentiable quantity; it is stop_gradient state by construction)
# ---------------------------------------------------------------------------

# Batches at or above this size dispatch the two-pass block-parallel
# scatter (grid over table tiles); below it, the single-program fori-loop
# kernel (shorter loop, no tiling overhead). See repro.kernels.ledger.
LEDGER_BLOCK_MIN_BATCH = 256


def ledger_record_priority(
    ema: jax.Array,
    count: jax.Array,
    last_seen: jax.Array,
    owner: jax.Array,
    ids: jax.Array,
    losses: jax.Array,
    step: jax.Array,
    *,
    decay: float,
    unseen_priority: float,
    staleness_half_life: float = float("inf"),
    valid: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    variant: Optional[str] = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One-pass ledger transaction -> (ema', count', last_seen', owner', pri).

    ``valid`` ([B] bool) masks the write (dropped items are still scored);
    ``staleness_half_life`` feeds the priority's exp2(age/half_life) boost
    (inf = no boost, the pre-mask behavior where every scored id was just
    recorded at age 0). On the Pallas path, ``variant`` picks the scatter
    kernel: None dispatches by batch size (>= LEDGER_BLOCK_MIN_BATCH items
    takes the two-pass block-parallel tiling, below it the single-program
    fori loop); "fori"/"block" force one.
    """
    impl = resolve(impl)
    if impl == "ref":
        return _ref.ledger_record_priority_ref(
            ema, count, last_seen, owner, ids, losses, step,
            decay, unseen_priority, staleness_half_life, valid,
        )
    return _ledger.ledger_record_priority(
        ema, count, last_seen, owner, ids, losses, step,
        valid=valid,
        decay=decay,
        unseen_priority=unseen_priority,
        staleness_half_life=staleness_half_life,
        interpret=(impl == "interpret"),
        variant=variant,
        batch_threshold=LEDGER_BLOCK_MIN_BATCH,
    )


# ---------------------------------------------------------------------------
# SSD chunk scan
# ---------------------------------------------------------------------------


def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    chunk: int = 128,
    impl: Optional[str] = None,
) -> tuple[jax.Array, jax.Array]:
    impl = resolve(impl)
    if impl == "ref":
        from repro.models.ssm import ssd_chunked  # chunked jnp (fast ref path)

        return ssd_chunked(x, dt, a, b, c, chunk=min(chunk, x.shape[1]))
    return _ssd.ssd(x, dt, a, b, c, chunk=chunk, interpret=(impl == "interpret"))
