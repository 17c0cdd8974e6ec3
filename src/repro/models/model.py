"""Decoder-LM assembly covering all 10 assigned architectures.

One parameterized decoder family; the config's `family` + feature flags pick
the block type per layer:

  dense / audio / vlm : [norm -> attention (GQA or MLA) -> +res] [norm -> SwiGLU -> +res]
  moe                 : same, FFN = MoE (optionally first_k_dense dense layers)
  ssm                 : [norm -> Mamba2/SSD -> +res]
  hybrid (Zamba2)     : groups of `hybrid_attn_every` SSM layers, each group
                        preceded by ONE weight-shared attention block

Layers are stacked pytrees scanned with `jax.lax.scan` (+ optional
`jax.checkpoint` remat per layer) so the HLO is O(1) in depth — this is what
keeps the 88-layer granite dry-run compilable. Audio/VLM frontends are stubs
per the assignment: `prefix_embed` [B, P, D] precomputed frame/patch
embeddings prepended to the token embeddings.

Three entry points (the shapes the dry-run lowers):
  * per_example_loss / train forward  — full sequence, returns [B] losses
  * prefill        — full sequence, returns logits of last position + cache
  * decode_step    — one token against the cache (serve_step)
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.config import ModelConfig
from repro.models.params import ParamSpec, is_spec

Array = jax.Array
F32 = jnp.float32


def activation_constraint(x: Array, kind: str) -> Array:
    """Lazy indirection to distributed.sharding (avoids a circular import;
    trace-time only, zero runtime cost)."""
    from repro.distributed.sharding import activation_constraint as _ac

    return _ac(x, kind)


def param_gather(p: dict) -> dict:
    """ZeRO-3 per-layer weight gather point (no-op unless the active
    sharding rules set gather_params)."""
    from repro.distributed.sharding import param_gather_constraint

    return param_gather_constraint(p)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def stack_specs(tree: Any, n: int) -> Any:
    """Add a leading stacked-layers dim to every spec in the tree."""
    return jax.tree.map(
        lambda s: ParamSpec((n, *s.shape), ("layers", *s.axes), s.init, s.scale),
        tree,
        is_leaf=is_spec,
    )


def _attn_specs(cfg: ModelConfig) -> dict:
    return L.mla_specs(cfg) if cfg.attn_impl == "mla" else L.gqa_specs(cfg)


def _attn_block_specs(cfg: ModelConfig, ffn: str) -> dict:
    d = cfg.d_model
    spec = {
        "attn_norm": L.rmsnorm_spec(d),
        "attn": _attn_specs(cfg),
        "ffn_norm": L.rmsnorm_spec(d),
    }
    if ffn == "dense":
        spec["mlp"] = L.mlp_specs(d, cfg.d_ff, gelu=cfg.mlp_gelu)
    elif ffn == "moe":
        spec["moe"] = M.moe_specs(cfg)
    return spec


def _ssm_block_specs(cfg: ModelConfig) -> dict:
    return {"norm": L.rmsnorm_spec(cfg.d_model), "ssm": S.ssm_specs(cfg)}


def param_specs(cfg: ModelConfig) -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    specs: dict = {
        "embed": ParamSpec((v, d), ("vocab", "embed"), scale=0.02),
        "final_norm": L.rmsnorm_spec(d),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((v, d), ("vocab", "embed"), scale=d**-0.5)

    if cfg.family in ("dense", "audio", "vlm"):
        specs["blocks"] = stack_specs(
            _attn_block_specs(cfg, "dense"), cfg.num_layers
        )
    elif cfg.family == "moe":
        n_moe = cfg.num_layers - cfg.first_k_dense
        if cfg.first_k_dense:
            specs["dense_blocks"] = stack_specs(
                _attn_block_specs(cfg, "dense"), cfg.first_k_dense
            )
        specs["blocks"] = stack_specs(_attn_block_specs(cfg, "moe"), n_moe)
    elif cfg.family == "ssm":
        specs["blocks"] = stack_specs(_ssm_block_specs(cfg), cfg.num_layers)
    elif cfg.family == "hybrid":
        groups = cfg.num_layers // cfg.hybrid_attn_every
        inner = stack_specs(_ssm_block_specs(cfg), cfg.hybrid_attn_every)
        specs["blocks"] = stack_specs(inner, groups)  # [G, E, ...]
        specs["shared_attn"] = _attn_block_specs(cfg, "dense")
    else:
        raise NotImplementedError(cfg.family)
    return specs


# ---------------------------------------------------------------------------
# block bodies (full-sequence)
# ---------------------------------------------------------------------------


def _attend(x: Array, p: dict, cfg: ModelConfig, positions: Array) -> Array:
    if cfg.attn_impl == "mla":
        return L.mla_attend(x, p, cfg, positions)
    return L.gqa_attend(x, p, cfg, positions)


def _attn_block(
    x: Array, p: dict, cfg: ModelConfig, positions: Array, ffn: str
) -> tuple[Array, Array]:
    p = param_gather(p)
    h = L.rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    x = x + _attend(h, p["attn"], cfg, positions)
    h = L.rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
    if ffn == "moe":
        out, aux = M.moe_ffn(h, p["moe"], cfg)
    else:
        out, aux = L.mlp(h, p["mlp"]), jnp.zeros((), F32)
    x = activation_constraint(x + out, "residual")
    return x, aux


def _ssm_block(x: Array, p: dict, cfg: ModelConfig) -> Array:
    p = param_gather(p)
    h = L.rmsnorm(x, p["norm"], cfg.norm_eps)
    return activation_constraint(x + S.ssm_block(h, p["ssm"], cfg), "residual")


def _scan(body, x: Array, stacked: Any, remat: bool) -> tuple[Array, Array]:
    """Scan `body(x, layer_params) -> (x, aux)` over stacked layer params."""
    if remat:
        body = jax.checkpoint(body)

    def f(carry, lp):
        x, aux = carry
        x, a = body(x, lp)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(f, (x, jnp.zeros((), F32)), stacked)
    return x, aux


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(
    params: dict, cfg: ModelConfig, tokens: Array, prefix: Optional[Array]
) -> Array:
    dt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(dt)[tokens]
    if prefix is not None:
        x = jnp.concatenate([prefix.astype(dt), x], axis=1)
    return x


def unembed(params: dict, cfg: ModelConfig, x: Array) -> Array:
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype))


# ---------------------------------------------------------------------------
# full-sequence forward
# ---------------------------------------------------------------------------


def forward_hidden(
    params: dict,
    cfg: ModelConfig,
    tokens: Array,
    prefix: Optional[Array] = None,
) -> tuple[Array, Array]:
    """tokens [B,S_tok] (+ prefix [B,P,D]) -> (hidden [B,S,D], moe_aux)."""
    x = embed_tokens(params, cfg, tokens, prefix)
    x = activation_constraint(x, "residual")
    positions = jnp.arange(x.shape[1])

    if cfg.family in ("dense", "audio", "vlm"):
        body = lambda x, lp: _attn_block(x, lp, cfg, positions, "dense")
        x, aux = _scan(body, x, params["blocks"], cfg.remat)
    elif cfg.family == "moe":
        if cfg.first_k_dense:
            dbody = lambda x, lp: _attn_block(x, lp, cfg, positions, "dense")
            x, _ = _scan(dbody, x, params["dense_blocks"], cfg.remat)
        body = lambda x, lp: _attn_block(x, lp, cfg, positions, "moe")
        x, aux = _scan(body, x, params["blocks"], cfg.remat)
    elif cfg.family == "ssm":
        body = lambda x, lp: (_ssm_block(x, lp, cfg), jnp.zeros((), F32))
        x, aux = _scan(body, x, params["blocks"], cfg.remat)
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group(x, group_params):
            x, _ = _attn_block(x, shared, cfg, positions, "dense")
            inner = lambda x, lp: (_ssm_block(x, lp, cfg), jnp.zeros((), F32))
            x, _ = _scan(inner, x, group_params, remat=False)
            return x, jnp.zeros((), F32)

        x, aux = _scan(group, x, params["blocks"], cfg.remat)
    else:
        raise NotImplementedError(cfg.family)

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def per_token_loss(logits: Array, labels: Array) -> Array:
    """Cross-entropy per token; labels < 0 are masked. [B,S,V],[B,S] -> [B,S]."""
    lse = jax.nn.logsumexp(logits.astype(F32), axis=-1)
    picked = jnp.take_along_axis(
        logits.astype(F32), jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    return jnp.where(labels >= 0, lse - picked, 0.0)


def per_example_loss(
    params: dict, cfg: ModelConfig, batch: dict[str, Array]
) -> tuple[Array, Array]:
    """-> (per-example mean CE [B], moe aux loss). The OBFTF loss signal."""
    prefix = batch.get("prefix_embed")
    hidden, aux = forward_hidden(params, cfg, batch["tokens"], prefix)
    if prefix is not None:  # loss only over the token (non-prefix) positions
        hidden = hidden[:, prefix.shape[1] :, :]
    logits = unembed(params, cfg, hidden)
    ce = per_token_loss(logits, batch["labels"])
    denom = jnp.maximum(jnp.sum(batch["labels"] >= 0, axis=-1), 1)
    return jnp.sum(ce, axis=-1) / denom.astype(F32), aux


def per_example_signals(
    params: dict, cfg: ModelConfig, batch: dict[str, Array]
) -> tuple[Array, dict[str, Array], Array]:
    """-> (per-example CE [B], {"entropy", "margin"} [B], moe aux).

    The train-side twin of the serving recorder's signal derivation
    (``serving.recorder.full_signals``): per-token predictive entropy
    ``lse - sum(softmax * logits)`` and top-1/top-2 logit margin,
    masked-averaged over label positions. Benches use it to feed the
    signal ledger from training forwards when no serving fleet exists —
    same ``AUX_CHANNELS`` semantics, exact (dense-logit) values.
    """
    prefix = batch.get("prefix_embed")
    hidden, aux = forward_hidden(params, cfg, batch["tokens"], prefix)
    if prefix is not None:
        hidden = hidden[:, prefix.shape[1] :, :]
    logits = unembed(params, cfg, hidden).astype(F32)
    labels = batch["labels"]
    ce = per_token_loss(logits, labels)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ent = lse - jnp.sum(jax.nn.softmax(logits, axis=-1) * logits, axis=-1)
    top2 = jax.lax.top_k(logits, 2)[0]
    mar = top2[..., 0] - top2[..., 1]
    mask = (labels >= 0).astype(F32)
    denom = jnp.maximum(mask.sum(axis=-1), 1.0)
    signals = {
        "entropy": jnp.sum(ent * mask, axis=-1) / denom,
        "margin": jnp.sum(mar * mask, axis=-1) / denom,
    }
    return jnp.sum(ce, axis=-1) / denom, signals, aux


def loss_fn(cfg: ModelConfig):
    """`per_example_loss_fn(params, batch, rng) -> [B]` for the OBFTF step.

    MoE aux load-balancing loss is folded in per-example (it is a scalar
    shared across the batch; adding it keeps grad(mean(out)) correct).
    """

    def fn(params: dict, batch: dict[str, Array], rng: Array) -> Array:
        del rng
        losses, aux = per_example_loss(params, cfg, batch)
        if cfg.uses_moe:
            losses = losses + cfg.router_aux_coef * aux
        return losses

    return fn


# ---------------------------------------------------------------------------
# caches / prefill / decode
# ---------------------------------------------------------------------------


def _attn_init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype):
    if cfg.attn_impl == "mla":
        return L.mla_init_cache(cfg, batch, max_seq, dtype)
    return L.gqa_init_cache(cfg, batch, max_seq, dtype)


def _stack_over(n: int, make) -> Any:
    """Build a [n, ...]-stacked cache pytree without materializing n copies."""
    one = make()
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), one
    )


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    dt = jnp.dtype(cfg.compute_dtype)
    if cfg.family in ("dense", "audio", "vlm"):
        return {
            "blocks": _stack_over(
                cfg.num_layers, lambda: _attn_init_cache(cfg, batch, max_seq, dt)
            )
        }
    if cfg.family == "moe":
        c = {
            "blocks": _stack_over(
                cfg.num_layers - cfg.first_k_dense,
                lambda: _attn_init_cache(cfg, batch, max_seq, dt),
            )
        }
        if cfg.first_k_dense:
            c["dense_blocks"] = _stack_over(
                cfg.first_k_dense,
                lambda: _attn_init_cache(cfg, batch, max_seq, dt),
            )
        return c
    if cfg.family == "ssm":
        return {
            "blocks": _stack_over(
                cfg.num_layers, lambda: S.ssm_init_cache(cfg, batch, dt)
            )
        }
    if cfg.family == "hybrid":
        groups = cfg.num_layers // cfg.hybrid_attn_every
        return {
            "blocks": _stack_over(
                groups,
                lambda: _stack_over(
                    cfg.hybrid_attn_every, lambda: S.ssm_init_cache(cfg, batch, dt)
                ),
            ),
            "shared_attn": _stack_over(
                groups, lambda: _attn_init_cache(cfg, batch, max_seq, dt)
            ),
        }
    raise NotImplementedError(cfg.family)


def _attn_fill(x, p, cfg, positions, max_seq):
    if cfg.attn_impl == "mla":
        return L.mla_fill_cache(x, p, cfg, positions, max_seq)
    return L.gqa_fill_cache(x, p, cfg, positions, max_seq)


def _attn_block_fill(x, p, cfg, positions, max_seq, ffn):
    h = L.rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    a, cache = _attn_fill(h, p["attn"], cfg, positions, max_seq)
    x = x + a
    h = L.rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
    if ffn == "moe":  # serving: drop-free, so a pad changes no real token
        out, _ = M.moe_ffn_dropless(h, p["moe"], cfg)
    else:
        out = L.mlp(h, p["mlp"])
    return activation_constraint(x + out, "residual"), cache


def _ssm_block_fill(x, p, cfg):
    h = L.rmsnorm(x, p["norm"], cfg.norm_eps)
    out, cache = S.ssm_fill_cache(h, p["ssm"], cfg)
    return activation_constraint(x + out, "residual"), cache


def _scan_fill(body, x, stacked, remat):
    if remat:
        body = jax.checkpoint(body)

    def f(x, lp):
        return body(x, lp)

    return jax.lax.scan(f, x, stacked)


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int) -> dict:
    """Global paged KV pool, stacked over layers like the parameters.

    GQA layers hold K and V pages [L, P, kv, page, hd]; MLA layers one
    latent page pool [L, P, page, W] (``layers.mla_paged_init_cache``); an
    MoE family with leading dense layers keeps their pool under
    ``dense_blocks``. A physical page id addresses the same page across
    every layer, so one per-slot page table serves the whole stack (the
    vLLM block-table layout). Causal attention families qualify, dense or
    MoE (whose serving layer is drop-free); recurrent state (SSM, hybrid),
    rolling SWA windows and int8-quantized caches keep the dense per-slot
    layout."""
    if cfg.family not in ("dense", "audio", "vlm", "moe"):
        raise NotImplementedError(
            f"paged KV cache: family {cfg.family!r} has non-KV cache state"
        )
    if cfg.sliding_window is not None:
        raise NotImplementedError(
            "paged KV cache requires attention without a sliding window"
        )
    if cfg.kv_cache_dtype == "int8":
        raise NotImplementedError("paged KV cache: int8 KV not supported yet")
    dt = jnp.dtype(cfg.compute_dtype)
    init = (L.mla_paged_init_cache if cfg.attn_impl == "mla"
            else L.gqa_paged_init_cache)
    make = lambda: init(cfg, num_pages, page_size, dt)
    if cfg.family != "moe":
        return {"blocks": _stack_over(cfg.num_layers, make)}
    cache = {"blocks": _stack_over(cfg.num_layers - cfg.first_k_dense, make)}
    if cfg.first_k_dense:
        cache["dense_blocks"] = _stack_over(cfg.first_k_dense, make)
    return cache


def prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: Array,
    max_seq: int,
    prefix: Optional[Array] = None,
    last_pos: Optional[Array] = None,
) -> tuple[Array, dict]:
    """Full-sequence forward building the decode cache.

    Returns (last-position logits [B,V], cache). `max_seq` is the cache
    capacity (>= prompt length + generated tokens). `last_pos` ([B] int,
    optional) returns each example's logits at its own final position
    instead of the shared last one — the right-padded-prompt case of the
    continuous-batching engine, where row b's real prompt ends at
    `last_pos[b]` and positions beyond it are pad (their K/V rows land in
    the cache but decode's position-validity mask never attends to them).
    """
    x = embed_tokens(params, cfg, tokens, prefix)
    x = activation_constraint(x, "residual")
    positions = jnp.arange(x.shape[1])
    cache: dict = {}

    if cfg.family in ("dense", "audio", "vlm", "moe"):
        ffn = "moe" if cfg.family == "moe" else "dense"
        if cfg.family == "moe" and cfg.first_k_dense:
            body = lambda x, lp: _attn_block_fill(
                x, lp, cfg, positions, max_seq, "dense"
            )
            x, cache["dense_blocks"] = _scan_fill(
                body, x, params["dense_blocks"], cfg.remat
            )
        body = lambda x, lp: _attn_block_fill(x, lp, cfg, positions, max_seq, ffn)
        x, cache["blocks"] = _scan_fill(body, x, params["blocks"], cfg.remat)
    elif cfg.family == "ssm":
        body = lambda x, lp: _ssm_block_fill(x, lp, cfg)
        x, cache["blocks"] = _scan_fill(body, x, params["blocks"], cfg.remat)
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group(x, group_params):
            h = L.rmsnorm(x, shared["attn_norm"], cfg.norm_eps)
            a, attn_cache = _attn_fill(h, shared["attn"], cfg, positions, max_seq)
            x = x + a
            h = L.rmsnorm(x, shared["ffn_norm"], cfg.norm_eps)
            x = x + L.mlp(h, shared["mlp"])
            inner = lambda x, lp: _ssm_block_fill(x, lp, cfg)
            x, ssm_caches = _scan_fill(inner, x, group_params, remat=False)
            return x, (attn_cache, ssm_caches)

        x, (attn_caches, ssm_caches) = _scan_fill(
            group, x, params["blocks"], cfg.remat
        )
        cache = {"blocks": ssm_caches, "shared_attn": attn_caches}
    else:
        raise NotImplementedError(cfg.family)

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if last_pos is None:
        last = x[:, -1:, :]
    else:
        idx = jnp.asarray(last_pos, jnp.int32)[:, None, None]
        last = jnp.take_along_axis(x, idx, axis=1)
    logits = unembed(params, cfg, last)[:, 0, :]
    return logits, cache


def _attn_block_decode(x, p, cfg, cache, pos, max_seq, ffn, page_table=None):
    """One decode layer -> (x, (cache, tokens routed to each expert [E],
    None for a dense FFN))."""
    h = L.rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    if page_table is not None and cfg.attn_impl == "mla":
        a, cache = L.mla_paged_decode(h, p["attn"], cfg, cache, page_table, pos)
    elif page_table is not None:
        a, cache = L.gqa_paged_decode(h, p["attn"], cfg, cache, page_table, pos)
    elif cfg.attn_impl == "mla":
        a, cache = L.mla_decode(h, p["attn"], cfg, cache, pos, max_seq)
    else:
        a, cache = L.gqa_decode(h, p["attn"], cfg, cache, pos, max_seq)
    x = x + a
    h = L.rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
    counts = None
    if ffn == "moe":  # serving: drop-free, as in prefill
        out, counts = M.moe_ffn_dropless(h, p["moe"], cfg)
    else:
        out = L.mlp(h, p["mlp"])
    return x + out, (cache, counts)


def _ssm_block_decode(x, p, cfg, cache):
    h = L.rmsnorm(x, p["norm"], cfg.norm_eps)
    out, cache = S.ssm_decode(h, p["ssm"], cfg, cache)
    return x + out, cache


def decode_step(
    params: dict, cfg: ModelConfig, cache: dict, tokens: Array, pos: Array,
    page_table: Optional[Array] = None, routing: bool = False,
) -> tuple:
    """One decode step: tokens [B,1] -> (logits [B,V], cache).

    ``pos`` is the number of tokens already in the cache: a scalar when the
    whole batch decodes in lockstep, or a [B] vector when every row sits at
    its own depth (the continuous-batching engine). Attention families
    thread it through to the per-row cache scatter + validity mask; SSM
    recurrences are position-free and ignore it.

    ``page_table`` ([B, NP] i32, -1 = unallocated) switches the attention
    cache to the paged layout of :func:`init_paged_cache`: K/V writes and
    reads go through the table instead of a per-slot dense reservation.

    ``routing`` (MoE families) also returns the tokens each MoE layer
    routed to each expert, [L_moe, E] i32, every row of the batch counted.
    """
    x = embed_tokens(params, cfg, tokens, None)
    new_cache: dict = {}
    counts = None

    if page_table is not None and cfg.family not in (
        "dense", "audio", "vlm", "moe"
    ):
        raise NotImplementedError(
            f"paged decode: unsupported family {cfg.family!r}"
        )
    if cfg.family in ("dense", "audio", "vlm", "moe"):
        ffn = "moe" if cfg.family == "moe" else "dense"
        max_seq = (0 if page_table is not None
                   else _attn_cache_capacity(cfg, cache["blocks"]))
        if cfg.family == "moe" and cfg.first_k_dense:
            body = lambda x, lpc: _attn_block_decode(
                x, lpc[0], cfg, lpc[1], pos, max_seq, "dense", page_table
            )
            x, (new_cache["dense_blocks"], _) = jax.lax.scan(
                body, x, (params["dense_blocks"], cache["dense_blocks"])
            )
        body = lambda x, lpc: _attn_block_decode(
            x, lpc[0], cfg, lpc[1], pos, max_seq, ffn, page_table
        )
        x, (new_cache["blocks"], counts) = jax.lax.scan(
            body, x, (params["blocks"], cache["blocks"])
        )
    elif cfg.family == "ssm":
        body = lambda x, lpc: _ssm_block_decode(x, lpc[0], cfg, lpc[1])
        x, new_cache["blocks"] = jax.lax.scan(
            body, x, (params["blocks"], cache["blocks"])
        )
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        max_seq = _attn_cache_capacity(cfg, cache["shared_attn"])

        def group(x, inp):
            group_params, ssm_cache, attn_cache = inp
            h = L.rmsnorm(x, shared["attn_norm"], cfg.norm_eps)
            a, attn_cache = (
                L.mla_decode(h, shared["attn"], cfg, attn_cache, pos, max_seq)
                if cfg.attn_impl == "mla"
                else L.gqa_decode(h, shared["attn"], cfg, attn_cache, pos, max_seq)
            )
            x = x + a
            h = L.rmsnorm(x, shared["ffn_norm"], cfg.norm_eps)
            x = x + L.mlp(h, shared["mlp"])
            inner = lambda x, lpc: _ssm_block_decode(x, lpc[0], cfg, lpc[1])
            x, ssm_cache = jax.lax.scan(inner, x, (group_params, ssm_cache))
            return x, (ssm_cache, attn_cache)

        x, (ssm_caches, attn_caches) = jax.lax.scan(
            group, x, (params["blocks"], cache["blocks"], cache["shared_attn"])
        )
        new_cache = {"blocks": ssm_caches, "shared_attn": attn_caches}
    else:
        raise NotImplementedError(cfg.family)

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, x)[:, 0, :]
    if routing:
        return logits, new_cache, counts
    return logits, new_cache


def _attn_cache_capacity(cfg: ModelConfig, stacked_cache: dict) -> int:
    """Cache capacity T from the stacked cache leaves (static)."""
    if cfg.attn_impl == "mla":
        return stacked_cache["ckv"].shape[2]
    return stacked_cache["k"].shape[2]


# ---------------------------------------------------------------------------
# convenience
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0,))
def greedy_token(cfg: ModelConfig, logits: Array) -> Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
