"""Mixture-of-Experts FFN (Mixtral top-2, DeepSeek-V2 shared+routed top-6).

Two layers over one gate, :func:`route` (router logits in f32, softmax,
greedy top-k, renormalised only where ``route_norm``); only the dispatch
differs between them:

* :func:`moe_ffn`, the training path: GShard-style dense dispatch, the
  TPU-idiomatic formulation: tokens are grouped (one group per sequence),
  each group dispatches into per-expert capacity slots via one-hot
  einsums, expert FFNs run as a single stacked einsum over the expert
  axis, and a combine einsum scatters results back. Everything is
  static-shaped, so it pjit-shards cleanly: the expert axis maps to the
  "model" mesh axis (expert parallelism) and groups follow the batch.
  Capacity overflow drops tokens (their FFN output is 0 and the residual
  passes through) — the standard trade at scale; `capacity_factor`
  controls the drop rate and tests assert zero drops at cf >= k with
  balanced routers.
* :func:`moe_ffn_dropless`, the serving path (prefill and decode): every
  token-expert pair is computed. Pairs are sorted by expert, run through
  grouped matmuls (``jax.lax.ragged_dot``), unsorted and weighted by their
  gates. No token competes with another for a slot, so a right-pad can
  never change a real token's output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.params import ParamSpec
from repro.models.layers import mlp, mlp_specs

Array = jax.Array
F32 = jnp.float32


def moe_specs(cfg: ModelConfig) -> dict[str, ParamSpec]:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    p: dict = {
        "router": ParamSpec((d, e), ("embed", "experts"), scale=d**-0.5),
        "w1": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp"), scale=d**-0.5),
        "w3": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp"), scale=d**-0.5),
        "w2": ParamSpec((e, f, d), ("experts", "expert_mlp", "embed"), scale=f**-0.5),
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_specs(d, cfg.num_shared_experts * f)
    return p


def capacity(cfg: ModelConfig, group_tokens: int) -> int:
    """Slots per expert and group of :func:`moe_ffn` (the training path;
    the dropless serving path has none): at least 4, so a one-token decode
    group still computes 4 rows per expert."""
    c = int(
        group_tokens
        / cfg.num_experts
        * cfg.capacity_factor
        * cfg.experts_per_token
    )
    return max(4, -(-c // 4) * 4)  # >=4, rounded up to a multiple of 4


def route(x: Array, router: Array, cfg: ModelConfig):
    """x [..., D] -> (gates [..., K] f32, expert_idx [..., K], probs [..., E]).

    The logits are computed in f32, as DeepSeek-V2's MoEGate computes them:
    a bf16 logit near a tie would send a token to another expert, so the
    trainer and the server would route one token apart."""
    logits = jnp.einsum("...d,de->...e", x.astype(F32), router.astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.route_norm:  # Mixtral renormalizes the top-k; DeepSeek-V2 does not
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx, probs


def _dispatch_combine(idx: Array, gates: Array, e: int, c: int):
    """Build dispatch [G,S,E,C] bool and combine [G,S,E,C] f32 one-hots.

    Slot assignment is sequential over the k routing choices then over the
    token axis (cumsum), matching GShard: earlier tokens win capacity.
    """
    g, s, k = idx.shape
    counts = jnp.zeros((g, 1, e), jnp.int32)
    disp = jnp.zeros((g, s, e, c), jnp.bool_)
    comb = jnp.zeros((g, s, e, c), F32)
    for j in range(k):  # k is small and static: unrolled
        oh = jax.nn.one_hot(idx[:, :, j], e, dtype=jnp.int32)  # [G,S,E]
        pos = jnp.cumsum(oh, axis=1) - oh + counts  # position within expert
        keep = (pos < c) & (oh > 0)
        slot = jax.nn.one_hot(jnp.where(keep, pos, 0), c, dtype=jnp.bool_)
        slot = slot & keep[..., None]
        disp = disp | slot
        comb = comb + gates[:, :, j, None, None] * slot.astype(F32)
        counts = counts + jnp.sum(oh, axis=1, keepdims=True)
    return disp, comb


def load_balance_loss(probs: Array, idx: Array, e: int) -> Array:
    """Switch/GShard aux loss: E * sum_e fraction_e * mean_prob_e."""
    sel = jax.nn.one_hot(idx, e, dtype=F32).sum(axis=-2)  # [G,S,E]
    frac = jnp.mean(sel, axis=(0, 1)) / max(idx.shape[-1], 1)
    mean_p = jnp.mean(probs, axis=(0, 1))
    return e * jnp.sum(frac * mean_p)


def moe_ffn(
    x: Array, p: dict, cfg: ModelConfig
) -> tuple[Array, Array]:
    """x [B,S,D] -> (out [B,S,D], aux_loss scalar).

    Groups = sequences, or `cfg.moe_group`-token chunks when set (GShard
    grouping: dispatch tensor volume ∝ group size)."""
    dt = x.dtype
    bsz, seq, d = x.shape
    gs = cfg.moe_group
    regroup = bool(gs) and seq % gs == 0 and seq > gs
    if regroup:
        x = x.reshape(bsz * (seq // gs), gs, d)
    g, s, _ = x.shape
    e = cfg.num_experts
    c = capacity(cfg, s)

    gates, idx, probs = route(x, p["router"], cfg)
    aux = load_balance_loss(probs, idx, e)
    disp, comb = _dispatch_combine(idx, gates, e, c)

    # dispatch -> expert FFN (stacked over the expert axis) -> combine
    xe = jnp.einsum("gsec,gsd->egcd", disp.astype(dt), x)
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe, p["w1"].astype(dt)))
    h = h * jnp.einsum("egcd,edf->egcf", xe, p["w3"].astype(dt))
    ye = jnp.einsum("egcf,efd->egcd", h, p["w2"].astype(dt))
    out = jnp.einsum("gsec,egcd->gsd", comb.astype(dt), ye)

    if cfg.num_shared_experts:
        out = out + mlp(x, p["shared"])
    if regroup:
        out = out.reshape(bsz, seq, d)
    return out, aux


def moe_ffn_dropless(
    x: Array, p: dict, cfg: ModelConfig
) -> tuple[Array, Array]:
    """x [B,S,D] -> (out [B,S,D], tokens routed to each expert [E] i32).

    Every token's top-k pairs are sorted by expert (stably, so a token's
    pairs keep their order within an expert), gathered into one [N*k, D]
    operand whose rows run through each expert's SwiGLU as grouped
    matmuls, put back in pair order, and summed per token with the gate
    weights in f32; the shared experts add their dense MLP."""
    dt = x.dtype
    bsz, seq, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    xf = x.reshape(bsz * seq, d)
    gates, idx, _ = route(xf, p["router"], cfg)
    flat = idx.reshape(-1)  # [N*k] expert of each pair, token-major
    order = jnp.argsort(flat, stable=True)
    counts = jnp.bincount(flat, length=e).astype(jnp.int32)
    xs = xf[order // k]  # [N*k, D], grouped by expert
    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w1"].astype(dt), counts))
    h = h * jax.lax.ragged_dot(xs, p["w3"].astype(dt), counts)
    ys = jax.lax.ragged_dot(h, p["w2"].astype(dt), counts)
    y = ys[jnp.argsort(order)].reshape(bsz * seq, k, d)  # pair order
    out = jnp.einsum("nk,nkd->nd", gates, y.astype(F32)).astype(dt)
    out = out.reshape(bsz, seq, d)
    if cfg.num_shared_experts:
        out = out + mlp(x, p["shared"])
    return out, counts


def routing_stats(logits: Array, k: int) -> dict[str, Array]:
    """Free by-product of the selection forward (beyond-paper): per-batch
    router statistics recorded into the loss history alongside the loss."""
    probs = jax.nn.softmax(logits.astype(F32), axis=-1)
    top = jax.lax.top_k(probs, k)[0]
    return {
        "router_entropy": -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-9), -1)),
        "router_top1": jnp.mean(top[..., 0]),
    }
