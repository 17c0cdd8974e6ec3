"""DeepSeek-V2 (MLA + fine-grained MoE): seeded weights and the plain
float32 reference.

The reference follows the published DeepSeek-V2 block
(hf:deepseek-ai/DeepSeek-V2-Lite, ``modeling_deepseek.py``):

* RMSNorm before attention and FFN, a final RMSNorm, an untied LM head;
* multi-head latent attention, un-absorbed: q from ``wq`` (no q-LoRA,
  ``q_lora_rank`` null) split into nope and rope parts; ``wkv_a`` gives
  the latent ``ckv`` (RMSNorm over its ``kv_lora_rank`` columns) and one
  rope key shared by every head; ``wkv_b`` expands the latent into each
  head's nope key and value; causal softmax at ``(nope + rope)^-0.5``
  times ``yarn_get_mscale(factor, mscale_all_dim)^2``;
* rotary embedding with YaRN exactly as ``DeepseekV2YarnRotaryEmbedding``
  computes it (interpolated inverse frequencies over the correction range
  of ``beta_fast`` / ``beta_slow``, cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``);
* the first ``first_k_dense_replace`` layers with a SwiGLU MLP, the rest
  MoE: a softmax gate over ``n_routed_experts``, greedy top-k, no
  renormalisation (``norm_topk_prob`` false), times
  ``routed_scaling_factor``, and the shared experts as one SwiGLU MLP of
  ``n_shared_experts x moe_intermediate_size``. Drop-free: a token's
  output is the gate-weighted sum of its own k experts' outputs. It is
  computed as the sum over every expert of its output times the token's
  gate on it (zero for the experts the token did not pick), a few experts
  at a time.

Every matrix product runs in float32 at ``highest`` precision. It imports
nothing of the program; it shares with the program only the weights, which
this module makes from the seed in the layout the program's parameter
tree uses (the way a checkpoint loader would):

    embed [V, D]   final_norm [D]   lm_head [V, D]
    dense_blocks/ and blocks/: attn_norm, ffn_norm [L, D],
        attn/{wq [L, D, H, nope + rope], wkv_a [L, D, R + rope],
              kv_norm [L, R], wkv_b [L, R, H, nope + v], wo [L, H, v, D]}
    dense_blocks/mlp/{w1, w3 [L, D, F], w2 [L, F, D]}
    blocks/moe/{router [L, D, E], w1, w3 [L, E, D, Fe], w2 [L, E, Fe, D],
                shared/{w1, w3 [L, D, S * Fe], w2 [L, S * Fe, D]}}

Departures from the published model, none of which random weights can
tell apart: DeepSeek's checkpoint stores the rope columns of ``wq`` and
``wkv_a`` interleaved and its ``apply_rotary_pos_emb`` permutes them to
halves before ``rotate_half``; here (and in the program) those columns are
taken in halves as stored, which is the same model under a fixed
permutation of the columns. The greedy top-k breaks exact ties by index as
``jax.lax.top_k`` does.

``control`` is the same forward with both operands of every weight product
rounded to float8 (e4m3, one scale per row): the precision one step below
the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256  # query rows of one attention block
EXPERT_BLOCK = 8  # experts computed together
HEAD_BLOCK = 256  # token rows of one LM head block


def sizes(config: dict) -> dict:
    c = config["config"]
    y = c.get("rope_scaling") or {}
    return dict(
        d=c["hidden_size"], h=c["num_attention_heads"],
        r=c["kv_lora_rank"], pe=c["qk_rope_head_dim"],
        nope=c["qk_nope_head_dim"], vd=c["v_head_dim"],
        f=c["intermediate_size"], fe=c["moe_intermediate_size"],
        e=c["n_routed_experts"], k=c["num_experts_per_tok"],
        shared=c["n_shared_experts"], dense=c["first_k_dense_replace"],
        layers=c["num_hidden_layers"], vocab=c["vocab_size"],
        theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        routed_scale=float(c["routed_scaling_factor"]),
        yarn_factor=float(y.get("factor", 1.0)),
        yarn_orig=int(y.get("original_max_position_embeddings", 4096)),
        yarn_beta_fast=float(y.get("beta_fast", 32)),
        yarn_beta_slow=float(y.get("beta_slow", 1)),
        yarn_mscale=float(y.get("mscale", 1.0)),
        yarn_mscale_all_dim=float(y.get("mscale_all_dim", 0.0)),
    )


def _attn_shapes(s: dict, n: int) -> dict:
    d, h, r, pe, nope, vd = (s[k] for k in ("d", "h", "r", "pe", "nope",
                                            "vd"))
    return {
        "wq": ((n, d, h, nope + pe), d ** -0.5),
        "wkv_a": ((n, d, r + pe), d ** -0.5),
        "kv_norm": ((n, r), "norm"),
        "wkv_b": ((n, r, h, nope + vd), r ** -0.5),
        "wo": ((n, h, vd, d), (h * vd) ** -0.5),
    }


def _mlp_shapes(d: int, f: int, lead: tuple) -> dict:
    return {
        "w1": ((*lead, d, f), d ** -0.5),
        "w3": ((*lead, d, f), d ** -0.5),
        "w2": ((*lead, f, d), f ** -0.5),
    }


def weight_shapes(config: dict) -> dict:
    s = sizes(config)
    d, v, nd = s["d"], s["vocab"], s["dense"]
    nm = s["layers"] - nd
    out = {
        "embed": ((v, d), 0.02),
        "final_norm": ((d,), "norm"),
        "lm_head": ((v, d), d ** -0.5),
        "blocks": {
            "attn_norm": ((nm, d), "norm"),
            "ffn_norm": ((nm, d), "norm"),
            "attn": _attn_shapes(s, nm),
            "moe": {
                "router": ((nm, d, s["e"]), d ** -0.5),
                **_mlp_shapes(d, s["fe"], (nm, s["e"])),
                "shared": _mlp_shapes(d, s["shared"] * s["fe"], (nm,)),
            },
        },
    }
    if nd:
        out["dense_blocks"] = {
            "attn_norm": ((nd, d), "norm"),
            "ffn_norm": ((nd, d), "norm"),
            "attn": _attn_shapes(s, nd),
            "mlp": _mlp_shapes(d, s["f"], (nd,)),
        }
    return out


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_weights(config: dict, words: list[int], dtype=jnp.bfloat16):
    """Every weight from the seed in one jitted call on the device:
    normal(0, scale) matrices, norm gains 1 + normal(0, 0.1)."""
    key_data = np.asarray(words[:2], np.uint32)
    return weights_program(config, dtype)(jnp.asarray(key_data))


def weights_program(config: dict, dtype=jnp.bfloat16):
    """The jitted ``key data [2] uint32 -> weights`` program."""
    shapes = weight_shapes(config)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_leaf)[0]]
    order = {p: i for i, p in enumerate(sorted(paths))}

    @jax.jit
    def build(kd):
        key = jax.random.wrap_key_data(kd)

        def leaf(path, spec):
            shape, scale = spec
            k = jax.random.fold_in(key, order[jax.tree_util.keystr(path)])
            z = jax.random.normal(k, shape, F32)
            if scale == "norm":
                return (1.0 + 0.1 * z).astype(dtype)
            return (scale * z).astype(dtype)

        return jax.tree_util.tree_map_with_path(leaf, shapes, is_leaf=_is_leaf)

    return build


# ---------------------------------------------------------------------------
# YaRN, as DeepseekV2YarnRotaryEmbedding computes it
# ---------------------------------------------------------------------------


def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _correction_dim(rot: float, dim: int, base: float, max_pos: int):
    return (dim * math.log(max_pos / (rot * 2 * math.pi))) / (
        2 * math.log(base))


def yarn(s: dict) -> tuple[np.ndarray, float, float]:
    """(inverse frequencies [pe / 2] float32, cos/sin factor, softmax
    scale) of the configuration."""
    dim, base, f = s["pe"], s["theta"], s["yarn_factor"]
    ar = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    freq_extra = np.float32(1.0) / (np.float32(base) ** ar)
    freq_inter = np.float32(1.0) / (np.float32(f) * np.float32(base) ** ar)
    low = max(math.floor(_correction_dim(s["yarn_beta_fast"], dim, base,
                                         s["yarn_orig"])), 0)
    high = min(math.ceil(_correction_dim(s["yarn_beta_slow"], dim, base,
                                         s["yarn_orig"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = np.float32(1.0) - ramp.astype(np.float32)
    inv = (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32)
    cs = (yarn_get_mscale(f, s["yarn_mscale"])
          / yarn_get_mscale(f, s["yarn_mscale_all_dim"]))
    scale = (s["nope"] + s["pe"]) ** -0.5
    if s["yarn_mscale_all_dim"]:
        m = yarn_get_mscale(f, s["yarn_mscale_all_dim"])
        scale = scale * m * m
    return inv, cs, scale


# ---------------------------------------------------------------------------
# the reference forward
# ---------------------------------------------------------------------------


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, inv, cs):
    """Rotary embedding by halves (``rotate_half``): x [T, heads, pe]."""
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv)[None, :]
    emb = jnp.concatenate([ang, ang], -1)
    cos = (jnp.cos(emb) * cs)[:, None, :]
    sin = (jnp.sin(emb) * cs)[:, None, :]
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _fp8(x, axis=-1):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, low: bool, w_axis: int):
    """x [..., K] . w (contracting w's ``w_axis``) in float32; ``low``
    rounds both operands to float8 first (rows of x, output columns of
    w)."""
    w = w.astype(F32)
    if low:
        x = _fp8(x, -1)
        w = _fp8(w, w_axis)
    return jnp.tensordot(x, w, axes=[[x.ndim - 1], [w_axis]])


def _swiglu(x, w1, w3, w2, low):
    return _mm(jax.nn.silu(_mm(x, w1, low, 0)) * _mm(x, w3, low, 0), w2,
               low, 0)


def _attention(x, p, pos, s, low):
    t = x.shape[0]
    h, r, pe, nope, vd = (s[k] for k in ("h", "r", "pe", "nope", "vd"))
    inv, cs, scale = yarn(s)
    q = _mm(x, p["wq"].reshape(s["d"], -1), low, 0).reshape(t, h, nope + pe)
    kv_a = _mm(x, p["wkv_a"], low, 0)
    ckv = _rmsnorm(kv_a[:, :r], p["kv_norm"].astype(F32), s["eps"])
    k_pe = _rope(kv_a[:, None, r:], pos, inv, cs)  # [T, 1, pe]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv, cs)],
                        -1)
    kv = _mm(ckv, p["wkv_b"].reshape(r, -1), low, 0).reshape(t, h, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (t, h, pe))], -1)
    v = kv[..., nope:]
    qb = Q_BLOCK if t % Q_BLOCK == 0 else t

    def block(args):
        qi, pi = args  # [qb, H, nope + pe], [qb]
        sc = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        sc = jnp.where(pi[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    att = jax.lax.map(block, (q.reshape(t // qb, qb, h, nope + pe),
                              pos.reshape(t // qb, qb)))
    return _mm(att.reshape(t, h * vd), p["wo"].reshape(h * vd, s["d"]), low,
               0)


def _moe(x, p, s, low):
    """The routed experts (drop-free) plus the shared experts."""
    e, k = s["e"], s["k"]
    probs = jax.nn.softmax(_mm(x, p["router"], low, 0), -1)
    gates, idx = jax.lax.top_k(probs, k)  # greedy, not renormalised
    gates = gates * s["routed_scale"]
    # each token's gate on every expert: its top-k gates, zero elsewhere
    coef = jnp.einsum("tk,tke->te", gates, jax.nn.one_hot(idx, e, dtype=F32))
    eb = EXPERT_BLOCK if e % EXPERT_BLOCK == 0 else e

    def blocked(w):  # [E, ...] -> [E / eb, eb, ...]
        return w.reshape(e // eb, eb, *w.shape[1:])

    def body(out, blk):
        w1, w3, w2, c = blk  # [eb, D, Fe] x2, [eb, Fe, D], [T, eb]
        w1, w3, w2 = (w.astype(F32) for w in (w1, w3, w2))
        if low:
            xl = _fp8(x, -1)
            w1, w3, w2 = _fp8(w1, 1), _fp8(w3, 1), _fp8(w2, 1)
        else:
            xl = x
        hid = jax.nn.silu(jnp.einsum("td,edf->tef", xl, w1)) * jnp.einsum(
            "td,edf->tef", xl, w3)
        if low:
            hid = _fp8(hid, -1)
        y = jnp.einsum("tef,efd->ted", hid, w2)
        return out + jnp.einsum("te,ted->td", c, y), None

    routed, _ = jax.lax.scan(
        body, jnp.zeros_like(x),
        (blocked(p["w1"]), blocked(p["w3"]), blocked(p["w2"]),
         jnp.moveaxis(coef.reshape(-1, e // eb, eb), 1, 0)))
    sh = p["shared"]
    return routed + _swiglu(x, sh["w1"], sh["w3"], sh["w2"], low)


def _layer(x, p, pos, s, low, moe: bool):
    f32 = lambda a: a.astype(F32)
    x = x + _attention(_rmsnorm(x, f32(p["attn_norm"]), s["eps"]),
                       p["attn"], pos, s, low)
    h = _rmsnorm(x, f32(p["ffn_norm"]), s["eps"])
    if moe:
        return x + _moe(h, p["moe"], s, low)
    m = p["mlp"]
    return x + _swiglu(h, m["w1"], m["w3"], m["w2"], low)


def _hidden(weights, tokens, s, low):
    pos = jnp.arange(tokens.shape[0])
    x = weights["embed"][tokens].astype(F32)
    for key, moe in (("dense_blocks", False), ("blocks", True)):
        if key in weights:
            x, _ = jax.lax.scan(
                lambda x, p, moe=moe: (_layer(x, p, pos, s, low, moe), None),
                x, weights[key])
    return _rmsnorm(x, weights["final_norm"].astype(F32), s["eps"])


def _readings(logits, nxt, labels, topk):
    """Per-position readings of a block of [rows, V] logits."""
    lse = jax.nn.logsumexp(logits, -1)
    vals, idx = jax.lax.top_k(logits, topk)
    lab = jnp.maximum(labels, 0)
    hit = (idx == lab[:, None]).any(-1)
    picked = jnp.take_along_axis(logits, lab[:, None], -1)[:, 0]
    return {
        "best": vals[:, 0],
        "argmax": idx[:, 0],
        "next_logit": jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0],
        "loss": lse - jnp.where(hit, picked, vals[:, -1]),
        "logits": logits,
    }


@functools.partial(jax.jit, static_argnames=("sz", "low", "topk"))
def score(weights, tokens, labels, *, sz, low, topk):
    """Teacher-forced pass over one padded sequence.

    ``tokens`` [T] is prompt then served tokens, right-padded; ``labels``
    [T] holds at each position the label the engine scored there (-1
    elsewhere). Per position: the best logit, the logit of the next token
    in ``tokens``, the argmax, and the loss the recorder's top-k rule gives
    the label (exact on a top-k hit, else the tail floor ``lse -
    min(top-k)``). The LM head and the readings run a block of rows at a
    time, so that no [T, V] temporary beside the logits is held."""
    s = dict(sz)
    t = tokens.shape[0]
    tb = HEAD_BLOCK if t % HEAD_BLOCK == 0 else t
    nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
    with jax.default_matmul_precision("highest"):
        x = _hidden(weights, tokens, s, low)

        def block(args):
            xi, ni, li = args
            return _readings(_mm(xi, weights["lm_head"], low, 1), ni, li,
                             topk)

        out = jax.lax.map(block, (x.reshape(t // tb, tb, -1),
                                  nxt.reshape(t // tb, tb),
                                  labels.reshape(t // tb, tb)))
    return jax.tree.map(lambda a: a.reshape(t, *a.shape[2:]), out)


def run(weights, config: dict, tokens: np.ndarray, labels: np.ndarray,
        *, low: bool, topk: int) -> dict:
    """``score`` for one sequence: numpy per-position readings, and the
    [T, V] logits as a device array under ``logits``."""
    sz = tuple(sorted(sizes(config).items()))
    out = score(weights, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(labels, jnp.int32), sz=sz, low=low, topk=topk)
    logits = out.pop("logits")
    res = {k: np.asarray(v) for k, v in out.items()}
    res["logits"] = logits
    return res
