"""Qwen3 dense decoder: seeded weights and the plain float32 reference.

The reference follows the published Qwen3 block (hf:Qwen/Qwen3-14B,
``Qwen3ForCausalLM``): RMSNorm before attention and MLP, grouped-query
attention with an RMSNorm over each query and key head (qk-norm), rotary
embedding by halves (``rotate_half``) at ``rope_theta``, causal softmax,
SwiGLU MLP (``down(silu(gate(x)) * up(x))``), a final RMSNorm and an
untied LM head. Every matrix product runs in float32 at ``highest``
precision. It imports nothing of the program; it shares with the program
only the weights, which this module makes from the seed in the layout the
program's parameter tree uses (the way a checkpoint loader would):

    embed [V, D]   final_norm [D]   lm_head [V, D]
    blocks/attn_norm [L, D]   blocks/ffn_norm [L, D]
    blocks/attn/{wq [L, D, H, hd], wk, wv [L, D, KV, hd], wo [L, H, hd, D],
                 q_norm, k_norm [L, hd]}
    blocks/mlp/{w1 (gate) [L, D, F], w3 (up) [L, D, F], w2 (down) [L, F, D]}

``control`` is the same forward with both operands of every matrix
product rounded to float8 (e4m3, one scale per row): the precision one
step below the configuration's bfloat16, which a later change might be
tempted to take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
VOCAB_CHUNKS = 8  # the LM head runs in slices of the vocabulary


def sizes(config: dict) -> dict:
    c = config["config"]
    return dict(d=c["hidden_size"], f=c["intermediate_size"],
                h=c["num_attention_heads"], kv=c["num_key_value_heads"],
                hd=c["head_dim"], layers=c["num_hidden_layers"],
                vocab=c["vocab_size"], theta=float(c["rope_theta"]),
                eps=float(c["rms_norm_eps"]))


def weight_shapes(config: dict) -> dict:
    s = sizes(config)
    d, f, h, kv, hd, n, v = (s[k] for k in
                             ("d", "f", "h", "kv", "hd", "layers", "vocab"))
    return {
        "embed": ((v, d), 0.02),
        "final_norm": ((d,), "norm"),
        "lm_head": ((v, d), d ** -0.5),
        "blocks": {
            "attn_norm": ((n, d), "norm"),
            "ffn_norm": ((n, d), "norm"),
            "attn": {
                "wq": ((n, d, h, hd), d ** -0.5),
                "wk": ((n, d, kv, hd), d ** -0.5),
                "wv": ((n, d, kv, hd), d ** -0.5),
                "wo": ((n, h, hd, d), (h * hd) ** -0.5),
                "q_norm": ((n, hd), "norm"),
                "k_norm": ((n, hd), "norm"),
            },
            "mlp": {
                "w1": ((n, d, f), d ** -0.5),
                "w3": ((n, d, f), d ** -0.5),
                "w2": ((n, f, d), f ** -0.5),
            },
        },
    }


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
# the reference forward
# ---------------------------------------------------------------------------


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotary embedding by halves: x [T, heads, hd]."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


def _fp8(x, axis=-1):
    """Round to float8 e4m3 with one scale per slice along ``axis``
    (forward only: nothing here is differentiated)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, low: bool, w_axis: int):
    """x [..., K] . w (contracting w's ``w_axis``) in float32; ``low``
    rounds both operands to float8 first (rows of x, output columns of
    w)."""
    if low:
        x = _fp8(x, -1)
        w = _fp8(w, w_axis)
    return jnp.tensordot(x, w, axes=[[x.ndim - 1], [w_axis]])


def _layer(x, p, pos, s, low):
    t = x.shape[0]
    h = _rmsnorm(x, p["attn_norm"], s["eps"])
    wq = p["attn"]["wq"].reshape(s["d"], -1)
    wk = p["attn"]["wk"].reshape(s["d"], -1)
    wv = p["attn"]["wv"].reshape(s["d"], -1)
    q = _mm(h, wq, low, 0).reshape(t, s["h"], s["hd"])
    k = _mm(h, wk, low, 0).reshape(t, s["kv"], s["hd"])
    v = _mm(h, wv, low, 0).reshape(t, s["kv"], s["hd"])
    q = _rope(_rmsnorm(q, p["attn"]["q_norm"], s["eps"]), pos, s["theta"])
    k = _rope(_rmsnorm(k, p["attn"]["k_norm"], s["eps"]), pos, s["theta"])
    g = s["h"] // s["kv"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(s["hd"])
    causal = pos[None, :, None] >= pos[None, None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    wo = p["attn"]["wo"].reshape(-1, s["d"])
    x = x + _mm(att.reshape(t, -1), wo, low, 0)
    h = _rmsnorm(x, p["ffn_norm"], s["eps"])
    gate = _mm(h, p["mlp"]["w1"], low, 0)
    up = _mm(h, p["mlp"]["w3"], low, 0)
    return x + _mm(jax.nn.silu(gate) * up, p["mlp"]["w2"], low, 0)


def _hidden(weights, tokens, s, low):
    f32 = lambda t: jax.tree.map(lambda a: a.astype(F32), t)
    pos = jnp.arange(tokens.shape[0])
    x = weights["embed"][tokens].astype(F32)

    def body(x, p):
        return _layer(x, f32(p), pos, s, low), None

    x, _ = jax.lax.scan(body, x, weights["blocks"])
    return _rmsnorm(x, weights["final_norm"].astype(F32), s["eps"])


def _logits(weights, x, low):
    """[T, V] float32 logits, the LM head one vocabulary slice at a time."""
    head = weights["lm_head"]
    v, d = head.shape
    n = VOCAB_CHUNKS
    vc = -(-v // n)
    head = jnp.pad(head, ((0, n * vc - v), (0, 0))).reshape(n, vc, d)
    out = jax.lax.map(lambda w: _mm(x, w.astype(F32), low, 1), head)
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], n * vc)[:, :v]


@functools.partial(jax.jit, static_argnames=("sz", "low", "topk"))
def score(weights, tokens, labels, *, sz, low, topk):
    """Teacher-forced pass over one padded sequence.

    ``tokens`` [T] is prompt then served tokens, right-padded; ``labels``
    [T] holds at each position the label the engine scored there (-1
    elsewhere). Per position: the best logit, the logit of the next token
    in ``tokens``, the argmax, the log-sum-exp, and the loss the recorder's
    top-k rule gives the label (exact on a top-k hit, else the tail floor
    ``lse - min(top-k)``)."""
    s = dict(sz)
    with jax.default_matmul_precision("highest"):
        logits = _logits(weights, _hidden(weights, tokens, s, low), low)
    nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
    lse = jax.nn.logsumexp(logits, -1)
    vals, idx = jax.lax.top_k(logits, topk)
    lab = jnp.maximum(labels, 0)
    hit = (idx == lab[:, None]).any(-1)
    picked = jnp.take_along_axis(logits, lab[:, None], -1)[:, 0]
    loss = lse - jnp.where(hit, picked, vals[:, -1])
    return {
        "best": vals[:, 0],
        "argmax": idx[:, 0],
        "next_logit": jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0],
        "loss": loss,
        "logits": logits,
    }


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
