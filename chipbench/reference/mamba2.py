"""Mamba-2 language model: seeded weights and the plain float32 reference
of its loss, gradient and one AdamW step.

The reference follows the published Mamba-2 block (arXiv:2405.21060;
``mamba_ssm.modules.mamba2.Mamba2`` with its defaults, as
hf:state-spaces/mamba2-370m uses it): pre-norm residual blocks; ``in_proj``
to ``[z | x | B | C | dt]``; a depthwise causal convolution of width
``d_conv`` with bias over ``(x, B, C)`` and SiLU; ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``; the scan ``h_t = exp(dt_t A) h_{t-1} + dt_t
B_t x_t^T``, ``y_t = C_t h_t + D x_t``, here in the chunked form of the
paper's ``ssd_minimal_discrete`` listing (exact up to rounding); the gated
RMSNorm ``norm(y * silu(z))``; ``out_proj``; a final RMSNorm and the LM
head tied to the embedding. Matrix products run in float32 at ``highest``
precision. The per-example loss is the mean next-token cross-entropy.

It imports nothing of the program. It shares with the program only the
weights, which this module makes from the seed in the layout of the
program's parameter tree (as a checkpoint loader would):

    embed [V, D]   final_norm [D]
    blocks/norm [L, D]
    blocks/ssm/{in_proj [L, D, 2*Di + 2*N + H], conv_w [L, K, Di + 2*N],
                conv_b [L, Di + 2*N], a_log, dt_bias, d_skip [L, H],
                norm [L, Di], out_proj [L, Di, D]}

``low`` is the control, one step below each precision the configuration
states: both operands of every matrix product and the scan's inputs
rounded to float8 (e4m3, one scale per row) for the bfloat16 weights, and
the residual stream between blocks rounded to bfloat16 for
``residual_in_fp32``; the gradient passes straight through the rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def sizes(config: dict) -> dict:
    c = config["config"]
    d, e = c["d_model"], c["expand"]
    di = d * e
    pad = c.get("pad_vocab_size_multiple", 1)
    return dict(d=d, di=di, n=c["d_state"], hd=c["headdim"],
                h=di // c["headdim"], k=c["d_conv"], layers=c["n_layer"],
                vocab=-(-c["vocab_size"] // pad) * pad,
                tokens=c["vocab_size"], chunk=c["chunk_size"],
                eps=float(c["norm_epsilon"]))


def weight_shapes(config: dict) -> dict:
    s = sizes(config)
    d, di, n, h, k, L, v = (s[x] for x in
                            ("d", "di", "n", "h", "k", "layers", "vocab"))
    conv = di + 2 * n
    return {
        "embed": ((v, d), 0.02),
        "final_norm": ((d,), "norm"),
        "blocks": {
            "norm": ((L, d), "norm"),
            "ssm": {
                "in_proj": ((L, d, 2 * di + 2 * n + h), d ** -0.5),
                "conv_w": ((L, k, conv), "conv"),
                "conv_b": ((L, conv), 0.02),
                "a_log": ((L, h), "a_log"),
                "dt_bias": ((L, h), "dt_bias"),
                "d_skip": ((L, h), "norm"),
                "norm": ((L, di), "norm"),
                "out_proj": ((L, di, d), di ** -0.5),
            },
        },
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def weights_program(config: dict, dtype=jnp.bfloat16):
    """The jitted ``key data [2] uint32 -> weights`` program: normal
    matrices, norm gains and D at 1 + normal(0, 0.1), the convolution
    uniform in +-1/sqrt(width), A_log = log U[1, 16] and dt_bias the
    inverse softplus of dt ~ logU[1e-3, 0.1] (Mamba-2's initialisation)."""
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
            if scale == "norm":
                x = 1.0 + 0.1 * jax.random.normal(k, shape, F32)
            elif scale == "conv":
                lim = shape[-2] ** -0.5
                x = jax.random.uniform(k, shape, F32, -lim, lim)
            elif scale == "a_log":
                x = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
            elif scale == "dt_bias":
                u = jax.random.uniform(k, shape, F32)
                dt = jnp.exp(u * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
                x = dt + jnp.log(-jnp.expm1(-dt))
            else:
                x = scale * jax.random.normal(k, shape, F32)
            return x.astype(dtype)

        return jax.tree_util.tree_map_with_path(leaf, shapes, is_leaf=_is_leaf)

    return build


def make_weights(config: dict, words: list[int], dtype=jnp.bfloat16):
    return weights_program(config, dtype)(
        jnp.asarray(np.asarray(words[:2], np.uint32)))


# ---------------------------------------------------------------------------
# the reference forward and loss
# ---------------------------------------------------------------------------


def _fp8(x, axis=-1):
    """Round to float8 e4m3 with one scale per slice along ``axis``; the
    gradient passes the rounding straight through, so the backward runs
    on the rounded operands as a float8 matrix unit would."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x), axis=axis,
                                         keepdims=True))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _bf16(x):
    """Round to bfloat16, the gradient passed straight through."""
    return x + jax.lax.stop_gradient(x.astype(jnp.bfloat16).astype(F32) - x)


def _mm(x, w, low):
    """x [..., K] @ w [K, N] in float32 (``low``: float8 operands)."""
    if low:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _segsum(x):
    """[..., T] -> [..., T, T]: sum of x over (j, i] for i >= j, -inf
    above the diagonal (the paper's ``segsum``)."""
    t = x.shape[-1]
    xs = jnp.repeat(x[..., None], t, axis=-1)  # [..., i, j] = x_i
    below = jnp.tril(jnp.ones((t, t), bool), -1)
    xs = jnp.where(below, xs, 0.0)
    out = jnp.cumsum(xs, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def _ssd(x, a_dt, b, c, chunk):
    """The paper's ``ssd_minimal_discrete``: x [B, S, H, P] (already times
    dt), a_dt [B, S, H], b, c [B, S, N] (one group) -> y [B, S, H, P]."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    nc = s // chunk
    x = x.reshape(bsz, nc, chunk, h, p)
    a = a_dt.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2)  # b h c l
    b = b.reshape(bsz, nc, chunk, n)
    c = c.reshape(bsz, nc, chunk, n)
    a_cum = jnp.cumsum(a, axis=-1)
    ell = jnp.exp(_segsum(a))  # b h c l l
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", c, b, ell, x)
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)  # b h c l
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", b, decay_states, x)
    init = jnp.zeros_like(states[:, :1])
    states = jnp.concatenate([init, states], axis=1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0),
                                                            (1, 0)))))
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states)
    states = new_states[:, :-1]
    state_decay_out = jnp.exp(a_cum)
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", c, states, state_decay_out)
    return (y_diag + y_off).reshape(bsz, s, h, p)


def _mixer(x, p, s, low):
    """One Mamba-2 mixer on x [B, S, D] (float32)."""
    bsz, t, _ = x.shape
    di, n, h, hd, k = s["di"], s["n"], s["h"], s["hd"], s["k"]
    zxbcdt = _mm(x, p["in_proj"], low)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n],
                  zxbcdt[..., 2 * di + 2 * n:])
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + t] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, b, c = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [B, S, H]
    a = -jnp.exp(p["a_log"])
    xh = xs.reshape(bsz, t, h, hd)
    xdt = xh * dt[..., None]
    if low:  # the scan's operands in float8 too
        xdt, b, c = _fp8(xdt), _fp8(b), _fp8(c)
    y = _ssd(xdt, dt * a, b, c, s["chunk"])
    y = y + xh * p["d_skip"][:, None]
    y = y.reshape(bsz, t, di) * jax.nn.silu(z)
    y = _rmsnorm(y, p["norm"], s["eps"])
    return _mm(y, p["out_proj"], low)


def per_example_loss(weights, tokens, labels, s, low=False):
    """Mean next-token cross-entropy of each row: tokens, labels [B, S]."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(F32), t)
    emb = weights["embed"].astype(F32)
    x = emb[tokens]

    @jax.checkpoint
    def block(x, p):
        p = f32(p)
        x = x + _mixer(_rmsnorm(x, p["norm"], s["eps"]), p["ssm"], s, low)
        return _bf16(x) if low else x  # the residual stream in bfloat16

    x, _ = jax.lax.scan(lambda x, p: (block(x, p), None), x,
                        weights["blocks"])
    x = _rmsnorm(x, weights["final_norm"].astype(F32), s["eps"])
    logits = _mm(x, emb.T, low)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked, -1)


@functools.partial(jax.jit, static_argnames=("sz", "low"))
def loss_and_grad(weights, tokens, labels, *, sz, low):
    """(mean loss over the rows, per-example losses, gradient of the mean
    with respect to the float32 weights)."""
    s = dict(sz)

    def mean_loss(w):
        pel = per_example_loss(w, tokens, labels, s, low)
        return jnp.mean(pel), pel

    with jax.default_matmul_precision("highest"):
        w32 = jax.tree.map(lambda a: a.astype(F32), weights)
        (loss, pel), g = jax.value_and_grad(mean_loss, has_aux=True)(w32)
    return loss, pel, g


# ---------------------------------------------------------------------------
# selection and the optimizer, as the training job states them
# ---------------------------------------------------------------------------


def obftf_select(losses: np.ndarray, b: int, z: float, swaps: int) -> np.ndarray:
    """The paper's OBFTF subset (Algorithm 1 with the appendix's solver):
    the target sum is ``b`` times a mean drawn as mean + z * std / sqrt(b);
    start from the stride picks over the losses sorted in descending order
    (``floor(i * n / (b + 1))`` for i = 1..b), then up to ``swaps`` times
    take the single (selected, unselected) exchange that brings the
    selected sum nearest the target, when it does. Returns the sorted
    selected row indices."""
    x = np.asarray(losses, np.float32)
    n = x.size
    total = np.float32(b) * np.float32(
        x.mean(dtype=np.float32)
        + np.float32(z) * (x.std(dtype=np.float32) / np.sqrt(np.float32(b))))
    order = np.argsort(-x, kind="stable")
    picks = np.minimum(np.arange(1, b + 1) * n // (b + 1), n - 1)
    mask = np.zeros(n, bool)
    mask[order[picks]] = True
    cur = np.float32(x[mask].sum(dtype=np.float32))
    for _ in range(swaps):
        resid = cur - total
        best, bi, bj = np.inf, -1, -1
        for i in range(n):
            if not mask[i]:
                continue
            for j in range(n):
                if mask[j]:
                    continue
                v = abs(resid + (x[j] - x[i]))
                if v < best:
                    best, bi, bj = v, i, j
        if best < abs(resid) - 1e-9:
            mask[bi], mask[bj] = False, True
            cur = cur - x[bi] + x[bj]
    return np.flatnonzero(mask)


def adamw_step(params, grads, m, v, step: int, job: dict):
    """One AdamW step as the job states it: clip the gradient to global
    norm ``clip``, moments b1/b2, bias correction, ``lr`` constant,
    decoupled weight decay; parameters stored in bfloat16."""
    b1, b2, eps = job["b1"], job["b2"], job["eps"]
    lr, wd, clip = job["lr"], job["weight_decay"], job["clip_norm"]
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / (gn + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, a, b):
        u = (a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p.astype(F32)
        return (p + (-lr * u).astype(p.dtype)).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), grads, m, v
