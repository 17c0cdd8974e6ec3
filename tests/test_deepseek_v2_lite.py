"""DeepSeek-V2-Lite on the serving path, at SMOKE size on the CPU with
seeded random weights: MLA without q-LoRA, DeepSeek's YaRN, the latent
page pool with its paged kernel, and the drop-free MoE layer.

Everything runs in float32 (parameters and activations), so the program
and the float32 reference differ only in the order of their sums:
absorbed against un-absorbed attention, grouped against dense expert
products, blocked against whole softmax.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import deepseek_v2 as R
from repro import configs
from repro.kernels import ops
from repro.models import layers as L
from repro.models import model as Mdl
from repro.models import moe as M
from repro.models.params import materialize
from repro.serving import Engine, OutcomeRecorder

SMOKE = dataclasses.replace(configs.get_smoke("deepseek-v2-lite"),
                            param_dtype="float32", compute_dtype="float32")
RNG = jax.random.key(0)


def hf_config(cfg) -> dict:
    """The configuration file's ``config`` block for a program config (the
    keys ``reference.deepseek_v2.sizes`` reads)."""
    return {"config": {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "v_head_dim": cfg.v_head_dim, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.moe_d_ff,
        "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "n_shared_experts": cfg.num_shared_experts,
        "first_k_dense_replace": cfg.first_k_dense,
        "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "routed_scaling_factor": 1.0,
        "rope_scaling": {
            "type": "yarn", "factor": cfg.yarn_factor,
            "original_max_position_embeddings": cfg.yarn_original_max_pos,
            "beta_fast": cfg.yarn_beta_fast, "beta_slow": cfg.yarn_beta_slow,
            "mscale": cfg.yarn_mscale,
            "mscale_all_dim": cfg.yarn_mscale_all_dim,
        },
    }}


CONF = hf_config(SMOKE)


@pytest.fixture(scope="module")
def weights():
    return R.make_weights(CONF, [3, 4, 5, 6], dtype=jnp.float32)


def reference_logits(weights, tokens: np.ndarray) -> np.ndarray:
    sz = tuple(sorted(R.sizes(CONF).items()))
    out = R.score(weights, jnp.asarray(tokens, jnp.int32),
                  jnp.full(tokens.shape, -1, jnp.int32), sz=sz, low=False,
                  topk=4)
    return np.asarray(out["logits"])


def test_reference_weights_are_the_program_tree(weights):
    want = jax.tree.map(lambda s: s.shape, Mdl.param_specs(SMOKE),
                        is_leaf=lambda x: hasattr(x, "axes"))
    assert jax.tree.map(lambda a: a.shape, weights) == want


# ---------------------------------------------------------------------------
# the engine against the float32 reference
# ---------------------------------------------------------------------------


def test_engine_prefill_and_paged_latent_decode_match_reference(weights):
    """Three requests through the engine at once (bucketed prefill, latent
    pages of 4 tokens, temperature 0): the logits the recorder retained
    for every generated position equal the reference's teacher-forced
    logits over prompt and served tokens. Tolerance 2e-4 of the largest
    logit: float32 on both sides, only the order of sums differs (the
    readings are ~1e-6 of it)."""
    slots, gen = 3, 6
    rec = OutcomeRecorder(slots, gen, SMOKE.vocab_size, retention="full")
    eng = Engine(SMOKE, weights, rec, slots=slots, max_prompt=24,
                 max_gen=gen, page_size=4)
    assert eng.prompt_buckets == (8, 16, 24)  # MoE prefill pads now
    rs = np.random.default_rng(7)
    prompts = [rs.integers(0, SMOKE.vocab_size, n) for n in (5, 24, 11)]
    ids = [eng.submit(p, max_new=gen, expect_labels=True) for p in prompts]
    for _ in range(gen):
        eng.step()
    slot_of = dict(eng._slot_of)
    logits = np.asarray(jax.device_get(eng._rstate.logits))
    for iid in ids:
        eng.deliver_outcome(iid, np.zeros(gen, np.int64))
    eng.run(max_steps=20)
    for iid, prompt in zip(ids, prompts):
        toks = np.concatenate([prompt, eng.finished[iid]])
        want = reference_logits(weights, toks)[len(prompt) - 1: -1]
        got = logits[slot_of[iid]]
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, atol=2e-4 * scale, rtol=0)


def test_padded_prefill_equals_exact_length_for_moe(weights):
    """A right-padded prompt reads its logits at its own last position and
    leaves every real position's latent as the exact-length prefill does
    (drop-free routing: pads take no expert capacity from real tokens)."""
    toks = jax.random.randint(RNG, (1, 11), 0, SMOKE.vocab_size)
    padded = jnp.pad(toks, [(0, 0), (0, 5)])
    lg, cache = Mdl.prefill(weights, SMOKE, toks, max_seq=16)
    lgp, cachep = Mdl.prefill(weights, SMOKE, padded, max_seq=16,
                              last_pos=jnp.asarray([10]))
    np.testing.assert_allclose(np.asarray(lgp), np.asarray(lg), atol=1e-5)
    for key in ("dense_blocks", "blocks"):
        for leaf in ("ckv", "kpe"):
            np.testing.assert_allclose(
                np.asarray(cachep[key][leaf][:, :, :11]),
                np.asarray(cache[key][leaf][:, :, :11]), atol=1e-5)


# ---------------------------------------------------------------------------
# the drop-free MoE layer
# ---------------------------------------------------------------------------


def per_token_moe(x, p, cfg):
    """Each token's own top-k experts, one at a time, plus the shared
    experts: the loop the grouped matmuls replace."""
    xs = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    res = []
    for xt in xs:
        logits = xt @ np.asarray(p["router"], np.float64)
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[: cfg.experts_per_token]
        out = np.zeros_like(xt)
        for e in top:
            w1, w3, w2 = (np.asarray(p[k][e], np.float64)
                          for k in ("w1", "w3", "w2"))
            a = xt @ w1
            out += probs[e] * (((a / (1 + np.exp(-a))) * (xt @ w3)) @ w2)
        sh = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}
        a = xt @ sh["w1"]
        out += ((a / (1 + np.exp(-a))) * (xt @ sh["w3"])) @ sh["w2"]
        res.append(out)
    return np.stack(res).reshape(x.shape)


@pytest.mark.parametrize("router", ["seeded", "one_expert"])
def test_dropless_moe_equals_per_token_sum(router):
    """Every token's pairs are computed, even when the router sends every
    token to the same expert (a capacity layer would drop all but its
    capacity there)."""
    cfg = SMOKE
    p = materialize(M.moe_specs(cfg), RNG, dtype=jnp.float32)
    if router == "one_expert":
        p["router"] = p["router"].at[:, 3].set(50.0)
    x = jax.random.normal(jax.random.key(1), (2, 24, cfg.d_model))
    if router == "one_expert":
        x = jnp.abs(x)  # a positive row sum: expert 3 wins every token
    out, counts = M.moe_ffn_dropless(x, p, cfg)
    assert int(counts.sum()) == 48 * cfg.experts_per_token
    if router == "one_expert":
        assert int(counts[3]) == 48
    np.testing.assert_allclose(np.asarray(out), per_token_moe(x, p, cfg),
                               rtol=1e-5, atol=1e-5)


def test_training_and_serving_layers_route_alike():
    """Both layers take their gate from ``moe.route`` (f32 logits). Experts
    0 and 1 tie for the third of three places up to one router entry,
    which moves their logits (~3.2) ~0.002 apart: below a bf16 logit's
    half ulp (0.0078), so only an f32 gate sends every token to expert 1.
    With room for every pair the capacity layer (training) then gives the
    drop-free layer's output (serving): a token is routed alike where its
    ledger loss is recorded and where that loss is recycled."""
    cfg = dataclasses.replace(SMOKE, capacity_factor=float(SMOKE.num_experts))
    p = materialize(M.moe_specs(cfg), RNG, dtype=jnp.bfloat16)
    col = jnp.array([0.75, 0.75, 1.0, 1.0, -1.0, -1.0, -1.0, -1.0])
    router = jnp.broadcast_to(col, (cfg.d_model, 8)).at[5, 1].add(2.0**-5)
    p["router"] = router.astype(jnp.bfloat16)
    u = jax.random.uniform(jax.random.key(2), (2, 64, cfg.d_model))
    x = ((1.0 + 0.1 * u) / 16).astype(jnp.bfloat16)
    serve, counts = M.moe_ffn_dropless(x, p, cfg)
    assert int(counts[1]) == 128 and int(counts[0]) == 0
    train, _ = M.moe_ffn(x, p, cfg)
    train, serve = (np.asarray(a, np.float32) for a in (train, serve))
    assert np.abs(train - serve).max() <= 0.02 * np.abs(serve).max()


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def test_yarn_frequencies_and_scale_by_hand():
    """At the published widths (rope 64, theta 1e4, factor 40, original
    4096 positions, beta 32 / 1): the correction range is dims 10..23
    (floor(64 ln(4096 / 64 pi) / 2 ln 1e4) = floor(10.47), ceil(22.54));
    below it the plain frequency, above it the frequency over 40, between
    a linear ramp. mscale(40, 0.707) = 1 + 0.0707 ln 40 = 1.260804, whose
    square multiplies the softmax scale 192^-0.5."""
    cfg = configs.get("deepseek-v2-lite")
    inv = L.yarn_inv_freq(cfg, 64)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13  # dim 16: 6/13 of the way to the interpolation
    np.testing.assert_allclose(
        inv[16], plain[16] / 40 * ramp + plain[16] * (1 - ramp), rtol=1e-6)
    m = 1 + 0.1 * 0.707 * math.log(40)
    assert m == pytest.approx(1.260804, abs=1e-6)
    assert L.mla_softmax_scale(cfg) == pytest.approx(m * m / math.sqrt(192))
    # mscale == mscale_all_dim: cos and sin keep unit norm
    x = jnp.ones((1, 3, 1, 64))
    y = L.rope_cfg(x, jnp.arange(3), cfg)
    np.testing.assert_allclose(np.asarray(y[0, 0]), np.asarray(x[0, 0]))
    # the reference's own YaRN agrees
    s = R.sizes(hf_config(cfg))
    rinv, cs, scale = R.yarn(s)
    np.testing.assert_allclose(rinv, inv, rtol=1e-7)
    assert cs == 1.0 and scale == pytest.approx(L.mla_softmax_scale(cfg))


def test_plain_rope_configs_are_unchanged():
    """No ``yarn_factor``: ``rope_cfg`` is plain rope and MLA's scale is
    (nope + pe)^-0.5, as before YaRN existed."""
    cfg = configs.get_smoke("deepseek-v2-236b")
    x = jax.random.normal(RNG, (1, 5, 2, 8))
    pos = jnp.arange(5)
    np.testing.assert_array_equal(np.asarray(L.rope_cfg(x, pos, cfg)),
                                  np.asarray(L.rope(x, pos, cfg.rope_theta)))
    assert L.mla_softmax_scale(cfg) == (16 + 8) ** -0.5


# ---------------------------------------------------------------------------
# the paged latent kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("page", [8, 16])
def test_paged_latent_kernel_interpret_matches_ref(page):
    """The Pallas kernel in interpret mode against the jnp ref, in float32
    (so only the order of sums differs): a slot at depth 0, one over 30
    scattered pages (two page blocks at page 16, one at page 8), and a
    free slot (no first page; reads zeros)."""
    b, h, w, vd, npg, pool = 3, 16, 640, 512, 40, 48
    q = jax.random.normal(jax.random.key(2), (b, h, w), jnp.float32)
    lat = jax.random.normal(jax.random.key(3), (pool, page, w), jnp.float32)
    perm = np.random.default_rng(0).permutation(pool)[:30]
    pt = np.full((b, npg), -1, np.int32)
    pt[0, 0] = 4
    pt[1, :30] = perm
    pos = jnp.asarray([0, 29 * page + 3, 0], jnp.int32)
    run = functools.partial(ops.paged_latent_attn, q, lat, jnp.asarray(pt),
                            pos, scale=0.05, v_dim=vd)
    got = np.asarray(run(impl="interpret"))
    want = np.asarray(run(impl="ref"))
    np.testing.assert_allclose(got[:2], want[:2], atol=1e-5, rtol=1e-5)
    assert not got[2].any()


def test_latent_pool_holds_one_padded_row_a_token():
    # the cell's cut: 9 of 27 layers
    cfg = dataclasses.replace(configs.get("deepseek-v2-lite"), num_layers=9)
    assert L.mla_latent_width(cfg) == 640  # 512 ckv + 64 kpe, to 128 lanes
    shapes = jax.eval_shape(lambda: Mdl.init_paged_cache(cfg, 10, 16))
    assert shapes["blocks"]["lat"].shape == (8, 10, 16, 640)
    assert shapes["dense_blocks"]["lat"].shape == (1, 10, 16, 640)
