"""Beyond-paper perf features: int8 KV cache, MoE grouping, CP/Ulysses
constraints, int8 ZeRO-3 gathers — correctness on CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as Mdl
from repro.models.params import materialize

RNG = jax.random.key(0)


def _fp32(cfg, **kw):
    return dataclasses.replace(
        cfg, param_dtype="float32", compute_dtype="float32", **kw
    )


def test_int8_kv_cache_decode_close_to_bf16():
    cfg = _fp32(configs.get_smoke("llama3_8b"))
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    params = materialize(Mdl.param_specs(cfg), RNG, dtype=jnp.float32)
    b, s, s0 = 2, 24, 16
    toks = jax.random.randint(RNG, (b, s), 0, cfg.vocab_size)
    lg, c = Mdl.prefill(params, cfg, toks[:, :s0], max_seq=s)
    lg8, c8 = Mdl.prefill(params, cfg8, toks[:, :s0], max_seq=s)
    for t in range(s0, s):
        lg, c = Mdl.decode_step(params, cfg, c, toks[:, t : t + 1],
                                jnp.asarray(t, jnp.int32))
        lg8, c8 = Mdl.decode_step(params, cfg8, c8, toks[:, t : t + 1],
                                  jnp.asarray(t, jnp.int32))
        delta = float(jnp.abs(jax.nn.softmax(lg8) - jax.nn.softmax(lg)).max())
        assert delta < 5e-3, delta
        assert bool((jnp.argmax(lg8, -1) == jnp.argmax(lg, -1)).all())
    # cache payload really is int8
    assert c8["blocks"]["k"].dtype == jnp.int8
    assert c8["blocks"]["k_scale"].dtype == jnp.float32


def test_int8_kv_cache_sliding_window():
    cfg = _fp32(configs.get_smoke("mixtral_8x22b"), kv_cache_dtype="int8",
                capacity_factor=8.0)
    ref = _fp32(configs.get_smoke("mixtral_8x22b"), capacity_factor=8.0)
    params = materialize(Mdl.param_specs(ref), RNG, dtype=jnp.float32)
    b, s, s0 = 1, 28, 20
    toks = jax.random.randint(RNG, (b, s), 0, ref.vocab_size)
    lg, c = Mdl.prefill(params, ref, toks[:, :s0], max_seq=s)
    lg8, c8 = Mdl.prefill(params, cfg, toks[:, :s0], max_seq=s)
    for t in range(s0, s):
        lg, c = Mdl.decode_step(params, ref, c, toks[:, t : t + 1],
                                jnp.asarray(t, jnp.int32))
        lg8, c8 = Mdl.decode_step(params, cfg, c8, toks[:, t : t + 1],
                                  jnp.asarray(t, jnp.int32))
    delta = float(jnp.abs(jax.nn.softmax(lg8) - jax.nn.softmax(lg)).max())
    assert delta < 1e-2, delta


def test_moe_group_preserves_output():
    import repro.models.moe as M

    cfg = _fp32(configs.get_smoke("deepseek_v2_236b"), capacity_factor=8.0)
    p = materialize(M.moe_specs(cfg), RNG, dtype=jnp.float32)
    x = jax.random.normal(RNG, (2, 64, cfg.d_model), jnp.float32)
    o1, _ = M.moe_ffn(x, p, cfg)
    o2, _ = M.moe_ffn(x, p, dataclasses.replace(cfg, moe_group=16))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_blocked_attn_threshold_preserves_output():
    cfg = _fp32(configs.get_smoke("llama3_8b"))
    cfg_b = dataclasses.replace(cfg, blocked_attn_min=8)  # force blocked
    params = materialize(Mdl.param_specs(cfg), RNG, dtype=jnp.float32)
    toks = jax.random.randint(RNG, (2, 33), 0, cfg.vocab_size)
    h1, _ = Mdl.forward_hidden(params, cfg, toks)
    h2, _ = Mdl.forward_hidden(params, cfg_b, toks)
    np.testing.assert_allclose(
        np.asarray(h1, np.float32), np.asarray(h2, np.float32),
        atol=1e-4, rtol=1e-4,
    )


def test_constraints_are_noops_without_rules():
    """cp_kv_gather / ulysses / param_gather must be identity when no
    sharding context is active (single-device training path)."""
    from repro.distributed.sharding import (
        cp_kv_gather,
        param_gather_constraint,
        set_rules,
        ulysses_constraint,
    )

    set_rules(None, None)
    x = jnp.ones((2, 8, 4, 16))
    assert cp_kv_gather(x, 1) is x
    assert ulysses_constraint(x, "heads") is x
    tree = {"w": jnp.ones((4, 4))}
    assert param_gather_constraint(tree)["w"] is tree["w"]


def test_int8_zero3_gather_values_and_grads():
    from repro.distributed import sharding as S

    mesh = jax.make_mesh(
        (1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
    rules = dataclasses.replace(
        S.DEFAULT_RULES, gather_params=True, int8_gather=True
    )
    w = jax.random.normal(RNG, (32, 16), jnp.float32)
    c = jax.random.normal(jax.random.key(1), (32, 16), jnp.float32)
    with S.use_rules(mesh, rules):
        out = jax.jit(
            lambda w: S.param_gather_constraint({"w": w})["w"]
        )(w)
        g = jax.jit(
            jax.grad(lambda w: jnp.sum(S.param_gather_constraint({"w": w})["w"] * c))
        )(w)
    assert float(jnp.abs(out - w).max()) <= float(jnp.abs(w).max()) / 127 + 1e-6
    # straight-through: exact c up to the bf16 cotangent cast
    assert float(jnp.abs(g - c).max()) < 2e-2
