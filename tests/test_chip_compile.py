"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described and not attached, so these tests catch what interpret mode
cannot: block shapes the lowering refuses and kernels over the fast-memory
budget. Each compiles one kernel at the widths the chip runs and checks
that the program holds the kernel (``tpu_custom_call``). Nothing runs.

The topology is described inside a module fixture and never while a module
is imported: only one process at a time may load the TPU library.

The last tests run on the CPU: they pin the dispatch rule that sends the
main path to these kernels on a TPU.
"""

import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import decode_attn as DA_mod
from repro.kernels import ops
from repro.kernels import topk_lse as TK_mod

QWEN = configs.get("qwen3-14b")  # the served config's published widths
LEDGER_CAPACITY = 1 << 16  # HistoryConfig's default table


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to a persistent cache
    # but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows", [1, 32, 256])
def test_topk_lse_compiles_at_full_vocab(one_chip, rows):
    text = _compiled_text(
        lambda x: TK_mod.topk_lse(x, 64), one_chip,
        ((rows, QWEN.vocab_size), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "b,npg,pool_pages,hq,hkv,page",
    [
        pytest.param(16, 256, 16 * 256, QWEN.num_heads, QWEN.num_kv_heads,
                     16, id="16"),
        pytest.param(16, 32, 16 * 32, QWEN.num_heads, QWEN.num_kv_heads,
                     128, id="128"),
        # the serve cell: 64 slots of 1,024 positions, a 4,096-page pool
        pytest.param(64, 64, 4096, QWEN.num_heads, QWEN.num_kv_heads, 16,
                     id="serve-cell"),
        # MHA (deepseek-7b's 32 kv heads): the block rule must fit VMEM
        pytest.param(16, 64, 16 * 64, 32, 32, 16, id="mha"),
    ],
)
def test_paged_decode_attn_compiles_at_qwen3_widths(one_chip, b, npg,
                                                    pool_pages, hq, hkv,
                                                    page):
    pool = (2, pool_pages, hkv, page, QWEN.head_dim)  # a stack of 2 layers
    text = _compiled_text(
        DA_mod.paged_decode_attn, one_chip,
        ((b, hq, QWEN.head_dim), jnp.bfloat16),
        (pool, jnp.bfloat16),
        (pool, jnp.bfloat16),
        ((b, npg), jnp.int32),
        ((b,), jnp.int32),
        ((), jnp.int32),
    )
    assert "tpu_custom_call" in text


DSV2 = configs.get("deepseek-v2-lite")  # published widths


def test_paged_latent_attn_compiles_at_the_cell_shapes(one_chip):
    """The dsv2lite-rag cell: 64 slots of 240 pages (3,840 positions), a
    15,360-page pool of rows of 640 (576 latent columns to 128 lanes), in
    a stack of 2 layers."""
    from repro.models.layers import mla_latent_width, mla_softmax_scale

    w = mla_latent_width(DSV2)
    text = _compiled_text(
        functools.partial(DA_mod.paged_latent_attn,
                          scale=mla_softmax_scale(DSV2),
                          v_dim=DSV2.kv_lora_rank), one_chip,
        ((64, DSV2.num_heads, w), jnp.bfloat16),
        ((2, 64 * 240, 16, w), jnp.bfloat16),
        ((64, 240), jnp.int32),
        ((64,), jnp.int32),
        ((), jnp.int32),
    )
    assert "paged_latent_attn" in text and "tpu_custom_call" in text


def _big_arrays(text: str, elems: int) -> list[tuple[str, str, tuple]]:
    """(name, opcode, shape) of every instruction outside a fusion body
    whose array holds at least ``elems`` elements."""
    fused = set(re.findall(r"fusion\(.*?calls=(%[\w.\-]+)", text))
    comp, out = None, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        inst = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]\S* "
                        r"([\w\-]+)\(", line)
        if comp in fused or not inst:
            continue
        shape = tuple(int(d) for d in inst.group(2).split(",") if d)
        if math.prod(shape) >= elems:
            out.append((inst.group(1), inst.group(3), shape))
    return out


@pytest.mark.parametrize("arch,layers,pages,npg", [
    # the qwen3-decode cell's pool: 4,096 pages of 16 for 64 slots of 64
    ("qwen3-14b", 2, 4096, 64),
    # dsv2lite-rag's: 15,360 pages for 64 slots of 240 (a dense first
    # layer, then 2 MoE layers)
    ("deepseek-v2-lite", 3, 15360, 240),
])
def test_paged_decode_step_keeps_the_pool_in_place(one_chip, monkeypatch,
                                                   arch, layers, pages, npg):
    """The fused step's paged decode, compiled for the chip at published
    widths with the pool donated, carries the stacked pool through its
    layer scan: no instruction makes an array of one layer's pool (a
    slice, a relayout or a restack of it), and the step's temporaries
    stay under one layer's pool. Only the in-place row writes, of the
    stack's whole shape, and the routed experts' weights of one layer
    (MoE), which the grouped matmuls read, are as large."""
    from repro.models import model as Mdl
    from repro.models.params import ParamSpec

    monkeypatch.setattr(ops, "default_impl", lambda: "pallas")
    cfg = dataclasses.replace(configs.get(arch), num_layers=layers)
    slots, page = 64, 16
    shaped = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = shaped(jax.tree.map(
        lambda sp: jax.ShapeDtypeStruct(sp.shape, jnp.bfloat16),
        Mdl.param_specs(cfg), is_leaf=lambda x: isinstance(x, ParamSpec)))
    cache = shaped(jax.eval_shape(
        lambda: Mdl.init_paged_cache(cfg, pages, page)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    step = jax.jit(lambda p, c, t, pos, pt: Mdl.decode_step(p, cfg, c, t,
                                                            pos, pt),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache, i32(slots, 1), i32(slots),
                          i32(slots, npg)).compile()

    # the stacks themselves; a stack of one layer is that layer's pool
    stacks = {a.shape for a in jax.tree.leaves(cache)}
    stacks |= {s[1:] for s in stacks if s[0] == 1}
    pool = next(iter(jax.tree.leaves(cache["blocks"])))
    slab = math.prod(pool.shape[1:])
    experts = set()
    if cfg.family == "moe":  # one layer's routed experts, [E, ...]
        moe = Mdl.param_specs(cfg)["blocks"]["moe"]
        experts = {moe[w].shape[1:] for w in ("w1", "w2", "w3")}
    passes = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}
    big = [(name, op, shape)
           for name, op, shape in _big_arrays(compiled.as_text(), slab)
           if op not in passes and shape not in stacks | experts]
    assert big == []
    slab_bytes = slab * pool.dtype.itemsize
    expert_bytes = max((math.prod(e) * 2 for e in experts), default=0)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < slab_bytes + expert_bytes, (temp, slab_bytes)


@pytest.mark.parametrize("tokens", [64, 3072], ids=["decode", "prefill"])
def test_dropless_moe_compiles_at_published_widths(one_chip, tokens):
    """The drop-free expert layer of 64 experts of 1,408, top-6: a decode
    step of 64 slots and a prefill of 3,072 tokens. Its grouped matmuls
    are the ``ragged-dot`` operations ``moe_roofline`` reads."""
    from repro.models import moe
    from repro.models.params import ParamSpec

    specs = moe.moe_specs(DSV2)
    shapes = jax.tree.map(lambda s: (s.shape, jnp.bfloat16), specs,
                          is_leaf=lambda x: isinstance(x, ParamSpec))
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(
        x, tuple) and isinstance(x[0], tuple))

    def layer(x, *ps):
        return moe.moe_ffn_dropless(x, jax.tree.unflatten(tree, ps), DSV2)

    text = _compiled_text(layer, one_chip,
                          ((1, tokens, DSV2.d_model), jnp.bfloat16), *leaves)
    assert text.count("ragged-dot") >= 3


@pytest.mark.parametrize("variant,batch", [("fori", 64), ("block", 512)])
def test_ledger_record_priority_compiles(one_chip, variant, batch):
    cap = LEDGER_CAPACITY

    def record(ema, count, last_seen, owner, ids, losses, step):
        return ops.ledger_record_priority(
            ema, count, last_seen, owner, ids, losses, step,
            decay=0.9, unseen_priority=1e6, impl="pallas", variant=variant,
        )

    text = _compiled_text(
        record, one_chip,
        ((cap,), jnp.float32), ((cap,), jnp.int32), ((cap,), jnp.int32),
        ((cap,), jnp.int32), ((batch,), jnp.int32), ((batch,), jnp.float32),
        ((), jnp.int32),
    )
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# the dispatch rule (CPU): on a TPU the main path reaches the kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,want", [("tpu", "pallas"), ("cpu", "ref"),
                                          ("gpu", "ref")])
def test_default_impl_follows_the_platform(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.default_impl() == want


def test_tpu_main_path_calls_the_kernels(monkeypatch):
    """With a TPU backend, the paged decode layer and the recorder's top-k
    summary call the Pallas kernels compiled for the chip (interpret off),
    never the jnp oracle."""
    from repro.models import layers
    from repro.models import model as Mdl
    from repro.models.params import materialize
    from repro.serving import OutcomeRecorder

    calls = []

    def spy(name, real):
        def run(*a, interpret=False, **k):
            calls.append((name, interpret))
            return real(*a, interpret=True, **k)  # CPU stand-in for the chip
        return run

    monkeypatch.setattr(DA_mod, "paged_decode_attn",
                        spy("paged", DA_mod.paged_decode_attn))
    monkeypatch.setattr(TK_mod, "topk_lse", spy("topk", TK_mod.topk_lse))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    cfg = configs.get_smoke("qwen3-14b")
    params = materialize(Mdl.param_specs(cfg), jax.random.key(0),
                         jnp.float32)
    layer = jax.tree.map(lambda x: x[0], params["blocks"]["attn"])
    cache = jax.tree.map(lambda a: a[None],
                         layers.gqa_paged_init_cache(cfg, 4, 8, jnp.float32))
    x = jnp.ones((2, 1, cfg.d_model), jnp.float32)
    pt = jnp.asarray([[0, -1], [1, 2]], jnp.int32)
    layers.gqa_paged_decode(x, layer, cfg, cache, pt,
                            jnp.asarray([3, 9], jnp.int32), jnp.int32(0))
    rec = OutcomeRecorder(2, 4, cfg.vocab_size, retention="topk", topk=8)
    rec.observe(rec.init_state(), jnp.zeros((2,), jnp.int32),
                jnp.asarray(np.random.default_rng(0).normal(
                    size=(2, cfg.vocab_size)), jnp.float32),
                jnp.ones((2,), bool))
    assert calls == [("paged", False), ("topk", False)]
