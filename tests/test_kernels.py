"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attn as DA_mod
from repro.kernels import ops, ref
from repro.kernels import ssd as SSD_mod
from repro.kernels import topk_lse as TK_mod
from repro.kernels import xent as X_mod

RNG = jax.random.key(7)


# ---------------------------------------------------------------------------
# xent
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,v", [(8, 128), (100, 1000), (256, 2048), (5, 97)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_xent_fwd_matches_ref(t, v, dtype):
    logits = (jax.random.normal(RNG, (t, v), jnp.float32) * 4).astype(dtype)
    labels = jax.random.randint(RNG, (t,), 0, v)
    loss, lse = X_mod.xent_fwd(logits, labels, bt=32, bv=256, interpret=True)
    rl, rlse = ref.xent_ref(logits, labels)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(loss), np.asarray(rl), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse), atol=tol, rtol=tol)


@pytest.mark.parametrize("t,v", [(16, 256), (64, 513)])
def test_xent_bwd_matches_ref(t, v):
    logits = jax.random.normal(RNG, (t, v), jnp.float32) * 3
    labels = jax.random.randint(RNG, (t,), 0, v)
    g = jax.random.normal(RNG, (t,))
    _, lse = ref.xent_ref(logits, labels)
    grad = X_mod.xent_bwd(logits, labels, lse, g, bt=32, bv=256, interpret=True)
    gref = ref.xent_grad_ref(logits, labels, lse, g)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(gref), atol=2e-6)


def test_xent_custom_vjp_consistent_with_autodiff():
    logits = jax.random.normal(RNG, (12, 65), jnp.float32)
    labels = jax.random.randint(RNG, (12,), 0, 65)
    f_kernel = lambda l: jnp.sum(jnp.tanh(ops.xent_loss(l, labels, "interpret")))
    f_ref = lambda l: jnp.sum(jnp.tanh(ops.xent_loss(l, labels, "ref")))
    g1, g2 = jax.grad(f_kernel)(logits), jax.grad(f_ref)(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=2e-6)


def test_xent_extreme_logits_stable():
    """Online LSE must not overflow with large-magnitude logits."""
    logits = jnp.asarray([[1e4, -1e4, 0.0, 5e3]] * 8, jnp.float32)
    labels = jnp.zeros((8,), jnp.int32)
    loss, _ = X_mod.xent_fwd(logits, labels, bt=8, bv=128, interpret=True)
    assert np.isfinite(np.asarray(loss)).all()
    np.testing.assert_allclose(np.asarray(loss), 0.0, atol=1e-3)


@pytest.mark.parametrize("t,v", [(5, 97), (13, 130), (9, 257)])
def test_xent_negative_label_parity(t, v):
    """The -1 "unknown" sentinel must mean NO HIT (loss = lse) in both the
    kernel and the ref oracle. Pre-fix, ref's take_along_axis wrapped -1
    to the LAST vocab column (loss = lse - logits[:, -1]) while the
    kernel scored lse — a silent kernel/oracle disagreement on exactly
    the label value the recorder uses for unlabeled positions."""
    logits = jax.random.normal(RNG, (t, v), jnp.float32) * 3
    labels = np.array(jax.random.randint(RNG, (t,), 0, v))
    labels[::2] = -1  # mix sentinel and real labels
    labels = jnp.asarray(labels)
    loss, lse = X_mod.xent_fwd(logits, labels, bt=8, bv=128, interpret=True)
    rl, rlse = ref.xent_ref(logits, labels)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(rl),
                               atol=1e-5, rtol=1e-5)
    neg = np.asarray(labels) < 0
    np.testing.assert_allclose(np.asarray(loss)[neg], np.asarray(lse)[neg],
                               rtol=1e-6)


@pytest.mark.parametrize("t,v", [(5, 97), (100, 1000), (13, 513)])
def test_xent_bwd_nonmultiple_shapes_parity(t, v):
    """fwd+bwd parity at non-multiple-of-8 T / non-multiple-of-128 V —
    the padded-region regime where the fwd's label pad fill used to
    differ from the bwd's (0 vs -1, aliasing pad rows onto vocab col 0).
    Sentinel labels ride along: grad rows for -1 labels are pure p*g."""
    logits = jax.random.normal(RNG, (t, v), jnp.float32) * 3
    labels = np.array(jax.random.randint(RNG, (t,), 0, v))
    labels[1::3] = -1
    labels = jnp.asarray(labels)
    g = jax.random.normal(RNG, (t,))
    loss, lse = X_mod.xent_fwd(logits, labels, bt=32, bv=256, interpret=True)
    rl, rlse = ref.xent_ref(logits, labels)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(rl),
                               atol=1e-5, rtol=1e-5)
    grad = X_mod.xent_bwd(logits, labels, lse, g, bt=32, bv=256,
                          interpret=True)
    gref = ref.xent_grad_ref(logits, labels, lse, g)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(gref), atol=2e-6)


# ---------------------------------------------------------------------------
# topk_lse (retained-outcome summary)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "t,v,k",
    [(8, 128, 8), (5, 97, 16), (33, 513, 32), (100, 1000, 64), (3, 300, 64)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_lse_matches_ref(t, v, k, dtype):
    """Streaming top-k merge + online lse vs jax.lax.top_k + logsumexp,
    across multi-block vocab, padded T/V remainders and k > bv slices."""
    logits = (jax.random.normal(RNG, (t, v), jnp.float32) * 3).astype(dtype)
    vals, idx, lse = TK_mod.topk_lse(logits, k, bt=16, bv=256,
                                     interpret=True)
    rv, ri, rl = ref.topk_lse_ref(logits, k)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv),
                               atol=tol, rtol=tol)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rl),
                               atol=tol, rtol=tol)


def test_topk_lse_tie_break_lowest_index():
    """Duplicate values across vocab blocks: ties resolve to the lowest
    index, first-occurrence order — jax.lax.top_k semantics."""
    row = np.array([2.0, 5.0, 5.0, 1.0, 5.0, 0.0, 2.0, 7.0], np.float32)
    logits = jnp.asarray(np.tile(row, (4, 32)))  # [4, 256], 2 vocab blocks
    vals, idx, lse = TK_mod.topk_lse(logits, 9, bv=128, interpret=True)
    rv, ri, rl = ref.topk_lse_ref(logits, 9)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rl), rtol=1e-6)


def test_topk_lse_extreme_logits_stable():
    logits = jnp.asarray([[1e4, -1e4, 0.0, 5e3] * 64] * 8, jnp.float32)
    vals, idx, lse = TK_mod.topk_lse(logits, 4, interpret=True)
    assert np.isfinite(np.asarray(lse)).all()
    assert np.isfinite(np.asarray(vals)).all()
    np.testing.assert_allclose(np.asarray(vals[:, 0]), 1e4)


def test_topk_lse_k_equals_v_recovers_everything():
    """k == V: the summary is lossless (a value-sorted permutation)."""
    logits = jax.random.normal(RNG, (6, 130), jnp.float32)
    vals, idx, lse = TK_mod.topk_lse(logits, 130, bv=128, interpret=True)
    np.testing.assert_allclose(
        np.sort(np.asarray(vals), axis=-1)[:, ::-1], np.asarray(vals),
        err_msg="values must come back descending",
    )
    # every column accounted for exactly once
    np.testing.assert_array_equal(
        np.sort(np.asarray(idx), axis=-1), np.arange(130)[None, :].repeat(6, 0)
    )


def test_topk_lse_ops_dispatch():
    logits = jax.random.normal(RNG, (8, 200), jnp.float32)
    a = ops.topk_lse(logits, 16, "ref")
    b = ops.topk_lse(logits, 16, "interpret")
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError):
        TK_mod.topk_lse(logits, 0, interpret=True)
    with pytest.raises(ValueError):
        TK_mod.topk_lse(logits, 201, interpret=True)


# ---------------------------------------------------------------------------
# decode_attn
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,hq,hkv,d,t",
    [(2, 8, 2, 64, 300), (1, 4, 4, 128, 128), (3, 16, 1, 64, 700), (2, 4, 2, 32, 129)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_matches_ref(b, hq, hkv, d, t, dtype):
    ks = jax.random.split(RNG, 4)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32).astype(dtype)
    lens = jax.random.randint(ks[3], (b,), 1, t + 1)
    valid = jnp.arange(t)[None, :] < lens[:, None]
    out = DA_mod.decode_attn(q, k, v, valid, bt=128, interpret=True)
    r = ref.decode_attn_ref(q, k, v, valid)
    tol = 2e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(r, np.float32), atol=tol
    )


def test_decode_attn_single_valid_position():
    """Degenerate mask: only one position valid -> output = its value."""
    b, hq, hkv, d, t = 1, 2, 1, 16, 64
    q = jax.random.normal(RNG, (b, hq, d))
    k = jax.random.normal(RNG, (b, t, hkv, d))
    v = jax.random.normal(RNG, (b, t, hkv, d))
    valid = (jnp.arange(t) == 17)[None, :]
    out = DA_mod.decode_attn(q, k, v, valid, bt=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out[0, 0]), np.asarray(v[0, 17, 0]), atol=1e-5
    )


# ---------------------------------------------------------------------------
# paged decode_attn
# ---------------------------------------------------------------------------


def _paged_case(b, hq, hkv, d, page, npg, seed=3, dtype=jnp.float32,
                extra=0):
    """Random head-major pool [P, Hkv, page, D] + per-row page tables: each
    row owns a random subset of physical pages (shuffled — logical order
    != physical order), with the blocks past ``pages_for(pos+1) + extra``
    unallocated (-1); ``extra`` > 0 allocates pages past the depth, as a
    bucket-padded prompt does."""
    ks = jax.random.split(jax.random.key(seed), 4)
    pool_pages = b * npg + 3  # spare pages nobody owns
    kp = jax.random.normal(ks[0], (pool_pages, hkv, page, d), jnp.float32)
    vp = jax.random.normal(ks[1], (pool_pages, hkv, page, d), jnp.float32)
    q = jax.random.normal(ks[2], (b, hq, d), jnp.float32)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(pool_pages)
    pos = rng.integers(0, npg * page, size=b).astype(np.int32)
    pt = np.full((b, npg), -1, np.int32)
    used = 0
    for i in range(b):
        n_alloc = min(int(pos[i]) // page + 1 + extra, npg)
        pt[i, :n_alloc] = perm[used : used + n_alloc]
        used += n_alloc
    return (q.astype(dtype), kp.astype(dtype), vp.astype(dtype),
            jnp.asarray(pt), jnp.asarray(pos))


@pytest.mark.parametrize(
    "b,hq,hkv,d,page,npg", [(2, 8, 2, 32, 16, 4), (3, 4, 4, 64, 8, 5)]
)
def test_paged_decode_attn_ref_equals_dense_gather(b, hq, hkv, d, page, npg):
    """The paged ref must be BIT-identical to hand-gathering the pages into
    the dense layout and running decode_attn_ref — the property the serving
    engine's dense/paged bit-parity stands on."""
    q, kp, vp, pt, pos = _paged_case(b, hq, hkv, d, page, npg)
    out = ref.paged_decode_attn_ref(q, kp, vp, pt, pos)
    ptc = np.maximum(np.asarray(pt), 0)
    k = np.asarray(kp)[ptc].swapaxes(2, 3).reshape(b, npg * page, hkv, d)
    v = np.asarray(vp)[ptc].swapaxes(2, 3).reshape(b, npg * page, hkv, d)
    valid = np.arange(npg * page)[None] <= np.asarray(pos)[:, None]
    want = ref.decode_attn_ref(q, jnp.asarray(k), jnp.asarray(v),
                               jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def _bf16_ulp(x):
    """One bf16 ulp at |x| (8 significant bits)."""
    _, e = np.frexp(np.abs(x))
    return np.ldexp(1.0, e - 8)


@pytest.mark.parametrize(
    "b,hq,hkv,d,page,npg,dtype,extra",
    [
        pytest.param(2, 8, 2, 32, 16, 4, jnp.float32, 0, id="2-8-2-32-16-4"),
        pytest.param(3, 4, 4, 64, 8, 5, jnp.float32, 0, id="3-4-4-64-8-5"),
        pytest.param(2, 8, 8, 32, 16, 6, jnp.float32, 0, id="mha"),
        pytest.param(3, 8, 1, 64, 16, 5, jnp.float32, 0, id="mqa"),
        # f32, 4 x 32 x 128: 64 KiB a page, 16 pages a block, 20 pages
        pytest.param(3, 8, 4, 128, 32, 20, jnp.float32, 0,
                     id="np-not-a-multiple-of-the-block"),
        pytest.param(3, 8, 2, 128, 16, 6, jnp.bfloat16, 0, id="bf16"),
        pytest.param(3, 8, 2, 32, 8, 6, jnp.float32, 2,
                     id="pages-allocated-past-pos"),
    ],
)
def test_paged_decode_attn_kernel_matches_ref(b, hq, hkv, d, page, npg,
                                              dtype, extra):
    q, kp, vp, pt, pos = _paged_case(b, hq, hkv, d, page, npg, dtype=dtype,
                                     extra=extra)
    ppb = DA_mod._pages_per_block(hkv, page, d, kp.dtype.itemsize, npg)
    if npg % ppb:  # the partial last block is attended by some row
        assert int(pos.max()) >= (npg // ppb) * ppb * page
    out = DA_mod.paged_decode_attn(q, kp, vp, pt, pos, interpret=True)
    want = ref.paged_decode_attn_ref(q, kp, vp, pt, pos)
    assert out.dtype == want.dtype == dtype
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(out, want, atol=2e-6)
    else:  # f32 inside both: only the final cast to bf16 may differ
        np.testing.assert_array_less(
            np.abs(out - want), _bf16_ulp(np.maximum(np.abs(out),
                                                     np.abs(want))) * 1.001)


def test_paged_decode_attn_slot_without_pages():
    """A free slot (table row all -1) comes out finite, and the other rows
    still equal the ref."""
    q, kp, vp, pt, pos = _paged_case(3, 8, 2, 32, 8, 6)
    pt = pt.at[1].set(-1)
    out = np.asarray(DA_mod.paged_decode_attn(q, kp, vp, pt, pos,
                                              interpret=True))
    want = np.asarray(ref.paged_decode_attn_ref(q, kp, vp, pt, pos))
    assert np.isfinite(out[1]).all()
    np.testing.assert_allclose(out[[0, 2]], want[[0, 2]], atol=2e-6)


def test_paged_decode_attn_ops_dispatch():
    q, kp, vp, pt, pos = _paged_case(1, 4, 2, 32, 8, 3)
    r = ops.paged_decode_attn(q, kp, vp, pt, pos, impl="ref")
    i = ops.paged_decode_attn(q, kp, vp, pt, pos, impl="interpret")
    np.testing.assert_allclose(np.asarray(r), np.asarray(i), atol=2e-6)


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "bsz,s,h,p,g,n,chunk",
    [(2, 64, 4, 16, 1, 32, 16), (1, 96, 2, 32, 2, 16, 32), (2, 50, 4, 16, 1, 16, 16)],
)
def test_ssd_kernel_matches_sequential_ref(bsz, s, h, p, g, n, chunk):
    ks = jax.random.split(RNG, 5)
    x = jax.random.normal(ks[0], (bsz, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b = jax.random.normal(ks[3], (bsz, s, g, n)) * 0.5
    c = jax.random.normal(ks[4], (bsz, s, g, n)) * 0.5
    y, st = SSD_mod.ssd(x, dt, a, b, c, chunk=chunk, interpret=True)
    yr, sr = ref.ssd_ref(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=3e-4, rtol=1e-3)


def test_ssd_xla_path_matches_ref():
    """repro.models.ssm.ssd_chunked (the XLA fallback) vs sequential ref."""
    from repro.models.ssm import ssd_chunked

    ks = jax.random.split(RNG, 5)
    bsz, s, h, p, g, n = 2, 80, 4, 16, 2, 24
    x = jax.random.normal(ks[0], (bsz, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b = jax.random.normal(ks[3], (bsz, s, g, n)) * 0.5
    c = jax.random.normal(ks[4], (bsz, s, g, n)) * 0.5
    y, st = ssd_chunked(x, dt, a, b, c, chunk=16)
    yr, sr = ref.ssd_ref(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=3e-4, rtol=1e-3)


def test_ssd_decode_step_matches_scan_tail():
    """prefill-then-decode == full-sequence on the SSD recurrence."""
    from repro.models.ssm import ssd_chunked
    from repro.kernels.ref import ssd_ref

    ks = jax.random.split(RNG, 5)
    bsz, s, h, p, g, n = 1, 40, 2, 8, 1, 16
    x = jax.random.normal(ks[0], (bsz, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b = jax.random.normal(ks[3], (bsz, s, g, n)) * 0.5
    c = jax.random.normal(ks[4], (bsz, s, g, n)) * 0.5
    _, st_prefix = ssd_chunked(x[:, :30], dt[:, :30], a, b[:, :30], c[:, :30], chunk=10)
    from repro.models.ssm import ssd_decode_step

    st = st_prefix
    outs = []
    for t in range(30, s):
        y, st = ssd_decode_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], st)
        outs.append(y)
    y_full, st_full = ssd_ref(x, dt, a, b, c)
    np.testing.assert_allclose(
        np.stack([np.asarray(o) for o in outs], 1),
        np.asarray(y_full[:, 30:]),
        atol=1e-4, rtol=1e-3,
    )
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_full), atol=1e-4, rtol=1e-3)


def test_ops_dispatch_modes(monkeypatch):
    logits = jax.random.normal(RNG, (8, 64))
    labels = jax.random.randint(RNG, (8,), 0, 64)
    a = ops.xent_loss(logits, labels, "ref")
    b = ops.xent_loss(logits, labels, "interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # the platform rule: the CPU backend defaults to the jnp oracle ...
    assert ops.default_impl() == "ref"
    c = ops.xent_loss(logits, labels)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(a))
    # ... a TPU backend to the Pallas kernels; an explicit impl still wins
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.default_impl() == "pallas"
    assert ops.resolve(None) == "pallas"
    assert ops.resolve("interpret") == "interpret"
    with pytest.raises(ValueError):
        ops.resolve("mosaic")


# ---------------------------------------------------------------------------
# fused recycle-ledger record+priority
# ---------------------------------------------------------------------------


def _ledger_state(cap):
    return (
        jnp.zeros((cap,), jnp.float32),
        jnp.zeros((cap,), jnp.int32),
        jnp.full((cap,), -1, jnp.int32),
        jnp.full((cap,), -1, jnp.int32),
    )


def _ledger_args(cap, batch, seed, id_range=None):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, id_range or 8 * cap, size=batch).astype(np.int32)
    losses = rng.normal(2, 1, size=batch).astype(np.float32)
    return jnp.asarray(ids), jnp.asarray(losses)


@pytest.mark.parametrize("cap,batch", [(128, 8), (1024, 16), (4096, 100)])
def test_ledger_kernel_matches_ref(cap, batch):
    """One transaction, arbitrary collision pattern: interpret == oracle."""
    state = _ledger_state(cap)
    ids, losses = _ledger_args(cap, batch, seed=cap + batch, id_range=cap)
    kw = dict(decay=0.9, unseen_priority=1e6)
    want = ops.ledger_record_priority(*state, ids, losses, jnp.int32(3),
                                      impl="ref", **kw)
    got = ops.ledger_record_priority(*state, ids, losses, jnp.int32(3),
                                     impl="interpret", **kw)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def test_ledger_kernel_chained_transactions():
    """Multi-step: kernel output feeds the next call; EMA blending, count
    increments and evictions all match the oracle over time."""
    cap = 512
    st_k = st_r = _ledger_state(cap)
    kw = dict(decay=0.7, unseen_priority=1e6)
    for step in range(6):
        ids, losses = _ledger_args(cap, 24, seed=step, id_range=200)
        out_r = ops.ledger_record_priority(*st_r, ids, losses,
                                           jnp.int32(step), impl="ref", **kw)
        out_k = ops.ledger_record_priority(*st_k, ids, losses,
                                           jnp.int32(step),
                                           impl="interpret", **kw)
        st_r, st_k = out_r[:4], out_k[:4]
        np.testing.assert_allclose(np.asarray(out_k[4]), np.asarray(out_r[4]),
                                   rtol=1e-5)
    for g, w in zip(st_k, st_r):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5)


def test_ledger_kernel_intra_batch_duplicates():
    """Same id three times in one batch: numpy last-write-wins semantics,
    and the dup items all read the winner's post-update priority."""
    state = _ledger_state(128)
    ids = jnp.asarray([5, 9, 5, 5], jnp.int32)
    losses = jnp.asarray([1.0, 2.0, 3.0, 8.0], jnp.float32)
    kw = dict(decay=0.5, unseen_priority=1e6)
    for impl in ("ref", "interpret"):
        ema, cnt, ls, own, pri = ops.ledger_record_priority(
            *state, ids, losses, jnp.int32(0), impl=impl, **kw)
        np.testing.assert_allclose(np.asarray(pri), [8.0, 2.0, 8.0, 8.0],
                                   rtol=1e-6)


@pytest.mark.parametrize("cap,batch", [(1024, 300), (2048, 513), (256, 64)])
def test_ledger_block_kernel_matches_ref(cap, batch):
    """The two-pass block-parallel scatter (grid over table tiles) must be
    exact vs the oracle — including write masks, collisions, staleness —
    at batch sizes both above and below the auto-dispatch threshold."""
    rng = np.random.default_rng(cap + batch)
    state = _ledger_state(cap)
    ids = jnp.asarray(rng.integers(0, 3 * cap, size=batch).astype(np.int32))
    losses = jnp.asarray(rng.normal(2, 1, size=batch).astype(np.float32))
    valid = jnp.asarray(rng.random(batch) > 0.25)
    kw = dict(decay=0.8, unseen_priority=1e6, staleness_half_life=40.0,
              valid=valid)
    want = ops.ledger_record_priority(*state, ids, losses, jnp.int32(5),
                                      impl="ref", **kw)
    got = ops.ledger_record_priority(*state, ids, losses, jnp.int32(5),
                                     impl="interpret", variant="block", **kw)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def test_ledger_variant_dispatch_by_batch():
    """None = auto: small batches take the fori kernel, large the block
    tiling; both agree with ref through a chained sequence."""
    from repro.kernels.ledger import resolve_variant
    from repro.kernels.ops import LEDGER_BLOCK_MIN_BATCH

    rows = 1024 // 128
    assert resolve_variant(None, 8, LEDGER_BLOCK_MIN_BATCH, rows) == "fori"
    assert resolve_variant(
        None, LEDGER_BLOCK_MIN_BATCH, LEDGER_BLOCK_MIN_BATCH, rows
    ) == "block"
    st_r = st_k = _ledger_state(1024)
    kw = dict(decay=0.7, unseen_priority=1e6)
    for step, b in enumerate((24, 300, 24)):  # crosses the threshold
        ids, losses = _ledger_args(1024, b, seed=step, id_range=500)
        out_r = ops.ledger_record_priority(*st_r, ids, losses,
                                           jnp.int32(step), impl="ref", **kw)
        out_k = ops.ledger_record_priority(*st_k, ids, losses,
                                           jnp.int32(step),
                                           impl="interpret", **kw)
        st_r, st_k = out_r[:4], out_k[:4]
        np.testing.assert_allclose(np.asarray(out_k[4]), np.asarray(out_r[4]),
                                   rtol=1e-5)
    for g, w in zip(st_k, st_r):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5)


def test_ledger_kernel_matches_host_ledger():
    """Full-stack agreement: Pallas interpret kernel == numpy LossHistory."""
    from repro.core.history import HistoryConfig, LossHistory

    cfg = HistoryConfig(capacity=1024, decay=0.8)
    h = LossHistory(cfg)
    state = _ledger_state(cfg.capacity)
    kw = dict(decay=cfg.decay, unseen_priority=cfg.unseen_priority)
    for step in range(4):
        ids, losses = _ledger_args(cfg.capacity, 13, seed=step, id_range=5000)
        h.record(np.asarray(ids, np.int64), np.asarray(losses), step)
        out = ops.ledger_record_priority(*state, ids, losses, jnp.int32(step),
                                         impl="interpret", **kw)
        state = out[:4]
        np.testing.assert_allclose(
            np.asarray(out[4]), h.priority(np.asarray(ids, np.int64), step),
            rtol=1e-5,
        )
    sd = h.state_dict()
    np.testing.assert_allclose(np.asarray(state[0]), sd["ema"], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(state[3]),
                                  sd["owner"].astype(np.int32))
