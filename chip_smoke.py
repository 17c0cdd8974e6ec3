"""Smoke run of the paper's loop on TPU: serve -> device ledger -> train.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the routed ledger across four chips

Everything runs in this one process, which holds the chip(s); it starts no
child process. Without a TPU it exits non-zero before any phase.

One chip, three phases:

1. kernels: each Pallas kernel of the main path against its jnp oracle at
   qwen3-14b widths (paged decode attention, top-k + lse over the 151936
   vocab, the ledger record+priority in both variants at 2^16 slots).
2. serve: qwen3-14b at every published width, ``num_layers`` cut from 40
   to 8 so the weights fit one 16 GB chip, through ``launch.serve``'s
   ``build_engine`` / ``submit_stream`` and ``Engine.run``: paged KV cache,
   top-k retention, device ledger, labels delivered late. The compiled
   decode step must hold the Pallas kernels.
3. train: ``launch.train.main`` on the whole mamba2-370m, selecting its
   backward from the serve phase's ledger (``--recycle --ledger device
   --ledger-in``).

``--chips 4`` runs only the routed ledger: the engine with ``--ledger-route``
on the 4-way "data" mesh, once per exchange (gather, a2a), against the
single-table engine on one chip (greedy tokens equal, ledger state dicts
equal to ``tests/_ledger_parity.py``'s tolerances), then trainer steps on
the 4-way data axis with the routed device ledger.

Any failed check exits non-zero. Earlier stdout lines report each phase;
the last line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "tests"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

OUT = os.path.join(ROOT, ".chip_smoke")  # the served ledger, train summary

SERVE_ARCH, SERVE_LAYERS = "qwen3-14b", 8  # 40 published; ~4.2 B params
TRAIN_ARCH = "mamba2-370m"  # whole: ~0.43 B params
ROUTED_LAYERS = 2  # the 4-chip phase tests the ledger exchange, not depth


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(ok, msg: str) -> None:
    if not ok:
        fail(msg)


class CompileClock:
    """Sums JAX's own compile events (tracing, lowering, backend compile —
    a persistent-cache hit takes the backend compile's place) and counts
    persistent-cache hits and misses, per phase."""

    _SECS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self._reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _reset(self):
        self.secs, self.hits, self.misses = 0.0, 0, 0

    def _duration(self, event, duration, **_):
        if event in self._SECS:
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def lap(self) -> str:
        s = (f"compile_s={self.secs:.1f} cache_hits={self.hits} "
             f"cache_misses={self.misses}")
        self._reset()
        return s


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def served_config(layers: int):
    from repro import configs

    full = configs.get(SERVE_ARCH)
    cfg = dataclasses.replace(full, num_layers=layers).validate()
    print(f"config {SERVE_ARCH}: num_layers {full.num_layers} -> {layers} "
          f"(every width as published: d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size})")
    return cfg


def random_params(cfg):
    from repro.models import model as Mdl
    from repro.models.params import materialize, tree_bytes

    params = materialize(Mdl.param_specs(cfg), jax.random.key(0),
                         jnp.dtype(cfg.param_dtype))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n / 1e9:.3f} B ({tree_bytes(params) / 1e9:.2f} GB "
          f"{cfg.param_dtype}), random from seed 0")
    return params


# ---------------------------------------------------------------------------
# phase 1: kernel parity
# ---------------------------------------------------------------------------


def phase_kernels(cfg, *, batch=8, ctx=4096, pages=(16, 128), rows=(32, 256),
                  capacity=1 << 16):
    from repro.kernels import ops

    impl = ops.default_impl()
    print(f"kernels: default impl on {jax.default_backend()} = {impl}")
    check(impl == "pallas", f"the TPU dispatches {impl!r}, not the kernels")
    rng = np.random.default_rng(0)
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    for page in pages:
        npg = ctx // page
        n_pool = batch * npg + 3
        k0, k1, k2 = jax.random.split(jax.random.key(page), 3)
        shape = (1, n_pool, hkv, page, d)  # one layer of the stacked pool
        kp = jax.random.normal(k0, shape, jnp.float32).astype(jnp.bfloat16)
        vp = jax.random.normal(k1, shape, jnp.float32).astype(jnp.bfloat16)
        q = (4 * jax.random.normal(k2, (batch, hq, d))).astype(jnp.bfloat16)
        pos = rng.integers(0, ctx, size=batch).astype(np.int32)
        pt = np.full((batch, npg), -1, np.int32)
        perm = rng.permutation(n_pool)
        used = 0
        for i in range(batch):
            n = int(pos[i]) // page + 1
            pt[i, :n] = perm[used:used + n]
            used += n
        args = (q, kp, vp, jnp.asarray(pt), jnp.asarray(pos), 0)
        got = np.asarray(ops.paged_decode_attn(*args), np.float32)
        want = np.asarray(ops.paged_decode_attn(*args, impl="ref"),
                          np.float32)
        rel = float(np.abs(got - want).max() / np.abs(want).max())
        print(f"  paged_decode_attn B={batch} Hq={hq} Hkv={hkv} D={d} "
              f"page={page} T={ctx}: max|pallas-ref|/max|ref| = {rel:.2e}")
        check(got.shape == (batch, hq, d) and np.isfinite(got).all(),
              "paged_decode_attn output shape/finiteness")
        check(rel <= 1e-2, f"paged_decode_attn page={page} rel err {rel}")
    v, k = cfg.vocab_size, 64
    for t in rows:
        logits = 3 * jax.random.normal(jax.random.key(t), (t, v))
        vals, idx, lse = (np.asarray(a) for a in ops.topk_lse(logits, k))
        rv, ri, rl = (np.asarray(a) for a in ops.topk_lse(logits, k, "ref"))
        lse_err = float(np.abs(lse - rl).max())
        print(f"  topk_lse T={t} V={v} k={k}: indices equal="
              f"{bool((idx == ri).all())}, max|lse diff| = {lse_err:.2e}")
        check((idx == ri).all(), f"topk_lse indices differ at T={t}")
        np.testing.assert_allclose(vals, rv, rtol=1e-6)
        np.testing.assert_allclose(lse, rl, rtol=1e-5)
    state = (jnp.zeros((capacity,), jnp.float32),
             jnp.zeros((capacity,), jnp.int32),
             jnp.full((capacity,), -1, jnp.int32),
             jnp.full((capacity,), -1, jnp.int32))
    kw = dict(decay=0.9, unseen_priority=1e6)
    for variant, b in (("fori", 64), ("block", 512)):
        ids = jnp.asarray(rng.integers(0, 4 * capacity, b).astype(np.int32))
        losses = jnp.asarray(rng.normal(2, 1, b).astype(np.float32))
        got = ops.ledger_record_priority(*state, ids, losses, jnp.int32(3),
                                         variant=variant, **kw)
        want = ops.ledger_record_priority(*state, ids, losses, jnp.int32(3),
                                          impl="ref", **kw)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-6)
        print(f"  ledger_record_priority[{variant}] capacity={capacity} "
              f"batch={b}: equal to ref")


# ---------------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------------


def serve_args(*, slots, prompt_len, gen, page_size, delay, extra=()):
    from repro.launch import serve

    return serve.parser().parse_args([
        "--arch", SERVE_ARCH, "--batch", str(slots),
        "--prompt-len", str(prompt_len), "--gen", str(gen),
        "--page-size", str(page_size), "--outcome-delay", str(delay),
        "--retain", "topk", "--ledger", "device", *extra,
    ])


def serve_once(cfg, params, args):
    """One engine through the serve CLI's own builders; returns the engine
    after checking that every request was served and every labeled
    position recorded."""
    from repro.launch import serve
    from repro.serving import delayed_outcomes

    args.requests = args.requests or 3 * args.batch
    engine = serve.build_engine(args, cfg, params)
    waves, submitted = serve.submit_stream(engine, args, cfg)
    t0 = time.perf_counter()
    stats = engine.run(max_steps=100_000,
                       on_step=delayed_outcomes(submitted, args.outcome_delay))
    wall = time.perf_counter() - t0
    want = sum(len(lab) for _, lab in submitted)
    print(f"  served {stats['evicted']} requests in {waves} waves, "
          f"{want} output tokens ({stats['generated_tokens']} from "
          f"{stats['steps']} decode steps), "
          f"{stats['recorded']} ledger records "
          f"({stats['topk_misses']} at the top-k tail floor), "
          f"a2a_overflow={stats['a2a_overflow']}, {wall:.1f}s wall "
          f"incl. compile")
    check(stats["evicted"] == args.requests and stats["in_flight"] == 0
          and stats["queued"] == 0, f"requests left behind: {stats}")
    # each request's first token comes from its prefill, the rest from
    # decode steps (what generated_tokens counts)
    check(stats["generated_tokens"] + stats["admitted"] == want,
          f"tokens {stats} != {want}")
    check(stats["recorded"] == want and stats["missed_outcomes"] == 0,
          f"records {stats} != {want}")
    toks = np.concatenate([t for t in engine.finished.values()])
    check(((toks >= 0) & (toks < cfg.vocab_size)).all(), "token out of vocab")
    return engine, submitted


def phase_serve(cfg, params, ledger_path, *, slots=16, prompt_len=128,
                gen=64, page_size=16, delay=4):
    args = serve_args(slots=slots, prompt_len=prompt_len, gen=gen,
                      page_size=page_size, delay=delay)
    print(f"serve: slots={slots} prompt<={prompt_len} gen={gen} "
          f"page={page_size} retain=topk[k={args.topk}] ledger=device "
          f"outcome_delay={delay}")
    engine, submitted = serve_once(cfg, params, args)
    ids = np.asarray([iid for iid, _ in submitted], np.int64)
    ema, seen = (np.asarray(a) for a in engine.ledger.lookup(ids))
    print(f"  ledger: hit rate {seen.mean():.3f} over {len(ids)} served ids, "
          f"mean loss EMA {ema[seen].mean():.4f}")
    check(seen.all(), "a served id is missing from the ledger")
    check(np.isfinite(ema).all() and (ema[seen] > 0).all(),
          "ledger losses not finite and positive")
    text = engine._decode.lower(
        engine.params, engine._estate, engine._rstate
    ).as_text()
    n_calls = text.count("tpu_custom_call")
    print(f"  compiled decode step: {n_calls} Pallas custom calls "
          "(paged decode attention + top-k/lse)")
    check(n_calls >= 2, "the decode step holds no Pallas kernels")
    np.savez(ledger_path, **engine.ledger_state_dict())
    return len(ids)


# ---------------------------------------------------------------------------
# phase 3: train
# ---------------------------------------------------------------------------


def phase_train(arch, ledger_path, *, steps=8, batch=8, seq_len=512,
                pool=48, extra=()):
    from repro.launch import train

    out = os.path.join(OUT, "train.json")
    argv = ["--arch", arch, "--steps", str(steps), "--global-batch",
            str(batch), "--seq-len", str(seq_len), "--recycle", "--ledger",
            "device", "--ledger-in", ledger_path, "--instance-pool",
            str(pool), "--log-every", "1", "--json-out", out, *extra]
    print("train: launch.train " + " ".join(argv))
    check(train.main(argv) == 0, "train.main returned non-zero")
    with open(out) as f:
        s = json.load(f)
    print(f"  steps={s['steps']} loss {s['loss_first']:.4f} -> "
          f"{s['loss_last']:.4f}, mean_step_cost={s['mean_step_cost']:.3f}, "
          f"ledger hit rate mean={s['ledger_hits_mean']:.3f}, "
          f"ledger shards={s['ledger_shards']}")
    check(s["steps"] == steps, f"trained {s['steps']} of {steps} steps")
    check(math.isfinite(s["loss_first"]) and math.isfinite(s["loss_last"])
          and math.isfinite(s["mean_step_cost"]), "non-finite loss or cost")
    check(s["ledger_hits_mean"] > 0, "the trainer never hit the ledger")
    return s


# ---------------------------------------------------------------------------
# four chips: the routed ledger
# ---------------------------------------------------------------------------


def phase_routed(cfg, params, ledger_path, *, slots=16, prompt_len=64,
                 gen=32, page_size=16, delay=4):
    from _ledger_parity import (
        DERIVED_RTOL,
        assert_ema_close,
        assert_ledger_states_close,
    )

    kw = dict(slots=slots, prompt_len=prompt_len, gen=gen,
              page_size=page_size, delay=delay)
    print(f"routed ledger: slots={slots} prompt<={prompt_len} gen={gen} "
          f"page={page_size} outcome_delay={delay}")
    print(" single table, one chip:")
    engine, _ = serve_once(cfg, params, serve_args(**kw))
    tokens, sd_single = dict(engine.finished), engine.ledger_state_dict()
    del engine
    gc.collect()
    for exchange in ("gather", "a2a"):
        print(f" routed, exchange={exchange}:")
        engine, _ = serve_once(cfg, params, serve_args(
            **kw, extra=("--ledger-route", "--ledger-exchange", exchange)))
        shards = engine.recorder.ops.shards
        same = engine.finished.keys() == tokens.keys() and all(
            np.array_equal(engine.finished[i], tokens[i]) for i in tokens)
        sd = engine.ledger_state_dict()
        ema, ema1 = sd["ema"], sd_single["ema"]
        rel = float(np.max(np.abs(ema - ema1) / np.maximum(np.abs(ema1),
                                                           1e-30)))
        print(f"  {shards} ledger shards; greedy tokens equal to the single "
              f"table: {same}; max EMA rel diff {rel:.2e}")
        check(shards == len(jax.devices()), f"{shards} ledger shards")
        check(same, f"routed ({exchange}) tokens differ from the single table")
        # the serve-time channels (entropy, margin) are derived from the
        # logits: the convention's derived tolerance
        assert_ema_close(sd.pop("sig"), sd_single["sig"], rtol=DERIVED_RTOL)
        assert_ledger_states_close(
            sd, {k: v for k, v in sd_single.items() if k != "sig"})
        print("  ledger state dict equal to the single table (ints exact, "
              "EMA rtol 1e-6, signal channels rtol 1e-5)")
        if exchange == "gather":
            np.savez(ledger_path, **sd)
        del engine
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels, serve, train on one chip; 4: only the "
                         "routed ledger across four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform} devices")
    if len(devices) != args.chips:
        fail(f"--chips {args.chips} but JAX found {len(devices)} devices")

    from repro.launch import use_compile_cache

    cache = use_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache} ({n_cached} entries before this run)")
    os.makedirs(OUT, exist_ok=True)
    ledger_path = os.path.join(OUT, "ledger.npz")
    clock = CompileClock()

    if args.chips == 1:
        cfg = served_config(SERVE_LAYERS)
        t = time.perf_counter()
        phase_kernels(cfg)
        print(f"phase kernels: {time.perf_counter() - t:.1f}s "
              f"{clock.lap()} peak_bytes_in_use={peak_bytes()}")
        t = time.perf_counter()
        params = random_params(cfg)
        phase_serve(cfg, params, ledger_path)
        del params
        gc.collect()
        print(f"phase serve: {time.perf_counter() - t:.1f}s {clock.lap()} "
              f"peak_bytes_in_use={peak_bytes()}")
        t = time.perf_counter()
        phase_train(TRAIN_ARCH, ledger_path)
        print(f"phase train: {time.perf_counter() - t:.1f}s {clock.lap()} "
              f"peak_bytes_in_use={peak_bytes()}")
    else:
        cfg = served_config(ROUTED_LAYERS)
        t = time.perf_counter()
        params = random_params(cfg)
        phase_routed(cfg, params, ledger_path)
        del params
        gc.collect()
        print(f"phase routed serve: {time.perf_counter() - t:.1f}s "
              f"{clock.lap()} peak_bytes_in_use={peak_bytes()}")
        t = time.perf_counter()
        # 4 rows per chip, so each shard's selection keeps 1 of 4 (the
        # instance pool of 48 served ids is a multiple of the batch)
        s = phase_train(TRAIN_ARCH, ledger_path, batch=4 * len(devices),
                        extra=("--ledger-route",))
        check(s["ledger_shards"] == len(devices),
              f"trainer ledger on {s['ledger_shards']} shards")
        print(f"phase routed train: {time.perf_counter() - t:.1f}s "
              f"{clock.lap()} peak_bytes_in_use={peak_bytes()}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
