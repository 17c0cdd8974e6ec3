"""Train driver: the OBFTF step with the device ledger fused in.

The system under test is the program's recycled OBFTF step, composed from
its own functions as ``repro.launch.train`` composes it:
``core.obftf.make_train_step`` over ``models.model.loss_fn``, the program's
AdamW, and ``core.device_ledger`` lookup before and record after, in one
jitted call. The ledger stands in for the selection forward
(``recycle_forward``): one backward on ``ratio`` of each batch.

Set-up makes the weights from the seed, seeds the ledger with a loss for
every instance id the run can draw (hit rate 1.0), and drives the one
compiled step through its first ``check_steps`` steps, on rows that all
differ, keeping what the check compares. The window then goes on with the
same object. ``train_tok_s`` counts the stream tokens consumed (global
batch x sequence length a step) from the window's opening to the end of
its last step. After the window the program's state is freed and the
plain reference follows the first steps.
"""

from __future__ import annotations

import contextlib
import gc
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, trace

OUT = os.path.join(harness.ROOT, ".chipbench")
COLD_LOSS = 1e3  # the program's own recorded-loss fallback for a miss



def build(job: dict, mcfg, donate: bool = True):
    """The jitted ``(state, ledger, batch, rng) -> (state, ledger,
    metrics)`` step and its optimizer and ledger config."""
    from repro import optim
    from repro.core import device_ledger as dledger
    from repro.core.history import HistoryConfig
    from repro.core.obftf import OBFTFConfig, make_train_step
    from repro.core.selection import SelectionConfig
    from repro.models import model as Mdl

    o = job["optimizer"]
    optimizer = optim.adamw(optim.constant(o["lr"]), optim.AdamWConfig(
        b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"], clip_norm=o["clip_norm"]))
    sel = SelectionConfig(method=job["method"], ratio=job["ratio"],
                          swaps=job["swaps"],
                          noisy_target=job["noisy_target"])
    step_fn = make_train_step(
        Mdl.loss_fn(mcfg), optimizer,
        OBFTFConfig(selection=sel, recycle_forward=True, mode="obftf"))
    lcfg = HistoryConfig()

    def step(state, lstate, batch, rng):
        ids = batch["instance_id"]
        ema, seen = dledger.lookup(lstate, ids)
        rec = jnp.where(seen, ema, COLD_LOSS).astype(jnp.float32)
        state, m = step_fn(state, dict(batch, recorded_loss=rec), rng)
        lstate = dledger.record(lcfg, lstate, ids, m["per_example_loss"],
                                state["step"], valid=m["per_example_fresh"])
        return state, lstate, {
            "loss": m["loss"], "kept": m["per_example_fresh"],
            "hits": jnp.mean(seen.astype(jnp.float32)),
        }

    jstep = jax.jit(step, donate_argnums=(0, 1) if donate else (1,))
    return jstep, optimizer, lcfg


class Feed:
    """Batches from the seed: step ``k`` draws rows ``k * B ..`` of the
    instance pool and fresh tokens, so no two of the first steps share a
    row."""

    def __init__(self, job: dict, words: list[int], pool: np.ndarray,
                 vocab: int):
        self.job, self.words, self.pool, self.vocab = job, words, pool, vocab

    def batch(self, k: int) -> dict:
        b, s = self.job["global_batch"], self.job["seq_len"]
        rng = np.random.default_rng([*self.words, 1000 + k])
        seq = rng.integers(0, self.vocab, (b, s + 1), dtype=np.int32)
        ids = self.pool[(k * b + np.arange(b)) % self.pool.size]
        return {"tokens": seq[:, :-1], "labels": seq[:, 1:],
                "instance_id": ids.astype(np.int32)}

    def rng(self, k: int):
        return jax.random.fold_in(jax.random.wrap_key_data(
            jnp.asarray(np.asarray(self.words[2:4], np.uint32))), k)


def seeded_losses(job: dict, words: list[int], ids: np.ndarray) -> np.ndarray:
    """The loss the ledger holds for each id before the first step."""
    rng = np.random.default_rng([*words, 7])
    x = rng.normal(job["seeded_loss"]["mean"], job["seeded_loss"]["std"],
                   ids.size)
    return x.astype(np.float32)


def seed_ledger(job, words, lcfg):
    """A ledger holding one seeded loss for each pool id; ids that another
    id's hash evicted are dropped from the pool."""
    from repro.core import device_ledger as dledger

    ids = np.arange(1, job["instance_pool"] + 1, dtype=np.int32)
    losses = seeded_losses(job, words, ids)
    rec = jax.jit(lambda st, i, x: dledger.record(lcfg, st, i, x, 0),
                  donate_argnums=(0,))
    st = dledger.init_state(lcfg)
    chunk = 1024
    for a in range(0, ids.size, chunk):
        st = rec(st, jnp.asarray(ids[a:a + chunk]),
                 jnp.asarray(losses[a:a + chunk]))
    _, seen = jax.device_get(dledger.lookup(st, jnp.asarray(ids)))
    return st, ids[np.asarray(seen)], dict(zip(ids.tolist(), losses))


def leaf_norms(tree) -> list[float]:
    return [float(x) for x in jax.device_get(jax.tree.leaves(jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        tree)))]


def run(cell: harness.Cell, seed: int, seconds: float, traced: bool,
        t_process: float, control: bool = False):
    """One run of a train cell; returns what ``serve.run`` returns."""
    from repro.core import device_ledger as dledger

    devices = harness.require_devices(cell.chips)
    cache = harness.use_compile_cache()
    clock = harness.CompileClock()
    ref = cell.reference()
    conf, job = cell.config, cell.traffic
    words = harness.seed_words(seed)
    sz = ref.sizes(conf)
    mcfg = harness.program_config(conf)
    peak = harness.peaks(devices[0].device_kind)
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}; compile cache {cache}", flush=True)

    split = {}
    sw = harness.Stopwatch()
    jstep, optimizer, lcfg = build(job, mcfg)
    params = ref.make_weights(conf, words)
    state = {"params": params, "opt": optimizer.init(params),
             "step": jnp.zeros((), jnp.int32)}
    lstate, pool, seeded = seed_ledger(job, words, lcfg)
    feed = Feed(job, words, pool, sz["tokens"])
    jax.block_until_ready((state, lstate))
    split["init_s"] = sw.lap()

    # the checked steps: the window's own compiled step and feed
    p0 = jax.tree.map(jnp.copy, state["params"])
    got = {"loss": [], "kept": [], "ids": []}
    n_check = int(job["check_steps"])
    for k in range(n_check):
        b = feed.batch(k)
        state, lstate, m = jstep(state, lstate,
                                 jax.tree.map(jnp.asarray, b), feed.rng(k))
        m = jax.device_get(m)
        got["loss"].append(float(m["loss"]))
        got["kept"].append(np.flatnonzero(m["kept"]))
        got["ids"].append(b["instance_id"])
        if k == 0:
            b1 = job["optimizer"]["b1"]
            got["grad"] = [x / (1 - b1) for x in
                           leaf_norms(state["opt"]["m"])]
        got.setdefault("hits", []).append(float(m["hits"]))
    got["change"] = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        state["params"], p0))
    kept_ids = np.concatenate([i[k] for i, k in zip(got["ids"],
                                                    got["kept"])])
    ema, seen = jax.device_get(dledger.lookup(lstate, jnp.asarray(kept_ids)))
    got["ledger"] = dict(zip(kept_ids.tolist(), np.asarray(ema).tolist()))
    del p0
    split["check_steps_s"] = sw.lap()
    split.update(clock.lap())

    trace_dir = os.path.join(OUT, "trace", cell.name)
    annotate = (jax.profiler.TraceAnnotation if traced
                else contextlib.nullcontext)
    steps: list[tuple[float, float]] = []
    tracing = None
    k = n_check
    t_open = time.perf_counter()
    setup_s = t_open - t_process
    t_close = t_open + seconds
    if traced:
        trace.start(trace_dir)
        span_win = jax.profiler.TraceAnnotation("traced.window")
        span_win.__enter__()
        tracing = [time.perf_counter(), None]
    while True:
        ts = time.perf_counter()
        if tracing and tracing[1] is None and \
                ts >= tracing[0] + float(job["trace_s"]):
            span_win.__exit__(None, None, None)
            trace.stop()
            tracing[1] = time.perf_counter()
        if ts >= t_close:
            break
        with annotate("train.step"):
            b = jax.tree.map(jnp.asarray, feed.batch(k))
            state, lstate, m = jstep(state, lstate, b, feed.rng(k))
        with annotate("fetch"):
            m = jax.device_get(m)
        steps.append((ts, time.perf_counter()))
        k += 1
    if tracing and tracing[1] is None:
        span_win.__exit__(None, None, None)
        trace.stop()
        tracing[1] = time.perf_counter()
    compiles_in_window = clock.now["compiles"]
    peak_bytes = harness.memory_peak(devices)
    print("setup: " + " ".join(f"{a}={v}" for a, v in split.items())
          + f" setup_s={setup_s}", flush=True)
    print(f"window: {len(steps)} steps, compiles in window "
          f"{compiles_in_window}, ledger hit rate in the checked steps "
          f"{got['hits']}", flush=True)
    tr = trace.load(trace_dir, harness.HOST_SPANS) if traced else None
    rec = types.SimpleNamespace(cell=cell, sizes=sz, costs=cell.costs(), peak=peak,
                 devices=devices, steps=steps, t_open=t_open,
                 seconds=seconds, setup_s=setup_s, trace=tr,
                 trace_span=tracing, job=job)
    del state, lstate, m
    gc.collect()
    checks = check(ref, conf, job, words, feed, seeded, got, control)
    return rec, peak_bytes, len(steps), 0, checks



# ---------------------------------------------------------------------------
# correctness: the first steps against the reference
# ---------------------------------------------------------------------------


def follow(ref, conf, job, words, feed, seeded, n_steps, low=False,
           half=False):
    """The reference through the program's first steps: per step the rows
    it selects, the mean loss, the per-example losses of the kept rows;
    the first clipped gradient's leaf norms; the leaf norms of the change
    after ``n_steps``; and the ledger EMA of every kept id. ``half`` plants
    a fault in the reference: the loss is the mean over the first half of
    the kept rows, and the rest record that mean."""
    sz = tuple(sorted(ref.sizes(conf).items()))
    sel_b = max(1, round(job["ratio"] * job["global_batch"]))
    w = ref.make_weights(conf, words)
    w0 = jax.tree.map(jnp.copy, w)
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)
    out = {"loss": [], "kept": [], "ledger": {}, "rowloss": []}
    decay = float(job["ledger_decay"])
    for k in range(n_steps):
        b = feed.batch(k)
        rec = np.asarray([seeded[int(i)] for i in b["instance_id"]],
                         np.float32)
        z = float(jax.random.normal(jax.random.split(feed.rng(k), 3)[1], (),
                                    jnp.float32))
        rows = ref.obftf_select(rec, sel_b, z, job["swaps"])
        use = rows[: max(1, rows.size // 2)] if half else rows
        loss, pel, g = ref.loss_and_grad(
            w, jnp.asarray(b["tokens"][use]), jnp.asarray(b["labels"][use]),
            sz=sz, low=low)
        if half:
            pel = np.concatenate([np.asarray(pel), np.full(
                rows.size - use.size, float(loss), np.float32)])
        w, gc_, m, v = ref.adamw_step(w, g, m, v, k + 1, job["optimizer"])
        if k == 0:
            out["grad"] = leaf_norms(gc_)
            out["grad_raw"] = out["grad"]
        pel = np.asarray(pel)
        out["loss"].append(float(loss))
        out["kept"].append(rows)
        out["rowloss"].append(pel)
        for i, x in zip(b["instance_id"][rows], pel):
            out["ledger"][int(i)] = decay * seeded[int(i)] + (1 - decay) * x
        del g, gc_
    out["change"] = leaf_norms(jax.tree.map(
        lambda a, c: a.astype(jnp.float32) - c.astype(jnp.float32), w, w0))
    return out


def leaf_gaps(got: list[float], want: list[float], grad: list[float]):
    """|got - want| / max(want, median of want) of each leaf whose
    reference gradient is at least a thousandth of the median leaf's (the
    rest move by round-off alone). The median is over the leaves the
    reference moves: bfloat16 storage leaves a leaf whose every update is
    under half its unit in the last place unmoved, in the program and in
    the reference alike."""
    want_a, got_a = np.asarray(want), np.asarray(got)
    g = np.asarray(grad)
    live = g >= 1e-3 * np.median(g)
    moved = want_a[live & (want_a > 0)]
    scale = np.median(moved) if moved.size else 1.0
    den = np.maximum(want_a, scale)
    return np.abs(got_a - want_a)[live] / den[live]


def compare(got: dict, want: dict) -> dict:
    rows = sum(int(not np.array_equal(np.sort(a), np.sort(b)))
               for a, b in zip(got["kept"], want["kept"]))
    loss = max(abs(a - b) for a, b in zip(got["loss"], want["loss"]))
    ledger = max(abs(float(got["ledger"][i]) - float(want["ledger"][i]))
                 for i in want["ledger"])
    grad = leaf_gaps(got["grad"], want["grad"], want["grad"])
    change = leaf_gaps(got["change"], want["change"], want["grad"])
    return {
        "rows_differing": rows,
        "loss_gap": float(loss),
        "first_loss_gap": abs(got["loss"][0] - want["loss"][0]),
        "grad_gap": float(grad.max()),
        "grad_gap_median": float(np.median(grad)),
        "change_gap": float(change.max()),
        "change_gap_median": float(np.median(change)),
        "ledger_gap": ledger,
    }


def check(ref, conf, job, words, feed, seeded, got, control) -> dict:
    n = int(job["check_steps"])
    want = follow(ref, conf, job, words, feed, seeded, n)
    vals = compare(got, want)
    print(f"check: {n} steps, losses program {got['loss']} reference "
          f"{want['loss']}; readings without a limit (not compared): "
          + ", ".join(f"{k}={v!r}" for k, v in vals.items()
                      if k not in job["limits"]),
          file=sys.stderr, flush=True)
    out = judge(vals, job["limits"])
    if control:
        out["readings"] = vals
        for name, kw in (("control", {"low": True}),
                         ("half_batch", {"half": True})):
            other = compare(follow(ref, conf, job, words, feed, seeded, n,
                                   **kw), want)
            other["correct"] = all(c["ok"] for c in
                                   judge(other, job["limits"]).values())
            out[name] = other
    return out


def judge(vals: dict, limits: dict) -> dict:
    """Each number compared beside its limit; the program's readings, the
    control's and a planted fault's go through this one rule."""
    return {k: {"value": vals[k], "limit": lim,
                "ok": bool(harness.finite(vals[k]) and vals[k] <= lim)}
            for k, lim in limits.items()}
