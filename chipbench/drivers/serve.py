"""Serve driver: an open-loop schedule through the program's engine.

The system under test is ``repro.serving.Engine`` as the serve CLI builds
it (``repro.launch.serve.build_engine``): paged KV cache, top-k + lse
retention, the device ledger, labels delivered late. The driver calls
``Engine.submit`` when each request falls due and ``Engine.deliver_outcome``
a fixed number of engine steps after its admission, and drives
``Engine.step`` in between. Tokens are timed from the step metrics the
engine fetches anyway: a request's tokens exist on the host once a step's
metrics report its ``gen_idx`` past them.

Phases, on one clock from the schedule's start: set-up (weights, engine,
warm-up of every prefill bucket the schedule uses), pre-roll (the mix at
its rate until the slots reach steady occupancy), the window (``--seconds``)
and the drain (the rate goes on until every request due in the window has
its first token). ``setup_s`` runs from process start to the window's
opening. After the drain the engine is freed and the plain reference
scores a sample of the window's requests that finished.
"""

from __future__ import annotations

import contextlib
import gc
import os
import sys
import time
import types
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, trace, traffic

OUT = os.path.join(harness.ROOT, ".chipbench")


class Req:
    """What the driver saw of one request."""

    __slots__ = ("spec", "iid", "submit_t", "admit_t", "admit_step",
                 "tok_t", "gen", "losses", "done")

    def __init__(self, spec: traffic.Request):
        self.spec = spec
        self.iid: Optional[int] = None
        self.submit_t: Optional[float] = None
        self.admit_t: Optional[float] = None
        self.admit_step: Optional[int] = None
        self.tok_t: list[float] = []
        self.gen = 0  # tokens seen so far
        self.losses: list[float] = []  # ledger records, in position order
        self.done = False



def build(mix: dict, mcfg, params):
    from repro.launch import serve

    e = mix["engine"]
    args = serve.parser().parse_args([
        "--arch", mcfg.name, "--batch", str(e["slots"]),
        "--prompt-len", str(e["max_prompt"]), "--gen", str(e["max_gen"]),
        "--page-size", str(e["page_size"]), "--retain", e["retain"],
        "--ledger", e["ledger"], "--topk", str(e["topk"]),
        "--outcome-delay", str(mix["label_delay_steps"]),
    ])
    return serve.build_engine(args, mcfg, params)


def warm_up(engine, buckets: list[int], vocab: int, slots: int) -> None:
    """Compile every program the window will run: a prefill and an insert
    per bucket the schedule uses, the fused decode step, label delivery,
    page growth and clearing, and the gather of 1..slots evicting rows."""
    rng = np.random.default_rng(0)
    ids = []
    for b in buckets:
        ids.append(engine.submit(rng.integers(0, vocab, b, dtype=np.int32),
                                 max_new=2, expect_labels=True))
    pending = set(ids)
    while pending:
        engine.step()
        for iid in engine.in_flight_ids():
            if iid in pending:
                engine.deliver_outcome(iid, np.zeros(2, np.int64))
                pending.discard(iid)
    while engine.in_flight_ids():
        engine.step()
        engine.step()
    out = engine._estate.out_toks
    for n in range(1, slots + 1):
        jax.device_get(out[np.arange(n, dtype=np.int32)])


def run(cell: harness.Cell, seed: int, seconds: float, traced: bool,
        t_process: float, control: bool = False):
    """One run of a serve cell. Returns the run record, the peak device
    bytes, the requests attempted and failed, and the checks. ``control``
    (never set by a benchmark run) adds the control's readings on the same
    sample under ``checks["control"]``."""
    devices = harness.require_devices(cell.chips)
    cache = harness.use_compile_cache()
    clock = harness.CompileClock()
    ref = cell.reference()
    costs = cell.costs()
    conf, mix = cell.config, cell.traffic
    words = harness.seed_words(seed)
    sz = ref.sizes(conf)
    mcfg = harness.program_config(conf)
    peak = harness.peaks(devices[0].device_kind)
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}; compile cache {cache}", flush=True)

    split = {}
    sw = harness.Stopwatch()
    params = ref.make_weights(conf, words)
    jax.block_until_ready(params)
    split["weights_s"] = sw.lap()
    engine = build(mix, mcfg, params)
    del params  # the engine holds them
    sched = traffic.schedule(mix, words[2:], seconds, sz["vocab"])
    buckets = traffic.buckets_used(sched, engine.prompt_buckets)
    split["engine_s"] = sw.lap()
    warm_up(engine, buckets, sz["vocab"], engine.slots)
    split["warmup_s"] = sw.lap()
    split.update(clock.lap())

    reqs = [Req(r) for r in sched]
    by_iid: dict[int, Req] = {}
    window = [r for r in reqs if r.spec.phase == "window"]
    spans = traffic.phase_spans(mix, seconds)
    delay = int(mix["label_delay_steps"])
    ticks: list[tuple] = []  # (t_start, t_end, rows, keys, occ, occ_keys,
    #                           [admitted prompt lengths])
    late: list[float] = []
    waiting: list[Req] = []  # admitted, labels not yet delivered
    ending: list[Req] = []  # window requests generated whole, not evicted
    n_started = 0  # window requests with a first token
    annotate = (jax.profiler.TraceAnnotation if traced
                else contextlib.nullcontext)
    trace_s = min(float(mix["trace_s"]), seconds)
    trace_dir = os.path.join(OUT, "trace", cell.name)
    tracing = None  # (t_on, t_off) once the profiler ran
    span_win = None

    t0 = time.perf_counter()
    t_open = t0 + spans["preroll"]
    t_close = t_open + seconds
    t_cap = t_close + spans["drain"]
    nxt = 0
    setup_s = None
    compiles_before = None
    while True:
        now = time.perf_counter()
        if setup_s is None and now >= t_open:
            setup_s = now - t_process
            split["preroll_s"] = now - t0
            compiles_before = clock.now["compiles"]
            if traced:
                trace.start(trace_dir)
                span_win = jax.profiler.TraceAnnotation("traced.window")
                span_win.__enter__()
                tracing = [time.perf_counter(), None]
        if tracing and tracing[1] is None and now >= tracing[0] + trace_s:
            span_win.__exit__(None, None, None)
            trace.stop()
            tracing[1] = time.perf_counter()
        if now >= t_close and (n_started == len(window) or now >= t_cap):
            t_end = now
            break
        with annotate("submit"):
            while nxt < len(reqs) and t0 + reqs[nxt].spec.due <= now:
                r = reqs[nxt]
                r.iid = engine.submit(r.spec.prompt, max_new=r.spec.max_new,
                                      expect_labels=True)
                r.submit_t = time.perf_counter()
                late.append(r.submit_t - (t0 + r.spec.due))
                by_iid[r.iid] = r
                nxt += 1
        if waiting:
            with annotate("deliver"):
                keep = []
                for r in waiting:
                    if engine.steps_run >= r.admit_step + delay:
                        engine.deliver_outcome(r.iid, r.spec.labels)
                    else:
                        keep.append(r)
                waiting = keep
        ts = time.perf_counter()
        with annotate("engine.step"):
            m = engine.step()
        te = time.perf_counter()
        if m is None:
            wake = t0 + reqs[nxt].spec.due if nxt < len(reqs) else t_cap
            with annotate("idle.wait"):
                time.sleep(max(0.0, min(wake, t_cap) - time.perf_counter()))
            continue
        with annotate("fetch"):
            admitted = []
            for iid in engine.in_flight_ids():
                r = by_iid.get(iid)
                if r is not None and r.admit_t is None:
                    r.admit_t, r.admit_step = ts, engine.steps_run
                    admitted.append(r.spec.prompt.size)
                    waiting.append(r)
            inst, gi = m["inst"], m["gen_idx"]
            dec, occ = m["decoding"], m["occupied"]
            valid, loss = m["loss_valid"], m["loss"]
            rows = keys = n_occ = occ_keys = 0
            for s in np.flatnonzero(occ):
                r = by_iid.get(int(inst[s]))
                g = int(gi[s])
                plen = r.spec.prompt.size if r is not None else 0
                if dec[s]:
                    rows += 1
                    keys += plen + g - 1
                n_occ += 1
                occ_keys += plen + g - (1 if dec[s] else 0)
                if r is None:
                    continue
                if g > r.gen:
                    if r.gen == 0 and r.spec.phase == "window":
                        n_started += 1
                    r.tok_t.extend([te] * (g - r.gen))
                    r.gen = g
                    if g == r.spec.max_new and r.spec.phase == "window":
                        ending.append(r)
                if valid[s]:
                    r.losses.append(float(loss[s]))
            ticks.append((ts, te, rows, keys, n_occ, occ_keys, admitted))
            keep = []
            for r in ending:
                if r.iid in engine.finished:
                    r.done = True
                else:
                    keep.append(r)
            ending = keep
    if tracing and tracing[1] is None:
        span_win.__exit__(None, None, None)
        trace.stop()
        tracing[1] = time.perf_counter()
    compiles_in_window = clock.now["compiles"] - (compiles_before or 0)
    peak_bytes = harness.memory_peak(devices)
    stats = engine.stats()
    print("setup: " + " ".join(f"{k}={v}" for k, v in split.items())
          + f" setup_s={setup_s}", flush=True)
    late_ms = np.asarray(late) * 1e3
    print(f"generator: {len(late)} submitted, lateness p50 "
          f"{np.percentile(late_ms, 50):.3f} ms p95 "
          f"{np.percentile(late_ms, 95):.3f} ms max {late_ms.max():.3f} ms; "
          f"compiles in window {compiles_in_window}", flush=True)
    print(f"engine: {stats}", flush=True)

    finished = [r for r in window if r.done
                and len(engine.finished[r.iid]) == r.spec.max_new
                and len(r.tok_t) == r.spec.max_new]
    failed = len(window) - n_started
    served = {r.iid: np.asarray(engine.finished[r.iid]) for r in finished}
    ledger_ema = _ledger(engine, [r.iid for r in finished])
    tr = None
    if traced:
        tr = trace.load(trace_dir, harness.HOST_SPANS)
    rec = types.SimpleNamespace(
        cell=cell, sizes=sz, costs=costs, peak=peak, devices=devices,
        requests=reqs, window=window, finished=finished, ticks=ticks,
        t0=t0, t_open=t_open, t_close=t_close, t_end=t_end,
        seconds=seconds, setup_s=setup_s,
        trace=tr, trace_span=tracing, topk=mix["engine"]["topk"],
        logits_itemsize=jnp.dtype(mcfg.compute_dtype).itemsize,
        slots=engine.slots,
    )
    del engine, m
    gc.collect()
    checks = check(ref, words, conf, mix, finished, served, ledger_ema,
                   control)
    return rec, peak_bytes, len(window), failed, checks



def _ledger(engine, iids: list[int]) -> dict[int, float]:
    """The device ledger's loss EMA for every id that still owns its
    slot (a later id hashed onto the same slot evicts it)."""
    ema, seen = engine.ledger.lookup(np.asarray(iids, np.int64))
    return {i: float(e) for i, e, s in zip(iids, np.asarray(ema),
                                           np.asarray(seen)) if s}


# ---------------------------------------------------------------------------
# correctness: the served tokens and ledger records against the reference
# ---------------------------------------------------------------------------


def sample(finished: list[Req], seed_word: int, min_tokens: int) -> list[Req]:
    """The longest finished request, then others drawn from the seed,
    until ``min_tokens`` served tokens are in the sample."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (r.spec.max_new, -r.spec.index))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng(seed_word).permutation(len(rest))
    out, n = [longest], longest.spec.max_new
    for i in order:
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += rest[i].spec.max_new
    return out


def compare(ref, weights, conf: dict, mix: dict, picked: list[Req],
            served: dict, ledger_ema: dict, low: bool) -> dict:
    """Readings of one side (the program's, or the control's with
    ``low``) over the sampled requests:

    * ``token_gap``: the widest gap by which a served token's logit lies
      below the reference's best logit at its position (for the control:
      the token it puts first);
    * ``loss_gap``: the widest difference between a recorded ledger loss
      and the reference's loss for that position under the recorder's
      top-k rule, and ``loss_gap_mean`` the mean difference over every
      record;
    * ``ema_gap``: the widest difference between the ledger's loss EMA of
      a request and the EMA of the reference's losses in record order.
    """
    e = mix["engine"]
    t_pad = e["max_prompt"] + e["max_gen"]
    decay = float(mix.get("ledger_decay", 0.9))
    tok_gap = loss_gap = ema_gap = loss_sum = 0.0
    n_tok = n_loss = n_ema = 0
    for r in picked:
        p, toks = r.spec.prompt, served[r.iid]
        n = toks.size
        seq = np.zeros(t_pad, np.int32)
        seq[: p.size] = p
        seq[p.size: p.size + n] = toks
        labels = np.full(t_pad, -1, np.int32)
        labels[p.size - 1: p.size - 1 + n] = r.spec.labels[:n]
        rows = np.arange(p.size - 1, p.size - 1 + n)
        truth = ref.run(weights, conf, seq, labels, low=False, topk=e["topk"])
        if low:
            ctl = ref.run(weights, conf, seq, labels, low=True,
                          topk=e["topk"])
            at = np.asarray(jnp.take_along_axis(
                truth["logits"], jnp.asarray(ctl["argmax"])[:, None], -1)
            )[:, 0]
            gaps = truth["best"][rows] - at[rows]
            losses = ctl["loss"][rows]
            del ctl
        else:
            gaps = truth["best"][rows] - truth["next_logit"][rows]
            losses = np.asarray(r.losses, np.float32)
        want = truth["loss"][rows]
        del truth
        tok_gap = max(tok_gap, float(gaps.max()))
        n_tok += n
        if losses.size != want.size:
            raise RuntimeError(f"request {r.iid}: {losses.size} ledger "
                               f"records for {want.size} positions")
        diff = np.abs(losses - want)
        loss_gap = max(loss_gap, float(diff.max()))
        loss_sum += float(diff.sum())
        n_loss += losses.size
        if r.iid in ledger_ema:
            ema_ref = want[0]
            ema_got = losses[0]
            for x, y in zip(want[1:], losses[1:]):
                ema_ref = decay * ema_ref + (1 - decay) * x
                ema_got = decay * ema_got + (1 - decay) * y
            got = ema_got if low else ledger_ema[r.iid]
            ema_gap = max(ema_gap, abs(float(got) - float(ema_ref)))
            n_ema += 1
    return {"token_gap": tok_gap, "loss_gap": loss_gap,
            "loss_gap_mean": loss_sum / max(n_loss, 1), "ema_gap": ema_gap,

            "tokens": n_tok, "records": n_loss, "emas": n_ema}


READINGS = ("token_gap", "loss_gap", "loss_gap_mean", "ema_gap")


def judge(got: dict, limits: dict) -> dict:
    """Each number compared beside its limit; the program's readings and
    the control's go through this one rule."""
    out = {}
    for k, lim in limits.items():
        v = got[k]
        out[k] = {"value": v, "limit": lim,
                  "ok": harness.finite(v) and v <= lim}
    if "ema_gap" in out and got["emas"] == 0:
        out["ema_gap"]["ok"] = False
    if got["records"] == 0:
        for k in ("loss_gap", "loss_gap_mean"):
            if k in out:
                out[k]["ok"] = False
    return out


def check(ref, words, conf, mix, finished, served, ledger_ema,
          control: bool) -> dict:
    limits = mix["limits"]
    picked = sample(finished, words[3], int(mix["check_tokens"]))
    if not picked:
        return {k: {"value": None, "limit": v, "ok": False}
                for k, v in limits.items()}
    weights = ref.make_weights(conf, words)
    got = compare(ref, weights, conf, mix, picked, served, ledger_ema,
                  low=False)
    print(f"check: {len(picked)} requests, {got['tokens']} served tokens, "
          f"{got['records']} ledger records, {got['emas']} ledger EMAs "
          f"(ids still owning their slot); readings without a limit (not "
          f"compared): " + ", ".join(f"{k}={got[k]!r}" for k in READINGS
                                     if k not in limits),
          file=sys.stderr, flush=True)
    out = judge(got, limits)
    if control:
        low = compare(ref, weights, conf, mix, picked, served, ledger_ema,
                      low=True)
        verdict = judge(low, limits)
        low["correct"] = all(c["ok"] for c in verdict.values())
        out["control"] = low
    return out


# ---------------------------------------------------------------------------
# what the per-layer readers take from a traced run
# ---------------------------------------------------------------------------

# the names under which the trace shows the engine's programs (the XLA
# module of each jitted function) and kernels (their operations). The
# prefill and the label delivery are both jitted lambdas, one module name
# for two programs, so neither is read until the program names them.
PROGRAMS = {
    "decode": lambda n: "_fused_step" in n,
}
KERNELS = {
    "paged_attn": lambda n: n.startswith("paged_decode_attn"),
    "topk_lse": lambda n: n.startswith("topk_lse"),
}


def traced_ticks(rec) -> list[tuple]:
    """The engine steps that ran whole inside the traced window."""
    on, off = rec.trace_span
    return [t for t in rec.ticks if t[0] >= on and t[1] <= off]


def program_seconds(rec, kind: str) -> float:
    """Device seconds of one of the engine's programs in the traced
    window, over the chips used; none at all is an error."""
    tr = rec.trace
    total = 0.0
    for plane in tr.ops:
        evs = [e for e in tr.modules.get(plane, [])
               if PROGRAMS[kind](e.name)]
        if not evs:
            raise RuntimeError(f"no {kind} program in the trace of {plane}")
        total += sum(t - s for s, t in trace.clip(evs, tr.window_ns)) * 1e-9
    return total


def kernel_seconds(rec, kind: str) -> float:
    tr = rec.trace
    return sum(sum(t - s for s, t in trace.clip(
        trace.find(tr, plane, KERNELS[kind], kind), tr.window_ns)) * 1e-9
        for plane in tr.ops)
