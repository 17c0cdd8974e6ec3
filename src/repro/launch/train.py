"""Production train driver: OBFTF training with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train \
        --arch llama3-8b --smoke --steps 200 --method obftf --ratio 0.25

Features exercised end-to-end (and how they map to a 1000+-node job):
  * mesh from live devices (`make_elastic_mesh`) — on restart after a node
    loss the mesh shrinks and the per-shard batch is recomputed;
  * OBFTF train step (selection fused on-device, shard-local);
  * async atomic checkpointing (keep-k), `--resume auto`;
  * SIGTERM/SIGINT -> final blocking checkpoint (preemption grace window);
  * step-time straggler watchdog (EMA + outlier threshold; in a multi-host
    job this signal feeds the controller that evicts the slow host);
  * deterministic data (restart replays the exact stream);
  * TRUE per-instance losses recorded from the step's forwards (selection
    forward for the whole batch, backward forward for the kept subset) —
    the paper's "record a constant amount of information per instance"
    ledger, never a batch-mean broadcast;
  * ledger state checkpointed with the params (``ledger.npz`` in the step
    dir, same .npz interchange as serve's ``--ledger-out``), so --resume
    restores the recycle signal warm instead of cold.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs
from repro.checkpoint import CheckpointManager
from repro.core import device_ledger as dledger
from repro.core.history import HistoryConfig, LossHistory
from repro.core.obftf import OBFTFConfig, make_train_step, step_cost_savings
from repro.core.selection import (
    POLICIES,
    SelectionConfig,
    get_policy,
    policy_score,
)
from repro.data import DataConfig, Prefetcher, RecycleFeed, SyntheticLMStream
from repro.distributed.ledger import sharded_ledger_ops
from repro.distributed.sharding import DEFAULT_RULES, use_rules
from repro.launch import use_compile_cache
from repro.launch.mesh import make_elastic_mesh, validate_batch
from repro.launch.specs import state_specs
from repro.models import model as Mdl
from repro.models.params import materialize

COLD_LOSS = 1e3  # recorded-loss fallback for ledger misses (cold start)


class Watchdog:
    """Step-time EMA; flags stragglers (steps > `factor` x EMA)."""

    def __init__(self, factor: float = 3.0, warmup: int = 5):
        self.factor = factor
        self.warmup = warmup
        self.ema = None
        self.n = 0
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        self.n += 1
        if self.ema is None:
            self.ema = dt
            return False
        slow = self.n > self.warmup and dt > self.factor * self.ema
        if slow:
            self.flagged += 1
        else:  # don't poison the EMA with outliers
            self.ema = 0.9 * self.ema + 0.1 * dt
        return slow


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--method", default="obftf", help="selection method")
    ap.add_argument("--ratio", type=float, default=0.25)
    ap.add_argument("--recycle", action="store_true",
                    help="reuse recorded losses as the selection signal")
    ap.add_argument("--policy", default="loss_ema",
                    choices=sorted(POLICIES),
                    help="selection policy scoring the recycled ledger "
                         "signals (loss EMA, serve-time entropy/margin, "
                         "or the uniform control); only meaningful with "
                         "--recycle")
    ap.add_argument("--ledger", default="host", choices=("host", "device"),
                    help="recycle ledger placement: host numpy store with a "
                         "per-step round-trip, or device-resident (lookup + "
                         "record fused into the jitted step, no host hop)")
    ap.add_argument("--ledger-in", default="",
                    help="warm-start the ledger from an .npz state_dict "
                         "(e.g. written by launch.serve --ledger-out or a "
                         "checkpoint's ledger.npz); re-hashed on a layout "
                         "change")
    ap.add_argument("--ledger-out", default="",
                    help="save the final ledger state_dict as .npz (global "
                         "slot layout, the shared interchange format)")
    ap.add_argument("--ledger-route", action="store_true",
                    help="cross-shard id routing for the sharded device "
                         "ledger: exchange each id to the shard owning its "
                         "global slot before record/lookup, for feeds that "
                         "do not pin instances to a data shard")
    ap.add_argument("--ledger-exchange", default="gather",
                    choices=("gather", "a2a"),
                    help="routed exchange realization: all_gather+home-mask "
                         "(O(shards*batch) bytes) or capacity-factor "
                         "all_to_all with exact overflow fallback "
                         "(O(batch*cf) bytes); results are bit-identical")
    ap.add_argument("--capacity-factor", type=float, default=1.25,
                    help="a2a send-buffer slack: per-destination capacity = "
                         "ceil(batch*cf/shards); items past it take the "
                         "exact fallback round (counted in a2a_overflow)")
    ap.add_argument("--json-out", default="",
                    help="write a run summary (losses, step cost) as JSON")
    ap.add_argument("--instance-pool", type=int, default=0,
                    help="distinct instance ids before the stream repeats "
                         "(0 = DataConfig default 2^20); small pools make "
                         "the recycle ledger hit within a smoke run")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="", help="'auto' or a step number")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="tensor-parallel degree (the \"model\" mesh axis); "
                         "the rest of the devices form the \"data\" axis")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    obs.add_cli_args(ap)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    telem = obs.from_args(args)
    mesh = make_elastic_mesh(model_parallel=args.model_parallel)
    rules = DEFAULT_RULES
    single_device = mesh.devices.size == 1
    local_batch = validate_batch(args.global_batch, mesh, rules.batch_axes)
    print(
        f"arch={cfg.name} devices={mesh.devices.size} mesh={dict(mesh.shape)} "
        f"global_batch={args.global_batch} (x{local_batch}/shard) "
        f"method={args.method} ratio={args.ratio}"
    )

    sel = SelectionConfig(method=args.method, ratio=args.ratio)
    obftf = OBFTFConfig(selection=sel, recycle_forward=args.recycle,
                        mode="full" if args.method == "full" else "obftf")
    state_abs, state_sh, optimizer = state_specs(
        cfg, None if single_device else mesh, rules, lr=args.lr,
        total_steps=args.steps,
    )
    step_fn = make_train_step(
        Mdl.loss_fn(cfg), optimizer, obftf,
        mesh=None if single_device else mesh,
        dp_axes=rules.batch_axes,
    )

    rng = jax.random.key(args.seed)
    params = materialize(Mdl.param_specs(cfg), rng, jnp.dtype(cfg.param_dtype))
    state = {
        "params": params,
        "opt": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }

    ckpt = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start_step = 0
    resume_ledger = None  # applied below, once the ledger exists
    if ckpt and args.resume:
        s = ckpt.latest() if args.resume == "auto" else int(args.resume)
        if s is not None:
            state = ckpt.restore(s, state)
            state = jax.tree.map(jnp.asarray, state)
            start_step = int(state["step"])
            resume_ledger = ckpt.restore_ledger(s)
            print(f"resumed from step {start_step}"
                  + (" (with ledger)" if resume_ledger is not None else ""))

    dcfg = DataConfig(args.global_batch, args.seq_len, cfg.vocab_size,
                      seed=args.seed)
    if args.instance_pool:
        if args.instance_pool % args.global_batch:
            # divisibility keeps each id at a fixed batch offset across pool
            # wraps — the id->shard pinning the zero-communication sharded
            # ledger relies on (see repro.distributed.ledger)
            raise SystemExit(
                f"--instance-pool {args.instance_pool} must be a multiple "
                f"of --global-batch {args.global_batch}"
            )
        dcfg = dataclasses.replace(dcfg, instance_pool=args.instance_pool)
    stream = SyntheticLMStream(dcfg)
    lcfg = HistoryConfig()
    use_device_ledger = args.recycle and args.ledger == "device"
    led_ops = led_state = None
    history = None
    feed = stream

    def load_device_sd(sd):
        """State_dict -> placed LedgerState (each loader re-hashes foreign
        layouts into its own; sharded placement goes through the ops)."""
        if led_ops is not None:
            return led_ops.load_state_dict(sd)
        led = dledger.DeviceLedger(lcfg)
        led.load_state_dict(sd)
        return led.state

    if use_device_ledger:
        # device-resident ledger: lookup + record fuse into the jitted step
        # below; the recycle signal never touches the host.
        if single_device:
            led_state = dledger.init_state(lcfg)
        else:
            led_ops = sharded_ledger_ops(
                mesh, lcfg, rules.batch_axes, route=args.ledger_route,
                exchange=args.ledger_exchange,
                capacity_factor=args.capacity_factor,
            )
            led_state = led_ops.init()
        if args.ledger_in:
            led_state = load_device_sd(dict(np.load(args.ledger_in)))
            print(f"ledger warm-start from {args.ledger_in} "
                  f"({int(np.sum(np.asarray(led_state.owner) >= 0))} live slots)")
    else:
        history = LossHistory(lcfg)
        if args.ledger_in:
            history.load_state_dict(dict(np.load(args.ledger_in)))
            print(f"ledger warm-start from {args.ledger_in} "
                  f"({int((history.owner >= 0).sum())} live slots)")
        if args.recycle:
            feed = RecycleFeed(stream, history, ledger="host",
                               cold_loss=COLD_LOSS, policy=args.policy)
    if resume_ledger is not None:
        # the checkpoint's ledger wins over --ledger-in: it is the recycle
        # signal as of the resumed step, not the (older) serve-time export
        if use_device_ledger:
            led_state = load_device_sd(resume_ledger)
        else:
            history.load_state_dict(resume_ledger)
        live = int((np.asarray(resume_ledger["owner"]) >= 0).sum())
        print(f"ledger restored from checkpoint ({live} live slots)")

    def ledger_state_dict():
        """Current ledger as an .npz-able state_dict: the global
        interchange layout, except a pinned multi-shard table which
        exports raw with a ``pinned_shards`` marker (lossless same-layout
        resume; other loaders re-hash it)."""
        if use_device_ledger:
            if led_ops is not None:
                return led_ops.state_dict(led_state)
            return dledger.state_dict_of(led_state)
        return history.state_dict()

    watchdog = Watchdog()

    stop = {"now": False}

    def _sigterm(signum, frame):
        print(f"signal {signum}: checkpoint + exit after this step")
        stop["now"] = True

    signal.signal(signal.SIGTERM, _sigterm)
    signal.signal(signal.SIGINT, _sigterm)

    if use_device_ledger:
        led_lookup = led_ops.lookup if led_ops else dledger.lookup
        led_lookup_sig = (
            led_ops.lookup_signals if led_ops else dledger.lookup_signals
        )
        policy = get_policy(args.policy)
        if led_ops:
            def led_record(lstate, ids, losses, step, valid):
                return led_ops.record(lstate, ids, losses, step, valid,
                                      return_stats=True)
        else:
            def led_record(lstate, ids, losses, step, valid):
                st = dledger.record(lcfg, lstate, ids, losses, step,
                                    valid=valid)
                return st, {"a2a_overflow": jnp.zeros((), jnp.int32)}

        def step_with_ledger(state, lstate, batch, rng):
            """Ledger probe -> OBFTF step -> ledger write, one jit, zero
            host transfers (the whole point of the device ledger).

            Non-default policies score the ledger's multi-channel
            signals in-jit (``policy_score``) and feed the score as the
            recycled pseudo-loss; the historical loss_ema default keeps
            its exact raw-EMA join."""
            ids = batch["instance_id"]
            if policy.name == "loss_ema":
                ema, seen = led_lookup(lstate, ids)
                rec = jnp.where(seen, ema, COLD_LOSS).astype(jnp.float32)
            else:
                ema, sig, seen = led_lookup_sig(lstate, ids)
                rec = policy_score(policy, ema, sig, seen, COLD_LOSS)
            state, metrics = step_fn(state, dict(batch, recorded_loss=rec),
                                     rng)
            # TRUE per-example losses from the step's forwards, written
            # only where a loss was computed this step (`fresh`): under
            # --recycle that is the backward subset — replayed records are
            # never re-recorded as observations (which would fake
            # last_seen and collapse the signal toward its own echo).
            lstate, lstats = led_record(
                lstate,
                ids,
                metrics["per_example_loss"],
                state["step"],
                metrics["per_example_fresh"],
            )
            metrics = dict(metrics, ledger_hits=jnp.mean(
                seen.astype(jnp.float32)),
                a2a_overflow=lstats["a2a_overflow"])
            # the per-example arrays exist for the ledger write above;
            # don't ship [batch] arrays to the host with the scalars.
            for k in ("per_example_loss", "per_example_fresh"):
                del metrics[k]
            return state, lstate, metrics

        jit_step = jax.jit(
            step_with_ledger,
            out_shardings=(state_sh, None, None)
            if not single_device else None,
            donate_argnums=(1,),
        )
    else:
        jit_step = jax.jit(step_fn, out_shardings=(state_sh, None)
                           if not single_device else None)
    losses_log = []
    cost_log = []
    hits_log = []
    a2a_overflow = 0  # items that took the a2a exact fallback round

    # telemetry: bound once; per-step updates are host arithmetic on the
    # step's already-fetched metrics (same contract as the engine — the
    # instrumented jitted step stays transfer_guard("disallow")-clean).
    # NOTE: no EMA-drift oracle on the device-ledger train path — that
    # path deliberately deletes the per-example arrays from the shipped
    # metrics (docs/observability.md), so the loop-health gauges here are
    # rates only.
    c_steps = telem.counter("trainer.steps")
    c_straggler = telem.counter("trainer.stragglers")
    c_overflow = telem.counter("trainer.a2a_overflow")
    g_loss = telem.gauge("trainer.loss")
    g_cost = telem.gauge("trainer.step_cost")
    g_savings = telem.gauge("trainer.step_cost_savings")
    g_hits = telem.gauge("trainer.ledger_hit_rate")
    h_step = telem.histogram("trainer.step_ms")

    def train_health() -> dict:
        steps_done = len(losses_log)
        return {
            "steps": steps_done,
            "loss": losses_log[-1] if losses_log else None,
            "step_cost": cost_log[-1] if cost_log else None,
            "step_cost_savings": (
                step_cost_savings(cost_log[-1]) if cost_log else None
            ),
            "mean_step_cost": float(np.mean(cost_log)) if cost_log else None,
            "ledger_hit_rate": hits_log[-1] if hits_log else None,
            "a2a_overflow_rate": obs.rate_of(a2a_overflow, steps_done),
            "straggler_rate": obs.rate_of(watchdog.flagged, steps_done),
            "step_ms_ema": (watchdog.ema or 0.0) * 1e3,
        }

    if not single_device:
        # start from the placement the step returns, so step 1 reuses step
        # 0's executable instead of compiling the whole step again
        state = jax.device_put(state, state_sh)
    # one device has nothing to constrain: sharding hints there would only
    # tag the step's outputs with a mesh its inputs lack (a second compile)
    with use_rules(None if single_device else mesh, rules):
        for step in range(start_step, args.steps):
            t0 = time.time()
            raw = feed.batch(step)
            batch = {
                "tokens": jnp.asarray(raw["tokens"]),
                "labels": jnp.asarray(raw["labels"]),
            }
            rng, sub = jax.random.split(rng)
            with telem.span("train.dispatch", step=step):
                if use_device_ledger:
                    batch["instance_id"] = jnp.asarray(
                        raw["instance_id"].astype(np.int32)
                    )
                    state, led_state, metrics = jit_step(state, led_state,
                                                         batch, sub)
                else:
                    if args.recycle:
                        batch["recorded_loss"] = jnp.asarray(
                            raw["recorded_loss"]
                        )
                    state, metrics = jit_step(state, batch, sub)
            with telem.span("train.fetch_metrics"):
                metrics = jax.device_get(metrics)
            dt = time.time() - t0
            slow = watchdog.observe(dt)
            if history is not None:
                # true per-example losses from the step's forwards — only
                # entries computed THIS step (fresh), never the replayed
                # record and never a batch-mean broadcast
                fresh = np.asarray(metrics["per_example_fresh"], bool)
                if fresh.any():
                    history.record(
                        raw["instance_id"][fresh],
                        np.asarray(metrics["per_example_loss"])[fresh],
                        step,
                    )
            if use_device_ledger:
                hits_log.append(float(metrics["ledger_hits"]))
                a2a_overflow += int(metrics["a2a_overflow"])
            elif args.recycle:
                hits_log.append(float(raw.get("ledger_hit_rate", 0.0)))
            losses_log.append(float(metrics["loss"]))
            cost_log.append(float(metrics["step_cost"]))
            c_steps.inc()
            if slow:
                c_straggler.inc()
            g_loss.set(losses_log[-1])
            g_cost.set(cost_log[-1])
            g_savings.set(step_cost_savings(cost_log[-1]))
            h_step.observe(dt * 1e3)
            if use_device_ledger:
                c_overflow.inc(int(metrics["a2a_overflow"]))
            if hits_log:
                g_hits.set(hits_log[-1])
            if telem.events is not None and \
                    (step + 1) % args.metrics_every == 0:
                telem.event("loop_health", **train_health())
            if step % args.log_every == 0 or slow:
                print(
                    f"step {step:5d} loss={metrics['loss']:.4f} "
                    f"sel_resid={metrics['selection_residual']:.4f} "
                    f"kept={int(metrics['kept'])} "
                    f"gnorm={metrics['grad_norm']:.3f} {dt*1e3:.0f}ms"
                    + ("  [STRAGGLER]" if slow else "")
                )
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state, ledger=ledger_state_dict())
            if stop["now"]:
                break

    if ckpt:
        # the SIGTERM/final save carries the ledger too: a preempted job
        # resumes with its recycle signal warm, not cold
        ckpt.save(int(state["step"]), state, block=True,
                  ledger=ledger_state_dict())
        print(f"final checkpoint at step {int(state['step'])}")
    if args.ledger_out:
        sd = ledger_state_dict()
        layout = ("pinned-sharded" if "pinned_shards" in sd else "global")
        np.savez(args.ledger_out, **sd)
        print(f"ledger saved to {args.ledger_out} ({layout} layout)")
    mean_cost = float(np.mean(cost_log)) if cost_log else 0.0
    print(f"done: {len(losses_log)} steps, "
          f"loss {losses_log[0]:.4f} -> {losses_log[-1]:.4f}, "
          f"step_cost {mean_cost:.3f}C, "
          f"stragglers flagged: {watchdog.flagged}")
    # one summary for every consumer: --json-out and the final "summary"
    # event of --metrics-out carry the identical payload
    summary = {
        "steps": len(losses_log),
        "loss_first": losses_log[0],
        "loss_last": losses_log[-1],
        "mean_step_cost": mean_cost,
        "step_cost_savings": step_cost_savings(mean_cost),
        "method": args.method,
        "ratio": args.ratio,
        "recycle": bool(args.recycle),
        "policy": args.policy,
        "ledger": args.ledger,
        "exchange": (args.ledger_exchange if args.ledger_route
                     else "none"),
        "capacity_factor": args.capacity_factor,
        "a2a_overflow": a2a_overflow,
        "ledger_shards": led_ops.shards if led_ops is not None else 1,
        "stragglers": watchdog.flagged,
        "ledger_hits_first": hits_log[0] if hits_log else None,
        "ledger_hits_mean": float(np.mean(hits_log)) if hits_log else None,
        "health": train_health(),
    }
    if telem.registry is not None:
        summary["metrics"] = telem.snapshot()
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f)
    telem.close(summary=summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
