"""Serving driver: a thin CLI over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --batch 8 --prompt-len 32 --gen 32 --requests 24 --ledger device

This is the "ten forward" side of the title: the serving fleet runs
forwards anyway; when ground-truth labels arrive (clicks, ratings, next
events), the engine's OutcomeRecorder scores the logits we already paid
for and records per-instance losses into the LossHistory ledger — every
generated position, against a stable monotone instance id, inside the
jitted decode step (no host hop; ``--ledger-route`` shards + routes the
table over the mesh). The training side (`--recycle` in launch.train)
then selects with NO extra selection forward — one backward from ten
(already-run) forwards.

Requests come from the same deterministic SyntheticLMStream the trainer
feeds on, carrying the SAME instance ids — so the ledger this driver
writes (``--ledger-out``) is directly consumable by
``train --recycle --ledger-in`` (and vice versa: ``--ledger-in`` accepts a
train checkpoint's ledger.npz). ``--outcome-delay`` delivers each
request's labels N engine steps after admission instead of at submit,
exercising the late-outcome path a real fleet lives on.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs
from repro.core.history import HistoryConfig
from repro.data import DataConfig, SyntheticLMStream
from repro.launch import use_compile_cache
from repro.launch.mesh import make_elastic_mesh
from repro.models import model as Mdl
from repro.models.params import materialize
from repro.serving import Engine, OutcomeRecorder, delayed_outcomes, pad_safe


def build_engine(args, cfg, params, telemetry=None):
    mesh = make_elastic_mesh() if args.ledger_route else None
    if args.ledger_route and args.ledger != "device":
        raise SystemExit("--ledger-route requires --ledger device")
    recorder = OutcomeRecorder(
        args.batch,
        args.gen,
        cfg.vocab_size,
        HistoryConfig(),
        ledger=args.ledger,
        mesh=mesh,
        route=args.ledger_route,
        exchange=args.ledger_exchange,
        capacity_factor=args.capacity_factor,
        retention=args.retain,
        topk=args.topk,
    )
    return Engine(
        cfg,
        params,
        recorder,
        slots=args.batch,
        max_prompt=args.prompt_len,
        max_gen=args.gen,
        page_size=args.page_size if args.page_size > 0 else None,
        num_pages=args.num_pages if args.num_pages > 0 else None,
        temperature=args.temperature,
        top_p=args.top_p,
        sample_seed=args.seed,
        telemetry=telemetry,
    )


def submit_stream(engine, args, cfg):
    """Queue --requests requests off the deterministic synthetic stream.

    Prompt lengths vary per row (pad-safe families exercise the bucketed
    prefill; others keep the full length — exact-length compile), labels
    are the stream's ground-truth continuation, instance ids are the
    stream's own (stable across serve runs and shared with the trainer's
    feed).
    """
    stream = SyntheticLMStream(
        DataConfig(
            args.batch,
            args.prompt_len + args.gen,
            cfg.vocab_size,
            seed=args.seed,
            instance_pool=args.instance_pool,
        )
    )
    waves = -(-args.requests // args.batch)
    vary = pad_safe(cfg) and args.prompt_len >= 8
    n = 0
    submitted = []
    for w in range(waves):
        raw = stream.batch(w)
        for r in range(args.batch):
            if n >= args.requests:
                break
            plen = args.prompt_len - (r % 4) * (args.prompt_len // 8) if vary \
                else args.prompt_len
            toks = raw["tokens"][r]
            labels = toks[plen : plen + args.gen]
            iid = engine.submit(
                toks[:plen],
                max_new=len(labels),
                labels=None if args.outcome_delay else labels,
                instance_id=int(raw["instance_id"][r]),
                expect_labels=bool(args.outcome_delay),
            )
            submitted.append((iid, labels))
            n += 1
    return waves, submitted


def parser() -> argparse.ArgumentParser:
    """The serve CLI's flags (also how ``chip_smoke.py`` builds its args)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8,
                    help="decode slots (the fixed-size continuous batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32,
                    help="max new tokens per request")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests to stream through the engine "
                         "(0 = 3 waves, i.e. 3x --batch)")
    ap.add_argument("--outcome-delay", type=int, default=0,
                    help="deliver each request's labels N engine steps "
                         "after admission (0 = attach at submit) — the "
                         "late-outcome serving path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache page size in tokens (0 = dense "
                         "per-slot reservation)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="global KV page pool size (0 = dense-equivalent "
                         "slots * ceil(max_seq / page_size))")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-slot sampling temperature (0 = greedy argmax, "
                         "the bit-reproducible default)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (only with --temperature>0)")
    ap.add_argument("--instance-pool", type=int, default=1 << 20,
                    help="distinct stream instance ids before reuse")
    ap.add_argument("--retain", default="topk", choices=("full", "topk"),
                    help="retained-outcome layout: the compressed (top-k "
                         "values/indices, exact lse) summary — constant "
                         "size in V; late labels score exactly on a top-k "
                         "hit, at the lse-min(topk) tail floor on a miss — "
                         "or the dense [slots,gen,V] logits buffer, the "
                         "exact oracle (~10 GB at 32 slots x 512 tokens of "
                         "a 152k vocab)")
    ap.add_argument("--topk", type=int, default=64,
                    help="retained top-k width under --retain topk")
    ap.add_argument("--ledger", default="host", choices=("host", "device"),
                    help="record outcomes into the host numpy ledger or the "
                         "device-resident one (no host transfer per record)")
    ap.add_argument("--ledger-route", action="store_true",
                    help="shard the device ledger over the mesh and route "
                         "each record to the shard owning its global slot "
                         "(sharded_ledger_ops(route=True) inside the step)")
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
    ap.add_argument("--ledger-out", default="",
                    help="save the ledger state_dict as .npz (interchange "
                         "format shared by host and device ledgers and by "
                         "train-checkpoint ledger.npz files; feed to "
                         "launch.train --ledger-in for recycle training)")
    ap.add_argument("--ledger-in", default="",
                    help="warm-start from an .npz state_dict (e.g. a train "
                         "checkpoint's ledger.npz), so serving-time records "
                         "accumulate on top of the trainer's signal")
    ap.add_argument("--json-out", default="",
                    help="write a run summary (throughput, records, ledger "
                         "stats) as JSON")
    obs.add_cli_args(ap)
    return ap


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    use_compile_cache()
    if args.requests <= 0:
        args.requests = 3 * args.batch

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    telem = obs.from_args(args)
    rng = jax.random.key(args.seed)
    params = materialize(Mdl.param_specs(cfg), rng, jnp.dtype(cfg.param_dtype))
    engine = build_engine(args, cfg, params, telemetry=telem)

    if args.ledger_in:
        engine.load_ledger_state_dict(dict(np.load(args.ledger_in)))
        live = int((np.asarray(engine.ledger_state_dict()["owner"]) >= 0).sum())
        print(f"ledger warm-start from {args.ledger_in} ({live} live slots)")

    waves, submitted = submit_stream(engine, args, cfg)
    shards = engine.recorder.ops.shards if engine.recorder.ops else 1
    bps = engine.recorder.retained_bytes_per_slot()
    print(
        f"arch={cfg.name} slots={args.batch} requests={args.requests} "
        f"({waves} waves) gen<= {args.gen} ledger={args.ledger}"
        + (f"[routed x{shards}]" if args.ledger_route else "")
        + f" retain={args.retain}"
        + (f"[k={args.topk}]" if args.retain == "topk" else "")
        + f" ({bps / 1e6:.3f} MB retained/slot)"
    )

    deliver = (
        delayed_outcomes(submitted, args.outcome_delay)  # pairs: dup ids ok
        if args.outcome_delay else None
    )

    def on_step(eng, metrics):
        if deliver is not None:
            deliver(eng, metrics)
        if telem.events is not None and eng.steps_run % args.metrics_every == 0:
            # drift=True fetches the device ledger's state_dict — a device
            # round-trip, which is why it rides the snapshot cadence and
            # never the per-step path
            telem.event("loop_health", **eng.loop_health(drift=True))

    t0 = time.time()
    stats = engine.run(max_steps=100_000, on_step=on_step)
    dt = time.time() - t0
    tok_s = stats["generated_tokens"] / max(dt, 1e-9)
    print(
        f"served {stats['evicted']} requests, "
        f"{stats['generated_tokens']} decode tokens in {dt:.2f}s "
        f"({tok_s:.1f} tok/s, {stats['steps']} engine steps)"
    )

    ids = np.asarray([iid for iid, _ in submitted], np.int64)
    ema, seen = engine.ledger.lookup(ids)
    print(
        f"recorded serving losses: {stats['recorded']} positions, "
        f"mean ema={float(np.asarray(ema)[np.asarray(seen)].mean() if np.asarray(seen).any() else 0):.3f}; "
        f"ledger hit rate={float(np.asarray(seen).mean()):.2f}"
    )
    if args.retain == "topk":
        print(
            f"top-k tail-floor records: {stats['topk_misses']} of "
            f"{stats['recorded']} (rest scored exactly)"
        )
    if args.ledger_out:
        sd = engine.ledger_state_dict()
        np.savez(args.ledger_out, **sd)
        print(f"ledger saved to {args.ledger_out} ({args.ledger} layout)")
    print("sample generations (token ids):")
    for iid in list(engine.finished)[:2]:
        print("  ", engine.finished[iid][:12].tolist())
    # ONE summary dict serves every consumer: --json-out, the final
    # "summary" event of --metrics-out, and the stdout epilogue above all
    # read the same engine.stats() snapshot (one batched device fetch)
    summary = dict(
        stats,
        tok_per_s=tok_s,
        waves=waves,
        ledger=args.ledger,
        routed=bool(args.ledger_route),
        exchange=args.ledger_exchange if args.ledger_route else "none",
        capacity_factor=args.capacity_factor,
        shards=shards,
        hit_rate=float(np.asarray(seen).mean()),
        outcome_delay=args.outcome_delay,
        retention=args.retain,
        topk=args.topk,
        retained_bytes_per_slot=bps,
        health=engine.loop_health(drift=True),
    )
    if telem.registry is not None:
        summary["metrics"] = telem.snapshot()
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f)
    telem.close(summary=summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
