"""Rate sweep of a serve cell, to find its knee once (not part of a run).

    python3 -m chipbench.sweep --workload qwen3-decode --seed 5 --seconds 30 \
        --rates 1.2 1.3 1.4 --orders 12 13

One process runs the cell once per offered rate and arrival order (the
mix's ``schedule_seed`` set to each of ``--orders``; weights, engine and
warm-up anew each time, the programs from the process's own cache), each
point on a seed of its own, and prints per point the tokens completed per
second, the latency tails and the queue of submitted, unadmitted requests
at the window's opening and closing. The knee is the highest rate whose
queue does not grow over the window in any order; a cell runs at about
four fifths of it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import harness  # noqa: E402


def queued(rec, t: float) -> int:
    return sum(1 for r in rec.requests
               if r.submit_t is not None and r.submit_t <= t
               and (r.admit_t is None or r.admit_t > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--orders", type=int, nargs="+", default=None)
    args = ap.parse_args(argv)
    bench = harness.benchmark(ROOT)
    seed = args.seed
    for rate in args.rates:
        for order in args.orders or [None]:
            cell = harness.Cell(bench, args.workload, ROOT)
            cell.traffic["rate_per_s"] = rate
            if order is not None:
                cell.traffic["schedule_seed"] = order
            point(cell, seed, args.seconds, rate, order)
            seed += 1
    return 0


def point(cell, seed: int, seconds: float, rate: float, order) -> None:
    rec, _, att, failed, checks = cell.driver().run(
        cell, seed, seconds, False, time.perf_counter())
    row = {"rate_per_s": rate, "order": order, "seed": seed,
           "attempted": att, "failed": failed,
           "queue_open": queued(rec, rec.t_open),
           "queue_close": queued(rec, rec.t_close),
           "correct": all(c["ok"] for c in checks.values()),
           "checks": {k: c["value"] for k, c in checks.items()}}
    for m in ("serve_tok_s", "ttft_p95_ms", "itl_p95_ms",
              "queue_wait_p95_ms"):
        row[m] = harness.metric_reader(m, ROOT)(rec)
    print("sweep " + json.dumps(row), flush=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
