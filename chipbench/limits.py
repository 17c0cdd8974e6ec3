"""Readings for setting a cell's correctness limits (not part of a run).

    python3 -m chipbench.limits --workload qwen3-decode --seconds 5 \
        --seeds 101 102 ... --control 3

One process runs the cell once per seed, at the cell's own load with a
short window, and prints the program's readings of every number compared;
for the first ``--control`` seeds it also prints the control's readings on
the same requests (the reference in the precision one step below the
configuration's), and for a train cell the readings of a fault planted in
the reference (half of the kept rows left out). The control's and the
fault's readings go through the run's own comparison with the cell's
limits, and ``correct`` beside them has to read false. A limit lies above
the largest program reading and below the smallest control reading
(PERF.md gives both).
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    bench = harness.benchmark(ROOT)
    for i, seed in enumerate(args.seeds):
        cell = harness.Cell(bench, args.workload, ROOT)
        _, _, att, failed, checks = cell.driver().run(
            cell, seed, args.seconds, False, time.perf_counter(),
            control=i < args.control)
        row = {"seed": seed, "attempted": att, "failed": failed,
               "program": {k: c["value"] for k, c in checks.items()
                           if "limit" in c},
               "correct": all(c["ok"] for c in checks.values()
                              if "limit" in c)}
        row.update({k: c for k, c in checks.items() if "limit" not in c})
        print("limits " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
