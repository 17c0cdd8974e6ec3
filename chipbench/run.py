"""Run one benchmark cell once and print its result as the last line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result. ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` profiles a sub-window and reports its per-layer
metrics, with the device's busy and traced seconds and a breakdown.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # before JAX and the program load

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import harness, trace  # noqa: E402


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def result(cell: harness.Cell, rec, traced: bool) -> tuple[dict, dict]:
    """The metrics the run reports, each from its reader, and with
    ``--trace 1`` the device block's busy and window seconds and the
    breakdown."""
    metrics, extra = {}, {}
    wanted = cell.per_layer if traced else cell.end_to_end
    for m in wanted:
        v = harness.metric_reader(m["name"], cell.root)(rec)
        if v is None:
            continue
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if traced:
        tr = rec.trace
        busy = [trace.busy_s(tr, p) for p in sorted(tr.ops)]
        extra["busy_s"] = sum(busy) / len(busy)
        extra["window_s"] = tr.window_s
    return metrics, extra


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    bench = harness.benchmark(ROOT)
    cell = harness.Cell(bench, args.workload, ROOT)
    driver = cell.driver()
    traced = bool(args.trace)
    rec, peak_bytes, attempted, failed, checks = driver.run(
        cell, args.seed, args.seconds, traced, T_PROCESS)
    metrics, extra = result(cell, rec, traced)
    device = harness.device_info(rec.devices, peak_bytes)
    device.update(extra)
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = trace.breakdown(rec.trace)
    harness.emit(out, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
