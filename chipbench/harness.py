"""What every cell shares: the spec files, the device check, seeds, the
compile clock, the metric readers and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
they are looked up under the names ``BENCHMARK.json`` gives them.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Callable, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# host names of what the run was doing, on the profiler's own clock: each
# idle gap of a traced run is labelled by the annotation it falls in
HOST_SPANS = ("engine.step", "submit", "deliver", "fetch", "train.step",
              "idle.wait")


class BenchError(SystemExit):
    """A run that must not print a result (exit code 2)."""

    def __init__(self, msg: str):
        print(f"chipbench: {msg}", file=sys.stderr)
        super().__init__(2)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    return load_json(path)


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration, traffic
    and metric entries resolved from their files."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise BenchError(f"unknown workload {name!r}; known: "
                             f"{sorted(by_name)}")
        self.root = root
        self.dir = os.path.join(root, "chipbench")
        self.workload = by_name[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(os.path.join(root,
                                             self.config_entry["file"]))
        self.traffic = load_json(os.path.join(
            self.dir, "traffic", self.workload["traffic"] + ".json"))
        self.chips = int(self.workload["chips"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _reports(m, name)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if m["moves"] in moved and _reports(m, name)]

    def driver(self):
        return importlib.import_module(
            "chipbench.drivers." + self.traffic["driver"])

    def reference(self):
        return importlib.import_module(
            "chipbench.reference." + self.config["model"])

    def costs(self):
        return importlib.import_module("chipbench.costs." +
                                       self.config["model"])


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def metric_reader(name: str, root: str = ROOT
                  ) -> Callable[[Any], Optional[float]]:
    """``metrics/<name>.py``'s ``read(record)``; the file name is the
    metric's name, dots and all, so it is loaded by path."""
    path = os.path.join(root, "chipbench", "metrics", name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_config(conf: dict):
    """The program's ``ModelConfig`` as the configuration file states it:
    the program's preset for ``program.arch`` with every field that
    ``program.fields`` maps from the file's ``config`` set to the file's
    value (widths included, so the program runs the published sizes
    whatever its preset holds). A field given as ``{"field": f,
    "values": {...}}`` is a setting the program spells otherwise: ``f``
    takes the value listed under the file's value, written as JSON."""
    import dataclasses

    from repro import configs

    prog = conf["program"]
    base = configs.get(prog["arch"])
    changes = {}
    for key, field in prog["fields"].items():
        value = conf["config"][key]
        if isinstance(field, dict):
            field, value = field["field"], field["values"][json.dumps(value)]
        changes[field] = value
    changes.update(prog.get("set", {}))
    return dataclasses.replace(base, **changes).validate()


def seed_words(seed: int, n: int = 4) -> list[int]:
    """``n`` 32-bit words from any whole seed (negative or past 64 bits
    included): the one place a seed is turned into randomness."""
    import numpy as np

    return [int(w) for w in np.random.SeedSequence(
        abs(int(seed)) * 2 + (seed < 0)).generate_state(n)]


def peaks(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise BenchError(f"no peaks for device_kind {kind!r} in "
                         f"peaks.json (known: {sorted(table)})")
    return table[kind]


def require_devices(chips: int):
    """The run's devices, or no result: a TPU and at least ``chips`` of
    them. Returns the first ``chips`` devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``$JAX_COMPILATION_CACHE_DIR`` where that is set), holding
    every program however short its compile, so that only a cell's first
    run in a checkout compiles. Off a TPU (the tests) there is none."""
    import jax

    if jax.default_backend() != "tpu":
        return ""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """JAX's own compile events: seconds of tracing, lowering and backend
    compile (a persistent-cache hit takes the backend compile's place),
    cache hits and misses, and how many backend compiles ran. Copied from
    the program's ``chip_smoke.py``, with the count of compiles added."""

    _SECS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.lap()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def lap(self) -> dict:
        out = dict(getattr(self, "now", {}))
        self.now = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0,
                    "cache_misses": 0}
        return out

    def _duration(self, event, duration, **_):
        if event in self._SECS:
            self.now["compile_s"] += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.now["compiles"] += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.now["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.now["cache_misses"] += 1


def percentile(values, q: float) -> Optional[float]:
    import numpy as np

    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def device_info(devices, peak_bytes: int) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(peak_bytes)}


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def emit(result: dict, checks: dict) -> None:
    """The result as the last stdout line, with every number compared
    beside its limit as the last lines on stderr and as the line's last
    key."""
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAILED'})", file=sys.stderr)
    line = dict(result)
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    sys.stderr.flush()
    print(json.dumps(line, allow_nan=False), flush=True)


def finite(x) -> bool:
    return x is not None and math.isfinite(x)


class Stopwatch:
    def __init__(self):
        self.t = time.perf_counter()

    def lap(self) -> float:
        now = time.perf_counter()
        dt, self.t = now - self.t, now
        return dt
