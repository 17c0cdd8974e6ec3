"""The profiler trace of a run's traced sub-window, reduced to what the
metric readers need: device operations and programs with their times, the
busy union per chip, and the idle gaps labelled by what the host was
doing.

The trace is the JAX profiler's ``.xplane.pb``, read with
``jax.profiler.ProfileData``. On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per operation
run and whose line ``XLA Modules`` holds one event per program run; the
host's ``TraceAnnotation`` spans are on the host plane's threads. All
times are nanoseconds on the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
from typing import Iterable

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    dur: float  # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: dict  # device plane name -> [Event] (operations)
    modules: dict  # device plane name -> [Event] (program runs)
    host: list  # [Event] host annotations
    window_ns: tuple  # (start, end) of the traced window

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9


def start(outdir: str) -> None:
    import jax

    shutil.rmtree(outdir, ignore_errors=True)
    os.makedirs(outdir, exist_ok=True)
    jax.profiler.start_trace(outdir)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def op_name(text: str) -> str:
    """An operation's own name from the HLO instruction the trace gives
    as its event name (``%paged_decode_attn.9 = bf16[...] custom-call(...)``
    -> ``paged_decode_attn.9``): its operands name other operations."""
    return text.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda n: n) -> list[Event]:
    return [Event(name(e.name), float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load(outdir: str, host_names: Iterable[str],
         window_name: str = "traced.window") -> Trace:
    """Read the one ``.xplane.pb`` under ``outdir``. The traced window is
    the host span ``window_name``, which the driver opens right after the
    profiler starts and closes right before it stops."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {outdir}, found "
                           f"{len(files)}")
    return from_profile(ProfileData.from_file(files[0]), host_names,
                        window_name)


def from_profile(pd, host_names: Iterable[str],
                 window_name: str = "traced.window") -> Trace:
    """The ``Trace`` of a loaded ``ProfileData``."""
    ops, modules, host = {}, {}, []
    wanted = set(host_names) | {window_name}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line, op_name)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line) if e.name in wanted)
    win = [e for e in host if e.name == window_name]
    if not win:
        raise RuntimeError(f"no {window_name!r} span in the trace")
    if not ops:
        raise RuntimeError("the trace holds no device operations")
    host = [e for e in host if e.name != window_name]
    return Trace(ops, modules, host, (win[0].start, win[0].end))


def clip(events: list[Event], window: tuple) -> list[tuple[float, float]]:
    lo, hi = window
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_s(tr: Trace, plane: str) -> float:
    return sum(t - s for s, t in union(clip(tr.ops[plane], tr.window_ns))) \
        * 1e-9


def gaps(tr: Trace, plane: str) -> list[tuple[float, float]]:
    """Idle intervals of one chip inside the traced window."""
    lo, hi = tr.window_ns
    out, cur = [], lo
    for s, t in union(clip(tr.ops[plane], tr.window_ns)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def segments(tr: Trace) -> list[tuple[float, float, str]]:
    """The host's timeline as disjoint labelled segments: at each moment
    the innermost (latest-started) annotation that is open."""
    bounds = sorted({x for e in tr.host for x in (e.start, e.end)})
    evs = sorted(tr.host, key=lambda e: e.start)
    out, active, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(evs) and evs[i].start <= a:
            active.append(evs[i])
            i += 1
        active = [e for e in active if e.end > a]
        if active:
            out.append((a, b, max(active, key=lambda e: e.start).name))
    return out


def label_gaps(tr: Trace, plane: str) -> list[tuple[float, str]]:
    """Each idle gap of one chip with its length (ns) and the host
    activity that covers most of it (``other`` where none does)."""
    segs = segments(tr)
    out, j = [], 0
    for s, t in gaps(tr, plane):
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        cover: dict = {}
        k = j
        while k < len(segs) and segs[k][0] < t:
            c = min(segs[k][1], t) - max(segs[k][0], s)
            if c > 0:
                cover[segs[k][2]] = cover.get(segs[k][2], 0.0) + c
            k += 1
        name = max(cover, key=cover.get) if cover else "other"
        out.append((t - s, name))
    return out


# operations that hold others (a loop's body runs inside its ``while``):
# left out of the breakdown, which would count their time twice
CONTAINERS = ("while", "conditional", "call")


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time and the longest idle
    gaps by host activity, over the chips used (summed)."""
    by_op: dict = {}
    by_gap: dict = {}
    for plane, evs in tr.ops.items():
        for s, t, name in ((max(e.start, tr.window_ns[0]),
                            min(e.end, tr.window_ns[1]), e.name)
                           for e in evs
                           if e.name.split(".")[0] not in CONTAINERS):
            if t > s:
                by_op[name] = by_op.get(name, 0.0) + (t - s) * 1e-9
        for dur, k in label_gaps(tr, plane):
            by_gap[k] = by_gap.get(k, 0.0) + dur * 1e-9
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(by_gap.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in top_gaps]}


def find(tr: Trace, plane: str, match, what: str) -> list[Event]:
    """The operations ``match`` accepts; none at all is an error, so that
    a renamed kernel or program never reads as zero work."""
    evs = [e for e in tr.ops[plane] if match(e.name)]
    if not evs:
        names = sorted({e.name for e in tr.ops[plane]})[:40]
        raise RuntimeError(f"no {what} in the trace of {plane}; "
                           f"operations seen include {names}")
    return evs
