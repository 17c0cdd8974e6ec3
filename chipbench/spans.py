"""The program's own spans in a traced run, on the profiler's clock.

The engine opens a span at every phase of ``Engine.step`` (``repro.obs``):
``engine.evict`` (over ``engine.evict_fetch`` and ``engine.clear``),
``engine.admit`` (over ``engine.prefill`` and ``engine.insert``),
``engine.grow_pages``, ``engine.decode_step``, ``engine.fetch_metrics``
and ``engine.account``, and ``engine.deliver`` for late labels. Each is a
``jax.profiler.TraceAnnotation`` whose arguments are the event's stats.
This module reads them, arguments and all, from the run's own
``.xplane.pb`` and keeps them on the run record; the harness's
``trace.load`` keeps only the benchmark's own annotations.

A program that opens none of these spans (one older than them) reads as
``None``, and its metrics are left out of the result line. A program that
opens them but lacks one a reader needs is an error: a renamed span never
reads as zero.

    python3 -m chipbench.spans [--workload qwen3-decode]

prints, for the last traced run of a cell, the device's idle time split
by the span the host was in, beside the breakdown's idle under
``engine.step`` and ``(engine_host_ms + fetch_wait_ms) x steps``.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import json
import os
import sys
from typing import Optional

from chipbench import harness, trace
from chipbench.drivers import serve

PREFIX = "engine."
STEP = "engine.step"  # the benchmark's annotation around Engine.step
FETCH = "engine.fetch_metrics"
# spans in which the host waits on a transfer from the device
WAITS = (FETCH, "engine.evict_fetch")
# the engine's programs by XLA module name
PROGRAMS = {**serve.PROGRAMS, "prefill": lambda n: "_prefill_fn" in n}


@dataclasses.dataclass
class Span(trace.Event):
    args: dict = dataclasses.field(default_factory=dict)


def trace_dir(workload: str) -> str:
    return os.path.join(serve.OUT, "trace", workload)


def from_profile(pd) -> list[Span]:
    """Every program span of a loaded ``ProfileData``, by start (the
    benchmark's own ``engine.step`` is not one)."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(Span(e.name, float(e.start_ns),
                                float(e.duration_ns), dict(e.stats))
                           for e in line.events
                           if e.name.startswith(PREFIX)
                           and e.name not in harness.HOST_SPANS)
    return sorted(out, key=lambda s: s.start)


def read_dir(outdir: str) -> list[Span]:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {outdir}, found "
                           f"{len(files)}")
    return from_profile(ProfileData.from_file(files[0]))


def load(rec) -> Optional[list[Span]]:
    """The program spans of a traced run, read once and kept on the
    record; ``None`` for an untraced run or a program that opens none."""
    if rec.trace is None:
        return None
    if not hasattr(rec, "program_spans"):
        rec.program_spans = read_dir(trace_dir(rec.cell.name))
    return rec.program_spans or None


def named(spans: list[Span], name: str) -> list[Span]:
    out = [s for s in spans if s.name == name]
    if not out:
        raise RuntimeError(f"no {name!r} span in the trace; the program's "
                           f"spans seen: {sorted({s.name for s in spans})}")
    return out


def _inside(e, lo: float, hi: float) -> bool:
    return lo <= e.start and e.end <= hi


def steps(tr: trace.Trace) -> list[trace.Event]:
    """The benchmark's ``engine.step`` annotations wholly inside the
    traced window."""
    out = [e for e in tr.host
           if e.name == STEP and _inside(e, *tr.window_ns)]
    if not out:
        raise RuntimeError(f"no {STEP!r} annotation inside the traced "
                           "window")
    return out


def engine_host_ms(tr: trace.Trace, spans: list[Span]) -> float:
    """Mean over the window's engine steps of the step's host time that
    waits on no transfer: the ``engine.step`` annotation less the
    ``engine.fetch_metrics`` and ``engine.evict_fetch`` spans inside it."""
    waits = [s for s in spans if s.name in WAITS]
    fetched = 0
    host = []
    for st in steps(tr):
        inner = [w for w in waits if _inside(w, st.start, st.end)]
        fetched += sum(w.name == FETCH for w in inner)
        host.append(st.dur - sum(w.dur for w in inner))
    if not fetched:
        raise RuntimeError(f"no {FETCH!r} span inside an {STEP!r} "
                           "annotation of the window")
    return sum(host) / len(host) * 1e-6


def _runs(tr: trace.Trace, kind: str) -> dict:
    """Each chip's runs of one of the engine's programs, by start."""
    out = {p: sorted((e for e in tr.modules.get(p, [])
                      if PROGRAMS[kind](e.name)), key=lambda e: e.start)
           for p in tr.ops}
    for plane, runs in out.items():
        if not runs:
            raise RuntimeError(f"no {kind} program in the trace of {plane}")
    return out


def fetch_waits_ns(tr: trace.Trace, spans: list[Span]) -> list[float]:
    """For each ``engine.fetch_metrics`` span wholly inside the window:
    its end less the end of the decode run it waited on, the last to
    start before the fetch returned (on several chips, the latest such
    end)."""
    runs = _runs(tr, "decode")
    starts = {p: [e.start for e in r] for p, r in runs.items()}
    out = []
    for f in named(spans, FETCH):
        if not _inside(f, *tr.window_ns):
            continue
        ends = []
        for p, r in runs.items():
            i = bisect.bisect_left(starts[p], f.end) - 1
            if i < 0:
                raise RuntimeError(f"no decode run before the fetch at "
                                   f"{f.start} ns on {p}")
            ends.append(r[i].end)
        out.append(f.end - max(ends))
    if not out:
        raise RuntimeError(f"no {FETCH!r} span inside the traced window")
    return out


def fetch_wait_ms(tr: trace.Trace, spans: list[Span]) -> float:
    w = fetch_waits_ns(tr, spans)
    return sum(w) / len(w) * 1e-6


def prefills(tr: trace.Trace, spans: list[Span]) -> list[tuple[Span, float]]:
    """Each ``engine.prefill`` span that starts in the window, with the
    device seconds of the prefill run it dispatched (the first to start
    after it, on each chip), where that run ends in the window."""
    lo, hi = tr.window_ns
    pre = [s for s in spans if s.name == "engine.prefill" and s.start >= lo]
    if not pre:
        if any(s.name == "engine.admit" and s.start >= lo for s in spans):
            raise RuntimeError("admissions in the window, but no "
                               "'engine.prefill' span")
        return []
    out = []
    for runs in _runs(tr, "prefill").values():
        j = 0
        for s in pre:
            while j < len(runs) and runs[j].start < s.start:
                j += 1
            if j == len(runs):
                break
            if runs[j].end <= hi:
                out.append((s, runs[j].dur * 1e-9))
            j += 1
    return out


def idle_by_span(tr: trace.Trace, spans: list[Span]) -> dict:
    """Device idle seconds inside the traced window by the innermost span
    the host was in (the benchmark's annotations and the program's),
    summed over chips; ``other`` where the host was in none."""
    # of spans that open together the innermost (the shortest) is
    # listed first, which is the one ``trace.segments`` keeps
    host = sorted(spans, key=lambda s: (s.start, s.end)) + list(tr.host)
    segs = trace.segments(dataclasses.replace(tr, host=host))
    out: dict = {}
    for plane in tr.ops:
        j = 0
        for s, t in trace.gaps(tr, plane):
            while j < len(segs) and segs[j][1] <= s:
                j += 1
            covered, k = 0.0, j
            while k < len(segs) and segs[k][0] < t:
                a, b, name = segs[k]
                c = min(b, t) - max(a, s)
                if c > 0:
                    out[name] = out.get(name, 0.0) + c * 1e-9
                    covered += c
                k += 1
            out["other"] = out.get("other", 0.0) + (t - s - covered) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def summary(tr: trace.Trace, spans: list[Span]) -> dict:
    """The three readings of the idle inside ``engine.step`` for one
    traced run, side by side."""
    n = len(steps(tr))
    host, wait = engine_host_ms(tr, spans), fetch_wait_ms(tr, spans)
    idle = idle_by_span(tr, spans)
    gaps = dict(trace.breakdown(tr, top=100)["idle_gaps"])
    return {
        "window_s": tr.window_s, "steps": n,
        "engine_host_ms": host, "fetch_wait_ms": wait,
        "host_plus_wait_x_steps_s": (host + wait) * n * 1e-3,
        "breakdown_idle_engine_step_s": gaps.get(STEP, 0.0),
        "idle_in_program_spans_s": sum(v for k, v in idle.items()
                                       if k.startswith(PREFIX)
                                       and k != STEP),
        "idle_by_span_s": idle,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="qwen3-decode")
    outdir = trace_dir(ap.parse_args(argv).workload)
    tr = trace.load(outdir, harness.HOST_SPANS)
    print(json.dumps(summary(tr, read_dir(outdir)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
