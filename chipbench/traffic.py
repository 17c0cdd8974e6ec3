"""The one traffic generator: an open-loop schedule from a mix's data file.

A mix names its arrival process, its length distributions and its rate.
The schedule has three phases on one clock, each with a fixed count of
requests: ``preroll`` (fills the slots before the window opens),
``window`` (the measured ``--seconds``) and ``drain`` (keeps the load on
after the window closes, while the window's last requests finish).

Every run gets the same prompt lengths, output lengths and arrival times:
the sizes are the distribution's quantiles at ``(i + 0.5) / n`` and the
gaps the arrival process's, each phase's gaps scaled to span the phase
exactly, in an order fixed by the mix's ``schedule_seed``. Which long
request lands in the window moves the window's work by ~10% (PERF.md), so
the run's seed draws only the token ids and labels (and, in the drivers,
the weights).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
from scipy import stats

PHASES = ("preroll", "window", "drain")


@dataclasses.dataclass
class Request:
    index: int
    phase: str
    due: float  # seconds after the schedule's start
    prompt: np.ndarray  # int32 token ids
    max_new: int
    labels: np.ndarray  # int32, one per output position


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, as ints
    within ``[min, max]``."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * stats.norm.ppf(u))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
    elif kind == "fixed":
        x = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", np.inf)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def gaps(arrivals: dict, n: int, span: float) -> np.ndarray:
    """``n`` inter-arrival gaps that sum to ``span`` seconds: the
    stratified quantiles of the arrival process's gap distribution
    (exponential for ``poisson``, gamma of the given coefficient of
    variation for ``gamma``), scaled to the span."""
    u = (np.arange(n) + 0.5) / n
    kind = arrivals["kind"]
    if kind == "poisson":
        g = stats.expon.ppf(u)
    elif kind == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = stats.gamma.ppf(u, shape)
    elif kind == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return g * (span / g.sum())


def phase_counts(mix: dict, seconds: float) -> dict[str, int]:
    rate = float(mix["rate_per_s"])
    spans = phase_spans(mix, seconds)
    return {p: max(1, int(round(rate * spans[p]))) for p in PHASES}


def phase_spans(mix: dict, seconds: float) -> dict[str, float]:
    return {"preroll": float(mix["preroll_s"]), "window": float(seconds),
            "drain": float(mix["drain_s"])}


def schedule(mix: dict, seed_words: list[int], seconds: float,
             vocab: int, counts: Optional[dict] = None) -> list[Request]:
    """The whole open-loop schedule of one run, sorted by due time. The
    order of the sizes and gaps comes from the mix's ``schedule_seed``,
    the same for every run; the run's seed draws the tokens and labels."""
    order = np.random.default_rng(int(mix["schedule_seed"]))
    rng = np.random.default_rng(seed_words)
    spans = phase_spans(mix, seconds)
    counts = counts or phase_counts(mix, seconds)
    out: list[Request] = []
    t0 = 0.0
    for phase in PHASES:
        n = counts[phase]
        plen = order.permutation(quantiles(mix["prompt_len"], n))
        olen = order.permutation(quantiles(mix["output_len"], n))
        g = order.permutation(gaps(mix["arrivals"], n, spans[phase]))
        # the first arrival of a phase lands one gap after its start
        due = t0 + np.cumsum(g)
        for i in range(n):
            out.append(Request(
                index=len(out), phase=phase, due=float(due[i]),
                prompt=rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                max_new=int(olen[i]),
                labels=rng.integers(0, vocab, int(olen[i]), dtype=np.int32),
            ))
        t0 += spans[phase]
    out.sort(key=lambda r: r.due)
    return out


def buckets_used(requests: list[Request], buckets: tuple[int, ...]) -> list[int]:
    """The prefill lengths the schedule needs, given the engine's padding
    buckets (the first bucket at or above each prompt's length)."""
    used = set()
    for r in requests:
        used.add(next(b for b in buckets if b >= r.prompt.size))
    return sorted(used)
