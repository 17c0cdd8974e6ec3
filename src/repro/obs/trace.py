"""Hot-path timing spans, on the JAX profiler's clock and, with
``--trace-out``, as Chrome ``trace_event`` JSON (Perfetto-loadable).

Every span opens a ``jax.profiler.TraceAnnotation`` named for it, its
keyword arguments attached as the event's stats. While a JAX profiler
session runs (``jax.profiler.start_trace``), the span therefore lands on
the profiler's host plane, on the same clock as the device's programs and
operations, so a gap on the device can be read against what the host was
doing in it. Without a session an annotation costs well under a
microsecond to open and close.

A :class:`TraceRecorder` (``--trace-out``) also keeps one complete
("ph": "X") event per span, timed on ``time.perf_counter``;
``TraceRecorder.save`` writes the standard ``{"traceEvents": [...]}``
envelope that chrome://tracing and https://ui.perfetto.dev open directly.

Spans measure HOST wall time at dispatch granularity: a span around a
jitted call times enqueue + (on sync) completion. Spans must never run
inside ``jax.trace``-d code — a traced span would record compile-time
once and nothing at run time; call sites that can be traced (the sharded
ledger ops) guard with a tracer check and take :data:`NULL_SPAN` instead.

Thread-safe appends (the checkpoint save thread emits spans).
"""

from __future__ import annotations

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation


class _NullSpan:
    """Reusable no-op context manager for code being traced by JAX."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class Span:
    """A profiler annotation that also appends its Chrome JSON event to
    a :class:`TraceRecorder` when it closes."""

    __slots__ = ("_rec", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, rec: "TraceRecorder", name: str, cat: str, args: dict):
        self._rec = rec
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._rec._complete(
            self.name, self.cat, self._t0, time.perf_counter(), self.args
        )
        self._ann.__exit__(*exc)
        return False


class TraceRecorder:
    """In-memory trace_event buffer, bounded to ``max_events`` (oldest
    kept: the interesting part of a runaway run is usually the start —
    warmup, compiles, first admissions — and a bound keeps --trace-out
    safe to leave on)."""

    def __init__(self, max_events: int = 200_000):
        self.max_events = max_events
        self.events: list[dict] = []
        self.dropped = 0
        self._epoch = time.perf_counter()
        self._lock = threading.Lock()

    def _complete(self, name, cat, t0, t1, args) -> None:
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        ev = {
            "ph": "X",
            "name": name,
            "cat": cat,
            "ts": (t0 - self._epoch) * 1e6,  # trace_event ts unit: us
            "dur": (t1 - t0) * 1e6,
            "pid": os.getpid(),
            "tid": threading.get_ident() & 0x7FFFFFFF,
        }
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def span(self, name: str, cat: str = "host", **args) -> Span:
        return Span(self, name, cat, args)

    def save(self, path: str) -> None:
        with self._lock, open(path, "w", encoding="utf-8") as f:
            json.dump(
                {
                    "traceEvents": self.events,
                    "displayTimeUnit": "ms",
                    "otherData": {"dropped_events": self.dropped},
                },
                f,
            )


def load_trace(path: str) -> list[dict]:
    """The saved trace's event list (test/consumer helper)."""
    with open(path, encoding="utf-8") as f:
        return json.load(f)["traceEvents"]


__all__ = ["NULL_SPAN", "Span", "TraceRecorder", "load_trace"]
