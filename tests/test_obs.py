"""Telemetry layer: registry/trace/event units, the engine's
transfer-freedom contract, loop health, drift oracle, CLI acceptance.

The load-bearing guarantee is that instrumentation NEVER adds a device
sync: instruments update exclusively from the step's single
already-fetched numpy metrics dict. The regression test here drives a
fully-instrumented engine (the fused step already runs under
``jax.transfer_guard("disallow")``), then replays ``_obs_on_step`` /
``loop_health`` / ``snapshot`` inside an explicit disallow guard — any
jax.Array sneaking into the telemetry path raises.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs, obs
from repro.core.history import HistoryConfig
from repro.models import model as Mdl
from repro.models.params import materialize
from repro.serving import Engine, OutcomeRecorder

CFG = configs.get_smoke("llama3-8b")
LCFG = HistoryConfig(capacity=1 << 12, decay=0.8)

ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/tmp"}
if "JAX_PLATFORMS" in os.environ:
    ENV["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
CWD = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def params():
    return materialize(
        Mdl.param_specs(CFG), jax.random.key(0), jnp.dtype(CFG.param_dtype)
    )


def make_engine(params, telem, *, slots=4, max_gen=6, ledger="device"):
    rec = OutcomeRecorder(slots, max_gen, CFG.vocab_size, LCFG,
                          ledger=ledger)
    return Engine(CFG, params, rec, slots=slots, max_prompt=16,
                  max_gen=max_gen, telemetry=telem)


def drive(engine, n=9, max_gen=6, seed=0):
    rs = np.random.default_rng(seed)
    for _ in range(n):
        plen = int(rs.integers(3, 17))
        gen = int(rs.integers(2, max_gen + 1))
        engine.submit(rs.integers(0, CFG.vocab_size, plen), max_new=gen,
                      labels=rs.integers(0, CFG.vocab_size, gen))
    engine.run(max_steps=2000)


# ---------------------------------------------------------------------------
# registry / events / trace units
# ---------------------------------------------------------------------------


def test_registry_instruments():
    reg = obs.MetricsRegistry()
    c = reg.counter("reqs", path="admit")
    c.inc()
    c.inc(4)
    assert reg.counter("reqs", path="admit") is c  # get-or-create
    g = reg.gauge("occupancy")
    g.set(0.5)
    g.set(0.75)
    h = reg.histogram("lat_ms", bounds=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["reqs{path=admit}"] == 5
    assert snap["gauges"]["occupancy"] == 0.75
    hs = snap["histograms"]["lat_ms"]
    assert hs["count"] == 3 and hs["buckets"]["le_1"] == 1
    assert hs["buckets"]["inf"] == 1


def test_null_instrument_and_disabled_telemetry():
    t = obs.Telemetry(enabled=False)
    assert t.counter("x") is obs.NULL_INSTRUMENT
    assert t.gauge("x") is t.histogram("x")  # same shared null object
    t.counter("x").inc(3)
    t.gauge("x").set(1.0)
    assert t.snapshot() == {}
    # spans stay on: a profiler annotation alone, no Chrome JSON buffer
    assert isinstance(t.span("s", k=1), jax.profiler.TraceAnnotation)
    with t.span("s"):
        pass
    assert t.trace is None
    t.event("never", x=1)
    t.close(summary={"unused": True})  # no outputs: must be a no-op


def test_event_log_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    log = obs.EventLog(path)
    log.write("loop_health", steps=3, rate=0.5)
    log.write("summary", done=True)
    log.close()
    with open(path, "a") as f:
        f.write('{"torn')  # crash mid-write: reader must tolerate it
    rows = obs.read_jsonl(path)
    assert [r["kind"] for r in rows] == ["loop_health", "summary"]
    assert rows[0]["seq"] == 0 and rows[1]["seq"] == 1
    assert rows[0]["steps"] == 3


def test_trace_recorder_save_load(tmp_path):
    tr = obs.TraceRecorder()
    with tr.span("outer", cat="test", k=1):
        with tr.span("inner", cat="test"):
            pass
    with tr.span("marker", cat="test"):
        pass
    path = str(tmp_path / "t.json")
    tr.save(path)
    events = obs.load_trace(path)
    names = [e["name"] for e in events]
    assert set(names) == {"outer", "inner", "marker"}
    for e in events:
        assert {"ph", "name", "cat", "ts", "pid", "tid"} <= set(e)
    inner = next(e for e in events if e["name"] == "inner")
    outer = next(e for e in events if e["name"] == "outer")
    assert outer["dur"] >= inner["dur"]
    assert outer["args"] == {"k": 1}


def test_trace_recorder_bounded(tmp_path):
    tr = obs.TraceRecorder(max_events=4)
    for i in range(10):
        with tr.span(f"e{i}"):
            pass
    path = str(tmp_path / "t.json")
    tr.save(path)
    with open(path) as f:
        doc = json.load(f)
    assert len(doc["traceEvents"]) == 4
    # oldest kept (a truncated trace should show the run's head, with the
    # drop count in otherData)
    assert [e["name"] for e in doc["traceEvents"]] == ["e0", "e1", "e2", "e3"]
    assert doc["otherData"]["dropped_events"] == 6


def test_ledger_spans_open_on_host_calls_only():
    """A ledger op dispatched from host Python opens one span; the same op
    traced inside a jit (as the fused engine and train steps call it)
    opens none — the span would time the trace, not the run."""
    from jax.sharding import Mesh

    from repro.core import device_ledger as dledger
    from repro.distributed.ledger import sharded_ledger_ops

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    ops = sharded_ledger_ops(mesh, LCFG, ("data",), route=True)
    state = ops.init()
    ids = jnp.arange(4, dtype=jnp.int32)
    losses = jnp.ones((4,), jnp.float32)
    telem = obs.Telemetry(trace_out="unused.json")
    prev = obs.current()
    obs.install(telem)
    try:
        state = ops.record(state, ids, losses, 1)
        jax.jit(lambda st, i, l: ops.record(st, i, l, 2))(state, ids, losses)
        jax.jit(lambda st, i: ops.lookup(st, i))(state, ids)
    finally:
        obs.install(prev)
    assert [e["name"] for e in telem.trace.events] == ["ledger.record"]
    assert isinstance(state, dledger.LedgerState)


def test_rate_of_and_drift_helpers():
    assert obs.rate_of(3, 4) == 0.75
    assert obs.rate_of(3, 0) == 0.0  # empty denominator, not a crash
    sd = {"owner": np.array([1, 2, -1]), "ema": np.ones(3),
          "sig": np.ones((3, 2))}
    d = obs.ledger_drift(sd, {k: v.copy() for k, v in sd.items()},
                         ("entropy", "margin"))
    assert d["slots_compared"] == 2
    assert d["ema"] == 0.0 and d["entropy"] == 0.0 and d["margin"] == 0.0


# ---------------------------------------------------------------------------
# engine integration: counters, health, drift, transfer freedom
# ---------------------------------------------------------------------------


def test_engine_counters_match_stats(params):
    telem = obs.Telemetry(enabled=True)
    eng = make_engine(params, telem)
    drive(eng)
    stats = eng.stats()
    snap = telem.snapshot()
    assert snap["counters"]["engine.steps"] == stats["steps"]
    assert snap["counters"]["engine.generated_tokens"] == \
        stats["generated_tokens"]
    assert snap["counters"]["engine.admitted"] == stats["admitted"]
    assert snap["counters"]["engine.evicted"] == stats["evicted"]
    # host-accumulated record counter agrees with the device counter
    assert snap["counters"]["engine.ledger_records"] == stats["recorded"]
    assert snap["histograms"]["engine.step_ms"]["count"] == stats["steps"]


def test_step_ms_times_the_whole_step(params):
    """``engine.step_ms`` is the wall time of all of ``Engine.step``:
    eviction and admission count, not just decode and fetch."""
    telem = obs.Telemetry(enabled=True)
    eng = make_engine(params, telem)
    admit = eng._admit_inner

    def slow_admit(req):
        time.sleep(0.05)
        admit(req)
    eng._admit_inner = slow_admit
    drive(eng, n=2)
    h = telem.snapshot()["histograms"]["engine.step_ms"]
    assert h["count"] == eng.steps_run
    assert h["max"] >= 50.0


def test_loop_health_rates_and_drift(params):
    telem = obs.Telemetry(enabled=True)
    eng = make_engine(params, telem)
    drive(eng)
    h = eng.loop_health(drift=True)
    assert h["steps"] == eng.steps_run
    assert h["occupancy"] == 0.0 and h["queue_depth"] == 0  # drained
    assert h["records_per_step"] > 0
    assert 0.0 <= h["missed_outcome_rate"] <= 1.0
    # the host shadow oracle replayed the same rows the fused step
    # recorded on device: per-channel EMA drift at FMA-level rounding
    d = h["ledger_drift"]
    assert d["slots_compared"] > 0
    for ch in ("ema", "entropy", "margin"):
        assert d[ch] < 1e-4, d


def test_telemetry_path_is_transfer_free(params):
    """The contract pinned: every per-step telemetry update runs off
    already-fetched numpy metrics, so it must survive an explicit
    transfer_guard("disallow") — on top of the fused decode step itself
    already running under one inside the engine."""
    telem = obs.Telemetry(enabled=True)
    eng = make_engine(params, telem)
    drive(eng)
    metrics = eng._last_metrics
    assert metrics is not None
    with jax.transfer_guard("disallow"):
        eng._obs_on_step(metrics, 1.0)
        eng.loop_health(drift=False)  # drift=True is the documented fetch
        telem.snapshot()


def test_disabled_telemetry_default(params):
    eng = make_engine(params, None)  # no telemetry handed in
    drive(eng, n=4)
    assert eng.telemetry.enabled is False
    assert eng.stats()["steps"] > 0  # instruments were nulls, loop ran


# ---------------------------------------------------------------------------
# CLI acceptance: the drivers' --metrics-out / --trace-out / --json-out
# ---------------------------------------------------------------------------


def _run(args, timeout=600):
    return subprocess.run(
        [sys.executable, "-m", *args],
        capture_output=True, text=True, encoding="utf-8", errors="replace",
        timeout=timeout, env=ENV, cwd=CWD,
    )


def test_serve_cli_telemetry(tmp_path):
    mpath = str(tmp_path / "m.jsonl")
    tpath = str(tmp_path / "t.json")
    jpath = str(tmp_path / "s.json")
    r = _run([
        "repro.launch.serve", "--arch", "qwen3-14b", "--smoke",
        "--batch", "4", "--prompt-len", "8", "--gen", "4",
        "--ledger", "device", "--metrics-out", mpath, "--trace-out", tpath,
        "--metrics-every", "5", "--json-out", jpath,
    ])
    assert r.returncode == 0, r.stdout + r.stderr
    rows = obs.read_jsonl(mpath)
    kinds = [row["kind"] for row in rows]
    assert kinds.count("loop_health") >= 1 and kinds[-1] == "summary"
    health = next(row for row in rows if row["kind"] == "loop_health")
    assert health["records_per_step"] > 0
    assert health["ledger_drift"]["ema"] < 1e-4
    summary = rows[-1]
    with open(jpath) as f:
        js = json.load(f)
    # ONE summary: the final event and --json-out carry the same snapshot
    assert summary["steps"] == js["steps"]
    assert js["health"]["steps"] == js["steps"]
    assert js["metrics"]["counters"]["engine.steps"] == js["steps"]
    names = {e["name"] for e in obs.load_trace(tpath)}
    assert {"engine.admit", "engine.prefill", "engine.decode_step",
            "engine.fetch_metrics", "engine.evict_fetch"} <= names


def test_train_cli_telemetry(tmp_path):
    mpath = str(tmp_path / "m.jsonl")
    tpath = str(tmp_path / "t.json")
    jpath = str(tmp_path / "s.json")
    r = _run([
        "repro.launch.train", "--arch", "llama3-8b", "--smoke",
        "--steps", "8", "--global-batch", "8", "--seq-len", "32",
        "--ratio", "0.25", "--recycle", "--ledger", "device",
        "--instance-pool", "32", "--log-every", "4",
        "--metrics-out", mpath, "--trace-out", tpath,
        "--metrics-every", "4", "--json-out", jpath,
    ])
    assert r.returncode == 0, r.stdout + r.stderr
    rows = obs.read_jsonl(mpath)
    kinds = [row["kind"] for row in rows]
    assert "loop_health" in kinds and kinds[-1] == "summary"
    health = next(row for row in rows if row["kind"] == "loop_health")
    assert health["steps"] > 0
    assert 0.0 <= health["step_cost_savings"] <= 1.0
    with open(jpath) as f:
        js = json.load(f)
    assert js["steps"] == 8
    # recycled OBFTF at r=0.25: 3rC = 0.75C -> savings 0.75
    assert abs(js["step_cost_savings"] - 0.75) < 1e-6
    assert js["metrics"]["counters"]["trainer.steps"] == 8
    names = {e["name"] for e in obs.load_trace(tpath)}
    assert {"train.dispatch", "train.fetch_metrics"} <= names
