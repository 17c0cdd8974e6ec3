"""The engine's own spans on the profiler's clock, and its programs by name.

A tiny paged engine runs under a JAX profiler session on the CPU with
telemetry disabled (what a benchmark or a deployment without
``--trace-out`` gets). Every phase of ``Engine.step`` must land on the
host plane as a span, nested and in order, carrying the arguments that tie
spans to a request. The programs the engine jits must lower to XLA
modules whose names tell them apart: a profile finds the prefill, the
label delivery and the decode step by module name.
"""

import ast
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs, obs
from repro.core.history import HistoryConfig
from repro.models import model as Mdl
from repro.models.params import materialize
from repro.serving import Engine, OutcomeRecorder

CFG = configs.get_smoke("llama3-8b")
LCFG = HistoryConfig(capacity=1 << 12, decay=0.8)
# (prompt length, max_new): three requests through two slots, so the
# third admits only after an eviction; the first outgrows its prompt's pages
SCHEDULE = ((7, 4), (9, 3), (3, 2))


@pytest.fixture(scope="module")
def params():
    return materialize(
        Mdl.param_specs(CFG), jax.random.key(0), jnp.dtype(CFG.param_dtype)
    )


def make_engine(params):
    rec = OutcomeRecorder(2, 4, CFG.vocab_size, LCFG, ledger="device")
    return Engine(CFG, params, rec, slots=2, max_prompt=16, max_gen=4,
                  page_size=4)


def drive(engine, seed):
    """Serve SCHEDULE with labels delivered one step after admission."""
    rs = np.random.default_rng(seed)
    ids = [engine.submit(rs.integers(0, CFG.vocab_size, n), max_new=g,
                         expect_labels=True) for n, g in SCHEDULE]
    delivered = set()
    while engine.in_flight_ids() or engine._queue:
        engine.step()
        for iid in engine.in_flight_ids():
            if iid not in delivered:
                engine.deliver_outcome(iid, rs.integers(0, CFG.vocab_size, 4))
                delivered.add(iid)
    return ids


@pytest.fixture(scope="module")
def profiled(params, tmp_path_factory):
    """(program spans in start order, the ids served, the pages occupied
    slots held at each decode dispatch) of one profiled pass; a first pass
    compiles every program outside the profile."""
    assert obs.current().enabled is False
    engine = make_engine(params)
    drive(engine, 0)
    held, decode = [], engine._decode

    def counted_decode(*args):
        held.append(sum(len(engine._slot_pages[s])
                        for s in engine._slot_of.values()))
        return decode(*args)
    engine._decode = counted_decode
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        ids = drive(engine, 1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name,
                     dict(e.stats))
                    for e in line.events if e.name.startswith("engine."))
    return sorted(spans), ids, held


def inside(child, parent):
    return parent[0] <= child[0] and child[1] <= parent[1]


def test_spans_nest_under_their_phase(profiled):
    spans, *_ = profiled
    names = {s[2] for s in spans}
    assert names >= {
        "engine.evict", "engine.evict_fetch", "engine.clear",
        "engine.admit", "engine.prefill", "engine.insert",
        "engine.grow_pages", "engine.decode_step", "engine.fetch_metrics",
        "engine.account", "engine.deliver",
    }, names
    parent = {"engine.evict_fetch": "engine.evict",
              "engine.clear": "engine.evict",
              "engine.prefill": "engine.admit",
              "engine.insert": "engine.admit"}
    for s in spans:
        if s[2] in parent:
            assert any(p[2] == parent[s[2]] and inside(s, p)
                       for p in spans), s
    for a in (s for s in spans if s[2] == "engine.admit"):
        kids = [s[2] for s in spans if s is not a and inside(s, a)]
        assert kids == ["engine.prefill", "engine.insert"], kids


def test_step_phases_run_in_order(profiled):
    """Outside label delivery (the caller's, between steps), the top-level
    spans read: eviction, admissions, page growth, the decode dispatch,
    the blocking fetch of its metrics, the host accounting."""
    spans, *_ = profiled
    top = [s[2][len("engine."):] for s in spans
           if s[2] != "engine.deliver"
           and not any(p is not s and inside(s, p) for p in spans)]
    seq = " ".join(top) + " "
    step = r"(evict )?(admit )*grow_pages decode_step fetch_metrics account "
    assert re.fullmatch(f"({step})+(evict )?", seq), seq
    assert re.search(r"evict (admit )+grow_pages", seq), seq


def test_spans_carry_the_request(profiled):
    spans, ids, _ = profiled
    admits = [s[3] for s in spans if s[2] == "engine.admit"]
    assert [a["inst"] for a in admits] == ids
    assert [a["prompt"] for a in admits] == [n for n, _ in SCHEDULE]
    assert all(a["waited_ms"] >= 0.0 for a in admits)
    prefills = [s[3] for s in spans if s[2] == "engine.prefill"]
    assert [p["prompt"] for p in prefills] == [n for n, _ in SCHEDULE]
    assert [p["padded_len"] for p in prefills] == [8, 16, 8]
    evicted = [i for s in spans if s[2] == "engine.evict"
               for i in ast.literal_eval(str(s[3]["insts"]))]
    assert sorted(evicted) == sorted(ids)
    delivered = [s[3]["inst"] for s in spans if s[2] == "engine.deliver"]
    assert sorted(delivered) == sorted(ids)


def test_decode_step_carries_the_pages_held(profiled):
    """``engine.decode_step`` carries ``pages``, the pages held by the
    occupied slots at the dispatch (what the paged kernel walks), beside
    ``occupied``."""
    spans, _, held = profiled
    steps = [s[3] for s in spans if s[2] == "engine.decode_step"]
    assert [int(s["pages"]) for s in steps] == held
    assert len(set(held)) > 1 and min(held) > 0, held
    assert all(int(s["occupied"]) >= 1 for s in steps)


def _module_name(jitted, args) -> str:
    text = jitted.lower(*args).as_text()
    return re.search(r"module @(\S+)", text).group(1)


def test_programs_lower_to_distinct_names(params):
    """Each program the engine dispatches, lowered with the arguments of
    a real call: prefill, insert, deliver, grow and clear read apart from
    one another and from the decode step."""
    engine = make_engine(params)
    seen = {}

    def spy(attr):
        jitted = getattr(engine, attr)

        def call(*args):
            seen.setdefault(attr, (jitted, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                if isinstance(x, jax.Array) else x, args)))
            return jitted(*args)
        setattr(engine, attr, call)

    for attr in ("_insert", "_deliver", "_grow_jit", "_clear_jit",
                 "_decode"):
        spy(attr)
    prefill = engine._prefill

    def prefill_spy(padded_len):
        seen.setdefault("_prefill", (prefill(padded_len), None))
        return prefill(padded_len)
    engine._prefill = prefill_spy
    drive(engine, 0)
    jitted, _ = seen.pop("_prefill")
    names = {"_prefill": _module_name(jitted, (
        engine.params, jax.ShapeDtypeStruct((1, 8), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32)))}
    names.update({k: _module_name(j, a) for k, (j, a) in seen.items()})
    assert "_fused_step" in names.pop("_decode")
    assert len(set(names.values())) == 5, names
    for name in names.values():
        assert "lambda" not in name and "_fused_step" not in name, names
    assert "_prefill_fn" in names["_prefill"]
    assert "_deliver_fn" in names["_deliver"]
