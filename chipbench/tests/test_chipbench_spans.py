"""The readers of the program's own spans, on a small recorded trace (an
XSpace in text form: one chip, the host's annotations with their stats).

Timeline (microseconds) inside a traced window [0, 1000). Device: a
gather [58, 65), the prefill run [110, 160), decode runs [170, 360) and
[445, 860). Host: ``engine.step`` [50, 400) holding evict [50, 80) (its
fetch [55, 70)), admit [80, 150) (prefill [85, 100), insert [100, 110)),
grow_pages [150, 160), decode_step [160, 170), fetch_metrics [170, 380),
account [380, 395); the benchmark's ``fetch`` [400, 420); ``engine.step``
[420, 900) holding grow_pages [420, 430), decode_step [430, 440),
fetch_metrics [440, 870), account [870, 890); ``idle.wait`` [900, 1000).
"""

import dataclasses
import glob
import os
import re
import types

import pytest
from jax.profiler import ProfileData

from chipbench import harness, spans, trace
from chipbench.costs import qwen3

DATA = os.path.join(os.path.dirname(__file__), "data", "spans.pbtxt")
DEV = "/device:TPU:0"
US = 1e3  # ns


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        pd = ProfileData.from_text_proto(f.read())
    return trace.from_profile(pd, harness.HOST_SPANS), spans.from_profile(pd)


def record(recorded, **kw):
    tr, sp = recorded
    rec = types.SimpleNamespace(trace=tr, program_spans=sp, **kw)
    return rec


def test_spans_read_with_their_args(recorded):
    _, sp = recorded
    assert [s.name for s in sp][:4] == ["engine.evict", "engine.evict_fetch",
                                        "engine.admit", "engine.prefill"]
    admit = spans.named(sp, "engine.admit")[0]
    assert admit.args == {"inst": 5, "prompt": 100, "waited_ms": 2.5}
    assert spans.named(sp, "engine.prefill")[0].args == {
        "padded_len": 128, "prompt": 100}
    assert spans.named(sp, "engine.evict")[0].args == {"insts": "[3]"}
    assert len(spans.named(sp, spans.FETCH)) == 2


def test_engine_host_ms(recorded):
    # step 1: 350 - (15 evict_fetch + 210 fetch) = 125; step 2: 480 - 430
    # = 50; mean 87.5 us
    rec = record(recorded)
    assert harness.metric_reader("engine_host_ms")(rec) == \
        pytest.approx(0.0875)


def test_fetch_wait_ms(recorded):
    # fetch 1 ends 380, its decode run 360: 20; fetch 2 870 - 860 = 10
    assert spans.fetch_waits_ns(*recorded) == [20 * US, 10 * US]
    assert harness.metric_reader("fetch_wait_ms")(record(recorded)) == \
        pytest.approx(0.015)


def test_prefill_mfu(recorded):
    # one prefill of 100 tokens on a 1-layer toy: layer products
    # 2 * 144 * 100 = 28,800, attention 4 * 2 * 2 * 5,050 = 80,800, head
    # 2 * 10 * 4 = 80; 109,680 operations in 50 us at 1e12 = 0.21936%
    sizes = {"d": 4, "h": 2, "kv": 1, "hd": 2, "f": 8, "layers": 1,
             "vocab": 10}
    assert qwen3.prefill_flops(sizes, [100]) == 109_680
    rec = record(recorded, costs=qwen3, sizes=sizes,
                 peak={"bf16_flops_per_s": 1e12})
    assert harness.metric_reader("prefill_mfu")(rec) == \
        pytest.approx(0.21936)


def test_idle_by_span_matches_breakdown(recorded):
    idle = {k: v / 1e-6 for k, v in spans.idle_by_span(*recorded).items()}
    assert idle == pytest.approx({
        "other": 50, "idle.wait": 100, "engine.fetch_metrics": 35,
        "engine.account": 35, "engine.decode_step": 20, "fetch": 20,
        "engine.evict": 15, "engine.prefill": 15, "engine.step": 15,
        "engine.insert": 10, "engine.grow_pages": 10,
        "engine.evict_fetch": 8, "engine.admit": 5})
    s = spans.summary(*recorded)
    assert s["steps"] == 2
    # the breakdown files whole gaps by majority: 58 + 45 + 10 + 85
    assert s["breakdown_idle_engine_step_s"] == pytest.approx(198e-6)
    assert s["idle_in_program_spans_s"] == pytest.approx(153e-6)
    assert s["host_plus_wait_x_steps_s"] == pytest.approx(205e-6)


def test_a_program_without_spans_reads_nothing():
    """The benchmark's recorded trace holds no program span, as a traced
    run of a program older than them: every new metric is left out."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace.pbtxt")) as f:
        pd = ProfileData.from_text_proto(f.read())
    rec = types.SimpleNamespace(
        trace=trace.from_profile(pd, harness.HOST_SPANS),
        program_spans=spans.from_profile(pd))
    assert rec.program_spans == []
    for name in ("engine_host_ms", "fetch_wait_ms", "prefill_mfu"):
        assert harness.metric_reader(name)(rec) is None
        assert harness.metric_reader(name)(
            types.SimpleNamespace(trace=None)) is None


def test_a_missing_span_or_program_raises(recorded):
    tr, sp = recorded
    no_fetch = [s for s in sp if s.name != spans.FETCH]
    with pytest.raises(RuntimeError, match="engine.fetch_metrics"):
        spans.engine_host_ms(tr, no_fetch)
    with pytest.raises(RuntimeError, match="engine.fetch_metrics"):
        spans.fetch_wait_ms(tr, no_fetch)
    no_prefill = [s for s in sp if s.name != "engine.prefill"]
    with pytest.raises(RuntimeError, match="engine.prefill"):
        spans.prefills(tr, no_prefill)
    unnamed = dataclasses.replace(tr, modules={DEV: [
        dataclasses.replace(e, name="jit__lambda_(3)")
        if "_prefill_fn" in e.name else e for e in tr.modules[DEV]]})
    with pytest.raises(RuntimeError, match="no prefill program"):
        spans.prefills(unnamed, sp)
    with pytest.raises(RuntimeError, match="no decode program"):
        spans.fetch_wait_ms(dataclasses.replace(tr, modules={DEV: []}), sp)
    with pytest.raises(RuntimeError, match="engine.step"):
        spans.engine_host_ms(dataclasses.replace(tr, host=[]), sp)


def test_program_spans_keep_off_the_benchmarks_names():
    """No span the program opens shares a name with the benchmark's own
    annotations, whose idle labels and breakdown would change."""
    src = os.path.join(harness.ROOT, "src", "repro")
    names = set()
    for path in glob.glob(os.path.join(src, "**", "*.py"), recursive=True):
        with open(path) as f:
            names.update(re.findall(r'span\(\s*"([^"]+)"', f.read()))
    assert {"engine.admit", "engine.fetch_metrics",
            "train.dispatch"} <= names
    assert not names & set(harness.HOST_SPANS), names & set(
        harness.HOST_SPANS)
