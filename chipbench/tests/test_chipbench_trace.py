"""The trace reduction on a small recorded trace (an XSpace in text form
with the planes and lines a TPU run writes): busy union, idle share, idle
gaps labelled by host activity, program and kernel seconds, and a loud
failure when a name the readers look for is missing.

Timeline (microseconds) of the one chip: ops at [100, 300), [300, 400),
[450, 600), [650, 700) inside a traced window [0, 1000); the host is in
engine.step over [50, 720), fetch [720, 800), idle.wait [800, 1000)."""

import os
import types

import pytest
from jax.profiler import ProfileData

from chipbench import harness, trace
from chipbench.drivers import serve

DATA = os.path.join(os.path.dirname(__file__), "data", "trace.pbtxt")
DEV = "/device:TPU:0"


@pytest.fixture(scope="module")
def tr():
    with open(DATA) as f:
        pd = ProfileData.from_text_proto(f.read())
    return trace.from_profile(pd, harness.HOST_SPANS)


def test_window_and_busy(tr):
    assert tr.window_s == pytest.approx(1e-3)
    assert [e.name for e in tr.host] == ["engine.step", "fetch", "idle.wait"]
    assert trace.busy_s(tr, DEV) == pytest.approx(500e-6)
    rec = types.SimpleNamespace(trace=tr)
    assert harness.metric_reader("idle_share.serve")(rec) == \
        pytest.approx(50.0)


def test_gaps_by_host_activity(tr):
    assert trace.gaps(tr, DEV) == [(0, 100e3), (400e3, 450e3),
                                   (600e3, 650e3), (700e3, 1000e3)]
    labels = trace.label_gaps(tr, DEV)
    assert labels == [(100e3, "engine.step"), (50e3, "engine.step"),
                      (50e3, "engine.step"), (300e3, "idle.wait")]
    b = trace.breakdown(tr)
    assert b["idle_gaps"][0] == ["idle.wait", pytest.approx(300e-6)]
    assert b["idle_gaps"][1] == ["engine.step", pytest.approx(200e-6)]
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(200e-6)]


def test_programs_and_kernels(tr, monkeypatch):
    rec = types.SimpleNamespace(trace=tr)
    assert serve.program_seconds(rec, "decode") == pytest.approx(500e-6)
    assert serve.kernel_seconds(rec, "paged_attn") == pytest.approx(100e-6)
    assert serve.kernel_seconds(rec, "topk_lse") == pytest.approx(150e-6)
    monkeypatch.setitem(serve.PROGRAMS, "prefill", lambda n: "lambda" in n)
    with pytest.raises(RuntimeError, match="prefill"):
        serve.program_seconds(rec, "prefill")
