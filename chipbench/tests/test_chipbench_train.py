"""The train driver end to end on the CPU at a tiny size, with the chip
check stepped over: the comparison with the reference passes for the
program, fails for the control (the reference in float8), and fails for
each fault a one-chip training cell can have, planted in the timed path.

The tiny model has its own limits, set from CPU readings of this size
(three seeds: program at most 5.7e-4 / 0.010 / 0.0010 / 6.4e-5 for the
loss, gradient, change and ledger gaps; control at least 2.5e-3 / 0.013 /
0.0024 / 2.5e-4).
"""

import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness

TINY_LIMITS = {"rows_differing": 0, "loss_gap": 0.0015, "grad_gap": 0.05,
               "change_gap": 0.05, "ledger_gap": 2e-4}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads((root / "chipbench/configs/mamba2-370m.json")
                      .read_text())
    conf["name"] = "tiny"
    conf["config"].update(d_model=64, n_layer=2, d_state=16, headdim=16,
                          chunk_size=16, vocab_size=500)
    conf["program"]["set"]["vocab_size"] = 512
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(conf))
    job = json.loads((root / "chipbench/traffic/obftf_recycled.json")
                     .read_text())
    job.update(global_batch=8, seq_len=64, instance_pool=256, trace_s=1,
               limits=TINY_LIMITS)
    (root / "chipbench/traffic/tiny.json").write_text(json.dumps(job))
    bench = harness.benchmark(harness.ROOT)
    bench["configs"] = [{"name": "tiny", "source": "x", "reduced": [],
                         "file": "chipbench/configs/tiny.json", "why": "x"}]
    bench["workloads"] = [{"name": "tiny", "config": "tiny",
                           "traffic": "tiny", "chips": 1, "why": "x"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def run(root, monkeypatch, control=False):
    monkeypatch.setattr(harness, "require_devices",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    cell = harness.Cell(harness.benchmark(root), "tiny", root)
    rec, _, att, failed, checks = cell.driver().run(
        cell, 5, 0.5, False, time.perf_counter(), control=control)
    assert att > 0 and rec.steps
    return rec, checks


def test_program_passes_control_fails(root, monkeypatch):
    rec, checks = run(root, monkeypatch, control=True)
    ctl = checks.pop("control")
    half = checks.pop("half_batch")
    assert checks.pop("readings")["rows_differing"] == 0
    assert all(c["ok"] for c in checks.values()), checks
    assert any(ctl[k] > lim for k, lim in TINY_LIMITS.items()), ctl
    assert any(half[k] > lim for k, lim in TINY_LIMITS.items()), half
    assert ctl["correct"] is False and half["correct"] is False
    assert harness.metric_reader("train_tok_s", root)(rec) > 0


def _state_unchanged(monkeypatch):
    from repro.core import obftf

    monkeypatch.setattr(obftf, "apply_updates", lambda p, u: p)


def _half_batch(monkeypatch):
    """The loss of the kept rows is the mean over half of them."""
    from repro.models import model

    real = model.loss_fn

    def loss_fn(cfg):
        fn = real(cfg)

        def half(params, batch, rng):
            pel = fn(params, batch, rng)
            n = pel.shape[0] // 2
            return jnp.concatenate(
                [pel[:n], jnp.broadcast_to(jnp.mean(pel[:n]), pel[n:].shape)])

        return half

    monkeypatch.setattr(model, "loss_fn", loss_fn)


def _alter_record(monkeypatch):
    from repro.core import device_ledger

    real = device_ledger.record

    def record(cfg, state, ids, losses, step, **kw):
        return real(cfg, state, ids, losses + 0.25, step, **kw)

    monkeypatch.setattr(device_ledger, "record", record)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _alter_record])
def test_faults_are_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    _, checks = run(root, monkeypatch)
    assert not all(c["ok"] for c in checks.values()), checks
