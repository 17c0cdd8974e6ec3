"""The serve driver end to end on the CPU at a tiny size, with the chip
check stepped over: the comparison passes for the program, fails for the
control (the reference in float8), and fails for each fault a serve cell
can have, planted in the timed path.

The tiny model has its own limits, set from CPU readings of this size
(program at most 0.037 for the token gap over five runs and 0.011 for the
mean ledger loss gap over six; control at least 0.15 and 0.045). The
widest loss gap and the EMA gap are not compared, as in the cell: their
two readings lie too close. The mean loss gap is, so that an altered
ledger record is caught.
"""

import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness

TINY_LIMITS = {"token_gap": 0.1, "loss_gap_mean": 0.025}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads((root / "chipbench/configs/qwen3-14b-8layer.json")
                      .read_text())
    conf["name"] = "tiny"
    conf["config"].update(hidden_size=64, intermediate_size=128,
                          num_attention_heads=4, num_key_value_heads=2,
                          head_dim=16, num_hidden_layers=2, vocab_size=512)
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(conf))
    mix = json.loads((root / "chipbench/traffic/chat_decode.json")
                     .read_text())
    mix["engine"].update(slots=4, max_prompt=32, max_gen=16, topk=8)
    mix["prompt_len"].update(median=8, min=4, max=32)
    mix["output_len"].update(median=8, min=4, max=16)
    mix.update(rate_per_s=6.0, preroll_s=0.5, drain_s=20, trace_s=1,
               check_tokens=20, limits=TINY_LIMITS)
    (root / "chipbench/traffic/tiny.json").write_text(json.dumps(mix))
    bench = harness.benchmark(harness.ROOT)
    bench["configs"] = [{"name": "tiny", "source": "x", "reduced": [],
                         "file": "chipbench/configs/tiny.json", "why": "x"}]
    bench["workloads"] = [{"name": "tiny", "config": "tiny",
                           "traffic": "tiny", "chips": 1, "why": "x"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def run(root, monkeypatch, seed=1, control=False):
    monkeypatch.setattr(harness, "require_devices",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    cell = harness.Cell(harness.benchmark(root), "tiny", root)
    rec, _, att, failed, checks = cell.driver().run(
        cell, seed, 1.0, False, time.perf_counter(), control=control)
    assert att > 0 and failed == 0
    return rec, checks


def test_program_passes_control_fails(root, monkeypatch):
    rec, checks = run(root, monkeypatch, control=True)
    ctl = checks.pop("control")
    assert all(c["ok"] for c in checks.values()), checks
    assert any(ctl[k] > lim for k, lim in TINY_LIMITS.items()), ctl
    assert ctl["correct"] is False
    for k in ("ttft_p95_ms", "itl_p95_ms", "serve_tok_s",
              "queue_wait_p95_ms"):
        v = harness.metric_reader(k, root)(rec)
        assert v is not None and v >= 0


def _alter_token(monkeypatch):
    from repro.serving import engine

    real = engine.make_slot_sampler

    def sampler(*a):
        pick = real(*a)
        return lambda logits, inst, gen_idx: jnp.where(
            gen_idx == 3, (pick(logits, inst, gen_idx) + 1) % logits.shape[-1],
            pick(logits, inst, gen_idx))

    monkeypatch.setattr(engine, "make_slot_sampler", sampler)


def _state_unchanged(monkeypatch):
    """The decode step hands back the cache it was given: no K/V is ever
    written past the prompt."""
    from repro.models import model

    real = model.decode_step

    def step(params, cfg, cache, *a, **k):
        logits, _ = real(params, cfg, cache, *a, **k)
        return logits, cache

    monkeypatch.setattr(model, "decode_step", step)


def _alter_loss(monkeypatch):
    from repro.serving import recorder

    real = recorder.topk_score

    def score(*a):
        loss, hit = real(*a)
        return loss + 0.25, hit

    monkeypatch.setattr(recorder, "topk_score", score)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _alter_loss])
def test_faults_are_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    _, checks = run(root, monkeypatch)
    assert not all(c["ok"] for c in checks.values()), checks
