"""The DeepSeek-V2-Lite cell's pieces on the CPU: its configuration file
against the published config and the program, the operation and byte
counts against hand-computed values at the published widths (9 of 27
layers, as the cell runs), and the two new readers on a synthetic trace."""

import json
import os
import types

import pytest

from chipbench import harness, spans, trace
from chipbench.costs import deepseek_v2 as C
from chipbench.reference import deepseek_v2 as R

CONF = os.path.join(os.path.dirname(__file__), "..", "configs",
                    "deepseek-v2-lite-9layer.json")
DEV = "/device:TPU:0"
US = 1e3  # ns


@pytest.fixture(scope="module")
def conf():
    with open(CONF) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def s(conf):
    return R.sizes(conf)


def test_program_config_is_the_file(conf):
    """Every width the file states reaches the program, and the preset
    holds the file's YaRN and gate."""
    m = harness.program_config(conf)
    c, y = conf["config"], conf["config"]["rope_scaling"]
    assert (m.num_layers, m.d_model, m.num_heads, m.kv_lora_rank,
            m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.d_ff,
            m.moe_d_ff, m.num_experts, m.experts_per_token,
            m.num_shared_experts, m.first_k_dense, m.vocab_size) == (
        9, 2048, 16, 512, 128, 64, 128, 10944, 1408, 64, 6, 2, 1, 102400)
    assert c["q_lora_rank"] is None and m.q_lora_rank == 0
    assert (m.yarn_factor, m.yarn_original_max_pos, m.yarn_beta_fast,
            m.yarn_beta_slow, m.yarn_mscale, m.yarn_mscale_all_dim) == (
        y["factor"], y["original_max_position_embeddings"], y["beta_fast"],
        y["beta_slow"], y["mscale"], y["mscale_all_dim"])
    assert (m.route_norm, c["routed_scaling_factor"], c["scoring_func"],
            c["topk_method"]) == (False, 1, "softmax", "greedy")
    assert m.norm_eps == c["rms_norm_eps"] and not m.tie_embeddings


def test_published_keys_stand_at_the_top_level(conf):
    """The file states every published key at its top level, as the
    catalog's entry gives it, and the ``config`` group that the harness
    and the reference read holds the same values; only the keys listed
    in ``reduced`` depart from the published model."""
    for key, value in conf["config"].items():
        assert conf[key] == value, key
    assert set(conf["published"]) == set(conf["reduced"])
    for key in conf["reduced"]:
        assert conf[key] != conf["published"][key], key


def test_params(s):
    # wq 2048*16*192 + wkv_a 2048*576 + wkv_b 512*16*256 + wo 16*128*2048
    assert C.attn_params(s) == 13_762_560
    assert C.expert_params(s) == 8_650_752  # 3 * 2048 * 1408
    # 9 attention + the dense MLP (3 * 2048 * 10944) + 8 * (router
    # 2048 * 64 + (6 routed + 2 shared) experts)
    assert C.active_matmul_params(s) == (123_863_040 + 67_239_936
                                         + 8 * (131_072 + 69_206_016))
    # attention, dense MLP, routers, shared experts, norms
    # (9 * (2 * 2048 + 512) + 2048) and the head 102400 * 2048
    assert C.resident_params(s) == (123_863_040 + 67_239_936
                                    + 8 * (131_072 + 17_301_504)
                                    + 43_520 + 209_715_200)
    # with every routed expert and the embedding: the 5.18 B of the
    # deployment the file states
    assert C.resident_params(s) + 8 * 64 * 8_650_752 + 209_715_200 == \
        5_179_222_528


def test_decode(s):
    # 2 * (745,799,680 + head 209,715,200) + absorption 2 * 9 * 16 * 512
    # * (128 + 128) a row; 2 * 9 * 16 * (2 * 512 + 64) a key
    assert C.decode_flops(s, 1, 0) == 1_911_029_760 + 37_748_736
    assert C.decode_flops(s, 1, 10) - C.decode_flops(s, 1, 0) == \
        10 * 313_344
    assert C.latent_bytes_per_token(s) == 10_368  # 9 * 576 * 2 B
    # one row hits 6 experts a layer; 64 rows nearly all 64
    assert C.experts_hit(s, 1) == pytest.approx(8 * 6)
    assert C.experts_hit(s, 64) == pytest.approx(
        8 * 64 * (1 - (58 / 64) ** 64))
    assert C.decode_bytes(s, 1, 100) == pytest.approx(
        (540_322_304 + 48 * 8_650_752) * 2 + 2048 * 2 + 100 * 10_368)


def test_prefill(s):
    # 2 * 745,799,680 * 1024 + 2 * 9 * 16 * 320 * (1024 * 1025 / 2)
    #   + 2 * 102400 * 2048
    assert C.prefill_flops(s, [1024]) == (1_527_397_744_640
                                          + 48_365_568_000 + 419_430_400)
    assert C.prefill_flops(s, [7, 9]) == C.prefill_flops(s, [7]) + \
        C.prefill_flops(s, [9])


def test_kernels(s):
    flops, nbytes = C.moe(s, routed=384, hit=512)
    assert flops == 6 * 2048 * 1408 * 384
    # the 8 layers' 64 experts (8.86 GB) and 384 pairs of
    # (3 * 2048 + 3 * 1408) activations
    assert nbytes == 8_858_370_048 + 384 * 10_368 * 2
    flops, nbytes = C.latent_attn(s, rows=2, keys=300)
    assert flops == 300 * 313_344
    assert nbytes == 300 * 10_368 + 2 * 9 * 16 * (1024 + 64) * 2
    flops, nbytes = C.topk_lse(s, rows=64, k=64, itemsize=2)
    assert (flops, nbytes) == (3 * 64 * 102400,
                               64 * 102400 * 2 + 64 * (8 * 64 + 4))


# ---------------------------------------------------------------------------
# the readers on a synthetic trace
# ---------------------------------------------------------------------------

TOY = {"d": 4, "fe": 2, "e": 8, "k": 2, "layers": 3, "dense": 1,
       "h": 2, "r": 4, "pe": 2}
PEAK = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6}


def synthetic(ops, program_spans=(), ticks=()):
    """A traced window [0, 1000) us holding ``ops`` (name, start us, us)
    on one chip."""
    tr = trace.Trace(
        ops={DEV: [trace.Event(n, a * US, d * US) for n, a, d in ops]},
        modules={DEV: []}, host=[], window_ns=(0.0, 1000 * US))
    return types.SimpleNamespace(
        trace=tr, program_spans=list(program_spans), sizes=TOY, costs=C,
        peak=PEAK, ticks=list(ticks), trace_span=(0.0, 1.0))


def span(name, start_us, **args):
    return spans.Span(name, start_us * US, 0.0, args)


def test_moe_roofline():
    # two decode steps (10 pairs over 5 experts, 6 over 4) and a prefill
    # of 8 padded tokens (8 * 2 * 2 = 32 pairs, all 16 experts of the 2
    # MoE layers); one step's span falls after the window
    sp = [span("engine.moe", 100, routed=10, experts_hit=5),
          span("engine.prefill", 150, padded_len=8, prompt=5),
          span("engine.moe", 400, routed=6, experts_hit=4),
          span("engine.moe", 1200, routed=6, experts_hit=4)]
    least = 0.0
    for routed, hit in ((10, 5), (32, 16), (6, 4)):
        flops = 6 * 4 * 2 * routed  # 6 d Fe a pair
        nbytes = hit * 24 * 2 + routed * (12 + 6) * 2
        least += max(flops, nbytes) / 1e6
    # 210 us of grouped matmuls, 20 us of them past the window's end
    rec = synthetic([("ragged-dot-metadata", 90, 10),
                     ("ragged-dot-none", 100, 100),
                     ("ragged-dot-none.1", 200, 70),
                     ("fusion.3", 300, 50),
                     ("ragged-dot-none.2", 990, 30)], sp)
    assert harness.metric_reader("moe_roofline")(rec) == pytest.approx(
        100 * least / 190e-6)


def test_latent_attn_roofline():
    # two steps: 3 occupied slots over 40 keys, then 2 over 25; one tick
    # outside the traced span
    ticks = [(0.1, 0.2, 3, 37, 3, 40, []), (0.3, 0.4, 2, 23, 2, 25, []),
             (1.1, 1.2, 2, 23, 2, 25, [])]
    rec = synthetic([("paged_latent_attn.3", 10, 40),
                     ("paged_latent_attn.3", 500, 60),
                     ("paged_decode_attn.9", 600, 100)], ticks=ticks)
    least = 0.0
    for rows, keys in ((3, 40), (2, 25)):
        flops = 2 * 3 * 2 * (2 * 4 + 2) * keys
        nbytes = keys * 3 * 6 * 2 + rows * 3 * 2 * (2 * 4 + 2) * 2
        least += max(flops, nbytes) / 1e6
    assert harness.metric_reader("latent_attn_roofline")(rec) == \
        pytest.approx(100 * least / 100e-6)


@pytest.mark.parametrize("name", ["moe_roofline", "latent_attn_roofline"])
def test_a_missing_kernel_is_an_error(name):
    rec = synthetic([("fusion.1", 0, 10)],
                    [span("engine.moe", 100, routed=10, experts_hit=5)],
                    ticks=[(0.1, 0.2, 3, 37, 3, 40, [])])
    with pytest.raises(RuntimeError, match="no "):
        harness.metric_reader(name)(rec)


def test_moe_roofline_without_program_spans_reads_nothing():
    rec = synthetic([("ragged-dot-none", 0, 10)])
    rec.program_spans = []  # a program that opens no spans
    assert harness.metric_reader("moe_roofline")(rec) is None


# ---------------------------------------------------------------------------
# the serve driver end to end at a tiny size (the chip check stepped over)
# ---------------------------------------------------------------------------

# from CPU readings at this size: the program's token gap at most 0.035
# over three seeds, the float8 control's at least 0.21
TINY_LIMITS = {"token_gap": 0.1}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import shutil

    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads((root / "chipbench/configs/deepseek-v2-lite-9layer.json")
                      .read_text())
    conf["name"] = "tiny"
    conf["config"].update(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_intermediate_size=32, n_routed_experts=8,
        num_experts_per_tok=3, num_hidden_layers=3, vocab_size=512)
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(conf))
    mix = json.loads((root / "chipbench/traffic/rag_chat.json").read_text())
    mix["engine"].update(slots=4, max_prompt=48, max_gen=16, topk=8)
    mix["prompt_len"].update(median=24, min=16, max=48)
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


def run_tiny(root, monkeypatch, control=False):
    import time

    import jax

    monkeypatch.setattr(harness, "require_devices",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    cell = harness.Cell(harness.benchmark(root), "tiny", root)
    _, _, att, failed, checks = cell.driver().run(
        cell, 2, 1.0, False, time.perf_counter(), control=control)
    assert att > 0 and failed == 0
    return checks


def test_tiny_cell_program_passes_control_fails(root, monkeypatch):
    checks = run_tiny(root, monkeypatch, control=True)
    ctl = checks.pop("control")
    assert all(c["ok"] for c in checks.values()), checks
    assert ctl["token_gap"] > TINY_LIMITS["token_gap"], ctl
    assert ctl["correct"] is False


def test_tiny_cell_unwritten_latents_are_not_correct(root, monkeypatch):
    """The decode step hands back the pool it was given: no latent is
    written past the prompt."""
    from repro.models import model

    real = model.decode_step

    def step(params, cfg, cache, *a, **k):
        out = real(params, cfg, cache, *a, **k)
        return (out[0], cache, *out[2:])

    monkeypatch.setattr(model, "decode_step", step)
    checks = run_tiny(root, monkeypatch)
    assert not all(c["ok"] for c in checks.values()), checks
