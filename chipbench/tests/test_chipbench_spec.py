"""BENCHMARK.json against the benchmark's contract, the peak table, and
that a new cell, mix or metric is found from new files alone."""

import json
import os
import re
import shutil

import pytest

from chipbench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark(ROOT)


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    for n in names:
        assert NAME.match(n), n
    assert len(set(m["name"] for m in bench["end_to_end"] +
                   bench["per_layer"])) == len(bench["end_to_end"]) + \
        len(bench["per_layer"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        assert NAME.match(w["traffic"])


def test_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m["workloads"]:
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or w in moved["workloads"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"], ROOT))


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = harness.Cell(bench, w["name"], ROOT)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert cell.driver() and cell.reference() and cell.costs()
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/")
        conf = harness.load_json(os.path.join(ROOT, c["file"]))
        assert conf["reduced"] == c["reduced"] and conf["name"] == c["name"]
        for k in conf["reduced"]:
            assert conf["published"][k] != conf["config"][k]


def test_unknown_device_kind_is_refused():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit) as e:
        harness.peaks("TPU v99 imaginary")
    assert e.value.code == 2


def test_no_chip_no_result(capsys):
    """On the CPU the run stops before any result line."""
    from chipbench import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "qwen3-decode", "--seed", "1", "--seconds",
                  "1", "--trace", "0"])
    assert e.value.code == 2
    assert "no TPU" in capsys.readouterr().err


def test_new_files_make_a_new_cell(bench, tmp_path):
    """A configuration, a traffic mix and a metric added as files, with
    entries in BENCHMARK.json, are found with no existing file edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in
              (str(q) for q in (root / "chipbench").rglob("*") if q.is_file())}
    conf = json.loads((root / "chipbench/configs/qwen3-14b-8layer.json")
                      .read_text())
    conf["name"] = "qwen3-14b-4layer"
    conf["config"]["num_hidden_layers"] = 4
    (root / "chipbench/configs/qwen3-14b-4layer.json").write_text(
        json.dumps(conf))
    mix = json.loads((root / "chipbench/traffic/chat_decode.json")
                     .read_text())
    mix["rate_per_s"] = 3.0
    (root / "chipbench/traffic/chat_slow.json").write_text(json.dumps(mix))
    (root / "chipbench/metrics/preroll_share.py").write_text(
        "def read(rec):\n    return 42.0\n")
    b = json.loads(json.dumps(bench))
    b["configs"].append({"name": "qwen3-14b-4layer", "source": "x",
                         "file": "chipbench/configs/qwen3-14b-4layer.json",
                         "reduced": ["num_hidden_layers"], "why": "x"})
    b["workloads"].append({"name": "qwen3-slow", "config": "qwen3-14b-4layer",
                           "traffic": "chat_slow", "chips": 1, "why": "x"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m and "qwen3-decode" in m["workloads"]:
            m["workloads"].append("qwen3-slow")
    b["per_layer"].append({"name": "preroll_share", "unit": "%",
                           "better": "lower", "source": "host_clock",
                           "layer": "harness", "moves": "setup_s",
                           "workloads": ["qwen3-slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = harness.Cell(harness.benchmark(str(root)), "qwen3-slow",
                        str(root))
    assert cell.traffic["rate_per_s"] == 3.0
    assert harness.program_config(cell.config).num_layers == 4
    assert "preroll_share" in [m["name"] for m in cell.per_layer]
    assert harness.metric_reader("preroll_share", str(root))(None) == 42.0
    for p, data in before.items():
        assert open(p, "rb").read() == data, p
