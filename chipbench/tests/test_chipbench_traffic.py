"""The traffic generator: seeds move order, never the work."""

import json
import os

import numpy as np
import pytest

from chipbench import harness, traffic

MIXES = os.path.join(os.path.dirname(__file__), "..", "traffic")


def load(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def mixes():
    """The serving mixes (a training job's file has no arrivals)."""
    return sorted(f[:-5] for f in os.listdir(MIXES) if f.endswith(".json")
                  and load(f[:-5])["driver"] == "serve")


@pytest.mark.parametrize("name", mixes())
def test_same_seed_same_schedule(name):
    mix = load(name)
    w = harness.seed_words(2**31 + 11)
    a = traffic.schedule(mix, w, 7.0, 1000)
    b = traffic.schedule(mix, w, 7.0, 1000)
    assert [r.due for r in a] == [r.due for r in b]
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert np.array_equal(x.labels, y.labels)
        assert x.max_new == y.max_new


@pytest.mark.parametrize("name", mixes())
def test_seeds_share_the_work(name):
    """Two seeds: the same sizes and arrival times, other tokens."""
    mix = load(name)
    a = traffic.schedule(mix, harness.seed_words(1), 7.0, 1000)
    b = traffic.schedule(mix, harness.seed_words(2), 7.0, 1000)
    assert [r.due for r in a] == [r.due for r in b]
    assert [(r.phase, r.prompt.size, r.max_new) for r in a] == \
        [(r.phase, r.prompt.size, r.max_new) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the order is a shuffle of the stratified quantiles, not sorted
    win = [r.max_new for r in a if r.phase == "window"]
    assert win != sorted(win)


@pytest.mark.parametrize("name", mixes())
def test_lengths_in_range_and_rate(name):
    mix = load(name)
    e = mix["engine"]
    reqs = traffic.schedule(mix, harness.seed_words(5), 10.0, 1000)
    pl, ol = mix["prompt_len"], mix["output_len"]
    for r in reqs:
        assert pl["min"] <= r.prompt.size <= pl["max"] <= e["max_prompt"]
        assert ol["min"] <= r.max_new <= ol["max"] <= e["max_gen"]
        assert r.labels.size == r.max_new
        assert ((r.prompt >= 0) & (r.prompt < 1000)).all()
    win = [r for r in reqs if r.phase == "window"]
    assert len(win) == round(mix["rate_per_s"] * 10.0)
    dues = np.asarray([r.due for r in win])
    assert dues.min() > mix["preroll_s"]
    assert dues.max() == pytest.approx(mix["preroll_s"] + 10.0)


def test_large_and_negative_seeds():
    assert harness.seed_words(2**31 + 5) != harness.seed_words(2**31 + 6)
    assert harness.seed_words(-3) != harness.seed_words(3)
    assert all(0 <= w < 2**32 for w in harness.seed_words(2**40))


def test_quantiles_match_the_distribution():
    q = traffic.quantiles({"kind": "lognormal", "median": 128, "sigma": 0.8,
                           "min": 32, "max": 512}, 1001)
    assert q[500] == 128 and q.min() >= 32 and q.max() <= 512
    g = traffic.gaps({"kind": "gamma", "cv": 3.0}, 400, 20.0)
    assert g.sum() == pytest.approx(20.0)
    assert g.std() / g.mean() > 2.0  # bursty
