"""Continuous-batching engine: invariants, ledger parity, outcome paths.

What the engine must guarantee (and an earlier serve.py did NOT):

* stable, monotone, non-colliding instance ids — never a per-batch
  ``arange`` that aliases distinct requests onto the same ledger slot;
* EVERY generated position's loss recorded against its instance id (the
  old driver scored only the prefill logits);
* continuous batching is invisible to results: a request decoded through
  a busy slotted batch produces the same tokens and the same recorded
  losses as the same request served alone;
* the fused decode+score+record step is transfer-free (the engine runs it
  under ``jax.transfer_guard("disallow")`` by default — every test here
  inherits that);
* host-, device-, and routed-sharded-ledger placements agree bit-for-bit
  on the same schedule.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from _ledger_parity import DERIVED_RTOL, assert_ema_close, \
    assert_ledger_states_close
from repro.core.history import HistoryConfig, slot_for
from repro.data import DataConfig, RecycleFeed, SyntheticLMStream
from repro.launch.mesh import make_elastic_mesh
from repro.models import model as Mdl
from repro.models.params import materialize
from repro.serving import (
    Engine,
    OutcomeRecorder,
    delayed_outcomes,
    make_slot_sampler,
    pages_for,
)

CFG = configs.get_smoke("llama3-8b")
LCFG = HistoryConfig(capacity=1 << 12, decay=0.8)


@pytest.fixture(scope="module")
def params():
    return materialize(
        Mdl.param_specs(CFG), jax.random.key(0), jnp.dtype(CFG.param_dtype)
    )


def make_engine(params, *, slots=4, max_prompt=16, max_gen=6, ledger="device",
                route=False, **kw):
    mesh = make_elastic_mesh() if route else None
    rec = OutcomeRecorder(slots, max_gen, CFG.vocab_size, LCFG,
                          ledger=ledger, mesh=mesh, route=route)
    return Engine(CFG, params, rec, slots=slots, max_prompt=max_prompt,
                  max_gen=max_gen, **kw)


def random_requests(n, max_prompt=16, max_gen=6, seed=0):
    rs = np.random.default_rng(seed)
    reqs = []
    for _ in range(n):
        plen = int(rs.integers(3, max_prompt + 1))
        gen = int(rs.integers(2, max_gen + 1))
        reqs.append((
            rs.integers(0, CFG.vocab_size, plen),
            gen,
            rs.integers(0, CFG.vocab_size, gen),
        ))
    return reqs


def drive(engine, reqs, delay=0, label_frac=1.0, seed=0):
    """Submit, run, deliver labels `delay` steps after admission for a
    `label_frac` share of requests; returns [(iid, labels|None), ...]."""
    rs = np.random.default_rng(seed + 1)
    submitted = []
    for prompt, gen, labels in reqs:
        labeled = rs.random() < label_frac
        iid = engine.submit(
            prompt, max_new=gen,
            labels=labels if (labeled and delay == 0) else None,
            expect_labels=labeled and delay > 0,
        )
        submitted.append((iid, labels if labeled else None))
    pending = {
        iid: lab for iid, lab in submitted if lab is not None and delay > 0
    }
    deliver = delayed_outcomes(pending, delay)

    def on_step(eng, metrics):
        deliver(eng, metrics)
        assert len(eng.in_flight_ids()) <= eng.slots  # never over-committed

    engine.run(max_steps=2000, on_step=on_step if delay else None)
    return submitted


# ---------------------------------------------------------------------------
# invariants under a randomized schedule
# ---------------------------------------------------------------------------


def test_engine_admission_eviction_invariants(params):
    reqs = random_requests(11, seed=3)
    eng = make_engine(params, slots=4)
    submitted = drive(eng, reqs)
    stats = eng.stats()
    # every request admitted exactly once, every slot freed, queue drained
    assert stats["admitted"] == stats["evicted"] == len(reqs)
    assert stats["queued"] == stats["in_flight"] == 0
    # ids are engine-assigned, monotone, unique
    ids = [iid for iid, _ in submitted]
    assert ids == sorted(set(ids))
    # each request generated exactly max_new tokens
    for (prompt, gen, _), (iid, _) in zip(reqs, submitted):
        assert eng.finished[iid].shape == (gen,)
    # decode tokens = sum(gen - 1): position 0 comes from prefill
    assert stats["generated_tokens"] == sum(g - 1 for _, g, _ in reqs)
    # every labeled position recorded exactly once
    assert stats["recorded"] == sum(g for _, g, _ in reqs)
    assert stats["missed_outcomes"] == 0


def test_engine_partial_labels_and_late_delivery(params):
    reqs = random_requests(9, seed=5)
    eng_now = make_engine(params, slots=3)
    sub_now = drive(eng_now, reqs, delay=0, label_frac=0.6, seed=7)
    labeled = sum(1 for _, lab in sub_now if lab is not None)
    assert eng_now.stats()["recorded"] == sum(
        g for (_, g, _), (_, lab) in zip(reqs, sub_now) if lab is not None
    )
    # same schedule, labels delivered 3 steps late: identical ledger
    eng_late = make_engine(params, slots=3)
    drive(eng_late, reqs, delay=3, label_frac=0.6, seed=7)
    assert eng_late.stats()["recorded"] == eng_now.stats()["recorded"]
    sd_now, sd_late = eng_now.ledger_state_dict(), eng_late.ledger_state_dict()
    np.testing.assert_array_equal(sd_now["owner"], sd_late["owner"])
    np.testing.assert_array_equal(sd_now["count"], sd_late["count"])
    assert_ema_close(sd_now["ema"], sd_late["ema"])
    assert labeled > 0


def test_duplicate_in_flight_id_defers_admission(params):
    """Two queued requests under one instance id: the second must wait for
    the first's slot to evict — two live slots under one id would corrupt
    the slot map and leak a slot forever."""
    eng = make_engine(params, slots=4)
    rs = np.random.default_rng(31)
    for _ in range(2):
        eng.submit(rs.integers(0, CFG.vocab_size, 6), max_new=3,
                   labels=rs.integers(0, CFG.vocab_size, 3), instance_id=77)

    def on_step(e, m):
        assert list(e.in_flight_ids()).count(77) <= 1

    eng.run(max_steps=300, on_step=on_step)
    s = eng.stats()
    assert s["admitted"] == s["evicted"] == 2, s
    assert s["in_flight"] == 0 and s["queued"] == 0, s
    slot = slot_for(np.asarray([77]), LCFG.capacity)[0]
    sd = eng.ledger_state_dict()
    # both servings recorded under the id: 3 + 3 observations
    assert sd["owner"][slot] == 77 and sd["count"][slot] == 6


def test_duplicate_id_delayed_outcomes_fifo(params):
    """Pool wrap under --outcome-delay: the same id served twice with
    different outcomes — each residency must get its own labels (FIFO),
    and both must drain (neither residency wedges awaiting labels)."""
    rs = np.random.default_rng(37)
    prompts = [rs.integers(0, CFG.vocab_size, 6) for _ in range(2)]
    labels = [rs.integers(0, CFG.vocab_size, 3) for _ in range(2)]
    eng = make_engine(params, slots=2)
    outcomes = []
    for p, lab in zip(prompts, labels):
        iid = eng.submit(p, max_new=3, expect_labels=True, instance_id=55)
        outcomes.append((iid, lab))
    eng.run(max_steps=300, on_step=delayed_outcomes(outcomes, delay=2))
    s = eng.stats()
    assert s["evicted"] == 2 and s["in_flight"] == 0, s
    assert s["recorded"] == 6, s
    slot = slot_for(np.asarray([55]), LCFG.capacity)[0]
    assert eng.ledger_state_dict()["count"][slot] == 6


def test_deliver_before_admission_attaches_to_queued_request(params):
    """Outcomes may land while the request is still queued: they must
    attach to it (delivered at admission), not be dropped as missed —
    dropping would wedge an expect_labels slot forever."""
    rs = np.random.default_rng(41)
    eng = make_engine(params, slots=2)
    iid = eng.submit(rs.integers(0, CFG.vocab_size, 6), max_new=3,
                     expect_labels=True)
    labels = rs.integers(0, CFG.vocab_size, 3)
    assert eng.deliver_outcome(iid, labels)  # before any step ran
    eng.run(max_steps=100)
    s = eng.stats()
    assert s["evicted"] == 1 and s["recorded"] == 3, s
    assert s["missed_outcomes"] == 0


def test_labels_beyond_max_new_dropped_and_counted(params):
    """[bugfix] Late-label truncation mismatch: admission always truncated
    labels to the request's max_new, but deliver_outcome accepted them up
    to recorder.max_gen — positions >= max_new have no decoded logits and
    were silently unscoreable, without ever being counted. Both paths must
    cut at max_new and count the dropped positions in missed_outcomes."""
    rs = np.random.default_rng(43)
    eng = make_engine(params, slots=2)
    # late path: max_new=3 but 6 labels delivered mid-residency
    iid = eng.submit(rs.integers(0, CFG.vocab_size, 6), max_new=3,
                     expect_labels=True)
    extra = rs.integers(0, CFG.vocab_size, 6)
    eng.run(max_steps=300, on_step=delayed_outcomes([(iid, extra)], delay=1))
    s = eng.stats()
    assert s["evicted"] == 1 and s["recorded"] == 3, s
    assert s["missed_outcomes"] == 3, s
    # admission path: labels attached at submit get the same cut + count
    eng.submit(rs.integers(0, CFG.vocab_size, 6), max_new=2,
               labels=rs.integers(0, CFG.vocab_size, 5))
    eng.run(max_steps=300)
    s = eng.stats()
    assert s["recorded"] == 5 and s["missed_outcomes"] == 6, s


def test_explicit_id_advances_auto_lane(params):
    """[bugfix] An explicit instance id on the engine's auto-assign lane
    used to collide with a later auto id, silently merging two requests'
    records under one ledger id."""
    rs = np.random.default_rng(47)
    eng = make_engine(params, slots=4)

    def req(**kw):
        return eng.submit(rs.integers(0, CFG.vocab_size, 5), max_new=2,
                          labels=rs.integers(0, CFG.vocab_size, 2), **kw)

    a = req()                 # auto: 0
    b = req(instance_id=1)    # explicit, on the lane
    c = req()                 # pre-fix: 1 again — collides with b
    assert len({a, b, c}) == 3, (a, b, c)
    eng.run(max_steps=200)
    sd = eng.ledger_state_dict()
    for iid in (a, b, c):
        slot = slot_for(np.asarray([iid]), LCFG.capacity)[0]
        # each id's ledger slot holds exactly its own 2 observations
        assert sd["owner"][slot] == iid and sd["count"][slot] == 2, iid
    # off-lane explicit ids leave the auto lane alone; on-lane ids ahead
    # of the cursor jump it past them
    eng2 = make_engine(params, slots=2, id_start=0, id_stride=2)
    eng2.submit(rs.integers(0, CFG.vocab_size, 5), instance_id=7)  # off-lane
    assert eng2.submit(rs.integers(0, CFG.vocab_size, 5)) == 0
    eng2.submit(rs.integers(0, CFG.vocab_size, 5), instance_id=6)  # on-lane
    assert eng2.submit(rs.integers(0, CFG.vocab_size, 5)) == 8


def test_outcome_after_eviction_is_counted_missed(params):
    eng = make_engine(params, slots=2)
    (prompt, gen, labels) = random_requests(1, seed=9)[0]
    iid = eng.submit(prompt, max_new=gen)  # no labels, none expected
    eng.run(max_steps=100)
    assert eng.stats()["evicted"] == 1 and eng.stats()["recorded"] == 0
    assert not eng.deliver_outcome(iid, labels)  # slot long gone
    assert eng.stats()["missed_outcomes"] == 1


# ---------------------------------------------------------------------------
# regression: per-position recording with stable ids (old serve.py bugs)
# ---------------------------------------------------------------------------


def test_every_position_recorded_under_stable_ids(params):
    """The one-shot driver scored only logits_seq[0] and re-used
    ids=arange(batch) across runs. The engine must record max_new losses
    per request under ids that never collide across waves."""
    reqs = random_requests(8, seed=11)
    eng = make_engine(params, slots=2)  # 4 waves through 2 slots
    submitted = drive(eng, reqs)
    sd = eng.ledger_state_dict()
    for (prompt, gen, _), (iid, _) in zip(reqs, submitted):
        slot = slot_for(np.asarray([iid]), LCFG.capacity)[0]
        assert sd["owner"][slot] == iid
        # count == generated positions: every position was an observation
        assert sd["count"][slot] == gen, (iid, gen, sd["count"][slot])


def test_engine_matches_solo_serving(params):
    """Continuous batching must be invisible: a request served through a
    busy 4-slot engine yields the same tokens and same recorded EMA as the
    same request served alone (slots=1)."""
    reqs = random_requests(6, max_prompt=12, max_gen=5, seed=13)
    busy = make_engine(params, slots=4, max_prompt=12, max_gen=5)
    sub_busy = drive(busy, reqs)
    solo = make_engine(params, slots=1, max_prompt=12, max_gen=5)
    sub_solo = drive(solo, reqs)
    sd_b, sd_s = busy.ledger_state_dict(), solo.ledger_state_dict()
    for (iid_b, _), (iid_s, _) in zip(sub_busy, sub_solo):
        np.testing.assert_array_equal(
            busy.finished[iid_b], solo.finished[iid_s]
        )
        sb = slot_for(np.asarray([iid_b]), LCFG.capacity)[0]
        ss = slot_for(np.asarray([iid_s]), LCFG.capacity)[0]
        assert_ema_close(sd_b["ema"][sb], sd_s["ema"][ss], rtol=DERIVED_RTOL)


def test_recorded_ema_matches_hand_rolled_decode(params):
    """Oracle: prefill + greedy decode by hand, fold per-position CE into
    an EMA — the ledger slot must hold exactly that (all positions, in
    order)."""
    rs = np.random.default_rng(17)
    prompt = rs.integers(0, CFG.vocab_size, 9)
    labels = rs.integers(0, CFG.vocab_size, 5)
    eng = make_engine(params, slots=2, max_prompt=12, max_gen=5)
    iid = eng.submit(prompt, max_new=5, labels=labels)
    eng.run(max_steps=50)

    logits, cache = Mdl.prefill(
        params, CFG, jnp.asarray(prompt[None].astype(np.int32)), max_seq=17
    )
    want = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for g in range(5):
        lf = np.asarray(logits, np.float32)[0]
        m = lf.max()
        want.append(m + np.log(np.exp(lf - m).sum()) - lf[labels[g]])
        if g < 4:
            logits, cache = Mdl.decode_step(
                params, CFG, cache, tok, jnp.asarray(9 + g, jnp.int32)
            )
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    ema = want[0]
    for l in want[1:]:
        ema = LCFG.decay * ema + (1 - LCFG.decay) * l
    sd = eng.ledger_state_dict()
    slot = slot_for(np.asarray([iid]), LCFG.capacity)[0]
    assert sd["owner"][slot] == iid and sd["count"][slot] == 5
    # float64 hand-rolled oracle vs the f32 on-device chain: a shade looser
    # than the host/device convention
    assert_ema_close(sd["ema"][slot], ema, rtol=2e-5)


# ---------------------------------------------------------------------------
# ledger placements agree
# ---------------------------------------------------------------------------


def test_host_device_routed_ledgers_agree(params):
    """One schedule, three placements. The two DEVICE placements (single
    table, routed sharded table — 1-shard mesh here; the multi-shard case
    is tests/test_serving_sharded.py) must agree bit-for-bit: the routed
    layout IS the global layout. The host numpy table matches to f32
    rounding (numpy and XLA may fuse the EMA multiply-add differently)."""
    reqs = random_requests(7, seed=19)
    sds = []
    for kw in (dict(ledger="host"), dict(ledger="device"),
               dict(ledger="device", route=True)):
        eng = make_engine(params, slots=4, **kw)
        drive(eng, reqs)
        sds.append(eng.ledger_state_dict())
    host, dev, routed = sds
    keys = ("ema", "count", "last_seen", "owner")
    for k in keys:
        np.testing.assert_array_equal(dev[k], routed[k], err_msg=k)
    assert_ledger_states_close(
        {k: host[k] for k in keys}, {k: dev[k] for k in keys}
    )


def test_ledger_interchange_and_recycle_feed(params):
    """serve -> .npz -> warm engine, and the LIVE engine handle joining a
    RecycleFeed batch (ledger="engine") with real hit rates."""
    reqs = random_requests(6, seed=23)
    eng = make_engine(params, slots=3)
    submitted = drive(eng, reqs)
    sd = eng.ledger_state_dict()

    eng2 = make_engine(params, slots=3)
    handle2 = eng2.ledger
    _, seen_cold = handle2.lookup(np.asarray([0], np.int64))
    assert not seen_cold.any()  # snapshot of the empty table
    eng2.load_ledger_state_dict(sd)
    ids = np.asarray([iid for iid, _ in submitted], np.int64)
    # the SAME handle must see the loaded table (epoch bump invalidates
    # its snapshot even though the engine hasn't stepped)
    ema2, seen2 = handle2.lookup(ids)
    ema1, seen1 = eng.ledger.lookup(ids)
    np.testing.assert_array_equal(np.asarray(seen1), np.asarray(seen2))
    assert_ema_close(ema1, ema2)

    # live handle -> RecycleFeed: ids the engine served get its EMA, the
    # rest fall back to cold_loss
    stream = SyntheticLMStream(DataConfig(4, 8, CFG.vocab_size,
                                          instance_pool=16))
    feed = RecycleFeed(stream, history=eng.ledger, ledger="engine",
                       cold_loss=123.0)
    batch = feed.batch(1)  # ids 4..7: engine served 0..5 -> 4,5 hit, 6,7 cold
    served = set(int(i) for i, _ in submitted)
    for row, iid in enumerate(batch["instance_id"]):
        if int(iid) in served:
            assert batch["recorded_loss"][row] != 123.0
        else:
            assert batch["recorded_loss"][row] == 123.0
    assert 0.0 < batch["ledger_hit_rate"] <= 1.0


def test_exact_length_families_reject_padding(params):
    """Recurrent and windowed families must refuse prompt padding (pads
    would perturb real positions) but still serve via exact-length
    prefill."""
    cfg = configs.get_smoke("mamba2-370m")
    p = materialize(Mdl.param_specs(cfg), jax.random.key(1),
                    jnp.dtype(cfg.param_dtype))
    rec = OutcomeRecorder(2, 4, cfg.vocab_size, LCFG, ledger="device")
    with pytest.raises(ValueError, match="right-pad"):
        Engine(cfg, p, rec, slots=2, max_prompt=8, max_gen=4,
               prompt_buckets=(8,))
    rec2 = OutcomeRecorder(2, 4, cfg.vocab_size, LCFG, ledger="device")
    eng = Engine(cfg, p, rec2, slots=2, max_prompt=8, max_gen=4)
    assert eng.prompt_buckets is None
    rs = np.random.default_rng(29)
    for plen in (5, 7):
        eng.submit(rs.integers(0, cfg.vocab_size, plen), max_new=3,
                   labels=rs.integers(0, cfg.vocab_size, 3))
    eng.run(max_steps=100)
    assert eng.stats()["evicted"] == 2
    assert eng.stats()["recorded"] == 6


# ---------------------------------------------------------------------------
# paged KV cache + per-slot sampling
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("page_size", [2, 11])  # both divide max_seq = 22
def test_paged_engine_bit_identical_to_dense(params, page_size):
    """The tentpole acceptance contract: a paged-cache engine at
    temperature 0 reproduces the dense engine's generated tokens AND its
    ledger records bit-for-bit on the same schedule (late labels
    included), while every page returns to the pool at drain."""
    reqs = random_requests(10, seed=41)
    dense = make_engine(params, slots=4)
    drive(dense, reqs, delay=2, label_frac=0.7, seed=5)
    paged = make_engine(params, slots=4, page_size=page_size)
    drive(paged, reqs, delay=2, label_frac=0.7, seed=5)
    assert set(dense.finished) == set(paged.finished)
    for iid in dense.finished:
        np.testing.assert_array_equal(dense.finished[iid],
                                      paged.finished[iid])
    sd, sp = dense.ledger_state_dict(), paged.ledger_state_dict()
    for k in sd:  # device-vs-device same placement: BIT-equal, incl. ema
        np.testing.assert_array_equal(sd[k], sp[k], err_msg=k)
    st = paged.stats()
    assert st["pages_free"] == st["pages_total"]  # no page leaked
    assert st["pages_reserved"] == 0


def test_paged_pool_exhaustion_defers_and_preserves_results(params):
    """A pool sized for ~2 worst-case residents under a 4-slot engine must
    defer admissions (never touch a live slot) and still produce the same
    tokens and per-instance ledger values — deferral shifts WHEN a request
    runs, never WHAT it computes. (last_seen moves with the admission
    step, so it is excluded.)"""
    reqs = random_requests(10, seed=43)
    dense = make_engine(params, slots=4)
    drive(dense, reqs)
    worst = pages_for(22, 2)  # max_seq pages at page_size=2
    starved = make_engine(params, slots=4, page_size=2,
                          num_pages=2 * worst)
    drive(starved, reqs)
    assert starved.deferred_admissions > 0
    assert set(dense.finished) == set(starved.finished)
    for iid in dense.finished:
        np.testing.assert_array_equal(dense.finished[iid],
                                      starved.finished[iid])
    sd, sp = dense.ledger_state_dict(), starved.ledger_state_dict()
    for k in ("ema", "count", "owner", "sig"):
        np.testing.assert_array_equal(sd[k], sp[k], err_msg=k)
    st = starved.stats()
    assert st["pages_free"] == st["pages_total"]


def test_sampled_decode_deterministic_and_schedule_invariant(params):
    """temperature > 0: per-slot RNG lanes are keyed by (instance id,
    generated position) only — rerunning, changing the slot count, or
    switching cache layouts reproduces the same tokens; and sampling
    actually leaves the greedy path somewhere."""
    reqs = random_requests(8, seed=47)
    kw = dict(temperature=0.8, top_p=0.9, sample_seed=3)
    runs = {}
    for name, ekw in (
        ("a", dict(slots=4, **kw)),
        ("rerun", dict(slots=4, **kw)),
        ("fewer_slots", dict(slots=2, **kw)),
        ("paged", dict(slots=4, page_size=2, **kw)),
        ("greedy", dict(slots=4)),
    ):
        eng = make_engine(params, **ekw)
        drive(eng, reqs)
        runs[name] = eng
    base = runs["a"].finished
    for name in ("rerun", "fewer_slots", "paged"):
        for iid in base:
            np.testing.assert_array_equal(
                base[iid], runs[name].finished[iid], err_msg=name
            )
    assert any(
        not np.array_equal(base[iid], runs["greedy"].finished[iid])
        for iid in base
    )


def test_sampler_semantics():
    """Unit contract of make_slot_sampler: temperature<=0 IS argmax (same
    op, not merely close); top-p keeps a token iff the sorted mass
    strictly before it is < top_p (top-1 always survives)."""
    logits = jax.random.normal(jax.random.key(2), (3, 64), jnp.float32) * 3
    inst = jnp.asarray([5, -1, 9], jnp.int32)
    gidx = jnp.asarray([0, 2, 7], jnp.int32)
    greedy = make_slot_sampler(0.0, 0.5, 11)
    np.testing.assert_array_equal(
        np.asarray(greedy(logits, inst, gidx)),
        np.asarray(jnp.argmax(logits, -1)),
    )
    # mass 0.6/0.3/0.05/0.05: top_p=0.5 keeps only token 0; =0.7 adds tok 1
    probs = jnp.log(jnp.asarray([[0.6, 0.3, 0.05, 0.05]]))
    one = jnp.asarray([7], jnp.int32)
    for top_p, allowed in ((0.5, {0}), (0.7, {0, 1}), (1.0, {0, 1, 2, 3})):
        s = make_slot_sampler(1.0, top_p, 0)
        got = {
            int(s(probs, one, jnp.asarray([g], jnp.int32))[0])
            for g in range(300)
        }
        assert got <= allowed, (top_p, got)
        assert 0 in got
