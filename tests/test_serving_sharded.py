"""Serving engine on a real 4-shard mesh (virtual CPU devices, spawned in
a subprocess so the main test process keeps its single-device view —
the ``test_routed_ledger.py`` pattern).

The scenario: a serving fleet records outcomes into a ledger SHARDED over
the mesh, with ``route=True`` exchanging every record to the shard that
owns its global slot, inside the engine's fused (and transfer-guarded)
decode step. The routed sharded table must come out bit-identical to a
single-table engine run of the same request schedule — the acceptance
contract that makes sharded serving ledgers checkpoint-compatible with
everything else.
"""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro import configs
from repro.core.history import HistoryConfig, slot_for
from repro.launch.mesh import make_elastic_mesh
from repro.models import model as Mdl
from repro.models.params import materialize
from repro.serving import Engine, OutcomeRecorder

assert jax.device_count() == 4
cfg = configs.get_smoke("llama3-8b")
params = materialize(Mdl.param_specs(cfg), jax.random.key(0),
                     jnp.dtype(cfg.param_dtype))
lcfg = HistoryConfig(capacity=4096, decay=0.8)
SLOTS, GEN, MP = 8, 5, 12  # slots divisible by the 4 ledger shards

def schedule():
    rs = np.random.default_rng(0)
    return [(rs.integers(0, cfg.vocab_size, int(rs.integers(3, MP + 1))),
             int(rs.integers(2, GEN + 1)),
             rs.integers(0, cfg.vocab_size, GEN))
            for _ in range(2 * SLOTS)]

def run(mesh, route, exchange="gather", cf=1.25, **kw):
    rec = OutcomeRecorder(SLOTS, GEN, cfg.vocab_size, lcfg,
                          ledger="device", mesh=mesh, route=route,
                          exchange=exchange, capacity_factor=cf)
    eng = Engine(cfg, params, rec, slots=SLOTS, max_prompt=MP, max_gen=GEN,
                 **kw)
    ids = [eng.submit(p, max_new=g, labels=l[:g]) for p, g, l in schedule()]
    eng.run(max_steps=500)
    assert eng.stats()["in_flight"] == 0, eng.stats()
    return eng, ids

# the CLIs' own mesh: every device on the "data" axis the ledger shards over
mesh = make_elastic_mesh()
assert dict(mesh.shape) == {"data": 4, "model": 1}, mesh.shape
eng_routed, ids = run(mesh, route=True)
assert eng_routed.recorder.ops.shards == 4
eng_single, ids2 = run(None, route=False)
assert ids == ids2

# the routed 4-shard table is bit-identical to the single-table run
sd_r, sd_s = eng_routed.ledger_state_dict(), eng_single.ledger_state_dict()
for k in ("ema", "count", "last_seen", "owner"):
    np.testing.assert_array_equal(np.asarray(sd_r[k]), np.asarray(sd_s[k]),
                                  err_msg=k)

# every request's every generated position was recorded at its hash slot
want = sum(g for _, g, _ in schedule())
assert int(eng_routed.stats()["recorded"]) == want, (
    eng_routed.stats(), want)
slots = slot_for(np.asarray(ids, np.int64), lcfg.capacity)
assert (sd_r["owner"][slots] == np.asarray(ids)).all()

# and the table really lives sharded on the mesh (a slice per device)
led = eng_routed._rstate.ledger
shardings = {str(d.sharding.spec) for d in (led.ema, led.owner)}
assert shardings == {"PartitionSpec('data',)"}, shardings
assert eng_routed.stats()["a2a_overflow"] == 0  # gather never overflows

# a2a exchange inside the guarded fused step: same schedule through the
# capacity-factor all_to_all dispatch must match the single-table run to
# the tests/_ledger_parity.py convention (ints bit-exact, EMA to the
# 1-ulp FMA rtol — a different collective program compiles different
# fusions than the single-device one). cf=4.0 makes each send buffer as
# large as the local batch (2 slots/shard), so overflow is statically
# impossible: the counter must read 0.
eng_a2a, ids4 = run(mesh, route=True, exchange="a2a", cf=4.0)
assert ids == ids4
assert eng_a2a.stats()["a2a_overflow"] == 0, eng_a2a.stats()
sd_a = eng_a2a.ledger_state_dict()
for k in ("count", "last_seen", "owner"):
    np.testing.assert_array_equal(np.asarray(sd_a[k]), np.asarray(sd_s[k]),
                                  err_msg="a2a-" + k)
np.testing.assert_allclose(np.asarray(sd_a["ema"]), np.asarray(sd_s["ema"]),
                           rtol=1e-6, atol=0, err_msg="a2a-ema")

# starve the send buffers (cap floors at ONE forwarded record per
# destination per step): the exact overflow fallback must fire — counted
# in stats() — and the table must STILL match the single run
eng_ovf, _ = run(mesh, route=True, exchange="a2a", cf=0.125)
assert eng_ovf.stats()["a2a_overflow"] > 0, eng_ovf.stats()
sd_o = eng_ovf.ledger_state_dict()
for k in ("count", "last_seen", "owner"):
    np.testing.assert_array_equal(np.asarray(sd_o[k]), np.asarray(sd_s[k]),
                                  err_msg="ovf-" + k)
np.testing.assert_allclose(np.asarray(sd_o["ema"]), np.asarray(sd_s["ema"]),
                           rtol=1e-6, atol=0, err_msg="ovf-ema")
print(f"a2a overflow counters: cf=4.0 -> 0, "
      f"cf=0.125 -> {eng_ovf.stats()['a2a_overflow']}")

# PAGED KV cache on the routed 4-shard mesh: same schedule through the
# page pool (page_size=1 so the pool tokens == max_seq exactly) must be
# bit-identical to the dense routed run — tokens AND ledger — and drain
# every page back to the pool
eng_paged, ids3 = run(mesh, route=True, page_size=1)
assert ids == ids3
sd_p = eng_paged.ledger_state_dict()
for k in ("ema", "count", "last_seen", "owner"):
    np.testing.assert_array_equal(np.asarray(sd_p[k]), np.asarray(sd_r[k]),
                                  err_msg="paged-" + k)
for iid in eng_routed.finished:
    np.testing.assert_array_equal(eng_routed.finished[iid],
                                  eng_paged.finished[iid], err_msg=str(iid))
stp = eng_paged.stats()
assert stp["pages_free"] == stp["pages_total"], stp

# LATE-outcome delivery on the routed mesh, with the compressed topk
# retention: deliver_outcome routes each delivered row through
# recorder.replicate, so the updated labels stay mesh-placed and the next
# guarded fused step never needs an implicit transfer. The routed
# late-delivery table must still match a single-table late run of the
# same schedule bit-for-bit.
from jax.sharding import NamedSharding
from repro.serving import delayed_outcomes

def run_late(mesh, route):
    rec = OutcomeRecorder(SLOTS, GEN, cfg.vocab_size, lcfg,
                          ledger="device", mesh=mesh, route=route,
                          retention="topk", topk=16)
    eng = Engine(cfg, params, rec, slots=SLOTS, max_prompt=MP, max_gen=GEN)
    outs = [(eng.submit(p, max_new=g, expect_labels=True), l[:g])
            for p, g, l in schedule()]
    eng.run(max_steps=800, on_step=delayed_outcomes(outs, 2))
    assert eng.stats()["in_flight"] == 0, eng.stats()
    return eng

late_routed = run_late(mesh, True)
assert int(late_routed.stats()["recorded"]) == want, late_routed.stats()
lab = late_routed._rstate.labels
assert isinstance(lab.sharding, NamedSharding), lab.sharding
assert lab.sharding.mesh.shape["data"] == 4, lab.sharding
late_single = run_late(None, False)
sd_lr, sd_ls = (late_routed.ledger_state_dict(),
                late_single.ledger_state_dict())
for k in ("ema", "count", "last_seen", "owner"):
    np.testing.assert_array_equal(np.asarray(sd_lr[k]), np.asarray(sd_ls[k]),
                                  err_msg="late-" + k)
print("SERVING-SHARDED-OK")
"""

ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/tmp"}
if "JAX_PLATFORMS" in os.environ:
    ENV["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
CWD = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_serving_engine_routed_sharded_ledger():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=900, env=ENV, cwd=CWD,
    )
    assert "SERVING-SHARDED-OK" in res.stdout, res.stdout + res.stderr
