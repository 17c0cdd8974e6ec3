"""Memory rehearsal: compile each cell's programs at their real sizes
for a described TPU v5e, without a chip, and print what each needs.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m chipbench.rehearse [workload ...]

For every program the cell runs (weights, each prefill bucket and insert,
the fused decode step, or the train step; the reference and its control)
it prints
``memory_analysis()``: argument, output and temporary bytes on the chip.
The engine's state is built on the host at full size (the KV pool is a
few GB of zeros); the weights are shapes only. A program the chip's
compiler refuses raises here, at no chip time.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import harness, traffic  # noqa: E402
from chipbench.drivers import serve as serve_driver  # noqa: E402

GB = 1e9


def on_chip(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def report(name: str, compiled) -> int:
    m = compiled.memory_analysis()
    arg, out, tmp = (m.argument_size_in_bytes, m.output_size_in_bytes,
                     m.temp_size_in_bytes)
    alias = getattr(m, "alias_size_in_bytes", 0)
    print(f"  {name:<28} args {arg / GB:7.3f} GB  out {out / GB:7.3f} GB  "
          f"temp {tmp / GB:7.3f} GB  alias {alias / GB:6.3f} GB", flush=True)
    return tmp


def serve_cell(cell: harness.Cell, one) -> None:
    ref = cell.reference()
    conf, mix = cell.config, cell.traffic
    mcfg = harness.program_config(conf)
    wprog = ref.weights_program(conf)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    comp = wprog.lower(key).compile()
    report("weights", comp)
    wshapes = jax.eval_shape(wprog, key)
    params = on_chip(wshapes, one)
    wbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    engine = serve_driver.build(mix, mcfg, params)
    est, rst = on_chip(engine._estate, one), on_chip(engine._rstate, one)
    sbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree.leaves((est, rst)))
    print(f"  weights {wbytes / GB:.3f} GB, engine state {sbytes / GB:.3f} GB",
          flush=True)
    tmps = [report("decode step", engine._decode.lower(params, est, rst)
                   .compile())]
    sched = traffic.schedule(mix, harness.seed_words(0)[2:], 30.0,
                             ref.sizes(conf)["vocab"])
    for b in traffic.buckets_used(sched, engine.prompt_buckets):
        toks = jax.ShapeDtypeStruct((1, b), jnp.int32, sharding=one)
        lp = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)
        pre = engine._prefill(b)
        tmps.append(report(f"prefill {b}", pre.lower(params, toks, lp)
                           .compile()))
        logits0, cache = jax.eval_shape(pre, params, toks, lp)
        i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        row = jax.ShapeDtypeStruct((engine.recorder.max_gen,), jnp.int32,
                                   sharding=one)
        pt = jax.ShapeDtypeStruct((engine.pages_per_slot,), jnp.int32,
                                  sharding=one)
        tmps.append(report(f"insert {b}", engine._insert.lower(
            est, rst, on_chip(cache, one), on_chip(logits0, one), i32, i32,
            i32, i32, row, pt).compile()))
    e = mix["engine"]
    t_pad = e["max_prompt"] + e["max_gen"]
    seq = jax.ShapeDtypeStruct((t_pad,), jnp.int32, sharding=one)
    sz = tuple(sorted(ref.sizes(conf).items()))
    for low in (False, True):
        comp = ref.score.lower(params, seq, seq, sz=sz, low=low,
                               topk=e["topk"]).compile()
        report("reference" + (" control" if low else ""), comp)
    print(f"  serving peak estimate: weights + state + largest temp = "
          f"{(wbytes + sbytes + max(tmps)) / GB:.3f} GB", flush=True)


def train_cell(cell: harness.Cell, one) -> None:
    from repro.core import device_ledger as dledger

    from chipbench.drivers import train as train_driver

    ref = cell.reference()
    conf, job = cell.config, cell.traffic
    mcfg = harness.program_config(conf)
    wprog = ref.weights_program(conf)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    params = on_chip(jax.eval_shape(wprog, key), one)
    jstep, optimizer, lcfg = train_driver.build(job, mcfg)
    state = {"params": params,
             "opt": on_chip(jax.eval_shape(optimizer.init, params), one),
             "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=one)}
    led = on_chip(jax.eval_shape(lambda: dledger.init_state(lcfg)), one)
    b, s = job["global_batch"], job["seq_len"]
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one),
             "labels": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one),
             "instance_id": jax.ShapeDtypeStruct((b,), jnp.int32,
                                                 sharding=one)}
    rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)
    sbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree.leaves((state, led)))
    print(f"  state (weights, moments, ledger) {sbytes / GB:.3f} GB",
          flush=True)
    report("train step", jstep.lower(state, led, batch, rng).compile())
    kept = max(1, round(job["ratio"] * b))
    rows = jax.ShapeDtypeStruct((kept, s), jnp.int32, sharding=one)
    sz = tuple(sorted(ref.sizes(conf).items()))
    for low in (False, True):
        report("reference" + (" control" if low else ""),
               ref.loss_and_grad.lower(params, rows, rows, sz=sz, low=low)
               .compile())


def main(argv=None) -> int:
    from repro.kernels import ops

    # the program picks its kernels from the backend, which is the CPU
    # here: steer it to the chip's choice for these compiles
    ops.default_impl = lambda: "pallas"
    jax.config.update("jax_enable_compilation_cache", False)
    bench = harness.benchmark(ROOT)
    names = argv or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for name in names:
        cell = harness.Cell(bench, name, ROOT)
        print(f"{name} ({cell.traffic['driver']}, {cell.chips} chip):",
              flush=True)
        if cell.traffic["driver"] == "serve":
            serve_cell(cell, one)
        else:
            train_cell(cell, one)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
