"""Production mesh construction + elastic validation.

The target is TPU v5e: 16x16 = 256 chips per pod, 2 pods over DCN for the
multi-pod dry-run. Axes:

  pod   — DCN dimension: pure data parallelism, gradient all-reduce only
          (int8-compressed, see repro.distributed.compression)
  data  — in-pod DP/FSDP: batch + FSDP weight shards + ZeRO-1 moments
  model — in-pod TP/EP/SP: heads, FFN, experts, vocab, decode-cache seq

`make_production_mesh` is a FUNCTION (never module-level state) so imports
don't touch jax device init. `make_elastic_mesh` builds a best mesh from
whatever devices exist — the elasticity entry point: on a resize the
launcher rebuilds the mesh, revalidates divisibility, and reshards from
checkpoint (parameters are saved layout-independent).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

from repro.distributed.sharding import AxisRules, DEFAULT_RULES

POD_SHAPE = (16, 16)  # 256 chips / pod (v5e)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def production_rules(*, multi_pod: bool = False) -> AxisRules:
    import dataclasses

    return dataclasses.replace(
        DEFAULT_RULES,
        batch_axes=("pod", "data") if multi_pod else ("data",),
    )


def make_elastic_mesh(
    devices: Optional[Sequence] = None, model_parallel: int = 1
) -> Mesh:
    """Best (data, model) mesh from the devices that are actually up.

    `model_parallel` pins the TP degree; the DP degree absorbs the rest, so
    a job restarted with fewer healthy hosts keeps running (smaller batch
    or more grad accumulation — the train loop recomputes per-shard batch).
    The default, pure data parallelism, gives every device its own "data"
    shard: the axis the batch, the FSDP weights and the sharded recycle
    ledger split over.
    """
    import numpy as np

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if model_parallel < 1 or n % model_parallel:
        raise ValueError(f"{n} devices not divisible by TP={model_parallel}")
    arr = np.asarray(devices).reshape(n // model_parallel, model_parallel)
    return Mesh(arr, ("data", "model"))


def validate_batch(global_batch: int, mesh: Mesh, batch_axes: Sequence[str]):
    shards = math.prod(mesh.shape[a] for a in batch_axes)
    if global_batch % shards:
        raise ValueError(
            f"global batch {global_batch} not divisible by {shards} "
            f"data shards (mesh {dict(mesh.shape)}); adjust batch or mesh"
        )
    return global_batch // shards
