"""The one place this repo calls ``jax.shard_map`` and mesh-axis queries.

Call sites go through :func:`shard_map` (a fixed keyword signature with the
replication check off by default) and :func:`linear_axis_index` instead of
spelling the JAX calls out each time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def linear_axis_index(axes):
    """This shard's rank in the row-major flattening of ``axes`` (inside
    shard_map). Matches the segment order of tiled collectives
    (``all_gather(..., tiled=True)``) and of a global batch sharded over
    the same axes — the alignment both shard-local selection and ledger
    routing depend on."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check,
    )
