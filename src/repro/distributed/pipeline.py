"""GPipe-style pipeline parallelism over a mesh axis (the cross-pod DCN
axis is the natural fit: one activation hop per microbatch per boundary,
vs per-layer collectives for TP/FSDP — PP is how the 2-pod mesh scales to
many pods without drowning the slow links).

Formulation (pure JAX, differentiable):
  * stage s owns a contiguous slice of the layer stack (params' leading
    layer axis sharded over the pipeline axis inside shard_map);
  * activations flow stage -> stage+1 via `lax.ppermute` inside a
    `lax.scan` over T = n_micro + n_stages - 1 ticks (the GPipe schedule,
    bubble included);
  * the BACKWARD schedule is not hand-written: ppermute and scan are
    differentiable, so `jax.grad` through `pipeline_apply` yields the
    reverse pipeline automatically (activation stash = scan residuals,
    i.e. 1F1B-style memory is a remat-policy choice).

`pipeline_apply` is the composable primitive; `make_pipeline_fn` wires it
to a stacked-params layer body. Tested end-to-end (values + grads) against
the sequential scan in tests/test_pipeline.py on a virtual 2x... mesh.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.compat import shard_map

Array = jax.Array


def pipeline_apply(
    body: Callable[[Any, Array], Array],
    stage_params: Any,  # leaves [layers_per_stage, ...] (this stage's slice)
    micro: Array,  # [n_micro, mb, ...] microbatched inputs (same on all stages)
    axis: str,  # pipeline mesh axis name (bound inside shard_map)
) -> Array:
    """Run the pipeline; every stage returns the final outputs [n_micro, ...]
    (identical on all stages — the last stage's results are broadcast back
    through the same ring, costing one extra ring pass)."""
    n_stages = jax.lax.axis_size(axis)
    stage = jax.lax.axis_index(axis)
    n_micro = micro.shape[0]
    ticks = n_micro + n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_fn(x):
        def layer(x, lp):
            return body(lp, x), None

        return jax.lax.scan(layer, x, stage_params)[0]

    def tick(carry, t):
        outs, prev = carry
        # stage 0 ingests microbatch t (when in range); others take the wire
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        x_in = jnp.where(stage == 0, micro[mb_idx], prev)
        y = stage_fn(x_in)
        # which microbatch did THIS stage just finish? m = t - stage
        m = t - stage
        valid = (m >= 0) & (m < n_micro)
        outs = jnp.where(
            valid & (stage == n_stages - 1),
            outs.at[jnp.clip(m, 0, n_micro - 1)].set(y),
            outs,
        )
        nxt = jax.lax.ppermute(y, axis, fwd_perm)
        return (outs, nxt), None

    outs0 = jnp.zeros_like(micro)
    prev0 = jnp.zeros_like(micro[0])
    (outs, _), _ = jax.lax.scan(
        tick, (outs0, prev0), jnp.arange(ticks)
    )
    # broadcast final outputs from the last stage to everyone (ring pass)
    def bring_home(o, _):
        return jax.lax.ppermute(o, axis, fwd_perm), None

    outs, _ = jax.lax.scan(bring_home, outs, None, length=1)
    # after 1 hop, stage 0 holds them; rotate stage-0's copy to all
    outs = jax.lax.all_gather(outs, axis)[0]
    return outs


def make_pipeline_fn(
    body: Callable[[Any, Array], Array],
    mesh: Mesh,
    axis: str,
    n_micro: int,
):
    """Build `f(stacked_params, x [B, ...]) -> y [B, ...]` running the layer
    stack as a pipeline over `axis`. B must divide by n_micro; the layer
    axis of every param leaf must divide by the stage count."""
    n_stages = mesh.shape[axis]

    def fn(params, x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        micro = x.reshape(n_micro, b // n_micro, *x.shape[1:])

        def inner(stage_params, micro_l):
            return pipeline_apply(body, stage_params, micro_l, axis)

        pspec = jax.tree.map(
            lambda p: P(axis, *([None] * (p.ndim - 1))), params
        )
        out = shard_map(
            inner,
            mesh=mesh,
            in_specs=(pspec, P()),
            out_specs=P(),
        )(params, micro)
        return out.reshape(b, *x.shape[1:])

    return fn
