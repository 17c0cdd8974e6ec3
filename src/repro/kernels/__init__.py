"""Pallas TPU kernels for the paper's compute hot-spots.

xent        — fused per-token CE over blocked vocab: makes "record a loss
              from every forward" ~free at 128k-152k vocabs (the paper's
              §1 production insight, adapted to TPU memory hierarchy).
decode_attn — flash decode attention: the serving forward whose losses
              OBFTF recycles.
ssd         — Mamba2 chunk scan (assigned ssm/hybrid architectures).
ledger      — fused recycle-ledger record+priority: one VMEM residency per
              batch for the device ledger's hash + EMA scatter + score
              (repro.core.device_ledger dispatches here on a TPU).

Each kernel: <name>.py (pl.pallas_call + BlockSpec), ref.py oracle entry,
ops.py jit'd wrapper with backend dispatch + custom_vjp. ``default_impl``
is the one dispatch rule: the Pallas kernels on a TPU, the jnp oracle
elsewhere.
"""

from repro.kernels import ops  # noqa: F401
from repro.kernels.ops import (  # noqa: F401
    default_impl,
    ssd_scan,
    xent_loss,
)
# NB: ops.decode_attn is NOT re-exported here — it would shadow the
# repro.kernels.decode_attn submodule. Use repro.kernels.ops.decode_attn.
