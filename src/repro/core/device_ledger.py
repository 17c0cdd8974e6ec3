"""Device-resident recycle ledger: ``LossHistory`` as pure JAX ops.

The host-side ``repro.core.history.LossHistory`` is the paper's "record a
constant amount of information per instance" store, but looking it up from a
train step costs a device->host->device round-trip per batch. This module is
the production port: the same fixed-capacity EMA table held as device arrays,
with ``record`` / ``lookup`` / ``priority`` as jittable pure functions
(scatter-EMA write, hash-probe read, staleness-boosted score) that fuse into
the OBFTF step — the recycle signal never leaves the accelerator.

Addressing is shared with the host ledger (``history.slot_for``, 32-bit
Fibonacci hash), so ``state_dict`` round-trips between the two: the numpy
ledger stays the reference implementation and checkpoint interchange format.
Collision semantics match exactly, including deterministic last-write-wins
on intra-batch slot collisions (numpy fancy-assignment order).

Sharding: ``repro.distributed.ledger`` maps these ops over the data axes
with each shard owning a slice of the table, so capacity scales with the
mesh instead of host RAM. The fused ``record_priority`` additionally has a
Pallas kernel (``repro.kernels.ledger``), taken by default on a TPU.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.history import (  # noqa: F401  (rehash re-exported: it is
    AUX_CHANNELS,  # the migration half of this module's state_dict
    FIB32,  # interchange)
    N_AUX,
    HistoryConfig,
    LossHistory,
    rehash_state_dict,
    slot_for,
)

Array = jax.Array
I32 = jnp.int32
F32 = jnp.float32


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LedgerState:
    """The ledger table as a pytree of device arrays.

    ``count``/``last_seen``/``owner`` are int32 on device (JAX x32); the
    host interchange format is int64. Ids are keyed by their low 32 bits.
    """

    ema: Array  # [capacity] f32
    count: Array  # [capacity] i32
    last_seen: Array  # [capacity] i32, -1 = never
    owner: Array  # [capacity] i32, -1 = empty
    sig: Array  # [capacity, N_AUX] f32 aux channels (history.AUX_CHANNELS)

    def tree_flatten(self):
        return (
            self.ema, self.count, self.last_seen, self.owner, self.sig,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.ema.shape[0]


def init_state(cfg: HistoryConfig) -> LedgerState:
    assert cfg.capacity & (cfg.capacity - 1) == 0, "capacity must be 2^k"
    n = cfg.capacity
    return LedgerState(
        ema=jnp.zeros((n,), F32),
        count=jnp.zeros((n,), I32),
        last_seen=jnp.full((n,), -1, I32),
        owner=jnp.full((n,), -1, I32),
        sig=jnp.zeros((n, N_AUX), F32),
    )


def slot_for_jnp(ids: Array, capacity: int) -> Array:
    """jnp twin of ``history.slot_for`` — bit-identical for any int input."""
    x = ids.astype(I32).astype(jnp.uint32)  # low 32 bits, like numpy's view
    h = x * jnp.uint32(FIB32)
    h = h ^ (h >> jnp.uint32(16))
    return (h & jnp.uint32(capacity - 1)).astype(I32)


def _winner_mask(
    slots: Array, capacity: int, order: Optional[Array] = None
) -> Array:
    """True for the last batch item targeting each slot (numpy fancy-index
    semantics: with duplicate slots the last write wins, deterministically —
    plain ``.at[].set`` with duplicates is unspecified in XLA). Items whose
    slot is already OOB (masked-out writes) never win.

    ``order`` (i32 [B], optional) overrides the in-batch position as the
    winner key: the item with the LARGEST order value wins its slot. The
    routed all_to_all exchange uses this to record a batch that arrives
    re-binned (a2a-received items + overflow-fallback items, concatenated)
    under the ORIGINAL global batch order, keeping the write bit-identical
    to recording the un-binned batch. Order keys must be unique among
    items that can share a slot.
    """
    if order is None:
        order = jnp.arange(slots.shape[0], dtype=I32)
    last = jnp.full((capacity,), -1, I32).at[slots].max(order, mode="drop")
    return (slots < capacity) & (last[slots] == order)


def record(
    cfg: HistoryConfig,
    state: LedgerState,
    ids: Array,
    losses: Array,
    step,
    valid: Optional[Array] = None,
    signals: Optional[Array] = None,
    order: Optional[Array] = None,
) -> LedgerState:
    """Pure scatter-EMA write; semantics identical to ``LossHistory.record``.

    ``valid`` (bool [B], optional) drops masked-out items entirely — they
    neither write nor participate in intra-batch last-write-wins. Equivalent
    to recording only the valid subset, with static shapes (needed both for
    "record only the fresh per-example losses" at train time and for the
    routed sharded ledger, where each shard records only the ids homed to
    it out of a globally gathered batch).

    ``order`` (i32 [B], optional) overrides the in-batch position as the
    last-write-wins key (see ``_winner_mask``): the all_to_all exchange
    records items out of their global batch order and passes the global
    indices here so duplicate-slot resolution stays bit-identical to the
    single global table. The per-item EMA/count math is elementwise, so
    only the winner choice depends on it.

    ``signals`` (optional [B, N_AUX] f32, ``history.AUX_CHANNELS`` order)
    EMAs the auxiliary channels under the same decay/ownership rules.
    Without it, same-owner records leave the channels untouched (train-side
    loss records must not erase the serve-side signal); evicting records
    zero them (the new owner has no signal yet).
    """
    ids = jnp.asarray(ids).astype(I32)
    losses = jnp.asarray(losses).astype(F32)
    slots = slot_for_jnp(ids, state.capacity)
    fresh = state.owner[slots] != ids
    d = cfg.decay
    prev = jnp.where(fresh, losses, state.ema[slots])
    new_ema = d * prev + (1.0 - d) * losses
    new_count = jnp.where(fresh, 1, state.count[slots] + 1)
    if signals is None:
        new_sig = jnp.where(fresh[:, None], 0.0, state.sig[slots])
    else:
        signals = jnp.asarray(signals).astype(F32).reshape(
            ids.shape[0], N_AUX
        )
        prev_sig = jnp.where(fresh[:, None], signals, state.sig[slots])
        new_sig = d * prev_sig + (1.0 - d) * signals
    if valid is not None:
        # invalid items hash OOB: dropped by the scatter AND by the winner
        # computation (a masked write must not shadow a valid one)
        slots = jnp.where(jnp.asarray(valid, bool), slots, state.capacity)
    keep = _winner_mask(slots, state.capacity, order=order)
    tgt = jnp.where(keep, slots, state.capacity)  # OOB scatters are dropped
    step32 = jnp.asarray(step).astype(I32)
    return LedgerState(
        ema=state.ema.at[tgt].set(new_ema, mode="drop"),
        count=state.count.at[tgt].set(new_count, mode="drop"),
        last_seen=state.last_seen.at[tgt].set(
            jnp.broadcast_to(step32, tgt.shape), mode="drop"
        ),
        owner=state.owner.at[tgt].set(ids, mode="drop"),
        sig=state.sig.at[tgt].set(new_sig, mode="drop"),
    )


LOOKUP_VARIANTS = ("gather", "onehot")


def lookup(
    state: LedgerState, ids: Array, variant: str = "gather"
) -> tuple[Array, Array]:
    """Hash-probe read -> (ema_loss f32, seen_mask bool).

    ``variant`` selects how the EMA column is read:

    * ``"gather"`` — ``state.ema[slots]``, a [B]-row gather. On TPU this
      lowers to VPU dynamic-slice/select work proportional to B*C.
    * ``"onehot"`` — ``one_hot(slots, C) @ state.ema``, the same read as
      one [B, C] x [C] MXU matmul (the ROADMAP "replace VPU-select
      gathers with one-hot matmuls" item). Bit-identical to the gather:
      each one-hot row has exactly one 1.0, so every product term is
      either the exact table value or exactly 0.0 and float addition of
      zeros is exact. The ``owner`` probe (int compare) stays a gather —
      only the f32 column rides the MXU.
    """
    if variant not in LOOKUP_VARIANTS:
        raise ValueError(f"lookup variant {variant!r} not in "
                         f"{LOOKUP_VARIANTS}")
    ids = jnp.asarray(ids).astype(I32)
    slots = slot_for_jnp(ids, state.capacity)
    seen = state.owner[slots] == ids
    if variant == "onehot":
        oh = (
            slots[:, None] == jnp.arange(state.capacity, dtype=I32)[None, :]
        ).astype(F32)
        ema = oh @ state.ema
    else:
        ema = state.ema[slots]
    return jnp.where(seen, ema, 0.0).astype(F32), seen


def lookup_signals(
    state: LedgerState, ids: Array
) -> tuple[Array, Array, Array]:
    """Hash-probe read -> (ema [B], sig [B, N_AUX], seen [B]).

    The multi-channel twin of ``lookup`` — one hash, one table visit for
    every channel a selection policy might consume (feed the triple to
    ``selection.policy_score``). Unseen rows are 0.
    """
    ids = jnp.asarray(ids).astype(I32)
    slots = slot_for_jnp(ids, state.capacity)
    seen = state.owner[slots] == ids
    ema = jnp.where(seen, state.ema[slots], 0.0).astype(F32)
    sig = jnp.where(seen[:, None], state.sig[slots], 0.0).astype(F32)
    return ema, sig, seen


def priority(cfg: HistoryConfig, state: LedgerState, ids: Array, step) -> Array:
    """Staleness-boosted score, identical to ``LossHistory.priority``."""
    ids = jnp.asarray(ids).astype(I32)
    slots = slot_for_jnp(ids, state.capacity)
    seen = state.owner[slots] == ids
    step32 = jnp.asarray(step).astype(I32)
    age = jnp.maximum(step32 - state.last_seen[slots], 0).astype(F32)
    boost = jnp.exp2(age / cfg.staleness_half_life)
    score = state.ema[slots] * boost
    return jnp.where(seen, score, cfg.unseen_priority).astype(F32)


def _sig_scatter(
    cfg: HistoryConfig,
    state: LedgerState,
    ids: Array,
    signals: Optional[Array],
    valid: Optional[Array],
) -> Array:
    """The ``sig``-channel half of ``record`` in isolation — used when the
    other four arrays go through the Pallas kernel (which predates the
    signal store and stays a 4-array scatter); same slots, same ownership
    and winner semantics, so the fused path stays bit-identical to ref."""
    ids = jnp.asarray(ids).astype(I32)
    slots = slot_for_jnp(ids, state.capacity)
    fresh = state.owner[slots] != ids
    if signals is None:
        new_sig = jnp.where(fresh[:, None], 0.0, state.sig[slots])
    else:
        signals = jnp.asarray(signals).astype(F32).reshape(
            ids.shape[0], N_AUX
        )
        prev_sig = jnp.where(fresh[:, None], signals, state.sig[slots])
        new_sig = cfg.decay * prev_sig + (1.0 - cfg.decay) * signals
    if valid is not None:
        slots = jnp.where(jnp.asarray(valid, bool), slots, state.capacity)
    keep = _winner_mask(slots, state.capacity)
    tgt = jnp.where(keep, slots, state.capacity)
    return state.sig.at[tgt].set(new_sig, mode="drop")


def record_priority(
    cfg: HistoryConfig,
    state: LedgerState,
    ids: Array,
    losses: Array,
    step,
    valid: Optional[Array] = None,
    impl: Optional[str] = None,
    signals: Optional[Array] = None,
) -> tuple[LedgerState, Array]:
    """Fused write+score: record the batch, return post-record priorities.

    Equivalent to ``record`` (honoring the optional ``valid`` write mask
    and the optional ``signals`` channels) followed by ``priority`` over
    ALL ids at the same step, in one pass (one hash, one table visit).
    ``impl`` overrides the platform's backend as in ``repro.kernels.ops``
    ("ref" = the jnp path below, "pallas"/"interpret" = the fused Pallas
    kernel, the default on a TPU; the kernel covers the four scalar-channel
    arrays and the ``sig`` channels ride the jnp scatter alongside it).
    """
    from repro.kernels import ops as kops

    if kops.resolve(impl) != "ref":
        sig = _sig_scatter(cfg, state, ids, signals, valid)
        ema, count, last_seen, owner, pri = kops.ledger_record_priority(
            state.ema,
            state.count,
            state.last_seen,
            state.owner,
            jnp.asarray(ids).astype(I32),
            jnp.asarray(losses).astype(F32),
            jnp.asarray(step).astype(I32),
            decay=cfg.decay,
            unseen_priority=cfg.unseen_priority,
            staleness_half_life=cfg.staleness_half_life,
            valid=valid,
            impl=impl,
        )
        return LedgerState(ema, count, last_seen, owner, sig), pri
    new = record(cfg, state, ids, losses, step, valid=valid, signals=signals)
    return new, priority(cfg, new, ids, step)


def state_dict_of(state: LedgerState) -> dict[str, np.ndarray]:
    """Export a ``LedgerState`` in the ``LossHistory`` checkpoint format
    (int64 host dtypes) — the .npz interchange shared by serve's
    ``--ledger-out``, train's ``--ledger-in`` and checkpoint restore."""
    return {
        "ema": np.asarray(state.ema, np.float32),
        "count": np.asarray(state.count, np.int64),
        "last_seen": np.asarray(state.last_seen, np.int64),
        "owner": np.asarray(state.owner, np.int64),
        "sig": np.asarray(state.sig, np.float32),
    }


def state_from_dict(sd: dict[str, np.ndarray]) -> LedgerState:
    """Load the host interchange format back into device arrays (dicts
    written before the signal channels existed get sig = 0)."""
    n = np.asarray(sd["ema"]).shape[0]
    sig = np.asarray(
        sd.get("sig", np.zeros((n, N_AUX))), np.float32
    )
    return LedgerState(
        ema=jnp.asarray(np.asarray(sd["ema"], np.float32)),
        count=jnp.asarray(np.asarray(sd["count"]).astype(np.int32)),
        last_seen=jnp.asarray(np.asarray(sd["last_seen"]).astype(np.int32)),
        owner=jnp.asarray(np.asarray(sd["owner"]).astype(np.int32)),
        sig=jnp.asarray(sig),
    )


class DeviceLedger:
    """Object wrapper mirroring the ``LossHistory`` API on device arrays.

    Methods are jitted; the held state never leaves the device except via
    ``state_dict()`` (the host interchange path). Use the pure functions
    above to fuse ledger ops into a larger jitted step.
    """

    def __init__(self, cfg: HistoryConfig = HistoryConfig()):
        self.cfg = cfg
        self.state = init_state(cfg)
        self._record = jax.jit(partial(record, cfg), donate_argnums=(0,))
        self._lookup = jax.jit(lookup, static_argnames=("variant",))
        self._lookup_signals = jax.jit(lookup_signals)
        self._priority = jax.jit(partial(priority, cfg))

    # -- LossHistory-compatible surface ------------------------------------

    def record(self, ids, losses, step, valid=None, signals=None) -> None:
        self.state = self._record(
            self.state, ids, losses, step, valid, signals
        )

    def lookup(self, ids, variant: str = "gather") -> tuple[Array, Array]:
        return self._lookup(self.state, ids, variant=variant)

    def lookup_signals(self, ids) -> tuple[Array, Array, Array]:
        return self._lookup_signals(self.state, ids)

    def priority(self, ids, step) -> Array:
        return self._priority(self.state, ids, step)

    def record_priority(
        self, ids, losses, step, valid=None, impl=None, signals=None
    ) -> Array:
        self.state, pri = record_priority(
            self.cfg, self.state, ids, losses, step, valid=valid, impl=impl,
            signals=signals,
        )
        return pri

    # -- host interchange ---------------------------------------------------

    def state_dict(self) -> dict[str, np.ndarray]:
        """Export in the ``LossHistory`` checkpoint format (int64 host dtypes)."""
        return state_dict_of(self.state)

    def load_state_dict(self, state: dict[str, np.ndarray]) -> None:
        state = dict(state)
        foreign = state.pop("pinned_shards", None) is not None
        n = np.asarray(state["ema"]).shape[0]
        if foreign or n != self.cfg.capacity:  # layout change: re-hash
            state = rehash_state_dict(state, self.cfg.capacity)
        self.state = state_from_dict(state)

    @classmethod
    def from_host(cls, history: LossHistory) -> "DeviceLedger":
        led = cls(history.cfg)
        led.load_state_dict(history.state_dict())
        return led

    def to_host(self) -> LossHistory:
        h = LossHistory(self.cfg)
        h.load_state_dict(self.state_dict())
        return h
