"""Outcome recording for the serving engine: late labels -> ledger records.

The paper's serving-side contract is "the fleet already paid for the
forward; record a constant amount of per-instance information from it when
the outcome arrives". At engine granularity that means per-slot state, all
device-resident, in one of two retention modes:

* ``retention="topk"`` (the production mode) — per generated position keep
  ``(top-k values, top-k indices, exact lse)``: ``topk_vals`` [S, G, K]
  f32, ``topk_idx`` [S, G, K] i32, ``lse`` [S, G] f32, computed inside the
  fused decode step by the ``kernels.topk_lse`` streaming summary.
  Constant size in V: at V=152k / K=64 this is ~1100x smaller than the
  dense row (see :meth:`OutcomeRecorder.retained_bytes_per_slot`), which
  is what lets a fixed HBM budget hold 50x+ more concurrent slots. A late
  label is scored EXACTLY when it hits the top-k set (its logit was
  retained verbatim, and the lse is exact by construction); on a miss the
  loss is clamped to the tail floor ``lse - min(topk)`` — a certain lower
  bound, since the missed logit is <= every retained one. Recorded losses
  therefore never exceed the exact loss, and the ledger EMA drifts below
  the exact-scoring EMA by at most the largest per-position gap (EMA
  weights sum to <= 1). Misses are counted (``n_miss``).
* ``retention="full"`` (the oracle) — ``logits`` [S, G, V], the dense
  retained forwards. Exact on every label; the acceptance tests score the
  same schedule through both modes and bound the drift.

Common to both: ``labels`` [S, G] i32 (-1 = not yet known; delivered at
admission or any time later via :meth:`OutcomeRecorder.deliver`) and
``scored`` [S, G] (which positions already recorded). Retention is the
price of *late* outcomes — a label arriving after its position was
decoded is scored without a second forward; outcomes arriving after
eviction are dropped and counted.

Each fused engine step scores AT MOST ONE position per slot — the oldest
labeled-but-unscored one. One-per-step keeps every record a separate
ledger observation (the EMA compounds position by position, exactly like
the host ``LossHistory`` fed the same sequence) instead of collapsing a
batch of same-id records into last-write-wins; with labels delivered
promptly it drains at exactly the generation rate.

The ledger itself is placed by construction: a single device table
(``DeviceLedger`` layout), or a mesh-sharded one via
``sharded_ledger_ops`` — optionally *routed* (``route=True``), where each
record is exchanged to the shard owning its global slot before the table
visit, making the sharded table bit-identical to a single global table.
The record runs inside the engine's jitted step: the loss never touches
the host on its way to the ledger. A ``ledger="host"`` recorder computes
losses on device but leaves the table to a numpy ``LossHistory`` the
engine driver owns (the reference path).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.distributed.compat import shard_map

from repro import obs
from repro.core import device_ledger as dledger
from repro.core.history import HistoryConfig, LossHistory
from repro.distributed.ledger import ShardedLedgerOps, sharded_ledger_ops
from repro.kernels import ops as kops

Array = jax.Array
I32 = jnp.int32
F32 = jnp.float32

LEDGERS = ("host", "device")
RETENTIONS = ("full", "topk")


def topk_score(
    vals: Array, idx: Array, lse: Array, labels: Array
) -> tuple[Array, Array]:
    """Score labels against (top-k, lse) summaries -> (loss, hit).

    ``vals``/``idx`` [..., K], ``lse``/``labels`` [...]. Exact
    ``lse - logit[label]`` when the label is in the top-k set (``hit``);
    on a miss the loss is the tail floor ``lse - min(topk)``, a certain
    lower bound of the true loss (the missed logit is <= every retained
    one). Negative labels never hit (the recorder's -1 sentinel).
    """
    inset = idx == labels[..., None]  # [..., K]
    hit = inset.any(axis=-1) & (labels >= 0)
    picked = jnp.sum(jnp.where(inset, vals.astype(F32), 0.0), axis=-1)
    tail = jnp.min(vals.astype(F32), axis=-1)
    return lse.astype(F32) - jnp.where(hit, picked, tail), hit


def topk_signals(vals: Array, lse: Array) -> tuple[Array, Array]:
    """Serve-time signals from a (top-k values, exact lse) summary.

    ``vals`` [..., K] (sorted descending by the top-k kernel), ``lse``
    [...]. Returns ``(entropy, margin)``:

    * ``entropy`` — a certain LOWER bound of the predictive entropy
      ``sum_k p_k (lse - v_k) + p_tail (lse - min(topk))`` with
      ``p_k = exp(v_k - lse)``: the retained terms are exact and every
      tail token's surprisal ``lse - logit`` is >= the tail floor
      ``lse - min(topk)``, so the truncation only under-counts. Exact
      when the tail mass is zero (K = V).
    * ``margin`` — top-1/top-2 logit gap ``vals[..., 0] - vals[..., 1]``
      (0 when K < 2: a single retained logit carries no gap).

    Both are derived from data the recorder already retains — the
    signals are free at serving time (no extra forward work).
    """
    v = vals.astype(F32)
    lse = lse.astype(F32)
    p = jnp.exp(v - lse[..., None])  # [..., K]
    p_tail = jnp.maximum(1.0 - p.sum(axis=-1), 0.0)
    entropy = jnp.sum(p * (lse[..., None] - v), axis=-1) + p_tail * (
        lse - jnp.min(v, axis=-1)
    )
    if v.shape[-1] < 2:
        margin = jnp.zeros(lse.shape, F32)
    else:
        margin = v[..., 0] - v[..., 1]
    return entropy, margin


def full_signals(logits: Array, lse: Array) -> tuple[Array, Array]:
    """Exact (entropy, margin) from dense retained logits [..., V]."""
    x = logits.astype(F32)
    lse = lse.astype(F32)
    p = jax.nn.softmax(x, axis=-1)
    entropy = lse - jnp.sum(p * x, axis=-1)
    if x.shape[-1] < 2:
        margin = jnp.zeros(lse.shape, F32)
    else:
        top2 = jax.lax.top_k(x, 2)[0]
        margin = top2[..., 0] - top2[..., 1]
    return entropy, margin


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RecorderState:
    """Device state of the outcome recorder (a pytree; see module doc).

    Exactly one of (``logits``) / (``topk_vals``, ``topk_idx``, ``lse``)
    is populated, per the owning recorder's ``retention`` mode; the
    other mode's fields are None (absent pytree subtrees).
    """

    ledger: Optional[dledger.LedgerState]  # None for ledger="host"
    logits: Optional[Array]  # [S, G, V] retained forwards (retention="full")
    topk_vals: Optional[Array]  # [S, G, K] f32 (retention="topk")
    topk_idx: Optional[Array]  # [S, G, K] i32 (retention="topk")
    lse: Optional[Array]  # [S, G] f32 exact lse (retention="topk")
    labels: Array  # [S, G] i32, -1 = unknown
    scored: Array  # [S, G] bool
    n_recorded: Array  # [] i32: ledger records made (diagnostics)
    n_miss: Array  # [] i32: topk records clamped to the tail floor

    def tree_flatten(self):
        return (
            self.ledger, self.logits, self.topk_vals, self.topk_idx,
            self.lse, self.labels, self.scored, self.n_recorded,
            self.n_miss,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class OutcomeRecorder:
    """Owns ledger placement + the scoring/record pure functions.

    ``ledger="device"`` with a mesh gives the sharded table (``route=True``
    adds the cross-shard exchange); without a mesh, a single device table.
    ``ledger="host"`` keeps a numpy ``LossHistory`` — device scoring, host
    table (the engine records the step's (ids, losses, valid) into it).

    ``retention`` picks the retained-outcome layout (module doc):
    ``"full"`` the dense [S, G, V] oracle, ``"topk"`` the compressed
    (top-``topk`` values/indices, exact lse) summary, computed by
    ``kernels.ops.topk_lse`` (the Pallas kernel on a TPU).
    """

    def __init__(
        self,
        slots: int,
        max_gen: int,
        vocab: int,
        cfg: HistoryConfig = HistoryConfig(),
        *,
        ledger: str = "device",
        mesh: Optional[Mesh] = None,
        dp_axes: Sequence[str] = ("data",),
        route: bool = False,
        exchange: str = "gather",
        capacity_factor: float = 1.25,
        logits_dtype=jnp.float32,
        retention: str = "full",
        topk: int = 64,
    ):
        assert ledger in LEDGERS, ledger
        assert retention in RETENTIONS, retention
        self.slots = slots
        self.max_gen = max_gen
        self.vocab = vocab
        self.cfg = cfg
        self.ledger = ledger
        self.logits_dtype = jnp.dtype(logits_dtype)
        self.retention = retention
        self.topk = min(int(topk), vocab)
        if self.topk <= 0:
            raise ValueError(f"topk must be positive, got {topk}")
        self.ops: Optional[ShardedLedgerOps] = None
        self.host_history: Optional[LossHistory] = None
        if ledger == "device" and mesh is not None:
            self.ops = sharded_ledger_ops(
                mesh, cfg, dp_axes, route=route, exchange=exchange,
                capacity_factor=capacity_factor,
            )
            if slots % self.ops.shards:
                raise ValueError(
                    f"engine slots {slots} not divisible by "
                    f"{self.ops.shards} ledger shards"
                )
        elif ledger == "host":
            self.host_history = LossHistory(cfg)

    @property
    def route(self) -> bool:
        return self.ops is not None and self.ops.route

    def retained_bytes_per_slot(self) -> int:
        """HBM footprint of one slot's retained outcomes (labels/scored
        bookkeeping excluded — identical across modes)."""
        g = self.max_gen
        if self.retention == "full":
            return g * self.vocab * self.logits_dtype.itemsize
        # per position: K f32 values + K i32 indices + 1 f32 lse
        return g * (self.topk * (4 + 4) + 4)

    def _summarize(self, logits: Array) -> tuple[Array, Array, Array]:
        """[T, V] -> (vals [T,K], idx [T,K], lse [T]) via the fused kernel."""
        return self.per_device(lambda x: kops.topk_lse(x, self.topk))(
            logits.astype(F32)
        )

    # -- state ---------------------------------------------------------------

    def replicate(self, tree):
        """Place a pytree mesh-replicated (sharded recorders only): every
        array entering the engine's guarded fused step must already live
        on the mesh, or the jit boundary would need an implicit transfer —
        exactly what transfer_guard("disallow") rejects."""
        if self.ops is None:
            return tree
        sh = NamedSharding(self.ops.mesh, PartitionSpec())
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def per_device(self, fn):
        """``fn`` over mesh-replicated inputs, run whole on each device
        (sharded recorders only; otherwise ``fn`` itself). The compiler
        cannot partition a Pallas kernel on its own, and each device
        then runs the one-device program."""
        if self.ops is None:
            return fn
        return shard_map(fn, mesh=self.ops.mesh, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec())

    def init_state(self) -> RecorderState:
        s, g, v, k = self.slots, self.max_gen, self.vocab, self.topk
        if self.ledger == "host":
            led = None
        elif self.ops is not None:
            led = self.ops.init()
        else:
            led = dledger.init_state(self.cfg)
        full = self.retention == "full"
        return RecorderState(
            ledger=led,
            logits=self.replicate(jnp.zeros((s, g, v), self.logits_dtype))
            if full else None,
            topk_vals=None if full
            else self.replicate(jnp.zeros((s, g, k), F32)),
            topk_idx=None if full
            else self.replicate(jnp.full((s, g, k), -1, I32)),
            lse=None if full else self.replicate(jnp.zeros((s, g), F32)),
            labels=self.replicate(jnp.full((s, g), -1, I32)),
            scored=self.replicate(jnp.zeros((s, g), bool)),
            n_recorded=self.replicate(jnp.zeros((), I32)),
            n_miss=self.replicate(jnp.zeros((), I32)),
        )

    # -- pure functions (traced inside the engine's jitted step) -------------

    def clear_slot(
        self,
        state: RecorderState,
        slot: Array,
        logits0: Array,
        labels_row: Array,
    ) -> RecorderState:
        """Reset a slot at admission; position 0's logits come from prefill."""
        g, v, k = self.max_gen, self.vocab, self.topk
        if self.retention == "full":
            logits = state.logits.at[slot].set(
                jnp.zeros((g, v), self.logits_dtype)
            )
            retained = dict(
                logits=logits.at[slot, 0].set(
                    logits0.astype(self.logits_dtype)
                ),
            )
        else:
            v0, i0, l0 = self._summarize(logits0[None, :])
            retained = dict(
                topk_vals=state.topk_vals.at[slot]
                .set(jnp.zeros((g, k), F32)).at[slot, 0].set(v0[0]),
                topk_idx=state.topk_idx.at[slot]
                .set(jnp.full((g, k), -1, I32)).at[slot, 0].set(i0[0]),
                lse=state.lse.at[slot]
                .set(jnp.zeros((g,), F32)).at[slot, 0].set(l0[0]),
            )
        return dataclasses.replace(
            state,
            labels=state.labels.at[slot].set(labels_row.astype(I32)),
            scored=state.scored.at[slot].set(jnp.zeros((g,), bool)),
            **retained,
        )

    def observe(
        self, state: RecorderState, gen_idx: Array, logits: Array,
        writing: Array,
    ) -> RecorderState:
        """Retain this step's decode outcome summary at [slot, gen_idx]
        where ``writing``; masked rows scatter out of bounds and are
        dropped."""
        bidx = jnp.arange(self.slots)
        tgt = jnp.where(writing, gen_idx, self.max_gen)
        if self.retention == "full":
            return dataclasses.replace(
                state,
                logits=state.logits.at[bidx, tgt].set(
                    logits.astype(self.logits_dtype), mode="drop"
                ),
            )
        vals, idx, lse = self._summarize(logits)
        return dataclasses.replace(
            state,
            topk_vals=state.topk_vals.at[bidx, tgt].set(vals, mode="drop"),
            topk_idx=state.topk_idx.at[bidx, tgt].set(idx, mode="drop"),
            lse=state.lse.at[bidx, tgt].set(lse, mode="drop"),
        )

    def deliver(
        self, state: RecorderState, slot: Array, labels_row: Array
    ) -> RecorderState:
        """Write late-arriving labels for a slot (-1 entries leave the
        existing value — partial outcomes may arrive in pieces)."""
        labels_row = labels_row.astype(I32)
        cur = state.labels[slot]
        return dataclasses.replace(
            state,
            labels=state.labels.at[slot].set(
                jnp.where(labels_row >= 0, labels_row, cur)
            ),
        )

    def score_one(
        self,
        state: RecorderState,
        inst: Array,  # [S] i32, -1 = free slot
        produced: Array,  # [S] i32: generated positions with logits retained
        step: Array,  # scalar i32: ledger record step
    ) -> tuple[RecorderState, dict[str, Array]]:
        """Score the oldest labeled-but-unscored position of every slot.

        Returns the updated state and {loss, entropy, margin, valid,
        pending, miss}: per-slot loss of the scored position (``valid``
        marks slots that recorded one; ``miss`` the valid records
        clamped to the top-k tail floor — always all-False under
        retention="full") and ``pending`` — whether labeled-unscored
        positions remain (the drain signal eviction waits on).

        ``entropy``/``margin`` are the serve-time signal channels
        (``AUX_CHANNELS`` order) derived from the retained summary of
        the scored position — exact under retention="full", the
        certain entropy lower bound under "topk" (see
        :func:`topk_signals`). They ride the same ledger record as the
        loss: the whole derivation traces inside the engine's fused
        step, so nothing touches the host even under
        ``jax.transfer_guard("disallow")``.
        """
        s, g = self.slots, self.max_gen
        bidx = jnp.arange(s)
        giota = jnp.arange(g)[None, :]
        cand = (
            (state.labels >= 0)
            & ~state.scored
            & (giota < produced[:, None])
        )  # [S, G]
        has = cand.any(axis=1)
        pos = jnp.argmax(cand, axis=1)  # first True (0 if none; masked out)
        sel_label = jnp.take_along_axis(state.labels, pos[:, None], axis=1)[
            :, 0
        ]
        if self.retention == "full":
            sel_logits = jnp.take_along_axis(
                state.logits, pos[:, None, None], axis=1
            )[:, 0].astype(F32)  # [S, V]
            lse = jax.nn.logsumexp(sel_logits, axis=-1)
            picked = jnp.take_along_axis(
                sel_logits, jnp.maximum(sel_label, 0)[:, None], axis=-1
            )[:, 0]
            loss = lse - picked
            hit = jnp.ones((s,), bool)
            entropy, margin = full_signals(sel_logits, lse)
        else:
            sel_vals = jnp.take_along_axis(
                state.topk_vals, pos[:, None, None], axis=1
            )[:, 0]  # [S, K]
            sel_idx = jnp.take_along_axis(
                state.topk_idx, pos[:, None, None], axis=1
            )[:, 0]
            sel_lse = jnp.take_along_axis(state.lse, pos[:, None], axis=1)[
                :, 0
            ]
            loss, hit = topk_score(sel_vals, sel_idx, sel_lse, sel_label)
            entropy, margin = topk_signals(sel_vals, sel_lse)
        signals = jnp.stack([entropy, margin], axis=-1)  # AUX_CHANNELS
        valid = has & (inst >= 0)
        miss = valid & ~hit
        scored = state.scored.at[
            bidx, jnp.where(valid, pos, g)
        ].set(True, mode="drop")
        ledger = state.ledger
        a2a_overflow = jnp.zeros((), I32)
        if ledger is not None:
            if self.ops is not None:
                ledger, lstats = self.ops.record(
                    ledger, inst, loss, step, valid, signals=signals,
                    return_stats=True,
                )
                a2a_overflow = lstats["a2a_overflow"]
            else:
                ledger = dledger.record(
                    self.cfg, ledger, inst, loss, step, valid=valid,
                    signals=signals,
                )
        new = dataclasses.replace(
            state,
            ledger=ledger,
            scored=scored,
            n_recorded=state.n_recorded + valid.sum().astype(I32),
            n_miss=state.n_miss + miss.sum().astype(I32),
        )
        pending = (
            (new.labels >= 0) & ~new.scored & (giota < produced[:, None])
        ).any(axis=1)
        return new, {
            "loss": loss, "entropy": entropy, "margin": margin,
            "valid": valid, "pending": pending, "miss": miss,
            "a2a_overflow": a2a_overflow,
        }

    # -- host interchange ----------------------------------------------------

    def record_host(
        self, ids, losses, valid, step: int, signals=None
    ) -> None:
        """The ledger="host" record half (driver-side, numpy).

        ``signals`` is the optional [S, N_AUX] stack in ``AUX_CHANNELS``
        order from :meth:`score_one`'s info dict.
        """
        assert self.host_history is not None
        v = np.asarray(valid, bool)
        if v.any():
            with obs.span("recorder.record_host", n=int(v.sum())):
                self.host_history.record(
                    np.asarray(ids, np.int64)[v], np.asarray(losses)[v], step,
                    signals=None if signals is None
                    else np.asarray(signals, np.float32)[v],
                )

    def counters(self, state: RecorderState) -> tuple[int, int]:
        """(n_recorded, n_miss) as Python ints in ONE batched device_get —
        ``Engine.stats()`` calls this instead of fetching each scalar
        separately."""
        n_rec, n_miss = jax.device_get((state.n_recorded, state.n_miss))
        return int(n_rec), int(n_miss)

    def state_dict(self, state: RecorderState) -> dict[str, np.ndarray]:
        if self.ledger == "host":
            return self.host_history.state_dict()
        if self.ops is not None:
            return self.ops.state_dict(state.ledger)
        return dledger.state_dict_of(state.ledger)

    def load_state_dict(
        self, state: RecorderState, sd: dict[str, np.ndarray]
    ) -> RecorderState:
        if self.ledger == "host":
            self.host_history.load_state_dict(sd)
            return state
        if self.ops is not None:
            return dataclasses.replace(
                state, ledger=self.ops.load_state_dict(sd)
            )
        led = dledger.DeviceLedger(self.cfg)
        led.load_state_dict(dict(sd))
        return dataclasses.replace(state, ledger=led.state)
