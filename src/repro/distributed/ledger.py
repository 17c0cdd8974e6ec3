"""Mesh-sharded recycle ledger: each data shard owns a slice of the table.

The device ledger (`repro.core.device_ledger`) holds one [capacity] table.
At scale that table should grow with the fleet, not with one chip's HBM:
here the table is laid out along the data axes — shard s owns a
[C/S]-slot slice — and every ledger op runs inside ``shard_map`` over
those axes. Total capacity scales linearly with the data-parallel degree
and the recycle signal never touches the host. Two id-placement modes:

* **pinned** (``route=False``): ids hash into the shard-local slice, so
  ``record``/``lookup``/``priority`` are zero-communication — an
  instance's record lives on the shard that consumed it, which is exactly
  the shard that will see it again *when the feed pins each id to a fixed
  data shard* (a production feed keyed by a stable partitioner).

* **routed** (``route=True``): before the local table visit, each batch
  item is exchanged to the shard that owns its GLOBAL slot —
  ``home = slot_for(id, C) // (C/S)`` — so feeds that do NOT pin
  instances to a shard still hit their records. Routing makes the sharded
  table bit-identical to the single global table: shard s's slice IS
  global slots [s*C/S, (s+1)*C/S) — because ``slot_for(id, C/S)`` equals
  ``slot_for(id, C) mod C/S``, the local hash lands every routed record
  at its global offset.

  Two exchange realizations (``exchange=``), identical results:

  - ``"gather"`` — all_gather + home-mask: every shard replicates every
    other shard's batch and visits its own items; lookup answers return
    via a masked psum. Exact for arbitrarily imbalanced hash
    distributions, but moves O(S*b) payload per op — every shard pays
    for the whole global batch.

  - ``"a2a"`` — MoE-style capacity-factor dispatch (the GShard cumsum
    position-assignment idiom, see ``models/moe.py``): each shard bins
    its items by home shard into per-destination send buffers of
    ``cap = ceil(b * capacity_factor / S)`` rows, ships them with ONE
    ``lax.all_to_all``, visits the table on the home shard, and returns
    answers with a second all_to_all — O(b * capacity_factor) payload
    per op instead of O(S*b). Items past a destination's capacity
    (hash skew) are resolved EXACTLY by a residual gather round — one
    ``lax.cond``-gated all_gather + masked psum covering only the
    overflow set, entered by all shards together iff any shard
    overflowed (the predicate is a psum, hence replicated) — and counted
    in the op's ``a2a_overflow`` stat. Records re-binned this way carry
    their GLOBAL batch index as the last-write-wins key (``order=`` in
    ``device_ledger.record``), so the a2a table stays bit-identical to
    the gather exchange and to the single global table: no dropped
    records, ever. See ``exchange_bytes_per_op`` for the crossover
    accounting ``selection_bench`` reports.

The addressing consequence: a *routed* sharded ledger's ``state_dict`` is
the plain global interchange format (concatenation of the slices), and
migrating between shard counts is a lossless reshape. A *pinned* ledger's
records sit on consumer shards instead of hash-home shards, so exporting
one re-hashes every record into the global layout (recency wins on
collisions) — see ``merge_shard_state_dicts`` / ``split_state_dict``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.device_ledger import (
    LedgerState,
    init_state,
    lookup,
    lookup_signals,
    priority,
    record,
    record_priority,
    rehash_state_dict,
    slot_for_jnp,
    state_dict_of,
    state_from_dict,
)
from repro.core.history import HistoryConfig
from repro.distributed.compat import linear_axis_index, shard_map

I32 = jnp.int32

EXCHANGES = ("gather", "a2a")


def a2a_capacity(batch: int, shards: int, capacity_factor: float) -> int:
    """Per-destination send-buffer rows for one shard's batch of ``batch``
    items: ``max(1, ceil(batch * capacity_factor / shards))``. At
    ``capacity_factor >= shards`` every possible binning fits (cap >= b)
    and the overflow fallback is statically unreachable."""
    if capacity_factor <= 0:
        raise ValueError(f"capacity_factor must be > 0, got {capacity_factor}")
    return max(1, int(np.ceil(batch * capacity_factor / shards)))


def bin_by_home(
    home: jax.Array, n_shards: int, capacity: int,
    active: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """GShard cumsum position assignment: bin items by ``home`` shard into
    ``capacity`` send-buffer rows per destination, earlier items first.

    Returns ``(pos, kept, overflow)``: ``pos`` [B] i32 — the item's row
    within its home's capacity bucket (its rank among same-home active
    items, meaningful only where ``kept``); ``kept`` [B] — active items
    that won a row; ``overflow`` [B] — active items past capacity (the
    residual set the exact fallback round resolves). ``active`` (bool [B],
    default all) excludes items from binning entirely — they are neither
    kept nor overflow and consume no capacity (the record path passes its
    ``valid`` mask here so masked-out writes never crowd out real ones).

    Invariants (pinned by the hypothesis property test): kept and
    overflow partition the active set; within each home the kept
    positions are exactly 0..k-1 with k <= capacity; permuting the batch
    permutes kept ∪ overflow identically (the SPLIT may differ — earlier
    items win capacity — but no item is ever lost or duplicated).
    """
    if active is None:
        active = jnp.ones(home.shape, bool)
    oh = (home[:, None] == jnp.arange(n_shards, dtype=home.dtype)[None, :])
    oh = (oh & active[:, None]).astype(I32)  # [B, S]
    pos = jnp.cumsum(oh, axis=0) - oh  # items before me with my home
    pos = jnp.sum(pos * oh, axis=1).astype(I32)
    kept = active & (pos < capacity)
    return pos, kept, active & ~kept


def exchange_bytes_per_op(
    exchange: str,
    shards: int,
    batch: int,
    capacity_factor: float = 1.25,
    item_bytes: int = 16,
    overflow: bool = False,
) -> int:
    """Analytic per-shard exchange payload of ONE routed ledger op.

    ``item_bytes`` is the per-item payload a record ship carries (id i32 +
    order i32 + loss f32 + valid i32 = 16); the return direction is
    counted at the same width, so both modes price a full round trip:

    * ``gather`` — every op replicates the global batch (all_gather of
      S*b items) and answers come back over the same S*b lanes (masked
      psum): ``2 * S * b * item_bytes``, independent of load balance.
    * ``a2a`` — two all_to_alls of ``S * cap`` rows with
      ``cap = a2a_capacity(b, S, cf)``, i.e. ~``2 * b * cf * item_bytes``
      — constant in S for fixed per-shard batch. When ``overflow`` the
      cond-gated residual round adds one full gather-mode round trip (the
      fallback IS the gather exchange, applied to the overflow set; the
      collective still moves S*b lanes). Zero-overflow steps never pay it.

    The crossover: a2a wins iff ``capacity_factor < shards`` (strictly,
    on overflow-free steps) — at S=4, cf=1.25 it moves ~3.2x fewer
    bytes, and the gap widens linearly with the mesh.
    """
    if exchange not in EXCHANGES:
        raise ValueError(f"exchange {exchange!r} not in {EXCHANGES}")
    gather_round = 2 * shards * batch * item_bytes
    if exchange == "gather":
        return gather_round
    cap = a2a_capacity(batch, shards, capacity_factor)
    n = 2 * shards * cap * item_bytes
    return n + (gather_round if overflow else 0)


def _host_span(name: str, ids, **args):
    """A telemetry span only when dispatching from host Python. These ops
    also trace INSIDE fused jits (the engine step / train step call
    ``record`` through ``recorder.score_one``), where opening a span would
    time the trace once and record nothing at run time — a call whose
    ``ids`` are a tracer gets the shared null span instead."""
    if isinstance(ids, jax.core.Tracer):
        return obs.NULL_SPAN
    return obs.span(name, cat="ledger", **args)


@dataclasses.dataclass(frozen=True)
class ShardedLedgerOps:
    """Jittable ledger ops closed over (mesh, dp_axes, per-shard config).

    All entry points take/return a ``LedgerState`` whose arrays are sharded
    ``P(dp_axes)`` along the slot axis; ids/losses are sharded the same way
    along the batch axis. Fuse these into a jitted train step — nothing
    here ever leaves the device. With ``route=True`` every op first
    exchanges batch items to their home shard (see module docstring).
    """

    mesh: Mesh
    dp_axes: tuple[str, ...]
    cfg: HistoryConfig  # global config; capacity = global slots
    local_cfg: HistoryConfig  # per-shard slice config
    route: bool = False
    exchange: str = "gather"  # routed-mode realization: "gather" | "a2a"
    capacity_factor: float = 1.25  # a2a send-buffer slack (GShard-style)

    @property
    def shards(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    def _state_spec(self):
        # every table array shards along its leading (slot) axis — the 2-D
        # ``sig`` [slots, N_AUX] included (P over axis 0 only)
        dp = P(tuple(self.dp_axes))
        return LedgerState(dp, dp, dp, dp, dp)

    def _wrap(self, fn, n_batch_args, out_specs):
        dp = P(tuple(self.dp_axes))
        in_specs = (self._state_spec(),) + (dp,) * n_batch_args + (P(),)
        return shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs
        )

    # -- routing helpers (traced inside shard_map) --------------------------

    def _home(self, ids: jax.Array) -> jax.Array:
        """Global-layout owner shard of each id: slot_for(id, C) // (C/S)."""
        return slot_for_jnp(ids, self.cfg.capacity) // self.local_cfg.capacity

    def _exchange(self, *per_shard: jax.Array):
        """The routing hop: gather every shard's batch (tiled, shard-major
        — the global batch order) and mark the items homed to this shard."""
        ax = tuple(self.dp_axes)
        gathered = [
            jax.lax.all_gather(x, ax, tiled=True) for x in per_shard
        ]
        mine = self._home(gathered[0]) == linear_axis_index(self.dp_axes)
        return (*gathered, mine)

    def _return_route(self, values: jax.Array, mine: jax.Array, b: int):
        """Send each answer back to the shard that asked: exactly one shard
        has ``mine`` set per item, so a masked psum is the inverse
        exchange; then slice this shard's segment of the global batch.
        ``values`` may carry trailing channel axes ([B] or [B, N_AUX]);
        ``mine`` masks the leading batch axis."""
        zero = jnp.zeros((), values.dtype)
        mask = mine.reshape(mine.shape + (1,) * (values.ndim - 1))
        total = jax.lax.psum(
            jnp.where(mask, values, zero), tuple(self.dp_axes)
        )
        start = linear_axis_index(self.dp_axes) * b
        return jax.lax.dynamic_slice_in_dim(total, start, b, axis=0)

    # -- a2a exchange helpers (traced inside shard_map) ----------------------

    @property
    def _a2a(self) -> bool:
        return self.route and self.exchange == "a2a"

    def _a2a_dispatch(self, ids, payloads=(), active=None):
        """Bin this shard's batch by home shard into capacity-bounded send
        buffers (``bin_by_home``) and ship ids + global-order keys + the
        payloads with one tiled all_to_all. Returns a dict:

        * ``recv_ids``/``recv_ord``/``recv`` — [S*cap] home-side buffers;
          ``recv_ord`` holds global batch indices, -1 marking unfilled
          rows (a destination that got fewer than cap items);
        * ``home``/``pos``/``kept``/``overflow``/``cap`` — the sender-side
          binning, for collecting answers and the residual round;
        * ``n_ovf`` — psum of the overflow count: replicated, so it can
          gate the fallback ``lax.cond`` (all shards branch together) and
          surface as the op's ``a2a_overflow`` stat.
        """
        ax = tuple(self.dp_axes)
        S = self.shards
        b = ids.shape[0]
        cap = a2a_capacity(b, S, self.capacity_factor)
        home = self._home(ids)
        pos, kept, overflow = bin_by_home(home, S, cap, active=active)
        # one-past-end target for non-kept rows: scatters there are
        # dropped (never -1, which wraps numpy-style before "drop")
        tgt = jnp.where(kept, home * cap + pos, S * cap)
        order = (linear_axis_index(self.dp_axes) * b
                 + jnp.arange(b, dtype=I32))

        def ship(x, init):
            buf = jnp.full((S * cap,) + x.shape[1:], init, x.dtype)
            return jax.lax.all_to_all(
                buf.at[tgt].set(x, mode="drop"), ax, 0, 0, tiled=True
            )

        return dict(
            cap=cap, home=home, pos=pos, kept=kept, overflow=overflow,
            recv_ids=ship(ids, 0),
            recv_ord=ship(order, -1),
            recv=tuple(ship(p, jnp.zeros((), p.dtype)) for p in payloads),
            n_ovf=jax.lax.psum(overflow.sum().astype(I32), ax),
        )

    def _a2a_collect(self, values, disp):
        """Inverse ship: return per-row answers to the asking shard with a
        second all_to_all, then gather each of this shard's kept items'
        answers from the row it was sent in. Non-kept rows read row 0 —
        garbage the caller overwrites with the residual round's answer."""
        ret = jax.lax.all_to_all(values, tuple(self.dp_axes), 0, 0,
                                 tiled=True)
        idx = jnp.where(disp["kept"], disp["home"] * disp["cap"]
                        + disp["pos"], 0)
        return ret[idx]

    def _residual_return(self, values, overflow_all, ids_all, b):
        """The answer half of the exact overflow fallback: mask ``values``
        (computed over the full gathered batch) to this shard's overflow
        items, psum back, slice this shard's segment — gather-exchange
        semantics applied to the residual set only."""
        mine = overflow_all & (
            self._home(ids_all) == linear_axis_index(self.dp_axes)
        )
        return self._return_route(values, mine, b)

    def _a2a_read(self, st, i, visit):
        """Shared routed-read skeleton (lookup / lookup_signals /
        priority): ``visit(state, ids) -> tuple of per-item answers`` runs
        on the home shard over the a2a-received buffer; kept items collect
        their answer over the return all_to_all, overflow items over the
        cond-gated residual gather round. ``visit`` outputs must be
        psum-able (callers ship bools as i32)."""
        ax = tuple(self.dp_axes)
        b = i.shape[0]
        d = self._a2a_dispatch(i)
        kept = d["kept"]
        a2a_ans = tuple(
            self._a2a_collect(a, d) for a in visit(st, d["recv_ids"])
        )

        def bm(a):  # broadcast kept over trailing channel axes
            return kept.reshape(kept.shape + (1,) * (a.ndim - 1))

        def fast(_):  # no overflow anywhere: kept is all-True
            return tuple(
                jnp.where(bm(a), a, jnp.zeros((), a.dtype)) for a in a2a_ans
            )

        def slow(_):
            i_all = jax.lax.all_gather(i, ax, tiled=True)
            ovf_all = jax.lax.all_gather(d["overflow"], ax, tiled=True)
            res = tuple(
                self._residual_return(f, ovf_all, i_all, b)
                for f in visit(st, i_all)
            )
            return tuple(
                jnp.where(bm(a), a, o) for a, o in zip(a2a_ans, res)
            )

        return jax.lax.cond(d["n_ovf"] > 0, slow, fast, None)

    def _a2a_record(self, st, i, l, v, s, sg=None):
        """Routed record via the capacity-factor all_to_all. The table
        write is ONE ``record`` call per shard covering the a2a-received
        items (fast path) or their concatenation with the gathered
        overflow items (slow path), keyed by GLOBAL batch order — so
        same-slot duplicates split across the two arrival paths resolve
        exactly as in the single global table (winner choice AND
        non-compounding EMA), and the a2a table stays bit-identical to
        the gather exchange. Returns ``(state, n_overflow)``."""
        ax = tuple(self.dp_axes)
        payloads = (l, v) + (() if sg is None else (sg,))
        # active=v: masked-out items never crowd real writes out of
        # capacity (they neither write nor need an answer)
        d = self._a2a_dispatch(i, payloads, active=v)
        r_l = d["recv"][0]
        r_v = d["recv"][1] & (d["recv_ord"] >= 0)  # unfilled rows: no write
        r_sg = d["recv"][2] if sg is not None else None

        def fast(_):
            return record(self.local_cfg, st, d["recv_ids"], r_l, s,
                          valid=r_v, order=d["recv_ord"], signals=r_sg)

        def slow(_):
            i_all = jax.lax.all_gather(i, ax, tiled=True)
            l_all = jax.lax.all_gather(l, ax, tiled=True)
            ovf_all = jax.lax.all_gather(d["overflow"], ax, tiled=True)
            use = ovf_all & (
                self._home(i_all) == linear_axis_index(self.dp_axes)
            )
            cat = jnp.concatenate
            sig = None if sg is None else cat(
                [r_sg, jax.lax.all_gather(sg, ax, tiled=True)]
            )
            return record(
                self.local_cfg, st,
                cat([d["recv_ids"], i_all]), cat([r_l, l_all]), s,
                valid=cat([r_v, use]),
                order=cat([d["recv_ord"],
                           jnp.arange(i_all.shape[0], dtype=I32)]),
                signals=sig,
            )

        st2 = jax.lax.cond(d["n_ovf"] > 0, slow, fast, None)
        return st2, d["n_ovf"]

    def _a2a_record_priority(self, st, i, l, v, s, sg=None):
        """Fused routed write+score under a2a: the ``_a2a_record`` combined
        write (global order keys), then POST-record priorities for every
        asking item — kept items over the return all_to_all, the rest over
        the residual round. Bins ALL items (not just valid ones): an
        invalid item skips the write but still needs its score answered.
        Always the ref scatter — the Pallas record kernel has no order-key
        support, and ref ``record_priority`` is record+priority by
        definition, so this stays bit-identical to the gather path."""
        ax = tuple(self.dp_axes)
        b = i.shape[0]
        payloads = (l, v) + (() if sg is None else (sg,))
        d = self._a2a_dispatch(i, payloads)
        r_l = d["recv"][0]
        r_v = d["recv"][1] & (d["recv_ord"] >= 0)
        r_sg = d["recv"][2] if sg is not None else None

        def fast(_):
            st2 = record(self.local_cfg, st, d["recv_ids"], r_l, s,
                         valid=r_v, order=d["recv_ord"], signals=r_sg)
            pri = priority(self.local_cfg, st2, d["recv_ids"], s)
            return st2, jnp.where(d["kept"], self._a2a_collect(pri, d), 0.0)

        def slow(_):
            i_all = jax.lax.all_gather(i, ax, tiled=True)
            l_all = jax.lax.all_gather(l, ax, tiled=True)
            v_all = jax.lax.all_gather(v, ax, tiled=True)
            ovf_all = jax.lax.all_gather(d["overflow"], ax, tiled=True)
            # overflow here includes invalid items (active=None above):
            # the write mask re-applies valid, the answer mask does not
            use = v_all & ovf_all & (
                self._home(i_all) == linear_axis_index(self.dp_axes)
            )
            cat = jnp.concatenate
            sig = None if sg is None else cat(
                [r_sg, jax.lax.all_gather(sg, ax, tiled=True)]
            )
            st2 = record(
                self.local_cfg, st,
                cat([d["recv_ids"], i_all]), cat([r_l, l_all]), s,
                valid=cat([r_v, use]),
                order=cat([d["recv_ord"],
                           jnp.arange(i_all.shape[0], dtype=I32)]),
                signals=sig,
            )
            pri = priority(self.local_cfg, st2, d["recv_ids"], s)
            a = jnp.where(d["kept"], self._a2a_collect(pri, d), 0.0)
            o = self._residual_return(
                priority(self.local_cfg, st2, i_all, s), ovf_all, i_all, b
            )
            return st2, jnp.where(d["kept"], a, o)

        st2, pri = jax.lax.cond(d["n_ovf"] > 0, slow, fast, None)
        return st2, pri, d["n_ovf"]

    # -- ops ----------------------------------------------------------------

    def init(self) -> LedgerState:
        """Global [capacity] state, placed sharded over the slot axis."""
        sh = NamedSharding(self.mesh, P(tuple(self.dp_axes)))
        return jax.tree.map(
            lambda x: jax.device_put(x, sh), init_state(self.cfg)
        )

    def record(
        self, state: LedgerState, ids, losses, step, valid=None,
        signals=None, return_stats: bool = False,
    ):
        """Record a batch; with ``return_stats=True`` also return a stats
        dict (``a2a_overflow``: replicated count of items that missed the
        a2a capacity this call — always 0 off the a2a exchange)."""
        state_spec = self._state_spec()
        if valid is None:
            valid = jnp.ones(jnp.asarray(ids).shape, bool)
        has_sig = signals is not None

        def local(st, i, l, v, *rest):
            sg = rest[0] if has_sig else None
            s = rest[-1]
            if self._a2a:
                return self._a2a_record(st, i, l, v, s, sg=sg)
            if self.route:
                if has_sig:
                    i, l, v, sg, mine = self._exchange(i, l, v, sg)
                else:
                    i, l, v, mine = self._exchange(i, l, v)
                v = v & mine
            st2 = record(self.local_cfg, st, i, l, s, valid=v, signals=sg)
            return st2, jnp.zeros((), I32)

        fn = self._wrap(local, 4 if has_sig else 3, (state_spec, P()))
        args = (state, ids, losses, valid) + ((signals,) if has_sig else ())
        with _host_span(
            "ledger.record",
            ids,
            exchange=self.exchange if self.route else "pinned",
            shards=self.shards,
        ):
            st, ovf = fn(*args, jnp.asarray(step, I32))
        if return_stats:
            return st, {"a2a_overflow": ovf}
        return st

    def lookup(self, state: LedgerState, ids):
        dp = P(tuple(self.dp_axes))

        def local(st, i, s):
            if not self.route:
                return lookup(st, i)
            if self._a2a:
                def visit(st_, x):
                    ema, seen = lookup(st_, x)
                    return ema, seen.astype(I32)

                ema, seen = self._a2a_read(st, i, visit)
                return ema, seen > 0
            b = i.shape[0]
            i_all, mine = self._exchange(i)
            ema, seen = lookup(st, i_all)
            return (
                self._return_route(ema, mine, b),
                self._return_route(seen.astype(I32), mine, b) > 0,
            )

        fn = self._wrap(local, 1, (dp, dp))
        with _host_span("ledger.lookup", ids, shards=self.shards):
            return fn(state, ids, jnp.zeros((), I32))

    def lookup_signals(self, state: LedgerState, ids):
        """Multi-channel probe -> (ema [B], sig [B, N_AUX], seen [B]);
        routed mode answers from each id's home shard like ``lookup``."""
        dp = P(tuple(self.dp_axes))

        def local(st, i, s):
            if not self.route:
                return lookup_signals(st, i)
            if self._a2a:
                def visit(st_, x):
                    ema, sig, seen = lookup_signals(st_, x)
                    return ema, sig, seen.astype(I32)

                ema, sig, seen = self._a2a_read(st, i, visit)
                return ema, sig, seen > 0
            b = i.shape[0]
            i_all, mine = self._exchange(i)
            ema, sig, seen = lookup_signals(st, i_all)
            return (
                self._return_route(ema, mine, b),
                self._return_route(sig, mine, b),
                self._return_route(seen.astype(I32), mine, b) > 0,
            )

        fn = self._wrap(local, 1, (dp, dp, dp))
        with _host_span("ledger.lookup_signals", ids, shards=self.shards):
            return fn(state, ids, jnp.zeros((), I32))

    def priority(self, state: LedgerState, ids, step):
        dp = P(tuple(self.dp_axes))

        def local(st, i, s):
            if not self.route:
                return priority(self.local_cfg, st, i, s)
            if self._a2a:
                (pri,) = self._a2a_read(
                    st, i,
                    lambda st_, x: (priority(self.local_cfg, st_, x, s),),
                )
                return pri
            b = i.shape[0]
            i_all, mine = self._exchange(i)
            pri = priority(self.local_cfg, st, i_all, s)
            return self._return_route(pri, mine, b)

        fn = self._wrap(local, 1, dp)
        with _host_span("ledger.priority", ids, shards=self.shards):
            return fn(state, ids, jnp.asarray(step, I32))

    def record_priority(
        self,
        state: LedgerState,
        ids,
        losses,
        step,
        valid=None,
        impl: Optional[str] = None,
        signals=None,
        return_stats: bool = False,
    ):
        dp = P(tuple(self.dp_axes))
        state_spec = self._state_spec()
        if valid is None:
            valid = jnp.ones(jnp.asarray(ids).shape, bool)
        has_sig = signals is not None

        def local(st, i, l, v, *rest):
            sg = rest[0] if has_sig else None
            s = rest[-1]
            if self._a2a:
                return self._a2a_record_priority(st, i, l, v, s, sg=sg)
            if not self.route:
                st2, pri = record_priority(
                    self.local_cfg, st, i, l, s, valid=v, impl=impl,
                    signals=sg,
                )
                return st2, pri, jnp.zeros((), I32)
            b = i.shape[0]
            if has_sig:
                i_all, l_all, v_all, sg_all, mine = self._exchange(
                    i, l, v, sg
                )
            else:
                i_all, l_all, v_all, mine = self._exchange(i, l, v)
                sg_all = None
            st2, pri = record_priority(
                self.local_cfg, st, i_all, l_all, s,
                valid=v_all & mine, impl=impl, signals=sg_all,
            )
            return st2, self._return_route(pri, mine, b), jnp.zeros((), I32)

        fn = self._wrap(local, 4 if has_sig else 3, (state_spec, dp, P()))
        args = (state, ids, losses, valid) + ((signals,) if has_sig else ())
        with _host_span(
            "ledger.record_priority",
            ids,
            exchange=self.exchange if self.route else "pinned",
            shards=self.shards,
        ):
            st, pri, ovf = fn(*args, jnp.asarray(step, I32))
        if return_stats:
            return st, pri, {"a2a_overflow": ovf}
        return st, pri

    # -- host interchange / migration ---------------------------------------

    def state_dict(self, state: LedgerState) -> dict[str, np.ndarray]:
        """Export the table as an .npz-able state_dict.

        Routed tables (and 1-shard ones) ARE the global interchange
        layout. A pinned multi-shard table holds records on *consumer*
        shards — a placement only meaningful to this (shard count, pinned
        feed) pair — so it is exported raw with a ``pinned_shards`` marker:
        ``load_state_dict`` below round-trips it losslessly into the same
        layout, and every other loader (``DeviceLedger``/``LossHistory``/
        ``rehash_state_dict``) treats a marked dict as a bag of records and
        re-hashes it into its own layout.
        """
        raw = state_dict_of(state)
        if not self.route and self.shards > 1:
            raw["pinned_shards"] = np.int64(self.shards)
        return raw

    def load_state_dict(self, sd: dict[str, np.ndarray]) -> LedgerState:
        """Restore a state_dict, preserving placement when possible.

        A ``pinned_shards`` export matching this ops' (pinned, same shard
        count, same capacity) layout is placed verbatim — the lossless
        checkpoint round-trip. Anything else is re-hashed into the global
        layout and placed at hash-home shards: exact for routed lookups,
        but a PINNED multi-shard target will only hit records whose
        consumer shard coincides with the home shard, so that combination
        gets a loud warning (use ``route=True``, or restore into the
        layout that wrote the file)."""
        sd = dict(sd)
        marker = sd.pop("pinned_shards", None)
        n = np.asarray(sd["ema"]).shape[0]
        pinned_match = (
            marker is not None
            and int(marker) == self.shards
            and not self.route
            and n == self.cfg.capacity
        )
        if not pinned_match and (marker is not None or n != self.cfg.capacity):
            sd = rehash_state_dict(sd, self.cfg.capacity)
        if not pinned_match and not self.route and self.shards > 1:
            print(
                "WARNING: loading a foreign-layout ledger into a pinned "
                f"{self.shards}-shard table places records at hash-home "
                "shards; a pinned feed will mostly miss them. Use "
                "route=True (train --ledger-route) to look them up there."
            )
        sh = NamedSharding(self.mesh, P(tuple(self.dp_axes)))
        return jax.tree.map(
            lambda x: jax.device_put(x, sh), state_from_dict(sd)
        )


def sharded_ledger_ops(
    mesh: Mesh,
    cfg: HistoryConfig = HistoryConfig(),
    dp_axes: Sequence[str] = ("data",),
    route: bool = False,
    exchange: str = "gather",
    capacity_factor: float = 1.25,
) -> ShardedLedgerOps:
    """Build sharded ledger ops; global capacity must divide over the mesh.

    ``route=True`` adds the cross-shard id exchange so unpinned feeds hit
    their records (see the module docstring for the layout consequences).
    ``exchange`` picks its realization: ``"gather"`` (all_gather +
    home-mask, O(S·b) bytes) or ``"a2a"`` (capacity-factor all_to_all
    dispatch, O(b·capacity_factor) bytes, exact overflow fallback) —
    bit-identical results either way. ``capacity_factor`` sizes the a2a
    send buffers (ignored for gather).
    """
    if exchange not in EXCHANGES:
        raise ValueError(f"exchange must be one of {EXCHANGES}: {exchange!r}")
    if capacity_factor <= 0:
        raise ValueError(f"capacity_factor must be > 0: {capacity_factor}")
    shards = 1
    for a in dp_axes:
        shards *= mesh.shape[a]
    if cfg.capacity % shards:
        raise ValueError(
            f"ledger capacity {cfg.capacity} not divisible by {shards} shards"
        )
    local_cap = cfg.capacity // shards
    if local_cap & (local_cap - 1):
        raise ValueError(f"per-shard capacity {local_cap} must be 2^k")
    local_cfg = dataclasses.replace(cfg, capacity=local_cap)
    return ShardedLedgerOps(
        mesh=mesh, dp_axes=tuple(dp_axes), cfg=cfg, local_cfg=local_cfg,
        route=route, exchange=exchange, capacity_factor=capacity_factor,
    )


# ---------------------------------------------------------------------------
# host-side layout migration (checkpoint-time, numpy)
# ---------------------------------------------------------------------------


def split_state_dict(
    sd: dict[str, np.ndarray], shards: int
) -> list[dict[str, np.ndarray]]:
    """Global layout -> per-shard local tables (hash-home placement).

    Because the routed layout is the global table sliced contiguously,
    this is a lossless reshape: the record at global slot g lands on shard
    g // (C/S) at local slot g mod (C/S) — its local hash slot.
    """
    cap = np.asarray(sd["owner"]).shape[0]
    if cap % shards:
        raise ValueError(f"capacity {cap} not divisible by {shards} shards")
    lc = cap // shards
    if lc & (lc - 1):
        raise ValueError(f"per-shard capacity {lc} must be 2^k")
    return [
        {k: np.asarray(v)[s * lc : (s + 1) * lc].copy() for k, v in sd.items()}
        for s in range(shards)
    ]


def merge_shard_state_dicts(
    sds: Sequence[dict[str, np.ndarray]],
    capacity: Optional[int] = None,
) -> dict[str, np.ndarray]:
    """Per-shard local tables -> one global-layout table.

    The inverse of ``split_state_dict`` (lossless for hash-home placement:
    re-hashing puts every record back at its global slot). For tables
    populated by a *pinned* feed, records from different shards can
    collide at the same global slot — the most recent one wins, matching
    the ledger's lossy-cache eviction semantics.
    """
    keys = ("ema", "count", "last_seen", "owner")
    if all("sig" in sd for sd in sds):  # pre-signal-channel dicts merge too
        keys += ("sig",)
    concat = {
        k: np.concatenate([np.asarray(sd[k]) for sd in sds])
        for k in keys
    }
    return rehash_state_dict(concat, capacity or concat["owner"].shape[0])
