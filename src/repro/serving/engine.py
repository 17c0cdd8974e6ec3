"""Continuous-batching serving engine with fused outcome recording.

The "ten forward" side of the paper, grown from the one-shot demo into a
real subsystem: a fixed-size decode batch of ``slots`` that requests flow
through continuously —

* **admission**: a queued request takes a free slot; its prompt runs
  through a jitted prefill (batch 1, right-padded to a length bucket when
  the family permits) and the resulting KV/state cache is scattered into
  the slot's row of the batch cache (``insert`` — one jit);
* **decode**: ONE fused jitted step advances every occupied slot by one
  token at its own depth (``pos`` is a per-slot vector; see
  ``models.layers`` decode), retains the logits, and lets the
  :class:`~repro.serving.recorder.OutcomeRecorder` score + record the
  oldest labeled-but-unscored position of each slot into the (optionally
  sharded + routed) device ledger — the whole data plane is device-resident
  and the step raises nothing under ``jax.transfer_guard("disallow")``;
* **eviction**: a slot frees when its generation finished AND its outcome
  backlog drained (labels scored), returning the generated tokens.

Instance ids are **stable and globally monotone**: ``submit`` assigns
``id_start + k * id_stride`` (stride = number of engines in a fleet keeps
ids disjoint across hosts), never a per-batch ``arange`` — so records from
different requests can never collide in the ledger under the same id.

Control plane (queueing, admission, eviction, label bookkeeping) is host
Python between steps, like any serving scheduler; the data plane
(decode, retention, scoring, ledger) is the fused jit.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.history import AUX_CHANNELS, LossHistory
from repro.models import model as Mdl
from repro.models.config import ModelConfig
from repro.serving.pages import PagePool, pages_for
from repro.serving.recorder import OutcomeRecorder, RecorderState

Array = jax.Array
I32 = jnp.int32

# Families where a right-padded prompt cannot perturb real positions:
# causal attention, with a dense FFN or the drop-free MoE layer that
# serving runs (no token competes for expert capacity). Recurrent state
# integrates pads, and a rolling sliding-window cache shifts by them.
_PAD_SAFE_FAMILIES = ("dense", "vlm", "audio", "moe")


def pad_safe(cfg: ModelConfig) -> bool:
    return cfg.family in _PAD_SAFE_FAMILIES and cfg.sliding_window is None


@dataclasses.dataclass
class Request:
    """One serving request. ``labels`` (ground-truth continuation) may be
    attached now or delivered later via ``Engine.deliver_outcome``;
    ``expect_labels`` holds the slot open (after generation) until they
    arrive, so late outcomes within the residency window are never lost."""

    prompt: np.ndarray
    max_new: int
    instance_id: int
    labels: Optional[np.ndarray] = None
    expect_labels: bool = False
    submit_t: float = 0.0  # time.perf_counter() at submit()


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EngineState:
    """Per-slot device state (a pytree). ``inst == -1`` marks a free slot."""

    cache: Any  # model decode cache, batch dim = slots
    cur_tok: Array  # [S, 1] next input token
    pos: Array  # [S] tokens already in the cache (per-slot depth)
    gen_idx: Array  # [S] generated positions produced so far
    inst: Array  # [S] instance id, -1 = free
    prompt_len: Array  # [S]
    max_new: Array  # [S]
    out_toks: Array  # [S, G] generated tokens
    step: Array  # [] i32 monotone decode-step counter (= ledger step)
    page_table: Any = None  # [S, NP] i32 physical page per block (paged mode)

    def tree_flatten(self):
        return (
            self.cache, self.cur_tok, self.pos, self.gen_idx, self.inst,
            self.prompt_len, self.max_new, self.out_toks, self.step,
            self.page_table,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _cache_batch_axis(cfg: ModelConfig, key: str) -> int:
    # hybrid stacks ssm blocks [groups, every, batch, ...]; everything else
    # is [layers, batch, ...]
    return 2 if (cfg.family == "hybrid" and key == "blocks") else 1


def insert_cache_slot(
    cfg: ModelConfig, cache: dict, new: dict, slot: Array
) -> dict:
    """Scatter a batch-1 prefill cache into row ``slot`` of the batch cache."""
    out = {}
    for key, sub in cache.items():
        ax = _cache_batch_axis(cfg, key)
        out[key] = jax.tree.map(
            lambda c, n, a=ax: jax.lax.dynamic_update_index_in_dim(
                c, jax.lax.index_in_dim(n, 0, a, keepdims=False), slot, a
            ),
            sub,
            new[key],
        )
    return out


def insert_paged_cache_slot(
    cfg: ModelConfig, cache: dict, new: dict, pt_row: Array, page_size: int
) -> dict:
    """Scatter a batch-1 dense prefill cache into the pages a slot owns.

    ``pt_row`` [NP] maps the slot's logical blocks to physical pages of the
    global pool; -1 entries (blocks not yet allocated — growth pages, or the
    tail past the prompt bucket) drop their writes. The prefill cache is
    dense: [L, 1, T, kv, hd] K and V for the pool's [L, P, kv, page, hd],
    or [L, 1, T, R] ``ckv`` and [L, 1, T, pe] ``kpe`` for the latent pool
    [L, P, page, W] (one row, zero-padded to W). T need not fill NP pages —
    the tail pads with zeros, which only lands in allocated pages past the
    prompt where decode overwrites it before validity ever reaches it.
    """
    npg = pt_row.shape[0]

    def put(pool, dense):
        l, _, t = dense.shape[:3]
        pad = [(0, 0), (0, npg * page_size - t)]
        d = jnp.pad(dense[:, 0], pad + [(0, 0)] * (dense.ndim - 3))
        d = d.reshape(l, npg, page_size, *dense.shape[3:])
        if d.ndim == 5:  # K/V: head-major pages [kv, page, hd]
            d = d.swapaxes(2, 3)
        # -1 would WRAP to the pool's last page (negative indices resolve
        # numpy-style before mode="drop" sees them) — remap to one-past-end
        idx = jnp.where(pt_row >= 0, pt_row, pool.shape[1])
        return pool.at[:, idx].set(d, mode="drop")

    out = {}
    for key, sub in cache.items():
        n = new[key]
        if "lat" in sub:
            row = jnp.concatenate([n["ckv"], n["kpe"]], -1)
            w = sub["lat"].shape[-1]
            row = jnp.pad(row, [(0, 0)] * 3 + [(0, w - row.shape[-1])])
            out[key] = {"lat": put(sub["lat"], row.astype(sub["lat"].dtype))}
        else:
            out[key] = {"kp": put(sub["kp"], n["k"]),
                        "vp": put(sub["vp"], n["v"])}
    return out


def make_slot_sampler(temperature: float, top_p: float, seed: int):
    """Per-slot token sampler for the fused decode step.

    ``temperature <= 0`` returns exact greedy argmax — bit-identical to the
    historical behavior, the setting every parity test pins. Otherwise each
    slot samples from its own stateless RNG lane: the key is
    ``fold_in(fold_in(key(seed), instance_id), gen_idx)``, a pure function
    of (instance, position) — deterministic across runs and independent of
    slot assignment or what else is in the batch. ``top_p < 1`` applies
    nucleus filtering first (keep a token iff the probability mass strictly
    before it in sorted order is < top_p; the top-1 token always survives).
    """
    if temperature <= 0.0:
        return lambda logits, inst, gen_idx: jnp.argmax(
            logits, axis=-1
        ).astype(I32)
    base = jax.random.key(seed)

    def sample(logits: Array, inst: Array, gen_idx: Array) -> Array:
        keys = jax.vmap(
            lambda i, g: jax.random.fold_in(jax.random.fold_in(base, i), g)
        )(inst.astype(jnp.uint32), gen_idx.astype(jnp.uint32))
        x = logits.astype(jnp.float32) / temperature
        if top_p < 1.0:
            srt = jnp.sort(x, axis=-1)[:, ::-1]
            p = jax.nn.softmax(srt, axis=-1)
            mass_before = jnp.cumsum(p, axis=-1) - p
            keep = mass_before < top_p
            cut = jnp.min(
                jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True
            )
            x = jnp.where(x >= cut, x, -jnp.inf)
        return jax.vmap(jax.random.categorical)(keys, x).astype(I32)

    return sample


class Engine:
    """Continuous batching over a request queue (see module docstring).

    ``recorder`` owns ledger placement; ``prompt_buckets`` pads prompts up
    to the nearest bucket so distinct lengths share one prefill compile
    (pad-safe families only — recurrent and windowed families prefill at
    exact length, one compile per distinct length).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        recorder: OutcomeRecorder,
        *,
        slots: int = 8,
        max_prompt: int = 64,
        max_gen: Optional[int] = None,
        prompt_buckets: Optional[Sequence[int]] = None,
        id_start: int = 0,
        id_stride: int = 1,
        pad_token: int = 0,
        guard_transfers: bool = True,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        temperature: float = 0.0,
        top_p: float = 1.0,
        sample_seed: int = 0,
        telemetry: Optional[obs.Telemetry] = None,
        track_drift: Optional[bool] = None,
    ):
        self.cfg = cfg
        self.recorder = recorder  # self.params set below (mesh-replicated)
        self.slots = slots
        self.max_prompt = max_prompt
        self.max_gen = max_gen if max_gen is not None else recorder.max_gen
        assert self.max_gen <= recorder.max_gen, (
            self.max_gen, recorder.max_gen,
        )
        assert recorder.slots == slots, (recorder.slots, slots)
        self.max_seq = max_prompt + self.max_gen
        self.pad_token = pad_token
        self.guard_transfers = guard_transfers

        # paged KV cache: slots share a global pool of page_size-token
        # pages instead of each reserving a dense max_seq stripe. Admission
        # allocates the prompt's pages AND reserves the request's
        # worst-case growth, so mid-decode growth can never fail; pool
        # exhaustion defers admission instead.
        self.page_size = page_size
        self.pool: Optional[PagePool] = None
        if page_size is not None:
            assert page_size > 0, page_size
            self.pages_per_slot = pages_for(self.max_seq, page_size)
            if num_pages is None:  # dense-equivalent capacity
                num_pages = slots * self.pages_per_slot
            assert num_pages >= self.pages_per_slot, (
                num_pages, self.pages_per_slot,
            )
            self.num_pages = num_pages
            self.pool = PagePool(num_pages, page_size)
            self._slot_pages: dict[int, list[int]] = {}  # slot -> pages
            self._slot_reserve: dict[int, int] = {}  # slot -> growth budget
            self._pos_host = np.zeros((slots,), np.int64)  # device pos mirror
        self.deferred_admissions = 0

        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self._sample = make_slot_sampler(
            self.temperature, self.top_p, sample_seed
        )
        if prompt_buckets is None and pad_safe(cfg):
            b, buckets = 8, []
            while b < max_prompt:
                buckets.append(b)
                b *= 2
            prompt_buckets = (*buckets, max_prompt)
        if prompt_buckets is not None and not pad_safe(cfg):
            raise ValueError(
                f"{cfg.family} family (or sliding-window attention) cannot "
                "right-pad prompts (pads perturb recurrent state / rolling "
                "caches); use exact-length prefill (prompt_buckets=None)"
            )
        self.prompt_buckets = (
            tuple(sorted(prompt_buckets)) if prompt_buckets else None
        )

        self._id_next = id_start
        self._id_stride = id_stride
        self._queue: list[Request] = []
        self._slot_of: dict[int, int] = {}
        self._max_new_of: dict[int, int] = {}  # resident slots only
        self._free = list(range(slots))[::-1]  # pop() -> lowest slot first
        self._await_labels: dict[int, bool] = {}
        self._admission_seq: dict[int, int] = {}
        # slots with labels delivered since the last fused step: their
        # ``pending`` metric is stale (predates the delivery), so eviction
        # holds until the next step has actually seen the labels
        self._fresh_labels: set[int] = set()
        self._last_metrics: Optional[dict] = None
        self._warm = False
        self._ledger_epoch = 0  # bumped on out-of-band ledger mutation

        # results / counters
        self.finished: dict[int, np.ndarray] = {}
        self.generated_tokens = 0
        self.admitted = 0
        self.evicted = 0
        self.steps_run = 0
        self.missed_outcomes = 0
        # total items that missed the a2a send capacity and took the exact
        # overflow fallback round (0 unless the recorder routes exchange="a2a")
        self.a2a_overflow = 0

        # sharded recorder: everything the guarded fused step touches must
        # already live on the mesh (params + engine state replicated, the
        # ledger sharded by ops.init) — otherwise the jit call would need
        # an implicit reshard-transfer every step
        self.params = recorder.replicate(params)
        self._estate = recorder.replicate(self._init_state())
        self._rstate = recorder.init_state()

        self._prefill_jits: dict[int, Any] = {}
        self._insert = jax.jit(self._insert_fn, donate_argnums=(0, 1))
        # params go in as an ARGUMENT (closing over them would bake the
        # weights into the jaxpr as constants)
        self._decode = jax.jit(self._fused_step, donate_argnums=(1, 2))
        self._deliver = jax.jit(self._deliver_fn, donate_argnums=(0,))
        # host->device slot maintenance (outside the transfer guard, like
        # admission): scatter freshly grown pages (paged mode) / free
        # evicted slots, both at fixed [slots] shape with the padding
        # dropped so one compile serves any count
        self._grow_jit = jax.jit(self._grow_fn, donate_argnums=(0,))
        self._clear_jit = jax.jit(self._clear_fn, donate_argnums=(0,))

        # -- telemetry: instruments bound ONCE here; per-step updates are
        # host arithmetic on the step's already-fetched numpy metrics
        # (obs module doc / tests/test_obs.py transfer-guard regression)
        t = telemetry if telemetry is not None else obs.current()
        self.telemetry = t
        self._c_steps = t.counter("engine.steps")
        self._c_tokens = t.counter("engine.generated_tokens")
        self._c_records = t.counter("engine.ledger_records")
        self._c_miss = t.counter("engine.topk_miss")
        self._c_overflow = t.counter("engine.a2a_overflow")
        self._c_admitted = t.counter("engine.admitted")
        self._c_evicted = t.counter("engine.evicted")
        self._c_deferred = t.counter("engine.deferred_admissions")
        self._c_missed = t.counter("engine.missed_outcomes")
        self._g_occupancy = t.gauge("engine.occupancy")
        self._g_queue = t.gauge("engine.queue_depth")
        self._h_step_ms = t.histogram("engine.step_ms")
        # host-side mirrors of the device record/miss counters so
        # loop_health() derives rates without a device fetch
        self._records_host = 0
        self._miss_host = 0
        # EMA-drift oracle: a host LossHistory fed the exact rows the
        # fused step records on device; compared channel-by-channel in
        # loop_health(drift=True). Device-ledger runs only (the host
        # ledger IS the oracle) and only when telemetry is live.
        if track_drift is None:
            track_drift = t.enabled and recorder.ledger == "device"
        self._shadow: Optional[LossHistory] = (
            LossHistory(recorder.cfg)
            if track_drift and recorder.ledger == "device"
            else None
        )

    # -- device state --------------------------------------------------------

    def _init_state(self) -> EngineState:
        s, g = self.slots, self.max_gen
        if self.page_size is not None:
            cache = Mdl.init_paged_cache(
                self.cfg, self.num_pages, self.page_size
            )
            page_table = jnp.full((s, self.pages_per_slot), -1, I32)
        else:
            cache = Mdl.init_cache(self.cfg, s, self.max_seq)
            page_table = None
        return EngineState(
            cache=cache,
            page_table=page_table,
            cur_tok=jnp.zeros((s, 1), I32),
            pos=jnp.zeros((s,), I32),
            gen_idx=jnp.zeros((s,), I32),
            inst=jnp.full((s,), -1, I32),
            prompt_len=jnp.zeros((s,), I32),
            max_new=jnp.zeros((s,), I32),
            out_toks=jnp.zeros((s, g), I32),
            step=jnp.zeros((), I32),
        )

    # every program is a named method, so its XLA module in a profile
    # (jit__prefill_fn, jit__insert_fn, jit__fused_step, ...) reads apart
    # from the others

    def _prefill(self, padded_len: int):
        fn = self._prefill_jits.get(padded_len)
        if fn is None:
            fn = jax.jit(self._prefill_fn)
            self._prefill_jits[padded_len] = fn
        return fn

    def _prefill_fn(self, params, toks, last_pos):
        return Mdl.prefill(
            params, self.cfg, toks, max_seq=self.max_seq, last_pos=last_pos
        )

    def _deliver_fn(self, rstate, slot, row):
        return self.recorder.deliver(rstate, slot, row)

    def _insert_fn(
        self, estate, rstate, new_cache, logits0, slot, inst, plen, max_new,
        labels_row, pt_row=None,
    ):
        if pt_row is None:
            cache = insert_cache_slot(self.cfg, estate.cache, new_cache, slot)
            page_table = estate.page_table
        else:
            cache = insert_paged_cache_slot(
                self.cfg, estate.cache, new_cache, pt_row, self.page_size
            )
            page_table = estate.page_table.at[slot].set(pt_row)
        inst_v = jnp.reshape(jnp.asarray(inst, I32), (1,))
        t0 = self._sample(logits0, inst_v, jnp.zeros((1,), I32))[0]
        out_toks = estate.out_toks.at[slot].set(
            jnp.zeros((self.max_gen,), I32)
        )
        out_toks = out_toks.at[slot, 0].set(t0)
        estate = EngineState(
            cache=cache,
            page_table=page_table,
            cur_tok=estate.cur_tok.at[slot, 0].set(t0),
            pos=estate.pos.at[slot].set(jnp.asarray(plen, I32)),
            gen_idx=estate.gen_idx.at[slot].set(1),
            inst=estate.inst.at[slot].set(jnp.asarray(inst, I32)),
            prompt_len=estate.prompt_len.at[slot].set(jnp.asarray(plen, I32)),
            max_new=estate.max_new.at[slot].set(jnp.asarray(max_new, I32)),
            out_toks=out_toks,
            step=estate.step,
        )
        rstate = self.recorder.clear_slot(rstate, slot, logits0[0], labels_row)
        return estate, rstate

    def _fused_step(self, params, estate: EngineState, rstate: RecorderState):
        """Decode every slot one token + retain logits + score + record —
        one jit, all inputs device-resident (transfer-free by design)."""
        occupied = estate.inst >= 0
        decoding = occupied & (estate.gen_idx < estate.max_new)
        routing = self.cfg.uses_moe
        out = self.recorder.per_device(
            lambda p, c, tok, pos, pt: Mdl.decode_step(
                p, self.cfg, c, tok, pos, page_table=pt, routing=routing
            )
        )(params, estate.cache, estate.cur_tok, estate.pos, estate.page_table)
        logits, cache = out[:2]
        nxt = self._sample(logits, estate.inst, estate.gen_idx)
        bidx = jnp.arange(self.slots)
        tgt = jnp.where(decoding, estate.gen_idx, self.max_gen)
        out_toks = estate.out_toks.at[bidx, tgt].set(nxt, mode="drop")
        cur_tok = jnp.where(decoding[:, None], nxt[:, None], estate.cur_tok)
        rstate = self.recorder.observe(rstate, estate.gen_idx, logits, decoding)
        adv = decoding.astype(I32)
        gen_idx = estate.gen_idx + adv
        step = estate.step + 1
        rstate, info = self.recorder.score_one(
            rstate, estate.inst, gen_idx, step
        )
        new_es = EngineState(
            cache=cache,
            page_table=estate.page_table,
            cur_tok=cur_tok,
            pos=estate.pos + adv,
            gen_idx=gen_idx,
            inst=estate.inst,
            prompt_len=estate.prompt_len,
            max_new=estate.max_new,
            out_toks=out_toks,
            step=step,
        )
        metrics = {
            "inst": estate.inst,
            "occupied": occupied,
            "decoding": decoding,
            "gen_idx": gen_idx,
            "finished": occupied & (gen_idx >= estate.max_new),
            "pending": info["pending"],
            "loss": info["loss"],
            "entropy": info["entropy"],
            "margin": info["margin"],
            "loss_valid": info["valid"],
            "topk_miss": info["miss"],
            "n_recorded": rstate.n_recorded,
            "a2a_overflow": info["a2a_overflow"],
        }
        if routing:  # [L_moe, E] tokens per expert, every slot's row
            counts = out[2]
            metrics["moe_routed"] = counts.sum()
            metrics["moe_experts_hit"] = (counts > 0).sum()
            metrics["moe_load_max"] = jnp.max(
                counts.max(-1) / counts.astype(jnp.float32).mean(-1)
            )
        return new_es, rstate, metrics

    def _grow_fn(self, estate, slots_arr, idxs, pages):
        pt = estate.page_table.at[slots_arr, idxs].set(pages, mode="drop")
        return dataclasses.replace(estate, page_table=pt)

    def _clear_fn(self, estate, slots_arr):
        inst = estate.inst.at[slots_arr].set(-1, mode="drop")
        if estate.page_table is None:
            return dataclasses.replace(estate, inst=inst)
        pt = estate.page_table.at[slots_arr].set(-1, mode="drop")
        return dataclasses.replace(estate, page_table=pt, inst=inst)

    # -- paged-cache host bookkeeping ----------------------------------------

    def _pages_needed(self, req: Request) -> tuple[int, int, int]:
        """(allocate now, reserve for growth, total) pages for a request.

        Now = the bucketed prompt; total = enough to hold the deepest
        position the slot ever writes (``plen + max_new - 1``). Reserving
        total - now at admission makes every later ``grow()`` infallible —
        the per-REQUEST worst case, not the engine-wide ``max_seq``, which
        is where the paged layout's HBM win comes from.
        """
        ps = self.page_size
        n_now = pages_for(self._bucket(req.prompt.size), ps)
        n_total = max(n_now, pages_for(req.prompt.size + req.max_new, ps))
        return n_now, n_total - n_now, n_total

    def _grow_pages(self) -> None:
        """Allocate pages (from each slot's admission-time reservation) so
        the next fused step's K/V write at ``pos`` lands in an owned page.
        Runs before every decode; finished slots are already at their total
        and no-op."""
        ups: list[tuple[int, int, int]] = []
        for slot in self._slot_of.values():
            need = pages_for(int(self._pos_host[slot]) + 1, self.page_size)
            while len(self._slot_pages[slot]) < need:
                assert self._slot_reserve[slot] > 0, slot
                self._slot_reserve[slot] -= 1
                pg = self.pool.grow()
                ups.append((slot, len(self._slot_pages[slot]), pg))
                self._slot_pages[slot].append(pg)
        if not ups:
            return
        assert len(ups) <= self.slots  # <= 1 new page per slot per step
        # pad with slots (one-past-end -> dropped); NOT -1, which would
        # wrap numpy-style to the last slot's row before "drop" applies
        s = np.full((self.slots,), self.slots, np.int32)
        i = np.zeros((self.slots,), np.int32)
        p = np.zeros((self.slots,), np.int32)
        for j, (sl, ix, pg) in enumerate(ups):
            s[j], i[j], p[j] = sl, ix, pg
        rep = self.recorder.replicate
        self._estate = self._grow_jit(
            self._estate, rep(jnp.asarray(s)), rep(jnp.asarray(i)),
            rep(jnp.asarray(p)),
        )

    # -- host API ------------------------------------------------------------

    def submit(
        self,
        prompt: np.ndarray,
        max_new: Optional[int] = None,
        labels: Optional[np.ndarray] = None,
        instance_id: Optional[int] = None,
        expect_labels: Optional[bool] = None,
    ) -> int:
        """Queue a request; returns its (monotone, stable) instance id."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not 0 < prompt.size <= self.max_prompt:
            raise ValueError(
                f"prompt length {prompt.size} not in (0, {self.max_prompt}]"
            )
        max_new = self.max_gen if max_new is None else max_new
        if not 0 < max_new <= self.max_gen:
            raise ValueError(f"max_new {max_new} not in (0, {self.max_gen}]")
        if instance_id is None:
            instance_id = self._id_next
            self._id_next += self._id_stride
        else:
            iid = int(instance_id)
            on_lane = (iid - self._id_next) % self._id_stride == 0
            if on_lane and iid >= self._id_next:
                # an explicit id on this engine's auto lane: advance past
                # it, or a later auto-assigned id would collide and merge
                # two requests' records under one ledger id
                self._id_next = iid + self._id_stride
        if expect_labels is None:
            expect_labels = False
        self._queue.append(
            Request(prompt, max_new, int(instance_id),
                    None if labels is None else np.asarray(labels, np.int64),
                    bool(expect_labels), time.perf_counter())
        )
        return int(instance_id)

    def deliver_outcome(self, instance_id: int, labels: np.ndarray) -> bool:
        """Late labels for a (possibly still decoding) request. A request
        still waiting in the queue gets them attached for admission; after
        its slot left, they are dropped and counted missed. Labels beyond
        the request's ``max_new`` can never be scored (no position was
        decoded for them) — they are dropped and counted in
        ``missed_outcomes``, same as at admission."""
        slot = self._slot_of.get(int(instance_id))
        if slot is None:
            for req in self._queue:  # not yet admitted: attach to request
                if req.instance_id == int(instance_id) and req.labels is None:
                    req.labels = np.asarray(labels, np.int64)
                    req.expect_labels = False
                    return True
            self.missed_outcomes += 1
            self._c_missed.inc()
            return False
        limit = self._max_new_of.get(int(instance_id), self.max_gen)
        row = np.full((self.recorder.max_gen,), -1, np.int64)
        labels = np.asarray(labels, np.int64).reshape(-1)
        use = min(labels.size, limit)
        row[:use] = labels[:use]
        cut = int((labels[limit:] >= 0).sum())
        self.missed_outcomes += cut
        self._c_missed.inc(cut)
        # route the row onto the recorder's placement (mesh-replicated on
        # sharded recorders) BEFORE the jit: a default-device array would
        # need an implicit transfer at the _deliver boundary, and the
        # updated labels could come back off-mesh and trip the next
        # guarded fused step
        with self.telemetry.span(
            "engine.deliver", inst=int(instance_id), slot=slot
        ):
            self._rstate = self._deliver(
                self._rstate, slot,
                self.recorder.replicate(jnp.asarray(row.astype(np.int32))),
            )
        self._await_labels[int(instance_id)] = False
        self._fresh_labels.add(slot)
        return True

    def _bucket(self, n: int) -> int:
        if self.prompt_buckets is None:
            return n
        for b in self.prompt_buckets:
            if b >= n:
                return b
        return self.max_prompt

    def _admit(self, req: Request) -> None:
        with self.telemetry.span(
            "engine.admit", inst=req.instance_id, prompt=int(req.prompt.size),
            waited_ms=(time.perf_counter() - req.submit_t) * 1e3,
        ):
            self._admit_inner(req)
        self._c_admitted.inc()

    def _admit_inner(self, req: Request) -> None:
        slot = self._free.pop()
        pt_row = None
        if self.pool is not None:
            n_now, n_later, _ = self._pages_needed(req)
            pages = self.pool.admit(n_now, n_later)
            assert pages is not None  # step() gated admission on fits()
            row = np.full((self.pages_per_slot,), -1, np.int32)
            row[: len(pages)] = pages
            pt_row = self.recorder.replicate(jnp.asarray(row))
            self._slot_pages[slot] = list(pages)
            self._slot_reserve[slot] = n_later
            self._pos_host[slot] = req.prompt.size
        p = self._bucket(req.prompt.size)
        toks = np.full((1, p), self.pad_token, np.int32)
        toks[0, : req.prompt.size] = req.prompt
        lp = np.asarray([req.prompt.size - 1], np.int32)
        with self.telemetry.span(
            "engine.prefill", padded_len=p, prompt=int(req.prompt.size)
        ):
            logits0, new_cache = self._prefill(p)(
                self.params, jnp.asarray(toks), jnp.asarray(lp)
            )
        row = np.full((self.recorder.max_gen,), -1, np.int64)
        if req.labels is not None:
            row[: min(req.labels.size, req.max_new)] = req.labels[
                : req.max_new
            ]
            # labels past max_new have no decoded position to score
            # against — drop and count them (deliver_outcome applies the
            # same max_new cut to labels arriving mid-residency)
            cut = int((req.labels[req.max_new:] >= 0).sum())
            self.missed_outcomes += cut
            self._c_missed.inc(cut)
        with self.telemetry.span("engine.insert"):
            self._estate, self._rstate = self._insert(
                self._estate, self._rstate, new_cache, logits0,
                slot, req.instance_id, req.prompt.size, req.max_new,
                jnp.asarray(row.astype(np.int32)), pt_row,
            )
        self._slot_of[req.instance_id] = slot
        self._max_new_of[req.instance_id] = req.max_new
        self._await_labels[req.instance_id] = req.expect_labels
        self.admitted += 1
        self._admission_seq[req.instance_id] = self.admitted

    def _evict_done(self) -> None:
        m = self._last_metrics
        if m is None:
            return
        done: list[tuple[int, int, int]] = []  # (inst, slot, gen)
        for inst, slot in list(self._slot_of.items()):
            if (
                m["finished"][slot]
                and not m["pending"][slot]
                and slot not in self._fresh_labels
                and not self._await_labels.get(inst, False)
            ):
                done.append((inst, slot, int(m["gen_idx"][slot])))
        if not done:
            return
        with self.telemetry.span(
            "engine.evict", insts=[inst for inst, _, _ in done]
        ):
            self._evict(done)

    def _evict(self, done: list[tuple[int, int, int]]) -> None:
        # ONE batched fetch of every evicting slot's token rows (was one
        # device_get per slot); the per-slot :gen cut happens on host
        freed = [s for _, s, _ in done]
        with self.telemetry.span("engine.evict_fetch", n=len(done)):
            rows = jax.device_get(
                self._estate.out_toks[np.asarray(freed, np.int32)]
            )
        for (inst, slot, gen), row in zip(done, np.asarray(rows)):
            self.finished[inst] = np.asarray(row[:gen])
            del self._slot_of[inst]
            self._max_new_of.pop(inst, None)
            self._await_labels.pop(inst, None)
            self._admission_seq.pop(inst, None)
            self._free.append(slot)
            self.evicted += 1
            self._c_evicted.inc()
            if self.pool is not None:
                self.pool.release(
                    self._slot_pages.pop(slot),
                    self._slot_reserve.pop(slot),
                )
                self._pos_host[slot] = 0
        # mark the freed slots free on the device (inst -1: the next step
        # reports them unoccupied) and, paged, clear their page-table rows
        # to -1 so the (still-resident-shaped) frozen K/V writes of a
        # reused slot can never land in pages that have moved on to another
        # owner; pad with one-past-end (a -1 pad would wrap to the last
        # slot and wipe its row)
        arr = np.full((self.slots,), self.slots, np.int32)
        arr[: len(freed)] = freed
        with self.telemetry.span("engine.clear"):
            self._estate = self._clear_jit(
                self._estate, self.recorder.replicate(jnp.asarray(arr))
            )

    def in_flight_ids(self) -> tuple[int, ...]:
        """Instance ids currently resident in a slot (admission order)."""
        return tuple(self._slot_of)

    def in_flight_admissions(self) -> tuple[tuple[int, int], ...]:
        """(instance id, admission sequence number) per resident slot.
        The sequence number distinguishes RESIDENCIES of a reused id —
        an evict + readmit can happen within one tick, invisible to
        ``in_flight_ids`` alone."""
        return tuple(
            (iid, self._admission_seq[iid]) for iid in self._slot_of
        )

    def step(self) -> Optional[dict]:
        """One engine tick: evict -> admit -> fused decode+score+record.

        Each phase opens its span (``engine.evict``, ``engine.admit``,
        ``engine.grow_pages``, ``engine.decode_step``,
        ``engine.fetch_metrics``, ``engine.account``): under a JAX profiler
        session they lie on the device trace's clock. MoE families also
        open a zero-length ``engine.moe`` after the fetch, whose arguments
        are the step's routing: ``routed`` token-expert pairs,
        ``experts_hit`` (experts with a token, summed over the MoE layers)
        and ``load_max`` (the worst layer's largest expert load over its
        mean)."""
        t0 = time.perf_counter()
        self._evict_done()
        while self._free:
            # a request whose instance id is already resident must wait for
            # that slot to evict (two live slots under one id would corrupt
            # _slot_of and leak the older slot); later requests may admit
            # ahead of it. In paged mode a request whose worst-case page
            # need exceeds the pool's headroom defers (a smaller request
            # behind it may still admit) — exhaustion never touches a live
            # slot.
            idx = None
            for i, r in enumerate(self._queue):
                if r.instance_id in self._slot_of:
                    continue
                if (
                    self.pool is not None
                    and not self.pool.fits(self._pages_needed(r)[2])
                ):
                    self.deferred_admissions += 1
                    self._c_deferred.inc()
                    continue
                idx = i
                break
            if idx is None:
                break
            self._admit(self._queue.pop(idx))
        if not self._slot_of:
            return None
        if self.pool is not None:
            with self.telemetry.span("engine.grow_pages"):
                self._grow_pages()
        step_args = {"occupied": len(self._slot_of)}
        if self.pool is not None:  # the pages the paged kernel walks
            step_args["pages"] = sum(
                len(p) for p in self._slot_pages.values()
            )
        with self.telemetry.span("engine.decode_step", **step_args):
            if self.guard_transfers and self._warm:
                with jax.transfer_guard("disallow"):
                    out = self._decode(
                        self.params, self._estate, self._rstate
                    )
            else:
                out = self._decode(self.params, self._estate, self._rstate)
                self._warm = True
        self._estate, self._rstate, metrics = out
        with self.telemetry.span("engine.fetch_metrics"):
            metrics = jax.device_get(metrics)
        if "moe_routed" in metrics:
            # zero-length: its arguments carry the step's routing
            with self.telemetry.span(
                "engine.moe", routed=int(metrics["moe_routed"]),
                experts_hit=int(metrics["moe_experts_hit"]),
                load_max=float(metrics["moe_load_max"]),
            ):
                pass
        with self.telemetry.span("engine.account"):
            self._account(metrics, t0)
        return metrics

    def _account(self, metrics: dict, t0: float) -> None:
        """Host work on one step's fetched metrics: the host or shadow
        ledger record, the counters, the ``pos`` mirror; ``t0`` is when
        the step began."""
        self._fresh_labels.clear()  # this step's `pending` saw every label
        if self.recorder.host_history is not None:
            self.recorder.record_host(
                metrics["inst"], metrics["loss"], metrics["loss_valid"],
                self.steps_run + 1,
                signals=np.stack(
                    [metrics["entropy"], metrics["margin"]], axis=-1
                ),
            )
        if self._shadow is not None:
            # the drift oracle: same rows, same step number the fused step
            # recorded on device — all from the metrics already fetched
            v = np.asarray(metrics["loss_valid"], bool)
            if v.any():
                self._shadow.record(
                    np.asarray(metrics["inst"], np.int64)[v],
                    np.asarray(metrics["loss"])[v],
                    self.steps_run + 1,
                    signals=np.stack(
                        [metrics["entropy"], metrics["margin"]], axis=-1
                    )[v],
                )
        self._last_metrics = metrics
        self.steps_run += 1
        self.generated_tokens += int(metrics["decoding"].sum())
        self.a2a_overflow += int(metrics["a2a_overflow"])
        if self.pool is not None:
            # host mirror of the device pos vector (what _grow_pages keys
            # on): advances exactly where the step decoded
            self._pos_host += np.asarray(metrics["decoding"], bool)
        self._obs_on_step(metrics, (time.perf_counter() - t0) * 1e3)

    def _obs_on_step(
        self, metrics: dict, dt_ms: Optional[float] = None
    ) -> None:
        """Update instruments from one step's ALREADY-FETCHED numpy
        metrics — plain host arithmetic, no jax.Array anywhere (the
        telemetry transfer-freedom contract; priced by the ``obs`` row in
        ``benchmarks/selection_bench``)."""
        n_rec = int(np.sum(metrics["loss_valid"]))
        n_miss = int(np.sum(metrics["topk_miss"]))
        self._records_host += n_rec
        self._miss_host += n_miss
        self._c_steps.inc()
        self._c_tokens.inc(int(np.sum(metrics["decoding"])))
        self._c_records.inc(n_rec)
        self._c_miss.inc(n_miss)
        self._c_overflow.inc(int(metrics["a2a_overflow"]))
        self._g_occupancy.set(len(self._slot_of) / self.slots)
        self._g_queue.set(len(self._queue))
        if dt_ms is not None:
            self._h_step_ms.observe(dt_ms)

    def loop_health(self, drift: bool = False) -> dict:
        """Loop-health gauges as RATES (not totals): the body of the
        periodic ``--metrics-out`` snapshot and the final summary's
        ``health`` block. The default is host-only arithmetic on counters
        the engine already keeps; ``drift=True`` additionally fetches the
        device ledger's state_dict and compares it per EMA channel against
        the host shadow oracle — that IS a device round-trip, so snapshot
        cadence only, never per step (and never inside the transfer
        guard, which only wraps the fused decode call)."""
        steps = self.steps_run
        attempts = self.admitted + self.deferred_admissions
        h = {
            "steps": steps,
            "occupancy": obs.rate_of(len(self._slot_of), self.slots),
            "queue_depth": len(self._queue),
            "admission_rate": obs.rate_of(self.admitted, steps),
            "eviction_rate": obs.rate_of(self.evicted, steps),
            "deferral_rate": obs.rate_of(self.deferred_admissions, attempts),
            "tokens_per_step": obs.rate_of(self.generated_tokens, steps),
            "records_per_step": obs.rate_of(self._records_host, steps),
            "topk_miss_frac": obs.rate_of(self._miss_host, self._records_host),
            "a2a_overflow_rate": obs.rate_of(
                self.a2a_overflow, self._records_host
            ),
            "missed_outcome_rate": obs.rate_of(
                self.missed_outcomes,
                self._records_host + self.missed_outcomes,
            ),
        }
        if self.pool is not None:
            h.update(
                {f"pool_{k}": v for k, v in self.pool.stats().items()}
            )
        if drift and self._shadow is not None:
            h["ledger_drift"] = obs.ledger_drift(
                self._shadow.state_dict(),
                self.ledger_state_dict(),
                AUX_CHANNELS,
            )
        return h

    def run(self, max_steps: int = 1_000_000, on_step=None) -> dict:
        """Drive until the queue is empty and every slot drained + evicted.

        ``on_step(engine, metrics)`` runs after every tick — the hook for
        drivers that deliver outcomes mid-flight or sample the ledger.
        """
        n = 0
        while (self._queue or self._slot_of) and n < max_steps:
            metrics = self.step()
            if on_step is not None:
                on_step(self, metrics)
            self._evict_done()
            n += 1
        return self.stats()

    def stats(self) -> dict:
        # one batched fetch of both device counters (recorder.counters)
        n_rec, n_miss = self.recorder.counters(self._rstate)
        return {
            "admitted": self.admitted,
            "evicted": self.evicted,
            "steps": self.steps_run,
            "generated_tokens": self.generated_tokens,
            "recorded": n_rec,
            "topk_misses": n_miss,
            "a2a_overflow": self.a2a_overflow,
            "missed_outcomes": self.missed_outcomes,
            "queued": len(self._queue),
            "in_flight": len(self._slot_of),
            **(
                {
                    "pages_total": self.num_pages,
                    "pages_free": self.pool.free_pages,
                    "pages_reserved": self.pool.reserved_pages,
                    "deferred_admissions": self.deferred_admissions,
                }
                if self.pool is not None
                else {}
            ),
        }

    # -- ledger interchange ---------------------------------------------------

    def ledger_state_dict(self) -> dict[str, np.ndarray]:
        return self.recorder.state_dict(self._rstate)

    def load_ledger_state_dict(self, sd: dict[str, np.ndarray]) -> None:
        self._rstate = self.recorder.load_state_dict(self._rstate, dict(sd))
        self._ledger_epoch += 1  # invalidate live-handle snapshots

    @property
    def ledger(self):
        """Live RecycleFeed-compatible handle (lookup/state_dict)."""
        if self.recorder.host_history is not None:
            return self.recorder.host_history
        return EngineLedgerHandle(self)


def delayed_outcomes(outcomes, delay: int):
    """Build a ``run(on_step=...)`` hook that delivers each instance's
    labels ``delay`` engine steps after its admission — the standard way
    to drive the late-outcome path (the serve CLI, the example and the
    tests all use it). ``outcomes`` is a dict ``{instance_id: labels}``
    or a sequence of ``(instance_id, labels)`` pairs; a repeated id (the
    stream's pool wrapped) queues per-residency labels FIFO, matching the
    engine's in-order admission of same-id requests. Delivered entries
    are consumed.
    """
    from collections import deque

    q: dict[int, deque] = {}
    items = outcomes.items() if isinstance(outcomes, dict) else outcomes
    for iid, labels in items:
        q.setdefault(int(iid), deque()).append(labels)
    due: dict[int, int] = {}
    seen: set[tuple[int, int]] = set()

    def on_step(engine: Engine, metrics) -> None:
        del metrics
        # keyed by (id, admission seq): exactly one delivery per RESIDENCY,
        # even when a reused id evicts + readmits within one tick
        for iid, seq in engine.in_flight_admissions():
            if (iid, seq) not in seen:
                seen.add((iid, seq))
                if iid in q:
                    due[iid] = engine.steps_run + delay
        for iid, at in list(due.items()):
            if engine.steps_run >= at:
                engine.deliver_outcome(iid, q[iid].popleft())
                if not q[iid]:
                    del q[iid]
                del due[iid]

    return on_step


class EngineLedgerHandle:
    """Read-only live view of an engine's device ledger.

    ``lookup(ids)`` answers from a host snapshot of the (global-layout)
    table, refreshed whenever the engine has stepped since the last call —
    the handle a ``data.RecycleFeed`` joins its batches against while the
    engine keeps serving.
    """

    def __init__(self, engine: Engine):
        self._engine = engine
        self._snap_at: Optional[tuple] = None
        self._hist: Optional[LossHistory] = None

    def _refresh(self) -> LossHistory:
        at = (
            int(jax.device_get(self._engine._estate.step)),
            self._engine._ledger_epoch,  # load_ledger_state_dict bumps it
        )
        if self._hist is None or at != self._snap_at:
            h = LossHistory(self._engine.recorder.cfg)
            h.load_state_dict(self._engine.ledger_state_dict())
            self._hist, self._snap_at = h, at
        return self._hist

    def lookup(self, ids):
        return self._refresh().lookup(ids)

    def lookup_signals(self, ids):
        return self._refresh().lookup_signals(ids)

    def priority(self, ids, step):
        return self._refresh().priority(ids, step)

    def state_dict(self):
        return self._engine.ledger_state_dict()
