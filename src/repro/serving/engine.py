"""Cascade-aware serving engine: prefill + decode with confidence-thresholded
early exit (Algorithm 1 applied per generated token), KV/state backfill, and
depth-compacted lane batching.

Each lane carries one :class:`repro.core.exec.DecodeState` — position cursor,
active mask, stateful-measure streaks, confidence EMA, and per-segment
execution counters — through the :class:`~repro.core.exec.StagedExecutor`.
Under ``cascade.exit_mode == "cond_batch"`` exited segments genuinely skip
their compute (lax.cond), and the engine reports BOTH the paper's analytic
MAC speedup (§6.2) and the measured wall-clock per-token cost, plus the real
(executed) skip rate next to the scheduling *opportunity* rate.

Exit decisions route through the shared :class:`repro.core.policy.ExitDecider`
resolved from the config's ``cascade.confidence`` / ``cascade.policy``
registry strings — swapping the measure (entropy, margin, patience@k, a
custom registered one) requires no engine change.

Two execution runtimes (``runtime=`` at construction):

* ``"host"`` — one jitted decode step per token, synced to host every tick
  (simple, admission-responsive; dispatch overhead per token).
* ``"device"`` — a :class:`repro.serving.runtime.DeviceDecodeLoop` decodes
  up to ``chunk`` tokens per dispatch inside a ``lax.while_loop``; tokens /
  exit indices land in device buffers and sync once per chunk.  Per-token
  dispatch cost is amortized ~chunk-fold (the win at small lane batches).
  Pass ``mesh`` to shard the whole loop carry over devices (shard_rules
  layout).  Token streams are bit-identical to the host runtime for
  requests admitted at the same points — i.e. whenever nothing queues
  (offered load <= slot capacity).  QUEUED requests admit at chunk
  boundaries here (up to ``chunk`` tokens later than the host runtime),
  so a lane's re-prefill can land at a different generated length and
  its sequences legitimately diverge: an admission-latency trade, not an
  execution-semantics difference.

Both runtimes time the jit warm-up call separately and report it as
``compile_seconds`` in :meth:`stats` — ``wallclock_us_per_token`` never
includes compilation.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import itertools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.exec import CONF_EMA_DECAY, StagedExecutor, effective_cohorts
from repro.core.macs import segment_macs_per_token
from repro.launch.shard_rules import (cache_spec, constrain_carry,
                                     place_params, to_shardings)
from repro.models.model import CascadeModel, extra_input_shapes
from repro.serving.batching import DepthCompactor, cohort_capacity
from repro.serving.paged import PagedCascadeCache
from repro.serving.runtime import DeviceDecodeLoop, kernel_provenance
from repro.utils import get_logger

log = get_logger("serving")

# flight-recorder process naming (traceviz tracks / fleet scrape labels):
# engines number themselves in construction order
_ENGINE_SEQ = itertools.count()


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    extra: Optional[dict] = None


@dataclasses.dataclass
class _Slot:
    request: Optional[Request] = None
    generated: Optional[List[int]] = None
    exit_depths: Optional[List[int]] = None
    confs: Optional[List[float]] = None
    pos: int = 0
    done: bool = True


def _escalation_extra(req: Request) -> Optional[dict]:
    """The tier's re-submission tag, set by ``repro.escalate`` when a
    deferred request is replayed into this engine (None for fresh
    traffic).  Carries ``replayed`` — how many of the prompt's trailing
    tokens are a prefix another stage already decoded — so the accounting
    can attribute that prefill to the escalated request instead of
    counting it as fresh traffic."""
    extra = req.extra or {}
    esc = extra.get("escalation")
    return esc if isinstance(esc, dict) else None


class CascadeServingEngine:
    """Multi-lane batched decode with cascade early exit.

    Each lane holds ``lane_batch`` sequences sharing one KV cache; lanes step
    independently so the DepthCompactor can group easy (shallow-exit) traffic
    away from hard traffic, letting ``cond_batch`` skips fire.
    """

    def __init__(self, cfg: ModelConfig, model: CascadeModel, params,
                 lane_batch: int = 4, n_lanes: int = 2,
                 cache_len: int = 256, runtime: str = "host",
                 chunk: int = 8, mesh=None, autotune=None):
        if runtime not in ("host", "device"):
            raise ValueError(
                f"runtime must be 'host' or 'device', got {runtime!r}")
        if mesh is not None and runtime != "device":
            raise ValueError(
                "mesh sharding is only applied by the device decode loop; "
                "the host per-token step runs unsharded — pass "
                "runtime='device' (or drop mesh=) rather than silently "
                "serving single-device")
        if autotune is not None and autotune is not False \
                and not cfg.autotune.enabled:
            raise ValueError(
                "autotune= needs telemetry in the decode graphs: build the "
                "model/engine with cfg.with_autotune(enabled=True) (plus "
                "epsilon= or mac_budget=) before passing a controller")
        self.cfg = cfg
        self.model = model
        self.mesh = mesh
        if mesh is not None:
            # weights go where the device loop's shardings put them, once;
            # passed as initialised they would be resharded on every chunk
            params = place_params(params, cfg, mesh)
        self.params = params
        # one-time layout normalization at admission capacity: lanes are
        # sized to a cohort multiple so cohort-split skipping never
        # silently degrades (the extra slots are plain admission capacity)
        rounded = cohort_capacity(lane_batch, cfg.cascade.n_cohorts)
        if rounded != lane_batch:
            log.info("lane_batch %d rounded up to %d (cohort multiple of "
                     "n_cohorts=%d)", lane_batch, rounded,
                     cfg.cascade.n_cohorts)
        self.lane_batch = rounded
        lane_batch = rounded
        self.n_lanes = n_lanes
        self.cache_len = cache_len
        self.runtime = runtime
        self.chunk = chunk
        self.cohorts = effective_cohorts(cfg.cascade.n_cohorts, lane_batch,
                                         warn=True)
        self.compactor = DepthCompactor(n_lanes, cfg.cascade.n_components)
        # flight recorder (repro.obs): host-side span assembly at the
        # existing sync points — never touches a traced graph, so enabling
        # it can neither retrace nor change streams (tests/test_obs.py)
        self.flight = None
        self._provenance = None
        if cfg.obs.enabled:
            from repro.obs.recorder import FlightRecorder
            self.flight = FlightRecorder.from_config(
                cfg.obs, name=f"engine{next(_ENGINE_SEQ)}")
            self._provenance = kernel_provenance(cfg)
        # tuned kernel tiles install BEFORE anything traces (tiles are
        # static kernel params — installing later would retrace every lane)
        if cfg.kernel_tune.enabled:
            from repro.kernels.autotune import ensure_tuned
            ensure_tuned(cfg)
        self.executor = StagedExecutor(model, cfg)
        self.decider = self.executor.decider
        self.mac_prefix = segment_macs_per_token(cfg, cache_len)
        # paged KV layout: shared block stores + per-slot block tables.
        # Admission claims pool blocks for exactly the positions a request
        # will span; slot finish returns them at the next host sync (the
        # dense layout's always-resident worst-case slab is the ablation).
        self.paged = cfg.paged_cache.layout == "paged"
        self.pcache = (PagedCascadeCache(model, cfg, lane_batch, n_lanes,
                                         cache_len)
                       if self.paged else None)
        # dense-equivalent cache footprint (for the stats()/bench memory
        # comparison, in both layouts)
        tmpl = jax.eval_shape(
            lambda: model.init_cache(lane_batch, cache_len))
        self._dense_cache_bytes = n_lanes * int(sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(tmpl["segments"])))
        # a fresh dense lane cache, made where the device loop reads it:
        # under a mesh, made whole on device 0, every idle lane would cost
        # that device a full lane cache
        self._init_cache = functools.partial(model.init_cache, lane_batch,
                                             cache_len)
        if mesh is not None:
            self._init_cache = jax.jit(self._init_cache, out_shardings=(
                to_shardings(mesh, cache_spec(tmpl, cfg, mesh, lane_batch))))
        self.lanes = []
        for i in range(n_lanes):
            lane = {
                "slots": [_Slot() for _ in range(lane_batch)],
                "state": self.executor.init_state(
                    lane_batch, mac_weights=self.mac_prefix,
                    block_tables=(self.pcache.device_tables(i)
                                  if self.paged else None)),
            }
            if self.paged:
                lane["cache"] = None
                lane["kpos"] = self.pcache.fresh_kpos()
            else:
                lane["cache"] = self._init_cache()
            self.lanes.append(lane)
        self.queue: List[Request] = []
        self.finished: Dict[int, dict] = {}
        # admission gate (fleet drain hook): False stops _admit() pulling
        # from the queue while in-flight slots keep decoding to exit or
        # budget — the "stop admitting, run to completion" half of a drain.
        # Plain host state; flipping it never touches device buffers.
        self.admitting = True
        # admission-latency accounting (ticks between submit and admit) and
        # lanes whose block tables changed since their state last synced
        self._tick = 0
        self._submit_tick: Dict[int, int] = {}
        self._tables_stale: set = set()
        # live thresholds (autotune): engine-wide vector pushed into every
        # lane's DecodeState as plain data — None until a controller (or a
        # caller) pushes one, in which case the config's static vector is
        # what the carried state was seeded with anyway
        self._live_thresholds = (tuple(cfg.cascade.thresholds)
                                 if cfg.autotune.enabled else None)
        # a ThresholdController (or True → build one from cfg.autotune)
        self.controller = None
        if autotune is True:
            from repro.autotune.controller import ThresholdController
            self.controller = ThresholdController(cfg, self.mac_prefix)
        elif autotune:
            self.controller = autotune
        # jit warm-up accounting: the first decode dispatch per runtime path
        # pays compilation and is reported as compile_seconds, never as
        # decode wall-clock (reset_metrics does NOT clear these — compile is
        # a one-time cost, not part of any measurement window)
        self._compile_seconds = 0.0
        self._decode_warm = False
        self.reset_metrics()
        # cache + DecodeState are donated: the engine never reuses the old
        # buffers, and in-place updates keep decode wall-clock honest
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(2, 3))
        self._decode = jax.jit(self._decode_impl, donate_argnums=(2, 3))
        # continuous (single-slot) admission prefill: only the shared block
        # stores are donated — the lane's kpos buffer stays live on the
        # host side, which is why this takes segments rather than a cache
        self._slot_prefill = jax.jit(self._slot_prefill_impl,
                                     donate_argnums=(2,))
        self.loop = (DeviceDecodeLoop(model, cfg, chunk=chunk,
                                      cache_len=cache_len, mesh=mesh)
                     if runtime == "device" else None)
        if self.controller is not None:
            self.controller.attach(self)

    def reset_metrics(self):
        """Zero the MAC / wall-clock / skip-rate accounting.  The
        compactor's learned depth EMAs survive (scheduler state); only its
        skip counters reset, so the MAC / wall-clock / skip rates in
        :meth:`stats` all cover the same step window.  ``compile_seconds``
        and the warm flags also survive: jit compilation is timed apart
        from decode automatically, so resetting after warm-up is no longer
        required for a clean ``wallclock_us_per_token``.  Per-request
        outputs (``finished``, and the ``requests_finished`` / exit-depth
        stats derived from them) are NOT cleared — they describe completed
        work, not a measurement window.  The warm-up dispatch (host: first
        step; device: first chunk) is excluded from EVERY window metric —
        MAC, skip, opportunity, wallclock — so they always describe the
        same steps.  Escalation accounting (replayed-prefix prefill
        tokens/MACs/seconds) is window accounting and resets with the
        rest; the paged pool's PEAK occupancy and lifetime reclaim
        counters survive (they describe high-water capacity, the same
        split that keeps ``compile_seconds`` out of the decode window) —
        only its per-chunk reclaim window clears."""
        self.compactor.reset_skip_counters()
        self._macs_spent = 0.0
        self._macs_dense = 0.0
        self._decode_seconds = 0.0
        self._decode_tokens = 0
        self._segments_run = np.zeros(self.cfg.cascade.n_components, np.int64)
        self._decode_steps = 0
        self._skip_opportunities = 0
        self._skip_opportunity_total = 0
        self._admit_waits: List[int] = []
        # escalation window: replayed-prefix prefill attributed to the
        # escalated requests that caused it, never to fresh traffic
        self._prefill_positions_fresh = 0
        self._prefill_positions_replayed = 0
        self._replay_prefill_macs = 0.0
        self._replay_prefill_seconds = 0.0
        self._escalated_admitted = 0
        self._cancelled_for_escalation = 0
        if getattr(self, "paged", False) and self.pcache is not None:
            self.pcache.pool.reset_window()

    # -- jitted cores ---------------------------------------------------
    def _prefill_impl(self, params, tokens, cache, state, extra):
        d, cache, state = self.executor.prefill(params, tokens, cache, extra,
                                                state=state)
        if self.mesh is not None:
            cache, state = constrain_carry(cache, state, self.cfg, self.mesh,
                                           tokens.shape[0])
        return d.prediction, d.exit_index, d.confidence, cache, state

    def _decode_impl(self, params, token, cache, state, extra):
        d, cache, state = self.executor.decode_step(params, token, cache,
                                                    state, extra)
        return d.prediction, d.exit_index, d.confidence, cache, state

    def _slot_prefill_impl(self, params, tokens, segments, positions,
                           write_slots, tables, extra):
        return self.model.prefill_into(
            params, tokens, {"segments": segments, "kpos": None},
            positions, write_slots, tables, extra)

    # -- cache layout plumbing -------------------------------------------
    def _lane_cache(self, lane):
        """The cache pytree a dispatch consumes: the lane's private slab
        (dense) or its kpos ring composed over the shared block stores
        (paged).  Lanes dispatch serially, so composing at dispatch time
        always picks up the stores adopted back from the previous lane."""
        if self.paged:
            return self.pcache.lane_cache(lane["kpos"])
        return lane["cache"]

    def _take_cache(self, lane, cache):
        """Adopt a dispatch's (donated-in, returned-out) cache."""
        if self.paged:
            lane["kpos"] = self.pcache.adopt(cache)
        else:
            lane["cache"] = cache

    def _sync_tables(self, lane, lane_id: int):
        """Push rebuilt block tables into the lane's DecodeState after
        release/alloc changed its rows — a data swap (same (K, B, nblk)
        int32 shape), never a retrace."""
        if self.paged and lane_id in self._tables_stale:
            lane["state"] = lane["state"].replace(
                block_tables=self.pcache.device_tables(lane_id))
            self._tables_stale.discard(lane_id)

    # -- public API -----------------------------------------------------
    def submit(self, req: Request):
        self._submit_tick.setdefault(req.rid, self._tick)
        self.queue.append(req)
        if self.flight is not None:
            self.flight.on_submit(req.rid, self._tick)

    # -- fleet surface ----------------------------------------------------
    def free_slot_count(self) -> int:
        """Slots a placement could admit into right now (all lanes)."""
        return sum(1 for ln in self.lanes for s in ln["slots"] if s.done)

    def queued_count(self) -> int:
        return len(self.queue)

    def live_rids(self) -> List[int]:
        """Rids currently decoding in a slot (admitted, not finished)."""
        return [s.request.rid for ln in self.lanes for s in ln["slots"]
                if not s.done and s.request is not None]

    def take_queue(self) -> List[Request]:
        """Drain hook: remove and return every still-queued request (FIFO
        order), clearing their submit-tick bookkeeping so a scheduler can
        requeue them to a sibling engine without this engine ever counting
        them as admitted or dropped."""
        taken, self.queue = self.queue, []
        for req in taken:
            self._submit_tick.pop(req.rid, None)
            if self.flight is not None:
                # the rid leaves this engine without ever being admitted;
                # finalize its flight so the recorder holds no dangling
                # live entry (the sibling that picks it up records anew)
                self.flight.on_finish(req.rid, "cancelled",
                                      {"queued": True, "reason": "requeue",
                                       "n_tokens": 0})
        return taken

    def _predict_depth(self, req: Request) -> float:
        """Expected exit depth for an incoming request: an explicit hint in
        ``req.extra["predicted_depth"]`` (e.g. from an earlier turn's prefill
        exit) wins; otherwise the compactor's population prior over observed
        prefill exits."""
        hint = (req.extra or {}).get("predicted_depth")
        return self.compactor.predict_depth(hint)

    def _record_admit(self, req: Request, lane_id: Optional[int] = None,
                      slot_idx: Optional[int] = None,
                      depth: Optional[float] = None):
        sub = self._submit_tick.pop(req.rid, self._tick)
        wait = self._tick - sub
        self._admit_waits.append(wait)
        esc = _escalation_extra(req)
        if esc is not None:
            self._escalated_admitted += 1
        if self.flight is not None:
            per = max(1, self.lane_batch // self.cohorts)
            attrs = dict(self._provenance or {})
            if esc is not None:
                attrs["escalated_from"] = esc.get("rid")
                attrs["replayed"] = esc.get("replayed")
                attrs["migrated"] = bool(esc.get("migrated"))
            self.flight.on_admit(
                req.rid, lane=lane_id, slot=slot_idx,
                cohort=(slot_idx // per if slot_idx is not None else None),
                predicted_depth=(float(depth) if depth is not None
                                 else None),
                wait_ticks=wait, tick=self._tick, attrs=attrs)

    def _replayed_len(self, req: Request) -> int:
        """Trailing prompt tokens another stage already decoded (0 for
        fresh traffic) — the prefill positions escalation accounting
        attributes to the escalated request."""
        esc = _escalation_extra(req)
        if esc is None:
            return 0
        return max(0, min(int(esc.get("replayed", 0)), len(req.prompt)))

    def _account_prefill(self, req: Request, seconds: float,
                         padded_positions: int):
        """Attribute one newly admitted request's prefill: its prompt
        positions split into fresh traffic vs a replayed prefix an earlier
        escalation stage already decoded.  Replayed positions are priced
        at the full-depth per-token MAC cost (prefill computes every
        component) and charged to the escalation window — NOT to the
        fresh prefill counter and never to the decode window, so
        ``wallclock_us_per_token`` keeps its decode-only meaning and the
        tier can account replay cost against the escalated request.
        ``seconds`` of a shared dispatch are attributed by the request's
        replayed share of the padded positions it rode in."""
        replayed = self._replayed_len(req)
        self._prefill_positions_fresh += len(req.prompt) - replayed
        self._prefill_positions_replayed += replayed
        if replayed:
            self._replay_prefill_macs += replayed * float(self.mac_prefix[-1])
            self._replay_prefill_seconds += seconds * (
                replayed / max(1, padded_positions))

    def _admit(self):
        if self.paged:
            return self._admit_paged()
        while self.queue:
            free = [i for i, lane in enumerate(self.lanes)
                    if any(s.done for s in lane["slots"])]
            if not free:
                break
            req = self.queue.pop(0)
            depth = self._predict_depth(req)
            lane_id = self.compactor.assign(depth, free)
            lane = self.lanes[lane_id]
            # within the lane, place the request in the cohort whose depth
            # band matches — cohort-split skip predicates (n_cohorts > 1)
            # only fire when a cohort's co-residents exit together
            free_slots = [i for i, s in enumerate(lane["slots"]) if s.done]
            slot_idx = self.compactor.pick_slot(
                depth, free_slots, self.lane_batch, self.cohorts)
            slot = lane["slots"][slot_idx]
            slot.request = req
            slot.generated = []
            slot.exit_depths = []
            slot.confs = []
            slot.done = False
            # cache is shared per-lane, so we prefill the whole lane
            # when admission changes (simple + correct).
            lane["dirty"] = True
            self._record_admit(req, lane_id, slot_idx, depth)

    # -- paged admission --------------------------------------------------
    def _free_per_cohort(self, lane) -> List[int]:
        per = self.lane_batch // self.cohorts
        return [sum(1 for i in range(c * per, (c + 1) * per)
                    if lane["slots"][i].done)
                for c in range(self.cohorts)]

    def _pad_prompt(self, n: int) -> int:
        """Continuous-admission prompts pad to a power of two (>= 2) so the
        B=1 slot-prefill jit compiles a bounded set of shapes."""
        return max(2, 1 << max(0, int(n - 1).bit_length()))

    def _continuous_feasible(self, lane_id: int, req: Request) -> bool:
        """Can ``req`` join this LIVE lane between chunks?  Needs a free
        slot, enough decoded history for the padded prompt's offset
        positions (P_pad <= t), and pool coverage for exactly the
        positions the slot will span."""
        lane = self.lanes[lane_id]
        if not any(s.done for s in lane["slots"]):
            return False
        t0 = int(np.asarray(lane["state"].t))
        P_pad = self._pad_prompt(len(req.prompt))
        if P_pad > t0:
            return False
        need = self.pcache.blocks_needed(t0 - P_pad,
                                         t0 + req.max_new_tokens)
        return self.pcache.can_admit(need)

    def _lane_plan_fits(self, lane_id: int, req: Request) -> bool:
        """Whole-lane path feasibility: would the lane's re-prefill plan
        (every live slot + ``req``, padded to the common context length)
        fit the pool once the lane's current reservations are released?
        Allocation itself happens at prefill time, when the true common
        length is known."""
        lane = self.lanes[lane_id]
        ctxs = [(len(s.request.prompt) + len(s.generated),
                 max(1, s.request.max_new_tokens - len(s.generated)))
                for s in lane["slots"] if not s.done]
        ctxs.append((len(req.prompt), req.max_new_tokens))
        S = max(2, max(c for c, _ in ctxs))
        need = sum(self.pcache.blocks_needed(0, S + rem)
                   for _, rem in ctxs)
        have = self.pcache.pool.free_blocks + sum(
            self.pcache.slot_blocks(lane_id, i)
            for i in range(self.lane_batch))
        return need <= have

    def _admit_paged(self):
        """Admission under the paged layout.  A request needs a free slot
        AND block coverage for the positions it will actually span — not a
        worst-case-length lane slot.  Two paths:

        * live lane → CONTINUOUS single-slot admission: blocks for
          ``[t - P_pad, t + budget)`` are claimed now and the prompt
          prefills into them between decode dispatches, leaving sibling
          streams untouched (no whole-lane re-prefill).
        * empty/dirty lane → the dense whole-lane path (bit-identity with
          the dense ablation for lanes admitted this way), feasibility-
          checked against the pool.

        Head-of-queue blocking: if the head fits nowhere the queue waits
        (FIFO — keeps exit accounting comparable with the dense ablation).
        Pool exhaustion therefore backpressures admission; it can never
        corrupt resident slots, because alloc_slot is all-or-nothing."""
        while self.queue:
            req = self.queue[0]
            if not self.pcache.fits_ever(
                    0, max(2, len(req.prompt)) + req.max_new_tokens):
                raise ValueError(
                    f"request rid={req.rid} can never fit: prompt + "
                    f"max_new_tokens spans more blocks than the pool owns; "
                    f"raise paged_cache.num_blocks or shrink the request")
            depth = self._predict_depth(req)
            whole = [i for i, ln in enumerate(self.lanes)
                     if (ln.get("dirty") or all(s.done for s in ln["slots"]))
                     and any(s.done for s in ln["slots"])]
            live = [i for i, ln in enumerate(self.lanes)
                    if i not in whole and any(s.done for s in ln["slots"])]
            cands = [i for i in live if self._continuous_feasible(i, req)]
            if cands:
                lane_id = self.compactor.assign(depth, cands)
                # _admit_continuous records the admit itself (it knows the
                # slot, and it may retire the request in the same call —
                # the flight's admit span must land before its terminal)
                self.queue.pop(0)
                self._admit_continuous(lane_id, req, depth)
            else:
                cands = [i for i in whole if self._lane_plan_fits(i, req)]
                if not cands:
                    break
                lane_id = self.compactor.assign(depth, cands)
                lane = self.lanes[lane_id]
                free_slots = [i for i, s in enumerate(lane["slots"])
                              if s.done]
                slot_idx = self.compactor.pick_slot(
                    depth, free_slots, self.lane_batch, self.cohorts,
                    free_per_cohort=self._free_per_cohort(lane))
                slot = lane["slots"][slot_idx]
                slot.request = req
                slot.generated = []
                slot.exit_depths = []
                slot.confs = []
                slot.done = False
                lane["dirty"] = True
                self.queue.pop(0)
                self._record_admit(req, lane_id, slot_idx, depth)

    def _admit_continuous(self, lane_id: int, req: Request, depth: float):
        """Prefill ``req`` into a single freed slot of a live lane.

        The prompt left-pads to ``P_pad`` and runs a B=1 full-mode forward
        at absolute positions ``[t - P_pad, t)`` writing ONLY through the
        slot's freshly allocated blocks; its kpos row masks everything it
        didn't write.  The sanctioned divergence from the dense ablation
        (which must re-prefill the whole lane and restart sibling
        alignment to a new common length): the admitted stream's history
        starts at an offset, so its token stream is its own — sibling
        streams are untouched, which is the point.  Telemetry shadow rows
        for this prefill are skipped (one B=1 decision; the decode-time
        telemetry picks the slot up on its first step)."""
        lane = self.lanes[lane_id]
        state = lane["state"]
        t0 = int(np.asarray(state.t))
        P = len(req.prompt)
        P_pad = self._pad_prompt(P)
        free_slots = [i for i, s in enumerate(lane["slots"]) if s.done]
        slot_idx = self.compactor.pick_slot(
            depth, free_slots, self.lane_batch, self.cohorts,
            free_per_cohort=self._free_per_cohort(lane))
        self._record_admit(req, lane_id, slot_idx, depth)
        ok = self.pcache.alloc_slot(lane_id, slot_idx, t0 - P_pad,
                                    t0 + req.max_new_tokens)
        assert ok, "continuous admission raced the feasibility check"
        start = t0 - P_pad
        toks = np.zeros((1, P_pad), np.int32)
        toks[0, P_pad - P:] = req.prompt
        W = self.pcache.W
        # ring slot -> (kept token index, kept absolute position): newest
        # position wins on ring wrap, everything unwritten stays masked
        write_slots = np.full((W,), -1, np.int32)
        krow = np.full((W,), -1, np.int32)
        for p in range(max(start, t0 - W), t0):
            write_slots[p % W] = p - start
            krow[p % W] = p
        tables = self.pcache.device_tables(lane_id)[
            :, slot_idx:slot_idx + 1, :]
        t_pre = time.perf_counter()
        logits, new_segs = self._slot_prefill(
            self.params, jnp.asarray(toks), self.pcache.segments,
            jnp.asarray(start + np.arange(P_pad, dtype=np.int32)),
            jnp.asarray(write_slots), tables, self._extra(1))
        jax.block_until_ready(logits)
        dt_pre = time.perf_counter() - t_pre
        self.pcache.segments = new_segs
        self._account_prefill(req, dt_pre, P_pad)
        if self.flight is not None:
            self.flight.on_prefill(lane_id, t_pre, dt_pre, [req.rid],
                                   [req.rid], P_pad)
        d, _ = self.decider.decide_with_carry(
            logits, thresholds=state.thresholds,
            state=self.decider.measure.init_state(
                self.cfg.cascade.n_components, 1),
            active=jnp.ones((1,), bool))
        # merge the B=1 prefill decision into the lane's carried state:
        # the prefill decision seeds the stateful-measure streak exactly
        # like whole-lane prefill does (exec._carry_forward)
        policy = state.policy
        if policy is not None and d.state is not None:
            policy = jax.tree_util.tree_map(
                lambda full, one: full.at[..., slot_idx].set(one[..., 0]),
                policy, d.state)
        conf = float(np.asarray(d.confidence)[0])
        ema = state.ema_conf.at[slot_idx].set(
            (1.0 - CONF_EMA_DECAY) * conf)
        lane["kpos"] = lane["kpos"].at[slot_idx].set(jnp.asarray(krow))
        s = lane["slots"][slot_idx]
        s.request = req
        s.generated = []
        s.exit_depths = []
        s.confs = []
        s.done = False
        lane["state"] = state.replace(
            active=jnp.asarray(self._live_mask(lane)),
            policy=policy, ema_conf=ema,
            block_tables=self.pcache.device_tables(lane_id))
        self._tables_stale.discard(lane_id)
        tok = int(np.asarray(d.prediction)[0])
        exit_idx = int(np.asarray(d.exit_index)[0])
        if not s.generated:
            self.compactor.observe_prefill_exit(float(exit_idx))
        s.generated.append(tok)
        s.exit_depths.append(exit_idx)
        s.confs.append(conf)
        self._finish_if_done(s, t0, lane_id, slot_idx)

    def _finish_if_done(self, s: _Slot, pos: int, lane_id: int,
                        slot_idx: int):
        if (len(s.generated) >= s.request.max_new_tokens
                or pos >= self.cache_len - 1):
            self._retire(s, lane_id, slot_idx)

    def _retire(self, s: _Slot, lane_id: int, slot_idx: int,
                escalated: bool = False, reason: str = "escalate"):
        s.done = True
        self.finished[s.request.rid] = {
            "tokens": list(s.generated),
            "exit_depths": list(s.exit_depths),
            "confs": list(s.confs),
            "lane": lane_id,
            "escalated": escalated,
        }
        if self.flight is not None:
            ds = np.asarray(s.exit_depths, np.int64)
            self.flight.on_finish(
                s.request.rid, reason if escalated else "exit", {
                    "n_tokens": len(s.generated),
                    "exit_component_last": (int(ds[-1]) if ds.size
                                            else None),
                    "mean_exit_depth": (float(ds.mean()) if ds.size
                                        else None),
                    "macs": (float(np.sum(
                        np.asarray(self.mac_prefix)[ds])) if ds.size
                        else 0.0),
                    "lane": lane_id,
                    "slot": slot_idx,
                })
        # retiring traffic decays the lane's depth EMA toward the
        # population prior so the lane doesn't keep repelling traffic
        # that no longer matches its drained residents
        self.compactor.observe_retire(lane_id)
        if self.paged:
            # skip-aware reclamation at the first host sync after the
            # slot finished (mid-chunk under the device runtime):
            # components the cascade never answered from release as
            # reclaimed_by_exit, the rest at retire (DESIGN.md)
            md = max(s.exit_depths) if s.exit_depths else None
            self.pcache.release_slot(lane_id, slot_idx,
                                     max_exit_depth=md)
            self._tables_stale.add(lane_id)

    def cancel(self, rid: int, keep: Optional[int] = None,
               reason: str = "escalate") -> Optional[dict]:
        """Escalation re-admission hook: retire a live request early,
        keeping only its first ``keep`` generated tokens (None = all).

        The tier calls this between engine ticks when a token finishes at
        the final component below the escalation threshold: the committed
        prefix stands, everything from the deferred token on is discarded
        (tokens past the defer point were decoded from a context the next
        stage re-answers — their compute is already in the MAC window,
        which is honest: it was spent).  Returns the finished record (its
        ``escalated`` flag set) or None if ``rid`` is not known.  A
        still-QUEUED request (submitted, never admitted) is removed from
        the queue and gets a well-formed empty record — no tokens, no
        lane, escalated=True — so drain-time requeue can treat "cancel
        then resubmit elsewhere" uniformly whether or not the request ever
        reached a slot.  Queue cancels do not count toward
        ``cancelled_for_escalation`` (nothing was decoded, so no
        escalation accounting applies) and never touch a lane.

        Safe between ticks in both runtimes: the slot's ``done`` flag
        drops it from the next dispatch's active mask, and the paged
        release path is the ordinary retire path (host-side bookkeeping
        only)."""
        for lane_id, lane in enumerate(self.lanes):
            for slot_idx, s in enumerate(lane["slots"]):
                if s.done or s.request is None or s.request.rid != rid:
                    continue
                if keep is not None:
                    s.generated = s.generated[:keep]
                    s.exit_depths = s.exit_depths[:keep]
                    s.confs = s.confs[:keep]
                self._cancelled_for_escalation += 1
                self._retire(s, lane_id, slot_idx, escalated=True,
                             reason=reason)
                return self.finished[rid]
        for qi, req in enumerate(self.queue):
            if req.rid != rid:
                continue
            self.queue.pop(qi)
            self._submit_tick.pop(rid, None)
            self.finished[rid] = {
                "tokens": [],
                "exit_depths": [],
                "confs": [],
                "lane": None,
                "escalated": True,
            }
            if self.flight is not None:
                # never admitted: terminal "cancelled" regardless of why —
                # no lane, no tokens, nothing to escalate or migrate
                self.flight.on_finish(rid, "cancelled",
                                      {"queued": True, "reason": reason,
                                       "n_tokens": 0})
            return self.finished[rid]
        return None

    def _live_mask(self, lane) -> np.ndarray:
        return np.array([not s.done for s in lane["slots"]])

    def _lane_prefill(self, lane, lane_id: int):
        """(Re)prefill a lane: pad contexts to a common length.

        In-flight slots re-prefill with their full context (prompt + tokens
        generated so far) so admission into a sibling slot never truncates a
        live sequence; the token predicted off that context is their normal
        next-step continuation."""
        slots = lane["slots"]
        prompts = [np.concatenate([s.request.prompt,
                                   np.asarray(s.generated, np.int32)])
                   if not s.done else np.zeros((1,), np.int32)
                   for s in slots]
        S = max(len(p) for p in prompts)
        S = max(S, 2)
        toks = np.zeros((self.lane_batch, S), np.int32)
        for i, p in enumerate(prompts):
            toks[i, -len(p):] = p          # left-pad (simplest alignment)
        if self.paged:
            # whole-lane re-prefill restarts every resident at the common
            # length: release ALL the lane's reservations (no slot keeps
            # coverage planned for the previous alignment), then claim
            # coverage for each live slot's full span at the new one.
            # Admission feasibility (_lane_plan_fits) guaranteed this fits.
            for i in range(self.lane_batch):
                self.pcache.release_slot(lane_id, i)
            for i, s in enumerate(slots):
                if s.done:
                    continue
                rem = max(1, s.request.max_new_tokens - len(s.generated))
                ok = self.pcache.alloc_slot(lane_id, i, 0, S + rem)
                assert ok, "lane prefill outgrew its admission plan"
            lane["kpos"] = self.pcache.fresh_kpos()
            cache_in = self.pcache.lane_cache(lane["kpos"])
            self._tables_stale.discard(lane_id)
        else:
            cache_in = self._init_cache()
        extra = self._extra(self.lane_batch)
        # re-prefill restarts the lane's DecodeState (streaks, EMA, cursors);
        # the prefill decision itself counts as the streak's first step.
        # Autotune telemetry and live thresholds are LANE-lifetime, not
        # prefill-lifetime: carry them across the re-init (telemetry is
        # passed INTO init_state so no zeroed counters are allocated just
        # to be discarded).
        old = lane.get("state")
        state = self.executor.init_state(
            self.lane_batch, active=self._live_mask(lane),
            mac_weights=self.mac_prefix,
            telemetry=(old.tel if old is not None
                       else StagedExecutor._AUTO_TELEMETRY),
            block_tables=(self.pcache.device_tables(lane_id)
                          if self.paged else None))
        if old is not None and old.thresholds is not None:
            state = state.replace(thresholds=old.thresholds)
        fresh_admits = [s for s in slots if not s.done and not s.generated]
        t_pre = time.perf_counter()
        tok, exit_idx, conf, cache, state = self._prefill(
            self.params, jnp.asarray(toks), cache_in, state, extra)
        self._take_cache(lane, cache)
        lane["state"] = state
        tok = np.asarray(tok)
        dt_pre = time.perf_counter() - t_pre
        exit_idx = np.asarray(exit_idx)
        conf = np.asarray(conf)
        # attribute this shared dispatch's replayed-prefix share to the
        # newly admitted escalated requests riding in it (if any)
        for s in fresh_admits:
            self._account_prefill(s.request, dt_pre, self.lane_batch * S)
        if self.flight is not None:
            # before the slot loop below, which may retire flights
            self.flight.on_prefill(
                lane_id, t_pre, dt_pre,
                [s.request.rid for s in slots if not s.done],
                [s.request.rid for s in fresh_admits], S)
        for i, s in enumerate(slots):
            if not s.done:
                if not s.generated:
                    # warm the admission depth prior with the FIRST prefill
                    # exit only (re-prefills of in-flight slots don't
                    # re-count toward the prior)
                    self.compactor.observe_prefill_exit(float(exit_idx[i]))
                s.generated.append(int(tok[i]))
                s.exit_depths.append(int(exit_idx[i]))
                s.confs.append(float(conf[i]))
                # the prefill token counts toward max_new_tokens like any
                # decode tick — an in-flight slot near its limit may finish
                self._finish_if_done(s, S, lane_id, i)
        self._sync_tables(lane, lane_id)
        lane["dirty"] = False

    def _extra(self, batch):
        shapes = extra_input_shapes(self.cfg, batch)
        if not shapes:
            return None
        return {k: jnp.zeros(v, jnp.float32) for k, v in shapes.items()}

    def step(self):
        """One engine tick: admit, prefill dirty lanes, then decode — one
        token per lane (``runtime="host"``) or up to ``chunk`` tokens per
        lane inside the device loop (``runtime="device"``).  With a
        ThresholdController attached, the tick ends with its (rarely
        firing) telemetry → solver → threshold-push check."""
        self._tick += 1
        if self.admitting:
            self._admit()
        for lane_id, lane in enumerate(self.lanes):
            if all(s.done for s in lane["slots"]):
                continue
            if lane.get("dirty"):
                self._lane_prefill(lane, lane_id)
                continue
            if self.runtime == "device":
                self._device_tick(lane, lane_id)
            else:
                self._host_tick(lane, lane_id)
        if self.controller is not None:
            self.controller.maybe_update(self)

    # -- autotune surface -------------------------------------------------
    def lane_telemetry(self) -> List:
        """The lanes' device-resident telemetry pytrees (lane order)."""
        return [lane["state"].tel for lane in self.lanes
                if lane["state"].tel is not None]

    def current_thresholds(self):
        """The live threshold vector lanes decode with, or None (static
        config thresholds)."""
        return self._live_thresholds

    def push_thresholds(self, thresholds) -> None:
        """Swap the live threshold vector in every lane's DecodeState.

        Thresholds are carry DATA — the replacement array has the shape
        and dtype of the one it replaces, so neither the host decode step
        nor the device while_loop retraces (pinned by
        ``tests/test_autotune.py``)."""
        pushed = tuple(float(t) for t in thresholds)
        ths = np.asarray(pushed, np.float32)
        n_m = self.cfg.cascade.n_components
        if ths.shape != (n_m,):
            raise ValueError(f"threshold vector shape {ths.shape} != "
                             f"({n_m},)")
        if not self.cfg.autotune.enabled:
            raise ValueError(
                "live threshold pushes need autotune-enabled decode graphs "
                "(cfg.with_autotune(enabled=True)); without them thresholds "
                "are static trace constants")
        for lane in self.lanes:
            # one device array PER lane: lane states are donated to the
            # jitted steps, so a buffer shared across lanes would be
            # invalidated for lane k+1 the moment lane k dispatches
            lane["state"] = lane["state"].replace(
                thresholds=jnp.array(ths))
        # report what the caller pushed, not its f32 quantization — the
        # controller/artifact values (e.g. the 1.1 never-exit sentinel)
        # must round-trip through current_thresholds() exactly
        self._live_thresholds = pushed
        if self.flight is not None:
            self.flight.on_event("threshold_push",
                                 {"thresholds": list(pushed),
                                  "tick": self._tick})

    # -- observability surface (repro.obs) --------------------------------
    @property
    def obs_events(self):
        """The engine-level event log (None with the recorder off) —
        the hook ThresholdController uses to record solver resolves."""
        return self.flight.events if self.flight is not None else None

    def dump_flight(self, rid: int) -> Optional[dict]:
        """One request's span tree (live or from the done ring), or None
        if unknown / ring-evicted / recorder off."""
        return self.flight.dump(rid) if self.flight is not None else None

    def flights(self, include_live: bool = False) -> List[dict]:
        return (self.flight.flights(include_live)
                if self.flight is not None else [])

    def latency_stats(self) -> dict:
        """p50/p95/p99 latency summaries.  ``admission_wait_ticks`` comes
        from the window counter (available with the recorder off, resets
        with :meth:`reset_metrics`); the rest come from the recorder's
        lifetime reservoirs (None with it off)."""
        from repro.obs.recorder import quantiles
        out = {"admission_wait_ticks": quantiles(self._admit_waits)}
        if self.flight is not None:
            lat = self.flight.latency()
            lat.pop("admission_wait_ticks", None)
            out.update(lat)
        else:
            out.update({"e2e_seconds": None, "per_token_seconds": None,
                        "macs_per_request": None,
                        "tokens_per_request": None})
        return out

    def scrape(self) -> str:
        """Prometheus text exposition of this engine's metrics."""
        from repro.obs.metrics import MetricsRegistry, engine_metrics_into
        return engine_metrics_into(MetricsRegistry(), self).render_text()

    def scrape_json(self) -> dict:
        from repro.obs.metrics import MetricsRegistry, engine_metrics_into
        return engine_metrics_into(MetricsRegistry(), self).render_json()

    def _account(self, lane_id: int, depths: np.ndarray, n_tokens: int,
                 ran: np.ndarray, steps: int, max_depths):
        """Shared per-tick accounting over ``steps`` decode steps of one
        lane: ``depths`` are the exit indices of every live (slot, step),
        ``ran`` the segment execution-counter deltas (cohort units),
        ``max_depths`` the per-step max live exit depth."""
        n_comp = self.cfg.cascade.n_components
        self._decode_steps += steps
        # real execution accounting from the carried segment counters: in
        # cond_batch mode skipped segments genuinely did not compute; with
        # C cohorts a segment-step splits into C independently skippable
        # cohort units, so the skipped count is fractional
        self._segments_run += ran.astype(np.int64)
        C = self.cohorts
        skipped_real = float(np.sum((C * steps - ran[1:]) / C))
        # scheduling headroom: segments nobody needed each step (what a
        # perfect cond_batch run would skip), vs what actually skipped
        for md in max_depths:
            self._skip_opportunities += max(0, (n_comp - 1) - md)
            self._skip_opportunity_total += n_comp - 1
        # analytic MAC accounting (paper §6.2): dense cost vs exit cost
        self._macs_dense += n_tokens * self.mac_prefix[-1]
        self._macs_spent += float(
            np.sum(np.asarray(self.mac_prefix)[depths])) if n_tokens else 0.0
        self.compactor.observe(lane_id, depths, skipped_real, steps=steps)

    def _host_tick(self, lane, lane_id: int):
        """Decode ONE token for every live slot of a lane (one dispatch +
        one host sync per token)."""
        last = [s.generated[-1] if not s.done else 0
                for s in lane["slots"]]
        token = jnp.asarray(np.array(last, np.int32)[:, None])
        live = self._live_mask(lane)
        state = lane["state"].replace(active=jnp.asarray(live))
        run_before = np.asarray(state.segments_run)
        if self.paged:
            self.pcache.pool.begin_chunk()
        t0 = time.perf_counter()
        tok, exit_idx, conf, cache, state = self._decode(
            self.params, token, self._lane_cache(lane), state,
            self._extra(self.lane_batch))
        tok = np.asarray(tok)              # forces device sync
        exit_idx = np.asarray(exit_idx)
        conf = np.asarray(conf)
        dt = time.perf_counter() - t0
        n_live = int(live.sum())
        warm = self._decode_warm
        if warm:
            self._decode_seconds += dt
            self._decode_tokens += n_live
        else:                              # first dispatch pays compilation
            self._compile_seconds += dt
            self._decode_warm = True
        self._take_cache(lane, cache)
        lane["state"] = state
        depths = exit_idx[live]
        ran = np.asarray(state.segments_run) - run_before
        if self.flight is not None:
            # stamped around the dispatch that just synced — the slot loop
            # below may retire flights, so the chunk span lands first
            self.flight.on_chunk(
                lane_id, t0, dt, 1,
                [(s.request.rid, [int(tok[i])], [int(exit_idx[i])],
                  [float(conf[i])])
                 for i, s in enumerate(lane["slots"]) if not s.done],
                compiled=not warm, segments_run=ran)
        if warm:
            # the warm-up dispatch is excluded from EVERY window metric
            # (MAC, skip, opportunity, wallclock) so stats() rates all
            # cover the same steps; its tokens still reach the slots below
            self._account(lane_id, depths, n_live, ran, steps=1,
                          max_depths=[int(depths.max()) if n_live else 0])
        for i, s in enumerate(lane["slots"]):
            if s.done:
                continue
            s.generated.append(int(tok[i]))
            s.exit_depths.append(int(exit_idx[i]))
            s.confs.append(float(conf[i]))
            self._finish_if_done(s, int(state.t), lane_id, i)
        self._sync_tables(lane, lane_id)
        if self.paged:
            self.pcache.pool.end_chunk()

    def _device_tick(self, lane, lane_id: int):
        """Decode up to ``chunk`` tokens for a lane inside the device
        while_loop — one dispatch and ONE host sync per chunk; finished
        slots drain from the returned buffers."""
        slots = lane["slots"]
        last = [s.generated[-1] if not s.done else 0 for s in slots]
        token = np.array(last, np.int32)[:, None]
        live = self._live_mask(lane)
        remaining = np.array(
            [s.request.max_new_tokens - len(s.generated) if not s.done else 0
             for s in slots], np.int32)
        # the host mask rides into the loop as is (placed by its sharding)
        state = lane["state"].replace(active=live)
        run_before = np.asarray(state.segments_run)
        if self.paged:
            self.pcache.pool.begin_chunk()
        chunk, cache, state = self.loop.run_chunk(
            self.params, token, self._lane_cache(lane), state, remaining,
            self._extra(self.lane_batch))
        self._take_cache(lane, cache)
        lane["state"] = state
        n = chunk.n_steps
        n_tok = int(chunk.live.sum())
        if chunk.compiled:                 # first dispatch pays compilation
            self._compile_seconds += chunk.seconds
        else:
            self._decode_seconds += chunk.seconds
            self._decode_tokens += n_tok
        if not n:
            if self.paged:
                self.pcache.pool.end_chunk()
            return
        if self.flight is not None:
            entries = []
            for i, s in enumerate(slots):
                if s.done:
                    continue
                rows = [step for step in range(n) if chunk.live[step, i]]
                entries.append((
                    s.request.rid,
                    [int(chunk.tokens[r, i]) for r in rows],
                    [int(chunk.exits[r, i]) for r in rows],
                    [float(chunk.confs[r, i]) for r in rows]))
            self.flight.on_chunk(
                lane_id, chunk.t_host, chunk.seconds, n, entries,
                compiled=chunk.compiled,
                segments_run=np.asarray(state.segments_run) - run_before)
        if not chunk.compiled:
            # like the host tick: the compile chunk is excluded from every
            # window metric so all stats() rates cover the same steps
            ran = np.asarray(state.segments_run) - run_before
            max_depths = []
            for step in range(n):
                d = chunk.exits[step][chunk.live[step]]
                max_depths.append(int(d.max()) if d.size else 0)
            self._account(lane_id, chunk.exits[chunk.live], n_tok, ran,
                          steps=n, max_depths=max_depths)
        pos = int(state.t)
        for i, s in enumerate(slots):
            if s.done:
                continue
            for step in range(n):
                if chunk.live[step, i]:
                    s.generated.append(int(chunk.tokens[step, i]))
                    s.exit_depths.append(int(chunk.exits[step, i]))
                    s.confs.append(float(chunk.confs[step, i]))
            self._finish_if_done(s, pos, lane_id, i)
        self._sync_tables(lane, lane_id)
        if self.paged:
            self.pcache.pool.end_chunk()

    def run(self, max_ticks: int = 1000):
        for _ in range(max_ticks):
            if not self.queue and all(
                    s.done for ln in self.lanes for s in ln["slots"]):
                break
            self.step()
        return self.finished

    # -- metrics ---------------------------------------------------------
    def speedup(self) -> float:
        """Analytic MAC speedup vs always running the full cascade."""
        if not self._macs_spent:
            return 1.0
        return self._macs_dense / self._macs_spent

    def wallclock_us_per_token(self) -> Optional[float]:
        """Measured decode wall-clock per generated token (µs).  The jit
        warm-up dispatch is timed separately (``compile_seconds`` in
        :meth:`stats`) and never counted here."""
        if not self._decode_tokens:
            return None
        return 1e6 * self._decode_seconds / self._decode_tokens

    def stats(self) -> dict:
        """A SNAPSHOT of the engine's metrics: every nested container is
        deep-copied, so a fleet poller holding the returned dict across
        later ``step()`` calls never observes torn state (the live
        counters — ``_admit_waits``, the paged pool's reclaim window, the
        escalation counters — keep mutating underneath)."""
        depths = list(itertools.chain.from_iterable(
            r["exit_depths"] for r in self.finished.values()))
        opp = (self._skip_opportunities / self._skip_opportunity_total
               if self._skip_opportunity_total else 0.0)
        return copy.deepcopy({
            "requests_finished": len(self.finished),
            "mean_exit_depth": float(np.mean(depths)) if depths else None,
            "exit_histogram": np.bincount(
                depths, minlength=self.cfg.cascade.n_components).tolist()
            if depths else None,
            "analytic_speedup": self.speedup(),
            # realized skips (cond_batch executes them; select never skips)
            "cond_batch_skip_rate": self.compactor.skip_rate(),
            # what perfect depth compaction could have skipped
            "skip_opportunity_rate": opp,
            "segments_run": self._segments_run.tolist(),
            "wallclock_us_per_token": self.wallclock_us_per_token(),
            # one-time jit compilation cost (first decode dispatch per
            # runtime path; cumulative across reset_metrics)
            "compile_seconds": self._compile_seconds,
            "runtime": self.runtime,
            "n_cohorts": self.cohorts,
            "cohort_layout": self.cfg.cascade.cohort_layout,
            "use_kernels": self.cfg.use_kernels,
            "lane_batch": self.lane_batch,
            "chunk": self.chunk if self.runtime == "device" else 1,
            "cache_layout": "paged" if self.paged else "dense",
            # ticks a request waited between submit and admission (0 =
            # admitted the same tick) — the continuous-batching win metric
            "admission_wait_ticks": list(self._admit_waits),
            "admission_wait_mean": (float(np.mean(self._admit_waits))
                                    if self._admit_waits else None),
            # block-pool occupancy (paged) vs the always-resident slab
            # footprint (dense) — same keys so the bench gate can compare
            "memory": (self.pcache.stats() if self.paged else {
                "cache_layout": "dense",
                "num_blocks": None,
                "block_size": None,
                "block_bytes": None,
                "blocks_free": None,
                "blocks_used": None,
                "peak_blocks_used": None,
                "reclaimed_by_exit": 0,
                "reclaimed_at_retire": 0,
                "blocks_reclaimed_per_chunk": [],
                "peak_cache_bytes": self._dense_cache_bytes,
                "dense_slab_bytes": self._dense_cache_bytes,
            }),
            # per-lane mean of the carried confidence EMA (slot difficulty
            # telemetry from DecodeState)
            "lane_conf_ema": [
                float(np.mean(np.asarray(lane["state"].ema_conf)))
                for lane in self.lanes],
            # per-request latency distributions (satellite of PR 10):
            # queueing + end-to-end p50/p95/p99 next to the per-token mean
            "latency": self.latency_stats(),
            "obs": (self.flight.stats() if self.flight is not None
                    else None),
            "autotune": self._autotune_stats(),
            # cross-model escalation accounting: replayed-prefix prefill is
            # attributed to the escalated request (fresh vs replayed
            # position split) so the tier's MAC window never double-counts
            # the committed prefix as new traffic
            "escalation": {
                "escalated_requests_admitted": self._escalated_admitted,
                "cancelled_for_escalation": self._cancelled_for_escalation,
                "prefill_positions_fresh": self._prefill_positions_fresh,
                "prefill_positions_replayed": self._prefill_positions_replayed,
                "replay_prefill_macs": self._replay_prefill_macs,
                "replay_prefill_seconds": self._replay_prefill_seconds,
            },
        })

    def _autotune_stats(self):
        if not self.cfg.autotune.enabled:
            return None
        from repro.autotune.telemetry import merge_telemetry
        tels = self.lane_telemetry()
        out = {
            "thresholds": (list(self._live_thresholds)
                           if self._live_thresholds is not None else None),
            "controller": (self.controller.stats()
                           if self.controller is not None else None),
        }
        if tels:
            tel = merge_telemetry(tels)
            out.update({
                "steps": float(tel["steps"]),
                "shadow_steps": float(tel["shadow_steps"]),
                "exit_counts": [float(c) for c in tel["exit_counts"]],
                "mac_spent": float(tel["mac_spent"]),
            })
        return out
