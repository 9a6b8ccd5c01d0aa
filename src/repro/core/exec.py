"""Staged cascade execution: the :class:`DecodeState` pytree and the
segment-skipping executor that makes early exit mean early *termination*.

The paper's claim is that inference stops as soon as the softmax confidence
clears the calibrated threshold — yet a batched TPU decode graph has a fixed
shape, so the seed implementation computed every segment and merely *selected*
the exit, leaving the measured speedup analytic (MACs), not wall-clock.  This
module closes that gap the way IDK Cascades (Wang et al., 2017) and Learning
to Cascade (Enomoto & Eda, 2021) frame it: the exit decision is part of the
execution program, not a post-hoc filter.

Two pieces:

* :class:`DecodeState` — the explicit, jit/shard-friendly pytree carried
  across decode steps: the cache-write cursor ``t``, the per-sequence
  ``active`` mask, the stateful-measure carry (patience streaks), an EMA of
  the answering confidence (per-slot difficulty telemetry, surfaced through
  the serving engine's stats), and per-segment execution counters.

* :class:`StagedExecutor` — runs the cascade one segment at a time, feeding
  each segment's exit logits to the shared
  :class:`~repro.core.policy.ExitDecider` scan (the fused exit-update Pallas
  kernel when ``cfg.use_kernels``).  Under ``cascade.exit_mode ==
  "cond_batch"`` every segment after the first sits under ``lax.cond``: once
  all live sequences have exited, deeper segments take only the cheap
  ``backfill`` path (cache coherence writes), skipping their matmuls
  entirely.  Under ``"select"`` the graph stays fixed (the dry-run /
  roofline shape) but applies the SAME masked state updates, so the two
  modes produce bit-identical tokens, exit indices, and carried state —
  ``exit_mode`` chooses an execution strategy, never a semantics.

Cohort-split execution (``cascade.n_cohorts > 1``) has two memory layouts,
picked by ``cascade.cohort_layout`` (bit-identical outputs, different
copies — see :meth:`StagedExecutor.decode_step`):

* ``"major"`` (default) — the cohort-major hot path.  Cohorts are
  contiguous equal batch ranges, so viewing the batch axis as
  ``(cohort, B/C)`` is a zero-copy reshape; the step's hidden state /
  decision carry / context / active mask split into per-cohort parts ONCE
  (not per segment), and every deep segment dispatches on the lane's exit
  state: all-exited → one whole-batch backfill, none-exited → one
  whole-batch dense segment, mixed → per-cohort ``lax.cond`` over
  cohort-major cache views.  The per-cohort slice/re-join machinery only
  runs when cohorts actually disagree.
* ``"copy"`` — the legacy layout: every segment re-slices the batch per
  cohort and re-concatenates hidden state, carry and the segment's cache
  output (dense rings' decode rows, recurrent states whole), whatever the
  exit state.  Kept as the ablation baseline the
  layout benchmark (``benchmarks/bench_llm_cascade.py``) measures against.

The per-slot ``DecodeState.active`` mask also rides in the decode context
(``ctx["live"]``), where the exit-masked decode-attention kernel early-outs
dead slots' grid cells (``cfg.use_kernels``).

Every segment computation runs under ``jax.named_scope("segment{k}")``,
every exit-head evaluation (norm, unembedding, confidence and exit
update, or the megakernel call) under ``"exit_head{k}"`` and every K/V
backfill of a skipped segment under ``"backfill{k}"``, in each branch
that computes them (prefill's live in ``models/model.py``).  The scopes
set HLO ``op_name`` metadata only, so a profile attributes device time
to them without changing the program.

This replaces the old fixed ``(params, token, t, cache, extra)`` serve-step
signature: launch steps and the serving engine now thread
``(params, token, cache, state, extra)`` with ``state: DecodeState`` (see
``launch/steps.py`` for the migration shim-free builders and
``launch/shard_rules.decode_state_spec`` for its sharding).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.policy import ExitDecider, ExitDecision
from repro.models.blocks import RingRows

# EMA decay for the per-slot answering-confidence telemetry carried in
# DecodeState (same decay as DepthCompactor's host-side depth prior).
CONF_EMA_DECAY = 0.8

# (requested n_cohorts, batch) pairs already warned about degrading.
_COHORT_WARNED = set()


def effective_cohorts(n_cohorts: int, batch: int, warn: bool = False) -> int:
    """Largest divisor of ``batch`` that is <= ``n_cohorts`` (>= 1).

    Cohort slices must be equal-size static ranges, so an indivisible batch
    degrades gracefully instead of erroring — the same policy the sharding
    rules apply to indivisible axes.  ``warn=True`` emits a one-time
    warning per (n_cohorts, batch) pair when the degradation actually
    triggers, because silently collapsing to fewer (or one) cohorts
    forfeits exactly the skip granularity ``n_cohorts`` was asked for —
    size lanes with :func:`repro.serving.batching.cohort_capacity` to avoid
    it.
    """
    want = max(1, min(int(n_cohorts), int(batch)))
    c = want
    while batch % c:
        c -= 1
    if warn and c != int(n_cohorts) and (n_cohorts, batch) not in _COHORT_WARNED:
        _COHORT_WARNED.add((n_cohorts, batch))
        warnings.warn(
            f"n_cohorts={n_cohorts} does not divide batch={batch}; "
            f"degrading to {c} cohort(s).  Round the lane capacity up to a "
            f"cohort multiple (repro.serving.batching.cohort_capacity) to "
            f"keep the requested skip granularity.", stacklevel=3)
    return c


def _slice_ctx(ctx, lo, hi):
    """Batch-slice a decode context: ``cross`` (B, T, d) and the per-slot
    exit mask ``live`` (B,) carry a batch dim; so do the paged-layout
    block tables (K, B, nblk) and the per-slot kpos ring (B, W).
    Everything else (dense lane-wide kpos, scalars, shared params) is
    batch-free and passes through."""
    out = ctx
    cross = ctx.get("cross")
    if cross is not None:
        out = {**out, "cross": cross[lo:hi]}
    live = ctx.get("live")
    if live is not None:
        out = {**out, "live": live[lo:hi]}
    bts = ctx.get("block_tables")
    if bts is not None:
        out = {**out, "block_tables": bts[:, lo:hi]}
    kpos = ctx.get("kpos")
    if kpos is not None and getattr(kpos, "ndim", 1) == 2:
        out = {**out, "kpos": kpos[lo:hi]}
    return out


@dataclasses.dataclass
class DecodeState:
    """Per-lane decode carry (a registered pytree).

    t             () int32   — decode position == cache-write cursor.
    active        (B,) bool  — sequences still generating; finished slots
                               neither block segment skipping nor update
                               EMAs, and their attention grid cells
                               early-out in the exit-masked decode kernel.
    policy        stateful-measure carry (e.g. patience streaks,
                               (n_components, B) int32) or None.
    ema_conf      (B,) f32   — EMA of the answering confidence per lane
                               slot (difficulty telemetry; the engine
                               reports it per lane in ``stats()``).
    segments_run  (n_components,) int32 — how many decode steps actually
                               computed each segment (physical compute: in
                               ``select`` mode every segment counts every
                               step; in ``cond_batch`` skipped segments
                               don't).  The real-skip evidence.
    tel           :class:`repro.autotune.telemetry.ExitTelemetry` counters
                               accumulated inside the decode program, or
                               None (autotune disabled — the default,
                               keeping the carry byte-identical to the
                               pre-autotune layout).
    thresholds    (n_components,) f32 live threshold vector, or None (use
                               the config's static thresholds).  As carry
                               DATA, a ThresholdController push is a plain
                               array swap — no retrace.
    block_tables  (n_components, B, W/block_size) int32 paged-cache block
                               tables (``cache_layout="paged"``), or None
                               (dense slab — the carry stays byte-identical
                               to the pre-paging layout).  Carry DATA: the
                               engine re-binding freed blocks between
                               chunks is a plain array swap — no retrace.
    """

    t: jnp.ndarray
    active: jnp.ndarray
    policy: Optional[jnp.ndarray]
    ema_conf: jnp.ndarray
    segments_run: jnp.ndarray
    tel: Optional[object] = None
    thresholds: Optional[jnp.ndarray] = None
    block_tables: Optional[jnp.ndarray] = None

    def replace(self, **kw) -> "DecodeState":
        return dataclasses.replace(self, **kw)


jax.tree_util.register_dataclass(
    DecodeState,
    data_fields=("t", "active", "policy", "ema_conf", "segments_run",
                 "tel", "thresholds", "block_tables"),
    meta_fields=())


def init_decode_state(decider: ExitDecider, batch: int, n_components: int,
                      t: int = 0, active=None, telemetry=None,
                      thresholds=None, block_tables=None) -> DecodeState:
    """Fresh decode carry for a lane of ``batch`` sequences."""
    return DecodeState(
        t=jnp.asarray(t, jnp.int32),
        active=(jnp.ones((batch,), bool) if active is None
                else jnp.asarray(active, bool)),
        policy=decider.measure.init_state(n_components, batch),
        ema_conf=jnp.zeros((batch,), jnp.float32),
        segments_run=jnp.zeros((n_components,), jnp.int32),
        tel=telemetry,
        thresholds=(None if thresholds is None
                    else jnp.asarray(thresholds, jnp.float32)),
        block_tables=(None if block_tables is None
                      else jnp.asarray(block_tables, jnp.int32)))


class StagedExecutor:
    """Segment-at-a-time cascade decode under one :class:`ExitDecider`.

    ``decode_step`` is THE decode program; ``cfg.cascade.exit_mode`` only
    picks how it is realized:

    * ``"select"`` — fixed graph: every segment computes, the skip
      predicate selects between the full result and the backfill result.
      Lowered by the dry-run (roofline shape).
    * ``"cond_batch"`` — ``lax.cond`` per segment: when every live sequence
      has exited, the deep segment's matmuls do not execute; only the cheap
      cache backfill runs.  Wall-clock savings, identical outputs.

    Works for every registered measure/policy whose decision reduces to
    per-component gates over static thresholds — including stateful
    patience@k (streaks ride in ``DecodeState.policy``) and a *fitted*
    BudgetPolicy (its thresholds resolve to static floats at trace time).
    """

    def __init__(self, model, cfg=None, decider: Optional[ExitDecider] = None):
        self.model = model
        self.cfg = cfg or model.cfg
        self.decider = decider or ExitDecider.from_config(self.cfg)
        self.mode = self.cfg.cascade.exit_mode
        self.layout = self.cfg.cascade.cohort_layout
        self.n_components = self.cfg.cascade.n_components
        # per-segment megakernel route (rmsnorm + unembed matmul + exit
        # update in one pallas_call) — requires the fused-scan decider;
        # heads the fusion can't express fall back per segment inside
        # _scan_exit
        kt = getattr(self.cfg, "kernel_tune", None)
        self.use_megakernel = bool(kt and kt.megakernel
                                   and self.decider.fused_scan)
        self.use_cohort_scatter = bool(kt and kt.cohort_scatter)

    # sentinel: init_state should build fresh telemetry itself
    _AUTO_TELEMETRY = object()

    # ------------------------------------------------------------------
    def init_state(self, batch: int, t: int = 0, active=None,
                   mac_weights=None,
                   telemetry=_AUTO_TELEMETRY,
                   block_tables=None) -> DecodeState:
        """Fresh carry.  With ``cfg.autotune.enabled`` the state also gets
        zeroed telemetry counters (``mac_weights`` prices exits for the MAC
        counter — the engine passes its cache-length-aware prefix) and a
        live threshold vector seeded from the config.  Pass ``telemetry=``
        to carry existing counters into the fresh state (lane re-prefill)
        instead of allocating zeroed ones that would be thrown away.
        ``block_tables`` (paged cache layout) ride the carry as data."""
        tel = thresholds = None
        if self.cfg.autotune.enabled:
            if telemetry is self._AUTO_TELEMETRY:
                from repro.autotune.telemetry import telemetry_for
                tel = telemetry_for(self.cfg, mac_weights)
            else:
                tel = telemetry
            thresholds = self.cfg.cascade.thresholds
        return init_decode_state(self.decider, batch, self.n_components,
                                 t=t, active=active, telemetry=tel,
                                 thresholds=thresholds,
                                 block_tables=block_tables)

    def _carry_forward(self, state: DecodeState,
                       decision: ExitDecision) -> DecodeState:
        conf = decision.confidence.astype(jnp.float32)
        ema = jnp.where(state.active,
                        CONF_EMA_DECAY * state.ema_conf
                        + (1.0 - CONF_EMA_DECAY) * conf,
                        state.ema_conf)
        return state.replace(policy=decision.state, ema_conf=ema)

    # ------------------------------------------------------------------
    def prefill(self, params, tokens, cache, extra=None,
                state: Optional[DecodeState] = None):
        """Full-sequence prefill; returns (decision, cache, state) with the
        prefill decision seeding the stateful-measure carry (it counts as
        the streak's first step) and ``t`` set past the prompt.

        With telemetry enabled, the prefill decision contributes a free
        SHADOW observation per live slot: prefill computes every component
        anyway, so the decision carry's rider rows hold the full per-
        component confidence/prediction vectors at zero extra compute.
        """
        if state is None:
            state = self.init_state(tokens.shape[0])
        logits, cache = self.model.prefill(params, tokens, cache, extra,
                                           block_tables=state.block_tables)
        decision, carry = self.decider.decide_with_carry(
            logits, thresholds=state.thresholds, state=state.policy,
            active=state.active)
        if state.tel is not None:
            from repro.autotune.telemetry import accumulate_prefill
            state = state.replace(tel=accumulate_prefill(
                state.tel, carry["tcode"], state.active))
        state = self._carry_forward(state, decision).replace(
            t=jnp.asarray(tokens.shape[1], jnp.int32))
        return decision, cache, state

    # ------------------------------------------------------------------
    def _scan_exit(self, si, params, h, ths, sc=None, state=None, live=None):
        """Measure segment ``si``'s exit from its hidden state ``h``
        ((B, 1, d)) and fold it into the decision scan — THE exit-head
        call every decode path routes through.

        With ``cfg.kernel_tune.megakernel`` and a fused-scan decider this
        takes the per-segment megakernel (:meth:`ExitDecider.scan_hidden`):
        the (B, V) exit logits never materialize and the per-slot ``live``
        mask early-outs dead batch blocks before the unembed matmul.  Heads
        the fusion can't express (enhancement MLP, layernorm bias — see
        :meth:`~repro.models.model.CascadeModel.exit_head_params`) and
        non-fused deciders fall back to ``exit_logits`` +
        :meth:`ExitDecider.scan_logits`, unchanged semantics.
        """
        decider, model = self.decider, self.model
        with jax.named_scope(f"exit_head{si}"):
            if self.use_megakernel:
                hp = model.exit_head_params(params, si)
                if hp is not None:
                    return decider.scan_hidden(
                        si, self.n_components, h[:, 0, :], hp[0], hp[1],
                        ths, carry=sc, state=state, live=live,
                        eps=self.cfg.norm_eps)
            lg = model.exit_logits(params, si, h)[:, 0, :]
            return decider.scan_logits(si, self.n_components, lg, ths, sc,
                                       state=state)

    def _run_segment(self, si, params, h, ctx, seg_cache):
        """Compute segment ``si`` (under its ``segment{si}`` scope)."""
        with jax.named_scope(f"segment{si}"):
            return self.model.run_segment(si, params, h, ctx, seg_cache)

    def _backfill(self, si, params, h, ctx, seg_cache):
        """Segment ``si``'s K/V backfill from the exit hidden state (under
        its ``backfill{si}`` scope); the caches as they are without
        ``cascade.state_backfill``."""
        if not self.cfg.cascade.state_backfill:
            return self.model.hold_segment(si, ctx, seg_cache)
        with jax.named_scope(f"backfill{si}"):
            return self.model.backfill_segment(si, params, h, ctx, seg_cache)

    def _rejoin(self, stage_cache, parts):
        """One stage's cache from its C cohorts' outputs.  Dense rings'
        decode rows (:class:`~repro.models.blocks.RingRows`, one token's
        row per layer) are concatenated.  Caches that come back whole
        (recurrent states) are too, or with
        ``cfg.kernel_tune.cohort_scatter`` written as C aliased partial
        writes into the input slab (bit-identical; XLA does not elide the
        concat's full-slab materialization inside while+cond)."""
        if self.use_cohort_scatter and not isinstance(parts[0], RingRows):
            from repro.kernels.ops import cohort_scatter_tree
            for c, part in enumerate(parts):
                stage_cache = cohort_scatter_tree(
                    stage_cache, part, c, len(parts),
                    interpret=self.cfg.kernel_interpret)
            return stage_cache
        return jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=1), *parts)

    # ------------------------------------------------------------------
    def _segment_paths(self, si, ctx_c, params, ths):
        """(run, skip) closures for one deeper segment over one cohort's
        (h, seg_cache, carry) triple — the two ``lax.cond`` branches.

        ``run`` computes the segment, measures its exit logits and folds
        them into the decision scan (:meth:`ExitDecider.scan_logits` — the
        fused exit-update kernel when enabled); ``skip`` only backfills the
        segment's caches from the exit hidden state.

        The DecodeState confidence EMA is deliberately NOT folded inside
        these branches: the fold is a mul+add chain XLA may contract into
        FMAs differently per surrounding computation, so folding in-branch
        puts ``select`` and ``cond_batch`` one ulp apart.  The executor
        folds once at the step boundary instead (:meth:`_carry_forward`),
        identically placed in every execution variant.  (The fused kernel
        still supports the in-kernel fold — ``ema_decay`` in
        :func:`repro.kernels.exit_update.exit_update` — for fixed-graph
        callers without a cross-branch bit-identity contract.)
        """
        def run(h, seg_cache, sc):
            h2, nc2, _ = self._run_segment(si, params, h, ctx_c, seg_cache)
            return h2, nc2, self._scan_exit(si, params, h2, ths, sc,
                                            live=ctx_c.get("live"))

        def skip(h, seg_cache, sc):
            return h, self._backfill(si, params, h, ctx_c, seg_cache), sc

        return run, skip

    def _segment_step(self, si, ctx_c, params, ths, h, seg_cache, sc,
                      active, shadow=False, hs=None):
        """One (segment, cohort) cell: cond-skip in ``cond_batch`` mode,
        compute-and-mask in ``select`` mode.  Returns
        (h, new_seg_cache, carry, ran, hs) with ``ran`` the 0/1 execution
        count feeding ``DecodeState.segments_run``.

        ``shadow`` / ``hs`` are the telemetry shadow pass (python False /
        None when telemetry is off — those graphs stay byte-identical to
        the pre-autotune program).  On a shadow step, segments the skip
        predicate would drop are OBSERVED, never committed: the shadow
        hidden chain ``hs`` (== the committed ``h`` until the first skip,
        since the skip predicate is monotone within a step) threads the
        true full-depth activations through the skipped suffix, each
        skipped segment computes its exit logits from it and lands ONLY
        the telemetry rider row — the committed hidden state, the
        backfilled caches, the decision carry and the patience streaks
        all keep exact skip semantics, so telemetry-on token streams are
        bit-identical to telemetry-off (pinned by tests/test_autotune.py).
        """
        run, skip_fn = self._segment_paths(si, ctx_c, params, ths)
        skip = self.decider.should_skip(sc, active)
        if shadow is False:
            if self.mode == "cond_batch":
                h, nc, sc = lax.cond(skip, skip_fn, run, h, seg_cache, sc)
                return (h, nc, sc,
                        jnp.logical_not(skip).astype(jnp.int32), hs)
            full = run(h, seg_cache, sc)
            lite = skip_fn(h, seg_cache, sc)
            h, nc, sc = jax.tree_util.tree_map(
                lambda a, b: jnp.where(skip, a, b), lite, full)
            return h, nc, sc, jnp.asarray(1, jnp.int32), hs
        def run4(h, seg_cache, sc, hs):
            h2, nc2, sc2 = run(h, seg_cache, sc)
            return h2, nc2, sc2, h2          # shadow chain = real chain

        def observe4(h, seg_cache, sc, hs):
            # full-depth OBSERVATION: compute from the shadow chain, keep
            # only the telemetry rider row; commit the skip results
            h2s, _, _ = self._run_segment(si, params, hs, ctx_c, seg_cache)
            sc_obs = self._scan_exit(si, params, h2s, ths, sc,
                                     live=ctx_c.get("live"))
            sc = {**sc, "tcode": sc_obs["tcode"]}
            h, seg_cache, sc = skip_fn(h, seg_cache, sc)
            return h, seg_cache, sc, h2s

        def skip4(h, seg_cache, sc, hs):
            h, seg_cache, sc = skip_fn(h, seg_cache, sc)
            return h, seg_cache, sc, hs

        if self.mode == "cond_batch":
            def skip_branch(h, c, s, hs):
                return lax.cond(shadow, observe4, skip4, h, c, s, hs)
            h, nc, sc, hs = lax.cond(skip, skip_branch, run4,
                                     h, seg_cache, sc, hs)
            ran = jnp.logical_or(jnp.logical_not(skip),
                                 shadow).astype(jnp.int32)
            return h, nc, sc, ran, hs
        # select: ONE dense run, from the shadow chain (hs == h while any
        # sample is still undecided, so the skip-masked decision merge is
        # unchanged); shadow steps take the rider row from the computed
        # observation even where skip holds
        full = run(hs, seg_cache, sc)
        lite = skip_fn(h, seg_cache, sc)
        h, nc, sc_sel = jax.tree_util.tree_map(
            lambda a, b: jnp.where(skip, a, b), lite, full)
        observed = jnp.logical_or(jnp.logical_not(skip), shadow)
        sc_sel = {**sc_sel, "tcode": jnp.where(observed, full[2]["tcode"],
                                               lite[2]["tcode"])}
        return h, nc, sc_sel, jnp.asarray(1, jnp.int32), full[0]

    # ------------------------------------------------------------------
    def decode_step(self, params, token, cache, state: DecodeState,
                    extra=None):
        """One staged decode step.  token: (B, 1) int32.

        Returns (decision, new_cache, new_state).  Segment 0 always runs;
        each deeper segment runs only while some live sequence has not
        exited (cond_batch) or computes-but-masks (select).

        ``cfg.cascade.n_cohorts > 1`` splits the batch into C contiguous
        equal-size cohorts, each with its OWN skip predicate: a deep
        segment's compute is skipped for a cohort as soon as every live
        sequence in THAT cohort has exited, even while another cohort still
        needs it (nested ``lax.cond`` per cohort).  The serving engine
        places similar-depth requests into the same cohort so this converts
        more of the measured skip opportunity into realized skips.
        ``segments_run`` counts in cohort units: segment ``si`` advances by
        the number of cohorts that actually computed it (C per step when
        nothing skips; C == 1 reproduces the whole-batch predicate exactly).

        ``cfg.cascade.cohort_layout`` picks the memory layout of the
        cohort split (outputs bit-identical):

        * ``"major"`` — hot path: h / carry / context / active split per
          cohort ONCE, segment caches viewed cohort-major
          (``(n, C, B/C, ...)`` — a zero-copy reshape, cohorts being
          contiguous), and each deep segment dispatches on the exit state
          (all-exited / none-exited / mixed) so the per-cohort slice +
          re-join machinery only runs when cohorts actually disagree.
        * ``"copy"`` — the legacy per-segment slice + concat regardless of
          exit state (ablation baseline; this is the copy overhead the
          ROADMAP flagged).
        """
        model, decider, n_m = self.model, self.decider, self.n_components
        # live thresholds (autotune: carry data, a push never retraces)
        # win over the config's static vector
        if state.thresholds is not None:
            ths = decider.resolved_thresholds(n_m, state.thresholds)
        else:
            ths = decider.resolved_thresholds(n_m)
        t = state.t
        # telemetry shadow schedule: every shadow_every-th step (by the
        # position cursor — deterministic and identical across host/device
        # runtimes) OBSERVES the full depth: skipped segments compute their
        # exit logits from the shadow hidden chain for the telemetry rider
        # only, while caches/decisions/streaks keep exact skip semantics
        # (see _segment_step) — token streams never change.  Python False
        # when telemetry is off: the graphs stay untouched.
        shadow = False
        if state.tel is not None:
            shadow = jnp.equal(
                jnp.mod(t, jnp.int32(self.cfg.autotune.shadow_every)), 0)
        B = token.shape[0]
        C = effective_cohorts(self.cfg.cascade.n_cohorts, B, warn=True)
        Bc = B // C
        h, ctx = model.begin_decode(params, token, t, cache, extra)
        # thread the exit mask to the kernels: dead slots' attention grid
        # cells early-out (zero rows) — safe, because a retired slot's
        # outputs are never read and its lane re-prefills before reuse
        ctx = {**ctx, "live": state.active}
        # paged layout: block tables ride the carry as data; the model
        # injects per-segment rows into each segment's attention ctx
        paged = state.block_tables is not None
        if paged:
            ctx = {**ctx, "block_tables": state.block_tables}
        segs = cache["segments"]
        new_segs = []
        ran = [jnp.asarray(C, jnp.int32)]

        # segment 0 computes for everyone (every cohort needs it)
        h, nc, _ = self._run_segment(0, params, h, ctx, segs[0])
        new_segs.append(nc)
        sc = self._scan_exit(0, params, h, ths, state=state.policy,
                             live=state.active)
        # the telemetry shadow chain starts at the committed hidden state
        # (segment 0 always computes); None keeps telemetry-off graphs
        # byte-identical to the pre-autotune program
        hs = h if shadow is not False else None

        if C == 1:
            for si in range(1, n_m):
                h, nc, sc, r, hs = self._segment_step(
                    si, ctx, params, ths, h, segs[si], sc, state.active,
                    shadow=shadow, hs=hs)
                new_segs.append(nc)
                ran.append(r)
        elif self.layout == "copy":
            # ablation baseline: re-slice + re-concat per segment.  Paged
            # stores have no batch dim to slice — each cohort addresses the
            # SHARED store through its own table rows (sliced via ctx), so
            # the store CHAINS through the cohorts (disjoint writes) and
            # the re-concat disappears.
            for si in range(1, n_m):
                h_parts, nc_parts, sc_parts = [], [], []
                hs_parts = [] if hs is not None else None
                ran_si = jnp.zeros((), jnp.int32)
                seg_cur = segs[si]
                for c in range(C):
                    lo, hi = c * Bc, (c + 1) * Bc
                    seg_c = seg_cur if paged else jax.tree_util.tree_map(
                        lambda x: x[:, lo:hi], segs[si])
                    h_c, nc_c, sc_c, r, hs_c = self._segment_step(
                        si, _slice_ctx(ctx, lo, hi), params, ths,
                        h[lo:hi], seg_c, decider.slice_carry(sc, lo, hi),
                        state.active[lo:hi], shadow=shadow,
                        hs=None if hs is None else hs[lo:hi])
                    ran_si = ran_si + r
                    h_parts.append(h_c)
                    if paged:
                        seg_cur = nc_c
                    else:
                        nc_parts.append(nc_c)
                    sc_parts.append(sc_c)
                    if hs_parts is not None:
                        hs_parts.append(hs_c)
                h = jnp.concatenate(h_parts, axis=0)
                if hs_parts is not None:
                    hs = jnp.concatenate(hs_parts, axis=0)
                nc = seg_cur if paged else jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs, axis=1), *nc_parts)
                sc = decider.concat_carry(sc_parts)
                ran.append(ran_si)
                new_segs.append(nc)
        else:
            # cohort-major hot path: h / decision carry / context / active
            # are split ONCE into per-cohort parts (zero-copy views —
            # cohorts are contiguous batch ranges) that persist across the
            # deep segments; each segment then DISPATCHES on the lane's
            # exit state instead of always paying the per-cohort machinery:
            #
            #   all exited  -> ONE whole-batch backfill: no cache slicing,
            #                  no per-cohort conds, no re-join — the common
            #                  state at low thresholds, i.e. exactly where
            #                  the paper's savings materialize;
            #   none exited -> ONE whole-batch dense segment: full-width
            #                  matmuls, again no cohort machinery — the
            #                  dense ceiling costs what C == 1 costs;
            #   mixed       -> per-cohort lax.cond over cohort-major cache
            #                  views, results re-joined per segment.
            #
            # The three branches are bit-identical per row because every
            # decode op is batch-separable (pinned by the layout parity
            # tests).  MoE couples rows through expert capacity, so MoE
            # configs keep a two-way (all-exited vs per-cohort) dispatch.
            spans = [(c * Bc, (c + 1) * Bc) for c in range(C)]
            h_parts = [h[lo:hi] for lo, hi in spans]
            hs_parts = ([p for p in h_parts] if hs is not None else None)
            sc_parts = [decider.slice_carry(sc, lo, hi) for lo, hi in spans]
            ctx_parts = [_slice_ctx(ctx, lo, hi) for lo, hi in spans]
            act_parts = [state.active[lo:hi] for lo, hi in spans]
            separable = self.cfg.n_experts == 0

            for si in range(1, n_m):
                preds = jnp.stack([decider.should_skip(s, a)
                                   for s, a in zip(sc_parts, act_parts)])

                def _all_skip(hp, seg, scp, hsp, _si=si):
                    seg = self._backfill(
                        _si, params, jnp.concatenate(hp, axis=0), ctx, seg)
                    return (list(hp), seg, list(scp),
                            jnp.zeros((), jnp.int32), hsp)

                def _mixed(hp, seg, scp, hsp, _si=si):
                    # dense: zero-copy cohort-major view of the slab.
                    # paged: no batch dim to view — the SHARED store chains
                    # through the cohorts, each addressing it through its
                    # own table rows (ctx_parts carry the sliced tables).
                    # the dense re-join is per stage (_rejoin)
                    if not paged:
                        view = jax.tree_util.tree_map(
                            lambda x: x.reshape((x.shape[0], C, Bc)
                                                + x.shape[2:]), seg)
                    hp, scp = list(hp), list(scp)
                    hsp = None if hsp is None else list(hsp)
                    parts = []
                    r = jnp.zeros((), jnp.int32)
                    for c in range(C):
                        seg_c = seg if paged else jax.tree_util.tree_map(
                            lambda x: x[:, c], view)
                        hp[c], nc_c, scp[c], rc, hs_c = self._segment_step(
                            _si, ctx_parts[c], params, ths, hp[c], seg_c,
                            scp[c], act_parts[c], shadow=shadow,
                            hs=None if hsp is None else hsp[c])
                        if hsp is not None:
                            hsp[c] = hs_c
                        if paged:
                            seg = nc_c
                        else:
                            parts.append(nc_c)
                        r = r + rc
                    if paged:
                        nc = seg
                    else:
                        nc = [self._rejoin(st, ps)
                              for st, ps in zip(seg, zip(*parts))]
                    return hp, nc, scp, r, hsp

                def _all_run(hp, seg, scp, hsp, _si=si):
                    h2, nc, _ = self._run_segment(
                        _si, params, jnp.concatenate(hp, axis=0), ctx, seg)
                    sc2 = self._scan_exit(_si, params, h2, ths,
                                          decider.concat_carry(list(scp)),
                                          live=ctx["live"])
                    out_parts = [h2[lo:hi] for lo, hi in spans]
                    return (out_parts, nc,
                            [decider.slice_carry(sc2, lo, hi)
                             for lo, hi in spans],
                            jnp.asarray(C, jnp.int32),
                            (None if hsp is None else list(out_parts)))

                if self.mode != "cond_batch":
                    # select: fixed graph — the dry-run / roofline shape
                    h_parts, nc, sc_parts, r, hs_parts = _mixed(
                        h_parts, segs[si], sc_parts, hs_parts)
                elif separable:
                    n_skip = jnp.sum(preds.astype(jnp.int32))
                    idx = jnp.where(n_skip == C, 0,
                                    jnp.where(n_skip == 0, 2, 1))
                    if shadow is not False:
                        # telemetry shadow step: any skipped cohort must
                        # be OBSERVED, which only the per-cohort dispatch
                        # does (skip semantics + rider-only observation in
                        # _segment_step); the none-skipped dense branch
                        # already observes everything
                        idx = jnp.where(
                            jnp.logical_and(shadow, n_skip > 0), 1, idx)
                    h_parts, nc, sc_parts, r, hs_parts = lax.switch(
                        idx, (_all_skip, _mixed, _all_run), h_parts,
                        segs[si], sc_parts, hs_parts)
                else:
                    all_skip = jnp.all(preds)
                    if shadow is not False:
                        # shadow steps observe skipped cohorts per cohort
                        all_skip = jnp.logical_and(
                            all_skip, jnp.logical_not(shadow))
                    h_parts, nc, sc_parts, r, hs_parts = lax.cond(
                        all_skip, _all_skip, _mixed, h_parts,
                        segs[si], sc_parts, hs_parts)
                new_segs.append(nc)
                ran.append(r)
            sc = decider.concat_carry(sc_parts)

        decision = decider.finish_scan(sc)
        cache = model.commit_decode(cache, new_segs, t)
        if state.tel is not None:
            from repro.autotune.telemetry import accumulate_decode
            state = state.replace(tel=accumulate_decode(
                state.tel, sc, decision, state.active, shadow))
        state = self._carry_forward(state, decision).replace(
            t=t + 1, segments_run=state.segments_run + jnp.stack(ran))
        return decision, cache, state
