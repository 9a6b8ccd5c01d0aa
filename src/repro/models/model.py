"""CascadeModel — the unified early-exit model over all architecture families.

The backbone is the per-layer kind sequence from blocks.layer_kinds(cfg),
split into ``n_components`` segments at the cascade exit boundaries.  Within a
segment, consecutive layers of the same kind form a *stage* executed with
``lax.scan`` over stacked parameters (HLO size O(#stages), not O(#layers)).

Exit heads (the paper's intermediate classifiers, adapted to LM heads) branch
after every segment but the last; the final head is the standard
norm + unembedding.  Each intermediate head is
``norm → [enhancement MLP] → unembed`` where the enhancement implements the
paper's classifier widening and the unembedding is shared with the final head
by default (cascade.share_unembed).

Public entry points:
  init(key)                                      -> params
  forward_train(params, tokens, extra)           -> (exit_logits, aux)
  init_cache(batch, cache_len, dtype)            -> cache
  prefill(params, tokens, cache, extra)          -> (exit_logits_last, cache)
  decode_step(params, token, t, cache, extra)    -> (exit_logits, cache)
  decode(params, token, cache, state, extra)     -> (decision, cache, state)

A decode step writes exactly one K and one V row per layer into each dense
ring cache: the layer scan hands every attention layer its stored ring as a
read-only :class:`~repro.models.blocks.RingView`, the layer attends over it
with its own row selected in at the step's slot (the ring as it reads after
the write), and the scan returns only the rows
(:class:`~repro.models.blocks.RingRows`), which :meth:`commit_decode` writes
into the stacked caches in place once the step's segments have run.  Prefill,
recurrent states, cross-attention and paged caches are rewritten per layer.

``decode_step`` is the dense reference: it computes every segment and returns
every exit's logits (what the prefill/decode consistency tests pin).  The
*staged* decode — ``cfg.cascade.exit_mode`` "select" | "cond_batch", carrying
a :class:`repro.core.exec.DecodeState` and skipping exited segments' compute —
is ``decode`` / :class:`repro.core.exec.StagedExecutor`, built from the
segment primitives exposed here (``begin_decode`` / ``run_segment`` /
``backfill_segment`` / ``exit_logits`` / ``commit_decode``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import nn
from repro.models.blocks import (BLOCKS, RingRows, RingView, in_place_ring,
                                 layer_kinds, read_rows, write_rows)
from repro.models.layers import attn_init, mlp_init, norm_apply, norm_init
from repro.utils import dtype_of


def _runs(kinds: List[str]) -> List[Tuple[str, int]]:
    runs = []
    for k in kinds:
        if runs and runs[-1][0] == k:
            runs[-1][1] += 1
        else:
            runs.append([k, 1])
    return [(k, n) for k, n in runs]


class CascadeModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        kinds = layer_kinds(cfg)
        assert len(kinds) == cfg.n_layers
        self.segment_runs: List[List[Tuple[str, int]]] = []
        for (start, end) in cfg.segments:
            self.segment_runs.append(_runs(kinds[start:end]))
        self.n_exits = cfg.cascade.n_components
        self.param_dtype = dtype_of(cfg.dtype)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dt = self.param_dtype
        keys = iter(jax.random.split(key, 64))
        p: Dict[str, Any] = {}
        p["embed"] = nn.embed_init(next(keys), (cfg.vocab_size, cfg.d_model), dt)
        if cfg.family == "audio" or cfg.rope_theta <= 0:
            p["pos_embed"] = nn.embed_init(
                next(keys), (cfg.max_seq_len, cfg.d_model), dt)
        segs = []
        for runs in self.segment_runs:
            stages = []
            for kind, n in runs:
                block = BLOCKS[kind]
                init_one = lambda k, _kind=kind: jax.tree_util.tree_map(
                    lambda x: x.astype(
                        dt if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype),
                    BLOCKS[_kind].init(k, cfg))
                stages.append(nn.stack_init(init_one, next(keys), n))
            segs.append(stages)
        p["segments"] = segs
        if cfg.family == "hybrid":
            ka, km = jax.random.split(next(keys))
            shared = {"attn": attn_init(ka, cfg), "mlp": mlp_init(km, cfg)}
            p["shared"] = jax.tree_util.tree_map(
                lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
                else x, shared)
        if cfg.family == "audio":
            p["encoder"] = self._init_encoder(next(keys))
        # exit heads
        exits = []
        for m in range(self.n_exits - 1):
            e: Dict[str, Any] = {"norm": norm_init(next(keys), cfg)}
            if cfg.cascade.enhance_dim:
                k1, k2 = jax.random.split(next(keys))
                e["enh_w1"] = nn.dense_init(
                    k1, (cfg.d_model, cfg.cascade.enhance_dim), dt)
                e["enh_w2"] = nn.zeros_init(
                    k2, (cfg.cascade.enhance_dim, cfg.d_model), dt)
            if not cfg.cascade.share_unembed:
                e["head"] = nn.dense_init(
                    next(keys), (cfg.d_model, cfg.vocab_size), dt)
            exits.append(e)
        p["exits"] = exits
        p["final_norm"] = norm_init(next(keys), cfg)
        if not cfg.tie_embeddings:
            p["lm_head"] = nn.dense_init(
                next(keys), (cfg.d_model, cfg.vocab_size), dt)
        return p

    def _init_encoder(self, key):
        cfg = self.cfg
        dt = self.param_dtype
        k1, k2, k3 = jax.random.split(key, 3)
        enc_block = lambda k: jax.tree_util.tree_map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, BLOCKS["enc"].init(k, cfg))
        return {
            "stages": nn.stack_init(enc_block, k1, cfg.encoder_layers),
            "norm": norm_init(k2, cfg),
            "pos_embed": nn.embed_init(k3, (cfg.n_audio_frames, cfg.d_model), dt),
        }

    # ------------------------------------------------------------------
    # stages
    # ------------------------------------------------------------------
    def _unroll(self, stacked):
        if not self.cfg.scan_unroll:
            return 1
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        return int(n)

    def _run_stage(self, kind, stacked, h, ctx, stacked_cache, remat=False):
        """Scan one stage's layers.  In a decode step over a dense ring
        cache (:func:`~repro.models.blocks.in_place_ring`) each layer
        attends over its stored ring with this step's row selected in, and
        the stage returns only :class:`~repro.models.blocks.RingRows`, one
        K/V row per layer, which :meth:`commit_decode` writes in place.
        Other caches go through the scan whole, one layer's slice each."""
        block = BLOCKS[kind]
        has_cache = stacked_cache is not None

        unroll = self._unroll(stacked)
        if has_cache:
            rows = in_place_ring(block, ctx)

            def body(h, xs):
                pa, ca = xs
                if rows:
                    ca = RingView(ca["k"], ca["v"])
                h2, c2, aux = block.apply(self.cfg, pa, h, ctx, ca)
                return h2, (c2, aux)
            h, (new_cache, auxs) = lax.scan(body, h, (stacked, stacked_cache),
                                            unroll=unroll)
            return h, new_cache, jnp.sum(auxs)
        else:
            def body(h, pa):
                h2, _, aux = block.apply(self.cfg, pa, h, ctx, None)
                return h2, aux
            if remat:
                if self.cfg.remat_policy == "dots":
                    body_fn = jax.checkpoint(
                        body, policy=jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable)
                else:
                    body_fn = jax.checkpoint(body)
            else:
                body_fn = body
            h, auxs = lax.scan(body_fn, h, stacked, unroll=unroll)
            return h, None, jnp.sum(auxs)

    def _run_segment(self, si, params, h, ctx, seg_cache, remat=False):
        if ctx.get("block_tables") is not None:
            # paged layout: each segment addresses the shared store through
            # its OWN table row block (B, nblk) — exit depth m frees rows
            # m+1.. while shallower components keep theirs
            ctx = {**ctx, "block_table": ctx["block_tables"][si]}
        new_caches = []
        aux = jnp.zeros((), jnp.float32)
        for pi, (kind, n) in enumerate(self.segment_runs[si]):
            cache_i = seg_cache[pi] if seg_cache is not None else None
            h, nc, a = self._run_stage(kind, params["segments"][si][pi], h,
                                       ctx, cache_i, remat)
            new_caches.append(nc)
            aux = aux + a
        return h, (new_caches if seg_cache is not None else None), aux

    def _backfill_segment(self, si, params, h, ctx, seg_cache):
        """Cheap path: update caches of segment si from the exit hidden state
        without computing the segment output (cascade state backfill).  A
        decode step over dense rings computes only each layer's K/V row
        (:class:`~repro.models.blocks.RingRows`) and reads no ring."""
        if ctx.get("block_tables") is not None:
            ctx = {**ctx, "block_table": ctx["block_tables"][si]}
        new_caches = []
        for pi, (kind, n) in enumerate(self.segment_runs[si]):
            block = BLOCKS[kind]
            stacked = params["segments"][si][pi]
            cache_i = seg_cache[pi]
            rows = in_place_ring(block, ctx)

            def body(h_const, xs):
                pa, ca = xs
                if rows:
                    ca = RingView(ca["k"], ca["v"])
                c2 = block.backfill(self.cfg, pa, h_const, ctx, ca)
                return h_const, c2
            _, nc = lax.scan(body, h, (stacked, cache_i),
                             unroll=self._unroll(stacked))
            new_caches.append(nc)
        return new_caches

    # public segment primitives for the staged executor (core/exec.py)
    def run_segment(self, si, params, h, ctx, seg_cache):
        """Compute segment ``si``: (h', new_seg_cache, aux)."""
        return self._run_segment(si, params, h, ctx, seg_cache)

    def backfill_segment(self, si, params, h, ctx, seg_cache):
        """Write segment ``si``'s caches from the exit hidden state without
        computing the segment (the skip path's cache-coherence write)."""
        return self._backfill_segment(si, params, h, ctx, seg_cache)

    def hold_segment(self, si, ctx, seg_cache):
        """Segment ``si``'s caches left as they are, in the form
        ``run_segment`` / ``backfill_segment`` return them in this step:
        dense rings give back the rows already stored at the step's slot,
        which :meth:`commit_decode` writes back unchanged."""
        if ctx.get("block_tables") is not None:
            ctx = {**ctx, "block_table": ctx["block_tables"][si]}
        return [read_rows(c, ctx["slot"])
                if in_place_ring(BLOCKS[kind], ctx) else c
                for (kind, _), c in zip(self.segment_runs[si], seg_cache)]

    # ------------------------------------------------------------------
    # heads
    # ------------------------------------------------------------------
    def _unembed(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def exit_logits(self, params, m: int, h):
        """Exit head m (m < n_exits-1: intermediate; else final)."""
        cfg = self.cfg
        if m >= self.n_exits - 1:
            x = norm_apply(params["final_norm"], cfg, h)
            return x @ self._unembed(params).astype(x.dtype)
        e = params["exits"][m]
        x = norm_apply(e["norm"], cfg, h)
        if "enh_w1" in e:
            x = x + jax.nn.gelu(x @ e["enh_w1"].astype(x.dtype)) \
                @ e["enh_w2"].astype(x.dtype)
        head = e["head"] if "head" in e else self._unembed(params)
        return x @ head.astype(x.dtype)

    def exit_head_params(self, params, m: int):
        """``(norm_w, head)`` when exit head ``m`` is megakernel-eligible.

        The per-segment megakernel (:mod:`repro.kernels.megakernel`) fuses
        exactly rmsnorm + one unembed matmul + exit update; heads with a
        layernorm bias or an enhancement MLP between norm and unembed do
        not fit that shape, so they return ``None`` and the caller falls
        back to ``exit_logits`` + the fused exit-update kernel.
        """
        if m >= self.n_exits - 1:
            norm = params["final_norm"]
            if "b" in norm:
                return None
            return norm["w"], self._unembed(params)
        e = params["exits"][m]
        if "b" in e["norm"] or "enh_w1" in e:
            return None
        head = e["head"] if "head" in e else self._unembed(params)
        return e["norm"]["w"], head

    # ------------------------------------------------------------------
    # embedding & extras
    # ------------------------------------------------------------------
    def _embed(self, params, tokens, positions=None):
        h = params["embed"][tokens]
        if "pos_embed" in params:
            if positions is None:
                positions = jnp.arange(tokens.shape[1])
            h = h + params["pos_embed"][positions]
        return h

    def _encode_audio(self, params, audio_embeds):
        """Whisper encoder over stubbed frame embeddings (B, T, d)."""
        cfg = self.cfg
        enc = params["encoder"]
        h = audio_embeds.astype(self.param_dtype) + enc["pos_embed"][None]
        ctx = {"mode": "full", "positions": jnp.arange(h.shape[1]),
               "write_slots": None, "cross": None, "shared": None}
        def body(h, pa):
            h2, _, _ = BLOCKS["enc"].apply(cfg, pa, h, ctx, None)
            return h2, ()
        h, _ = lax.scan(body, h, enc["stages"])
        return norm_apply(enc["norm"], cfg, h)

    def _make_cross(self, params, extra, mode):
        cfg = self.cfg
        if cfg.family == "vlm":
            return extra["image_embeds"].astype(self.param_dtype)
        if cfg.family == "audio":
            if mode == "decode":
                return None  # decode uses the cross K/V cache
            return self._encode_audio(params, extra["audio_embeds"])
        return None

    # ------------------------------------------------------------------
    # training / full-sequence forward
    # ------------------------------------------------------------------
    def forward_train(self, params, tokens, extra=None):
        """tokens: (B, S).  Returns ([exit logits (B,S,V)] * n_exits, aux)."""
        cfg = self.cfg
        S = tokens.shape[1]
        positions = jnp.arange(S)
        h = self._embed(params, tokens, positions)
        ctx = {"mode": "full", "positions": positions, "write_slots": None,
               "cross": self._make_cross(params, extra or {}, "full"),
               "shared": params.get("shared"), "kpos": None}
        logits, aux = [], jnp.zeros((), jnp.float32)
        stride = max(1, cfg.cascade.exit_loss_stride)
        for si in range(self.n_exits):
            h, _, a = self._run_segment(si, params, h, ctx, None,
                                        remat=cfg.remat)
            aux = aux + a
            if si < self.n_exits - 1:
                logits.append(self.exit_logits(params, si, h[:, ::stride]))
        logits.append(self.exit_logits(params, self.n_exits - 1, h))
        return logits, aux

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def cache_capacity(self, cache_len: int) -> int:
        w = self.cfg.attn_window
        return min(w, cache_len) if w else cache_len

    def init_cache(self, batch: int, cache_len: int, dtype=None):
        cfg = self.cfg
        dtype = dtype or self.param_dtype
        W = self.cache_capacity(cache_len)
        segs = []
        for si, runs in enumerate(self.segment_runs):
            stages = []
            for kind, n in runs:
                one = BLOCKS[kind].init_cache(cfg, batch, W, dtype)
                stacked = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x, (n,) + x.shape), one)
                stages.append(stacked)
            segs.append(stages)
        return {"kpos": jnp.full((W,), -1, jnp.int32), "segments": segs}

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    def prefill(self, params, tokens, cache, extra=None, block_tables=None):
        """Full-sequence forward writing KV/state caches.

        Returns ([exit logits at last position (B,V)] * n_exits, new cache).
        ``block_tables`` ((n_components, B, nblk) int32) switches the cache
        writes to the paged layout; the returned ``kpos`` is then the
        per-slot (B, W) ring instead of the lane-wide (W,).
        """
        cfg = self.cfg
        B, S = tokens.shape
        W = cache["kpos"].shape[-1]
        positions = jnp.arange(S)
        # per-slot gather index == the absolute position held by the slot
        write_slots = jnp.asarray(_prefill_kpos(S, W))
        h = self._embed(params, tokens, positions)
        ctx = {"mode": "full", "positions": positions,
               "write_slots": write_slots,
               "cross": self._make_cross(params, extra or {}, "full"),
               "shared": params.get("shared"), "kpos": cache["kpos"]}
        if block_tables is not None:
            ctx["block_tables"] = jnp.asarray(block_tables, jnp.int32)
        logits, new_segs = self._prefill_segments(params, h, ctx, cache)
        kpos = jnp.asarray(_prefill_kpos(S, W))
        if cache["kpos"].ndim == 2:
            kpos = jnp.broadcast_to(kpos, (B, W))
        return logits, {"kpos": kpos, "segments": new_segs}

    def prefill_into(self, params, tokens, cache, positions, write_slots,
                     block_tables, extra=None):
        """Single-request prefill at OFFSET positions into an occupied
        paged lane (continuous-batching admission).

        tokens: (1, S); ``positions`` (S,) the absolute positions the lane
        cursor will have covered when the slot starts decoding;
        ``write_slots`` (W,) the per-ring-slot absolute position to keep
        (-1 = slot unwritten), computed by the engine; ``block_tables``
        (n_components, 1, nblk) the admitted slot's table rows.  Writes go
        through the slot's own blocks, so the rest of the lane's cache is
        untouched.  Returns ([exit logits at last position (1, V)] *
        n_exits, new segment stores).
        """
        positions = jnp.asarray(positions, jnp.int32)
        h = self._embed(params, tokens, positions)
        ctx = {"mode": "full", "positions": positions,
               "write_slots": jnp.asarray(write_slots, jnp.int32),
               "cross": self._make_cross(params, extra or {}, "full"),
               "shared": params.get("shared"), "kpos": None,
               "block_tables": jnp.asarray(block_tables, jnp.int32)}
        return self._prefill_segments(params, h, ctx, cache)

    def _prefill_segments(self, params, h, ctx, cache):
        """Every segment over the prompt, each exit head at its last
        position, under the staged executor's ``segment{k}`` /
        ``exit_head{k}`` scopes.  Returns (exit logits, new segment
        caches)."""
        logits, new_segs = [], []
        for si in range(self.n_exits):
            with jax.named_scope(f"segment{si}"):
                h, nc, _ = self._run_segment(si, params, h, ctx,
                                             cache["segments"][si])
            new_segs.append(nc)
            with jax.named_scope(f"exit_head{si}"):
                logits.append(
                    self.exit_logits(params, si, h[:, -1:, :])[:, 0, :])
        return logits, new_segs

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def begin_decode(self, params, token, t, cache, extra=None):
        """Embed one decode token and build the step context.

        token: (B,1) int32; t: scalar int32 position.  Returns (h, ctx) for
        the segment primitives (``run_segment`` / ``backfill_segment``).
        """
        W = cache["kpos"].shape[-1]
        slot = jnp.asarray(t, jnp.int32) % W
        h = self._embed(params, token,
                        jnp.asarray(t, jnp.int32)[None] if "pos_embed" in params
                        else None)
        ctx = {"mode": "decode", "t": jnp.asarray(t, jnp.int32), "slot": slot,
               "kpos": cache["kpos"], "positions": None, "write_slots": None,
               "cross": self._make_cross(params, extra or {}, "decode"),
               "shared": params.get("shared")}
        return h, ctx

    def commit_decode(self, cache, new_segs, t):
        """Finish a decode step: write each dense ring's
        :class:`~repro.models.blocks.RingRows` into its stacked cache at
        slot ``t % W`` (one K and one V row per layer, in place, outside
        any of the step's branches) and record position t in the kpos ring
        (the lane-wide (W,) ring, or every slot's row of the paged per-slot
        (B, W) ring — dead slots' rows are masked by the kernels' live
        mask and re-planned at admission, so the broadcast is safe)."""
        W = cache["kpos"].shape[-1]
        slot = jnp.asarray(t, jnp.int32) % W
        segs = [[write_rows(old, new, slot) if isinstance(new, RingRows)
                 else new for old, new in zip(old_seg, new_seg)]
                for old_seg, new_seg in zip(cache["segments"], new_segs)]
        kpos = cache["kpos"].at[..., slot].set(jnp.asarray(t, jnp.int32))
        return {"kpos": kpos, "segments": segs}

    def decode_step(self, params, token, t, cache, extra=None):
        """One DENSE decode step: every segment computes, every exit's
        logits are returned (list of (B,V)), caches get the true deep
        features.  This is the reference path the consistency tests pin.

        Early-exit execution — segment skipping under ``lax.cond``, carried
        :class:`~repro.core.exec.DecodeState`, identical ``select`` /
        ``cond_batch`` semantics — lives in :meth:`decode` /
        :class:`repro.core.exec.StagedExecutor`.
        """
        h, ctx = self.begin_decode(params, token, t, cache, extra)
        logits: List[jnp.ndarray] = []
        new_segs: List[Any] = []
        for si in range(self.n_exits):
            h, nc, _ = self._run_segment(si, params, h, ctx,
                                         cache["segments"][si])
            new_segs.append(nc)
            logits.append(self.exit_logits(params, si, h)[:, 0, :])
        return logits, self.commit_decode(cache, new_segs, t)

    def decode(self, params, token, cache, state, extra=None, decider=None):
        """Staged decode step honoring ``cfg.cascade.exit_mode``.

        token: (B,1) int32; state: :class:`repro.core.exec.DecodeState`
        (carries the position cursor, active mask and measure state).
        Returns (ExitDecision, new_cache, new_state).  In ``cond_batch``
        mode segments nobody needs are skipped (backfill-only).
        """
        from repro.core.exec import StagedExecutor
        if decider is not None:
            executor = StagedExecutor(self, self.cfg, decider)
        else:
            executor = getattr(self, "_staged_executor", None)
            if executor is None:
                executor = self._staged_executor = StagedExecutor(self,
                                                                  self.cfg)
        return executor.decode_step(params, token, cache, state, extra)


def _prefill_kpos(S: int, W: int) -> np.ndarray:
    s = np.arange(W)
    if S >= W:
        kpos = S - 1 - ((S - 1 - s) % W)
    else:
        kpos = np.where(s < S, s, -1)
    return kpos.astype(np.int32)


def build_model(cfg: ModelConfig) -> CascadeModel:
    return CascadeModel(cfg)


def extra_input_shapes(cfg: ModelConfig, batch: int):
    """Shapes of the stubbed modality-frontend inputs, if any."""
    if cfg.family == "vlm":
        return {"image_embeds": (batch, cfg.n_image_tokens, cfg.d_model)}
    if cfg.family == "audio":
        return {"audio_embeds": (batch, cfg.n_audio_frames, cfg.d_model)}
    return {}
