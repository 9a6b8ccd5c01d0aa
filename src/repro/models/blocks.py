"""Block registry: every architecture family is a sequence of *block kinds*.

A block kind provides:
  init(key, cfg)                      -> params (one layer)
  apply(cfg, params, h, ctx, cache)   -> (h, new_cache, aux)
  init_cache(cfg, batch, W, dtype)    -> per-layer cache pytree ({} if none)
  backfill(cfg, params, h, ctx, cache)-> new_cache   (cascade state backfill:
        update this layer's KV / recurrent state from the early-exit hidden
        state WITHOUT computing the layer's output — the cheap path that keeps
        deeper caches coherent when a token exits early.)

In a decode step over a dense self-attention ring (:func:`in_place_ring`)
the ``cache`` a block gets is a read-only :class:`RingView` of its layer's
ring and the ``new_cache`` it returns is the token's :class:`RingRows`:
decode writes one K and one V row per layer, and attention reads the ring
as it stands after that write (the row selected in at the step's slot).
The model writes the rows into the stacked caches in place
(``CascadeModel.commit_decode``).

``ctx`` carries everything invariant across the layers of a stage:
  mode: "full" | "decode"      (static, via closure)
  positions: (B,S) absolute positions of the current tokens (full mode)
  t: scalar int32 current decode position (decode mode)
  kpos: (W,) absolute position of each KV slot (-1 empty)  [attention kinds]
  write_slots: (S,) ring slots to write during full-mode cache fill
  cross: (B,T,d) cross-attention memory (vlm image / whisper audio), or None
  shared: shared-parameter dict for 'attn_shared' blocks
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import nn, ssm, xlstm
from repro.models.layers import (attend_chunked, attend_decode, attend_full,
                                 attn_init, mlp_apply, mlp_init, norm_apply,
                                 norm_init, pick_attend, qkv_project)
from repro.models.moe import moe_apply, moe_init

ZERO = jnp.zeros((), jnp.float32)


@dataclasses.dataclass(frozen=True)
class BlockDef:
    init: Callable
    apply: Callable
    init_cache: Callable
    backfill: Callable


# ---------------------------------------------------------------------------
# attention cache helpers (ring buffer, shared by all attention kinds)
# ---------------------------------------------------------------------------

def attn_cache_init(cfg, batch, W, dtype):
    hd = cfg.resolved_head_dim
    return {"k": jnp.zeros((batch, W, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, W, cfg.n_kv_heads, hd), dtype)}


def _write_full(cache, k, v, gather_idx):
    """Fill ring slots from a full-sequence prefill.  gather_idx: (W,) —
    for each cache slot, the token index that lands in it (-1 = slot stays
    empty).  A gather per slot avoids nondeterministic duplicate scatters."""
    if cache is None:
        return None
    valid = gather_idx >= 0
    idx = jnp.maximum(gather_idx, 0)
    sel = valid[None, :, None, None]
    ck = jnp.where(sel, k[:, idx].astype(cache["k"].dtype), cache["k"])
    cv = jnp.where(sel, v[:, idx].astype(cache["v"].dtype), cache["v"])
    return {"k": ck, "v": cv}


class RingView(NamedTuple):
    """One layer's dense ring as a decode step over stacked caches sees
    it: ``k`` / ``v`` (B, W, kv, hd), as stored before the step and
    read-only.  A block handed a ``RingView`` returns :class:`RingRows`
    in place of a rewritten ring."""
    k: jax.Array
    v: jax.Array


class RingRows(NamedTuple):
    """The K/V rows a decode step writes into dense rings: (B, 1, kv, hd)
    from one layer, (L, B, 1, kv, hd) from a stage's layer scan.  They are
    written into the stacked caches in place once the step's segments
    have run (:meth:`CascadeModel.commit_decode`, :func:`write_rows`)."""
    k: jax.Array
    v: jax.Array


def in_place_ring(block: "BlockDef", ctx) -> bool:
    """Whether a decode step hands this block kind :class:`RingView` and
    takes back :class:`RingRows`: a dense self-attention ring
    (``attn_cache_init``'s ``{"k", "v"}`` with a ``W`` axis) outside the
    paged layout.  Prefill, recurrent states, cross-attention caches and
    paged stores keep a per-layer cache that the block rewrites."""
    return (ctx["mode"] == "decode" and ctx.get("block_table") is None
            and block.init_cache is attn_cache_init)


def write_rows(stacked, rows: RingRows, slot):
    """Write a stage's decode rows into its stacked (L, B, W, kv, hd) ring
    at ``slot``: one row per layer, in place; every other slot keeps its
    bytes."""
    return {n: lax.dynamic_update_slice(
        stacked[n], getattr(rows, n).astype(stacked[n].dtype),
        (0, 0, slot, 0, 0)) for n in ("k", "v")}


def read_rows(stacked, slot) -> RingRows:
    """The rows a stage's stacked ring holds at ``slot`` (writing them
    back leaves the ring as it is)."""
    L, B, _, kv, hd = stacked["k"].shape
    return RingRows(*(lax.dynamic_slice(stacked[n], (0, 0, slot, 0, 0),
                                        (L, B, 1, kv, hd))
                      for n in ("k", "v")))


def _write_decode(cache, k, v, slot):
    """One decode token's K/V (B, 1, kv, hd) for ring slot ``slot``: a
    per-layer ring comes back rewritten; a :class:`RingView` gives back
    just the :class:`RingRows`."""
    if isinstance(cache, RingView):
        return RingRows(k.astype(cache.k.dtype), v.astype(cache.v.dtype))
    ck = lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    cv = lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    return {"k": ck, "v": cv}


def _ring_kv(cache, new_cache, slot):
    """The (B, W, kv, hd) K and V rings a decode step attends over: the
    ring with this step's row written.  For a :class:`RingView` that is
    the row selected over the stored ring at ``slot``: the same values,
    read where the ring lies."""
    if isinstance(cache, RingView):
        at = jnp.arange(cache.k.shape[1])[None, :, None, None] == slot
        return (jnp.where(at, new_cache.k, cache.k),
                jnp.where(at, new_cache.v, cache.v))
    return new_cache["k"], new_cache["v"]


# ---------------------------------------------------------------------------
# paged-layout variants (cache_layout="paged"): the per-layer cache leaf is a
# SHARED block store (num_blocks, block_size, kv, hd) addressed through the
# slot's block-table row ``table`` (B, nblk) — ring position p lives at
# (table[b, p // bs], p % bs).  Dead/uncovered rows point at the trash block
# 0: duplicate scatters there are nondeterministic but the per-slot kpos ring
# masks those positions out of every read (masking, not zeroing, is the
# coherence mechanism — see DESIGN.md).
# ---------------------------------------------------------------------------

def _write_decode_paged(cache, k, v, slot, table):
    """One decode token through the block table.  slot = t % W (scalar);
    k/v (B, 1, kv, hd)."""
    bs = cache["k"].shape[1]
    phys = jnp.take(table, slot // bs, axis=1)      # (B,) physical blocks
    off = slot % bs
    ck = cache["k"].at[phys, off].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[phys, off].set(v[:, 0].astype(cache["v"].dtype))
    return {"k": ck, "v": cv}


def _write_full_paged(cache, k, v, gather_idx, table):
    """Prefill fill through the block table: gather the current logical
    ring view, apply the same valid-masked merge as :func:`_write_full`,
    scatter whole table rows back."""
    if cache is None:
        return None
    B, nblk = table.shape
    bs = cache["k"].shape[1]
    valid = gather_idx >= 0
    idx = jnp.maximum(gather_idx, 0)
    sel = valid[None, :, None, None]

    def merge(store, x):
        cur = store[table].reshape((B, nblk * bs) + store.shape[2:])
        new = jnp.where(sel, x[:, idx].astype(store.dtype), cur)
        return store.at[table].set(
            new.reshape((B, nblk, bs) + store.shape[2:]))

    return {"k": merge(cache["k"], k), "v": merge(cache["v"], v)}


def _paged_kv_view(cfg, cache, table):
    """The slot-logical (B, W, kv, hd) ring view of a paged store — the
    gather that makes the downstream attention (reference or Pallas
    kernel) IDENTICAL to the dense layout's, and therefore bit-identical:
    re-tiling attention to block granularity would change the online-
    softmax accumulation order."""
    if cfg.use_kernels:
        from repro.kernels.ops import paged_gather
        return (paged_gather(cache["k"], table,
                             interpret=cfg.kernel_interpret),
                paged_gather(cache["v"], table,
                             interpret=cfg.kernel_interpret))
    B, nblk = table.shape
    bs = cache["k"].shape[1]

    def view(store):
        return store[table].reshape((B, nblk * bs) + store.shape[2:])

    return view(cache["k"]), view(cache["v"])


def _self_attention(cfg, params, h, ctx, cache):
    """Shared self-attention sublayer logic for full and decode modes."""
    x = norm_apply(params["norm"], cfg, h)
    if ctx["mode"] == "full":
        q, k, v = qkv_project(params, cfg, x, rope_positions=ctx["positions"])
        S = x.shape[1]
        if cfg.use_kernels and S % 128 == 0 and q.shape[-1] % 8 == 0:
            from repro.kernels.ops import flash_attention_bshd
            out = flash_attention_bshd(q, k, v, causal=True,
                                       window=cfg.attn_window,
                                       interpret=cfg.kernel_interpret)
        else:
            attend = pick_attend(cfg, S, S, differentiable=cache is None)
            out = attend(q, k, v, ctx["positions"], ctx["positions"],
                         window=cfg.attn_window, causal=True)
        table = ctx.get("block_table")
        if cache is None:
            new_cache = None
        elif table is not None:
            new_cache = _write_full_paged(cache, k, v, ctx["write_slots"],
                                          table)
        else:
            new_cache = _write_full(cache, k, v, ctx["write_slots"])
    else:
        t = ctx["t"]
        q, k, v = qkv_project(params, cfg, x,
                              rope_positions=jnp.full((1, 1), t))
        slot = ctx["slot"]
        table = ctx.get("block_table")
        if table is not None:
            new_cache = _write_decode_paged(cache, k, v, slot, table)
            kv_k, kv_v = _paged_kv_view(cfg, new_cache, table)
        else:
            new_cache = _write_decode(cache, k, v, slot)
            kv_k, kv_v = _ring_kv(cache, new_cache, slot)
        # dense: lane-wide (W,) ring; paged: per-slot (B, W) ring
        kpos = ctx["kpos"].at[..., slot].set(t)
        if cfg.use_kernels and q.shape[-1] % 8 == 0:
            from repro.kernels.ops import decode_attention_cache
            # ctx["live"] is the per-slot exit mask threaded down from the
            # carried DecodeState: dead slots' (b, h, ik) grid cells
            # early-out inside the kernel (zero-filled rows; live rows are
            # bit-identical — decode attention is batch-separable)
            out = decode_attention_cache(q, kv_k, kv_v,
                                         t, kpos, window=cfg.attn_window,
                                         live=ctx.get("live"),
                                         interpret=cfg.kernel_interpret)
        else:
            out = attend_decode(q, kv_k, kv_v, t, kpos,
                                window=cfg.attn_window)
    B, S = x.shape[0], x.shape[1]
    out = out.reshape(B, S, -1) @ params["wo"].astype(x.dtype)
    return out, new_cache


def _attn_backfill(cfg, params, h, ctx, cache):
    """KV backfill: project k/v from the exit hidden state, write, skip attn."""
    if cache is None:
        return None
    x = norm_apply(params["norm"], cfg, h)
    hd = cfg.resolved_head_dim
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    B, S = x.shape[0], x.shape[1]
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    table = ctx.get("block_table")
    if ctx["mode"] == "decode":
        from repro.models.layers import apply_rope
        k = apply_rope(k, jnp.full((1, 1), ctx["t"]), cfg.rope_theta)
        if table is not None:
            return _write_decode_paged(cache, k, v, ctx["slot"], table)
        return _write_decode(cache, k, v, ctx["slot"])
    from repro.models.layers import apply_rope
    k = apply_rope(k, ctx["positions"], cfg.rope_theta)
    if table is not None:
        return _write_full_paged(cache, k, v, ctx["write_slots"], table)
    return _write_full(cache, k, v, ctx["write_slots"])


# ---------------------------------------------------------------------------
# dense / moe blocks
# ---------------------------------------------------------------------------

def dense_init_block(key, cfg):
    ka, km = nn.split_keys(key, 2)
    return {"attn": attn_init(ka, cfg), "mlp": mlp_init(km, cfg)}


def dense_apply(cfg, params, h, ctx, cache):
    a, new_cache = _self_attention(cfg, params["attn"], h, ctx, cache)
    h = h + a
    m = mlp_apply(params["mlp"], cfg,
                  norm_apply(params["mlp"]["norm"], cfg, h))
    return h + m, new_cache, ZERO


def dense_backfill(cfg, params, h, ctx, cache):
    return _attn_backfill(cfg, params["attn"], h, ctx, cache)


def moe_init_block(key, cfg):
    ka, km = nn.split_keys(key, 2)
    return {"attn": attn_init(ka, cfg), "moe": moe_init(km, cfg)}


def moe_apply_block(cfg, params, h, ctx, cache):
    a, new_cache = _self_attention(cfg, params["attn"], h, ctx, cache)
    h = h + a
    x = norm_apply(params["moe"]["norm"], cfg, h)
    m, aux = moe_apply(params["moe"], cfg, x)
    return h + m, new_cache, aux


# ---------------------------------------------------------------------------
# mamba / hybrid shared-attention blocks
# ---------------------------------------------------------------------------

def mamba_init_block(key, cfg):
    return {"ssm": ssm.ssm_init(key, cfg)}


def mamba_apply(cfg, params, h, ctx, cache):
    x = norm_apply(params["ssm"]["norm"], cfg, h)
    if ctx["mode"] == "full":
        y, new_cache = ssm.ssm_forward_full(params["ssm"], cfg, x, cache)
    else:
        y, new_cache = ssm.ssm_decode_step(params["ssm"], cfg, x, cache)
    return h + y, new_cache, ZERO


def mamba_cache(cfg, batch, W, dtype):
    del W
    return ssm.ssm_init_cache(cfg, batch, dtype)


def mamba_backfill(cfg, params, h, ctx, cache):
    """SSM state backfill = run the recurrence but skip out_proj/gating."""
    if cache is None:
        return None
    x = norm_apply(params["ssm"]["norm"], cfg, h)
    if ctx["mode"] == "full":
        _, new_cache = ssm.ssm_forward_full(params["ssm"], cfg, x, cache)
    else:
        _, new_cache = ssm.ssm_decode_step(params["ssm"], cfg, x, cache)
    return new_cache


def shared_attn_init(key, cfg):
    """Per-invocation params of the zamba2-style shared block: LoRA deltas on
    q/k/v.  The shared full-rank weights live in ctx['shared']."""
    r = 16
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4, k5, k6 = nn.split_keys(key, 6)
    return {
        "lora_q_a": nn.dense_init(k1, (cfg.d_model, r)),
        "lora_q_b": nn.zeros_init(k2, (r, cfg.n_heads * hd)),
        "lora_k_a": nn.dense_init(k3, (cfg.d_model, r)),
        "lora_k_b": nn.zeros_init(k4, (r, cfg.n_kv_heads * hd)),
        "lora_v_a": nn.dense_init(k5, (cfg.d_model, r)),
        "lora_v_b": nn.zeros_init(k6, (r, cfg.n_kv_heads * hd)),
    }


def shared_attn_apply(cfg, params, h, ctx, cache):
    shared = ctx["shared"]          # full attention + mlp params, shared
    lora = params
    # merge LoRA into the projections by adding low-rank outputs
    attn_p = dict(shared["attn"])

    def proj_with_lora(x, w, a, b):
        return x @ w.astype(x.dtype) + (x @ a.astype(x.dtype)) @ b.astype(x.dtype)

    x = norm_apply(attn_p["norm"], cfg, h)
    hd = cfg.resolved_head_dim
    B, S = x.shape[0], x.shape[1]
    q = proj_with_lora(x, attn_p["wq"], lora["lora_q_a"], lora["lora_q_b"])
    k = proj_with_lora(x, attn_p["wk"], lora["lora_k_a"], lora["lora_k_b"])
    v = proj_with_lora(x, attn_p["wv"], lora["lora_v_a"], lora["lora_v_b"])
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    from repro.models.layers import apply_rope
    if ctx["mode"] == "full":
        q = apply_rope(q, ctx["positions"], cfg.rope_theta)
        k = apply_rope(k, ctx["positions"], cfg.rope_theta)
        attend = pick_attend(cfg, S, S, differentiable=cache is None)
        out = attend(q, k, v, ctx["positions"], ctx["positions"],
                     window=0, causal=True)
        new_cache = (_write_full(cache, k, v, ctx["write_slots"])
                     if cache is not None else None)
    else:
        t = ctx["t"]
        q = apply_rope(q, jnp.full((1, 1), t), cfg.rope_theta)
        k = apply_rope(k, jnp.full((1, 1), t), cfg.rope_theta)
        new_cache = _write_decode(cache, k, v, ctx["slot"])
        kpos = ctx["kpos"].at[..., ctx["slot"]].set(t)
        out = attend_decode(q, *_ring_kv(cache, new_cache, ctx["slot"]),
                            t, kpos)
    out = out.reshape(B, S, -1) @ attn_p["wo"].astype(x.dtype)
    h = h + out
    m = mlp_apply(shared["mlp"], cfg, norm_apply(shared["mlp"]["norm"], cfg, h))
    return h + m, new_cache, ZERO


def shared_attn_backfill(cfg, params, h, ctx, cache):
    if cache is None:
        return None
    return _attn_backfill(cfg, ctx["shared"]["attn"], h, ctx, cache)


# ---------------------------------------------------------------------------
# xLSTM blocks
# ---------------------------------------------------------------------------

def mlstm_init_block(key, cfg):
    return {"mlstm": xlstm.mlstm_init(key, cfg)}


def mlstm_apply(cfg, params, h, ctx, cache):
    x = norm_apply(params["mlstm"]["norm"], cfg, h)
    if ctx["mode"] == "full":
        y, new_cache = xlstm.mlstm_forward_full(params["mlstm"], cfg, x, cache)
    else:
        y, new_cache = xlstm.mlstm_decode_step(params["mlstm"], cfg, x, cache)
    return h + y, new_cache, ZERO


def mlstm_cache(cfg, batch, W, dtype):
    del W
    return xlstm.mlstm_init_cache(cfg, batch, dtype)


def mlstm_backfill(cfg, params, h, ctx, cache):
    if cache is None:
        return None
    x = norm_apply(params["mlstm"]["norm"], cfg, h)
    if ctx["mode"] == "full":
        _, new_cache = xlstm.mlstm_forward_full(params["mlstm"], cfg, x, cache)
    else:
        _, new_cache = xlstm.mlstm_decode_step(params["mlstm"], cfg, x, cache)
    return new_cache


def slstm_init_block(key, cfg):
    return {"slstm": xlstm.slstm_init(key, cfg)}


def slstm_apply(cfg, params, h, ctx, cache):
    x = norm_apply(params["slstm"]["norm"], cfg, h)
    if ctx["mode"] == "full":
        y, new_cache = xlstm.slstm_forward_full(params["slstm"], cfg, x, cache)
    else:
        y, new_cache = xlstm.slstm_decode_step(params["slstm"], cfg, x, cache)
    return h + y, new_cache, ZERO


def slstm_cache(cfg, batch, W, dtype):
    del W
    return xlstm.slstm_init_cache(cfg, batch, dtype)


def slstm_backfill(cfg, params, h, ctx, cache):
    if cache is None:
        return None
    x = norm_apply(params["slstm"]["norm"], cfg, h)
    if ctx["mode"] == "full":
        _, new_cache = xlstm.slstm_forward_full(params["slstm"], cfg, x, cache)
    else:
        _, new_cache = xlstm.slstm_decode_step(params["slstm"], cfg, x, cache)
    return new_cache


# ---------------------------------------------------------------------------
# cross-attention blocks (vlm / whisper)
# ---------------------------------------------------------------------------

def _cross_attention(cfg, params, h, ctx, cache):
    """Cross-attend to ctx['cross'] (B,T,d).  Cross K/V cached at prefill."""
    x = norm_apply(params["norm"], cfg, h)
    hd = cfg.resolved_head_dim
    B, S = x.shape[0], x.shape[1]
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, S, cfg.n_heads, hd)
    if cache is not None and ctx["mode"] == "decode":
        k, v = cache["k"].astype(x.dtype), cache["v"].astype(x.dtype)
        new_cache = cache
    else:
        mem = ctx["cross"].astype(x.dtype)
        T = mem.shape[1]
        k = (mem @ params["wk"].astype(x.dtype)).reshape(B, T, cfg.n_kv_heads, hd)
        v = (mem @ params["wv"].astype(x.dtype)).reshape(B, T, cfg.n_kv_heads, hd)
        new_cache = ({"k": k.astype(cache["k"].dtype),
                      "v": v.astype(cache["v"].dtype)}
                     if cache is not None else None)
    T = k.shape[1]
    kpos = jnp.arange(T)
    qpos = jnp.full((S,), T, jnp.int32)  # non-causal: all memory visible
    out = attend_full(q, k, v, qpos, kpos, window=0, causal=False)
    out = out.reshape(B, S, -1) @ params["wo"].astype(x.dtype)
    if "gate" in params:  # llama-3.2-vision tanh gating
        out = out * jnp.tanh(params["gate"]).astype(out.dtype)
    return out, new_cache


def cross_cache_init(cfg, batch, W, dtype):
    del W
    hd = cfg.resolved_head_dim
    T = cfg.n_image_tokens or cfg.n_audio_frames
    return {"k": jnp.zeros((batch, T, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, T, cfg.n_kv_heads, hd), dtype)}


def xattn_init_block(key, cfg):
    ka, km = nn.split_keys(key, 2)
    return {"xattn": attn_init(ka, cfg, cross=True), "mlp": mlp_init(km, cfg)}


def xattn_apply(cfg, params, h, ctx, cache):
    a, new_cache = _cross_attention(cfg, params["xattn"], h, ctx, cache)
    h = h + a
    m = mlp_apply(params["mlp"], cfg, norm_apply(params["mlp"]["norm"], cfg, h))
    return h + m, new_cache, ZERO


def xattn_backfill(cfg, params, h, ctx, cache):
    return cache  # cross K/V depend only on the image/audio memory


def encdec_init_block(key, cfg):
    ka, kx, km = nn.split_keys(key, 3)
    return {"attn": attn_init(ka, cfg), "xattn": attn_init(kx, cfg),
            "mlp": mlp_init(km, cfg)}


def encdec_apply(cfg, params, h, ctx, cache):
    self_cache = cache["self"] if cache is not None else None
    a, new_self = _self_attention(cfg, params["attn"], h, ctx, self_cache)
    h = h + a
    cross_cache = cache["cross"] if cache is not None else None
    c, new_cross = _cross_attention(cfg, params["xattn"], h, ctx, cross_cache)
    h = h + c
    m = mlp_apply(params["mlp"], cfg, norm_apply(params["mlp"]["norm"], cfg, h))
    new_cache = ({"self": new_self, "cross": new_cross}
                 if cache is not None else None)
    return h + m, new_cache, ZERO


def encdec_cache(cfg, batch, W, dtype):
    return {"self": attn_cache_init(cfg, batch, W, dtype),
            "cross": cross_cache_init(cfg, batch, W, dtype)}


def encdec_backfill(cfg, params, h, ctx, cache):
    if cache is None:
        return None
    return {"self": _attn_backfill(cfg, params["attn"], h, ctx, cache["self"]),
            "cross": cache["cross"]}


def enc_init_block(key, cfg):
    ka, km = nn.split_keys(key, 2)
    return {"attn": attn_init(ka, cfg), "mlp": mlp_init(km, cfg)}


def enc_apply(cfg, params, h, ctx, cache):
    """Bidirectional encoder layer (whisper encoder)."""
    x = norm_apply(params["attn"]["norm"], cfg, h)
    S = x.shape[1]
    pos = jnp.arange(S)
    q, k, v = qkv_project(params["attn"], cfg, x, rope_positions=None)
    out = attend_full(q, k, v, pos, pos, window=0, causal=False)
    out = out.reshape(x.shape[0], S, -1) @ params["attn"]["wo"].astype(x.dtype)
    h = h + out
    m = mlp_apply(params["mlp"], cfg, norm_apply(params["mlp"]["norm"], cfg, h))
    return h + m, None, ZERO


def _no_cache(cfg, batch, W, dtype):
    return {}


def _no_backfill(cfg, params, h, ctx, cache):
    return cache


BLOCKS: Dict[str, BlockDef] = {
    "dense": BlockDef(dense_init_block, dense_apply, attn_cache_init,
                      dense_backfill),
    "moe": BlockDef(moe_init_block, moe_apply_block, attn_cache_init,
                    dense_backfill),
    "mamba": BlockDef(mamba_init_block, mamba_apply, mamba_cache,
                      mamba_backfill),
    "attn_shared": BlockDef(shared_attn_init, shared_attn_apply,
                            attn_cache_init, shared_attn_backfill),
    "mlstm": BlockDef(mlstm_init_block, mlstm_apply, mlstm_cache,
                      mlstm_backfill),
    "slstm": BlockDef(slstm_init_block, slstm_apply, slstm_cache,
                      slstm_backfill),
    "xattn": BlockDef(xattn_init_block, xattn_apply, cross_cache_init,
                      xattn_backfill),
    "encdec": BlockDef(encdec_init_block, encdec_apply, encdec_cache,
                       encdec_backfill),
    "enc": BlockDef(enc_init_block, enc_apply, _no_cache, _no_backfill),
}


def layer_kinds(cfg) -> list[str]:
    """The per-layer kind sequence of an architecture."""
    L = cfg.n_layers
    fam = cfg.family
    if fam == "dense":
        return ["dense"] * L
    if fam == "moe":
        return ["moe"] * L
    if fam == "ssm":  # xlstm
        if cfg.slstm_every:
            return ["slstm" if (i % cfg.slstm_every == cfg.slstm_every - 1)
                    else "mlstm" for i in range(L)]
        return ["mamba"] * L
    if fam == "hybrid":
        k = cfg.shared_attn_every
        return ["attn_shared" if (k and i % k == 0) else "mamba"
                for i in range(L)]
    if fam == "vlm":
        k = cfg.cross_attn_every
        return ["xattn" if (k and i % k == k - 1) else "dense"
                for i in range(L)]
    if fam == "audio":
        return ["encdec"] * L
    raise ValueError(f"unknown family {fam}")
