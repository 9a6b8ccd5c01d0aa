"""Single-query (decode) attention Pallas kernel with ring-buffer masking
and per-slot exit masking.

One new token attends over a KV cache of length W.  Grid: (B, KV_heads,
W/Tk) with the W axis innermost; the (qpk, hd) query-group tile stays in
VMEM and KV tiles stream through, carrying the online-softmax (acc, m, l)
in scratch.  The slot-position vector ``kpos`` (absolute position per cache
slot, −1 = empty) is streamed alongside each KV tile and implements causal
+ sliding-window + ring-wraparound masking in one comparison.

``live`` is the exit-aware part: a per-batch-slot mask (1 = still
generating).  Every ``(b, h, ik)`` grid cell belonging to a dead slot
early-outs under ``pl.when`` — no QK^T, no exp, no PV — and the output row
zero-fills (the serving engine discards dead slots' outputs anyway, and a
lane re-prefills from scratch before a slot is reused, so zero is as good
as the dense value at a fraction of the cost).  Live rows are bit-identical
to the unmasked kernel: decode attention is batch-separable, so masking one
row cannot perturb another.

Layout: q (B, KV, qpk, hd); k, v (B, KV, W, hd); kpos (W,) int32 — or
(B, W) for the paged cache layout's per-slot position rings (the lane-wide
(W,) vector is broadcast; the masking arithmetic per row is unchanged, so
dense calls are bit-identical to the 1-D operand); t scalar; live (B,)
int32.  ``t`` and ``live`` are scalar-prefetch operands (SMEM), and kpos
streams as (1, 1, Tk) blocks of a (B, 1, W) view, so every block obeys the
TPU's (8, 128) tiling rule at any B.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG = -1e30


def _decode_kernel(t_ref, live_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                   acc_s, m_s, l_s, *, tk, n_ktiles, window, scale):
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s[...])
        m_s[...] = jnp.full_like(m_s[...], NEG)
        l_s[...] = jnp.zeros_like(l_s[...])

    # exit mask: dead slots skip the whole tile's compute (their scratch
    # stays zero, so the final write below emits an all-zero row)
    @pl.when(live_ref[pl.program_id(0)] != 0)
    def _tile():
        t = t_ref[0]
        q = q_ref[0, 0].astype(jnp.float32)                # (qpk, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (Tk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        kpos = kpos_ref[0]                                 # (1, Tk)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = (kpos >= 0) & (kpos <= t)
        if window:
            mask &= kpos > t - window
        s = jnp.where(mask, s, NEG)
        m_old = m_s[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_old - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1)
        acc_s[...] = acc_s[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(jk == n_ktiles - 1)
    def _out():
        # dead rows: acc == 0, l == 0 -> 0 / 1e-30 == exact zero-fill
        o_ref[0, 0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, t, kpos, live=None, *,
                     window: int = 0, tk: int = 512,
                     interpret: "bool | None" = None):
    """q: (B, KV, qpk, hd); caches (B, KV, W, hd); t scalar int32;
    kpos (W,) int32 — or (B, W) per-slot rings (paged layout); live (B,)
    bool/int32 or None (all live)
    -> (B, KV, qpk, hd) with dead slots' rows zero-filled.

    ``interpret`` resolves OUTSIDE the jit boundary (env var / backend
    auto-detection re-consulted every call, not baked into the trace)."""
    return _decode_attention(q, k_cache, v_cache, t, kpos, live,
                             window=window, tk=tk,
                             interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("window", "tk", "interpret"))
def _decode_attention(q, k_cache, v_cache, t, kpos, live, *, window, tk,
                      interpret):
    B, KV, qpk, hd = q.shape
    W = k_cache.shape[2]
    tk = min(tk, W)
    pad = (-W) % tk
    # per-row position rings: the lane-wide (W,) vector broadcasts to
    # (B, W) so every grid cell streams ITS slot's ring — same arithmetic,
    # so dense (broadcast) calls are bit-identical to the 1-D operand
    kpos = jnp.broadcast_to(kpos, (B, W))
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=-1)
    Wp = W + pad
    n_ktiles = Wp // tk
    scale = 1.0 / math.sqrt(hd)
    live = (jnp.ones((B,), jnp.int32) if live is None
            else jnp.asarray(live).astype(jnp.int32))
    kernel = functools.partial(_decode_kernel, tk=tk, n_ktiles=n_ktiles,
                               window=window, scale=scale)
    # index maps take the grid indices, then the prefetched (t, live) refs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_ktiles),
        in_specs=[
            pl.BlockSpec((1, 1, qpk, hd), lambda b, h, ik, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, tk, hd), lambda b, h, ik, *_: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, tk, hd), lambda b, h, ik, *_: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, tk), lambda b, h, ik, *_: (b, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, qpk, hd),
                               lambda b, h, ik, *_: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((qpk, hd), jnp.float32),
                        pltpu.VMEM((qpk,), jnp.float32),
                        pltpu.VMEM((qpk,), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, qpk, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(t, jnp.int32).reshape(1), live, q, k_cache, v_cache,
      kpos[:, None, :])
    return out
