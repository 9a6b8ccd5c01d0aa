"""Fused softmax-confidence Pallas kernel — the paper's hot-spot on TPU.

δ_m = max softmax = exp(max z − logsumexp z) over a vocab of up to 256k per
exit head per decode step.  A naive implementation materializes the (B, V)
f32 softmax in HBM; this kernel streams vocab tiles through VMEM keeping only
running (max, Σexp, argmax) per row — O(B) output, one HBM read of the
logits, zero intermediate HBM traffic.

Grid: (B/Bt, V/Vt), vocab axis innermost so the running scratch accumulates
across the contraction.  Tiles are MXU/VPU aligned (Vt multiple of 128);
per-row results ride as (Bt, 1) column blocks (the TPU tiling rule refuses
a rank-1 (Bt,) block of a longer vector).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG = -1e30


def _conf_kernel(x_ref, idx_ref, conf_ref, m_s, l_s, a_s, *, n_vtiles, vt):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], NEG)
        l_s[...] = jnp.zeros_like(l_s[...])
        a_s[...] = jnp.zeros_like(a_s[...])

    x = x_ref[...].astype(jnp.float32)              # (Bt, Vt)
    tile_max = jnp.max(x, axis=-1, keepdims=True)   # (Bt, 1)
    tile_arg = (jnp.argmax(x, axis=-1, keepdims=True).astype(jnp.int32)
                + j * vt)
    m_old = m_s[...]
    m_new = jnp.maximum(m_old, tile_max)
    l_s[...] = (l_s[...] * jnp.exp(m_old - m_new)
                + jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True))
    a_s[...] = jnp.where(tile_max > m_old, tile_arg, a_s[...])
    m_s[...] = m_new

    @pl.when(j == n_vtiles - 1)
    def _out():
        idx_ref[...] = a_s[...]
        conf_ref[...] = 1.0 / l_s[...]              # exp(m − lse) = 1/Σe^{x−m}


def confidence(logits, *, bt: int = 8, vt: int = 2048,
               interpret: "bool | None" = None):
    """logits: (B, V) -> (argmax (B,) int32, δ (B,) f32).  ``interpret``
    resolves outside the jit boundary (never baked into the trace)."""
    return _confidence(logits, bt=bt, vt=vt,
                       interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bt", "vt", "interpret"))
def _confidence(logits, *, bt, vt, interpret):
    B, V = logits.shape
    bt = min(bt, B)
    vt = min(vt, V)
    padB = (-B) % bt
    padV = (-V) % vt
    x = logits
    if padB or padV:
        x = jnp.pad(x, ((0, padB), (0, padV)), constant_values=NEG)
    Bp, Vp = x.shape
    n_vtiles = Vp // vt
    kernel = functools.partial(_conf_kernel, n_vtiles=n_vtiles, vt=vt)
    idx, conf = pl.pallas_call(
        kernel,
        grid=(Bp // bt, n_vtiles),
        in_specs=[pl.BlockSpec((bt, vt), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((bt, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32),
                        pltpu.VMEM((bt, 1), jnp.float32),
                        pltpu.VMEM((bt, 1), jnp.int32)],
        interpret=interpret,
    )(x)
    return idx[:B, 0], conf[:B, 0]
