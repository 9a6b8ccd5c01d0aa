"""Fused per-segment exit-head megakernel: rmsnorm + shared-unembed matmul
+ softmax confidence + exit-update carry merge in ONE streaming pass.

Per decode step and cascade component, the staged executor's exit
evaluation is (a) the exit head's rmsnorm, (b) the ``(B, d) @ (d, V)``
unembedding, and (c) the exit-update scan step
(:mod:`repro.kernels.exit_update`).  Run separately, (b) materializes the
``(B, V)`` logits in HBM just for (c) to stream them back — at serving
vocab sizes the logits round-trip IS the exit head's bandwidth bill.
This kernel deletes it: grid ``(B/Bt, V/Vt)`` with the vocab axis
innermost, the normalized hidden block is computed once per row block
into VMEM scratch (at ``j == 0``), each grid cell multiplies it against
one ``(d, Vt)`` unembedding tile and feeds the logits tile straight into
the running (max, Σexp, argmax) scratch — logits never leave VMEM — and
the last vocab tile applies the full exit-update carry merge exactly as
:func:`repro.kernels.exit_update.exit_update` does.

**Fusion boundary.**  The megakernel fuses the *exit head*, not the
segment body: between decode attention and the exit head sit the
segment's remaining layers (qkv/wo/MLP matmuls under ``lax.scan``), so a
literal attention+head single kernel would have to inline entire
transformer layers.  Decode attention keeps its own exit-masked kernel
(:mod:`repro.kernels.decode_attention`); what this kernel adds is the
elimination of the O(B·V) logits intermediate — the largest tensor the
decode step touches.  Heads outside the boundary (layernorm bias,
enhancement MLP, non-rmsnorm) take the unfused path; callers route via
:meth:`repro.models.model.CascadeModel.exit_head_params`.

**Live-mask grid early-out.**  ``live`` is the per-slot exit mask
(``ctx["live"]`` = ``DecodeState.active``).  A grid cell whose whole
``Bt``-row block is dead skips the norm, the matmul and the softmax
update under ``pl.when`` — a fully-exited cohort's rows cost one
predicate per cell, the same contract as the decode-attention kernel's
per-slot early-out.  Dead rows pass their carry through unchanged (a
retired slot's outputs are never read and its lane re-prefills before
reuse, so pass-through is as good as the dense value at none of the
cost).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.exit_update import CARRY_DTYPES

NEG = -1e30


def _megakernel(*refs, n_vtiles, vt, V, threshold, m, n_components,
                patience_k, ema_decay, dynamic, tel_bins, eps, lowp):
    # ref layout: blk [th_ref?] x w head live | ans pred exit conf streak
    #             ema act | outs (6 or 7) | scratch: m l a xn
    refs = list(refs)
    blk_ref = refs.pop(0)
    th_ref = refs.pop(0) if dynamic else None
    (x_ref, w_ref, head_ref, live_ref, ans_ref, pred_ref, exit_ref,
     conf_ref, streak_ref, ema_ref, act_ref) = refs[:11]
    outs = refs[11:-4]
    ans_o, pred_o, exit_o, conf_o, streak_o, ema_o = outs[:6]
    m_s, l_s, a_s, xn_s = refs[-4:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], NEG)
        l_s[...] = jnp.zeros_like(l_s[...])
        a_s[...] = jnp.zeros_like(a_s[...])

    # any live row in this Bt block (a scalar from SMEM, not a vector
    # reduction: pl.when needs a scalar predicate)
    blk_live = blk_ref[pl.program_id(0)] != 0

    @pl.when(jnp.logical_and(blk_live, j == 0))
    def _norm():
        # the exit head's rmsnorm, once per row block (revisited scratch),
        # operand order bit-locked to kernels/rmsnorm.py
        xv = x_ref[...].astype(jnp.float32)
        var = jnp.mean(jnp.square(xv), axis=-1, keepdims=True)
        y = xv * jax.lax.rsqrt(var + eps)
        xn_s[...] = (y * w_ref[...].astype(jnp.float32)).astype(xn_s.dtype)

    @pl.when(blk_live)
    def _stream():
        lt = jnp.dot(xn_s[...], head_ref[...].astype(xn_s.dtype),
                     preferred_element_type=jnp.float32)
        if lowp:
            # low-precision models emit logits in the model dtype before
            # the f32 confidence math — same rounding as the unfused path
            lt = lt.astype(xn_s.dtype).astype(jnp.float32)
        # vocab pad columns (zero head columns) must never win the max
        col = j * vt + jax.lax.broadcasted_iota(jnp.int32, lt.shape, 1)
        lt = jnp.where(col < V, lt, NEG)
        tile_max = jnp.max(lt, axis=-1, keepdims=True)  # (Bt, 1)
        tile_arg = (jnp.argmax(lt, axis=-1, keepdims=True)
                    .astype(jnp.int32) + j * vt)
        m_old = m_s[...]
        m_new = jnp.maximum(m_old, tile_max)
        l_s[...] = (l_s[...] * jnp.exp(m_old - m_new)
                    + jnp.sum(jnp.exp(lt - m_new), axis=-1, keepdims=True))
        a_s[...] = jnp.where(tile_max > m_old, tile_arg, a_s[...])
        m_s[...] = m_new

    @pl.when(j == n_vtiles - 1)
    def _update():
        # exit_update's carry merge, with dead rows passing through: every
        # update funnels through ``gate``/``fresh``, so masking the gate
        # with the live row mask is the whole pass-through story (plus the
        # streak and EMA rows, which update outside the gate)
        lv = live_ref[...] != 0
        conf = 1.0 / l_s[...]                # exp(m − lse); inf when dead
        pred = a_s[...]
        last = m >= n_components - 1
        thr = th_ref[0] if dynamic else threshold
        if last:
            gate = jnp.ones_like(conf, bool)
        else:
            gate = conf >= thr
        if patience_k > 0:
            row = jnp.where(jnp.logical_and(gate, lv), streak_ref[...] + 1, 0)
            row = jnp.where(lv, row, streak_ref[...])
            streak_o[...] = row
            gate = row >= patience_k
            if last:
                gate = jnp.ones_like(gate)
        else:
            streak_o[...] = streak_ref[...]
        gate = jnp.logical_and(gate, lv)
        answered = ans_ref[...] != 0
        fresh = jnp.logical_and(gate, jnp.logical_not(answered))
        ans_o[...] = jnp.logical_or(answered, gate).astype(jnp.int32)
        pred_o[...] = jnp.where(fresh, pred, pred_ref[...])
        exit_o[...] = jnp.where(fresh, jnp.int32(m), exit_ref[...])
        cf = jnp.where(fresh, conf, conf_ref[...])
        conf_o[...] = cf
        if ema_decay > 0.0:
            fold = ema_decay * ema_ref[...] + (1.0 - ema_decay) * cf
            ema_o[...] = jnp.where(
                jnp.logical_and(act_ref[...] != 0, lv), fold, ema_ref[...])
        else:
            ema_o[...] = ema_ref[...]
        if tel_bins:
            from repro.autotune.telemetry import pack_rider
            code_o = outs[6]
            cf_t = jnp.where(lv, conf, 0.0)   # no inf into the bin math
            code_o[...] = jnp.where(lv, pack_rider(pred, cf_t, tel_bins), 0)


def exit_head_update(h, norm_w, head, answered, pred, exit_idx, conf,
                     streak, ema, active, *, threshold, m: int,
                     n_components: int, patience_k: int = 0,
                     ema_decay: float = 0.0, tel_bins: int = 0, live=None,
                     eps: float = 1e-5, bt: int = 8, vt: int = 1024,
                     interpret: "bool | None" = None):
    """One fused exit-head component step: rmsnorm(h) @ head streamed over
    vocab tiles into the exit-update scan.

    h (B, d); norm_w (d,); head (d, V); carry vectors as
    :func:`repro.kernels.exit_update.exit_update`; ``live`` the per-slot
    exit mask ((B,) bool, None = all live).  Live rows return exactly what
    ``exit_update(rmsnorm(h) @ head, ...)`` returns; dead rows pass every
    carry through unchanged (their grid cells skip the matmul entirely).
    ``threshold`` folds into the body when a float or rides as an operand
    when a jax scalar (live-threshold pushes never retrace).
    """
    dynamic = isinstance(threshold, jax.Array)
    if dynamic:
        th_arr = jnp.asarray(threshold, jnp.float32).reshape(1)
        th_static = 0.0
    else:
        th_arr = jnp.zeros((1,), jnp.float32)
        th_static = float(threshold)
    return _exit_head_update(
        th_arr, h, norm_w, head, answered, pred, exit_idx, conf, streak,
        ema, active, live, threshold=th_static, dynamic=dynamic, m=m,
        n_components=n_components, patience_k=patience_k,
        ema_decay=ema_decay, tel_bins=int(tel_bins), eps=float(eps), bt=bt,
        vt=vt, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "threshold", "dynamic", "m", "n_components", "patience_k", "ema_decay",
    "tel_bins", "eps", "bt", "vt", "interpret"))
def _exit_head_update(th_arr, h, norm_w, head, answered, pred, exit_idx,
                      conf, streak, ema, active, live, *, threshold,
                      dynamic, m, n_components, patience_k, ema_decay,
                      tel_bins, eps, bt, vt, interpret):
    B, d = h.shape
    V = head.shape[1]
    bt = min(bt, B)
    vt = min(vt, V)
    padB = (-B) % bt
    padV = (-V) % vt
    x = jnp.pad(h, ((0, padB), (0, 0))) if padB else h
    hd = jnp.pad(head, ((0, 0), (0, padV))) if padV else head
    live = (jnp.ones((B,), jnp.int32) if live is None
            else jnp.asarray(live).astype(jnp.int32))
    vecs = [live,
            jnp.asarray(answered).astype(jnp.int32),
            jnp.asarray(pred).astype(jnp.int32),
            jnp.asarray(exit_idx).astype(jnp.int32),
            jnp.asarray(conf).astype(jnp.float32),
            jnp.asarray(streak).astype(jnp.int32),
            jnp.asarray(ema).astype(jnp.float32),
            jnp.asarray(active).astype(jnp.int32)]
    vecs = [jnp.pad(v, (0, padB))[:, None] for v in vecs]
    Bp = B + padB
    blk = jnp.max(vecs[0].reshape(Bp // bt, bt), axis=1)
    n_vtiles = (V + padV) // vt
    kernel = functools.partial(
        _megakernel, n_vtiles=n_vtiles, vt=vt, V=V, threshold=threshold,
        m=int(m), n_components=int(n_components),
        patience_k=int(patience_k), ema_decay=float(ema_decay),
        dynamic=dynamic, tel_bins=tel_bins, eps=eps,
        lowp=(h.dtype != jnp.float32))
    vec_spec = pl.BlockSpec((bt, 1), lambda i, j: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem] + ([smem] if dynamic else [])
    in_specs += [pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
                 pl.BlockSpec((d,), lambda i, j: (0,)),
                 pl.BlockSpec((d, vt), lambda i, j: (0, j))]
    in_specs += [vec_spec] * 8
    out_specs = [vec_spec] * (7 if tel_bins else 6)
    out_shape = [jax.ShapeDtypeStruct((Bp, 1), dt) for dt in CARRY_DTYPES]
    if tel_bins:
        out_shape += [jax.ShapeDtypeStruct((Bp, 1), jnp.int32)]
    args = [blk] + ([th_arr] if dynamic else []) + [x, norm_w, hd] + vecs
    outs = pl.pallas_call(
        kernel,
        grid=(Bp // bt, n_vtiles),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32),
                        pltpu.VMEM((bt, 1), jnp.float32),
                        pltpu.VMEM((bt, 1), jnp.int32),
                        pltpu.VMEM((bt, d), h.dtype)],
        interpret=interpret,
    )(*args)
    outs = [o[:B, 0] for o in outs]
    outs[0] = outs[0].astype(bool)
    return tuple(outs)
