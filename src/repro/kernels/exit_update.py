"""Fused exit-update Pallas kernel: softmax-max confidence + threshold gate
+ decision-scan carry update + DecodeState update in ONE pass over the
logits.

Per decode step and cascade component, the exit decision needs (Defs.
3.2/3.3 + Algorithm 1 + the PABEE patience rewrite + the DecodeState
telemetry):

1. δ = max softmax of the (B, V) exit logits, and its argmax;
2. the threshold gate ``δ >= δ̂_m`` (the final component always answers);
3. the patience-streak rewrite (``streak' = gate ? streak+1 : 0``, gate
   becomes ``streak' >= k``) when the measure is ``patience@k``;
4. the first-open-gate carry merge (answered / pred / exit / conf); and
5. on the final component, the per-slot confidence-EMA fold carried in
   :class:`repro.core.exec.DecodeState` (``ema' = d·ema + (1−d)·conf`` for
   active slots).

The dense path runs these as a softmax pass plus ~10 separate (B,)
elementwise ops per component per token.  This kernel streams vocab tiles
through VMEM carrying running (max, Σexp, argmax) scratch — the softmax is
never materialized — and applies ALL the (B,) updates in-register at the
last vocab tile: one HBM read of the logits, O(B) outputs, zero
intermediate traffic.  ``δ̂_m``, the component index and the patience k are
static (thresholds resolve to floats at trace time), so the comparisons
fold into the kernel body.

``DecodeState.segments_run`` is the one piece of state that stays outside:
it counts which ``lax.cond`` branches actually executed, which only the
cond structure in :meth:`repro.core.exec.StagedExecutor.decode_step` can
know.

Grid: (B/Bt, V/Vt), vocab axis innermost.  All (B,) carry vectors ride as
(B, 1) columns in (Bt, 1) blocks, revisited every vocab tile and written
once at the last: a rank-1 (Bt,) block of a longer vector breaks the TPU's
(8, 128) block tiling rule, while a (Bt, 1) block spans the full minor dim.
A live threshold rides in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG = -1e30
# answered' pred' exit' conf' streak' ema'
CARRY_DTYPES = (jnp.int32, jnp.int32, jnp.int32, jnp.float32, jnp.int32,
                jnp.float32)


def _exit_update_kernel(*refs, n_vtiles, vt, threshold, m, n_components,
                        patience_k, ema_decay, dynamic, tel_bins):
    # ref layout: [th_ref?] x ans pred exit conf streak ema act |
    #             ans pred exit conf streak ema [tel_code]? | scratch×3
    refs = list(refs)
    th_ref = refs.pop(0) if dynamic else None
    (x_ref, ans_ref, pred_ref, exit_ref, conf_ref, streak_ref, ema_ref,
     act_ref) = refs[:8]
    outs = refs[8:-3]
    ans_o, pred_o, exit_o, conf_o, streak_o, ema_o = outs[:6]
    m_s, l_s, a_s = refs[-3:]
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
    def _update():
        conf = 1.0 / l_s[...]                       # exp(m − lse) = 1/Σe^{x−m}
        pred = a_s[...]
        last = m >= n_components - 1
        # the final component's gate is open BEFORE the patience rewrite
        # (its streak row always advances), exactly like the dense
        # ThresholdPolicy.component_gate + scan_component order
        thr = th_ref[0] if dynamic else threshold
        if last:
            gate = jnp.ones_like(conf, bool)
        else:
            gate = conf >= thr
        if patience_k > 0:                          # patience@k rewrite
            row = jnp.where(gate, streak_ref[...] + 1, 0)
            streak_o[...] = row
            gate = row >= patience_k
            if last:
                gate = jnp.ones_like(gate)
        else:
            streak_o[...] = streak_ref[...]
        answered = ans_ref[...] != 0
        fresh = jnp.logical_and(gate, jnp.logical_not(answered))
        ans_o[...] = jnp.logical_or(answered, gate).astype(jnp.int32)
        pred_o[...] = jnp.where(fresh, pred, pred_ref[...])
        exit_o[...] = jnp.where(fresh, jnp.int32(m), exit_ref[...])
        cf = jnp.where(fresh, conf, conf_ref[...])
        conf_o[...] = cf
        if ema_decay > 0.0:                         # DecodeState EMA fold
            ema_o[...] = jnp.where(
                act_ref[...] != 0,
                ema_decay * ema_ref[...] + (1.0 - ema_decay) * cf,
                ema_ref[...])
        else:
            ema_o[...] = ema_ref[...]
        if tel_bins:
            # autotune telemetry rides the same streaming pass: the ONE
            # packed prediction/confidence-bin code, O(Bt) extra work at
            # the last vocab tile.  pack_rider is pure jnp, so calling it
            # here keeps the kernel bit-locked to the dense path by
            # construction, not by comment.
            from repro.autotune.telemetry import pack_rider
            code_o = outs[6]
            code_o[...] = pack_rider(pred, conf, tel_bins)


def exit_update(logits, answered, pred, exit_idx, conf, streak, ema, active,
                *, threshold, m: int, n_components: int,
                patience_k: int = 0, ema_decay: float = 0.0,
                tel_bins: int = 0, bt: int = 8, vt: int = 1024,
                interpret: "bool | None" = None):
    """One fused component step of the exit-decision scan.

    logits (B, V); answered/active (B,) bool; pred/exit_idx/streak (B,)
    int32; conf/ema (B,) f32.  Static: component ``m`` of
    ``n_components``, ``patience_k`` (0 = stateless measure), ``ema_decay``
    (0 = no EMA fold; pass the final component's decay), ``tel_bins``
    (> 0 additionally returns autotune telemetry computed in the same
    streaming pass).  ``threshold`` δ̂_m is a float (folded into the
    kernel body — the default) or a jax scalar (read as a kernel operand:
    the autotune live-threshold path, where a controller pushes new
    thresholds without retracing).

    Returns (answered', pred', exit', conf', streak', ema') with exactly
    :meth:`repro.core.policy.ExitDecider.scan_component` semantics (plus
    the :class:`~repro.core.exec.DecodeState` EMA fold when asked); with
    ``tel_bins`` one extra (B,) int32 output follows: the packed
    telemetry code ``raw_pred * tel_bins + conf_bin``.
    """
    dynamic = isinstance(threshold, jax.Array)
    if dynamic:
        th_arr = jnp.asarray(threshold, jnp.float32).reshape(1)
        th_static = 0.0
    else:
        th_arr = jnp.zeros((1,), jnp.float32)
        th_static = float(threshold)
    return _exit_update(th_arr, logits, answered, pred, exit_idx, conf,
                        streak, ema, active, threshold=th_static,
                        dynamic=dynamic, m=m, n_components=n_components,
                        patience_k=patience_k, ema_decay=ema_decay,
                        tel_bins=int(tel_bins), bt=bt, vt=vt,
                        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "threshold", "dynamic", "m", "n_components", "patience_k", "ema_decay",
    "tel_bins", "bt", "vt", "interpret"))
def _exit_update(th_arr, logits, answered, pred, exit_idx, conf, streak,
                 ema, active, *, threshold, dynamic, m, n_components,
                 patience_k, ema_decay, tel_bins, bt, vt, interpret):
    B, V = logits.shape
    bt = min(bt, B)
    vt = min(vt, V)
    padB = (-B) % bt
    padV = (-V) % vt
    x = logits
    if padB or padV:
        x = jnp.pad(x, ((0, padB), (0, padV)), constant_values=NEG)
    vecs = [jnp.asarray(answered).astype(jnp.int32),
            jnp.asarray(pred).astype(jnp.int32),
            jnp.asarray(exit_idx).astype(jnp.int32),
            jnp.asarray(conf).astype(jnp.float32),
            jnp.asarray(streak).astype(jnp.int32),
            jnp.asarray(ema).astype(jnp.float32),
            jnp.asarray(active).astype(jnp.int32)]
    vecs = [jnp.pad(v, (0, padB))[:, None] for v in vecs]
    Bp, Vp = x.shape
    n_vtiles = Vp // vt
    kernel = functools.partial(
        _exit_update_kernel, n_vtiles=n_vtiles, vt=vt,
        threshold=threshold, m=int(m),
        n_components=int(n_components), patience_k=int(patience_k),
        ema_decay=float(ema_decay), dynamic=dynamic, tel_bins=tel_bins)
    vec_spec = pl.BlockSpec((bt, 1), lambda i, j: (i, 0))
    in_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)] if dynamic else [])
    in_specs += [pl.BlockSpec((bt, vt), lambda i, j: (i, j))]
    in_specs += [vec_spec] * 7
    out_specs = [vec_spec] * (7 if tel_bins else 6)
    out_shape = [jax.ShapeDtypeStruct((Bp, 1), dt) for dt in CARRY_DTYPES]
    if tel_bins:
        out_shape += [jax.ShapeDtypeStruct((Bp, 1), jnp.int32)]
    args = ([th_arr] if dynamic else []) + [x] + vecs
    outs = pl.pallas_call(
        kernel,
        grid=(Bp // bt, n_vtiles),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32),
                        pltpu.VMEM((bt, 1), jnp.float32),
                        pltpu.VMEM((bt, 1), jnp.int32)],
        interpret=interpret,
    )(*args)
    outs = [o[:B, 0] for o in outs]
    outs[0] = outs[0].astype(bool)
    return tuple(outs)
