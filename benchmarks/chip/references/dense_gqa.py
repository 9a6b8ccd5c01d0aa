"""Plain float32 reference of a dense GQA decoder with cascade exits.

The architecture is the Qwen2 / Llama decoder: pre-norm RMSNorm, rotary
position embedding (half-split rotation), grouped-query attention with
optional q/k/v biases, a SwiGLU MLP, and an unembedding of its own or,
where the configuration ties them, the transposed embedding.  The cascade adds
an exit head after every segment but the last (its own RMSNorm gain, the
shared unembedding); a token answers at the first component whose softmax
confidence max(softmax(z)) reaches that component's threshold, the last
component always answers.

Decode through the cache is replayed over the whole sequence at once.  At
a decode position the serving engine computes segment ``s`` only where
some live row of the row's cohort has not exited before ``s`` (``depth``,
the cohort's deepest exit at that step).  Where it did not, that segment's
layers hold K/V backfilled from the hidden state after segment ``depth``:
each layer's own K/V projection (with its bias) and rotary embedding
applied to it, the layer itself not computed.  Later positions attend to
those backfilled entries, and so does this reference.

Nothing here imports the program.  Weights are regenerated from the seed
(``weights.py``) one layer at a time, in float32, and every matmul runs at
``Precision.HIGHEST``.  ``quant="fp8"`` is the control: the same
computation with every matrix rounded to float8_e4m3fn with one scale per
output channel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
FP8_MAX = 448.0
MATRICES = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/w_up",
            "mlp/w_gate", "mlp/w_down")


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _fp8(w, axis):
    """Round to float8_e4m3fn with one absmax scale per slice along
    ``axis`` (the reduction axis), back in float32."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rope(x, pos, theta):
    """x (R, L, heads, hd); pos (L,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs          # (L, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("H", "KV", "hd", "theta", "eps", "quant"))
def _layer(w, h, hb, computed, *, H, KV, hd, theta, eps, quant):
    """One decoder layer over all positions.  ``h`` is the hidden state
    entering the layer, ``hb`` the backfill source, ``computed`` (R, L)
    whether this layer's segment ran at that position."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if quant == "fp8":
        w = {k: (_fp8(v, 0) if k in MATRICES else v) for k, v in w.items()}
    R, L, _ = h.shape
    pos = jnp.arange(L)

    def kv(x):
        k = _mm(x, w["attn/wk"])
        v = _mm(x, w["attn/wv"])
        if "attn/bk" in w:
            k, v = k + w["attn/bk"], v + w["attn/bv"]
        return (_rope(k.reshape(R, L, KV, hd), pos, theta),
                v.reshape(R, L, KV, hd))

    x = _rms(h, w["attn/norm/w"], eps)
    q = _mm(x, w["attn/wq"])
    if "attn/bq" in w:
        q = q + w["attn/bq"]
    q = _rope(q.reshape(R, L, H, hd), pos, theta)
    k, v = kv(x)
    kb, vb = kv(_rms(hb, w["attn/norm/w"], eps))
    sel = computed[:, :, None, None]
    k, v = jnp.where(sel, k, kb), jnp.where(sel, v, vb)
    g = H // KV
    qg = q.reshape(R, L, KV, g, hd)
    s = jnp.einsum("rqkgh,rskh->rkgqs", qg, k, precision=HIGHEST) / np.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rkgqs,rskh->rqkgh", p, v, precision=HIGHEST)
    h = h + _mm(o.reshape(R, L, H * hd), w["attn/wo"])
    x = _rms(h, w["mlp/norm/w"], eps)
    up = _mm(x, w["mlp/w_up"])
    gate = _mm(x, w["mlp/w_gate"])
    return h + _mm(jax.nn.silu(gate) * up, w["mlp/w_down"])


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(norm_w, lm_head, h, queries, *, eps, quant):
    """Exit-head statistics at the output positions: max logit, its
    token, log-sum-exp, and the logits of ``queries`` (R, G, Q)."""
    lm = lm_head.astype(jnp.float32)
    if quant == "fp8":
        lm = _fp8(lm, 0)
    z = _mm(_rms(h, norm_w.astype(jnp.float32), eps), lm)       # (R, G, V)
    zmax = jnp.max(z, axis=-1)
    lse = zmax + jnp.log(jnp.sum(jnp.exp(z - zmax[..., None]), axis=-1))
    return (zmax, jnp.argmax(z, axis=-1).astype(jnp.int32), lse,
            jnp.take_along_axis(z, queries, axis=-1))


@partial(jax.jit, static_argnames=("quant",))
def _embed(table, tokens, *, quant):
    t = table.astype(jnp.float32)
    if quant == "fp8":
        t = _fp8(t, 1)
    return t[tokens]


def _select_hidden(ends, depth):
    """Per position, the hidden state after segment ``depth`` (the
    backfill source of every deeper segment)."""
    hb = ends[0]
    for m in range(1, len(ends)):
        hb = jnp.where((depth == m)[..., None], ends[m], hb)
    return hb


def layout(sizes: dict) -> dict:
    """The parameter leaves (per layer, and the rest) with their shapes,
    as the weights are made and as the program must hold them."""
    d, H, KV, hd = (sizes["d"], sizes["n_heads"], sizes["n_kv"],
                    sizes["head_dim"])
    ff, V = sizes["d_ff"], sizes["vocab"]
    layer = {"attn/wq": (d, H * hd), "attn/wk": (d, KV * hd),
             "attn/wv": (d, KV * hd), "attn/wo": (H * hd, d),
             "attn/norm/w": (d,), "mlp/w_up": (d, ff),
             "mlp/w_gate": (d, ff), "mlp/w_down": (ff, d),
             "mlp/norm/w": (d,)}
    if sizes["bias"]:
        layer.update({"attn/bq": (H * hd,), "attn/bk": (KV * hd,),
                      "attn/bv": (KV * hd,)})
    top = {"embed": (V, d), "final_norm/w": (d,)}
    if not sizes["tied"]:
        top["lm_head"] = (d, V)
    for m in range(sizes["n_components"] - 1):
        top[f"exits/{m}/norm/w"] = (d,)
    return {"layer": layer, "top": top, "n_layers": sizes["n_layers"],
            "boundaries": list(sizes["boundaries"])}


def head_stats(sizes: dict, seed: int, prompts, served, depth, queries,
               quant=None) -> dict:
    """Replay ``prompts`` (R, P) followed by ``served`` (R, G) through the
    cascade and return, per component m and served token j, the exit
    head's statistics at the position that produced token j:
    ``zmax``, ``argmax``, ``lse`` (n, R, G) and ``zq`` (n, R, G, Q), the
    logits of ``queries`` (R, G, Q).

    ``depth`` (R, G) is the deepest component the row's cohort computed
    at the step that produced token j; token 0 comes from the prefill,
    which computes every component (its depth entry is ignored)."""
    prompts = np.asarray(prompts, np.int32)
    served = np.asarray(served, np.int32)
    R, P = prompts.shape
    G = served.shape[1]
    n = sizes["n_components"]
    bounds = [0] + list(sizes["boundaries"]) + [sizes["n_layers"]]
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1)   # (R, L)
    L = tokens.shape[1]
    dpos = np.full((R, L), n - 1, np.int32)
    dpos[:, P:] = np.asarray(depth, np.int32)[:, 1:]
    dpos = jnp.asarray(dpos)
    out_pos = np.arange(P - 1, P - 1 + G)
    key = W.seed_key(seed)
    lay = layout(sizes)
    top = W.top_weights_fn(lay)(key)
    layer_w = W.layer_weights_fn(lay)
    kw = dict(H=sizes["n_heads"], KV=sizes["n_kv"], hd=sizes["head_dim"],
              theta=float(sizes["rope_theta"]), eps=float(sizes["eps"]),
              quant=quant)
    h = _embed(top["embed"], jnp.asarray(tokens), quant=quant)
    ends = []
    for s in range(n):
        computed = dpos >= s
        hb = _select_hidden(ends, jnp.minimum(dpos, s - 1)) if s else h
        for layer in range(bounds[s], bounds[s + 1]):
            h = _layer(layer_w(key, layer), h, hb, computed, **kw)
        ends.append(h)
    q = jnp.asarray(np.asarray(queries, np.int32))
    unembed = top["embed"].T if sizes["tied"] else top["lm_head"]
    stats = {"zmax": [], "argmax": [], "lse": [], "zq": []}
    for m in range(n):
        norm = (top[f"exits/{m}/norm/w"] if m < n - 1
                else top["final_norm/w"])
        zmax, am, lse, zq = _head(norm, unembed, ends[m][:, out_pos],
                                  q, eps=float(sizes["eps"]), quant=quant)
        for k, v in (("zmax", zmax), ("argmax", am), ("lse", lse),
                     ("zq", zq)):
            stats[k].append(np.asarray(v))
    return {k: np.stack(v) for k, v in stats.items()}
