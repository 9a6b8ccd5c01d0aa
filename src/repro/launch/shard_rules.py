"""Name-based sharding rules: parameter / optimizer / cache / batch /
decode-state pytrees -> PartitionSpec trees for the production mesh.

Tensor-parallel layout (megatron-style): column-parallel projections shard
their output dim over ``model``; row-parallel shard their input dim (XLA
inserts the all-reduce after the row-parallel matmul).  MoE experts shard the
expert dim when divisible (expert parallelism), else fall back to
tensor-parallel inside each expert.  Vocab-sharded embedding/unembedding when
the vocab divides the axis.  The batch dim shards over (pod, data); the
batch-1 long-context shape shards the KV-cache *sequence* dim over data
instead (sequence-parallel decode).

Every divisibility decision funnels through ``_axis_if`` so a config change
can never produce an invalid sharding — it degrades to replication.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import axis_size, batch_axes, divisible
from repro.utils import path_str

COLUMN = {"wq", "wk", "wv", "w_up", "w_gate", "up_proj", "w_in", "in_proj",
          "head", "lm_head", "enh_w1"}
ROW = {"wo", "w_down", "down_proj", "out_proj", "w_dn", "enh_w2"}


def _axis_if(dim: int, mesh, axis: str) -> Optional[str]:
    return axis if divisible(dim, axis_size(mesh, axis)) else None


def _spec(ndim: int, **placed) -> P:
    """Build a PartitionSpec placing axes at (possibly negative) dims."""
    entries = [None] * ndim
    for pos, ax in placed.items():
        if ax is not None:
            entries[int(pos)] = ax
    return P(*entries)


def _add_fsdp(spec: P, shape, mesh) -> P:
    """ZeRO/FSDP: additionally shard the first free divisible dim over
    'data'.  GSPMD materializes the per-layer all-gather; optimizer state
    (same spec) stays fully sharded — this is what lets 90B-param AdamW fit
    16 GiB/chip."""
    dsz = axis_size(mesh, "data")
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (dim, cur) in enumerate(zip(shape, entries)):
        if cur is None and divisible(dim, dsz):
            entries[i] = "data"
            return P(*entries)
    return spec


def param_spec(params, cfg, mesh, fsdp: bool = True, mode: str = "default"):
    """PartitionSpec tree matching a CascadeModel (or optimizer) pytree.

    mode="default": megatron TP over 'model' + ZeRO/FSDP 'data' placement on
    the first free divisible dim (training layout — optimizer state must be
    fully sharded; the per-layer weight all-gather amortizes over a large
    fwd+bwd).

    mode="serve2d": inference layout — weights shard over the COMBINED
    ('model','data') axes on their TP dim, so no weight ever needs gathering;
    the row-parallel output all-reduce moves to activations, which at decode
    are ~1 token and orders of magnitude smaller than the weights (§Perf H1).
    Decode-only: at prefill the (B,S,d) activations would replicate over
    'data' and dwarf the weight traffic.

    mode="serve1d": prefill inference layout — megatron TP over 'model',
    weights REPLICATED over 'data' (no FSDP): inference has no optimizer
    state, so when params/16 fit HBM the per-layer FSDP all-gather is pure
    waste (§Perf H3).
    """
    combined = ("model", "data")
    comb_sz = axis_size(mesh, combined)

    def rule(path, leaf):
        ndim = np.ndim(leaf)
        shape = np.shape(leaf)
        name = None
        for part in reversed(path):
            key = getattr(part, "key", None)
            if isinstance(key, str):
                name = key
                break
        if name is None or ndim == 0:
            return P()
        p = path_str(path)
        if name == "embed":
            spec = _spec(ndim, **{str(ndim - 2): _axis_if(shape[-2], mesh,
                                                          "model")})
        elif name == "pos_embed":
            spec = P()
        elif "moe" in p and name in ("w_up", "w_gate", "w_down"):
            E = shape[-3]
            ff_dim = ndim - 1 if name != "w_down" else ndim - 2
            if divisible(E, axis_size(mesh, "model")):
                if (mode == "serve2d"
                        and divisible(shape[ff_dim], axis_size(mesh, "data"))):
                    # expert-parallel over model + intra-expert ff over data:
                    # fully sharded, zero weight gathers (§Perf H1)
                    return _spec(ndim, **{str(ndim - 3): "model",
                                          str(ff_dim): "data"})
                spec = _spec(ndim, **{str(ndim - 3): "model"})
            else:
                if mode == "serve2d" and divisible(shape[ff_dim], comb_sz):
                    return _spec(ndim, **{str(ff_dim): combined})
                spec = _spec(ndim, **{str(ff_dim): _axis_if(
                    shape[ff_dim], mesh, "model")})
        elif name in COLUMN and ndim >= 2:
            if mode == "serve2d" and divisible(shape[-1], comb_sz):
                return _spec(ndim, **{str(ndim - 1): combined})
            spec = _spec(ndim, **{str(ndim - 1): _axis_if(shape[-1], mesh,
                                                          "model")})
        elif name in ROW and ndim >= 2:
            if mode == "serve2d" and divisible(shape[-2], comb_sz):
                return _spec(ndim, **{str(ndim - 2): combined})
            spec = _spec(ndim, **{str(ndim - 2): _axis_if(shape[-2], mesh,
                                                          "model")})
        else:
            spec = P()
        # serve2d never places 'data' on a dim it can't fully own — a
        # data-sharded contraction dim is exactly what made GSPMD emit the
        # giant weight all-gathers the mode exists to remove.
        if fsdp and mode not in ("serve2d", "serve1d") and ndim >= 2:
            spec = _add_fsdp(spec, shape, mesh)
        return spec
    return jax.tree_util.tree_map_with_path(rule, params)


def cache_spec(cache, cfg, mesh, batch: int):
    """KV/state cache sharding.  batch > 1: shard batch over (pod,data);
    batch == 1 (long-context): shard the KV sequence dim over (pod,data)
    — sequence-parallel decode — and replicate recurrent states.

    Paged layout (detected from the per-slot ``(B, W)`` kpos ring): the
    shared k/v block stores are ``(L, num_blocks, bs, kv, hd)`` with NO
    batch dim — blocks are fungible across slots — so the physical block
    dim shards over (pod, data) instead (block-parallel store; GSPMD
    routes each table-indexed gather to the owning shard), and the kpos
    ring batch-shards like any per-slot leaf."""
    dp = batch_axes(mesh)
    dp_sz = axis_size(mesh, dp)
    batch_ok = divisible(batch, dp_sz)
    dp_ax = dp if batch_ok else None
    paged = (isinstance(cache, dict)
             and np.ndim(cache.get("kpos")) == 2)

    def rule(path, leaf):
        ndim = np.ndim(leaf)
        shape = np.shape(leaf)
        name = None
        for part in reversed(path):
            key = getattr(part, "key", None)
            if isinstance(key, str):
                name = key
                break
        if name == "kpos":
            if paged and ndim == 2:                # per-slot (B, W) ring
                return _spec(ndim, **{"0": dp_ax})
            return P()
        if ndim <= 1:
            return P()
        if name in ("k", "v") and ndim == 5:
            if paged:                              # (L, NB, bs, kv, hd)
                return _spec(ndim, **{"1": dp if divisible(shape[1], dp_sz)
                                      else None})
            if batch_ok:                           # (L, B, W, kv, hd)
                return _spec(ndim, **{"1": dp_ax})
            # sequence-parallel: shard the slot dim
            return _spec(ndim, **{"2": dp if divisible(shape[2], dp_sz)
                                  else None})
        if name == "conv" and ndim == 4:           # (L, B, W-1, ch)
            return _spec(ndim, **{"1": dp_ax})
        if name == "state" and ndim == 5:          # ssm (L, B, h, p, n)
            return _spec(ndim, **{"1": dp_ax})
        if name == "C" and ndim == 5:              # mlstm (L, B, h, p, p)
            return _spec(ndim, **{"1": dp_ax})
        if name == "n" and ndim == 4:              # mlstm (L, B, h, p)
            return _spec(ndim, **{"1": dp_ax})
        if name == "m" and ndim == 3:              # mlstm (L, B, h)
            return _spec(ndim, **{"1": dp_ax})
        if name in ("c", "n", "m", "h") and ndim == 3:  # slstm (L, B, d)
            return _spec(ndim, **{"1": dp_ax})
        return P()
    return jax.tree_util.tree_map_with_path(rule, cache)


def decode_state_spec(state, cfg, mesh, batch: int):
    """Sharding for the serve step's carried DecodeState pytree.

    Per-sequence leaves (``active``, ``ema_conf``: (B,), and the stateful
    measure carry ``policy``: (n_components, B)) shard their batch dim over
    (pod, data) exactly like the token batch; the scalar cursor ``t`` and
    the per-segment counters ``segments_run`` replicate.  The autotune
    riders — the live ``thresholds`` vector and every batch-free
    :class:`~repro.autotune.telemetry.ExitTelemetry` counter (histograms,
    exit/MAC/step counters) — replicate too: they are global accumulators,
    and GSPMD folds the batch-sharded scatter-adds into them with the
    appropriate reductions.  Divisibility degrades to replication,
    mirroring every other rule here.
    """
    dp = batch_axes(mesh)
    dp_ax = dp if divisible(batch, axis_size(mesh, dp)) else None

    def rule(path, leaf):
        ndim = np.ndim(leaf)
        name = None
        for part in reversed(path):
            key = getattr(part, "name", None) or getattr(part, "key", None)
            if isinstance(key, str):
                name = key
                break
        if ndim == 0 or name in ("t", "segments_run"):
            return P()
        if name in ("active", "ema_conf"):
            return _spec(ndim, **{"0": dp_ax})
        if name == "policy":          # (n_components, B, ...)
            return _spec(ndim, **{"1": dp_ax})
        if name == "block_tables":    # paged cache (n_components, B, nblk)
            return _spec(ndim, **{"1": dp_ax})
        # "thresholds" and the telemetry counters fall through: replicated
        return P()
    return jax.tree_util.tree_map_with_path(rule, state)


def decode_loop_in_specs(params, cache, state, cfg, mesh, batch: int):
    """Input PartitionSpecs for ``launch.steps.make_decode_loop_step``'s
    ``(params, token, cache, state, remaining, extra)`` signature — the whole
    while_loop carry sharded by the existing rules: weights via
    :func:`param_spec` (serve1d inference layout), the KV/state cache via
    :func:`cache_spec`, the carried DecodeState via
    :func:`decode_state_spec`, and the (B, 1) token / (B,) remaining-budget
    vectors batch-sharded like any token batch.  ``extra`` is left
    unconstrained (None)."""
    return (param_spec(params, cfg, mesh, mode="serve1d"),
            batch_spec(cfg, mesh, batch, 2),
            cache_spec(cache, cfg, mesh, batch),
            decode_state_spec(state, cfg, mesh, batch),
            batch_spec(cfg, mesh, batch, 1),
            None)


def place_params(params, cfg, mesh):
    """Put the weights where :func:`decode_loop_in_specs` wants them
    (serve1d), once: the jitted loop then takes them as they are instead
    of resharding them on every call.  A no-op for weights already there."""
    return jax.device_put(params, to_shardings(
        mesh, param_spec(params, cfg, mesh, mode="serve1d")))


def constrain_carry(cache, state, cfg, mesh, batch: int):
    """Inside a jitted step: pin a cache and DecodeState to the layout
    :func:`decode_loop_in_specs` gives the device loop, so a prefill's
    outputs already sit where the next decode chunk reads them."""
    cache = jax.lax.with_sharding_constraint(
        cache, to_shardings(mesh, cache_spec(cache, cfg, mesh, batch)))
    state = jax.lax.with_sharding_constraint(
        state, to_shardings(mesh, decode_state_spec(state, cfg, mesh,
                                                    batch)))
    return cache, state


def batch_spec(cfg, mesh, batch: int, ndim: int) -> P:
    dp = batch_axes(mesh)
    if divisible(batch, axis_size(mesh, dp)):
        return _spec(ndim, **{"0": dp})
    return P()


def to_shardings(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
