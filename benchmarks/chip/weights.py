"""Seeded random weights, made by the benchmark and handed to the program.

Every value is a function of (seed, leaf name, layer index) alone, rounded
to bfloat16, so the program's copy (one jitted call on the chip, in the
program's own pytree layout and leaf dtypes) and the reference's copy (one
layer at a time, in float32) hold the same numbers without either reading
the other's arrays.

Distributions: a norm gain is 1 + 0.1 N(0, 1), a bias 0.1 N(0, 1), the
embedding 0.02 N(0, 1), every other matrix N(0, 1) / sqrt(fan_in).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (the high word is folded in:
    ``jax.random.key`` alone keeps only the low 32 bits)."""
    seed = int(seed) % (1 << 64)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def _leaf_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _kind(name: str) -> str:
    if name.endswith("norm/w"):
        return "gain"
    if name.rsplit("/", 1)[-1] in ("bq", "bk", "bv"):
        return "bias"
    if name == "embed":
        return "embed"
    return "matrix"


def leaf_value(key, name: str, layer, shape) -> jnp.ndarray:
    """One leaf's values (bf16) at one layer index (a traced int is fine)."""
    k = jax.random.fold_in(jax.random.fold_in(key, _leaf_id(name)), layer)
    z = jax.random.normal(k, tuple(shape), jnp.float32)
    kind = _kind(name)
    if kind == "gain":
        v = 1.0 + 0.1 * z
    elif kind == "bias":
        v = 0.1 * z
    elif kind == "embed":
        v = 0.02 * z
    else:
        v = z * (1.0 / math.sqrt(shape[-2]))
    return v.astype(jnp.bfloat16)


def _path_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def _leaf_plan(template, layout):
    """(name, first layer, layers, ShapeDtypeStruct) per template leaf.
    Stacked layer leaves ``segments/<s>/<stage>/<leaf>`` hold the layers
    of segment s, their i-th slice layer ``start(s) + i``; every other
    leaf is one tensor (first layer None)."""
    b = [0] + list(layout["boundaries"]) + [layout["n_layers"]]
    plan = []
    for path, sds in jax.tree_util.tree_flatten_with_path(template)[0]:
        full = _path_name(path)
        parts = full.split("/")
        if parts[0] == "segments":
            if int(parts[2]) != 0:
                raise ValueError(f"{full}: the weight maker knows segments "
                                 "of one stage only")
            si = int(parts[1])
            plan.append(("/".join(parts[3:]), b[si], b[si + 1] - b[si], sds))
        else:
            plan.append((full, None, None, sds))
    return plan


def params_builder(template, layout):
    """``build(key) -> params`` in the layout and dtypes of ``template``
    (the program's ``jax.eval_shape(model.init, ...)``)."""
    treedef = jax.tree_util.tree_structure(template)
    plan = _leaf_plan(template, layout)

    def build(key):
        out = []
        for name, start, n, sds in plan:
            if start is None:
                v = leaf_value(key, name, 0, sds.shape)
            else:
                v = jax.vmap(lambda l, _n=name, _s=sds.shape[1:]:
                             leaf_value(key, _n, l, _s))(
                    jnp.arange(start, start + n))
            out.append(v.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def check_template(template, layout: dict) -> None:
    """Raise unless the program's parameter template has exactly the
    leaves and shapes of ``layout`` (a reference's ``layout(sizes)``:
    ``layer`` and ``top`` leaf shapes, segment ``boundaries``,
    ``n_layers``), which the reference and the work arithmetic assume."""
    seen = set()
    for name, start, n, sds in _leaf_plan(template, layout):
        if start is None:
            shape = tuple(layout["top"].get(name, (-1,)))
        else:
            shape = (n,) + tuple(layout["layer"].get(name, (-1,)))
        seen.add(name)
        if tuple(sds.shape) != shape:
            raise ValueError(f"program leaf {name} has shape "
                             f"{tuple(sds.shape)}, the benchmark expects "
                             f"{shape}")
    missing = (set(layout["layer"]) | set(layout["top"])) - seen
    if missing:
        raise ValueError(f"program has no leaves {sorted(missing)}")


def layer_weights_fn(layout: dict):
    """Jitted ``(key, layer) -> {name: bf16 array}`` for one layer."""
    shapes = layout["layer"]

    @jax.jit
    def one(key, layer):
        return {n: leaf_value(key, n, layer, s) for n, s in shapes.items()}

    return one


def top_weights_fn(layout: dict):
    """Jitted ``key -> {name: bf16 array}`` for the non-layer leaves."""
    shapes = layout["top"]

    @jax.jit
    def top(key):
        return {n: leaf_value(key, n, 0, s) for n, s in shapes.items()}

    return top
