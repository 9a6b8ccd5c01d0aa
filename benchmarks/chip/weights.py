"""Seeded random weights, made by the benchmark and handed to the program.

Every value is a function of (seed, leaf name, layer index) alone, rounded
to bfloat16, so the program's copy (one jitted call on the chip, in the
program's own pytree layout and leaf dtypes) and the reference's copy (one
layer at a time, in float32) hold the same numbers without either reading
the other's arrays.

A reference's ``layout(sizes)`` says which leaves there are, with their
shapes: ``top`` (the leaves outside the layer stacks) and either

* ``layer``, ``n_layers`` and ``boundaries``: one kind of layer, one run of
  it per segment; or
* ``kinds`` ({kind: {leaf: shape}}) and ``segments``, one list of
  ``[kind, layers]`` runs per segment in the program's stage order.

Distributions: by its name, a norm gain (``.../norm/w``) is
1 + 0.1 N(0, 1), a bias (``bq``, ``bk``, ``bv``) 0.1 N(0, 1), the embedding
0.02 N(0, 1), every other matrix N(0, 1) / sqrt(fan_in), its fan-in the
next-to-last axis.  A layout's ``dists`` ({leaf: [rule, *arguments]})
gives a leaf a rule of ``DISTS`` instead.
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


# rule -> the number of its arguments.  ``z`` is N(0, 1):
#   gain 1 + 0.1 z; bias 0.1 z; embed 0.02 z;
#   matrix: z / sqrt(shape[-2]) (a depthwise conv's (taps, channels) too);
#   log_uniform lo hi: log of U(lo, hi) (Mamba's A_log);
#   inv_softplus_log_uniform lo hi: log(expm1(dt)), log dt uniform over
#     [log lo, log hi] (Mamba's dt_bias);
#   constant c: c everywhere (Mamba's D).
DISTS = {"gain": 0, "bias": 0, "embed": 0, "matrix": 0,
         "log_uniform": 2, "inv_softplus_log_uniform": 2, "constant": 1}


def _rule(name: str, shape, spec=None):
    """(rule, arguments) of one leaf: ``spec`` as a layout declares it
    (a rule's name, or a list of it and its arguments), else the rule its
    name gives."""
    if spec is None:
        if name.endswith("norm/w"):
            return "gain", ()
        if name.rsplit("/", 1)[-1] in ("bq", "bk", "bv"):
            return "bias", ()
        if name == "embed":
            return "embed", ()
        spec = "matrix"
    rule, *args = [spec] if isinstance(spec, str) else spec
    if rule not in DISTS:
        raise ValueError(f"leaf {name}: unknown distribution {rule!r}; "
                         f"known: {sorted(DISTS)}")
    if len(args) != DISTS[rule]:
        raise ValueError(f"leaf {name}: {rule} takes {DISTS[rule]} "
                         f"arguments, the layout gives {args}")
    if rule == "matrix" and len(shape) < 2:
        raise ValueError(f"leaf {name} of shape {tuple(shape)}: no rule "
                         "covers it (a matrix has two axes or more); "
                         "declare one in the layout's dists")
    return rule, tuple(args)


def leaf_value(key, name: str, layer, shape, spec=None) -> jnp.ndarray:
    """One leaf's values (bf16) at one layer index (a traced int is fine),
    by the rule ``spec`` declares or its name gives."""
    rule, args = _rule(name, shape, spec)
    k = jax.random.fold_in(jax.random.fold_in(key, _leaf_id(name)), layer)
    shape = tuple(shape)
    if rule == "constant":
        v = jnp.full(shape, args[0], jnp.float32)
    elif rule == "log_uniform":
        v = jnp.log(jax.random.uniform(k, shape, jnp.float32, *args))
    elif rule == "inv_softplus_log_uniform":
        lo, hi = math.log(args[0]), math.log(args[1])
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
        v = jnp.log(jnp.expm1(dt))
    else:
        z = jax.random.normal(k, shape, jnp.float32)
        if rule == "gain":
            v = 1.0 + 0.1 * z
        elif rule == "bias":
            v = 0.1 * z
        elif rule == "embed":
            v = 0.02 * z
        else:
            v = z * (1.0 / math.sqrt(shape[-2]))
    return v.astype(jnp.bfloat16)


def _path_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


LAYER = "layer"         # the one kind of a layout given by ``layer``


def _stages(layout: dict):
    """({kind: {leaf: shape}}, per segment its [(kind, layers)] runs) of a
    layout in either form."""
    if "kinds" in layout:
        return layout["kinds"], [[(k, n) for k, n in runs]
                                 for runs in layout["segments"]]
    b = [0] + list(layout["boundaries"]) + [layout["n_layers"]]
    return ({LAYER: layout["layer"]},
            [[(LAYER, b[i + 1] - b[i])] for i in range(len(b) - 1)])


def _leaf_plan(template, layout):
    """(name, first layer, layers, ShapeDtypeStruct) per template leaf.
    Stacked layer leaves ``segments/<s>/<p>/<leaf>`` hold the layers of
    run p of segment s, their i-th slice layer ``start(s) + offset(p) +
    i``; every other leaf is one tensor (first layer None)."""
    _, segs = _stages(layout)
    plan = []
    for path, sds in jax.tree_util.tree_flatten_with_path(template)[0]:
        full = _path_name(path)
        parts = full.split("/")
        if parts[0] != "segments":
            plan.append((full, None, None, sds))
            continue
        si, p = int(parts[1]), int(parts[2])
        if si >= len(segs) or p >= len(segs[si]):
            raise ValueError(f"program leaf {full}: the layout has no run "
                             f"{p} in segment {si} (its runs: {segs})")
        start = sum(n for runs in segs[:si] for _, n in runs) + \
            sum(n for _, n in segs[si][:p])
        plan.append(("/".join(parts[3:]), start, segs[si][p][1], sds))
    return plan


def params_builder(template, layout):
    """``build(key) -> params`` in the layout and dtypes of ``template``
    (the program's ``jax.eval_shape(model.init, ...)``)."""
    treedef = jax.tree_util.tree_structure(template)
    plan = _leaf_plan(template, layout)
    dists = layout.get("dists", {})

    def build(key):
        out = []
        for name, start, n, sds in plan:
            spec = dists.get(name)
            if start is None:
                v = leaf_value(key, name, 0, sds.shape, spec)
            else:
                v = jax.vmap(lambda l, _n=name, _s=sds.shape[1:], _d=spec:
                             leaf_value(key, _n, l, _s, _d))(
                    jnp.arange(start, start + n))
            out.append(v.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def _leaf_names(tree) -> dict:
    return {_path_name(path): tuple(sds.shape) for path, sds in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def check_template(template, layout: dict) -> None:
    """Raise unless the program's parameter template has exactly the
    stages, leaves and shapes of ``layout`` (a reference's
    ``layout(sizes)``), which the reference and the work arithmetic
    assume, and a distribution for every leaf.  The error names the first
    mismatch."""
    kinds, segs = _stages(layout)
    stages = template.get("segments", [])
    if len(stages) != len(segs):
        raise ValueError(f"the program has {len(stages)} segments, the "
                         f"layout {len(segs)}")
    for si, (got, runs) in enumerate(zip(stages, segs)):
        if len(got) != len(runs):
            raise ValueError(f"segment {si}: the program has {len(got)} "
                             f"stages, the layout {len(runs)} runs {runs}")
        for p, (kind, n) in enumerate(runs):
            if kind not in kinds:
                raise ValueError(f"segment {si} run {p}: the layout has no "
                                 f"kind {kind!r}; known: {sorted(kinds)}")
            have, want = _leaf_names(got[p]), kinds[kind]
            if set(have) != set(want):
                raise ValueError(
                    f"segment {si} stage {p} is not of kind {kind!r}: the "
                    f"program's stage lacks {sorted(set(want) - set(have))} "
                    f"and has {sorted(set(have) - set(want))} besides")
            for name, shape in have.items():
                if shape != (n,) + tuple(want[name]):
                    raise ValueError(
                        f"program leaf segments/{si}/{p}/{name} has shape "
                        f"{shape}, the benchmark expects {n} layers of "
                        f"kind {kind!r}: {(n,) + tuple(want[name])}")
    top = {name: shape for name, shape in _leaf_names(template).items()
           if not name.startswith("segments/")}
    for name, shape in top.items():
        want = tuple(layout["top"].get(name, (-1,)))
        if shape != want:
            raise ValueError(f"program leaf {name} has shape {shape}, the "
                             f"benchmark expects {want}")
    missing = set(layout["top"]) - set(top)
    if missing:
        raise ValueError(f"program has no leaves {sorted(missing)}")
    dists = layout.get("dists", {})
    groups = [layout["top"]] + list(kinds.values())
    unknown = set(dists).difference(*groups)
    if unknown:
        raise ValueError(f"the layout's dists name no leaf: {sorted(unknown)}")
    for shapes in groups:
        for name, shape in shapes.items():
            _rule(name, shape, dists.get(name))


def layer_weights_fn(layout: dict, kind: str = None):
    """Jitted ``(key, layer) -> {name: bf16 array}`` for one layer of
    ``kind`` (which a layout of one kind need not name)."""
    kinds, _ = _stages(layout)
    if kind is None:
        if len(kinds) != 1:
            raise ValueError(f"the layout has kinds {sorted(kinds)}: name "
                             "the layer's")
        (kind,) = kinds
    shapes, dists = kinds[kind], layout.get("dists", {})

    @jax.jit
    def one(key, layer):
        return {n: leaf_value(key, n, layer, s, dists.get(n))
                for n, s in shapes.items()}

    return one


def top_weights_fn(layout: dict):
    """Jitted ``key -> {name: bf16 array}`` for the non-layer leaves."""
    shapes, dists = layout["top"], layout.get("dists", {})

    @jax.jit
    def top(key):
        return {n: leaf_value(key, n, 0, s, dists.get(n))
                for n, s in shapes.items()}

    return top
