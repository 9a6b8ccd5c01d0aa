"""The configuration layer: program builders named by a configuration
file, layouts of several layer kinds, declared weight distributions.

The dense configurations are held to what the commit before the program
builders and layer kinds gave (``data/parent_config_layer.json``, whose
``how`` says how it was recorded); the hybrid and MoE layouts are written
here against the program's reduced zamba2-1.2b and mixtral-8x7b."""
import dataclasses
import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_tiny
import harness
import weights
from repro.configs import get_config, reduced
from repro.models.model import build_model
from repro.models.ssm import dims

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "parent_config_layer.json")) as f:
    PARENT = json.load(f)
TRAFFIC = ("batch-allexit", "batch-mixed", "batch-noexit")


def _chip_cell(config: str, traffic: str) -> harness.Cell:
    return harness.Cell(
        name=f"{config}.{traffic}", chips=1,
        config=harness._json(os.path.join(harness.HERE, "configs",
                                          config + ".json")),
        traffic=harness._json(os.path.join(harness.HERE, "traffic",
                                           traffic + ".json")),
        limits={}, benchmark={})


def _parent_config(kwargs: dict):
    """The ``ModelConfig`` the parent's ``program_config`` built from the
    keyword arguments it passed; fields it left to their defaults take
    today's defaults."""
    from repro.configs.base import (CascadeConfig, ModelConfig, ObsConfig,
                                    PagedCacheConfig)
    nested = {"cascade": CascadeConfig, "paged_cache": PagedCacheConfig,
              "obs": ObsConfig}

    def args(d):
        return {k: tuple(v) if isinstance(v, list) else v
                for k, v in d.items()}

    return ModelConfig(**{k: nested[k](**args(v)) if k in nested else v
                          for k, v in args(kwargs).items()})


def _template(model_cfg):
    return jax.eval_shape(build_model(model_cfg).init, jax.random.PRNGKey(0))


@pytest.mark.parametrize("case", [f"{c}/{t}" for c in ("qwen2.5-3b",
                                                         "yi-9b-24l")
                                  for t in TRAFFIC]
                         + [f"tiny/{m}" for m in ("allexit", "mixed",
                                                  "noexit")])
def test_dense_gqa_model_config_matches_parent(case):
    config, mix = case.split("/")
    if config == "tiny":
        cell = chip_tiny.cell(mix)
        ths = chip_tiny.MIXES[mix]["thresholds"]
    else:
        cell = _chip_cell(config, mix)
        ths = cell.traffic["thresholds"]
    prog = harness.load_module(os.path.join(harness.HERE, "programs",
                                            "dense_gqa.py"), "dense_gqa")
    cfg = prog.model_config(cell.config, ths,
                            int(cell.traffic.get("n_cohorts", 1)))
    assert cfg == _parent_config(PARENT["model_config_kwargs"][case])
    assert harness.program_config(cell, ths) == cfg


def test_program_key_selects_the_builder():
    cell = chip_tiny.cell("mixed")
    named = chip_tiny.cell("mixed", program="dense_gqa")
    assert "program" not in cell.config
    assert harness.program_config(named, [0.1, 0.1]) == \
        harness.program_config(cell, [0.1, 0.1])
    with pytest.raises(harness.SetupError, match="no_such_program"):
        harness.program_config(chip_tiny.cell("mixed",
                                              program="no_such_program"),
                               [0.1, 0.1])
    with open(harness.__file__) as f:
        assert "family=" not in f.read()


def test_tiny_params_bit_identical_to_parent():
    cell = chip_tiny.cell("noexit")
    ref, _, sizes = harness.architecture(cell)
    template = _template(harness.program_config(cell, [0.0, 0.0]))
    build = jax.jit(weights.params_builder(template, ref.layout(sizes)))
    params = build(weights.seed_key(PARENT["tiny_params"]["seed"]))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(weights._path_name(path).encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARENT["tiny_params"]["sha256"]


@pytest.mark.parametrize("config", ["qwen2.5-3b", "yi-9b-24l"])
def test_leaf_plans_of_chip_configs_match_parent(config):
    cell = _chip_cell(config, "batch-noexit")
    ref, _, sizes = harness.architecture(cell)
    template = _template(harness.program_config(cell, [0.0, 0.0]))
    layout = ref.layout(sizes)
    weights.check_template(template, layout)
    plan = [[n, s, k, list(sds.shape), str(sds.dtype)]
            for n, s, k, sds in weights._leaf_plan(template, layout)]
    assert plan == PARENT["leaf_plans"][config]


# ---------------------------------------------------------------------------
# layouts of several kinds, written against the program's own models
# ---------------------------------------------------------------------------

def _attn(cfg, prefix):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return {f"{prefix}/wq": (d, cfg.n_heads * hd),
            f"{prefix}/wk": (d, cfg.n_kv_heads * hd),
            f"{prefix}/wv": (d, cfg.n_kv_heads * hd),
            f"{prefix}/wo": (cfg.n_heads * hd, d), f"{prefix}/norm/w": (d,)}


def _top(cfg):
    top = {"embed": (cfg.vocab_size, cfg.d_model),
           "final_norm/w": (cfg.d_model,),
           "lm_head": (cfg.d_model, cfg.vocab_size)}
    for m in range(cfg.cascade.n_components - 1):
        top[f"exits/{m}/norm/w"] = (cfg.d_model,)
    return top


MAMBA_DISTS = {"ssm/A_log": ["log_uniform", 1.0, 16.0],
               "ssm/dt_bias": ["inv_softplus_log_uniform", 1e-3, 1e-1],
               "ssm/D": ["constant", 1.0],
               "ssm/conv_b": "bias",
               "ssm/gate_norm_w": "gain"}


def zamba2():
    """The program's zamba2-1.2b at 6 layers: a shared attention block
    every 3 layers, one exit after layer 3, so each segment runs
    [attn_shared 1, mamba 2]."""
    base = get_config("zamba2-1.2b")
    cfg = reduced(base, n_layers=6, shared_attn_every=3,
                  cascade=dataclasses.replace(
                      base.cascade, n_components=2, exit_boundaries=(3,),
                      thresholds=(0.5, 0.0)))
    d, r, hd = cfg.d_model, 16, cfg.resolved_head_dim
    d_inner, n_heads, conv_ch = dims(cfg)
    lora = {}
    for x, width in (("q", cfg.n_heads), ("k", cfg.n_kv_heads),
                     ("v", cfg.n_kv_heads)):
        lora[f"lora_{x}_a"] = (d, r)
        lora[f"lora_{x}_b"] = (r, width * hd)
    mamba = {"ssm/in_proj": (d, 2 * d_inner + 2 * cfg.ssm_state + n_heads),
             "ssm/conv_w": (cfg.ssm_conv, conv_ch), "ssm/conv_b": (conv_ch,),
             "ssm/A_log": (n_heads,), "ssm/D": (n_heads,),
             "ssm/dt_bias": (n_heads,), "ssm/out_proj": (d_inner, d),
             "ssm/norm/w": (d,), "ssm/gate_norm_w": (d_inner,)}
    top = _top(cfg)
    top.update(_attn(cfg, "shared/attn"))
    top.update({"shared/mlp/w_up": (d, cfg.d_ff),
                "shared/mlp/w_gate": (d, cfg.d_ff),
                "shared/mlp/w_down": (cfg.d_ff, d),
                "shared/mlp/norm/w": (d,)})
    layout = {"kinds": {"attn_shared": lora, "mamba": mamba},
              "segments": [[["attn_shared", 1], ["mamba", 2]]] * 2,
              "top": top, "dists": dict(MAMBA_DISTS)}
    return cfg, layout


def mixtral():
    """The program's reduced mixtral-8x7b: 2 layers of attention and 4
    experts, one exit after layer 1."""
    cfg = reduced(get_config("mixtral-8x7b"))
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    moe = _attn(cfg, "attn")
    moe.update({"moe/router": (d, E), "moe/w_gate": (E, d, ff),
                "moe/w_up": (E, d, ff), "moe/w_down": (E, ff, d),
                "moe/norm/w": (d,)})
    layout = {"kinds": {"moe": moe}, "segments": [[["moe", 1]]] * 2,
              "top": _top(cfg)}
    return cfg, layout


MODELS = {"zamba2": zamba2, "mixtral": mixtral}


@pytest.mark.parametrize("model", sorted(MODELS))
def test_layer_kinds_build_and_check(model):
    cfg, layout = MODELS[model]()
    template = _template(cfg)
    weights.check_template(template, layout)
    key = weights.seed_key(2 ** 40 + 17)
    params = jax.jit(weights.params_builder(template, layout))(key)
    top = weights.top_weights_fn(layout)(key)
    per_kind = {k: weights.layer_weights_fn(layout, k)
                for k in layout["kinds"]}
    n_checked = 0
    start = 0
    for si, runs in enumerate(layout["segments"]):
        for p, (kind, n) in enumerate(runs):
            stage = params["segments"][si][p]
            for i in range(n):
                want = per_kind[kind](key, start + i)
                for name, v in want.items():
                    got = stage
                    for part in name.split("/"):
                        got = got[part]
                    np.testing.assert_array_equal(
                        np.asarray(got[i], np.float32),
                        np.asarray(v, np.float32), err_msg=name)
                    n_checked += 1
            start += n
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = weights._path_name(path)
        if not name.startswith("segments/"):
            np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                          np.asarray(top[name], np.float32),
                                          err_msg=name)
            n_checked += 1
    assert n_checked == len(layout["top"]) + sum(
        len(layout["kinds"][k]) * n for runs in layout["segments"]
        for k, n in runs)


def test_one_kind_layout_draws_as_the_layer_form():
    """A layout of one kind given as ``kinds`` / ``segments`` draws what
    the ``layer`` / ``boundaries`` form of the same layout draws."""
    cell = chip_tiny.cell("noexit")
    ref, _, sizes = harness.architecture(cell)
    old = ref.layout(sizes)
    new = {"kinds": {"dense": old["layer"]}, "top": old["top"],
           "segments": [[["dense", 2]]] * 3}
    template = _template(harness.program_config(cell, [0.0, 0.0]))
    weights.check_template(template, new)
    key = weights.seed_key(5)
    a = jax.jit(weights.params_builder(template, old))(key)
    b = jax.jit(weights.params_builder(template, new))(key)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
    one = weights.layer_weights_fn(old)(key, 3)
    for name, v in weights.layer_weights_fn(new, "dense")(key, 3).items():
        np.testing.assert_array_equal(np.asarray(v, np.float32),
                                      np.asarray(one[name], np.float32))


SHAPE = (64, 256)


def _draw(spec):
    v = weights.leaf_value(weights.seed_key(2 ** 33 + 1), "leaf", 7, SHAPE,
                           spec)
    assert v.shape == SHAPE and v.dtype == jnp.bfloat16
    v = np.asarray(v, np.float64)
    assert np.isfinite(v).all()
    return v


@pytest.mark.parametrize("spec,centre,scale", [
    ("gain", 1.0, 0.1), ("bias", 0.0, 0.1), ("embed", 0.0, 0.02),
    ("matrix", 0.0, 1 / 8), (["matrix"], 0.0, 1 / 8)])
def test_declared_normal_distribution_scale(spec, centre, scale):
    v = _draw(spec)
    assert v.mean() == pytest.approx(centre, abs=0.05 * scale)
    assert v.std() == pytest.approx(scale, rel=0.05)


@pytest.mark.parametrize("shape,scale", [((4, 4096), 0.5),
                                         ((4, 64, 256), 1 / 8)])
def test_matrix_fan_in_is_the_next_to_last_axis(shape, scale):
    """A depthwise conv's (taps, channels) draws at fan-in taps, an expert
    stack's (E, d, ff) at fan-in d."""
    v = np.asarray(weights.leaf_value(weights.seed_key(2 ** 35 + 3),
                                      "ssm/conv_w", 2, shape), np.float64)
    assert v.std() == pytest.approx(scale, rel=0.05)


@pytest.mark.parametrize("spec,shape,match", [
    (["matrix", 1], (4, 8), "matrix takes 0 arguments"),
    ("matrix", (8,), r"leaf x of shape \(8,\): no rule covers it"),
    (["constant"], (8,), "constant takes 1 arguments"),
    (["log_uniform", 1.0], (8,), "log_uniform takes 2 arguments")])
def test_bad_declaration_is_named(spec, shape, match):
    with pytest.raises(ValueError, match=match):
        weights.leaf_value(weights.seed_key(0), "x", 0, shape, spec)


def test_rules_checked_per_kind():
    """A leaf name in two kinds at different shapes is held to its rule at
    each: the 1-D ``x`` of kind ``b`` is refused though kind ``a``'s is a
    matrix."""
    sds = jax.ShapeDtypeStruct
    template = {"embed": sds((16, 8), jnp.bfloat16),
                "segments": [[{"x": sds((1, 8, 8), jnp.bfloat16)},
                              {"x": sds((2, 8), jnp.bfloat16)}]]}
    layout = {"kinds": {"b": {"x": (8,)}, "a": {"x": (8, 8)}},
              "segments": [[["a", 1], ["b", 2]]], "top": {"embed": (16, 8)}}
    with pytest.raises(ValueError, match=r"leaf x of shape \(8,\)"):
        weights.check_template(template, layout)
    layout["kinds"]["b"] = {"x": (8, 8)}
    template["segments"][0][1]["x"] = sds((2, 8, 8), jnp.bfloat16)
    weights.check_template(template, layout)


# bfloat16 keeps 8 significant bits: a value drawn in range may pass its
# end by half a unit in the last place, up to 2 ** -8 of itself
BF16 = 2.0 ** -8


@pytest.mark.parametrize("spec", [
    ["log_uniform", 1.0, 16.0], ["inv_softplus_log_uniform", 1e-3, 1e-1],
    ["constant", 1.0], ["constant", -0.5]])
def test_declared_distribution_lands_in_its_range(spec):
    rule, *args = spec
    v = _draw(spec)
    if rule == "constant":
        assert (v == args[0]).all()
        return
    lo, hi = args
    # softplus undoes the inverse softplus; v is rounded to bfloat16, so x
    # moves by up to |v| 2^-8 of itself
    x = np.exp(v) if rule == "log_uniform" else np.log1p(np.exp(v))
    slack = BF16 * np.abs(v).max()
    assert lo * math.exp(-slack) <= x.min() and x.max() <= hi * math.exp(slack)
    assert x.min() <= lo * math.exp(slack + 0.01) and \
        x.max() >= hi * math.exp(-slack - 0.01)
    # the draws fill the range: uniform in log x from lo to hi, or in x
    y = np.log(x) if rule != "log_uniform" else x
    a, b = (math.log(lo), math.log(hi)) if rule != "log_uniform" else (lo, hi)
    assert y.mean() == pytest.approx((a + b) / 2, abs=0.02 * (b - a))
    assert y.std() == pytest.approx((b - a) / math.sqrt(12), rel=0.05)


@pytest.mark.parametrize("case", ["kinds_swapped", "runs_too_short",
                                  "extra_run", "unknown_kind",
                                  "extra_segment"])
def test_wrong_layout_is_reported_by_name(case):
    cfg, layout = zamba2()
    segs = {"kinds_swapped": [[["mamba", 2], ["attn_shared", 1]]] * 2,
            "runs_too_short": [[["attn_shared", 1], ["mamba", 1]]] * 2,
            "extra_run": [[["attn_shared", 1], ["mamba", 1],
                           ["mamba", 1]]] * 2,
            "unknown_kind": [[["attn_shared", 1], ["mamba2", 2]]] * 2,
            "extra_segment": [[["attn_shared", 1], ["mamba", 2]]] * 3}[case]
    match = {"kinds_swapped": "segment 0 stage 0 is not of kind 'mamba'",
             "runs_too_short": r"segments/0/1/ssm/\S+ has shape .*1 layers "
                               "of kind 'mamba'",
             "extra_run": "segment 0: the program has 2 stages, the layout "
                          "3 runs",
             "unknown_kind": "segment 0 run 1: the layout has no kind "
                             "'mamba2'",
             "extra_segment": "the program has 2 segments, the layout 3"}[case]
    with pytest.raises(ValueError, match=match):
        weights.check_template(_template(cfg), dict(layout, segments=segs))


@pytest.mark.parametrize("name,shape", [("ssm/A_log", (8,)), ("scale", ())])
def test_leaf_no_rule_covers_is_named(name, shape):
    with pytest.raises(ValueError, match=rf"leaf {name} of shape "
                                         rf"{re.escape(str(shape))}"):
        weights.leaf_value(weights.seed_key(0), name, 0, shape)


def test_undeclared_mamba_leaf_fails_the_check_by_name():
    cfg, layout = zamba2()
    del layout["dists"]["ssm/D"]
    with pytest.raises(ValueError, match=r"leaf ssm/D of shape \(\d+,\)"):
        weights.check_template(_template(cfg), layout)
    layout["dists"]["ssm/D"] = ["cosine", 1.0]
    with pytest.raises(ValueError, match="unknown distribution 'cosine'"):
        weights.check_template(_template(cfg), layout)
