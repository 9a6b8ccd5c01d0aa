"""Staged execution: DecodeState-carrying decode, cond_batch == select
equivalence, real segment skipping, and stateful measures through the
launch serve step (jit + sharding)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced
from repro.core.exec import DecodeState, StagedExecutor, init_decode_state
from repro.core.policy import (BudgetPolicy, ConfidenceMeasure, ExitDecider,
                               register_measure)
from repro.launch.shard_rules import (batch_spec, cache_spec,
                                      decode_state_spec, param_spec,
                                      to_shardings)
from repro.launch.steps import (make_decode_state, make_decode_state_struct,
                                make_prefill_step, make_serve_step)
from repro.models.model import build_model
from repro.serving import CascadeServingEngine, Request


def _greedy_drive(cfg, params, toks, n_steps=6, donate=True):
    """Prefill + greedy decode through the staged executor; returns
    (tokens, exit_indices, segments_run)."""
    model = build_model(cfg)
    ex = StagedExecutor(model, cfg)
    cache = model.init_cache(toks.shape[0], 32)
    step = jax.jit(ex.decode_step,
                   donate_argnums=(2, 3) if donate else ())
    d, cache, state = ex.prefill(params, toks, cache)
    tokens, exits = [np.asarray(d.prediction)], [np.asarray(d.exit_index)]
    for _ in range(n_steps):
        d, cache, state = step(params, d.prediction[:, None], cache, state)
        tokens.append(np.asarray(d.prediction))
        exits.append(np.asarray(d.exit_index))
    return np.array(tokens), np.array(exits), np.asarray(state.segments_run)


@pytest.mark.parametrize("measure", ["softmax_max", "patience@2"])
@pytest.mark.parametrize("th", [0.0, 0.6, 1.1])
def test_cond_batch_matches_select_exactly(measure, th):
    """The acceptance contract: identical tokens and exit indices across
    execution modes, for stateless AND stateful measures, while cond_batch
    provably skips exited segments (its executed-segment counters stay 0)."""
    base = reduced(get_config("qwen2.5-3b")).replace(dtype="float32")
    base = base.with_cascade(thresholds=(th, 0.0), confidence=measure)
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, base.vocab_size, (2, 8)), jnp.int32)

    t_sel, e_sel, run_sel = _greedy_drive(
        base.with_cascade(exit_mode="select"), params, toks)
    t_cb, e_cb, run_cb = _greedy_drive(
        base.with_cascade(exit_mode="cond_batch"), params, toks)
    np.testing.assert_array_equal(t_sel, t_cb)
    np.testing.assert_array_equal(e_sel, e_cb)
    # select mode always computes everything
    assert run_sel[0] == run_sel[1] == 6
    if th == 0.0:
        # everyone exits at component 0 → the deep segment's compute counter
        # never advanced: lax.cond executed only the backfill branch
        assert run_cb[1] < run_sel[1]
        if measure == "softmax_max":
            assert run_cb[1] == 0
    else:
        assert run_cb[1] <= run_sel[1]


def test_cond_batch_skips_wallclock_and_flops():
    """cond_batch must actually terminate early: with a heavy deep segment
    and thresholds that exit everyone at component 0, the executed-segment
    trace shows zero deep-segment runs, and measured step time does not
    exceed the fixed select graph (lenient bound — CI timers are noisy; the
    counters are the authoritative skip evidence)."""
    base = reduced(get_config("qwen2.5-3b"), n_layers=8, d_model=512,
                   d_ff=2048, n_heads=8, n_kv_heads=2).replace(
                       dtype="float32")
    base = base.with_cascade(thresholds=(0.0, 0.0))
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, base.vocab_size, (2, 8)), jnp.int32)

    def timed(mode, n_steps=20):
        cfg = base.with_cascade(exit_mode=mode)
        ex = StagedExecutor(build_model(cfg), cfg)
        cache = ex.model.init_cache(2, 64)
        step = jax.jit(ex.decode_step, donate_argnums=(2, 3))
        d, cache, state = ex.prefill(params, toks, cache)
        d, cache, state = step(params, d.prediction[:, None], cache, state)
        jax.block_until_ready(d.prediction)           # exclude compile
        best = np.inf
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n_steps):
                d, cache, state = step(params, d.prediction[:, None], cache,
                                       state)
            jax.block_until_ready(d.prediction)
            best = min(best, (time.perf_counter() - t0) / n_steps)
        return best, np.asarray(state.segments_run)

    t_sel, run_sel = timed("select")
    t_cb, run_cb = timed("cond_batch")
    assert run_sel[1] > 0 and run_cb[1] == 0      # deep segment never ran
    assert t_cb <= t_sel * 1.25                    # and it isn't slower


def test_patience_serve_step_state_survives_jit_and_sharding():
    """A patience@k config serves through the launch step: the DecodeState
    (streak counters) must survive jit with explicit shardings — if the
    state were re-initialized per step, the streak would never reach k and
    component 0 could never answer."""
    cfg = reduced(get_config("qwen2.5-3b")).replace(dtype="float32")
    cfg = cfg.with_cascade(confidence="patience@2", thresholds=(0.0, 0.0))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 6)), jnp.int32)
    cache = model.init_cache(2, 32)

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    params_spec = param_spec(jax.eval_shape(lambda: params), cfg, mesh)
    cache_spec_t = cache_spec(jax.eval_shape(lambda: cache), cfg, mesh, 2)
    state = make_decode_state(cfg, 2)
    state_spec = decode_state_spec(jax.eval_shape(lambda: state), cfg,
                                   mesh, 2)
    tok_sh = NamedSharding(mesh, batch_spec(cfg, mesh, 2, 2))

    prefill = make_prefill_step(model, cfg)
    _, exit0, _, cache, state = prefill(params, toks, cache, None)
    assert int(np.max(np.asarray(exit0))) == 1    # streak 1 < k: final answers

    serve = jax.jit(make_serve_step(model, cfg),
                    in_shardings=(to_shardings(mesh, params_spec), tok_sh,
                                  to_shardings(mesh, cache_spec_t),
                                  to_shardings(mesh, state_spec), None))
    token = jnp.zeros((2, 1), jnp.int32)
    exits = []
    for _ in range(3):
        tok, exit_idx, conf, cache, state = serve(params, token, cache,
                                                  state, None)
        exits.append(int(np.max(np.asarray(exit_idx))))
        token = tok[:, None]
    # streak reached k on the first decode step and stays satisfied only
    # because the carried state survived jit + sharding
    assert exits == [0, 0, 0]
    assert isinstance(state, DecodeState)
    assert int(np.asarray(state.policy)[0].min()) >= 2
    assert int(state.t) == toks.shape[1] + 3


def test_decode_state_spec_structure_production_mesh():
    """decode_state_spec must cover every DecodeState leaf on the production
    mesh, batch-sharding the per-sequence leaves."""
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((16, 16), ("data", "model"))
    cfg = get_config("qwen2.5-3b").with_cascade(confidence="patience@3")
    struct = make_decode_state_struct(cfg, 128)
    spec = decode_state_spec(struct, cfg, mesh, 128)
    flat_struct = jax.tree_util.tree_leaves(struct)
    flat_spec = jax.tree_util.tree_leaves(
        spec, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_struct) == len(flat_spec)
    assert spec.active == P("data")
    assert spec.ema_conf == P("data")
    assert spec.policy == P(None, "data")
    assert spec.t == P() and spec.segments_run == P()
    # indivisible batch degrades to replication
    spec1 = decode_state_spec(make_decode_state_struct(cfg, 1), cfg, mesh, 1)
    assert spec1.active == P(None)


def test_engine_modes_agree_end_to_end():
    """The serving engine generates identical streams in select and
    cond_batch modes (same requests, same exits) while cond_batch records a
    real skip rate."""
    base = reduced(get_config("qwen2.5-3b")).replace(dtype="float32")
    base = base.with_cascade(thresholds=(0.0, 0.0))
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, base.vocab_size, 6).astype(np.int32)
               for _ in range(4)]

    def run(mode):
        cfg = base.with_cascade(exit_mode=mode)
        eng = CascadeServingEngine(cfg, model, params, lane_batch=2,
                                   n_lanes=2, cache_len=32)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(), max_new_tokens=5))
        eng.run(100)
        return eng

    sel = run("select")
    cb = run("cond_batch")
    assert sel.finished.keys() == cb.finished.keys()
    for rid in sel.finished:
        assert sel.finished[rid]["tokens"] == cb.finished[rid]["tokens"]
        assert (sel.finished[rid]["exit_depths"]
                == cb.finished[rid]["exit_depths"])
    assert sel.stats()["cond_batch_skip_rate"] == 0.0
    assert cb.stats()["cond_batch_skip_rate"] == 1.0
    assert cb.stats()["wallclock_us_per_token"] > 0


def test_budget_policy_explicit_override_warns_and_wins():
    """ROADMAP follow-up (a): a fitted BudgetPolicy no longer silently
    ignores per-call thresholds — the override is honored with a warning."""
    rng = np.random.default_rng(5)
    confs = [rng.random(500) for _ in range(3)]
    pol = BudgetPolicy("")
    pol.fit(confs, [1.0, 2.0, 4.0], mac_budget=2.0)
    dec = ExitDecider("softmax_max", policy=pol)
    logits = [jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
              for _ in range(3)]
    with pytest.warns(UserWarning, match="per-call override"):
        d = dec.decide(logits, thresholds=(0.0, 0.0, 0.0))
    np.testing.assert_array_equal(np.asarray(d.exit_index), 0)
    # without the override the fitted thresholds still rule
    d_fit = dec.decide(logits)
    assert int(np.asarray(d_fit.exit_index).max()) >= 0


def test_compactor_owns_population_depth_prior():
    """ROADMAP follow-up (c): one population depth prior, in the compactor."""
    from repro.serving.batching import DepthCompactor
    c = DepthCompactor(n_lanes=2, n_components=3, ema=0.8)
    assert c.predict_depth() == pytest.approx(1.0)     # (n_c - 1) / 2
    assert c.predict_depth(hint=2.5) == 2.5            # hint wins
    for _ in range(20):
        c.observe_prefill_exit(0.0)
    assert c.predict_depth() < 0.05                    # EMA converged

    cfg = reduced(get_config("qwen2.5-3b")).replace(dtype="float32")
    cfg = cfg.with_cascade(thresholds=(0.0, 0.0))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = CascadeServingEngine(cfg, model, params, lane_batch=2, n_lanes=1,
                               cache_len=32)
    assert not hasattr(eng, "_depth_prior")            # duplicate EMA is gone
    rng = np.random.default_rng(0)
    eng.submit(Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, 6).astype(np.int32), max_new_tokens=3))
    eng.run(50)
    # threshold 0 ⇒ prefill exits at 0 ⇒ the prior moved toward 0
    assert eng.compactor.predict_depth() < 1.0


def test_model_decode_wrapper_matches_executor():
    """CascadeModel.decode is the staged executor (cached), not a third
    decode implementation."""
    cfg = reduced(get_config("qwen2.5-3b")).replace(dtype="float32")
    cfg = cfg.with_cascade(thresholds=(0.0, 0.0), exit_mode="cond_batch")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 6)), jnp.int32)
    ex = StagedExecutor(model, cfg)

    d0, cache_a, st_a = ex.prefill(params, toks, model.init_cache(2, 32))
    _, cache_b, st_b = ex.prefill(params, toks, model.init_cache(2, 32))
    tok = d0.prediction[:, None]
    da, _, st_a = model.decode(params, tok, cache_a, st_a)
    db, _, st_b = ex.decode_step(params, tok, cache_b, st_b)
    np.testing.assert_array_equal(np.asarray(da.prediction),
                                  np.asarray(db.prediction))
    np.testing.assert_array_equal(np.asarray(da.exit_index),
                                  np.asarray(db.exit_index))
    np.testing.assert_array_equal(np.asarray(st_a.segments_run),
                                  np.asarray(st_b.segments_run))
    cached = model._staged_executor
    model.decode(params, tok, cache_a, st_a)
    assert model._staged_executor is cached      # executor built once


def test_decode_state_pytree_roundtrip():
    dec = ExitDecider("patience@2", thresholds=(0.5, 0.0))
    st = init_decode_state(dec, batch=3, n_components=2, t=7)
    leaves, treedef = jax.tree_util.tree_flatten(st)
    st2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert int(st2.t) == 7 and st2.policy.shape == (2, 3)
    st3 = st.replace(t=jnp.asarray(9, jnp.int32))
    assert int(st3.t) == 9 and int(st.t) == 7


# ---------------------------------------------------------------------------
# in-place decode rows: one K/V row per layer into the stacked caches
# ---------------------------------------------------------------------------

@register_measure("exec_first_half")
class _FirstHalfMeasure(ConfidenceMeasure):
    """Confident iff the row lies in the first half of the rows measured:
    with two cohorts of two rows, cohort 0 exits at component 0 and cohort
    1 runs on, so each deep segment takes the mixed branch."""

    name = "exec_first_half"

    def __init__(self, arg: str = ""):
        del arg

    def __call__(self, logits):
        half = jnp.arange(logits.shape[0]) < logits.shape[0] // 2
        return jnp.argmax(logits, axis=-1), half.astype(jnp.float32)


def _one_step(cfg, params, toks, W):
    """Prefill + one staged decode step: (cache before, token, decision,
    cache after, segments run by the step)."""
    model = build_model(cfg)
    ex = StagedExecutor(model, cfg)
    d, cache, state = ex.prefill(params, toks, model.init_cache(
        toks.shape[0], W))
    token = d.prediction[:, None]
    d1, cache1, state1 = jax.jit(ex.decode_step)(params, token, cache, state)
    ran = np.asarray(state1.segments_run) - np.asarray(state.segments_run)
    return cache, token, d1, cache1, ran


def _layer(stacked, l):
    return jax.tree_util.tree_map(lambda x: x[l], stacked)


@pytest.mark.parametrize("branch,n_cohorts,backfill", [
    ("run", 1, True), ("run", 2, True),
    ("skip", 1, True), ("skip", 2, True),
    ("mixed", 2, True), ("skip", 2, False),
])
def test_decode_writes_one_row_per_layer(monkeypatch, branch, n_cohorts,
                                         backfill):
    """One staged decode step writes, into every layer of each segment's
    stacked cache, only row ``t % W``: the K/V ``qkv_project`` makes from
    the layer's input where the segment ran, ``_attn_backfill``'s from the
    exit hidden state where it was skipped (nothing without
    ``state_backfill``), to float32 rounding of the eager reference.
    Every other slot keeps its bytes, and tokens, exits, confidences and
    caches equal, bit for bit, the per-layer ring path's (each layer
    rewriting its whole ring through the scan)."""
    from repro.models import model as model_mod
    from repro.models.blocks import BLOCKS, _attn_backfill
    from repro.models.layers import norm_apply, qkv_project

    measure, ths = {"run": ("softmax_max", (1.1, 1.1, 0.0)),
                    "skip": ("softmax_max", (0.0, 0.0, 0.0)),
                    "mixed": ("exec_first_half", (0.5, 0.5, 0.0))}[branch]
    cfg = reduced(get_config("qwen2.5-3b"), n_layers=6).with_cascade(
        n_components=3, exit_boundaries=(2, 4), thresholds=ths,
        confidence=measure, exit_mode="cond_batch", n_cohorts=n_cohorts,
        state_backfill=backfill)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S, W = 4, 10, 8          # the ring wraps: slot S % W holds data
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    cache0, token, d, cache, ran = _one_step(cfg, params, toks, W)
    # rows of the cohorts that ran each deep segment
    runs = {"run": [True] * B, "skip": [False] * B,
            "mixed": [False] * (B // 2) + [True] * (B // 2)}[branch]
    runs = np.asarray(runs)
    np.testing.assert_array_equal(
        ran, [n_cohorts] + [int(runs.sum()) // (B // n_cohorts)] * 2)

    # plain reference: layer by layer over per-layer rings
    model = build_model(cfg)
    h, ctx = model.begin_decode(params, token, S, cache0)
    slot = S % W
    rope_t = jnp.full((1, 1), S)
    h_exit = None
    for si in range(3):
        stage, ring = params["segments"][si][0], cache0["segments"][si][0]
        got = cache["segments"][si][0]
        for l in range(stage["attn"]["wk"].shape[0]):
            pa, ca = _layer(stage, l), _layer(ring, l)
            x = norm_apply(pa["attn"]["norm"], cfg, h)
            _, k_run, v_run = qkv_project(pa["attn"], cfg, x,
                                          rope_positions=rope_t)
            if si == 0:
                want_k, want_v = k_run, v_run
            else:
                src = (_attn_backfill(cfg, pa["attn"], h_exit, ctx, ca)
                       if backfill else ca)
                k_bf, v_bf = (src[n][:, slot:slot + 1] for n in ("k", "v"))
                sel = runs[:, None, None, None]
                want_k = jnp.where(sel, k_run, k_bf)
                want_v = jnp.where(sel, v_run, v_bf)
            np.testing.assert_allclose(np.asarray(got["k"][l, :, slot]),
                                       np.asarray(want_k[:, 0]),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(got["v"][l, :, slot]),
                                       np.asarray(want_v[:, 0]),
                                       rtol=1e-5, atol=1e-5)
            h, _, _ = BLOCKS["dense"].apply(cfg, pa, h, ctx, ca)
        if si == 0:
            h_exit = h
        else:
            h_exit = jnp.where(runs[:, None, None], h, h_exit)
        # every other slot keeps its bytes
        keep = np.arange(W) != slot
        for n in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(got[n])[:, :, keep],
                                          np.asarray(ring[n])[:, :, keep])

    # the per-layer ring path (the whole ring through the scan's outputs)
    monkeypatch.setattr(model_mod, "in_place_ring", lambda block, ctx: False)
    _, _, d_ring, cache_ring, ran_ring = _one_step(cfg, params, toks, W)
    np.testing.assert_array_equal(ran, ran_ring)
    for a, b in ((d.prediction, d_ring.prediction),
                 (d.exit_index, d_ring.exit_index),
                 (d.confidence, d_ring.confidence)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(cache),
                    jax.tree_util.tree_leaves(cache_ring)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
