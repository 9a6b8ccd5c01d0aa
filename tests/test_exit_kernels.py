"""Exit-aware kernel parity + cohort-layout bit-identity.

Covers the skip-aware hot path end to end:

* the exit-masked decode-attention kernel vs its ref.py oracle over the
  live-mask edge cases (all-live, all-exited, single survivor), plus the
  per-row bit-identity guarantee for live rows;
* the fused exit-update kernel vs both its oracle and the dense
  :class:`~repro.core.policy.ExitDecider` scan (streaks, EMA fold, carry
  merge, padding shapes);
* ``cohort_layout="major"`` (exit-state dispatch: all-skip / mixed /
  all-run) decodes bit-identically to the legacy ``"copy"`` layout —
  tokens, exit indices, confidences, carried DecodeState AND cache bytes;
* the interpret auto-detection precedence and the cohort-capacity
  satellites.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.exec import StagedExecutor, effective_cohorts
from repro.core.policy import (ConfidenceMeasure, ExitDecider,
                               register_measure)
from repro.kernels import ref
from repro.kernels.backend import resolve_interpret
from repro.kernels.cohort_cache import cohort_scatter
from repro.kernels.decode_attention import decode_attention
from repro.kernels.exit_update import exit_update
from repro.kernels.megakernel import exit_head_update
from repro.kernels.ops import exit_update_fused, rmsnorm_fused
from repro.models.model import build_model
from repro.serving import CascadeServingEngine, Request
from repro.serving.batching import cohort_capacity

RNG = np.random.default_rng(7)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ---------------------------------------------------------------------------
# exit-masked decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("live", [
    [1, 1, 1, 1],          # all live
    [0, 0, 0, 0],          # all exited
    [0, 0, 1, 0],          # single survivor
    [1, 0, 1, 1],
])
def test_decode_attention_live_mask_vs_ref(live):
    B, KV, qpk, W, hd, t = 4, 2, 2, 96, 32, 57
    q = _arr((B, KV, qpk, hd))
    kc = _arr((B, KV, W, hd))
    vc = _arr((B, KV, W, hd))
    kpos = jnp.asarray(np.where(np.arange(W) <= t, np.arange(W), -1),
                       jnp.int32)
    got = decode_attention(q, kc, vc, t, kpos, jnp.asarray(live, jnp.int32),
                           tk=32)
    want = ref.ref_decode_attention(
        q.reshape(B, KV * qpk, hd), kc.transpose(0, 2, 1, 3),
        vc.transpose(0, 2, 1, 3), t, kpos, live=np.asarray(live, bool))
    np.testing.assert_allclose(np.asarray(got.reshape(B, KV * qpk, hd)),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    # dead rows zero-fill EXACTLY; live rows are BIT-identical to the
    # unmasked kernel (decode attention is batch-separable, so masking one
    # slot cannot perturb another)
    unmasked = decode_attention(q, kc, vc, t, kpos, tk=32)
    live_b = np.asarray(live, bool)
    assert (np.asarray(got)[~live_b] == 0.0).all()
    np.testing.assert_array_equal(np.asarray(got)[live_b],
                                  np.asarray(unmasked)[live_b])


def test_decode_attention_live_none_matches_all_ones():
    B, KV, qpk, W, hd, t = 2, 1, 4, 64, 32, 30
    q = _arr((B, KV, qpk, hd))
    kc = _arr((B, KV, W, hd))
    vc = _arr((B, KV, W, hd))
    kpos = jnp.asarray(np.where(np.arange(W) <= t, np.arange(W), -1),
                       jnp.int32)
    a = decode_attention(q, kc, vc, t, kpos, tk=32)
    b = decode_attention(q, kc, vc, t, kpos, jnp.ones((B,), jnp.int32),
                         tk=32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# fused exit-update kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,V", [(1, 128), (5, 151), (8, 4096), (3, 50304)])
@pytest.mark.parametrize("m,n,k,decay", [
    (0, 3, 0, 0.0),        # stateless mid-scan component
    (1, 3, 2, 0.0),        # patience@2 rewrite
    (2, 3, 0, 0.8),        # final component + EMA fold
    (2, 3, 3, 0.8),        # final component, patience streak still advances
    (0, 1, 0, 0.8),        # single-component cascade
])
def test_exit_update_kernel_vs_oracle(B, V, m, n, k, decay):
    logits = _arr((B, V), scale=3.0)
    args = (logits,
            jnp.asarray(RNG.integers(0, 2, B), bool),
            jnp.asarray(RNG.integers(0, V, B), jnp.int32),
            jnp.asarray(RNG.integers(0, n, B), jnp.int32),
            jnp.asarray(RNG.random(B), jnp.float32),
            jnp.asarray(RNG.integers(0, 3, B), jnp.int32),
            jnp.asarray(RNG.random(B), jnp.float32),
            jnp.asarray(RNG.integers(0, 2, B), bool))
    kw = dict(threshold=0.01, m=m, n_components=n, patience_k=k,
              ema_decay=decay)
    got = exit_update(*args, **kw)
    want = ref.ref_exit_update(*args, **kw)
    names = ("answered", "pred", "exit", "conf", "streak", "ema")
    for g, w, name in zip(got, want, names):
        np.testing.assert_allclose(
            np.asarray(g, np.float64), np.asarray(w, np.float64),
            rtol=1e-5, atol=1e-6, err_msg=f"{name} (m={m}, k={k})")


@pytest.mark.parametrize("measure", ["softmax_max", "patience@2"])
def test_fused_scan_matches_dense_decider(measure):
    """ExitDecider.scan_logits through the fused kernel == the dense
    measure_one + scan_component path (same gates/exits; confidences to
    float tolerance) across a multi-component scan with an EMA fold."""
    n_m, B, V = 3, 6, 512
    logits = [_arr((B, V), scale=4.0) for _ in range(n_m)]
    ths = (0.04, 0.04, 0.0)
    dense = ExitDecider(measure, thresholds=ths, use_kernels=False)
    fused = ExitDecider(measure, thresholds=ths, use_kernels=True)
    assert fused.fused_scan and not dense.fused_scan

    def scan(dec):
        carry = None
        for m in range(n_m):
            carry = dec.scan_logits(m, n_m, logits[m], ths, carry,
                                    ema_decay=(0.8 if m == n_m - 1 else 0.0))
            if m == 0:
                carry["ema"] = jnp.zeros((B,), jnp.float32)
                carry["act"] = jnp.ones((B,), bool)
        return carry

    a, b = scan(dense), scan(fused)
    np.testing.assert_array_equal(np.asarray(a["answered"]),
                                  np.asarray(b["answered"]))
    np.testing.assert_array_equal(np.asarray(a["pred"]), np.asarray(b["pred"]))
    np.testing.assert_array_equal(np.asarray(a["exit"]), np.asarray(b["exit"]))
    np.testing.assert_allclose(np.asarray(a["conf"]), np.asarray(b["conf"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(a["ema"]), np.asarray(b["ema"]),
                               rtol=1e-5, atol=1e-6)
    if a["streak"] is not None:
        np.testing.assert_array_equal(np.asarray(a["streak"]),
                                      np.asarray(b["streak"]))


# ---------------------------------------------------------------------------
# per-segment exit-head megakernel
# ---------------------------------------------------------------------------

def _head_args(B, V, n):
    return (jnp.asarray(RNG.integers(0, 2, B), bool),
            jnp.asarray(RNG.integers(0, V, B), jnp.int32),
            jnp.asarray(RNG.integers(0, n, B), jnp.int32),
            jnp.asarray(RNG.random(B), jnp.float32),
            jnp.asarray(RNG.integers(0, 3, B), jnp.int32),
            jnp.asarray(RNG.random(B), jnp.float32),
            jnp.asarray(RNG.integers(0, 2, B), bool))


@pytest.mark.parametrize("B,d,V", [(8, 64, 512), (6, 32, 300)])
@pytest.mark.parametrize("m,n,k,decay", [
    (0, 3, 0, 0.0),        # stateless mid-scan component
    (1, 3, 2, 0.0),        # patience@2 rewrite
    (2, 3, 0, 0.8),        # final component + EMA fold
])
@pytest.mark.parametrize("live_pat", ["none", "rand", "block_dead"])
def test_exit_head_megakernel_vs_oracle(B, d, V, m, n, k, decay, live_pat):
    """The fused exit-head megakernel (rmsnorm + unembed matmul + streaming
    confidence + exit-update merge in ONE pallas_call) vs its pure-jnp
    oracle, including the live-mask early-out contract (dead rows pass
    every carry through unchanged; a fully dead batch block skips the
    matmul)."""
    h = _arr((B, d))
    w = jnp.asarray(1.0 + 0.1 * RNG.standard_normal(d), jnp.float32)
    head = _arr((d, V), scale=0.3)
    args = _head_args(B, V, n)
    live = {"none": None,
            "rand": jnp.asarray(RNG.integers(0, 2, B), bool),
            # the first full bt-block dead -> the grid early-out path
            "block_dead": jnp.asarray([0] * (B // 2) + [1] * (B - B // 2),
                                      bool)}[live_pat]
    kw = dict(threshold=0.5, m=m, n_components=n, patience_k=k,
              ema_decay=decay, live=live)
    got = exit_head_update(h, w, head, *args, bt=4, vt=128, **kw)
    want = ref.ref_exit_head_update(h, w, head, *args, **kw)
    names = ("answered", "pred", "exit", "conf", "streak", "ema")
    for g, x, name in zip(got, want, names):
        if np.asarray(g).dtype.kind in "bi":
            np.testing.assert_array_equal(np.asarray(g), np.asarray(x),
                                          err_msg=f"{name} ({live_pat})")
        else:
            np.testing.assert_allclose(
                np.asarray(g, np.float64), np.asarray(x, np.float64),
                rtol=1e-5, atol=1e-6, err_msg=f"{name} ({live_pat})")


def test_exit_head_megakernel_bitwise_vs_fused_kernels():
    """With MATCHING vocab tiles (the shipped defaults: both the megakernel
    and exit_update stream vt=1024 columns) the megakernel is BIT-identical
    to the unfused kernel pipeline rmsnorm_fused -> XLA matmul ->
    exit_update_fused — same streaming accumulation order, same rounding.
    This is the contract that lets cfg.kernel_tune.megakernel flip on
    without perturbing any pinned stream."""
    for (B, d, V) in [(8, 64, 512), (6, 32, 300), (16, 128, 2048)]:
        for (m, n, k, decay) in [(0, 3, 0, 0.0), (1, 3, 2, 0.0),
                                 (2, 3, 0, 0.8)]:
            h = _arr((B, d))
            w = jnp.asarray(1.0 + 0.1 * RNG.standard_normal(d), jnp.float32)
            head = _arr((d, V), scale=0.3)
            args = _head_args(B, V, n)
            kw = dict(threshold=0.5, m=m, n_components=n, patience_k=k,
                      ema_decay=decay)
            got = exit_head_update(h, w, head, *args, **kw)
            xn = rmsnorm_fused(h, w, interpret=True)
            want = exit_update_fused(xn @ head, *args, interpret=True, **kw)
            for gi, (g, x) in enumerate(zip(got, want)):
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(x),
                    err_msg=f"output {gi} (B={B}, V={V}, m={m})")


def test_scan_hidden_matches_scan_logits():
    """ExitDecider.scan_hidden (megakernel route) == exit-head matmul +
    scan_logits (fused exit-update route) across a full scan, bitwise."""
    n_m, B, d, V = 3, 8, 64, 512
    ths = (0.04, 0.04, 0.0)
    dec = ExitDecider("patience@2", thresholds=ths, use_kernels=True,
                      kernel_interpret=True)
    assert dec.fused_scan
    hs = [_arr((B, d)) for _ in range(n_m)]
    w = jnp.asarray(1.0 + 0.1 * RNG.standard_normal(d), jnp.float32)
    head = _arr((d, V), scale=0.3)
    ca = cb = None
    for m in range(n_m):
        lg = rmsnorm_fused(hs[m], w, interpret=True) @ head
        ca = dec.scan_logits(m, n_m, lg, ths, ca)
        cb = dec.scan_hidden(m, n_m, hs[m], w, head, ths, cb)
    for key in ("answered", "pred", "exit", "conf", "streak"):
        np.testing.assert_array_equal(np.asarray(ca[key]),
                                      np.asarray(cb[key]), err_msg=key)


# ---------------------------------------------------------------------------
# cohort cache scatter (mixed-exit re-join)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,C", [((3, 8, 16, 2, 8), 4), ((2, 6, 5), 3),
                                     ((4, 8), 2)])
def test_cohort_scatter_matches_at_set(shape, C):
    L, B = shape[0], shape[1]
    Bc = B // C
    dst = _arr(shape)
    for c in range(C):
        src = _arr((L, Bc) + shape[2:])
        got = cohort_scatter(dst, src, c, C, interpret=True)
        want = dst.at[:, c * Bc:(c + 1) * Bc].set(src)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        dst = got


def test_cohort_scatter_chain_equals_concat():
    """Chaining one scatter per cohort rebuilds exactly the concat of the
    per-cohort parts — the _mixed re-join replacement contract."""
    L, B, C = 2, 8, 4
    Bc = B // C
    parts = [_arr((L, Bc, 4, 8)) for _ in range(C)]
    cur = _arr((L, B, 4, 8))
    for c in range(C):
        cur = cohort_scatter(cur, parts[c], c, C, interpret=True)
    want = jnp.concatenate(parts, axis=1)
    np.testing.assert_array_equal(np.asarray(cur), np.asarray(want))


# ---------------------------------------------------------------------------
# cohort-layout bit-identity (the tentpole contract)
# ---------------------------------------------------------------------------

@register_measure("exit_kernels_parity")
class _ParityMeasure(ConfidenceMeasure):
    """Deterministic mixed-difficulty measure: confident iff the argmax
    token is even — exercises the mixed (per-cohort) dispatch branch."""

    name = "exit_kernels_parity"

    def __init__(self, arg: str = ""):
        del arg

    def __call__(self, logits):
        out = jnp.argmax(logits, axis=-1)
        return out, (out % 2 == 0).astype(jnp.float32)


@pytest.fixture(scope="module")
def tiny3():
    cfg = reduced(get_config("qwen2.5-3b"), n_layers=3).replace(
        dtype="float32").with_cascade(n_components=3, exit_boundaries=(1, 2),
                                      n_cohorts=2)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _decode_trace(model, params, cfg, steps=6):
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 6)), jnp.int32)
    ex = StagedExecutor(model, cfg)
    cache = model.init_cache(4, 32)
    step = jax.jit(ex.decode_step)
    d, cache, state = ex.prefill(params, toks, cache)
    out = []
    for _ in range(steps):
        d, cache, state = step(params, d.prediction[:, None], cache, state)
        out.append((np.asarray(d.prediction), np.asarray(d.exit_index),
                    np.asarray(d.confidence)))
    return out, state, cache


@pytest.mark.parametrize("kernels", [False, True])
@pytest.mark.parametrize("measure,ths", [
    ("softmax_max", (0.0, 0.0, 0.0)),            # all-skip branch every step
    ("exit_kernels_parity", (0.5, 0.5, 0.0)),    # mixed per-cohort branch
    ("softmax_max", (1.1, 1.1, 0.0)),            # all-run branch every step
])
def test_cohort_major_bit_identical_to_copy(tiny3, kernels, measure, ths):
    """layout="major" (exit-state dispatch over cohort-major views) must
    reproduce layout="copy" EXACTLY: tokens, exit indices, confidences,
    segments_run, confidence EMA, and every cache byte."""
    cfg, model, params = tiny3
    base = cfg.replace(use_kernels=kernels).with_cascade(
        thresholds=ths, exit_mode="cond_batch", confidence=measure)
    o0, s0, c0 = _decode_trace(model, params,
                               base.with_cascade(cohort_layout="copy"))
    o1, s1, c1 = _decode_trace(model, params,
                               base.with_cascade(cohort_layout="major"))
    for a, b in zip(o0, o1):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(np.asarray(s0.segments_run),
                                  np.asarray(s1.segments_run))
    np.testing.assert_array_equal(np.asarray(s0.ema_conf),
                                  np.asarray(s1.ema_conf))
    for a, b in zip(jax.tree_util.tree_leaves(c0),
                    jax.tree_util.tree_leaves(c1)):
        assert bool(jnp.all(a == b)), "cache bytes diverged between layouts"


def test_select_matches_cond_batch_in_major_layout(tiny3):
    """exit_mode stays an execution strategy in the major layout: the
    fixed-graph select mode and the dispatching cond_batch mode produce
    identical streams and state (kernels on, all-skip dominant)."""
    cfg, model, params = tiny3
    base = cfg.replace(use_kernels=True).with_cascade(
        thresholds=(0.02, 0.02, 0.0), cohort_layout="major")
    o0, s0, _ = _decode_trace(model, params,
                              base.with_cascade(exit_mode="select"))
    o1, s1, _ = _decode_trace(model, params,
                              base.with_cascade(exit_mode="cond_batch"))
    for a, b in zip(o0, o1):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(np.asarray(s0.ema_conf),
                                  np.asarray(s1.ema_conf))


# ---------------------------------------------------------------------------
# megakernel / cohort-scatter end-to-end stream identity
# ---------------------------------------------------------------------------

def _caches_equal(c0, c1):
    for a, b in zip(jax.tree_util.tree_leaves(c0),
                    jax.tree_util.tree_leaves(c1)):
        assert bool(jnp.all(a == b)), "cache bytes diverged"


@pytest.mark.parametrize("measure,ths", [
    ("softmax_max", (0.02, 0.02, 0.0)),
    ("patience@2", (0.04, 0.04, 0.0)),
])
@pytest.mark.parametrize("exit_mode", ["cond_batch", "select"])
def test_megakernel_decode_streams_bit_identical(tiny3, measure, ths,
                                                 exit_mode):
    """Flipping cfg.kernel_tune.megakernel must not perturb ANY stream:
    tokens, exit indices, confidences, EMA, segment counts, cache bytes —
    the megakernel and the unfused kernel path share tile sizes, hence
    accumulation order, hence bits."""
    cfg, model, params = tiny3
    base = cfg.replace(use_kernels=True).with_cascade(
        thresholds=ths, confidence=measure, exit_mode=exit_mode,
        cohort_layout="major")
    on = base.with_kernel_tune(megakernel=True)
    assert StagedExecutor(model, on).use_megakernel
    assert model.exit_head_params(params, 0) is not None
    o0, s0, c0 = _decode_trace(model, params, base)
    o1, s1, c1 = _decode_trace(model, params, on)
    for a, b in zip(o0, o1):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(np.asarray(s0.segments_run),
                                  np.asarray(s1.segments_run))
    np.testing.assert_array_equal(np.asarray(s0.ema_conf),
                                  np.asarray(s1.ema_conf))
    _caches_equal(c0, c1)


def test_cohort_scatter_decode_bit_identical(tiny3):
    """cfg.kernel_tune.cohort_scatter replaces the mixed-branch per-cohort
    concat with aliased partial writes — streams and cache bytes must not
    move (the parity measure forces the mixed dispatch every step)."""
    cfg, model, params = tiny3
    base = cfg.replace(use_kernels=True).with_cascade(
        thresholds=(0.5, 0.5, 0.0), confidence="exit_kernels_parity",
        exit_mode="cond_batch", cohort_layout="major")
    on = base.with_kernel_tune(cohort_scatter=True)
    assert StagedExecutor(model, on).use_cohort_scatter
    o0, s0, c0 = _decode_trace(model, params, base)
    o1, s1, c1 = _decode_trace(model, params, on)
    for a, b in zip(o0, o1):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(np.asarray(s0.segments_run),
                                  np.asarray(s1.segments_run))
    _caches_equal(c0, c1)


@pytest.fixture(scope="module")
def eng_params():
    cfg = reduced(get_config("qwen2.5-3b")).replace(dtype="float32")
    model = build_model(cfg)
    return cfg, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("paged,runtime", [
    (False, "host"), (True, "host"), (False, "device"), (True, "device"),
])
def test_megakernel_engine_streams_identical(eng_params, paged, runtime):
    """The serving engine's token/exit streams are identical with the
    megakernel + cohort scatter on vs off, across dense/paged caches and
    the host/device decode runtimes."""
    cfg0, params = eng_params
    cascade = dict(thresholds=(0.6, 0.0), confidence="patience@2",
                   exit_mode="cond_batch", n_cohorts=2)
    fins = {}
    for mk in (False, True):
        cfg = cfg0.replace(use_kernels=True,
                           kernel_interpret=True).with_cascade(**cascade)
        if paged:
            cfg = cfg.with_paged_cache(layout="paged", block_size=8,
                                       num_blocks=0)
        if mk:
            cfg = cfg.with_kernel_tune(megakernel=True, cohort_scatter=True)
        kw = dict(lane_batch=2, n_lanes=2, cache_len=32)
        if runtime == "device":
            kw.update(runtime="device", chunk=4)
        model = build_model(cfg)
        eng = CascadeServingEngine(cfg, model, params, **kw)
        rng = np.random.default_rng(3)
        for i in range(4):
            eng.submit(Request(
                rid=i,
                prompt=rng.integers(1, 50, size=rng.integers(2, 7))
                .astype(np.int32),
                max_new_tokens=4))
        fins[mk] = eng.run(max_ticks=200)
    assert set(fins[False]) == set(fins[True]) == {0, 1, 2, 3}
    for rid in fins[False]:
        assert fins[False][rid]["tokens"] == fins[True][rid]["tokens"], rid
        assert (fins[False][rid]["exit_depths"]
                == fins[True][rid]["exit_depths"]), rid


# ---------------------------------------------------------------------------
# satellites: interpret auto-detection, cohort capacity
# ---------------------------------------------------------------------------

def test_resolve_interpret_precedence(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    on_cpu = jax.default_backend() != "tpu"
    assert resolve_interpret(None) is on_cpu     # auto-detect
    assert resolve_interpret(True) is True       # explicit override wins
    assert resolve_interpret(False) is False
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
    assert resolve_interpret(None) is False      # env forces compiled
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    assert resolve_interpret(None) is True       # env forces interpreter
    assert resolve_interpret(False) is False     # explicit still wins


def test_resolve_interpret_refuses_interpreter_on_tpu(monkeypatch):
    """On a TPU backend nothing may quietly run the Pallas interpreter:
    auto-detection compiles, and forcing interpret mode raises."""
    from repro.kernels import backend
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    assert backend.resolve_interpret(None) is False
    assert backend.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret"):
        backend.resolve_interpret(True)
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    with pytest.raises(ValueError, match="interpret"):
        backend.resolve_interpret(None)
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
    assert backend.resolve_interpret(None) is False


def test_cohort_capacity_rounds_up():
    assert cohort_capacity(4, 2) == 4
    assert cohort_capacity(3, 2) == 4
    assert cohort_capacity(1, 4) == 4
    assert cohort_capacity(5, 4) == 8
    assert cohort_capacity(6, 1) == 6


def test_effective_cohorts_warns_once_on_degradation():
    from repro.core import exec as exec_mod
    exec_mod._COHORT_WARNED.discard((2, 3))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert effective_cohorts(2, 4, warn=True) == 2      # divides: silent
        assert effective_cohorts(2, 3, warn=True) == 1      # degrades: warns
        assert effective_cohorts(2, 3, warn=True) == 1      # ... once
    msgs = [str(x.message) for x in w]
    assert sum("degrading" in m for m in msgs) == 1
    assert any("cohort_capacity" in m for m in msgs)


def test_engine_rounds_lane_capacity_to_cohort_multiple():
    """The engine admits with cohort-multiple lanes, so the effective
    cohort count never silently degrades below the config's request."""
    cfg = reduced(get_config("qwen2.5-3b")).replace(
        dtype="float32").with_cascade(n_cohorts=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = CascadeServingEngine(cfg, model, params, lane_batch=3, n_lanes=1,
                               cache_len=32)
    assert eng.lane_batch == 4
    assert eng.cohorts == 2
    assert all(len(lane["slots"]) == 4 for lane in eng.lanes)
    assert eng.stats()["lane_batch"] == 4
