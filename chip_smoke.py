#!/usr/bin/env python3
"""Chip smoke test: serve qwen2.5-3b at its published widths on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the mesh path only

One process, no child processes.  Each phase prints what it found on lines
of its own (``<phase>: {json}``); a failed check raises, so the script
exits non-zero and never prints the last line.  Weights are random, drawn
from ``SEED``, at the published widths (36 layers, d 2048, 16/2 heads,
ff 11008, vocab 151,936, bf16) with the default 3-component cascade.

One chip:

* device   — JAX must find a TPU; there is no CPU fallback.
* build    — the full config's weights, initialised under jit on the chip.
* serve_xla — 8 requests (prompt 64, 16 new tokens) through
  ``CascadeServingEngine`` (2 lanes x lane_batch 8, 1024-slot cache,
  device runtime, ``cond_batch``) at three exit thresholds: 1.1 (nothing
  exits early), 0.0 (everything exits at component 0 and the deeper
  segments are skipped) and a mixed point, the median of the component-0
  confidences behind the 1.1 run's tokens.  At 0.0 the host runtime must
  give the device runtime's tokens.
* serve_kernels — the same requests with every Pallas kernel on (the
  exit-head megakernel and cohort scatter too; two cohorts at the mixed
  point), which must run Mosaic-compiled: exit indices at 0.0 and 1.1 equal
  the XLA run's; at every threshold each decode step of the kernel run,
  replayed from XLA's cache, agrees with XLA's step (confidence and token
  against XLA's logits, exit index, the cache it writes); prefill logits of
  every component agree with the XLA path.

Four chips (``--chips 4``): the device runtime with ``mesh=`` over the four
chips, lanes data-parallel by ``repro.launch.shard_rules``, beside the same
requests on a one-device engine that decodes as many rows per dispatch as
each chip does; tokens must be identical at 0.0 and 1.1, and decode chunks
must run without a device-to-device copy.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen2.5-3b"
SEED = 0
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, 64, 16
LANES, LANE_BATCH, CACHE_LEN, CHUNK = 2, 8, 1024, 8
NEVER, ALWAYS = 1.1, 0.0
# prefill length of the logits comparison: a multiple of 128, so the
# flash-attention kernel (not the XLA fallback) runs on the kernel side
COMPARE_LEN = 128
# Kernel-vs-XLA prefill logits, per component: ||k - x||_2 <= TOL ||x||_2.
# bf16 keeps 8 significant bits (unit roundoff 2^-9).  The two paths round
# at different points — the kernels accumulate norms and attention in f32
# and round once, XLA rounds per op — and the difference random-walks
# through 36 residual layers with ~10 rounding sites each:
# sqrt(360) * 2^-9 ~= 2^-4.8.  2^-4 leaves under 2x headroom over that
# estimate, and is still 16x tighter than unrelated logits (relative
# error ~sqrt(2)).
LOGIT_REL_TOL = 2.0 ** -4
# One logit's kernel-vs-XLA error: LOGIT_REL_TOL bounds the error's rms
# relative to the logits' rms, and a single logit's error stays within 4
# such rms (a Gaussian draw beyond 4 sigma has odds 6e-5).  A
# log-confidence (max logit - logsumexp) moves by about one logit's
# error, the gap between two logits by up to two.
LOGIT_SIGMAS = 4


def report(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields, default=_plain)}", flush=True)


def _plain(x):
    if isinstance(x, np.generic):
        return x.item()
    if isinstance(x, np.ndarray):
        return x.tolist()
    return str(x)


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"device: JAX found no TPU (platform "
                         f"{d0.platform!r}); no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"device: --chips {chips} but JAX sees "
                         f"{len(devs)} device(s)")
    report("device", platform=d0.platform, kind=d0.device_kind,
           count=len(devs), jax=jax.__version__)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def smoke_config():
    from repro.configs import get_config
    return get_config(ARCH).with_cascade(exit_mode="cond_batch")


def device_bytes(stat: str = "bytes_in_use"):
    import jax
    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out.append(ms.get(stat))
    return out


def phase_build(cfg, out_shardings=None):
    import jax
    from repro.models.model import build_model
    from repro.utils import tree_bytes, tree_size
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init, out_shardings=out_shardings)(
        jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    report("build", arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
           heads=[cfg.n_heads, cfg.n_kv_heads], d_ff=cfg.d_ff,
           vocab=cfg.vocab_size, dtype=cfg.dtype,
           segments=[list(s) for s in cfg.segments],
           param_count=tree_size(params), param_bytes=tree_bytes(params),
           init_seconds=time.perf_counter() - t0,
           peak_bytes_in_use=device_bytes("peak_bytes_in_use"))
    return model, params


def make_prompts(cfg) -> np.ndarray:
    rng = np.random.default_rng(SEED)
    return rng.integers(0, cfg.vocab_size,
                        (N_REQUESTS, PROMPT_LEN)).astype(np.int32)


def with_thresholds(cfg, th: float):
    n = cfg.cascade.n_components
    return cfg.with_cascade(thresholds=(th,) * (n - 1) + (0.0,))


def serve(cfg, params, prompts, runtime: str = "device", mesh=None,
          guard_chunks: bool = False, lane_batch: int = LANE_BATCH,
          n_lanes: int = LANES) -> dict:
    """Serve every prompt through a fresh engine; check the outputs are
    well-formed and return them as (N, MAX_NEW) arrays plus stats.

    ``guard_chunks`` disallows device-to-device copies on every tick that
    only decodes (no admission, no prefill): resharded weights or carry
    would raise there."""
    import jax
    from repro.models.model import build_model
    from repro.serving import CascadeServingEngine, Request
    eng = CascadeServingEngine(cfg, build_model(cfg), params,
                               lane_batch=lane_batch, n_lanes=n_lanes,
                               cache_len=CACHE_LEN, runtime=runtime,
                               chunk=CHUNK, mesh=mesh)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=MAX_NEW))
    t0 = time.perf_counter()
    guarded = 0
    for _ in range(4 * MAX_NEW):
        if not eng.queue and all(s.done for ln in eng.lanes
                                 for s in ln["slots"]):
            break
        decode_only = (guard_chunks and not eng.queue
                       and not any(ln.get("dirty") for ln in eng.lanes))
        ctx = (jax.transfer_guard_device_to_device("disallow")
               if decode_only else contextlib.nullcontext())
        with ctx:
            eng.step()
        guarded += int(decode_only)
    wall = time.perf_counter() - t0
    fin = eng.finished
    n_m = cfg.cascade.n_components
    check(sorted(fin) == list(range(len(prompts))),
          f"finished {sorted(fin)} of {len(prompts)} requests")
    tokens = np.array([fin[i]["tokens"] for i in range(len(prompts))])
    exits = np.array([fin[i]["exit_depths"] for i in range(len(prompts))])
    confs = np.array([fin[i]["confs"] for i in range(len(prompts))])
    check(tokens.shape == (len(prompts), MAX_NEW),
          f"token array {tokens.shape}")
    check(((tokens >= 0) & (tokens < cfg.vocab_size)).all(),
          "token id out of vocab")
    check(((exits >= 0) & (exits < n_m)).all(), "exit index out of range")
    check(np.isfinite(confs).all() and (confs > 0).all()
          and (confs <= 1.0 + 1e-6).all(), "confidence not in (0, 1]")
    st = eng.stats()
    out = {"tokens": tokens, "exits": exits, "confs": confs,
           "segments_run": st["segments_run"],
           "exit_histogram": st["exit_histogram"],
           "compile_seconds": st["compile_seconds"],
           "us_per_token": st["wallclock_us_per_token"],
           "wall_seconds": wall, "guarded_ticks": guarded}
    if mesh is not None:
        ks = [ln["cache"]["segments"][0][0]["k"] for ln in eng.lanes]
        k0 = ks[0]
        w0 = jax.tree_util.tree_leaves(eng.params)[0]
        out["cache_leaf_sharding"] = str(k0.sharding.spec)
        out["cache_leaf_shard_shapes"] = sorted(
            {str(s.data.shape) for s in k0.addressable_shards})
        # fewest devices any lane's cache spans (an idle lane's too)
        out["cache_leaf_devices"] = min(len(k.sharding.device_set)
                                        for k in ks)
        out["param_leaf_devices"] = len(w0.sharding.device_set)
        out["bytes_in_use"] = device_bytes()
    del eng
    gc.collect()
    return out


def summary(run: dict) -> dict:
    keep = ("segments_run", "exit_histogram", "compile_seconds",
            "us_per_token", "wall_seconds")
    return {k: run[k] for k in keep}


def component0_confidences(cfg, model, params, prompts, tokens):
    """(N, MAX_NEW) component-0 confidences behind each generated token of
    a run: the teacher-forced forward over prompt + generated tokens, read
    at the positions whose next-token decision produced them."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def conf0(p, t):
        logits = model.forward_train(p, t)[0][0].astype(jnp.float32)
        return jnp.max(jax.nn.softmax(logits, axis=-1), axis=-1)

    seq = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    return np.asarray(conf0(params, seq))[:, PROMPT_LEN - 1:]


def phase_serve_xla(cfg, model, params, prompts) -> dict:
    n_m = cfg.cascade.n_components
    runs = {}
    never = serve(with_thresholds(cfg, NEVER), params, prompts)
    check((never["exits"] == n_m - 1).all(),
          "threshold 1.1: some token exited before the final component")
    report("serve_xla", threshold=NEVER, **summary(never))
    runs[NEVER] = never

    always = serve(with_thresholds(cfg, ALWAYS), params, prompts)
    check((always["exits"] == 0).all(),
          "threshold 0.0: some token did not exit at component 0")
    check(always["segments_run"][0] > 0
          and not any(always["segments_run"][1:]),
          f"threshold 0.0: deeper segments ran {always['segments_run']}")
    report("serve_xla", threshold=ALWAYS, **summary(always))
    runs[ALWAYS] = always

    host = serve(with_thresholds(cfg, ALWAYS), params, prompts,
                 runtime="host")
    same = bool(np.array_equal(host["tokens"], always["tokens"]))
    report("serve_xla", threshold=ALWAYS, runtime="host",
           tokens_identical_to_device=same, **summary(host))
    check(same, "host and device runtimes gave different tokens")

    c0 = component0_confidences(cfg, model, params, prompts,
                                never["tokens"])
    mid = float(np.median(c0))
    mixed = serve(with_thresholds(cfg, mid), params, prompts)
    early = int((mixed["exits"] < n_m - 1).sum())
    report("serve_xla", threshold=mid, threshold_from=(
        "median of the 1.1 run's component-0 confidences"),
        c0_confidence_range=[float(c0.min()), float(c0.max())],
        tokens_exited_early=early, tokens_at_final=int(
            (mixed["exits"] == n_m - 1).sum()), **summary(mixed))
    check(0 < early < mixed["exits"].size,
          "mixed threshold: expected some tokens to exit early and some "
          "not")
    runs[mid] = mixed
    return runs


def replay_decode(xcfg, kcfg, params, prompts, tokens, dense_logits) -> dict:
    """Hold the kernel decode path to XLA one step at a time.

    XLA prefills the prompts; then at every step the kernel decode and the
    XLA decode (``xcfg``: the same thresholds and cohorts, no kernels) take
    the same token, the kernel run's own, from one shared cache and state,
    XLA's, so no error carries from one step to the next.  Per row, the
    kernel's confidence at its exit component and the logit of the token it
    chose are held to XLA's dense logits there (``LOGIT_SIGMAS`` per-logit
    error bounds); its exit index equals XLA's unless the confidence that
    decided it sits within that bound of the threshold; and the cache it
    wrote equals XLA's, the written slot within ``LOGIT_REL_TOL`` and every
    other slot bit for bit.  With two cohorts, steps where one cohort ran a
    segment and the other skipped it are the ones that take the cohort
    scatter; they must occur."""
    import jax
    import jax.numpy as jnp
    from repro.core.exec import StagedExecutor, effective_cohorts
    from repro.models.model import build_model
    xm = build_model(xcfg)
    xe, ke = StagedExecutor(xm), StagedExecutor(build_model(kcfg))
    B = len(prompts)
    n_c = effective_cohorts(kcfg.cascade.n_cohorts, B)
    log_th = np.log(np.maximum(np.asarray(xcfg.cascade.thresholds), 1e-30))

    @jax.jit
    def prefill(p, toks):
        _, cache, state = xe.prefill(p, toks, xm.init_cache(B, CACHE_LEN))
        return cache, state

    @jax.jit
    def cache_error(kc, xc, t):
        """(largest |difference| off the slot written at position t,
        kpos included; per segment, that slot's relative L2 difference)."""
        slot = t % kc["kpos"].shape[-1]
        off = jnp.max(jnp.abs(kc["kpos"] - xc["kpos"])).astype(jnp.float32)
        rel = []
        for ks, xs in zip(kc["segments"], xc["segments"]):
            num = den = jnp.zeros((), jnp.float32)
            for a, b in zip(jax.tree_util.tree_leaves(ks),
                            jax.tree_util.tree_leaves(xs)):
                # stacked (layers, B, W, ...) cache leaves
                at = (jnp.arange(a.shape[2]) == slot).reshape(
                    (1, 1, -1) + (1,) * (a.ndim - 3))
                d = a.astype(jnp.float32) - b.astype(jnp.float32)
                off = jnp.maximum(off, jnp.max(jnp.where(at, 0.0,
                                                         jnp.abs(d))))
                num = num + jnp.sum(jnp.where(at, d * d, 0.0))
                den = den + jnp.sum(jnp.where(
                    at, jnp.square(b.astype(jnp.float32)), 0.0))
            rel.append(jnp.sqrt(num / den))
        return off, jnp.stack(rel)

    def ref(x):
        """(log-confidence, per-logit error bound) of one row of logits."""
        top = x.max()
        lc = top - (top + np.log(np.exp(x - top).sum()))
        return lc, LOGIT_SIGMAS * LOGIT_REL_TOL * np.sqrt(np.mean(x * x))

    kernel_step, xla_step = jax.jit(ke.decode_step), jax.jit(xe.decode_step)
    cache, state = prefill(params, jnp.asarray(prompts))
    conf_err, token_gap, argmax_same, ambiguous = [], [], 0, 0
    slot_rel, off_slot, splits, caches_compared = [], 0.0, 0, 0
    for j in range(tokens.shape[1] - 1):
        tok = jnp.asarray(tokens[:, j:j + 1])
        logits = [np.asarray(x, np.float64)
                  for x in dense_logits(params, tok, cache, state.t)]
        dk, ck, sk = kernel_step(params, tok, cache, state)
        dx, cx, sx = xla_step(params, tok, cache, state)
        ek, pk, confk = (np.asarray(v) for v in (
            dk.exit_index, dk.prediction, dk.confidence))
        ex = np.asarray(dx.exit_index)
        for r in range(B):
            x = logits[ek[r]][r]
            lc, bound = ref(x)
            conf_err.append(abs(np.log(confk[r]) - lc) / bound)
            token_gap.append((x.max() - x[pk[r]]) / (2 * bound))
            argmax_same += int(pk[r] == x.argmax())
            if ek[r] != ex[r]:
                m = min(ek[r], ex[r])
                lc_m, bound_m = ref(logits[m][r])
                check(abs(lc_m - log_th[m]) <= bound_m,
                      f"step {j} row {r}: kernel exit {ek[r]}, XLA exit "
                      f"{ex[r]}, confidence not at the threshold")
                ambiguous += 1
        ran = np.asarray(sk.segments_run) - np.asarray(state.segments_run)
        splits += int(((ran[1:] > 0) & (ran[1:] < n_c)).any())
        if np.array_equal(ek, ex):
            # same exits, same skips: the caches must agree
            off, rel = cache_error(ck, cx, state.t)
            off_slot = max(off_slot, float(off))
            slot_rel.append(np.asarray(rel))
            caches_compared += 1
        cache, state = cx, sx
    out = {"steps": tokens.shape[1] - 1,
           "worst_conf_error_over_bound": max(conf_err),
           "worst_token_logit_gap_over_bound": max(token_gap),
           "argmax_agreement": argmax_same / len(conf_err),
           "exits_differing_at_threshold": ambiguous,
           "caches_compared": caches_compared,
           "worst_written_slot_rel_l2": (np.max(slot_rel, axis=0)
                                         if slot_rel else None),
           "largest_off_slot_difference": off_slot,
           "cohort_split_steps": splits}
    return out


def check_replay(th, n_cohorts: int, rep: dict) -> None:
    check(rep["worst_conf_error_over_bound"] <= 1.0,
          f"threshold {th}: kernel confidence off XLA's beyond the bound")
    check(rep["worst_token_logit_gap_over_bound"] <= 1.0,
          f"threshold {th}: kernel token not XLA's argmax within the bound")
    check(rep["caches_compared"] > 0, f"threshold {th}: no cache compared")
    check(rep["largest_off_slot_difference"] == 0.0,
          f"threshold {th}: kernel decode changed cache slots it must not")
    check((rep["worst_written_slot_rel_l2"] <= LOGIT_REL_TOL).all(),
          f"threshold {th}: kernel-written cache slot off XLA's")
    if n_cohorts > 1:
        check(rep["cohort_split_steps"] > 0,
              f"threshold {th}: no step split the cohorts")


def phase_serve_kernels(cfg, params, prompts, xla_runs: dict,
                        expect_backend: str = "compiled") -> dict:
    import jax
    import jax.numpy as jnp
    from repro.models.model import build_model
    from repro.serving.runtime import kernel_provenance
    kcfg = cfg.replace(use_kernels=True).with_kernel_tune(
        megakernel=True, cohort_scatter=True)
    prov = kernel_provenance(kcfg)
    report("serve_kernels", **prov)
    check(prov["kernel_backend"] == expect_backend,
          f"kernels run {prov['kernel_backend']}, not {expect_backend}")
    xm = build_model(cfg)
    # every component's logits of one dense XLA decode step
    dense_logits = jax.jit(lambda p, tok, c, t: [
        x.astype(jnp.float32) for x in xm.decode_step(p, tok, t, c)[0]])
    mid = [t for t in xla_runs if t not in (NEVER, ALWAYS)][0]
    runs = {}
    for th in (NEVER, ALWAYS, mid):
        c = with_thresholds(kcfg, th)
        if th == mid:
            c = c.with_cascade(n_cohorts=2)
        n_c = c.cascade.n_cohorts
        run = runs[th] = serve(c, params, prompts)
        ref = xla_runs[th]
        agree = float((run["tokens"] == ref["tokens"]).mean())
        report("serve_kernels", threshold=th, n_cohorts=n_c,
               token_agreement_with_xla=agree, **summary(run))
        if th in (NEVER, ALWAYS):
            check(np.array_equal(run["exits"], ref["exits"]),
                  f"threshold {th}: kernel exit indices differ from XLA")
        rep = replay_decode(with_thresholds(cfg, th).with_cascade(
            n_cohorts=n_c), c, params, prompts, run["tokens"], dense_logits)
        report("serve_kernels", threshold=th, decode_replay=rep)
        check_replay(th, n_c, rep)

    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size,
                        (LANE_BATCH, COMPARE_LEN)).astype(np.int32)

    def prefill_logits(c):
        m = build_model(c)
        f = jax.jit(lambda p, t: m.prefill(
            p, t, m.init_cache(t.shape[0], CACHE_LEN))[0])
        return [np.asarray(x, np.float32) for x in f(params, toks)]

    xs, ks = prefill_logits(cfg), prefill_logits(kcfg)
    for m, (x, k) in enumerate(zip(xs, ks)):
        rel = float(np.linalg.norm(k - x) / np.linalg.norm(x))
        report("serve_kernels", prefill_component=m, rel_l2_error=rel,
               tolerance=LOGIT_REL_TOL,
               max_abs_diff=float(np.abs(k - x).max()),
               max_abs_logit=float(np.abs(x).max()),
               argmax_agreement=float(
                   (k.argmax(-1) == x.argmax(-1)).mean()))
        check(np.isfinite(k).all(), f"component {m}: non-finite logits")
        check(rel <= LOGIT_REL_TOL,
              f"component {m}: kernel prefill logits off by {rel:.3g}")
    return runs


def phase_mesh(cfg, prompts, n_chips: int) -> None:
    """The device runtime on a data-parallel mesh over ``n_chips`` beside
    a one-device engine, both over one set of weights placed by the
    sharding rules (replicated: serve1d with a model axis of 1)."""
    import jax
    from repro.launch.shard_rules import param_spec, to_shardings
    from repro.models.model import build_model
    # plain (Auto-axis) mesh: GSPMD places what the rules leave open
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:n_chips]).reshape(n_chips, 1),
        ("data", "model"))
    model = build_model(cfg)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(SEED))
    shardings = to_shardings(mesh, param_spec(abstract, cfg, mesh,
                                              mode="serve1d"))
    _, params = phase_build(cfg, out_shardings=shardings)
    # the one-device engine reads device 0's replica: no second copy
    one = jax.tree_util.tree_map(lambda x: x.addressable_data(0), params)
    report("mesh", mesh=dict(mesh.shape), bytes_in_use=device_bytes())
    # The one-device engine decodes as many rows per dispatch as each chip
    # of the mesh does (lane_batch / n_chips rows, in n_chips times the
    # lanes).  XLA's TPU code for a 2-row and an 8-row matmul need not
    # round alike, and on random weights (top softmax ~1e-3 over 151,936
    # tokens) one rounding difference changes a greedy token.  The 8-row
    # engine is reported beside it.
    per_chip = LANE_BATCH // n_chips
    for th in (NEVER, ALWAYS):
        c = with_thresholds(cfg, th)
        sharded = serve(c, params, prompts, mesh=mesh, guard_chunks=True)
        single = serve(c, one, prompts, lane_batch=per_chip,
                       n_lanes=LANES * n_chips)
        wide = serve(c, one, prompts)
        same = bool(np.array_equal(single["tokens"], sharded["tokens"]))
        report("mesh", threshold=th, one_device_lane_batch=per_chip,
               tokens_identical=same,
               exits_identical=bool(np.array_equal(single["exits"],
                                                   sharded["exits"])),
               token_agreement_with_lane_batch_8=float(
                   (wide["tokens"] == sharded["tokens"]).mean()),
               first_token_confs_identical_to_lane_batch_8=bool(
                   np.array_equal(wide["confs"][:, 0],
                                  sharded["confs"][:, 0])),
               decode_ticks_without_device_copies=sharded["guarded_ticks"],
               cache_leaf_sharding=sharded["cache_leaf_sharding"],
               cache_leaf_shard_shapes=sharded["cache_leaf_shard_shapes"],
               cache_leaf_devices=sharded["cache_leaf_devices"],
               param_leaf_devices=sharded["param_leaf_devices"],
               bytes_in_use_with_mesh_engine=sharded["bytes_in_use"],
               single=summary(single), sharded=summary(sharded),
               single_lane_batch_8=summary(wide))
        check(same, f"threshold {th}: mesh tokens differ from one device")
        check(sharded["guarded_ticks"] > 0, "no decode-only tick guarded")
        check(sharded["cache_leaf_devices"] == n_chips
              and sharded["param_leaf_devices"] == n_chips,
              "carry or weights not spread over the mesh")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the four-chip mesh path")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    device = phase_device(args.chips)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    report("compile_cache", dir=enable_compile_cache())

    cfg = smoke_config()
    prompts = make_prompts(cfg)
    if args.chips > 1:
        phase_mesh(cfg, prompts, args.chips)
        report("done", total_seconds=time.perf_counter() - t_start)
    else:
        model, params = phase_build(cfg)
        xla = phase_serve_xla(cfg, model, params, prompts)
        kern = phase_serve_kernels(cfg, params, prompts, xla)
        # each engine's first decode dispatch: compile (or cache load) + run
        first = [r["compile_seconds"] for r in (*xla.values(), *kern.values())]
        report("done", first_decode_dispatch_seconds=sum(first),
               peak_bytes_in_use=device_bytes("peak_bytes_in_use"),
               total_seconds=time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
