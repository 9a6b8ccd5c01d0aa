"""Device-resident decode runtime: host/device bit-identity, cohort-split
skip counters, while_loop survival under jit + mesh sharding, and the
compile-time / retire-decay satellites."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding

from repro.configs import get_config, reduced
from repro.core.exec import DecodeState, StagedExecutor, effective_cohorts
from repro.core.policy import ConfidenceMeasure, register_measure
from repro.launch.shard_rules import cache_spec, to_shardings
from repro.models.model import build_model
from repro.serving import (CascadeServingEngine, DepthCompactor,
                           DeviceDecodeLoop, Request)


def _tiny(**cascade):
    cfg = reduced(get_config("qwen2.5-3b")).replace(dtype="float32")
    return cfg.with_cascade(**cascade)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny()
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# the acceptance contract: runtime="device" == runtime="host", bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("measure", ["softmax_max", "patience@2"])
def test_device_runtime_matches_host_engine(tiny_model, measure):
    """Same requests through both runtimes (cond_batch + 2 cohorts, mixed
    per-request budgets): identical tokens and exit indices for every
    request, for stateless AND stateful measures — the device while_loop is
    an execution strategy, not a semantics."""
    model, params = tiny_model
    cfg = _tiny(thresholds=(0.6, 0.0), exit_mode="cond_batch", n_cohorts=2,
                confidence=measure)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(4)]
    budgets = [3, 5, 4, 6]

    def run(runtime):
        eng = CascadeServingEngine(cfg, model, params, lane_batch=2,
                                   n_lanes=2, cache_len=32, runtime=runtime,
                                   chunk=4)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(),
                               max_new_tokens=budgets[i]))
        eng.run(100)
        return eng

    h = run("host")
    d = run("device")
    assert h.finished.keys() == d.finished.keys()
    for rid in h.finished:
        assert h.finished[rid]["tokens"] == d.finished[rid]["tokens"]
        assert (h.finished[rid]["exit_depths"]
                == d.finished[rid]["exit_depths"])
        assert len(d.finished[rid]["tokens"]) == budgets[rid]
    # both runtimes did identical real execution (the state-carried
    # counters cover every step; the stats() window excludes each
    # runtime's own warm-up dispatch, so compare the carried state)
    h_run = np.sum([np.asarray(l["state"].segments_run)
                    for l in h.lanes], axis=0)
    d_run = np.sum([np.asarray(l["state"].segments_run)
                    for l in d.lanes], axis=0)
    np.testing.assert_array_equal(h_run, d_run)
    assert d.stats()["wallclock_us_per_token"] > 0


# ---------------------------------------------------------------------------
# cohort-split skipping converts more opportunity into realized skips
# ---------------------------------------------------------------------------

@register_measure("parity")
class ParityMeasure(ConfidenceMeasure):
    """Test measure: confident iff the argmax token is even — a
    deterministic mixed-difficulty batch (some rows always exit at
    component 0, others never) without training anything."""

    name = "parity"

    def __init__(self, arg: str = ""):
        del arg

    def __call__(self, logits):
        out = jnp.argmax(logits, axis=-1)
        return out, (out % 2 == 0).astype(jnp.float32)


def test_cohort_skip_counters_dominate_whole_lane(tiny_model):
    """On a mixed-difficulty batch the per-cohort predicate must realize at
    least as many skips as the whole-lane predicate — and strictly more
    here, where single hard rows hold the whole lane hostage."""
    model, params = tiny_model
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 512, (4, 8)), jnp.int32)
    n_steps = 8

    def skip_fraction(n_cohorts):
        cfg = _tiny(thresholds=(0.5, 0.0), exit_mode="cond_batch",
                    confidence="parity", n_cohorts=n_cohorts)
        ex = StagedExecutor(model, cfg)
        cache = model.init_cache(4, 32)
        step = jax.jit(ex.decode_step, donate_argnums=(2, 3))
        d, cache, state = ex.prefill(params, toks, cache)
        for _ in range(n_steps):
            d, cache, state = step(params, d.prediction[:, None], cache,
                                   state)
        C = effective_cohorts(n_cohorts, 4)
        run_deep = int(np.asarray(state.segments_run)[1])
        return 1.0 - run_deep / (C * n_steps)

    whole = skip_fraction(1)
    cohort = skip_fraction(4)
    assert cohort >= whole
    assert cohort > whole        # deterministic under the fixed seed
    assert cohort > 0.0


def test_engine_places_requests_into_depth_cohorts(tiny_model):
    """Admission uses DepthCompactor depth predictions to pick the slot
    cohort: a shallow hint lands in cohort 0, a deep hint in the last."""
    model, params = tiny_model
    cfg = _tiny(thresholds=(1.1, 0.0), n_cohorts=2)
    eng = CascadeServingEngine(cfg, model, params, lane_batch=4, n_lanes=1,
                               cache_len=32)
    assert eng.cohorts == 2
    deep = Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                   max_new_tokens=2, extra={"predicted_depth": 1.0})
    shallow = Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                      max_new_tokens=2, extra={"predicted_depth": 0.0})
    eng.submit(deep)
    eng.submit(shallow)
    eng._admit()
    lane = eng.lanes[0]
    rid_by_slot = [s.request.rid if not s.done else None
                   for s in lane["slots"]]
    # lane_batch=4, 2 cohorts -> slots [0,1] are cohort 0, [2,3] cohort 1
    assert rid_by_slot.index(1) < 2      # shallow -> cohort 0
    assert rid_by_slot.index(0) >= 2     # deep -> cohort 1
    # mesh sharding is a device-loop feature; the host runtime refuses it
    # instead of silently serving single-device
    with pytest.raises(ValueError, match="device"):
        CascadeServingEngine(cfg, model, params, runtime="host",
                             mesh=object())


# ---------------------------------------------------------------------------
# the while_loop carry survives jit + mesh sharding
# ---------------------------------------------------------------------------

def test_decode_loop_state_survives_jit_and_mesh_sharding(tiny_model):
    """A patience@2 config through the sharded device loop: streaks,
    cursor and cache ride the while_loop carry under jit with explicit
    mesh shardings; per-slot budgets end the loop early."""
    model, params = tiny_model
    cfg = _tiny(confidence="patience@2", thresholds=(0.0, 0.0),
                exit_mode="cond_batch")
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    loop = DeviceDecodeLoop(model, cfg, chunk=8, cache_len=32, mesh=mesh)
    ex = StagedExecutor(model, cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 6)), jnp.int32)
    d, cache, state = ex.prefill(params, toks, model.init_cache(2, 32))

    chunk, cache, state = loop.run_chunk(
        params, np.asarray(d.prediction)[:, None], cache, state,
        remaining=[3, 5])
    assert chunk.compiled and loop.compile_seconds > 0
    assert chunk.n_steps == 5                  # ended early: budgets spent
    assert chunk.live[:3, 0].all() and not chunk.live[3:, 0].any()
    assert chunk.live[:, 1].all()
    np.testing.assert_array_equal(chunk.remaining, [0, 0])
    # patience streak seeded at prefill survived INTO the loop: with
    # threshold 0 and k=2 every decode step exits at component 0, which is
    # only reachable if the carried streaks were not re-initialized
    assert (chunk.exits[chunk.live] == 0).all()
    assert isinstance(state, DecodeState)
    assert int(state.t) == toks.shape[1] + 5
    assert int(np.asarray(state.policy)[0].min()) >= 2
    assert not np.asarray(state.active).any()

    # a drained lane no-ops (0 iterations) without recompiling
    chunk2, cache, state = loop.run_chunk(
        params, chunk.tokens[-1:].T, cache, state, remaining=[0, 0])
    assert chunk2.n_steps == 0 and not chunk2.compiled
    assert chunk2.tokens.shape == (0, 2)


def test_mesh_engine_places_weights_and_carry_once(tiny_model):
    """An engine given ``mesh=`` puts the weights where the device loop's
    rules want them at construction, makes every lane's cache there too,
    and pins the prefill's cache/state to the loop's layout, so no chunk
    reshards them; its streams equal the plain engine's."""
    model, params = tiny_model
    cfg = _tiny(thresholds=(0.6, 0.0), exit_mode="cond_batch")
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(3)]

    def assert_caches_placed(eng, exact):
        # leaf by leaf, the rules' NamedSharding: a cache made without
        # out_shardings (a SingleDeviceSharding) fails even on one device.
        # A loop output's spec drops the size-1 axis, hence ``exact`` only
        # for caches as the engine made them.
        want = to_shardings(mesh, cache_spec(eng.lanes[0]["cache"], cfg,
                                             mesh, 2))
        for lane in eng.lanes:
            got = jax.tree_util.tree_map(lambda x: x.sharding,
                                         lane["cache"])
            assert jax.tree_util.tree_structure(got) == \
                jax.tree_util.tree_structure(want)
            for g, w, x in zip(jax.tree_util.tree_leaves(got),
                               jax.tree_util.tree_leaves(want),
                               jax.tree_util.tree_leaves(lane["cache"])):
                assert isinstance(g, NamedSharding) and g.mesh == mesh
                assert (g == w) if exact else g.is_equivalent_to(w, x.ndim)

    def run(mesh):
        eng = CascadeServingEngine(cfg, model, params, lane_batch=2,
                                   n_lanes=2, cache_len=32,
                                   runtime="device", chunk=4, mesh=mesh)
        if mesh is not None:
            assert_caches_placed(eng, exact=True)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(), max_new_tokens=5))
        eng.run(100)
        if mesh is not None:
            assert_caches_placed(eng, exact=False)
        return eng

    plain, sharded = run(None), run(mesh)
    leaf = jax.tree_util.tree_leaves(sharded.params)[0]
    assert leaf.sharding.mesh == mesh
    k = sharded.lanes[0]["cache"]["segments"][0][0]["k"]
    assert k.sharding.mesh == mesh
    for rid in plain.finished:
        assert plain.finished[rid]["tokens"] == sharded.finished[rid]["tokens"]
        assert (plain.finished[rid]["exit_depths"]
                == sharded.finished[rid]["exit_depths"])


# ---------------------------------------------------------------------------
# satellites: compile-time separation, retire decay
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runtime", ["host", "device"])
def test_compile_time_reported_separately(tiny_model, runtime):
    """The first decode dispatch pays jit compilation; it must land in
    ``compile_seconds``, never in ``wallclock_us_per_token`` — with no
    reset_metrics() gymnastics by the caller."""
    model, params = tiny_model
    cfg = _tiny(thresholds=(0.6, 0.0), exit_mode="cond_batch")
    eng = CascadeServingEngine(cfg, model, params, lane_batch=2, n_lanes=1,
                               cache_len=32, runtime=runtime, chunk=4)
    rng = np.random.default_rng(0)
    for i in range(2):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, 6).astype(np.int32), max_new_tokens=6))
    eng.run(100)
    st = eng.stats()
    assert st["compile_seconds"] > 0
    assert st["wallclock_us_per_token"] > 0
    # compilation takes O(seconds); a warm decode step O(ms).  If warm-up
    # leaked into the wallclock average this ratio collapses.
    assert (st["wallclock_us_per_token"] / 1e6
            < st["compile_seconds"] / 2)
    # reset_metrics keeps the one-time compile cost (and stays warm)
    eng.reset_metrics()
    assert eng.stats()["compile_seconds"] == st["compile_seconds"]
    assert eng._decode_warm or runtime == "device"


def test_retire_decays_lane_depth_ema():
    """ROADMAP satellite: a retiring slot pulls the lane depth EMA back
    toward the population prior, so a lane that drained its deep requests
    stops repelling shallow traffic."""
    c = DepthCompactor(n_lanes=2, n_components=4, ema=0.8)
    c.lane_stats[0].depth_ema = 3.0        # lane served deep traffic
    prior = c.population_prior             # 1.5
    c.observe_retire(0)
    assert c.lane_stats[0].depth_ema == pytest.approx(
        0.8 * 3.0 + 0.2 * prior)
    for _ in range(50):
        c.observe_retire(0)
    assert c.lane_stats[0].depth_ema == pytest.approx(prior, abs=1e-3)
    # cohort placement helpers
    assert c.preferred_cohort(0.0, 2) == 0
    assert c.preferred_cohort(3.0, 2) == 1
    assert c.pick_slot(0.0, [1, 2, 3], lane_batch=4, n_cohorts=2) == 1
    assert c.pick_slot(3.0, [0, 1, 2], lane_batch=4, n_cohorts=2) == 2


def test_engine_end_to_end_with_retire_decay(tiny_model):
    """Serving traffic actually exercises the retire decay (depth EMAs end
    finite and sane) and finishes every request in device runtime."""
    model, params = tiny_model
    cfg = _tiny(thresholds=(0.0, 0.0), exit_mode="cond_batch", n_cohorts=2)
    eng = CascadeServingEngine(cfg, model, params, lane_batch=2, n_lanes=2,
                               cache_len=32, runtime="device", chunk=4)
    rng = np.random.default_rng(1)
    for i in range(6):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, 6).astype(np.int32), max_new_tokens=4))
    eng.run(200)
    st = eng.stats()
    assert st["requests_finished"] == 6
    assert st["cond_batch_skip_rate"] == 1.0   # threshold 0: all skip
    for ls in eng.compactor.lane_stats:
        assert 0.0 <= ls.depth_ema <= cfg.cascade.n_components
