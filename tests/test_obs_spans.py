"""Program spans (``repro.obs.spans``) on the engine's step boundaries and
the staged executor's named scopes.

The spans' annotations are recorded by swapping the helper's annotation
factory: they must tile every ``step()`` one level deep (no span inside
another, in the order admit → prefill / decode prep, launch, sync,
finish), the recorder's chunk seconds must be the launch and sync spans'
sum, and nothing about them may change a token.  The scopes must reach
the compiled decode loop's ``op_name`` metadata.
"""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.exec import StagedExecutor
from repro.models.model import build_model
from repro.obs import spans
from repro.serving import CascadeServingEngine, Request
from repro.serving.runtime import DeviceDecodeLoop

PREFILL = " ".join(f"engine.prefill.{p}"
                   for p in ("prep", "launch", "sync", "finish"))
# the device loop opens a second prep span (placing its host vectors)
DECODE = ("(engine.decode.prep ){1,2}engine.decode.launch "
          "engine.decode.sync engine.decode.finish")
STEP = re.compile(rf"^(engine\.admit ({PREFILL} engine\.admit )*)?"
                  rf"(({PREFILL}|{DECODE}) )*$")


def _tiny():
    """Mixed exits on a 3-component cascade, so every branch runs."""
    cfg = reduced(get_config("qwen2.5-3b"), n_layers=3).replace(
        dtype="float32")
    return cfg.with_cascade(n_components=3, exit_boundaries=(1, 2),
                            exit_mode="cond_batch",
                            thresholds=(0.021, 0.021, 0.0))


@pytest.fixture(scope="module")
def tiny_model():
    model = build_model(_tiny())
    return model, model.init(jax.random.PRNGKey(0))


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each open and
    close with its ``perf_counter`` stamp."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("open", name, time.perf_counter()))

            def __exit__(self, *exc):
                log.append(("close", name, time.perf_counter()))

        return _Ann()


def _serve(cfg, model, params, runtime, ann=None, n=5):
    """Serve ``n`` requests (more than the 2 slots: queueing; budgets that
    differ, so the paged layout admits into a live lane) one ``step()`` at
    a time, each step's edges in ``ann``."""
    eng = CascadeServingEngine(cfg, model, params, lane_batch=2, n_lanes=1,
                               cache_len=32, runtime=runtime, chunk=4)
    rng = np.random.default_rng(3)
    for rid in range(n):
        eng.submit(Request(rid=rid, prompt=rng.integers(
            0, cfg.vocab_size, 6).astype(np.int32),
            max_new_tokens=(3, 9, 4, 8, 5)[rid % 5]))
    for _ in range(300):
        if not eng.queue and all(s.done for ln in eng.lanes
                                 for s in ln["slots"]):
            break
        if ann is not None:
            ann.log.append(("step", None, time.perf_counter()))
        eng.step()
        if ann is not None:
            ann.log.append(("end", None, time.perf_counter()))
    return eng


def _steps(log):
    """Per step: the spans it opened, as (name, t_open, t_close), and its
    own (t_start, t_end)."""
    out = []
    for kind, name, t in log:
        if kind == "step":
            out.append(([], t, None))
        elif kind == "end":
            out[-1] = (out[-1][0], out[-1][1], t)
        elif kind == "open":
            out[-1][0].append([name, t, None])
        else:
            assert out[-1][0][-1][0] == name and out[-1][0][-1][2] is None
            out[-1][0][-1][2] = t
    return out


@pytest.mark.parametrize("runtime,layout", [
    ("host", "dense"), ("device", "dense"), ("device", "paged")])
def test_spans_tile_every_step(tiny_model, monkeypatch, runtime, layout):
    model, params = tiny_model
    cfg = _tiny().with_obs()
    if layout == "paged":
        cfg = cfg.with_paged_cache(layout="paged", block_size=8)
    ann = _Annotations()
    monkeypatch.setattr(spans, "_annotation", ann)
    eng = _serve(cfg, model, params, runtime, ann)
    assert len(eng.finished) == 5
    # one level deep: every open is closed before the next opens
    kinds = [k for k, _, _ in ann.log if k in ("open", "close")]
    assert kinds == ["open", "close"] * (len(kinds) // 2)
    steps = _steps(ann.log)
    seen, uncovered, total = set(), 0.0, 0.0
    for sp, t0, t1 in steps:
        names = [n for n, _, _ in sp]
        seen.update(names)
        assert STEP.match("".join(n + " " for n in names)), names
        assert all(t0 <= a <= b <= t1 for _, a, b in sp)
        total += t1 - t0
        uncovered += (t1 - t0) - sum(b - a for _, a, b in sp)
    assert {"engine.admit", "engine.decode.launch",
            "engine.prefill.launch"} <= seen
    if layout == "paged":
        # a continuous admission: admit closes around the slot prefill
        assert any([n for n, _, _ in sp].count("engine.admit") > 1
                   for sp, _, _ in steps)
    # the spans hold the step's time: what falls between them is the
    # step's own loop over its lanes
    assert uncovered < 0.1 * total


@pytest.mark.parametrize("runtime", ["host", "device"])
def test_chunk_seconds_are_launch_plus_sync(tiny_model, runtime):
    model, params = tiny_model
    cfg = _tiny().with_obs()
    eng = _serve(cfg, model, params, runtime)
    events = eng.flight.events.snapshot()
    chunks = [e["attrs"] for e in events if e["name"] == "lane_chunk"]
    prefills = [e["attrs"] for e in events if e["name"] == "lane_prefill"]
    assert chunks and prefills
    for a in chunks:
        assert a["seconds"] == a["launch_s"] + a["sync_s"]
        assert a["launch_s"] > 0 and a["sync_s"] > 0
    for a in prefills:
        assert a["seconds"] > 0
        assert a["prep_s"] > 0 and a["launch_s"] > 0 and a["sync_s"] > 0


@pytest.mark.parametrize("runtime", ["host", "device"])
def test_streams_bit_identical_to_a_recorder_off_engine(tiny_model,
                                                        monkeypatch,
                                                        runtime):
    model, params = tiny_model
    off = _serve(_tiny(), model, params, runtime).finished
    monkeypatch.setattr(spans, "_annotation", _Annotations())
    on = _serve(_tiny().with_obs(), model, params, runtime).finished
    assert sorted(on) == sorted(off) == list(range(5))
    for rid in off:
        for key in ("tokens", "exit_depths", "confs"):
            assert on[rid][key] == off[rid][key], (rid, key)
    depths = {d for r in off.values() for d in r["exit_depths"]}
    assert len(depths) > 1                 # the exits really are mixed


def test_scopes_reach_the_compiled_decode_loop(tiny_model):
    model, params = tiny_model
    cfg = _tiny()
    loop = DeviceDecodeLoop(model, cfg, chunk=4, cache_len=32)
    ex = StagedExecutor(model, cfg)
    toks = jnp.zeros((2, 6), jnp.int32)
    _, cache, state = ex.prefill(params, toks, model.init_cache(2, 32))
    hlo = jax.jit(loop._fn).lower(
        params, jnp.zeros((2, 1), jnp.int32), cache, state,
        jnp.full((2,), 4, jnp.int32), None).compile().as_text()
    ops = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("segment0", "segment1", "exit_head0", "exit_head2",
                  "backfill1"):
        assert any(f"/{scope}/" in op for op in ops), scope
