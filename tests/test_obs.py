"""Observability (repro.obs): flight recorder, metrics, trace export.

Pins the subsystem's contracts: every admitted request gets exactly ONE
terminal span (across host/device runtimes, dense/paged layouts,
escalation tiers, and a fleet drain — where a migrated request's flight
must span BOTH members), recorder-on token streams are bit-identical to
recorder-off, the Prometheus exposition round-trips through the parser
(and through a real HTTP socket), the ring buffer bounds memory while
lifetime counters stay lossless, and the Chrome trace-event export
passes the schema validator.
"""
import json
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models.model import build_model
from repro.obs import (EventLog, FlightRecorder, MetricsRegistry,
                       MetricsServer, export_trace, parse_prometheus,
                       trace_events, validate_trace_events)
from repro.obs.recorder import TERMINAL_KINDS, quantiles
from repro.serving import CascadeServingEngine, Request


def _tiny(**cascade):
    """Mixed-exit operating point on a 3-component cascade — exits must
    span depths for the stream-parity tests to mean anything."""
    cfg = reduced(get_config("qwen2.5-3b"), n_layers=3).replace(
        dtype="float32")
    kw = dict(n_components=3, exit_boundaries=(1, 2),
              exit_mode="cond_batch", thresholds=(0.021, 0.021, 0.0))
    kw.update(cascade)
    return cfg.with_cascade(**kw)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny()
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(cfg, model, params, **kw):
    kw.setdefault("lane_batch", 2)
    kw.setdefault("n_lanes", 1)
    kw.setdefault("cache_len", 32)
    kw.setdefault("chunk", 4)
    return CascadeServingEngine(cfg, model, params, **kw)


def _submit(engine, cfg, n, max_new=4, seed=3, prompt_len=6):
    rng = np.random.default_rng(seed)
    for i in range(n):
        engine.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                       prompt_len).astype(np.int32),
            max_new_tokens=max_new))


def _terminals(flight_dict):
    return [s for s in flight_dict["spans"]
            if s["name"] in TERMINAL_KINDS]


# ---------------------------------------------------------------------------
# recorder unit behavior: span assembly, ring bounds, event log
# ---------------------------------------------------------------------------

def test_recorder_span_tree_and_ring_bounds():
    """10 flights through a max_flights=4 recorder: the ring keeps the
    newest 4, eviction is counted, and the reservoirs' lifetime
    count/sum survive eviction (quantiles describe the ring only)."""
    rec = FlightRecorder(max_flights=4, max_events=8, reservoir=4)
    for rid in range(10):
        rec.on_submit(rid, tick=rid)
        rec.on_admit(rid, lane=0, slot=rid % 2, cohort=0,
                     predicted_depth=1.5, wait_ticks=2, tick=rid + 2)
        rec.on_chunk(0, t0=float(rid), seconds=0.01, steps=1,
                     entries=[(rid, [7], [1], [0.5])])
        rec.on_finish(rid, "exit", {"n_tokens": 1, "macs": 100.0})
    st = rec.stats()
    assert st["flights_live"] == 0
    assert st["flights_done"] == 4
    assert st["flights_evicted"] == 6
    assert rec.dump(0) is None                   # evicted
    f = rec.dump(9)
    assert [s["name"] for s in f["spans"]] == \
        ["queue_wait", "admit", "chunk", "exit"]
    assert f["terminal"] == "exit"
    assert len(_terminals(f)) == 1
    lat = rec.latency()
    # lifetime count is lossless even though the reservoir holds only 4
    assert lat["e2e_seconds"]["count"] == 10
    assert len(rec.reservoirs["e2e_seconds"].values()) == 4


def test_recorder_rejects_unknown_terminal_and_event_log_bounds():
    rec = FlightRecorder(max_flights=2, max_events=3)
    rec.on_submit(0, tick=0)
    with pytest.raises(ValueError):
        rec.on_finish(0, "vanished")
    log = EventLog(maxlen=3)
    for i in range(5):
        log.add("tick", {"i": i})
    assert len(log) == 3
    assert log.dropped == 2
    assert log.counts["tick"] == 5               # lifetime, not ring


def test_quantiles_interpolation():
    q = quantiles([1.0, 2.0, 3.0, 4.0])
    assert q["count"] == 4 and q["sum"] == 10.0
    assert q["p50"] == 2.5
    assert quantiles([]) is None


# ---------------------------------------------------------------------------
# engine integration: every admitted rid -> exactly one terminal span
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runtime,layout", [
    ("host", "dense"), ("device", "dense"),
    ("host", "paged"), ("device", "paged"),
])
def test_engine_flight_completeness(tiny_model, runtime, layout):
    model, params = tiny_model
    cfg = _tiny().with_obs()
    if layout == "paged":
        cfg = cfg.with_paged_cache(layout="paged", block_size=8)
    eng = _engine(cfg, model, params, runtime=runtime)
    n = 5                                        # > lane capacity: queueing
    _submit(eng, cfg, n)
    eng.run(300)
    assert eng.stats()["requests_finished"] == n
    assert eng.flight.stats()["flights_live"] == 0
    for rid in range(n):
        f = eng.dump_flight(rid)
        assert f is not None, f"rid {rid} not recorded"
        assert f["terminal"] == "exit"
        assert len(_terminals(f)) == 1
        names = [s["name"] for s in f["spans"]]
        assert names[0] == "queue_wait" and names[1] == "admit"
        assert any(n_ in ("prefill", "reprefill") for n_ in names)
        assert "chunk" in names
        # flight-level context: placement + kernel provenance
        assert f["attrs"]["lane"] is not None
        assert f["attrs"]["kernel_backend"] in ("interpret", "compiled")
    lat = eng.latency_stats()
    assert lat["e2e_seconds"]["count"] == n
    assert lat["admission_wait_ticks"]["count"] == n


def test_streams_bit_identical_recorder_on_vs_off(tiny_model):
    model, params = tiny_model
    base = _tiny()
    outs = {}
    for key, cfg in (("off", base), ("on", base.with_obs())):
        eng = _engine(cfg, model, params, runtime="device")
        _submit(eng, base, 4, max_new=6)
        eng.run(300)
        outs[key] = {r: tuple(v["tokens"]) for r, v in eng.finished.items()}
    assert outs["on"] == outs["off"]
    assert len(outs["on"]) == 4


def test_engine_ring_bounds_memory(tiny_model):
    model, params = tiny_model
    cfg = _tiny().with_obs(enabled=True, max_flights=3)
    eng = _engine(cfg, model, params)
    n = 8
    _submit(eng, cfg, n)
    eng.run(300)
    st = eng.flight.stats()
    assert st["flights_done"] == 3
    assert st["flights_evicted"] == n - 3
    assert len(eng.flights()) == 3
    # latency distributions still cover all n requests
    assert eng.latency_stats()["e2e_seconds"]["count"] == n


def test_threshold_push_lands_on_event_log(tiny_model):
    model, params = tiny_model
    # pushes need autotune-enabled decode graphs (thresholds as carry data)
    cfg = _tiny().with_obs().with_autotune(enabled=True)
    eng = _engine(cfg, model, params)
    _submit(eng, cfg, 2)
    for _ in range(2):
        eng.step()
    eng.push_thresholds((0.3, 0.3, 0.0))
    eng.run(300)
    assert eng.flight.events.counts["threshold_push"] == 1
    # and it shows up in the scrape as a counter
    samples = parse_prometheus(eng.scrape())
    push = [s for s in samples
            if s["name"] == "repro_threshold_push_total"]
    assert push and push[0]["value"] == 1.0


# ---------------------------------------------------------------------------
# metrics: registry, prometheus round-trip, HTTP server
# ---------------------------------------------------------------------------

def test_registry_renders_and_parses():
    reg = MetricsRegistry()
    reg.counter("repro_x_total", "Things.", 3, {"kind": "a"})
    reg.counter("repro_x_total", "Things.", 2, {"kind": "a"})
    reg.gauge("repro_depth", "Depth.", 1.5)
    reg.summary("repro_lat_seconds", "Latency.", [0.1, 0.2, 0.3],
                count=100, total=20.0)
    samples = parse_prometheus(reg.render_text())
    by = {(s["name"], tuple(sorted(s["labels"].items()))): s["value"]
          for s in samples}
    assert by[("repro_x_total", (("kind", "a"),))] == 5.0
    assert by[("repro_depth", ())] == 1.5
    assert by[("repro_lat_seconds_count", ())] == 100.0
    assert by[("repro_lat_seconds_sum", ())] == 20.0
    q50 = [s for s in samples if s["name"] == "repro_lat_seconds"
           and s["labels"].get("quantile") == "0.5"]
    assert q50 and abs(q50[0]["value"] - 0.2) < 1e-9
    with pytest.raises(ValueError):
        parse_prometheus("repro_bad{unclosed 1.0")


def test_engine_scrape_parses_and_server_round_trips(tiny_model):
    model, params = tiny_model
    cfg = _tiny().with_obs()
    eng = _engine(cfg, model, params)
    _submit(eng, cfg, 3)
    eng.run(300)
    samples = parse_prometheus(eng.scrape())
    names = {s["name"] for s in samples}
    assert "repro_requests_finished_total" in names
    assert "repro_request_latency_seconds_count" in names
    assert "repro_exit_component_total" in names
    with MetricsServer(0, eng.scrape, scrape_json=eng.scrape_json,
                       flights=eng.flights, flight=eng.dump_flight,
                       trace=lambda: trace_events([eng.flight])) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(base + "/metrics",
                                      timeout=10).read().decode()
        assert parse_prometheus(body) == samples
        mj = json.loads(urllib.request.urlopen(
            base + "/metrics.json", timeout=10).read())
        assert mj["repro_requests_finished_total"]["type"] == "counter"
        fl = json.loads(urllib.request.urlopen(
            base + "/flights/0", timeout=10).read())
        assert fl["rid"] == 0 and fl["terminal"] == "exit"
        tr = json.loads(urllib.request.urlopen(
            base + "/trace", timeout=10).read())
        validate_trace_events(tr["traceEvents"])
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/flights/999", timeout=10)
        assert ei.value.code == 404


# ---------------------------------------------------------------------------
# escalation: one flight per stage, annotated with the escalation context
# ---------------------------------------------------------------------------

def test_escalation_flight_spans_both_stages():
    from repro.escalate import ModelCascadeTier
    # stage-0 intra thresholds at the never-exit sentinel: every token is
    # answered at the final component, so escalation threshold 1.1 defers
    # EVERY request at its first token
    cfg0 = _tiny(thresholds=(1.1, 1.1, 0.0)).with_obs() \
        .with_escalation(enabled=True, threshold=1.1)
    cfg1 = reduced(get_config("qwen2.5-3b"),
                   n_layers=4).replace(dtype="float32") \
        .with_cascade(n_components=2, exit_boundaries=(2,),
                      thresholds=(1.1, 0.0)).with_obs()
    engines = []
    for s, cfg in enumerate((cfg0, cfg1)):
        model = build_model(cfg)
        engines.append(_engine(cfg, model,
                               model.init(jax.random.PRNGKey(s)),
                               lane_batch=4))
    tier = ModelCascadeTier(engines)
    _submit(tier, cfg0, 3)
    tier.run(400)
    st = tier.stats()
    assert st["requests_finished"] == 3
    assert st["escalations_total"] == 3          # 1.1 = always defer
    for rid in range(3):
        stages = tier.dump_flight(rid)
        by_stage = {d["stage"]: d for d in stages}
        assert set(by_stage) == {0, 1}
        assert by_stage[0]["terminal"] == "escalate"
        assert by_stage[1]["terminal"] == "exit"
        assert len(_terminals(by_stage[0])) == 1
        assert len(_terminals(by_stage[1])) == 1
        # the tier stamps routing context on the SOURCE flight; the
        # target engine stamps provenance at its escalated admission
        assert by_stage[0]["attrs"]["escalated_to_stage"] == 1
        assert by_stage[1]["attrs"]["escalated_from"] == rid
        assert by_stage[1]["attrs"]["replayed"] >= 0


# ---------------------------------------------------------------------------
# fleet: drain/migration visible, migrated flights span both members
# ---------------------------------------------------------------------------

def test_fleet_drain_flights_and_trace(tiny_model):
    from repro.fleet import FleetScheduler
    model, params = tiny_model
    cfg = _tiny().with_obs().with_fleet(n_engines=2, drain_mode="migrate")
    members = [_engine(cfg, model, params, runtime="device", chunk=2)
               for _ in range(2)]
    fleet = FleetScheduler(members)
    n = 6
    _submit(fleet, cfg, n, max_new=8)
    for _ in range(2):
        fleet.step()
    summary = fleet.drain(0, mode="migrate")
    fleet.run(500)
    st = fleet.stats()
    assert st["requests_finished"] == n
    assert st["discarded_tokens"] == 0
    migrated = summary["migrated"]
    assert migrated, "drain must catch in-flight work for this test"
    # exactly one terminal per member flight; migrated span both members
    for rid in range(n):
        fl = fleet.dump_flight(rid)
        assert fl is not None
        for m in fl["members"]:
            assert len(_terminals(m)) == 1
    for rid in migrated:
        fl = fleet.dump_flight(rid)
        kinds = {m["member"]: m["terminal"] for m in fl["members"]}
        assert len(kinds) == 2
        assert sorted(kinds.values()) == ["exit", "migrate"]
        target = [m for m in fl["members"]
                  if m["terminal"] == "exit"][0]
        assert target["attrs"].get("migrated") is True
    assert fleet.events.counts["drain"] == 1
    # member health surfaces through stats
    ms = st["members"][0]
    assert ms["healthy"] is True
    assert ms["consecutive_failures"] == 0
    # fleet scrape parses, with per-member + merged labels
    samples = parse_prometheus(fleet.scrape())
    members_seen = {s["labels"].get("member") for s in samples
                    if s["name"] == "repro_requests_finished_total"}
    assert members_seen == {"0", "1"}
    # every rid finalizes exactly once somewhere, plus one terminal on
    # the source member per migration/requeue
    merged = [s for s in samples
              if s["name"] == "repro_request_latency_seconds_count"
              and s["labels"].get("member") == "merged"]
    assert merged and merged[0]["value"] == float(
        n + len(migrated) + len(summary["requeued"]))
    healthy = [s for s in samples
               if s["name"] == "repro_fleet_member_healthy"]
    assert len(healthy) == 2
    # trace export validates with the drain instant present
    evs = fleet.trace_events()
    validate_trace_events(evs, require_names=("drain",))
    assert any(e["ph"] == "i" and e["name"].startswith("migrate ")
               for e in evs)


# ---------------------------------------------------------------------------
# trace schema validator
# ---------------------------------------------------------------------------

def test_validate_trace_events_rejects_malformed():
    ok = [{"ph": "X", "name": "chunk", "pid": 1, "tid": 0,
           "ts": 0.0, "dur": 1.0, "args": {}}]
    validate_trace_events(ok)
    with pytest.raises(ValueError):
        validate_trace_events([{**ok[0], "ph": "B"}])
    with pytest.raises(ValueError):
        validate_trace_events([{**ok[0], "ts": -1.0}])
    with pytest.raises(ValueError):
        validate_trace_events([dict(ok[0], ph="i")])    # missing scope
    with pytest.raises(ValueError):
        validate_trace_events([{**ok[0],
                                "args": {"bad": object()}}])
    with pytest.raises(ValueError, match="missing"):
        validate_trace_events(ok, require_names=("drain",))


def test_export_trace_writes_validated_doc(tiny_model, tmp_path):
    model, params = tiny_model
    cfg = _tiny().with_obs()
    eng = _engine(cfg, model, params)
    _submit(eng, cfg, 2)
    eng.run(300)
    path = tmp_path / "trace.json"
    doc = export_trace(str(path), [("engine", eng.flight)])
    on_disk = json.loads(path.read_text())
    assert on_disk["traceEvents"] == doc["traceEvents"]
    assert on_disk["displayTimeUnit"] == "ms"
    names = {e["name"] for e in on_disk["traceEvents"]}
    assert any(n.startswith("chunk ") for n in names)
    assert any(n.startswith("exit ") for n in names)
