"""The trace reduction: busy union, idle share, top device ops and idle
gaps by harness span, on a synthetic trace and on a recorded one."""
import json
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _synthetic():
    with open(os.path.join(DATA, "synthetic_trace.json")) as f:
        return json.load(f)


def test_busy_union_and_window():
    red = trace_reduce.reduce_events(_synthetic())
    # window [1000, 15000]; ops [1000,3000] [3000,4000] [4000,4500]
    # [8000,9000] [12000,15000] -> union 3500 + 1000 + 3000 = 7500 ns
    assert red["window_s"] == pytest.approx(14000e-9)
    assert red["busy_s"] == pytest.approx(7500e-9)
    assert red["n_device_events"] == 5


def test_top_ops_sum_their_durations():
    red = trace_reduce.reduce_events(_synthetic())
    ops = dict(red["device_ops"])
    assert ops["dot.3"] == pytest.approx(3500e-9)
    assert ops["fusion.1"] == pytest.approx(3000e-9)
    assert ops["fusion.2"] == pytest.approx(1000e-9)
    assert red["device_ops"][0][0] == "dot.3"


def test_idle_gaps_are_labelled_by_the_host_span():
    red = trace_reduce.reduce_events(_synthetic())
    # gaps: [4500, 8000] midpoint 6250 in client.receive,
    #       [9000, 12000] midpoint 10500 in engine.step
    assert red["idle_gaps"] == [
        ["client.receive", pytest.approx(3500e-9)],
        ["engine.step", pytest.approx(3000e-9)]]
    assert red["gap_by_span"] == {
        "client.receive": pytest.approx(3500e-9),
        "engine.step": pytest.approx(3000e-9)}


def test_nested_ops_count_their_self_time():
    ev = _synthetic()
    ev["device"]["/device:TPU:0"] = [
        ["%while.1 = (s32[]{:T(128)}) while(%t), body=%b", 1000, 10000],
        ["%fusion.2 = bf16[8]{0:T(256)} fusion(%x)", 2000, 3000],
        ["%fusion.3 = bf16[8]{0} fusion(%y)", 6000, 2000],
        ["%fusion.2 = bf16[8]{0:T(256)} fusion(%x)", 12000, 1000]]
    red = trace_reduce.reduce_events(ev)
    ops = dict(red["device_ops"])
    assert ops["%while.1 = (s32[]) while(%t), body=%b"] == \
        pytest.approx(5000e-9)
    assert ops["%fusion.2 = bf16[8] fusion(%x)"] == pytest.approx(4000e-9)
    assert ops["%fusion.3 = bf16[8] fusion(%y)"] == pytest.approx(2000e-9)
    assert red["busy_s"] == pytest.approx(11000e-9)


def test_ops_outside_the_window_are_clipped():
    ev = _synthetic()
    ev["device"]["/device:TPU:0"].append(["late", 14000, 5000])
    red = trace_reduce.reduce_events(ev)
    assert dict(red["device_ops"])["late"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(7500e-9)   # inside dot.3 already


def test_busy_is_averaged_over_device_planes():
    ev = _synthetic()
    ev["device"]["/device:TPU:1"] = [["fusion.9", 1000, 14000]]
    red = trace_reduce.reduce_events(ev)
    assert red["busy_s"] == pytest.approx((7500e-9 + 14000e-9) / 2)
    assert red["idle_gaps"] == []


def test_no_window_or_no_device_op_gives_nothing():
    ev = _synthetic()
    assert trace_reduce.reduce_events(ev, window_name="other") is None
    ev["device"] = {}
    assert trace_reduce.reduce_events(ev) is None


def test_recorded_trace_loads_the_harness_spans():
    ev = trace_reduce.load(os.path.join(DATA, "cpu_trace.xplane.pb"))
    names = [h[0] for h in ev["host"]]
    assert names.count("bench.traced") == 1
    assert names.count("engine.step") == 2
    assert names.count("client.receive") == 2
    win = next(h for h in ev["host"] if h[0] == "bench.traced")
    for name, start, dur in ev["host"]:
        assert win[1] <= start and start + dur <= win[1] + win[2]
    # a CPU run has no device plane: nothing to reduce
    assert ev["device"] == {}
    assert trace_reduce.reduce_events(ev) is None
