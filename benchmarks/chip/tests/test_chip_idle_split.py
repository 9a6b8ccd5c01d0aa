"""``trace_split``: ``idle_by_span`` splits each idle gap across the
spans covering it, ``busy_by_scope`` sums device self time by named
scope, the readers find the trace their run reduced; the reduction's own
keys stay as they were."""
import importlib.util
import json
import os
import shutil
import types

import pytest

import trace_reduce
import trace_split

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(DATA), "..", "metrics")
READERS = ("idle_engine_host_share", "idle_dispatch_share",
           "idle_sync_share", "exit_head_busy_share")


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _traced_run(tmp_path, monkeypatch, trace, name="chipbench-trace-a"):
    """A trace laid out as ``run.py`` leaves it during a traced run, and
    the readings the harness hands the readers."""
    monkeypatch.setattr(trace_split.tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(trace_split, "_cache", {})
    d = tmp_path / name
    prof = d / "plugins" / "profile" / "2026_01_01"
    prof.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, trace), prof / "h.xplane.pb")
    return types.SimpleNamespace(trace=trace_reduce.reduce_dir(str(d)))


def test_a_gap_over_three_spans_is_split_between_them():
    split = trace_split.split_events(_load("nested_trace.json"))
    # busy [0,3000] [6000,11000] [13000,18000]; gaps [3000,6000]
    # (prep / launch / sync), [11000,13000] (finish to 11500, then the
    # harness's engine.step, then client.receive), [18000,20000] (no span)
    assert split["idle_by_span"] == {
        "engine.decode.prep": pytest.approx(1000e-9),
        "engine.decode.launch": pytest.approx(1000e-9),
        "engine.decode.sync": pytest.approx(1000e-9),
        "engine.decode.finish": pytest.approx(500e-9),
        "engine.step": pytest.approx(500e-9),
        "client.receive": pytest.approx(1000e-9),
        "bench.traced": pytest.approx(2000e-9),
        "engine.admit": 0.0}
    assert sum(split["idle_by_span"].values()) == \
        pytest.approx(split["window_s"] - split["busy_s"])
    # the reduction's labels keep the midpoint rule
    red = trace_reduce.reduce_events(_load("nested_trace.json"))
    assert red["idle_gaps"][0] == ["engine.decode.launch",
                                   pytest.approx(3000e-9)]
    assert (red["window_s"], red["busy_s"]) == \
        (split["window_s"], split["busy_s"])


def test_busy_by_scope_takes_the_innermost_scope_of_self_time():
    split = trace_split.split_events(_load("nested_trace.json"))
    # the while's 5000 less its nested 1500 + 2000 is "other"
    assert split["busy_by_scope"] == {
        "backfill1": pytest.approx(5000e-9),
        "segment0": pytest.approx(3000e-9),
        "segment1": pytest.approx(2000e-9),
        "exit_head0": pytest.approx(1500e-9),
        "other": pytest.approx(1500e-9)}
    assert sum(split["busy_by_scope"].values()) == \
        pytest.approx(split["busy_s"])


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/while/body/exit_head2/dot_general:", "exit_head2"),
    ("jit(f)/segment1/cond/branch_0_fun/backfill1/mul:", "backfill1"),
    ("jit(f)/while/body/segment10/dot_general:", "segment10"),
    ("jit(f)/while/body/exit_heads/dot_general:", "other"),
    ("", "other"),
])
def test_scope_of(op_name, scope):
    assert trace_split.scope_of(op_name) == scope


def test_a_trace_without_scopes_or_program_spans_reads_other():
    split = trace_split.split_events(_load("synthetic_trace.json"))
    assert split["busy_by_scope"] == {"other": pytest.approx(split["busy_s"])}
    assert sum(split["idle_by_span"].values()) == \
        pytest.approx(split["window_s"] - split["busy_s"])


def test_the_keys_that_were_there_are_unchanged():
    red = trace_reduce.reduce_events(_load("synthetic_trace.json"))
    assert red == {
        "busy_s": 7.5e-06, "window_s": 1.4000000000000001e-05,
        "n_device_events": 5,
        "device_ops": [["dot.3", 3.5000000000000004e-06],
                       ["fusion.1", 3e-06],
                       ["fusion.2", 1.0000000000000002e-06]],
        "idle_gaps": [["client.receive", 3.5000000000000004e-06],
                      ["engine.step", 3e-06]],
        "gap_by_span": {"client.receive": 3.5000000000000004e-06,
                        "engine.step": 3e-06}}
    path = os.path.join(DATA, "cpu_trace.xplane.pb")
    ev = trace_reduce.load(path)
    assert ev["device"] == {} and trace_split.op_names(path) == {}
    assert ev["host"] == [
        ("bench.traced", 88007.0, 4515905.0),
        ("engine.step", 92199.0, 257337.0),
        ("client.receive", 351636.0, 2080171.0),
        ("engine.step", 2434855.0, 93810.0),
        ("client.receive", 2530025.0, 2072144.0)]


def test_op_names_are_read_from_the_device_planes_event_metadata():
    """A small xplane built to the profiler's schema: the ``tf_op`` stat
    of each XLA op's event metadata, given as a string or by reference
    to a stat metadata name, names the op's scope."""
    ev = trace_split.load(os.path.join(DATA, "scoped_trace.xplane.pb"))
    assert ev["op_names"] == {
        "%fusion.1 = bf16[8] fusion(%a)":
            "jit(f)/while/body/segment0/dot_general:",
        "%fusion.2 = bf16[8] fusion(%b)":
            "jit(f)/while/body/exit_head1/dot_general:"}
    split = trace_split.split_events(ev)
    assert split["busy_by_scope"] == {
        "exit_head1": pytest.approx(3000e-9),
        "segment0": pytest.approx(2000e-9),
        "other": pytest.approx(1000e-9)}
    # window [1000, 9000], ops [1000,3000] [3000,6000] [7000,8000]
    assert split["idle_by_span"] == {
        "engine.decode.launch": pytest.approx(1000e-9),
        "bench.traced": pytest.approx(1000e-9)}


def test_the_readers_find_their_runs_trace(tmp_path, monkeypatch):
    r = _traced_run(tmp_path, monkeypatch, "scoped_trace.xplane.pb")
    # a stale trace of another window, written later, is passed over
    _traced_run(tmp_path, monkeypatch, "cpu_trace.xplane.pb",
                name="chipbench-trace-b")
    got = {n: _reader(n).read(r) for n in READERS}
    # busy 6000 of an 8000 window; exit_head1 3000 of it; the idle
    # [6000,7000] under engine.decode.launch
    assert got == {"idle_engine_host_share": 0.0,
                   "idle_dispatch_share": pytest.approx(12.5),
                   "idle_sync_share": 0.0,
                   "exit_head_busy_share": pytest.approx(50.0)}
    assert got["idle_dispatch_share"] <= \
        100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])


def test_the_readers_read_nothing_without_spans_scopes_or_trace(
        tmp_path, monkeypatch):
    # a parent program's trace: harness spans only, no scopes
    r = _traced_run(tmp_path, monkeypatch, "cpu_trace.xplane.pb")
    assert r.trace is None or all(
        _reader(n).read(r) is None for n in READERS)
    # a window no trace on disk has
    monkeypatch.setattr(trace_split, "_cache", {})
    r = types.SimpleNamespace(trace={"window_s": 1.0, "busy_s": 0.5})
    assert all(_reader(n).read(r) is None for n in READERS)
    assert all(_reader(n).read(types.SimpleNamespace(trace=None)) is None
               for n in READERS)
