"""Idle time by program span and device time by named scope, from the
profiler trace of a ``--trace 1`` run: what the ``idle_*_share`` and
``exit_head_busy_share`` readers read.

It works beside ``trace_reduce.py``, on the lists its ``load`` gives, and
adds two splits to that reduction:

* ``idle_by_span`` splits every idle gap across the host spans that
  cover it, each part to the innermost covering span (the one that
  started last), the rest to the window; its parts sum to ``window_s -
  busy_s``.  It names every host span of the window, with 0 where no
  idle fell in it.  (``trace_reduce`` gives each whole gap to the span
  that holds its midpoint.)
* ``busy_by_scope`` is device self time by the innermost ``segment<k>``
  / ``exit_head<k>`` / ``backfill<k>`` component of each op's HLO
  ``op_name`` (the ``tf_op`` stat of the op's event metadata, decoded
  from the file by ``op_names``), ``other`` for every other op.

``for_readings`` finds the trace of the running process: ``run.py``
profiles a traced run into a fresh ``chipbench-trace-*`` directory of
the temporary directory and removes it after the readers have read.  A
trace counts as that run's only where its window is the one the
harness's reduction measured, to the nanosecond.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import tempfile

import trace_reduce

SCOPE = re.compile(r"^(segment|exit_head|backfill)\d+$")
OP_NAME_STAT = "tf_op"
TRACE_DIRS = "chipbench-trace-*"


def scope_of(op_name: str) -> str:
    """The innermost ``segment<k>`` / ``exit_head<k>`` / ``backfill<k>``
    component of an HLO ``op_name``, else ``other``."""
    for part in reversed(op_name.split("/")):
        if SCOPE.match(part.rstrip(":")):
            return part.rstrip(":")
    return "other"


def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of one serialized protobuf message: ints for
    varints, memoryviews for everything else."""
    i, n = 0, len(b)
    while i < n:
        tag, i = _varint(b, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield tag >> 3, v


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_names(path: str) -> dict:
    """``{op event name: op_name}`` for the device planes of an
    ``.xplane.pb``, from each XLA op's event metadata (``XSpace.planes``
    1; ``XPlane`` name 2, ``event_metadata`` 4, ``stat_metadata`` 5;
    ``XEventMetadata`` name 2, stats 5; ``XStat`` metadata_id 1,
    str_value 5, ref_value 7; ``XStatMetadata`` id 1, name 2).  ``jax``'s
    ``ProfileData`` does not expose metadata stats."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, emeta, smeta = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = bytes(pv).decode()
            elif pf == 4:
                emeta.append(pv)
            elif pf == 5:
                st = dict(_fields(_map_value(pv)))
                smeta[st.get(1)] = bytes(st.get(2, b"")).decode()
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        want = {k for k, v in smeta.items() if v == OP_NAME_STAT}
        for entry in emeta:
            ev_name, op = None, None
            for mf, mv in _fields(_map_value(entry)):
                if mf == 2:
                    ev_name = bytes(mv).decode(errors="replace")
                elif mf == 5:
                    st = dict(_fields(mv))
                    if st.get(1) in want:
                        op = (bytes(st[5]).decode(errors="replace")
                              if 5 in st else smeta.get(st.get(7), ""))
            if ev_name and op:
                out.setdefault(ev_name, op)
    return out


def load(path: str) -> dict:
    """``trace_reduce.load`` with ``"op_names"``: each device op's HLO
    ``op_name``."""
    return dict(trace_reduce.load(path), op_names=op_names(path))


def idle_split(gaps, spans, window_name):
    """Each gap's time split across the spans covering it: every stretch
    goes to the covering span that started last (the innermost, spans on
    one thread nesting), a stretch no span covers to ``window_name``.
    Seconds per span name, every span name present."""
    spans = sorted(spans, key=lambda x: x[1])
    out = collections.Counter({n: 0.0 for n, _, _ in spans})
    live, j = [], 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] < b:
            live.append(spans[j])
            j += 1
        live = [sp for sp in live if sp[1] + sp[2] > a]
        cuts = sorted({a, b} | {t for _, s, d in live for t in (s, s + d)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            cover = [sp for sp in live if sp[1] <= x and sp[1] + sp[2] >= y]
            name = max(cover, key=lambda sp: (sp[1], -sp[2]))[0] \
                if cover else window_name
            out[name] += (y - x) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def split_events(events: dict, window_name: str = "bench.traced"):
    """``window_s``, ``busy_s``, ``idle_by_span`` and ``busy_by_scope``
    of the lists ``load`` gives; ``None`` where the trace holds no window
    span or no device operation.  Busy time and the window are measured
    as ``trace_reduce.reduce_events`` measures them."""
    wins = [(s, s + d) for n, s, d in events["host"] if n == window_name]
    if not wins:
        return None
    w0, w1 = wins[0]
    names = events.get("op_names", {})
    busy, scopes, merged_all = [], collections.Counter(), []
    for _, evs in sorted(events["device"].items()):
        iv = [(max(s, w0), min(s + d, w1)) for _, s, d in evs
              if min(s + d, w1) > max(s, w0)]
        if not iv:
            continue
        scopes.update(trace_reduce.self_times(
            [(scope_of(names.get(n, "")), s, d) for n, s, d in evs],
            w0, w1)[0])
        m = trace_reduce.union(iv)
        busy.append(sum(e - s for s, e in m))
        merged_all.extend(m)
    if not busy:
        return None
    planes = len(busy)
    gaps, prev = [], w0
    for s, e in trace_reduce.union(merged_all) + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = [h for h in events["host"]
             if h[0] != window_name and h[1] < w1 and h[1] + h[2] > w0]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / planes * 1e-9,
        "idle_by_span": idle_split(gaps, spans, window_name),
        "busy_by_scope": {k: ns / planes * 1e-9
                          for k, ns in scopes.most_common()},
    }


_cache = {}


def for_readings(r, window_name: str = "bench.traced"):
    """The split of the trace the harness reduced into ``r.trace``: the
    newest trace of a ``chipbench-trace-*`` directory whose window is
    ``r.trace["window_s"]``; ``None`` where there is none.  The readers
    of one run share one reading."""
    if not r.trace or not r.trace.get("window_s"):
        return None
    window_s = r.trace["window_s"]
    if window_s in _cache:
        return _cache[window_s]
    dirs = sorted(glob.glob(os.path.join(tempfile.gettempdir(), TRACE_DIRS)),
                  key=os.path.getmtime, reverse=True)
    out = None
    for d in dirs:
        path = trace_reduce.find_trace(d)
        if path is None:
            continue
        events = trace_reduce.load(path)
        if [(s + d - s) * 1e-9 for n, s, d in events["host"]
                if n == window_name][:1] != [window_s]:
            continue
        out = split_events(dict(events, op_names=op_names(path)),
                           window_name)
        break
    _cache.clear()
    _cache[window_s] = out
    return out
