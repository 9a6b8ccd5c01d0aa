"""From a profiler trace to device busy time, idle share, the device
operations that took most time, and idle gaps labelled by the host span
they fall in.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain lists; the reduction works on those lists only, so it can be checked
on a small synthetic trace.

* Device operations are the events of each device plane's ``XLA Ops``
  line (``XLA Modules`` where a plane has no op line).  Busy time is the
  length of the union of their intervals inside the traced window,
  averaged over the device planes that ran anything.
* Ops nest on that line (a ``while`` spans the ops of its body), so the
  top operations are ranked by self time: an op's time inside the window
  less the time of the ops nested in it.  An op is named by its HLO text
  up to its operands' layouts, cut to ``NAME_CHARS`` characters.
* The traced window is the host span the harness names
  (``bench.traced``).
* An idle gap is a stretch of the window in which no operation ran on a
  device; it is labelled with the innermost harness span (``engine.*``,
  ``client.*``, ``bench.*``) that holds its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")
SPAN_PREFIXES = ("engine.", "client.", "bench.")
TOP = 10
NAME_CHARS = 160
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_key(name: str):
    """(key, label): the HLO instruction name (``%fusion.12``) and its
    text without layouts, cut short."""
    key = name.split(" = ", 1)[0]
    return key, _LAYOUT.sub("", name)[:NAME_CHARS]


def self_times(evs, w0, w1):
    """Per op key, the time inside [w0, w1] not covered by ops nested in
    it; and each key's label."""
    out, labels = collections.Counter(), {}
    stack = []                              # [end, key]
    for name, s, d in sorted(evs, key=lambda e: (e[1], -e[2])):
        e = s + d
        while stack and stack[-1][0] <= s:
            stack.pop()
        key, label = op_key(name)
        labels.setdefault(key, label)
        inside = max(0.0, min(e, w1) - max(s, w0))
        out[key] += inside
        if stack:
            out[stack[-1][1]] -= inside
        stack.append([e, key])
    return out, labels


def load(path: str) -> dict:
    """``{"device": {plane: [(name, start_ns, dur_ns)]}, "host":
    [(name, start_ns, dur_ns)]}`` — device ops per plane and the harness's
    host spans."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            for want in OP_LINES:
                if want in lines:
                    device[plane.name] = [
                        (ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in lines[want].events]
                    break
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    return {"device": device, "host": host}


def union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _labeller(spans, fallback):
    """``label(t)``: the harness span holding time ``t`` (the harness's
    spans on the host thread do not overlap), else ``fallback``."""
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]

    def label(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t <= spans[i][1] + spans[i][2]:
            return spans[i][0]
        return fallback

    return label


def reduce_events(events: dict, window_name: str = "bench.traced"):
    """The reduction; ``None`` where the trace holds no window span or no
    device operation."""
    wins = [(s, s + d) for n, s, d in events["host"] if n == window_name]
    if not wins:
        return None
    w0, w1 = wins[0]
    spans = [h for h in events["host"] if h[0] != window_name]
    busy, ops, labels = [], collections.Counter(), {}
    merged_all = []
    n_events = 0
    for _, evs in sorted(events["device"].items()):
        iv = []
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                iv.append((a, b))
        if not iv:
            continue
        own, lab = self_times(evs, w0, w1)
        ops.update(own)
        labels.update(lab)
        n_events += len(iv)
        m = union(iv)
        busy.append(sum(e - s for s, e in m))
        merged_all.extend(m)
    if not busy:
        return None
    planes = len(busy)
    merged = union(merged_all)
    gaps, prev = [], w0
    for s, e in merged + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    label = _labeller(spans, window_name)
    labelled = [(label((a + b) / 2), (b - a) * 1e-9) for a, b in gaps]
    by_span = collections.Counter()
    for name, sec in labelled:
        by_span[name] += sec
    labelled.sort(key=lambda x: -x[1])
    return {
        "busy_s": sum(busy) / planes * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "n_device_events": n_events,
        "device_ops": [[labels[key], ns / planes * 1e-9]
                       for key, ns in ops.most_common(TOP)],
        "idle_gaps": [[name, sec] for name, sec in labelled[:TOP]],
        "gap_by_span": {k: v for k, v in by_span.most_common()},
    }


def find_trace(trace_dir: str):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return files[-1] if files else None


def reduce_dir(trace_dir: str, window_name: str = "bench.traced"):
    path = find_trace(trace_dir)
    if path is None:
        return None
    return reduce_events(load(path), window_name)
