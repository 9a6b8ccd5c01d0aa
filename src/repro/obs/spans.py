"""Program spans: profiler annotations on the engine's step boundaries.

``with span("engine.decode.launch") as s:`` opens a
``jax.profiler.TraceAnnotation`` under the span's name and stamps
``time.perf_counter`` at both edges (``s.t0``, ``s.t1``, ``s.seconds``).
The same pair of edges goes to the profiler trace (on the device trace's
clock, where idle gaps are attributed to the span that holds them) and to
the flight recorder, which files ``s.seconds`` on its ``lane_prefill`` /
``lane_chunk`` events.  With no profiler running a span costs the
annotation's TraceMe check and two clock reads; it is always on.

The engine's spans tile each ``CascadeServingEngine.step()`` one level
deep; none opens inside another:

* ``engine.admit`` — admission;
* ``engine.prefill.prep`` / ``.launch`` / ``.sync`` / ``.finish`` — a
  lane (or slot) prefill: inputs built, jitted call until it returns,
  cache adoption and the host reads of its outputs, host bookkeeping;
* ``engine.decode.prep`` / ``.launch`` / ``.sync`` / ``.finish`` — one
  decode dispatch (a host tick or a device-loop chunk), split the same way;
* ``engine.control`` — the threshold controller's check, where one is
  attached.
"""
from __future__ import annotations

import time

import jax

# the annotation factory (tests swap it to record what the spans open)
_annotation = jax.profiler.TraceAnnotation


class span:
    """Context manager: one named span with its ``perf_counter`` edges."""

    __slots__ = ("name", "t0", "t1", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "span":
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0
