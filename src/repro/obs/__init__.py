"""Observability for the cascade serving stack (DESIGN.md §10).

Four pieces, all host-side and all assembled from data the jitted
programs already return at the engine's host-sync boundaries:

* :mod:`repro.obs.spans` — program spans: ``jax.profiler`` annotations
  on the engine's existing step boundaries (admit, prefill and decode
  prep / launch / sync / finish, control), with the ``perf_counter``
  edges the flight recorder files.  Their cost on the chip, profiler on
  and off, is recorded in PERF.md.
* :mod:`repro.obs.recorder` — the **flight recorder**: a structured span
  tree per request (submit → queue-wait → admit → prefill → per-chunk
  decode → exit | escalate | migrate → finalize) kept in a bounded ring,
  plus an engine-level event log (lane prefills and chunks with their
  launch / sync split, threshold pushes, drains) and bounded latency
  reservoirs.
* :mod:`repro.obs.metrics` — a small metrics registry (counters /
  gauges / quantile summaries) rendered as Prometheus text exposition
  or JSON; ``engine_metrics_into`` maps an engine's ``stats()`` +
  recorder onto it, ``parse_prometheus`` round-trips the text format.
* :mod:`repro.obs.traceviz` — Perfetto / Chrome trace-event JSON export
  (one track per lane/member, chunk-level slices, instant markers for
  threshold pushes and drains) plus a schema validator.

Nothing in here touches a traced graph: recording adds no host read and
no retrace, so streams are bit-identical recorder-on vs off
(``tests/test_obs.py``, ``tests/test_obs_spans.py``).
"""
from repro.obs.metrics import (MetricsRegistry, engine_metrics_into,
                               parse_prometheus)
from repro.obs.recorder import EventLog, FlightRecorder, Span
from repro.obs.server import MetricsServer
from repro.obs.spans import span
from repro.obs.traceviz import (export_trace, trace_events,
                                validate_trace_events)

__all__ = [
    "EventLog",
    "FlightRecorder",
    "MetricsRegistry",
    "MetricsServer",
    "Span",
    "engine_metrics_into",
    "export_trace",
    "parse_prometheus",
    "span",
    "trace_events",
    "validate_trace_events",
]
