"""Share of the traced window in which the device idled while the host
was in the engine's own bookkeeping: the idle time ``trace_split`` gives
the program spans ``engine.admit``, ``engine.control`` and
``engine.{prefill,decode}.{prep,finish}`` (``idle_by_span``), in percent.
Nothing where the trace holds no program span."""
import trace_split

SPANS = ("engine.admit", "engine.control", "engine.prefill.prep",
         "engine.prefill.finish", "engine.decode.prep",
         "engine.decode.finish")
PROGRAM = ("engine.admit", "engine.control", "engine.prefill.",
           "engine.decode.")


def read(r):
    split = trace_split.for_readings(r)
    idle = (split or {}).get("idle_by_span") or {}
    if not split or not split["window_s"] or \
            not any(n.startswith(PROGRAM) for n in idle):
        return None
    return 100.0 * sum(idle.get(n, 0.0) for n in SPANS) / split["window_s"]
