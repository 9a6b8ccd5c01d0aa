"""One run of one benchmark cell: load, warm up, measure, check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``configs/<name>.json`` (which names its program
builder in ``programs/``, its reference in ``references/`` and its work
arithmetic), its traffic mix in
``traffic/<name>.json`` (which names a module in ``generators/``), its
output limits in ``limits/<cell>.json``, per-layer metrics as
``metrics/<name>.py`` and the chip's peaks in ``peaks.json``.

The system under test is the program's ``CascadeServingEngine``, driven
through ``submit`` / ``step`` and the finished records it hands back.
Every call into it, and the generator, runs under a
``jax.profiler.TraceAnnotation`` so that idle gaps in a traced run can be
attributed to what the host was doing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import resource
import sys
import time
from typing import Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
# a traced run profiles the last seconds of its window: the profiler keeps
# every device op of about ten seconds of decode, and drops some of a
# longer span
TRACE_SECONDS = 10.0


class SetupError(Exception):
    """The run cannot measure: no chip, a missing file, a program whose
    shapes the benchmark does not know."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise SetupError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    benchmark: dict


def load_cell(name: str) -> Cell:
    bench_path = os.path.join(REPO, "BENCHMARK.json")
    if not os.path.exists(bench_path):
        raise SetupError(f"no {bench_path}")
    bench = _json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SetupError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(os.path.join(HERE, "configs",
                                          w["config"] + ".json")),
                traffic=_json(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json")),
                limits=_json(os.path.join(HERE, "limits", name + ".json")),
                benchmark=bench)


def architecture(cell: Cell):
    """The configuration's reference (``references/<reference>.py``), its
    work arithmetic (``<work>.py``) and its sizes, by the names in its
    file."""
    c = cell.config
    ref = load_module(os.path.join(HERE, "references",
                                   c["reference"] + ".py"),
                      "reference_" + c["reference"])
    work = load_module(os.path.join(HERE, c["work"] + ".py"),
                       "work_" + c["work"])
    return ref, work, work.sizes(c)


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))["kinds"]
    if kind not in table:
        raise SetupError(f"no peaks for device kind {kind!r}; known: "
                         f"{sorted(table)}")
    return table[kind]


def percentile(values, q: float) -> Optional[float]:
    """Linear interpolation between order statistics (numpy's default)."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# the program's configuration for a cell
# ---------------------------------------------------------------------------

def program_config(cell: Cell, thresholds):
    """The program's ``ModelConfig`` for ``cell`` at the intermediate
    ``thresholds``, from the builder its configuration file names
    (``programs/<program>.py``, ``dense_gqa`` where it names none)."""
    name = cell.config.get("program", "dense_gqa")
    prog = load_module(os.path.join(HERE, "programs", name + ".py"),
                       "program_" + name)
    return prog.model_config(cell.config, thresholds,
                             int(cell.traffic.get("n_cohorts", 1)))


# ---------------------------------------------------------------------------
# clients: submit, step, hand-back bookkeeping
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    spec: dict
    t_submit: float
    lane: Optional[int] = None
    slot: Optional[int] = None
    first_step: Optional[int] = None
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    t_done: Optional[float] = None
    n: int = 0
    marks: list = dataclasses.field(default_factory=list)
    result: Optional[dict] = None
    exits: list = dataclasses.field(default_factory=list)


class Clients:
    """Drives the engine for a generator: submits what the generator says,
    steps the engine, and stamps when each token reaches the host."""

    def __init__(self, eng, gen, annotate):
        self.eng = eng
        self.gen = gen
        self.ann = annotate
        self.recs = {}
        self.open = {}
        self.steps = 0
        self.step_calls = []        # (start, seconds) of every engine.step

    def submit(self, specs):
        from repro.serving import Request
        for spec in specs:
            with self.ann("engine.submit"):
                t = time.perf_counter()
                self.eng.submit(Request(rid=spec["rid"],
                                        prompt=spec["prompt"],
                                        max_new_tokens=spec["max_new_tokens"]))
            rec = Rec(spec=spec, t_submit=t)
            self.recs[spec["rid"]] = rec
            self.open[spec["rid"]] = rec

    def _advance(self, rec: Rec, n: int, t: float):
        if n > rec.n:
            if rec.n == 0:
                rec.t_first, rec.first_step = t, self.steps
            rec.n, rec.t_last = n, t
            rec.marks.append((t, n))

    def step(self) -> list:
        t_call = time.perf_counter()
        with self.ann("engine.step"):
            self.eng.step()
        t = time.perf_counter()
        self.step_calls.append((t_call, t - t_call))
        self.steps += 1
        with self.ann("client.receive"):
            for li, lane in enumerate(self.eng.lanes):
                for si, s in enumerate(lane["slots"]):
                    if s.done or s.request is None:
                        continue
                    rec = self.open.get(s.request.rid)
                    if rec is None:
                        continue
                    if rec.lane is None:
                        rec.lane, rec.slot = li, si
                    self._advance(rec, len(s.generated), t)
            done = [rid for rid in self.open if rid in self.eng.finished]
            out = []
            for rid in done:
                rec = self.open.pop(rid)
                rec.result = self.eng.finished[rid]
                self._advance(rec, len(rec.result["tokens"]), t)
                rec.t_done = t
                out.append(rec)
        return out

    def partial(self, rec: Rec) -> dict:
        """An unfinished request's exits so far (its slot's lists)."""
        s = self.eng.lanes[rec.lane]["slots"][rec.slot]
        return {"tokens": list(s.generated), "exit_depths":
                list(s.exit_depths), "confs": list(s.confs)}

    def wave(self, specs, limit_steps: int = 100000):
        """Submit ``specs`` and step until every open request finished."""
        self.submit(specs)
        for _ in range(limit_steps):
            if not self.open:
                return
            self.step()
        raise RuntimeError("warm-up wave did not finish")

    def window(self, seconds: float, start_trace=None):
        """The measured window: the generator's opening requests, then
        whatever it sends, until ``seconds`` have passed.  With
        ``start_trace``, the profiler is started between two steps
        ``TRACE_SECONDS`` before the close and its span ``bench.traced``
        covers the rest of the window."""
        t0 = time.perf_counter()
        t_end = t0 + seconds
        with contextlib.ExitStack() as traced:
            with self.ann("client.generate"):
                specs = self.gen.start()
            self.submit(specs)
            while True:
                if start_trace is not None and \
                        time.perf_counter() >= t_end - TRACE_SECONDS:
                    start_trace()
                    start_trace = None
                    traced.enter_context(self.ann("bench.traced"))
                done = self.step()
                now = time.perf_counter()
                if now >= t_end:
                    break
                with self.ann("client.generate"):
                    specs = self.gen.due(now - t0)
                    for rec in done:
                        specs += self.gen.on_finish(rec.spec)
                self.submit(specs)
        return t0, t_end


# ---------------------------------------------------------------------------
# readings and metrics
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Readings:
    """What the per-layer metric readers read (``metrics/<name>.py``)."""
    window_s: float
    prefills: list          # (seconds, rows, positions) per lane prefill
    chunks: list            # (seconds, steps, start) per chunk
    lane_steps: list        # (exits, kv_lens) per decode lane step
    compiles: int
    work: object            # the configuration's work arithmetic module
    sizes: dict
    peaks: dict
    trace: Optional[dict]


def _window_events(events, t0, t_close):
    pre, chunks = [], []
    for e in events:
        a = e["attrs"]
        if e["t"] < t0 or e["t"] + a.get("seconds", 0.0) > t_close:
            continue
        if e["name"] == "lane_prefill":
            pre.append((a["seconds"], a["rids"], a["positions"]))
        elif e["name"] == "lane_chunk" and not a.get("compiled"):
            chunks.append((a["seconds"], a["steps"], e["t"]))
    return pre, chunks


def _lane_steps(recs, t0, t_close):
    """Decode lane steps whose tokens reached the host in the window:
    rows of one lane wave (same lane, same first step) advance together,
    token j >= 1 coming from decode step j at context P + j."""
    waves = {}
    for r in recs:
        if r.lane is not None and r.first_step is not None:
            waves.setdefault((r.lane, r.first_step), []).append(r)
    out = []
    for rows in waves.values():
        for j in range(1, max(r.n for r in rows)):
            live = [r for r in rows if r.n > j]
            t = next(tt for tt, n in live[0].marks if n > j)
            if not (t0 <= t <= t_close):
                continue
            out.append(([r.exits[j] for r in live],
                        [len(r.spec["prompt"]) + j for r in live]))
    return out


def end_to_end(recs, t0: float, t_end: float, seconds: float):
    """The window's end-to-end metrics from the requests' hand-back
    stamps: output tokens that reached the host in [t0, t_end] over the
    window's seconds; time to first token of every request whose first
    token fell in the window; per request completed in the window,
    (last token time - first token time) / (tokens - 1).  Returns
    (metrics, finished requests, tokens, first tokens)."""
    n_tok = 0
    for r in recs:
        prev = 0
        for t, k in r.marks:
            if t0 <= t <= t_end:
                n_tok += k - prev
            prev = k
    ttft = [1e3 * (r.t_first - r.t_submit) for r in recs
            if r.t_first is not None and t0 <= r.t_first <= t_end]
    finished = [r for r in recs if r.t_done is not None and r.t_done <= t_end]
    tpot = [1e3 * (r.t_last - r.t_first) / (r.n - 1)
            for r in finished if r.n > 1]
    return ({"tokens_per_s": n_tok / seconds,
             "ttft_p90_ms": percentile(ttft, 90),
             "tpot_p90_ms": percentile(tpot, 90)},
            finished, n_tok, len(ttft))


class GcPauses:
    """The seconds of every garbage collection while the block runs."""

    def __init__(self):
        self.seconds = []
        self._t = None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds.append(time.perf_counter() - self._t)

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)


def log_stalls(log, chunks, step_calls, t0, t_end, gc_pauses, switches):
    """Where a slow window lost its time: the slowest decode chunks (on
    the device, dispatch to host sync) and ``engine.step`` calls, each
    with its start in seconds into the window, beside the host's garbage
    collections and involuntary context switches over the window."""
    def top(xs):
        return [[round(t - t0, 3), round(sec, 4)] + rest
                for sec, t, *rest in sorted(xs, reverse=True)[:5]]

    if chunks:
        med = float(np.median([c[0] / c[1] for c in chunks]))
        over = sum(max(0.0, c[0] - med * c[1]) for c in chunks)
        log(f"chunks: {len(chunks)}, median {1e3 * med:.3f} ms a step, "
            f"{over:.3f} s over that median in all; slowest [s into the "
            f"window, s, steps] {top([(c[0], c[2], c[1]) for c in chunks])}")
    calls = [(sec, t) for t, sec in step_calls if t0 <= t <= t_end]
    log(f"engine.step: {len(calls)} calls, {sum(c[0] for c in calls):.3f} s; "
        f"slowest [s into the window, s] {top(calls)}; garbage collections "
        f"{len(gc_pauses)}, {sum(gc_pauses):.4f} s, longest "
        f"{max(gc_pauses, default=0.0):.4f} s; involuntary context "
        f"switches {switches}")


def load_metric_readers(names):
    return {n: load_module(os.path.join(HERE, "metrics", n + ".py"),
                           "metric_" + n.replace(".", "_"))
            for n in names}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check_rows(cell: Cell, recs, rng, cohorts: int, lane_batch: int):
    """The sample the reference replays: ``limits["sample_requests"]``
    requests finished in the window (the longest among them), drawn with
    ``rng``, with each token's cohort depth from its cohort mates."""
    k = int(cell.limits["sample_requests"])
    done = sorted((r for r in recs if r.result is not None),
                  key=lambda r: r.spec["rid"])
    if not done:
        return None
    longest = max(done, key=lambda r: r.n)
    rest = [r for r in done if r is not longest]
    pick = [longest] + [rest[i] for i in rng.choice(
        len(rest), size=min(k - 1, len(rest)), replace=False)]
    per = lane_batch // cohorts
    G = min(r.n for r in pick)
    rows = {"prompts": [], "tokens": [], "exits": [], "confs": [],
            "depth": []}
    for r in pick:
        mates = [m for m in recs if m.lane == r.lane
                 and m.first_step == r.first_step
                 and m.slot // per == r.slot // per]
        depth = []
        for j in range(G):
            live = [m.exits[j] for m in mates if m.n > j]
            depth.append(max(live))
        rows["prompts"].append(r.spec["prompt"])
        rows["tokens"].append(r.result["tokens"][:G])
        rows["exits"].append(r.result["exit_depths"][:G])
        rows["confs"].append(r.result["confs"][:G])
        rows["depth"].append(depth)
    return {k2: np.asarray(v) for k2, v in rows.items()}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def generator_module(mix: dict):
    return load_module(os.path.join(HERE, "generators",
                                    mix["generator"] + ".py"),
                       "generator_" + mix["generator"])


def build_engine(cell: Cell, seed: int, log):
    """The weights (one jitted call from the seed, in the program's own
    layout) and the engine serving the cell at the traffic's thresholds.
    Returns (engine, intermediate thresholds)."""
    import jax

    from repro.models.model import build_model
    from repro.serving import CascadeServingEngine

    import weights

    ref, _, sizes = architecture(cell)
    mix, srv = cell.traffic, cell.config["serving"]
    n = sizes["n_components"]
    t_build = time.perf_counter()
    model = build_model(program_config(cell, [0.0] * (n - 1)))
    template = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layout = ref.layout(sizes)
    weights.check_template(template, layout)
    build = jax.jit(weights.params_builder(template, layout))
    params = jax.block_until_ready(build(weights.seed_key(seed)))
    log(f"weights: {time.perf_counter() - t_build:.3f} s")
    ths = [float(t) for t in mix["thresholds"]]
    if len(ths) != n - 1:
        raise SetupError(f"{len(ths)} thresholds for {n} components")
    log(f"thresholds: {ths}")
    cfg = program_config(cell, ths)
    eng = CascadeServingEngine(
        cfg, build_model(cfg), params, lane_batch=srv["lane_batch"],
        n_lanes=srv["n_lanes"], cache_len=srv["cache_len"],
        runtime=srv["runtime"], chunk=srv["chunk"])
    return eng, ths


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise SetupError(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, require_tpu: bool = True, log=None,
        trace_dir: Optional[str] = None, control: bool = False) -> dict:
    """One run; returns the result object the command prints last.
    ``control`` also reads the float8 control at the same positions
    (``limits_probe.py``; the benchmark's own runs never do)."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    dev = device_info(cell.chips, require_tpu)
    import jax
    from jax._src import dispatch

    from repro.launch.compile_cache import enable_compile_cache

    import compare

    if require_tpu:             # off the chip (the tests) nothing is kept
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = []

    def on_duration(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    ann = jax.profiler.TraceAnnotation
    peaks = peaks_for(dev["kind"]) if require_tpu else None
    ref, work, sizes = architecture(cell)
    mix = cell.traffic
    n = sizes["n_components"]

    with ann("bench.setup"):
        gen_mod = generator_module(mix)
        eng, ths = build_engine(cell, seed, log)
        warm = Clients(eng, gen_mod.Generator(mix, seed, sizes["vocab"],
                                              stream="warmup"), ann)
        warm.wave(warm.gen.start())
        eng.reset_metrics()
        clients = Clients(eng, gen_mod.Generator(mix, seed, sizes["vocab"]),
                          ann)
    setup_s = time.perf_counter() - t_process
    pauses = GcPauses()
    switches = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
    with ann("bench.window"), pauses:
        t0, t_end = clients.window(
            seconds, (lambda: jax.profiler.start_trace(trace_dir))
            if trace else None)
    switches = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw - switches
    if trace:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace written: {time.perf_counter() - t_stop:.3f} s")
    t_close = max((r.t_last for r in clients.recs.values()
                   if r.t_last is not None and r.t_last <= t_end), default=t0)
    peak = memory_peak()
    recs = list(clients.recs.values())
    for r in recs:
        src = r.result if r.result is not None else (
            clients.partial(r) if r.lane is not None else None)
        r.exits = list(src["exit_depths"]) if src else []
    events = eng.flight.events.snapshot()
    step_calls = clients.step_calls
    stats = eng.stats()
    cohorts, lane_batch = eng.cohorts, eng.lane_batch
    del eng, warm, clients
    gc.collect()

    # ---- end-to-end metrics ----------------------------------------------
    e2e, finished, n_tok, n_first = end_to_end(recs, t0, t_end, seconds)
    e2e["setup_s"] = setup_s
    attempted = sum(1 for r in recs if t0 <= r.t_submit <= t_end)
    failed = sum(1 for r in finished
                 if r.n != r.spec["max_new_tokens"]
                 or len(r.exits) != r.n)
    hist = np.bincount([e for r in recs for e in r.exits],
                       minlength=n).tolist()
    log(f"window: {seconds} s, requests attempted {attempted}, "
        f"first tokens {n_first}, finished {len(finished)}, "
        f"tokens {n_tok}, exit histogram {hist}, "
        f"segments_run {stats['segments_run']}")
    log("end_to_end: " + json.dumps(e2e))

    # ---- per-layer metrics -------------------------------------------------
    pre, chunks = _window_events(events, t0, t_close)
    readings = Readings(
        window_s=seconds, prefills=pre, chunks=chunks,
        lane_steps=_lane_steps(recs, t0, t_close),
        compiles=sum(1 for t in compiles if t0 <= t <= t_end),
        work=work, sizes=sizes, peaks=peaks, trace=None)
    log_stalls(log, chunks, step_calls, t0, t_end, pauses.seconds, switches)
    breakdown = None
    if trace:
        import trace_reduce
        t_red = time.perf_counter()
        red = trace_reduce.reduce_dir(trace_dir, window_name="bench.traced")
        log(f"trace reduced: {time.perf_counter() - t_red:.3f} s")
        readings.trace = red
        if red is not None:
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            log("trace: " + json.dumps({k: red[k] for k in (
                "busy_s", "window_s", "n_device_events", "gap_by_span")}))
    names = [m["name"] for m in cell.benchmark["per_layer"]
             if cell.name in m.get("workloads", [cell.name])]
    readers = load_metric_readers(names)
    units = {m["name"]: m["unit"] for m in cell.benchmark["per_layer"]}
    per_layer = {}
    for name, mod in readers.items():
        v = mod.read(readings)
        if v is not None:
            per_layer[name] = {"value": v, "unit": units[name]}
    log("per_layer: " + json.dumps({k: v["value"]
                                    for k, v in per_layer.items()}))

    # ---- the check -----------------------------------------------------------
    rng = np.random.default_rng([int(seed) % (1 << 64), 0xC4EC])
    rows = check_rows(cell, recs, rng, cohorts, lane_batch)
    limits = cell.limits
    bad = None
    if rows is None:
        got = {k: math.inf for k in compare.NAMES}
        log("check: no request finished in the window")
    else:
        t_ref = time.perf_counter()
        got, bad = compare.numbers(ref, sizes, seed, rows, ths,
                                   control=control)
        log(f"reference: {rows['tokens'].shape[0]} requests, "
            f"{rows['tokens'].size} tokens, "
            f"{time.perf_counter() - t_ref:.3f} s")
    correct = compare.judge(got, limits) and failed == 0

    units_e2e = {m["name"]: m["unit"] for m in cell.benchmark["end_to_end"]}
    wanted = [m["name"] for m in cell.benchmark["end_to_end"]
              if cell.name in m.get("workloads", [cell.name])]
    if trace:
        metrics = per_layer
    else:
        metrics = {k: {"value": e2e[k], "unit": units_e2e[k]}
                   for k in wanted if e2e.get(k) is not None}
    device = dict(dev, memory_peak_bytes=peak)
    if trace and readings.trace is not None:
        device["busy_s"] = readings.trace["busy_s"]
        device["window_s"] = readings.trace["window_s"]
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if bad is not None:
        out["control"] = bad
    out["checks"] = {k: {"value": got[k], "limit": limits[k]}
                     for k in compare.NAMES}
    for k in compare.NAMES:
        log(f"check {k}: {got[k]!r} limit {limits[k]!r}")
    return out
