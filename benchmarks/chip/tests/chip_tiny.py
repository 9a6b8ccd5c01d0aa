"""A CPU-sized cell for the benchmark's tests: the serving cells'
structure (3 components, 2 lanes, closed-loop clients) at widths a test
run can hold."""
import copy
import json
import os

import harness

HERE = os.path.dirname(os.path.abspath(__file__))

MIXES = {
    "allexit": {"thresholds": [0.0, 0.0], "n_cohorts": 1},
    "noexit": {"thresholds": [1.1, 1.1], "n_cohorts": 1},
    # the median softmax confidence of each intermediate component over a
    # prefill of the window's first prompts, 0.0378-0.0413 over eight
    # seeds at this size (the chip cell's thresholds are read the same way)
    "mixed": {"thresholds": [0.0395, 0.0395], "n_cohorts": 2},
}
# token_gap / conf_err / exit_margin limits at this size, from CPU
# readings over eight seeds per mix: the bfloat16 program read conf_err
# 0.021-0.045, token_gap <= 0.034, exit_margin <= 0.011; its float8
# control conf_err 0.17-0.37 (test_chip_control.py checks the bracket)
LIMITS = {"sample_requests": 4, "token_gap": 0.1, "conf_err": 0.08,
          "exit_margin": 0.04}


def config(**over) -> dict:
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        c = json.load(f)
    c.update(over)
    return c


def cell(mix: str, limits=None, **config_over) -> harness.Cell:
    traffic = {"generator": "closed_loop", "clients": 8, "prompt_len": 16,
               "max_new_tokens": 8}
    traffic.update(MIXES[mix])
    name = "tiny." + mix
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = copy.deepcopy(json.load(f))
    bench["workloads"].append({"name": name, "config": "tiny",
                               "traffic": mix, "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        m.setdefault("workloads", []).append(name)
    return harness.Cell(name=name, chips=1, config=config(**config_over),
                        traffic=traffic, limits=dict(limits or LIMITS),
                        benchmark=bench)


def run(c: harness.Cell, seed: int, seconds: float = 1.5, **kw) -> dict:
    import time
    return harness.run(c, seed, seconds, False, time.perf_counter(),
                       require_tpu=False, log=lambda *a: None, **kw)
