"""The peaks table, and the harness's refusal to measure without a chip."""
import json
import os
import subprocess
import sys

import pytest

import harness

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_v5e_peaks():
    for kind in ("TPU v5 lite", "TPU v5e"):
        p = harness.peaks_for(kind)
        assert p["bf16_flops_per_s"] == 197e12
        assert p["hbm_bytes_per_s"] == 819e9
    with open(os.path.join(CHIP, "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.SetupError):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(harness.SetupError):
        harness.peaks_for("cpu")


def test_harness_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         "qwen2.5-3b.batch-allexit", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=240)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        for k in ("token_gap", "conf_err", "exit_margin"):
            assert cell.limits[k] >= 0
        gen = harness.generator_module(cell.traffic)
        assert hasattr(gen, "Generator")
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(CHIP, "metrics",
                                           m["name"] + ".py"))
