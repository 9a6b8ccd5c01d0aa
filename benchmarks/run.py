"""Benchmark driver — one benchmark per paper table/figure plus the
beyond-paper LLM-cascade and kernel benches.

Prints ``name,us_per_call,derived`` CSV (and tees a copy to
results/bench.csv when results/ exists).  Whenever the llm_cascade bench
runs its host-vs-device serving comparison, the machine-readable summary
(wall-clock µs/token per runtime, device_speedup, realized skip rate,
opportunity rate, MAC speedup, compile seconds) is persisted to
``BENCH_serving.json`` at the repo root so the serving perf trajectory is
tracked across PRs.

    python benchmarks/run.py [--quick] [--only llm_cascade,fig3]

``--quick`` shrinks workloads (CI smoke lanes); ``--only`` selects benches.
"""
import argparse
import inspect
import json
import os
import sys
import traceback

# runnable as `python benchmarks/run.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# BENCH_serving.json summary schema: bump when a section's shape changes
# incompatibly.  The checker warns (not fails) on versions it does not
# know, so an old checker can still gate what it understands.
SCHEMA_VERSION = 2


def _run_meta() -> dict:
    """Run provenance stamped into the summary: which stack measured it."""
    import platform

    import jax
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "platform": platform.platform(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller workloads (CI smoke lanes)")
    ap.add_argument("--only", default="",
                    help="comma-separated bench names to run")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_table2, bench_fig3, bench_fig4,
                            bench_llm_cascade, bench_kernels,
                            bench_ablation, bench_autotune, bench_fleet,
                            bench_obs)
    mods = [("table2", bench_table2), ("fig3", bench_fig3),
            ("fig4", bench_fig4), ("ablation", bench_ablation),
            ("llm_cascade", bench_llm_cascade), ("kernels", bench_kernels),
            ("autotune", bench_autotune), ("fleet", bench_fleet),
            ("obs", bench_obs)]
    if args.only:
        wanted = {w.strip() for w in args.only.split(",") if w.strip()}
        unknown = wanted - {n for n, _ in mods}
        if unknown:
            sys.exit(f"unknown bench(es): {sorted(unknown)}")
        mods = [(n, m) for n, m in mods if n in wanted]
    lines = ["name,us_per_call,derived"]
    failed = False
    for name, mod in mods:
        try:
            kwargs = {}
            if "quick" in inspect.signature(mod.run).parameters:
                kwargs["quick"] = args.quick
            for row_name, us, derived in mod.run(**kwargs):
                lines.append(f"{row_name},{us:.1f},{derived}")
        except Exception as e:
            failed = True
            lines.append(f"{name}/ERROR,0.0,{type(e).__name__}:{e}")
            traceback.print_exc()
    out = "\n".join(lines)
    print(out)
    if os.path.isdir("results"):
        with open("results/bench.csv", "w") as f:
            f.write(out + "\n")
    summary = getattr(bench_llm_cascade, "LAST_SERVING_SUMMARY", None)
    autotune = getattr(bench_autotune, "LAST_AUTOTUNE_SUMMARY", None)
    fleet = getattr(bench_fleet, "LAST_FLEET_SUMMARY", None)
    kernels = getattr(bench_kernels, "LAST_KERNELS_SUMMARY", None)
    obs = getattr(bench_obs, "LAST_OBS_SUMMARY", None)
    sections = {"autotune": autotune, "fleet": fleet, "kernels": kernels,
                "obs": obs}
    if summary is not None or any(v is not None for v in sections.values()):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(root, "BENCH_serving.json")
        # partial runs (--only) update their section and keep the rest
        data = {}
        if os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
        if summary is not None:
            keep = {k: data.get(k) for k in sections}
            data = dict(summary)
            for k, v in keep.items():
                if v is not None:
                    data[k] = v
        for k, v in sections.items():
            if v is not None:
                data[k] = v
        data["schema_version"] = SCHEMA_VERSION
        data["meta"] = _run_meta()
        with open(path, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")
        print(f"# serving summary -> {path}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
