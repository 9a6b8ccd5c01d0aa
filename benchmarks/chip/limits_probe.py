#!/usr/bin/env python3
"""Readings that set a cell's output limits (``limits/<cell>.json``), in
one process on the chip:

    python3 benchmarks/chip/limits_probe.py --workload <cell> \
        --seeds 101,102,... --control-seeds 101,102,103 --seconds 12

Each seed is one whole run of the cell (``harness.run``) with a short
window; on the control seeds the float8 control is read at the same
positions too.  One JSON line per seed goes to standard output, then a
summary: the largest reading of the program over its seeds (the lower
reading) and the smallest of the control over its seeds (the upper one),
per number.  The benchmark's own runs never read the control.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(REPO, "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)

    import harness
    import compare
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    prog, bad = {k: [] for k in compare.NAMES}, {k: [] for k in compare.NAMES}
    for seed in seeds:
        t0 = time.perf_counter()
        out = harness.run(cell, seed, args.seconds, False, t0,
                          control=seed in ctrl)
        row = {"seed": seed, "correct": out["correct"],
               "checks": {k: v["value"] for k, v in out["checks"].items()},
               "control": out.get("control"),
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        for k in compare.NAMES:
            prog[k].append(row["checks"][k])
            if row["control"] is not None:
                bad[k].append(row["control"][k])
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "lower": {k: max(v) for k, v in prog.items()},
                      "upper": {k: (min(v) if v else None)
                                for k, v in bad.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
