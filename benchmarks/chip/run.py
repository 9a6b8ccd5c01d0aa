#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic mix by name (``harness.py``),
makes the weights on the chip from the seed, warms up every shape the mix
uses, measures for ``--seconds``, holds what the timed path served to the
float32 reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics and the
trace's ``breakdown``), ``device`` and, last, ``checks`` (each number
compared beside its limit, also the last lines on standard error).

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.  JAX's persistent compilation cache lives in
``.jax_cache`` at the root of the checkout.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(REPO, "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    try:
        cell = harness.load_cell(args.workload)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if args.trace else None
        try:
            out = harness.run(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS,
                              trace_dir=trace_dir)
        finally:
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
    except harness.SetupError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
