#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload sweep.hpcc --seed 7 --seconds 36 --trace 0

The cells, their metrics and their bounds are in ``BENCHMARK.json`` at
the root of the checkout.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace.
The run refuses (exit code 3, no result) where JAX finds no accelerator
or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace of --trace 1 here")
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    # bench/ itself off the path: its module names are not top-level.
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import harness
    harness.configure_jax_env()
    try:
        harness.run_cell(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START,
                         trace_dir=args.trace_dir)
    except harness.Refused as exc:
        print(f"bench: refused: {exc}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
