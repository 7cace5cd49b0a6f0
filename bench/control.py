#!/usr/bin/env python3
"""The lower-precision control of a cell, read beside the program.

    python3 bench/control.py --workload sweep.hpcc --seeds 11,12,13 --seconds 8

For each seed: set the cell up, run a short window at the cell's own
load, then read the cell's compared number twice, once for what the
program produced and once for the control put in its place (the kind's
``control``, ``bench/kinds/<kind>.py``): the float64 reference computed
in bfloat16 for the sweep and the plane, the program's own bfloat16
demand stream for the tune.

The control has to come out as not correct: its number above the
cell's limit.  The benchmark's own runs never run this.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", type=int, default=None,
                    help="read the control on the first N seeds only "
                         "(default: every seed)")
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    sys.path.insert(0, ROOT)
    from bench import harness
    harness.configure_jax_env()
    import jax
    from bench import fleet, generator
    if jax.devices()[0].platform == "cpu":
        print("control: JAX found no accelerator", file=sys.stderr)
        return 3
    spec = harness.cell_spec(harness.load_benchmark(), args.workload)
    cell = spec["cell"]
    cfg = fleet.load_json("configs", cell["config"])
    traffic = fleet.load_json("traffic", cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    n_ctl = len(seeds) if args.controls is None else args.controls
    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        d = generator.make(cfg, traffic, seed, int(cell["chips"]))
        d.setup()
        _, n = harness.run_window(d, args.seconds)
        d.free()
        checks = d.check()
        ctl = d.control() if k < n_ctl else {}
        line = " ".join(
            f"{name}: program={value!r} control={ctl.get(name)!r} "
            f"limit={limit!r};" for name, value, limit in checks)
        fails = (any(ctl.get(name, 0.0) > limit for name, _, limit in checks)
                 if ctl else None)
        print(f"control: workload={args.workload} seed={seed} calls={n} "
              f"{line} control_fails={fails} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
