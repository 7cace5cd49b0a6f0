"""ScenarioLab sweep-engine benchmarks.

Times the fleet-scale closed loop (phase-shifted HPCC demand, paper
Table I gains) across engines and knobs:

* ``python_loop``  -- ``simulate_fleet(engine="python")``: one fused
  jitted step per interval, re-entering Python T times.
* ``lab_scan``     -- ``simulate_fleet(engine="lab")``: the whole
  horizon as one jitted ``lax.scan`` (single dispatch).
* ``lab_sweep_G``  -- the device-resident engine amortized over a
  G-point gain grid: histories never leave the device (streamed stats
  + fixed-bin quantile bisection), O(G) bytes per chunk to the host.
* ``lab_sweep_cache_G`` -- the same sweep with CacheLoop enabled
  (resident set, hit curve, evict/refill flux, modeled app runtime in
  the scan carry): the cache-dynamics overhead over the saturated
  store.
* ``pallas_sweep_G`` / ``pallas_sweep_cache_G`` -- PR 9's fused
  PallasSweep engine (``engine="pallas"``) on the same grid.
* ``pallas_halving_cache_512`` -- in-scan successive halving over 512
  cache-on candidates in ONE dispatch.  Its throughput is the
  **grid-equivalent effective rate**: G*T*N updates a grid tuner would
  have run, divided by the halving wall time (the kernel masks
  dominated lanes dead at T/8 and T/2, executing ~27% of the
  lane-steps).  ``--engine both`` gates this row at >= 10x the
  same-run ``lab_sweep_cache_G`` throughput -- the PR-9 acceptance
  claim, measured on the same machine in the same process.

The figure of merit is **node*interval*config closed-loop updates per
second**.  Writes two artifacts at the repo root:

* ``BENCH_lab.json``   -- headline ``sweep_throughput`` rows plus a
  ``smoke_reference`` section (the small shape CI re-measures).
* ``BENCH_sweep.json`` -- ``chunked_throughput`` (chunk-size sweep on
  the device-resident path), ``device_scaling`` (gain axis
  ``shard_map``'d over forced host devices), ``time_to_best`` (grid vs
  successive-halving time-to-best-gain on swap-storm), and
  ``smoke_reference_pallas`` (the PallasSweep smoke rows CI gates).

Usage:

    PYTHONPATH=src python benchmarks/lab_bench.py [--nodes 4096]
    PYTHONPATH=src python benchmarks/lab_bench.py --smoke --engine both \
        --check-baseline BENCH_lab.json \
        --check-pallas-baseline BENCH_sweep.json   # CI regression gates

The smoke run times the small reference shape only (no artifacts
unless ``--out``/``--sweep-out`` is given) and, with
``--check-baseline``, fails if the sweep speedup over the same-run
``python_loop`` row regresses more than ``--max-regress`` (default
20%) against the checked-in ``smoke_reference`` -- normalizing by the
python-loop row keeps the gate honest across machine speeds.
``--check-pallas-baseline`` applies the same ratio-of-ratios gate to
the PallasSweep rows against ``smoke_reference_pallas``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

REPEATS = 3
SMOKE_SHAPE = dict(n_nodes=256, n_intervals=300, n_configs=16)


def _best(fn) -> float:
    """Best-of-N wall time, after a warmup call that pays compilation."""
    fn()
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def _row(name: str, n_nodes: int, n_intervals: int, configs: int,
         elapsed: float, **extra) -> dict:
    work = n_nodes * n_intervals * configs
    return {"engine": name, "n_nodes": n_nodes, "n_intervals": n_intervals,
            "n_configs": configs, "elapsed_s": elapsed,
            "throughput_upd_per_s": work / elapsed, **extra}


def _bench_gains(n_configs: int):
    """The benchmark's canonical ~n_configs (lam x r0) grid."""
    from repro.core.cluster_sim import paper_controller_params
    from repro.lab import grid_gains
    k = max(int(np.sqrt(n_configs)), 2)
    return grid_gains(paper_controller_params(),
                      lam=np.linspace(0.1, 1.8, k),
                      r0=np.linspace(0.88, 0.98, k))


def bench_engines(n_nodes: int, n_intervals: int, n_configs: int,
                  seed: int = 0) -> list:
    """The headline engine comparison at one (nodes, intervals) shape."""
    from repro.core.cluster_sim import paper_controller_params, simulate_fleet
    from repro.core.traces import fleet_demand_traces
    from repro.lab import sweep_demand

    p = paper_controller_params()
    rows = [
        _row("python_loop", n_nodes, n_intervals, 1,
             _best(lambda: simulate_fleet(n_nodes, n_intervals, seed=seed,
                                          engine="python"))),
        _row("lab_scan", n_nodes, n_intervals, 1,
             _best(lambda: simulate_fleet(n_nodes, n_intervals, seed=seed,
                                          engine="lab"))),
    ]
    # The sweep amortizes demand compilation across the grid: time only
    # the engine, as a tuner (which builds demand once) experiences it.
    demand = fleet_demand_traces(n_nodes, n_intervals, p.interval_s,
                                 seed=seed)
    gains = _bench_gains(n_configs)
    rows.append(_row(
        f"lab_sweep_{len(gains)}", n_nodes, n_intervals, len(gains),
        _best(lambda: sweep_demand(demand, gains, node_memory=p.total_memory,
                                   interval_s=p.interval_s))))
    # CacheLoop overhead: same grid with cache dynamics in the carry.
    from repro.lab import get_scenario
    cache = get_scenario("spark-iterative-cache").cache
    rows.append(_row(
        f"lab_sweep_cache_{len(gains)}", n_nodes, n_intervals, len(gains),
        _best(lambda: sweep_demand(demand, gains, node_memory=p.total_memory,
                                   interval_s=p.interval_s, cache=cache))))
    base = rows[0]["throughput_upd_per_s"]
    for r in rows:
        r["speedup_vs_python_loop"] = r["throughput_upd_per_s"] / base
    return rows


HALVING_CANDIDATES = 512
TENX_FLOOR = 10.0


def _halving_gains(n: int):
    """An n-point (lam x r0) grid (the smallest k x k grid covering n,
    sliced to exactly n lanes)."""
    k = int(np.ceil(np.sqrt(n)))
    return _bench_gains(k * k).take(np.arange(n))


def bench_pallas(n_nodes: int, n_intervals: int, n_configs: int,
                 xla_rows: list, seed: int = 0) -> list:
    """PallasSweep rows at the same shape as :func:`bench_engines`.

    ``xla_rows`` is the same-run output of :func:`bench_engines`: each
    pallas row's ``speedup_vs_xla`` divides by the matching same-run
    XLA row (sweep vs sweep, cache vs cache, halving vs the cache
    sweep it replaces), so both the baseline gate and the >= 10x claim
    are same-process, same-machine comparisons.
    """
    from repro.core.cluster_sim import paper_controller_params
    from repro.core.traces import fleet_demand_traces
    from repro.lab import GainSet, get_scenario, sweep_demand
    from repro.lab.pallas_sweep import halving_sweep

    p = paper_controller_params()
    demand = fleet_demand_traces(n_nodes, n_intervals, p.interval_s,
                                 seed=seed)
    gains = _bench_gains(n_configs)
    cache = get_scenario("spark-iterative-cache").cache
    kw = dict(node_memory=p.total_memory, interval_s=p.interval_s)
    rows = [
        _row(f"pallas_sweep_{len(gains)}", n_nodes, n_intervals, len(gains),
             _best(lambda: sweep_demand(demand, gains, engine="pallas",
                                        **kw))),
        _row(f"pallas_sweep_cache_{len(gains)}", n_nodes, n_intervals,
             len(gains),
             _best(lambda: sweep_demand(demand, gains, engine="pallas",
                                        cache=cache, **kw))),
    ]
    # In-scan halving: one dispatch tunes HALVING_CANDIDATES cache-on
    # lanes.  throughput_upd_per_s is the grid-equivalent effective
    # rate (G*T*N over the halving wall time); lane_steps_frac records
    # how much of that grid the masked kernel actually executed.
    big = _halving_gains(HALVING_CANDIDATES)
    base = GainSet.from_params(p)
    el = _best(lambda: halving_sweep(demand, big, base, cache=cache, **kw))
    from repro.lab.pallas_sweep import TILE_GAINS, halving_schedule
    horizons, keeps = halving_schedule(
        n_intervals, len(big), (0.125, 0.5, 1.0), 0.25, 4)
    pad = lambda n: -(-n // TILE_GAINS) * TILE_GAINS
    counts = [len(big) + 1] + [k + 1 for k in keeps]
    lane_steps = sum(pad(c) * (h - h0) for c, h, h0 in
                     zip(counts, horizons, [0] + horizons[:-1]))
    halving_row = _row(
        f"pallas_halving_cache_{len(big)}", n_nodes, n_intervals,
        len(big), el,
        effective="grid-equivalent",
        lane_steps_frac=lane_steps / (len(big) * n_intervals))
    rows.append(halving_row)
    # Normalize by the same-run XLA rows, not python_loop: both sides
    # are compute-bound scans of the same math, so the ratio is stable
    # across machines (python_loop is dispatch-bound and skews 2-3x
    # between hosts, which would poison a checked-in baseline).
    xla = {r["engine"]: r for r in xla_rows}
    ref_of = {
        f"pallas_sweep_{len(gains)}": f"lab_sweep_{len(gains)}",
        f"pallas_sweep_cache_{len(gains)}": f"lab_sweep_cache_{len(gains)}",
        halving_row["engine"]: f"lab_sweep_cache_{len(gains)}",
    }
    for r in rows:
        ref = xla.get(ref_of[r["engine"]])
        if ref:
            r["speedup_vs_xla"] = (r["throughput_upd_per_s"]
                                   / ref["throughput_upd_per_s"])
    if "speedup_vs_xla" in halving_row:
        halving_row["cache_on_speedup_vs_xla"] = \
            halving_row["speedup_vs_xla"]
    return rows


def check_tenx_gate(pallas_rows: list) -> int:
    """The PR-9 acceptance claim as a hard CI gate: the in-scan halving
    row's grid-equivalent rate >= 10x the same-run XLA cache-on sweep."""
    row = next((r for r in pallas_rows
                if r["engine"].startswith("pallas_halving_cache")), None)
    if row is None or "cache_on_speedup_vs_xla" not in row:
        print("# 10x gate: no halving row to check")
        return 1
    ratio = row["cache_on_speedup_vs_xla"]
    ok = ratio >= TENX_FLOOR
    print(f"# 10x gate: in-scan halving effective rate is {ratio:.1f}x the "
          f"same-run XLA cache-on sweep (floor {TENX_FLOOR:.0f}x) -> "
          f"{'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


def bench_chunks(n_nodes: int, n_intervals: int, n_configs: int,
                 seed: int = 0) -> list:
    """Device-resident throughput vs gain-chunk width (incl. auto)."""
    from repro.core.cluster_sim import paper_controller_params
    from repro.core.traces import fleet_demand_traces
    from repro.lab import sweep_demand

    p = paper_controller_params()
    demand = fleet_demand_traces(n_nodes, n_intervals, p.interval_s,
                                 seed=seed)
    gains = _bench_gains(n_configs)
    rows = []
    for chunk in (8, 32, 64, None):
        el = _best(lambda: sweep_demand(
            demand, gains, node_memory=p.total_memory,
            interval_s=p.interval_s, chunk=chunk))
        rows.append(_row(f"chunk_{'auto' if chunk is None else chunk}",
                         n_nodes, n_intervals, len(gains), el))
    return rows


def _time_device_count(n_nodes: int, n_intervals: int, n_configs: int,
                       ndev: int) -> dict:
    """Best-of-3 sweep wall time with the gain axis on ``ndev`` devices."""
    from repro.core.cluster_sim import paper_controller_params
    from repro.core.traces import fleet_demand_traces
    from repro.lab import grid_gains, sweep_demand

    p = paper_controller_params()
    demand = fleet_demand_traces(n_nodes, n_intervals, p.interval_s, seed=0)
    k = max(int(np.sqrt(n_configs)), 2)
    gains = grid_gains(p, lam=np.linspace(0.1, 1.8, k),
                       r0=np.linspace(0.88, 0.98, k))
    el = _best(lambda: sweep_demand(demand, gains,
                                    node_memory=p.total_memory,
                                    interval_s=p.interval_s, devices=ndev))
    return {"elapsed_s": el, "n_configs": len(gains)}


_SCALING_SNIPPET = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
sys.path.insert(0, %r)
from lab_bench import _time_device_count
print(json.dumps(_time_device_count(%d, %d, %d, %d)))
"""


def bench_device_scaling(n_nodes: int, n_intervals: int, n_configs: int,
                         device_counts=None) -> list:
    """Gain-axis shard_map scaling over 1 and several devices.

    On an accelerator every count runs in this process over the real
    local devices (default: 1 and all of them), since a chip belongs to
    one process.  On the CPU each count runs in a subprocess with
    forced host devices (default: 1 and 2), because XLA fixes the host
    device count at first jax init.  A failed count raises.
    """
    import jax

    on_cpu = jax.default_backend() == "cpu"
    if device_counts is None:
        device_counts = ((1, 2) if on_cpu else
                         tuple(sorted({1, len(jax.local_devices())})))
    here = os.path.dirname(os.path.abspath(__file__))
    rows = []
    for ndev in device_counts:
        if not on_cpu:
            out = _time_device_count(n_nodes, n_intervals, n_configs, ndev)
        else:
            code = _SCALING_SNIPPET % (ndev, here, n_nodes, n_intervals,
                                       n_configs, ndev)
            env = dict(os.environ)
            env["PYTHONPATH"] = env.get("PYTHONPATH") or "src"
            proc = subprocess.run([sys.executable, "-c", code],
                                  capture_output=True, text=True, env=env,
                                  timeout=1800)
            if proc.returncode != 0:
                raise RuntimeError(f"device_scaling ndev={ndev} failed:\n"
                                   f"{proc.stderr[-1500:]}")
            out = json.loads(proc.stdout.strip().splitlines()[-1])
        rows.append(_row(f"devices_{ndev}", n_nodes, n_intervals,
                         out["n_configs"], out["elapsed_s"]))
    base = rows[0]["throughput_upd_per_s"]
    for r in rows:
        r["scaling_vs_1_device"] = r["throughput_upd_per_s"] / base
    return rows


def bench_time_to_best(scenario: str = "swap-storm", budget: int = 64,
                       seed: int = 0) -> list:
    """Grid vs successive halving: wall-clock to the best gain point.

    Times the warm (executables compiled) search, the steady state a
    retuning deployment lives in; `compile_s` reports the one-time
    cost.
    """
    from repro.lab import tune_gains

    rows = []
    for method in ("grid", "halving"):
        run = lambda: tune_gains(scenario, method=method, budget=budget,
                                 seed=seed)
        t0 = time.perf_counter()
        result = run()
        cold = time.perf_counter() - t0
        warm = _best(run)
        rows.append({
            "method": method, "scenario": scenario, "budget": budget,
            "best_score": result.score,
            "best_r0": result.params.r0, "best_lam": result.params.lam,
            "wall_s_warm": warm, "compile_s": cold - warm,
        })
    g, h = rows
    h["wall_vs_grid"] = h["wall_s_warm"] / g["wall_s_warm"]
    h["reaches_grid_best"] = bool(h["best_score"] >= g["best_score"] - 1e-9)
    return rows


def check_baseline(smoke_rows: list, baseline_path: str,
                   max_regress: float, section: str = "smoke_reference",
                   prefix: str = "lab_sweep",
                   ratio_key: str = "speedup_vs_python_loop") -> int:
    """Compare the smoke sweep speedups against the checked-in ones.

    Every ``{prefix}*`` row present in both runs is gated (the
    cache-off sweep AND the CacheLoop sweep), each normalized by its
    own run's ``python_loop`` row so runner speed cancels.  The pallas
    gate reuses this ratio-of-ratios with ``section=
    "smoke_reference_pallas"``/``prefix="pallas"`` and the
    cross-engine ``speedup_vs_xla`` ratio (compute-bound on both
    sides, so it cancels machine skew that the dispatch-bound
    python_loop row does not).
    """
    with open(baseline_path) as fh:
        doc = json.load(fh)
    ref_rows = doc.get(section) or []
    ref = {r["engine"]: r for r in ref_rows}
    now = {r["engine"]: r for r in smoke_rows}
    names = [n for n in now if n.startswith(prefix) and n in ref]
    if not names:
        print(f"# no comparable {section} sweep row in "
              f"{baseline_path}; nothing to check")
        return 0
    failed = False
    for name in names:
        ref_ratio = ref[name][ratio_key]
        now_ratio = now[name][ratio_key]
        floor = ref_ratio * (1.0 - max_regress)
        ok = now_ratio >= floor
        failed |= not ok
        print(f"# {name} {ratio_key}: now {now_ratio:.2f}x, "
              f"baseline {ref_ratio:.2f}x, floor {floor:.2f}x -> "
              f"{'OK' if ok else 'REGRESSION'}")
    return 1 if failed else 0


def print_rows(title: str, rows: list) -> None:
    if not rows:
        return
    print(f"\n# {title}")
    cols = []
    for r in rows:
        cols.extend(k for k in r if k not in cols)
    print("  ".join(c.rjust(max(len(c), 12)) for c in cols))
    for r in rows:
        cells = []
        for c in cols:
            v = r.get(c)
            s = f"{v:.4g}" if isinstance(v, float) else ("" if v is None
                                                         else str(v))
            cells.append(s.rjust(max(len(c), 12)))
        print("  ".join(cells))


def main() -> int:
    ap = argparse.ArgumentParser()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap.add_argument("--out", default=None,
                    help="BENCH_lab.json path (default: repo root; "
                         "omitted in --smoke unless given)")
    ap.add_argument("--sweep-out", default=None,
                    help="BENCH_sweep.json path (same default rules)")
    ap.add_argument("--nodes", type=int, default=4096)
    ap.add_argument("--intervals", type=int, default=1000)
    ap.add_argument("--configs", type=int, default=64)
    ap.add_argument("--smoke", action="store_true",
                    help="small-shape engine rows only; fast enough "
                         "for a CI job")
    ap.add_argument("--check-baseline", default=None, metavar="PATH",
                    help="compare smoke speedups against this checked-in "
                         "artifact; non-zero exit on regression")
    ap.add_argument("--check-pallas-baseline", default=None, metavar="PATH",
                    help="ratio-of-ratios gate for the pallas rows against "
                         "this artifact's smoke_reference_pallas section")
    ap.add_argument("--max-regress", type=float, default=0.2)
    ap.add_argument("--engine", choices=("xla", "pallas", "both"),
                    default="xla",
                    help="which sweep engines to bench; pallas/both adds "
                         "the PallasSweep rows and the 10x halving gate")
    args = ap.parse_args()

    from repro.analysis.runtime import (excess_traces, reset_trace_counts,
                                        sanitizers_enabled, trace_counts)

    if args.smoke:
        # record_trace only counts with the sanitizers on; enable them
        # before the first dispatch -- an executable compiled before
        # that sits in the jit cache and would never be counted, so the
        # recompile gate below would vacuously pass.
        os.environ.setdefault("PLANECHECK_SANITIZERS", "1")
    reset_trace_counts()
    smoke_rows = bench_engines(**SMOKE_SHAPE)
    print_rows("smoke shape "
               f"({SMOKE_SHAPE['n_nodes']}x{SMOKE_SHAPE['n_intervals']})",
               smoke_rows)
    pallas_rows = []
    if args.engine in ("pallas", "both"):
        pallas_rows = bench_pallas(xla_rows=smoke_rows, **SMOKE_SHAPE)
        print_rows("PallasSweep smoke rows", pallas_rows)

    if args.smoke:
        status = 0
        # PR 3's time-to-best claim as a checked invariant: every
        # (chunk, horizon) shape the smoke rows dispatched must map to
        # exactly one compiled executable (PlaneCheck recompile
        # counter).  The "lab.sweep." prefix covers both engines'
        # dispatch keys (chunk loop + pallas specializations).
        if sanitizers_enabled():
            counts = trace_counts("lab.sweep.")
            excess = excess_traces("lab.sweep.")
            print(f"\nrecompile counter: "
                  f"{counts or '(no jitted sweeps ran)'}")
            if excess:
                print(f"FAIL: sweep hot path retraced: {excess}")
                return 1
        else:
            # setdefault above respects an explicit opt-out; say so
            # instead of printing a vacuously-empty counter.
            print("\nrecompile gate skipped (PLANECHECK_SANITIZERS "
                  "explicitly disabled)")
        if pallas_rows:
            status |= check_tenx_gate(pallas_rows)
        if args.out:
            doc = {"smoke_reference": smoke_rows}
            if pallas_rows:
                doc["smoke_reference_pallas"] = pallas_rows
            with open(args.out, "w") as fh:
                json.dump(doc, fh, indent=2)
            print(f"\nwrote {args.out}")
        if args.check_baseline:
            status |= check_baseline(smoke_rows, args.check_baseline,
                                     args.max_regress)
        if args.check_pallas_baseline and pallas_rows:
            status |= check_baseline(
                pallas_rows, args.check_pallas_baseline, args.max_regress,
                section="smoke_reference_pallas", prefix="pallas",
                ratio_key="speedup_vs_xla")
        return status

    rows = bench_engines(args.nodes, args.intervals, args.configs)
    chunk_rows = bench_chunks(args.nodes, args.intervals, args.configs)
    scaling_rows = bench_device_scaling(args.nodes, args.intervals,
                                        args.configs)
    ttb_rows = bench_time_to_best()

    print_rows(f"engines ({args.nodes}x{args.intervals})", rows)
    print_rows("chunked device-resident throughput", chunk_rows)
    print_rows("device scaling (forced host devices)", scaling_rows)
    print_rows("time-to-best-gain (swap-storm, 64+1 candidates)", ttb_rows)

    out = args.out or os.path.join(root, "BENCH_lab.json")
    with open(out, "w") as fh:
        json.dump({"sweep_throughput": rows,
                   "smoke_reference": smoke_rows}, fh, indent=2)
    sweep_out = args.sweep_out or os.path.join(root, "BENCH_sweep.json")
    sweep_doc = {"chunked_throughput": chunk_rows,
                 "device_scaling": scaling_rows,
                 "time_to_best": ttb_rows}
    if pallas_rows:
        sweep_doc["smoke_reference_pallas"] = pallas_rows
    with open(sweep_out, "w") as fh:
        json.dump(sweep_doc, fh, indent=2)
    print(f"\nwrote {out}\nwrote {sweep_out}")
    status = check_tenx_gate(pallas_rows) if pallas_rows else 0
    if args.check_baseline:
        status |= check_baseline(smoke_rows, args.check_baseline,
                                 args.max_regress)
    return status


if __name__ == "__main__":
    sys.exit(main())
