"""ScenarioLab end to end: sweep a gain grid, deploy the winner.

Tunes the DynIMS gains for one named scenario -- thousands of closed
loops (gain grid x fleet x horizon) compiled into one scanned/vmapped
device-resident program -- prints the leaderboard against the paper's
Table I defaults, then attaches the tuned ``ControllerParams`` to a
live ``MemoryPlane`` and replays a burst through it.

    PYTHONPATH=src python examples/tune_gains.py [scenario] [--budget N]
    PYTHONPATH=src python examples/tune_gains.py --method halving ...
    PYTHONPATH=src python examples/tune_gains.py --all   # retune presets
    PYTHONPATH=src python examples/tune_gains.py \
        --portfolio swap-storm bursty-serving   # worst-case tuning
    PYTHONPATH=src python examples/tune_gains.py \
        spark-iterative-cache --objective runtime   # CacheLoop: tune for
                                                    # modeled app runtime
    PYTHONPATH=src python examples/tune_gains.py --check-presets
        # preset-drift gate: regenerate every LAB_TUNED preset on its
        # tuning grid and exit 1 with a diff if configs/dynims.py is
        # stale relative to the tuning code (CI runs this)
    PYTHONPATH=src python examples/tune_gains.py --engine pallas ...
        # any of the above on PR 9's fused PallasSweep engine; presets
        # must regenerate identically on either engine
"""

import argparse
import sys

from repro.configs.dynims import (LAB_TUNED, LAB_TUNED_OBJECTIVES,
                                  tuned_scenarios)
from repro.core import (GiB, MemoryPlane, NodeSpec, PlaneSpec, ShardCache,
                        SimulatedMonitor, StoreSpec)
from repro.lab import (OBJECTIVES, get_scenario, list_scenarios, tune_gains,
                       tune_portfolio)
from repro.launch.compile_cache import enable_compile_cache


def tune_one(name: str, budget: int, method: str = "grid",
             objective: str = "default", engine: str = "xla"):
    spec = get_scenario(name)
    print(f"== {name}: {spec.description or spec.family}")
    print(f"   fleet={spec.n_nodes} nodes x {spec.n_intervals} intervals, "
          f"~{budget}+1 gain candidates, method={method}, "
          f"objective={objective}, engine={engine}")
    result = tune_gains(name, budget=budget, method=method,
                        objective=objective, engine=engine)
    if result.rounds:
        sched = " -> ".join(f"{r['n_candidates']}@T={r['horizon']}"
                            for r in result.rounds)
        print(f"   halving schedule: {sched}")
    print(result.summary())
    print()
    return result


def deploy(result) -> None:
    """Drive one burst through a MemoryPlane running the tuned gains."""
    p = result.params
    cache = ShardCache(capacity=p.u_max)
    for shard in range(int(p.u_max / GiB)):
        cache.put(shard, type("Blob", (), {"nbytes": 1 * GiB})())
    compute = [30 * GiB] * 6 + [95 * GiB] * 10 + [30 * GiB] * 14
    plane = MemoryPlane(PlaneSpec(
        params=p,
        nodes=(NodeSpec(
            "node0",
            monitor=SimulatedMonitor("node0", total=p.total_memory,
                                     usage=compute,
                                     storage_used_fn=cache.used),
            stores=(StoreSpec(cache, max_bytes=p.u_max),)),),
    ))
    print("deploying tuned gains on a MemoryPlane (30G base, 95G burst):")
    for i in range(len(compute)):
        a = plane.tick()[0]
        print(f"  t={i * p.interval_s:5.2f}s  util={a.utilization:5.2f}"
              f"  grant={a.u_next / GiB:6.1f} GiB"
              f"  store={cache.used() / GiB:6.1f} GiB")


_GAIN_FIELDS = ("r0", "lam", "lam_grant", "u_min", "u_max", "deadband",
                "feedforward")


def check_presets(budget: int, engine: str = "xla") -> int:
    """Preset-drift gate: are the checked-in LAB_TUNED presets what the
    tuning code produces today?

    Regenerates every preset on the default grid at ``budget`` (the
    grid the presets were derived from) under its recorded objective
    and diffs the winner against ``configs/dynims.py``.  A nonzero
    exit means the presets are stale -- rerun ``--all`` and commit the
    new values (with the finding that changed them).  ``engine=
    "pallas"`` must reproduce the same presets byte for byte (the
    grid's final ranking is computed host-side either way).
    """
    stale = []
    for name in tuned_scenarios():
        objective = LAB_TUNED_OBJECTIVES.get(name, "default")
        result = tune_gains(name, budget=budget, objective=objective,
                            engine=engine)
        preset = LAB_TUNED[name]
        diffs = [(f, getattr(preset, f), getattr(result.params, f))
                 for f in _GAIN_FIELDS
                 if getattr(preset, f) != getattr(result.params, f)]
        print(f"{name} [{objective}]: "
              f"{'STALE' if diffs else 'ok'} "
              f"(regenerated score {result.score:.3f})")
        for field, have, want in diffs:
            print(f"   {field}: preset {have!r} != regenerated {want!r}")
        if diffs:
            stale.append(name)
    if stale:
        print(f"\npreset drift in {len(stale)} scenario(s): "
              f"{', '.join(stale)}")
        print("regenerate with: python examples/tune_gains.py --all "
              f"--budget {budget}")
        return 1
    print(f"\nall {len(tuned_scenarios())} LAB_TUNED presets regenerate "
          "identically")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("scenario", nargs="?", default="bursty-serving",
                    choices=list_scenarios())
    # 100 -> the default grid the checked-in LAB_TUNED presets came
    # from (a paper-law 9x9 lam x r0 plane + the three beyond-paper law
    # variants); --all with the default budget reproduces them exactly.
    ap.add_argument("--budget", type=int, default=100)
    ap.add_argument("--method", default="grid",
                    choices=("grid", "random", "halving"))
    ap.add_argument("--objective", default="default",
                    choices=sorted(OBJECTIVES),
                    help="'runtime' optimizes CacheLoop's modeled app "
                         "runtime (cache-enabled scenarios)")
    ap.add_argument("--all", action="store_true",
                    help="retune every checked-in preset scenario")
    ap.add_argument("--check-presets", action="store_true",
                    help="preset-drift gate: regenerate every LAB_TUNED "
                         "preset and exit 1 with a diff if configs/"
                         "dynims.py is stale (CI runs this)")
    ap.add_argument("--portfolio", nargs="+", metavar="SCENARIO",
                    help="worst-case tune one gain set across these "
                         "scenarios instead of single-scenario tuning")
    ap.add_argument("--engine", default="xla", choices=("xla", "pallas"),
                    help="sweep engine: the default XLA scan or the "
                         "fused PallasSweep kernel")
    args = ap.parse_args()
    enable_compile_cache()

    if args.check_presets:
        sys.exit(check_presets(args.budget, args.engine))
    if args.portfolio:
        result = tune_portfolio(args.portfolio, budget=args.budget,
                                aggregate="worst", objective=args.objective,
                                engine=args.engine)
        print(f"== portfolio (worst-case over {', '.join(args.portfolio)})")
        for name, s in result.scenario_scores.items():
            print(f"   {name}: winner scores {s:.3f}")
        print(f"   tuned (r0={result.params.r0:.4f}, "
              f"lam={result.params.lam:.4f}) aggregate={result.score:.3f} "
              f"baseline={result.baseline_score:.3f} "
              f"(+{result.improvement:.3f})")
        return
    if args.all:
        for name in tuned_scenarios():
            objective = LAB_TUNED_OBJECTIVES.get(name, "default")
            r = tune_one(name, args.budget, args.method, objective,
                         args.engine)
            knobs = [f"r0={r.params.r0:.4f}", f"lam={r.params.lam:.4f}"]
            if r.params.lam_grant is not None:
                knobs.append(f"lam_grant={r.params.lam_grant:.4f}")
            if r.params.deadband:
                knobs.append(f"deadband={r.params.deadband:.4f}")
            if r.params.feedforward:
                knobs.append(f"feedforward={r.params.feedforward:.4f}")
            print(f"   preset: LAB_TUNED[{name!r}] = PAPER_TABLE_I.replace("
                  f"{', '.join(knobs)})\n")
        return
    result = tune_one(args.scenario, args.budget, args.method,
                      args.objective, args.engine)
    deploy(result)


if __name__ == "__main__":
    main()
