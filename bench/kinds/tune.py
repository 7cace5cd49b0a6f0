"""``tune``: back-to-back ``halving_tune`` searches.

Each call draws its own candidates from the seed (``gain_groups``),
runs ``halving_tune`` with the mix's schedule (``rounds``, ``keep``,
``min_survivors``) and ``program_args`` (``engine``, ``devices``, ...)
over the configuration's fleet, handed over as a captured trace, and
keeps what the tune answered: its final lanes, their statistics and the
winner.  The check replays one seeded tune of the window as a whole
float64 halving; the control is the program's own bfloat16 demand
stream (``halving_sweep(precision="bf16")``) on the same candidates.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

from bench import fleet, generator
from bench.reference import replay as ref

# A lane whose effective gain lam * (1 + feedforward) reaches 2 runs Eq. 1
# past its stability limit: its float32 and float64 trajectories part
# after a while, as any two roundings of an unstable loop do.  Such
# lanes are left out of the comparison by this rule (not by name).
UNSTABLE_GAIN = 2.0
# |d score / d field| of the default objective (``ref.default_score``).
# A score is only as exact as the statistics it is made of: its
# tolerance is the sum over these fields of weight times the field's
# parity tolerance (``generator.GAP_SCALE``).  Ranking and regret are
# judged in units of the two compared lanes' tolerances together, so a
# near-tie at a cut reads below 1.
SCORE_WEIGHTS = {"mean_capacity_gib": 1.0, "frac_intervals_over_r0": 200.0,
                 "pressure_violation_rate": 2000.0, "max_over_r0": 100.0,
                 "settle_intervals": 0.01, "app_slowdown": 50.0}
# The winner's pick is judged against the program's own scores, in
# units of SCORE_RTOL of the score's size.
SCORE_RTOL = 1e-4


class Kind(generator.Traffic):
    label = "halving_tune"
    spans = ("gain_draw",)

    def setup(self) -> None:
        import jax
        from repro.lab import ScenarioSpec, halving_tune
        from repro.lab.scenarios import ReplayTrace
        cfg = self.cfg
        if (jax.default_backend() != "cpu"
                and self.program_args.get("engine") == "pallas"):
            from repro.lab import pallas_sweep
            backend = pallas_sweep._backend(None)
            if backend != "mosaic":
                raise RuntimeError(f"pallas engine resolved to {backend!r} "
                                   "on the chip, not the Mosaic kernel")
        self._tune = halving_tune
        self.demand, self.m = fleet.build_fleet(cfg, self.seed)
        # The benchmark's own demand, handed over as a captured trace:
        # same-shape replay returns it exactly.
        self.spec = ScenarioSpec(
            name=cfg["name"], family="replay", n_nodes=int(cfg["n_nodes"]),
            n_intervals=int(cfg["n_intervals"]),
            interval_s=float(cfg["interval_s"]),
            node_memory_gib=float(cfg["node_memory_gib"]),
            cache=generator.cache_spec(cfg),
            replay=ReplayTrace(self.demand, self.m,
                               interval_s=float(cfg["interval_s"])))
        self.base = generator.params(cfg)
        self.gain_rng, self.sample_rng = fleet.seed_rngs(self.seed + 1, 2)
        self.drawn: List[Dict[str, np.ndarray]] = []
        self.call()

    def _run(self, gains):
        t = self.traffic
        return self._tune(self.spec, base_params=self.base,
                          gains=generator.gain_set(gains),
                          rounds=tuple(t["rounds"]), keep=float(t["keep"]),
                          min_survivors=int(t["min_survivors"]),
                          seed=self.seed, **self.program_args)

    def call(self) -> float:
        import jax
        with jax.profiler.TraceAnnotation("gain_draw"):
            gains = fleet.draw_gains(self.traffic["gain_groups"], self.law,
                                     self.gain_rng)
        with jax.profiler.TraceAnnotation(self.label):
            res = self._run(gains)
        self.drawn.append(gains)
        self.results.append(summary(gains, res))
        return float(schedule_updates(self.cfg, self.traffic))

    def e2e(self, window_s: float, n_calls: int) -> Dict[str, float]:
        return {"tune_s": window_s / n_calls}

    def free(self) -> None:
        self._tune = None

    def check(self) -> List[Tuple[str, float, float]]:
        """One seeded tune of the window against the float64 halving."""
        i = int(self.sample_rng.integers(self.first, len(self.results)))
        self.checked = i
        parts = self.compare(self.drawn[i], self.results[i])
        for name, (v, where) in parts.items():
            print(f"check: tune_gap part {name} = {v!r} at {where} (tune "
                  f"{i} of {len(self.results)})", file=generator.err())
        lim = self.traffic["limits"]
        return [(name, value, float(lim[name]))
                for name, value in compared(parts).items()]

    def compare(self, gains, got: dict) -> Dict[str, Tuple[float, str]]:
        self.want = reference_halving(self.demand, self.m, gains, self.law,
                                      self.cfg, self.traffic)
        return tune_parts(got, self.want, gains)

    def control(self) -> Dict[str, float]:
        """The program's bfloat16 demand stream on the checked tune."""
        from repro.lab import halving_tune, pallas_sweep
        self._tune = halving_tune
        gains = self.drawn[self.checked]
        orig = pallas_sweep.halving_sweep
        pallas_sweep.halving_sweep = functools.partial(orig, precision="bf16")
        try:
            res = self._run(gains)
        finally:
            pallas_sweep.halving_sweep = orig
        return compared(tune_parts(summary(gains, res), self.want, gains))


def summary(gains: Dict[str, np.ndarray], res) -> dict:
    """What a tune answered: its final lanes (indices into ``gains``),
    their statistics, and the winner (an index into the final lanes,
    the baseline last)."""
    g = res.sweep.gains
    cand = np.stack([gains[k] for k in ("r0", "lam", "lam_grant",
                                        "deadband", "feedforward")], 1)
    lanes = []
    for i in range(len(g) - 1):                      # baseline is last
        row = np.array([g.r0[i], g.lam[i], g.lam_grant[i],
                        g.deadband[i], g.feedforward[i]])
        hit = np.flatnonzero((cand == row).all(axis=1))
        lanes.append(int(hit[0]) if hit.size else -1)
    return {"lanes": lanes, "stats": generator.stats_dict(res.sweep.stats),
            "winner": int(res.index)}


def n_candidates(traffic: dict) -> int:
    return sum(int(g["n"]) for g in traffic["gain_groups"])


def halving_schedule(n_intervals: int, n_cand: int,
                     traffic: dict) -> Tuple[List[int], List[int]]:
    """Rung horizons and survivor counts of successive halving."""
    fracs = sorted(set(float(f) for f in traffic["rounds"]))
    if fracs[-1] != 1.0:
        fracs.append(1.0)
    horizons = [max(int(round(n_intervals * f)), 1) for f in fracs]
    horizons[-1] = n_intervals
    keeps, n = [], n_cand
    for _ in fracs[:-1]:
        n = min(max(int(np.ceil(n * float(traffic["keep"]))),
                    int(traffic["min_survivors"])), n)
        keeps.append(n)
    return horizons, keeps


def schedule_updates(cfg: dict, traffic: dict) -> int:
    """Node-interval updates the halving schedule needs.

    Lanes alive in each rung (the candidates kept plus the baseline)
    times the rung's intervals times the nodes; whatever padding or
    layout the program adds is not counted.
    """
    n_cand = n_candidates(traffic)
    horizons, keeps = halving_schedule(int(cfg["n_intervals"]), n_cand,
                                       traffic)
    total, prev = 0, 0
    for h, n in zip(horizons, [n_cand] + keeps):
        total += (n + 1) * (h - prev)
        prev = h
    return total * int(cfg["n_nodes"])


def baseline(law: Dict[str, float]) -> Dict[str, np.ndarray]:
    return {k: np.array([v]) for k, v in
            dict(r0=law["r0"], lam=law["lam"], lam_grant=law["lam"],
                 u_min=law["u_min"], u_max=law["u_max"], deadband=0.0,
                 feedforward=0.0).items()}


def reference_halving(demand, m, gains, law, cfg, traffic,
                      dtype=np.float64) -> dict:
    """The float64 halving: rung scores, survivors, final stats, winner.

    The rungs rank the candidates as the program's schedule does; the
    final lanes (survivors and the baseline) are then replayed from the
    start over the whole horizon with the exact 99th percentile.
    """
    n_cand = len(gains["r0"])
    horizons, keeps = halving_schedule(demand.shape[1], n_cand, traffic)
    allg = fleet.concat_gains(gains, baseline(law))
    kw = dict(interval_s=float(cfg["interval_s"]), cache=cfg.get("cache"),
              dtype=dtype)
    lanes = ref.LaneBlocks.start(demand, m, allg, **kw)
    alive = np.arange(n_cand + 1)              # original index; n_cand=base
    rungs = []
    for i, h in enumerate(horizons[:-1]):
        lanes.advance(h)
        st = lanes.stats()
        score = ref.default_score(st)
        cand = alive < n_cand
        order = np.argsort(-score[cand], kind="stable")[:keeps[i]]
        kept = np.flatnonzero(cand)[order]
        rungs.append({"lanes": alive.copy(), "score": score,
                      "tol": score_tol(st),
                      "kept": alive[kept]})
        pick = np.concatenate([kept, np.flatnonzero(~cand)])
        lanes = lanes.take(pick)
        alive = alive[pick]
    del lanes
    final = {k: v[alive] for k, v in allg.items()}
    stats = ref.replay_stats(demand, m, final, p99=True, **kw)
    return {"rungs": rungs, "final_lanes": alive, "final_stats": stats,
            "final_score": ref.default_score(stats),
            "final_tol": score_tol(stats)}


def score_tol(stats: Dict[str, np.ndarray]) -> np.ndarray:
    """Each lane's score tolerance: the parity tolerances of the fields
    the objective weighs, times their weights (:data:`SCORE_WEIGHTS`)."""
    tol = 0.0
    for f, w in SCORE_WEIGHTS.items():
        atol, rtol = generator.GAP_SCALE[f]
        tol = tol + w * (atol + rtol * np.abs(np.asarray(stats[f],
                                                         np.float64)))
    return tol


def stable(gains: Dict[str, np.ndarray]) -> np.ndarray:
    """Candidates whose effective gain stays under :data:`UNSTABLE_GAIN`."""
    lam = np.maximum(gains["lam"], gains["lam_grant"])
    return lam * (1.0 + gains["feedforward"]) < UNSTABLE_GAIN


def tune_parts(got: dict, want: dict,
               gains: Dict[str, np.ndarray]) -> Dict[str, Tuple[float, str]]:
    """The parts of the tune's compared number, each the worst over the
    stable lanes, with where it was worst:

    * ``stats``: for a final lane both kept, its worst scaled statistic
      gap, every field and the p99 with it;
    * ``rank``: for a lane the program kept and the reference dropped,
      how far below the reference's cut it scored; for one the
      reference kept and the program dropped, how far above the
      reference's best dropped lane it scored (at its closest rung);
    * ``regret``: the reference's regret for the program's winner,
      against the best stable final lane (where the winner itself is
      unstable, for the program's best stable lane by its own scores);
    * ``pick``: how far the program's winner scored below the program's
      own best final lane: a tune answers the argmax of its own scores,
      so a sound run reads 0, an unstable winner included.

    Ranking and regret are in units of the score tolerance of the two
    lanes compared (:func:`score_tol`), the pick in units of
    :data:`SCORE_RTOL`.
    """
    n_cand = len(gains["r0"])
    ok = np.append(stable(gains), True)
    final = [int(x) for x in want["final_lanes"]]
    prog = [int(x) for x in got["lanes"]] + [n_cand]
    bad = None
    if min(prog) < 0 or len(set(prog)) != len(prog):
        bad = "unknown or repeated survivor"
    elif len(prog) != len(final):
        bad = f"{len(prog)} final lanes, reference {len(final)}"
    if bad:
        return {k: (float("inf"), bad)
                for k in ("stats", "rank", "regret", "pick")}
    fs = want["final_stats"]
    stats, rank = [(0.0, "")], [(0.0, "")]
    for j, lane in enumerate(prog):
        if not ok[lane]:
            continue
        if lane in final:
            q = final.index(lane)
            g, f = generator.stats_gap({k: got["stats"][k][[j]] for k in fs},
                                       {k: v[[q]] for k, v in fs.items()})
            stats.append((g, f"lane {lane} {f}"))
        else:
            rank.append((_shortfall(lane, want),
                         f"lane {lane} kept, reference dropped"))
    for lane in final:
        if ok[lane] and lane not in prog:
            rank.append((_margin(lane, want),
                         f"lane {lane} dropped, reference kept"))
    ws = want["final_score"]
    best = max(float(ws[q]) for q, lane in enumerate(final) if ok[lane])
    own = ref.default_score(got["stats"])
    top = float(np.max(own))
    pick = max(top - float(own[got["winner"]]), 0.0) / (abs(top) + 1.0)
    w = prog[got["winner"]]
    if not ok[w]:
        # An unstable winner's reference trajectory is not the program's:
        # judge the program's best stable lane, by its own scores.
        w = max((j for j, lane in enumerate(prog) if ok[lane]),
                key=lambda j: own[j])
        w = prog[w]
    if w in final:
        q, b = final.index(w), int(np.argmax(np.where(ok[final], ws,
                                                      -np.inf)))
        regret = max(best - float(ws[q]), 0.0) / (
            want["final_tol"][q] + want["final_tol"][b])
    else:
        regret = _shortfall(w, want)
    return {"stats": max(stats), "rank": max(rank),
            "regret": (regret, f"winner lane {w}"),
            "pick": (pick / SCORE_RTOL, f"winner lane {prog[got['winner']]}")}


def compared(parts: Dict[str, Tuple[float, str]]) -> Dict[str, float]:
    """The compared numbers: ``tune_gap``, the worst of the statistic
    and pick parts, and ``rank_gap``, the worst of the ranking and regret
    parts, which are in score-tolerance units and have a limit of their
    own."""
    return {"tune_gap": max(parts["stats"][0], parts["pick"][0]),
            "rank_gap": max(parts["rank"][0], parts["regret"][0])}


def _shortfall(lane: int, want: dict) -> float:
    """How far below the reference's cut ``lane`` scored, at the rung
    that dropped it, in units of its and the cut lane's tolerance."""
    for rung in want["rungs"]:
        if lane in rung["kept"]:
            continue
        lanes = list(rung["lanes"])
        c = min((lanes.index(k) for k in rung["kept"]),
                key=lambda i: rung["score"][i])
        i = lanes.index(lane)
        return max(rung["score"][c] - rung["score"][i], 0.0) / (
            rung["tol"][c] + rung["tol"][i])
    return 0.0


def _margin(lane: int, want: dict) -> float:
    """How far above the best dropped lane ``lane`` scored, at the rung
    where that was least, in units of the two lanes' tolerance."""
    out = None
    n_cand = len(want["rungs"][0]["lanes"]) - 1 if want["rungs"] else 0
    for rung in want["rungs"]:
        lanes = list(rung["lanes"])
        kept = set(int(k) for k in rung["kept"])
        dropped = [i for i, k in enumerate(lanes)
                   if k < n_cand and k not in kept]
        if not dropped:
            continue
        t = max(dropped, key=lambda i: rung["score"][i])
        i = lanes.index(lane)
        m = max(rung["score"][i] - rung["score"][t], 0.0) / (
            rung["tol"][i] + rung["tol"][t])
        out = m if out is None else min(out, m)
    return out or 0.0
