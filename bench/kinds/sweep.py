"""``sweep``: back-to-back ``sweep_demand`` grid sweeps.

Each call draws its own gain set from the seed (``gain_groups``: sizes
fixed, values new), runs one ``sweep_demand`` over the whole fleet with
the mix's ``program_args`` (``engine``, ``devices``, ``node_shards``,
...), and keeps the ``FleetStats`` it answered.  The check replays a
seeded sample of (sweep, gain) answers of the window in float64 and
compares every statistic; the control is that replay computed in
bfloat16.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import fleet, generator
from bench.reference import replay as ref


class Kind(generator.Traffic):
    label = "sweep_demand"
    spans = ("gain_draw",)

    def setup(self) -> None:
        from repro.lab import sweep_demand
        self._sweep = sweep_demand
        self.demand, self.m = fleet.build_fleet(self.cfg, self.seed)
        self.cache = generator.cache_spec(self.cfg)
        self.gain_rng, self.sample_rng = fleet.seed_rngs(self.seed + 1, 2)
        self.drawn: List[Dict[str, np.ndarray]] = []
        # Warm-up: one sweep of the timed shapes (compiles, or loads from
        # the persistent cache).
        self.call()

    def call(self) -> float:
        import jax
        with jax.profiler.TraceAnnotation("gain_draw"):
            gains = fleet.draw_gains(self.traffic["gain_groups"], self.law,
                                     self.gain_rng)
            gs = generator.gain_set(gains)
        with jax.profiler.TraceAnnotation(self.label):
            stats = self._sweep(
                self.demand, gs, node_memory=self.m,
                interval_s=float(self.cfg["interval_s"]), cache=self.cache,
                **self.program_args)
        self.drawn.append(gains)
        self.results.append(generator.stats_dict(stats))
        return float(self.demand.size * len(gs))

    def e2e(self, window_s: float, n_calls: int) -> Dict[str, float]:
        return {"sweep_upd_per_s": sum(self.updates) / window_s}

    def free(self) -> None:
        self._sweep = None

    def check(self) -> List[Tuple[str, float, float]]:
        """A seeded sample of (sweep, gain) answers against the replay."""
        t = self.traffic
        first = self.first
        n_calls = len(self.results) - first
        n_gain = len(self.results[0]["mean_utilization"])
        k = min(int(t["check_answers"]), n_calls * n_gain)
        flat = self.sample_rng.choice(n_calls * n_gain, size=k,
                                      replace=False)
        pairs = [(first + int(i) // n_gain, int(i) % n_gain) for i in flat]
        gains = {key: np.array([self.drawn[c][key][g] for c, g in pairs])
                 for key in self.drawn[0]}
        prog = {f: np.array([self.results[c][f][g] for c, g in pairs])
                for f in self.results[0]}
        self.sampled = gains
        self.want = self.reference(gains)
        gap, where = generator.stats_gap(prog, self.want)
        print(f"check: stats_gap worst in {where} over {k} answers of "
              f"{n_calls} sweeps", file=generator.err())
        return [("stats_gap", gap, float(t["limits"]["stats_gap"]))]

    def reference(self, gains, dtype=np.float64) -> Dict[str, np.ndarray]:
        return ref.replay_stats(self.demand, self.m, gains,
                                interval_s=float(self.cfg["interval_s"]),
                                cache=self.cfg.get("cache"), dtype=dtype)

    def control(self) -> Dict[str, float]:
        """The float64 replay computed in bfloat16, on the first sampled
        answers (``control_answers``)."""
        import ml_dtypes
        k = int(self.traffic["control_answers"])
        gains = {key: v[:k] for key, v in self.sampled.items()}
        want = {f: v[:k] for f, v in self.want.items()}
        got = self.reference(gains, dtype=ml_dtypes.bfloat16)
        return {"stats_gap": generator.stats_gap(got, want)[0]}
