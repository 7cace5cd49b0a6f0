"""``plane``: a live ``MemoryPlane`` over the whole fleet, ticked in real time.

Every node has a ``SimulatedMonitor`` on its row of the configuration's
demand and one managed store that fills whatever it is granted, so
``used = demand + grant``: the sweep's closed loop, run live.  Ticks are
paced as ``MemoryPlane.run`` paces them, one every control interval, or
back to back while a tick overruns it.  ``program_args`` go to the
``PlaneSpec`` (``backend``, ...).  The check replays the grants of a
seeded node subset on every tick in float64; the control is that replay
in bfloat16.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import fleet, generator
from bench.reference import replay as ref


class SaturatedStore:
    """A managed store that always fills whatever it is granted."""

    priority = 0

    def __init__(self, name: str, capacity: float):
        self.name = name
        self._cap = float(capacity)

    def capacity(self) -> float:
        return self._cap

    def used(self) -> float:
        return self._cap

    def set_capacity(self, capacity: float):
        from repro.core.store import EvictionReport
        self._cap = max(float(capacity), 0.0)
        return EvictionReport(store=self.name, requested_capacity=capacity,
                              applied_capacity=self._cap)


class Kind(generator.Traffic):
    label = "tick"

    def setup(self) -> None:
        from repro.core import (MemoryPlane, NodeSpec, PlaneSpec,
                                SimulatedMonitor, StoreRegistry)
        cfg, t = self.cfg, self.traffic
        self.demand, self.m = fleet.build_fleet(cfg, self.seed)
        n = self.demand.shape[0]
        self.pace_s = float(cfg["interval_s"])
        (sample_rng,) = fleet.seed_rngs(self.seed + 1, 1)
        self.subset = np.sort(sample_rng.choice(
            n, size=min(int(t["check_nodes"]), n), replace=False))
        nodes = []
        for i in range(n):
            store = SaturatedStore(f"s{i}", self.law["u_max"])
            reg = StoreRegistry()
            reg.register(store, max_bytes=self.law["u_max"])
            nodes.append(NodeSpec(
                f"n{i}", monitor=SimulatedMonitor(
                    f"n{i}", total=float(self.m[i]), usage=self.demand[i],
                    storage_used_fn=store.used, dt=self.pace_s),
                registry=reg, u0=self.law["u_max"]))
        self.plane = MemoryPlane(PlaneSpec(params=generator.params(cfg),
                                           nodes=tuple(nodes),
                                           **self.program_args))
        self.names = [f"n{i}" for i in self.subset]
        self.short_ticks = 0
        for _ in range(int(t["warmup_ticks"])):
            self.call()

    def call(self) -> float:
        import jax
        with jax.profiler.TraceAnnotation(self.label):
            actions = self.plane.tick()
        n = self.demand.shape[0]
        if len(actions) == n:
            row = [actions[i].u_next for i in self.subset]
        else:
            self.short_ticks += 1
            by = {a.node: a.u_next for a in actions}
            row = [by.get(name, np.nan) for name in self.names]
        self.results.append(np.asarray(row, np.float64))
        return float(len(actions))

    def e2e(self, window_s: float, n_calls: int) -> Dict[str, float]:
        svc = np.array([e - s for s, e in self.calls]) * 1e3
        return {"tick_p50_ms": float(np.percentile(svc, 50)),
                "tick_p90_ms": float(np.percentile(svc, 90))}

    def failed(self) -> int:
        return self.short_ticks

    def free(self) -> None:
        self.plane = None

    def check(self) -> List[Tuple[str, float, float]]:
        """Every tick's grants (warm-up and window) of the subset."""
        got = np.stack(self.results)                  # (ticks, subset)
        self.want = self.reference(got.shape[0])
        gap = self.grant_gap(got, self.want)
        print(f"check: grant_gap over {got.shape[0]} ticks x "
              f"{got.shape[1]} nodes", file=generator.err())
        return [("grant_gap", gap,
                 float(self.traffic["limits"]["grant_gap"]))]

    def reference(self, n_ticks: int, dtype=np.float64) -> np.ndarray:
        return ref.grant_history(self.demand[self.subset],
                                 self.m[self.subset], self.law, n_ticks,
                                 dtype=dtype)

    def grant_gap(self, got: np.ndarray, want: np.ndarray) -> float:
        """Worst grant gap as a share of the node's memory."""
        gap = np.abs(got - want) / self.m[self.subset][None, :]
        return float(np.max(np.where(np.isfinite(gap), gap, np.inf)))

    def control(self) -> Dict[str, float]:
        """The grant replay computed in bfloat16, on the same ticks."""
        import ml_dtypes
        n = len(self.results)
        return {"grant_gap": self.grant_gap(
            self.reference(n, dtype=ml_dtypes.bfloat16), self.want)}
