"""Plain float64 replay of the DynIMS closed loop, the benchmark's reference.

A copy, written out in numpy, of what the program simulates: the
paper's Eq. 1 on every node,

    u' = clamp(u - lam * v * (v / M - r0) / r0, u_min, u_max),

driven by ``v = d + u`` (a saturated store, the float64 Eq.-1 replay
``lab.sweep.oracle_history``) or by ``v = d + resident`` with the
CacheLoop carry (the float64 replay ``cache_oracle`` of the CacheLoop
tests): eviction down to a shrunk grant, the analytic reuse-distance
hit curve with its cold first scan, read-through refill, and the Fig.-2
pressure curve priced into modeled app runtime.  It imports nothing of
the program and takes nothing it made.

Lanes (gain points) are rows of ``(G, N)`` arrays.  :class:`Replay`
advances a block of lanes interval by interval, so a halving schedule
can pause it, rank the lanes, and continue the survivors.  ``dtype``
sets the precision of the per-interval state and law; sums over the
history always accumulate in float64.  The statistics follow the
definitions the program states for its ``FleetStats``.
"""

from __future__ import annotations

import concurrent.futures
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

GiB = float(2**30)
OVER_R0_EPS = 1e-3           # "over r0" means r > r0 + 1e-3
SETTLE_TOL = 0.02            # settled once max r stays <= r0 + 0.02
P99 = 0.99
# Policy concentration of the analytic hit curve h(f) = c f^(1-a) + (1-c) f.
POLICY_CONCENTRATION = {"lfu": 1.0, "adaptive": 0.9, "lru": 0.65, "fifo": 0.35}


def hpl_slowdown(r: np.ndarray) -> np.ndarray:
    """Fig.-2 execution-time multiplier at utilization ``r``."""
    u = np.clip(r, 0.0, 1.5)
    return np.where(
        u <= 0.92, 1.0,
        np.where(u <= 0.98, 1.0 + (u - 0.92) / 0.06 * 0.35,
                 np.where(u <= 1.0, 1.35 + (u - 0.98) / 0.02 * 2.65,
                          4.0 + (u - 1.0) * 300.0)))


def default_score(s: Dict[str, np.ndarray]) -> np.ndarray:
    """The program's documented default tuning objective; higher wins."""
    return (s["mean_capacity_gib"] - 200.0 * s["frac_intervals_over_r0"]
            - 2000.0 * s["pressure_violation_rate"]
            - 100.0 * s["max_over_r0"] - 0.01 * s["settle_intervals"]
            - 50.0 * (s["app_slowdown"] - 1.0))


class Replay:
    """One block of lanes advancing through the closed loop.

    ``gains`` holds ``(G,)`` arrays ``r0, lam, lam_grant, u_min, u_max,
    deadband, feedforward`` (bytes for capacities).  ``cache`` is the
    configuration's cache dict or None.  ``p99`` keeps the largest 1% of
    utilization samples needed for the exact 99th percentile of the
    whole history (costly; only final comparisons ask for it).
    """

    def __init__(self, demand: np.ndarray, m: np.ndarray,
                 gains: Dict[str, np.ndarray], *, interval_s: float,
                 cache: Optional[dict] = None, dtype=np.float64,
                 p99: bool = False):
        self.demand = demand                      # (N, T) float64 bytes
        self.dt = dtype
        self.iv = float(interval_s)
        self.cache = cache
        n = demand.shape[0]
        self.n = n
        col = {k: np.asarray(v, np.float64)[:, None].astype(dtype)
               for k, v in gains.items()}
        self.g = col
        self.m = np.asarray(m, np.float64).astype(dtype)[None, :]
        self.t = 0
        g = len(gains["r0"])
        self.u = np.broadcast_to(col["u_max"], (g, n)).astype(dtype)
        zeros = np.zeros((g, n))
        self.acc = {k: zeros.copy() for k in ("us", "cs", "c2", "n_r0",
                                               "n_viol", "hs", "es", "ts")}
        self.mx = np.full((g, n), -np.inf)
        self.last_bad = np.full(g, -1, np.int64)
        self.keep_p99 = p99
        self.top: List[np.ndarray] = [np.empty(0)] * g
        self.buf: List[np.ndarray] = []
        if cache is not None:
            conc = POLICY_CONCENTRATION[cache["policy"]]
            self.conc = dtype(conc)
            self.hit_exp = dtype(1.0 - cache["reuse_skew"])
            self.w = (dtype(cache["working_set_frac"]) * self.m)
            self.access_g = cache["access_gibps"] * self.iv
            self.refill_b = dtype(cache["refill_gibps"] * GiB * self.iv)
            self.resident = (dtype(cache["warm_frac"])
                             * np.minimum(self.u, self.w)).astype(dtype)
            self.wf0 = (self.resident / self.w).astype(dtype)
            self.w_max = float(np.max(self.w.astype(np.float64)))
        d0 = demand[:, 0].astype(dtype)[None, :]
        self.v_prev = (d0 + (self.resident if cache is not None
                             else self.u)).astype(dtype)

    # -- one block of intervals ----------------------------------------------
    def advance(self, t_end: int) -> None:
        dt, g = self.dt, self.g
        cache = self.cache
        thr_over = (g["r0"].astype(np.float64) + OVER_R0_EPS)
        thr_settle = (g["r0"].astype(np.float64) + SETTLE_TOL)
        # Knobs no lane of this block uses cost nothing: the same
        # arithmetic for every lane, minus terms that are exactly zero.
        use_ff = bool((g["feedforward"] != 0).any())
        use_db = bool((g["deadband"] > 0).any())
        use_grant = bool((g["lam_grant"] != g["lam"]).any())
        a = self.acc
        for t in range(self.t, t_end):
            d = self.demand[:, t].astype(dt, copy=False)[None, :]
            u = self.u
            v = (d + (self.resident if cache is not None else u)) \
                .astype(dt, copy=False)
            v_eff = v
            if use_ff:
                v_eff = (v + g["feedforward"] * (v - self.v_prev)).astype(
                    dt, copy=False)
            err = (v_eff / self.m - g["r0"]).astype(dt, copy=False)
            lam = g["lam"]
            if use_grant:
                lam = np.where(err < 0, g["lam_grant"], g["lam"]).astype(
                    dt, copy=False)
            u_next = (u - lam * v_eff * err / g["r0"]).astype(dt, copy=False)
            if use_db:
                u_next = np.where((g["deadband"] > 0)
                                  & (np.abs(err) <= g["deadband"]), u, u_next)
            u_next = np.clip(u_next, g["u_min"], g["u_max"]).astype(
                dt, copy=False)
            r64 = (v / self.m).astype(dt, copy=False).astype(np.float64,
                                                            copy=False)
            a["us"] += r64
            c = u_next.astype(np.float64) / GiB
            a["cs"] += c
            c *= c
            a["c2"] += c
            np.maximum(self.mx, r64, out=self.mx)
            a["n_r0"] += r64 > thr_over
            a["n_viol"] += r64 > 1.0
            self.last_bad[(r64 > thr_settle).any(axis=1)] = t
            if self.keep_p99:
                self._keep(r64)
            if cache is not None:
                self._cache_step(t, r64, u_next)
            self.v_prev = v
            self.u = u_next
        self.t = t_end

    def _cache_step(self, t: int, r64: np.ndarray, u_next: np.ndarray) -> None:
        dt, cache = self.dt, self.cache
        res = self.resident
        res_ev = np.minimum(res, u_next)
        ev_g = ((res - res_ev) / dt(GiB)).astype(dt, copy=False)
        f = np.minimum(res_ev / self.w, dt(1.0)).astype(dt, copy=False)
        hit = (self.conc * f ** self.hit_exp
               + (dt(1.0) - self.conc) * f).astype(dt, copy=False)
        # Cold first scan: until a node has read its working set once,
        # only the warm prefix (and, with skew, hot blocks) can hit.
        scanned = t * self.access_g * GiB
        if scanned < self.w_max:
            cold = scanned < self.w
            wf = np.minimum(self.wf0, f)
            hit = np.where(cold, wf + dt(cache["reuse_skew"]) * (hit - wf),
                           hit).astype(dt, copy=False)
        miss_g = ((dt(1.0) - hit) * dt(self.access_g)).astype(dt, copy=False)
        target = np.minimum(u_next, self.w)
        self.resident = np.minimum(
            target, res_ev + np.minimum(miss_g * dt(GiB), self.refill_b)
        ).astype(dt, copy=False)
        dt_app = (self.iv * hpl_slowdown(r64)
                  + miss_g.astype(np.float64) * cache["miss_penalty_s_per_gib"]
                  + ev_g.astype(np.float64) * cache["evict_penalty_s_per_gib"])
        a = self.acc
        a["hs"] += hit.astype(np.float64) * self.access_g
        a["es"] += ev_g
        a["ts"] += dt_app

    # -- exact 99th percentile: keep the top 1% of samples --------------------
    def _keep(self, r64: np.ndarray) -> None:
        self.buf.append(r64)
        if len(self.buf) >= 64:
            self._merge()

    def _merge(self) -> None:
        if not self.buf:
            return
        k = self._k_top(self.demand.shape[1])
        block = np.stack(self.buf, axis=1)            # (G, steps, N)
        self.buf = []
        for i in range(block.shape[0]):
            cand = np.concatenate([self.top[i], block[i].ravel()])
            if cand.size > k:
                cand = np.partition(cand, cand.size - k)[cand.size - k:]
            self.top[i] = cand

    def _k_top(self, t_total: int) -> int:
        n_total = t_total * self.n
        return n_total - int(np.floor(P99 * (n_total - 1)))

    # -- statistics over the first self.t intervals ---------------------------
    def stats(self) -> Dict[str, np.ndarray]:
        t, n, a = self.t, self.n, self.acc
        samples = t * n
        r0 = self.g["r0"][:, 0].astype(np.float64)
        cs = a["cs"].sum(axis=1)
        mean_cap = cs / samples
        max_u = self.mx.max(axis=1)
        ideal_s = t * self.iv
        out = {
            "mean_utilization": a["us"].sum(axis=1) / samples,
            "max_utilization": max_u,
            "frac_intervals_over_r0": a["n_r0"].sum(axis=1) / samples,
            "max_over_r0": np.clip(max_u - r0, 0.0, None),
            "pressure_violation_rate": a["n_viol"].sum(axis=1) / samples,
            "mean_capacity_gib": mean_cap,
            "capacity_std_gib": np.sqrt(np.maximum(
                a["c2"].sum(axis=1) / samples - mean_cap ** 2, 0.0)),
            "granted_volume_gib_s": cs / n * self.iv,
            "settle_intervals": (self.last_bad + 1).astype(np.float64),
            "makespan": np.full(r0.shape, ideal_s),
        }
        if self.cache is None:
            out["hit_ratio"] = np.ones_like(r0)
            out["evicted_bytes"] = np.zeros_like(r0)
            out["app_runtime"] = np.full(r0.shape, ideal_s)
        else:
            out["hit_ratio"] = a["hs"].sum(axis=1) / (n * self.access_g * t)
            out["evicted_bytes"] = a["es"].sum(axis=1) * GiB
            out["app_runtime"] = a["ts"].max(axis=1)
        out["app_slowdown"] = out["app_runtime"] / ideal_s
        if self.keep_p99:
            self._merge()
            k = self._k_top(t)
            out["p99_utilization"] = np.array(
                [np.partition(x, x.size - k)[x.size - k] if x.size > k
                 else x.min() for x in self.top])
        return out

    def take(self, idx: Sequence[int]) -> "Replay":
        """A replay of the lanes ``idx``, continuing from here."""
        idx = np.asarray(idx, np.int64)
        new = object.__new__(Replay)
        new.__dict__.update(self.__dict__)
        new.g = {k: v[idx] for k, v in self.g.items()}
        new.u = self.u[idx]
        new.v_prev = self.v_prev[idx]
        new.acc = {k: v[idx] for k, v in self.acc.items()}
        new.mx = self.mx[idx]
        new.last_bad = self.last_bad[idx]
        new.top = [self.top[i] for i in idx]
        new.buf = [b[idx] for b in self.buf]
        if self.cache is not None:
            new.resident = self.resident[idx]
            new.wf0 = self.wf0[idx]
        return new


# Fewest lanes a thread's block holds: smaller blocks spend their time
# in per-operation overhead under the interpreter lock, not in numpy.
MIN_BLOCK_LANES = 16


def _parts(g: int, workers: int) -> int:
    return max(1, min(workers, g // MIN_BLOCK_LANES))


class LaneBlocks:
    """Lanes split into blocks that advance in parallel threads.

    numpy releases the interpreter lock inside its array loops, so
    blocks of lanes replay on several cores at once.
    """

    def __init__(self, blocks: List[Replay], workers: int):
        self.blocks = blocks
        self.workers = workers

    @classmethod
    def start(cls, demand, m, gains, *, workers: Optional[int] = None,
              **kw) -> "LaneBlocks":
        workers = workers or max(1, min(12, (os.cpu_count() or 2) - 1))
        g = len(gains["r0"])
        parts = np.array_split(np.arange(g), _parts(g, workers))
        return cls([Replay(demand, m, {k: v[p] for k, v in gains.items()},
                           **kw) for p in parts], workers)

    def advance(self, t_end: int) -> None:
        with concurrent.futures.ThreadPoolExecutor(self.workers) as ex:
            for f in [ex.submit(b.advance, t_end) for b in self.blocks]:
                f.result()

    def stats(self) -> Dict[str, np.ndarray]:
        parts = [b.stats() for b in self.blocks]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

    def take(self, idx: Sequence[int]) -> "LaneBlocks":
        """Keep lanes ``idx`` (global order), rebalanced over the workers."""
        idx = np.asarray(idx, np.int64)
        sizes = np.cumsum([0] + [len(b.g["r0"]) for b in self.blocks])
        picked = []
        for i in idx:
            b = int(np.searchsorted(sizes, i, side="right") - 1)
            picked.append(self.blocks[b].take([i - sizes[b]]))
        parts = np.array_split(np.arange(len(picked)),
                               _parts(len(picked), self.workers))
        return LaneBlocks([_concat([picked[j] for j in p]) for p in parts],
                          self.workers)


def _concat(reps: List[Replay]) -> Replay:
    first = reps[0]
    new = object.__new__(Replay)
    new.__dict__.update(first.__dict__)
    new.g = {k: np.concatenate([r.g[k] for r in reps]) for k in first.g}
    new.u = np.concatenate([r.u for r in reps])
    new.v_prev = np.concatenate([r.v_prev for r in reps])
    new.acc = {k: np.concatenate([r.acc[k] for r in reps]) for k in first.acc}
    new.mx = np.concatenate([r.mx for r in reps])
    new.last_bad = np.concatenate([r.last_bad for r in reps])
    new.top = [t for r in reps for t in r.top]
    new.buf = [np.concatenate([r.buf[i] for r in reps])
               for i in range(len(first.buf))]
    if first.cache is not None:
        new.resident = np.concatenate([r.resident for r in reps])
        new.wf0 = np.concatenate([r.wf0 for r in reps])
    return new


def replay_stats(demand, m, gains, *, interval_s: float,
                 cache: Optional[dict] = None, dtype=np.float64,
                 p99: bool = True,
                 workers: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Every statistic of every lane over the whole horizon."""
    lanes = LaneBlocks.start(demand, m, gains, interval_s=interval_s,
                             cache=cache, dtype=dtype, p99=p99,
                             workers=workers)
    lanes.advance(demand.shape[1])
    return lanes.stats()


def grant_history(demand: np.ndarray, m: np.ndarray, law: Dict[str, float],
                  n_ticks: int, dtype=np.float64) -> np.ndarray:
    """``(ticks, N)`` grants of a live plane whose stores fill their grant.

    Tick ``k`` observes ``v = d[:, k] + u`` (demand plus the grant in
    force) and decides the next grant by Eq. 1; this is the saturated
    replay with one lane per node.
    """
    n = demand.shape[0]
    u = np.full(n, law["u_max"]).astype(dtype)
    mm = np.asarray(m, np.float64).astype(dtype)
    out = np.empty((n_ticks, n))
    last = demand.shape[1] - 1
    for k in range(n_ticks):
        d = demand[:, min(k, last)].astype(dtype)
        v = (d + u).astype(dtype)
        err = (v / mm - dtype(law["r0"])).astype(dtype)
        u = np.clip((u - dtype(law["lam"]) * v * err / dtype(law["r0"]))
                    .astype(dtype), dtype(law["u_min"]),
                    dtype(law["u_max"])).astype(dtype)
        out[k] = u.astype(np.float64)
    return out
