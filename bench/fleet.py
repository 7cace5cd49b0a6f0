"""Inputs of a cell, made by the benchmark from ``--seed``.

The demand model is a copy of the program's Fig.-1 HPCC trace and its
fleet replay (``repro.core.traces.hpcc_trace`` /
``fleet_demand_traces``): the benchmark generates its own traffic, so a
change to the program's generators cannot change what is measured.
Configurations (``bench/configs/<name>.json``) state the deployment;
traffic mixes (``bench/traffic/<name>.json``) state the load.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

import numpy as np

GiB = float(2**30)
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

# Fig.-1 phase structure: (name, relative duration, base GiB, peak GiB,
# fraction of the phase at or near the peak).
HPCC_PHASES: Tuple[Tuple[str, float, float, float, float], ...] = (
    ("startup", 0.05, 5.0, 5.0, 0.0),
    ("hpl", 0.30, 20.0, 75.0, 0.45),
    ("dgemm", 0.10, 18.0, 30.0, 0.30),
    ("stream", 0.10, 28.0, 32.0, 0.50),
    ("ptrans", 0.15, 25.0, 73.0, 0.35),
    ("randomaccess", 0.10, 15.0, 22.0, 0.30),
    ("fft", 0.12, 20.0, 42.0, 0.35),
    ("network", 0.08, 8.0, 10.0, 0.0),
)


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json`` as a dict."""
    with open(os.path.join(BENCH_DIR, kind, f"{name}.json")) as fh:
        return json.load(fh)


def seed_rngs(seed: int, n: int) -> List[np.random.Generator]:
    """``n`` independent generators from one (possibly huge) seed."""
    seq = np.random.SeedSequence(int(seed) % (1 << 64))
    return [np.random.default_rng(s) for s in seq.spawn(n)]


def hpcc_trace(n: int, interval_s: float, rng: np.random.Generator,
               noise_gib: float) -> np.ndarray:
    """One node's Fig.-1-shaped compute demand over ``n`` intervals (bytes)."""
    total = sum(p[1] for p in HPCC_PHASES)
    out = np.empty(n, dtype=np.float64)
    i = 0
    for _, dur, base, peak, burst_frac in HPCC_PHASES:
        steps = min(max(int(round(n * dur / total)), 1), n - i)
        if steps <= 0:
            break
        seg = np.full(steps, base)
        if peak > base and burst_frac > 0:
            burst_len = max(int(steps * burst_frac), 1)
            start = (steps - burst_len) // 2
            ramp = min(max(int(2.0 / interval_s), 1), max(burst_len // 2, 1))
            prof = np.full(burst_len, peak)
            prof[:ramp] = np.linspace(base, peak, ramp)
            prof[-ramp:] = np.linspace(peak, base, ramp)
            seg[start:start + burst_len] = prof[: steps - start]
        out[i:i + steps] = seg
        i += steps
    if i < n:
        out[i:] = HPCC_PHASES[-1][2]
    out += rng.normal(0.0, noise_gib, size=n)
    peak = max(p[3] for p in HPCC_PHASES)
    return np.clip(out, 1.0, peak) * GiB


def build_fleet(cfg: dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(demand (N, T) bytes, node memory (N,) bytes)`` of a configuration.

    Every node replays one HPCC trace, rolled by its own phase offset
    and scaled by its own amplitude, on top of the configuration's
    static demand floor.
    """
    n, t = int(cfg["n_nodes"]), int(cfg["n_intervals"])
    dem = cfg["demand"]
    trace_rng, node_rng = seed_rngs(seed, 2)
    base = hpcc_trace(t, float(cfg["interval_s"]), trace_rng,
                      float(dem["noise_gib"]))
    shifts = (node_rng.integers(0, t, size=n) if dem["phase_shift"]
              else np.zeros(n, np.int64))
    amp = node_rng.uniform(*dem["amp_range"], size=n)
    # Row i is base rolled right by shifts[i]: gather through one index
    # array instead of n separate rolls.
    idx = (np.arange(t)[None, :] - shifts[:, None]) % t
    demand = base[idx] * amp[:, None] + float(dem["offset_gib"]) * GiB
    memory = np.full(n, float(cfg["node_memory_gib"]) * GiB)
    return demand, memory


def controller(cfg: dict) -> Dict[str, float]:
    """The configuration's control law in bytes: r0, lam, u_min, u_max."""
    c = cfg["controller"]
    return {"r0": float(c["r0"]), "lam": float(c["lam"]),
            "u_min": float(c["u_min_gib"]) * GiB,
            "u_max": float(c["u_max_gib"]) * GiB}


def draw_gains(groups: List[dict], law: Dict[str, float],
               rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """A gain set as plain arrays, one group after another.

    Each group draws ``n`` points uniformly in its ``lam`` and ``r0``
    ranges; ``lam_grant`` (default: ``lam``), ``deadband`` and
    ``feedforward`` are the group's constants.  Sizes never depend on
    the seed, only values do.
    """
    cols = {k: [] for k in ("r0", "lam", "lam_grant", "u_min", "u_max",
                            "deadband", "feedforward")}
    for g in groups:
        n = int(g["n"])
        lam = rng.uniform(*g["lam"], size=n)
        r0 = rng.uniform(*g["r0"], size=n)
        cols["r0"].append(r0)
        cols["lam"].append(lam)
        cols["lam_grant"].append(np.full(n, g["lam_grant"])
                                 if "lam_grant" in g else lam.copy())
        cols["u_min"].append(np.full(n, law["u_min"]))
        cols["u_max"].append(np.full(n, law["u_max"]))
        cols["deadband"].append(np.full(n, float(g.get("deadband", 0.0))))
        cols["feedforward"].append(np.full(n, float(g.get("feedforward",
                                                          0.0))))
    return {k: np.concatenate(v).astype(np.float64) for k, v in cols.items()}


def concat_gains(a: Dict[str, np.ndarray],
                 b: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    return {k: np.concatenate([a[k], b[k]]) for k in a}
