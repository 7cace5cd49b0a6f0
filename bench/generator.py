"""The general generator: what every kind of traffic shares.

A traffic mix (``bench/traffic/<name>.json``) is data: it names its
``kind`` and its parameters.  The kind is code of its own,
``bench/kinds/<kind>.py``, found by that name; its class ``Kind`` builds
the cell's inputs from the seed, warms up every shape, runs one unit of
work per :meth:`Traffic.call`, and after the window compares what the
timed calls produced with the benchmark's own float64 reference
(``bench/reference``).  ``program_args`` in a mix are handed to the
program's entry point as they stand, so a mix can set any knob the
entry takes (``engine``, ``devices``, ``node_shards``, ...) without code.
"""

from __future__ import annotations

import importlib.util
import os
import sys
from typing import Dict, List, Tuple

import numpy as np

from bench import fleet

KINDS_DIR = os.path.join(fleet.BENCH_DIR, "kinds")

# Per-field scale of a gap: |program - reference| / (atol + rtol * |ref|),
# the program's own parity tolerances against its float64 replays
# (``tests/test_lab.py`` PARITY_KEYS, ``tests/test_cacheloop.py``): rtol
# 1e-4 with a small atol for the streamed statistics, the fixed-bin p99
# bracket of 5e-4, 1e-3 relative for the cache loop's modeled app time
# and evicted bytes.  Shares of samples over a threshold take 1e-4 of
# the samples: the law holds utilization at r0, right beside the
# over-r0 threshold, so rounding flips samples there.  A gap of 1 is
# one unit.  The capacity's spread is
# compared as its second moment, std^2 + mean^2 (the program's plain
# float32 sum of squares, before the cancellation that turns it into a
# standard deviation and multiplies its rounding by mean^2 / std^2).
GAP_SCALE = {
    "mean_utilization": (1e-5, 1e-4), "p99_utilization": (5e-4, 0.0),
    "max_utilization": (1e-5, 1e-4), "frac_intervals_over_r0": (1e-4, 0.0),
    "max_over_r0": (1e-5, 1e-4), "pressure_violation_rate": (1e-4, 0.0),
    "mean_capacity_gib": (1e-5, 1e-4), "capacity_msq_gib2": (1e-5, 1e-4),
    "granted_volume_gib_s": (1e-5, 1e-4), "settle_intervals": (1.0, 0.0),
    "hit_ratio": (1e-6, 1e-4), "evicted_bytes": (1e-6, 1e-3),
    "app_runtime": (1e-6, 1e-3), "app_slowdown": (1e-6, 1e-3),
    "makespan": (1e-6, 1e-4),
}


def stats_gap(prog: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
              fields=None) -> Tuple[float, str]:
    """Worst scaled gap over fields and lanes, and the field it was in."""
    prog, want = _with_second_moment(prog), _with_second_moment(want)
    worst, where = 0.0, ""
    for f in fields or GAP_SCALE:
        if f not in want:
            continue
        atol, rtol = GAP_SCALE[f]
        p = np.asarray(prog[f], np.float64)
        r = np.asarray(want[f], np.float64)
        gap = np.abs(p - r) / (atol + rtol * np.abs(r))
        gap = np.where(np.isfinite(p), gap, np.inf)
        g = float(np.max(gap)) if gap.size else 0.0
        if g > worst or not where:
            worst, where = g, f
    return worst, where


def _with_second_moment(s: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    if "capacity_std_gib" not in s or "mean_capacity_gib" not in s:
        return s
    std = np.asarray(s["capacity_std_gib"], np.float64)
    mean = np.asarray(s["mean_capacity_gib"], np.float64)
    return dict(s, capacity_msq_gib2=std * std + mean * mean)


def stats_dict(stats) -> Dict[str, np.ndarray]:
    return {f: np.asarray(getattr(stats, f), np.float64)
            for f in stats._fields}


def gain_set(gains: Dict[str, np.ndarray]):
    from repro.lab import GainSet
    return GainSet(**gains)


def params(cfg: dict):
    from repro.core.control import ControllerParams
    law = fleet.controller(cfg)
    return ControllerParams(
        total_memory=float(cfg["node_memory_gib"]) * fleet.GiB,
        r0=law["r0"], lam=law["lam"], u_min=law["u_min"],
        u_max=law["u_max"], interval_s=float(cfg["interval_s"]))


def cache_spec(cfg: dict):
    if cfg.get("cache") is None:
        return None
    from repro.lab import CacheSpec
    return CacheSpec(**cfg["cache"])


def err():
    return sys.stderr


class Traffic:
    """Set-up, one call, end-to-end metrics, the check, and the control.

    A kind subclasses this as ``Kind`` in ``bench/kinds/<kind>.py``.
    """

    pace_s = 0.0                      # 0: calls run back to back
    label = "call"                    # the host span around one call
    spans: Tuple[str, ...] = ()       # further host spans that name gaps

    def __init__(self, cfg: dict, traffic: dict, seed: int, chips: int):
        self.cfg, self.traffic, self.seed, self.chips = \
            cfg, traffic, int(seed), int(chips)
        self.law = fleet.controller(cfg)
        self.program_args = dict(traffic.get("program_args", {}))
        self.calls: List[Tuple[float, float]] = []   # (start, end) per call
        self.updates: List[float] = []                # work per call
        self.lateness: List[float] = []
        self.results: list = []
        self.first = 0

    def mark_window(self) -> None:
        """Answers from here on are the window's (set-up's come before)."""
        self.first = len(self.results)

    def setup(self) -> None:
        raise NotImplementedError

    def call(self) -> float:
        """One unit of timed work; returns the updates it did."""
        raise NotImplementedError

    def e2e(self, window_s: float, n_calls: int) -> Dict[str, float]:
        raise NotImplementedError

    def free(self) -> None:
        """Drop every device buffer the program holds before the check."""

    def check(self) -> List[Tuple[str, float, float]]:
        """``(name, value, limit)`` of each compared number."""
        raise NotImplementedError

    def control(self) -> Dict[str, float]:
        """The compared numbers with the control in the program's place
        (after :meth:`check`, on the same answers), by name."""
        raise NotImplementedError

    def failed(self) -> int:
        return 0


_KIND_CACHE: Dict[str, object] = {}


def kind(name: str):
    """The module ``bench/kinds/<name>.py``."""
    if name not in _KIND_CACHE:
        path = os.path.join(KINDS_DIR, f"{name}.py")
        if not os.path.exists(path):
            raise KeyError(f"no traffic kind {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            "bench_kind_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _KIND_CACHE[name] = mod
    return _KIND_CACHE[name]


def make(cfg: dict, traffic: dict, seed: int, chips: int) -> Traffic:
    return kind(traffic["kind"]).Kind(cfg, traffic, seed, chips)
