"""Device-resident sweep engine: streaming quantile accuracy, chunk and
device invariance, successive halving, portfolio tuning.

The oracle here is an independent float64 numpy reimplementation of the
closed loop -- the engine's streamed statistics must match a dense
history it never materializes.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.cluster_sim import paper_controller_params
from repro.lab import (FleetStats, GainSet, QUANT_BINS, QUANT_RANGE,
                       get_scenario, grid_gains, halving_tune,
                       quantile_from_codes, run_sweep, sweep_demand,
                       tune_gains, tune_portfolio, utilization_codes)
from repro.lab.sweep import oracle_history

# Worst-case error of the streaming p99: 12-level bisection bracket
# (2^-13 of the QUANT_RANGE span) plus half a bin.  The satellite
# acceptance bound is 0.005; the implementation is ~10x tighter.
P99_TOL = 0.005


SCENARIO_SHRINKS = {
    "bursty-serving": dict(n_nodes=48, n_intervals=300),
    "hetero-fleet": dict(n_nodes=48, n_intervals=250),
    "swap-storm": dict(n_nodes=32, n_intervals=300),
}


@pytest.mark.parametrize("name", sorted(SCENARIO_SHRINKS))
def test_streaming_quantile_accuracy_vs_numpy(name):
    """Engine p99 within 0.005 of np.quantile over the dense history,
    across bursty / heterogeneous / swap-pressure demand shapes."""
    spec = get_scenario(name).replace(**SCENARIO_SHRINKS[name])
    p = paper_controller_params()
    demand = spec.build_demand(seed=4)
    m = spec.build_node_memory(seed=4)
    stats = sweep_demand(demand, GainSet.from_params(p), node_memory=m,
                         interval_s=spec.interval_s,
                         occupancy=spec.occupancy)
    ref, _ = oracle_history(demand, m, p, occupancy=spec.occupancy)
    assert abs(float(stats.p99_utilization[0])
               - np.quantile(ref, 0.99)) <= P99_TOL
    # the streamed companions stay pinned to the dense history too
    np.testing.assert_allclose(float(stats.mean_utilization[0]),
                               ref.mean(), rtol=1e-4)
    np.testing.assert_allclose(float(stats.max_utilization[0]),
                               ref.max(), rtol=1e-4)


def test_quantile_from_codes_unit():
    """The fixed-bin bisection against np.quantile on known samples."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    lo, hi = QUANT_RANGE
    for sample in (rng.uniform(0.2, 1.4, 20_000),               # smooth
                   np.concatenate([rng.normal(0.6, 0.05, 15_000),
                                   rng.normal(1.2, 0.02, 5_000)]),  # bimodal
                   np.full(8_192, 0.9731)):                     # point mass
        sample = np.clip(sample, lo, hi - 1e-6).astype(np.float32)
        sample = sample[:sample.size - sample.size % 64]
        codes = utilization_codes(jnp.asarray(sample.reshape(64, -1)))
        for q in (0.5, 0.99):
            got = float(quantile_from_codes(codes, q, sample.size))
            assert abs(got - np.quantile(sample, q)) <= P99_TOL, q


def test_device_resident_chunking_invariance():
    """Chunk size (auto or explicit, padded or exact) is invisible."""
    p = paper_controller_params()
    gains = grid_gains(p, lam=(0.3, 0.7, 1.1), r0=(0.9, 0.94, 0.97))
    spec = get_scenario("bursty-serving").replace(n_nodes=32,
                                                  n_intervals=200)
    runs = [run_sweep(spec, gains, seed=2, chunk=c)
            for c in (None, 2, 5, 16)]
    for other in runs[1:]:
        for f in FleetStats._fields:
            np.testing.assert_array_equal(
                getattr(runs[0].stats, f), getattr(other.stats, f),
                err_msg=f)


MULTIDEVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core.cluster_sim import paper_controller_params
from repro.core.traces import fleet_demand_traces
from repro.lab import FleetStats, get_scenario, grid_gains, sweep_demand
p = paper_controller_params()
demand = fleet_demand_traces(64, 300, p.interval_s, seed=3)
gains = grid_gains(p, lam=(0.3, 0.6, 0.9, 1.2), r0=(0.9, 0.93, 0.95))
assert len(jax.local_devices()) == 4
cache = get_scenario("cache-churn").cache
for kw in ({}, {"cache": cache}):       # saturated store AND CacheLoop
    multi = sweep_demand(demand, gains, node_memory=p.total_memory,
                         interval_s=p.interval_s, **kw)  # auto-detect: 4
    single = sweep_demand(demand, gains, node_memory=p.total_memory,
                          interval_s=p.interval_s, devices=1, **kw)
    for f in FleetStats._fields:
        assert np.array_equal(getattr(multi, f), getattr(single, f)), (kw, f)
print("MULTIDEVICE_PARITY_OK")
"""


@pytest.mark.slow
def test_sharded_sweep_matches_single_device():
    """Gain-axis shard_map over 4 forced host devices is bit-identical
    to the single-device path, with and without cache state in the
    carry."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", MULTIDEVICE_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MULTIDEVICE_PARITY_OK" in proc.stdout


# ---------------------------------------------------------------------------
# Successive halving + portfolio tuning
# ---------------------------------------------------------------------------

def test_halving_reaches_grid_best_on_swap_storm():
    grid = tune_gains("swap-storm", method="grid", budget=64, seed=0)
    halv = tune_gains("swap-storm", method="halving", budget=64, seed=0)
    assert halv.score >= grid.score - 1e-9
    assert halv.params == grid.params
    assert halv.score >= halv.baseline_score
    # round schedule: shrinking candidates over growing horizons
    horizons = [r["horizon"] for r in halv.rounds]
    cands = [r["n_candidates"] for r in halv.rounds]
    assert horizons == sorted(horizons) and horizons[-1] == 1000
    assert cands[0] > cands[-1]
    # the cheap rounds simulate a fraction of the grid's node-intervals
    # (the widened default grid may exceed the nominal budget)
    grid_work = 1000 * grid.sweep.n_configs
    halv_work = sum(r["horizon"] * r["n_candidates"] for r in halv.rounds)
    assert halv_work <= grid_work / 3


def test_halving_prefix_rounds_validate_args():
    with pytest.raises(ValueError):
        halving_tune("swap-storm", rounds=(0.0, 1.0))
    with pytest.raises(ValueError):
        run_sweep("swap-storm",
                  grid_gains(lam=(0.5,), r0=(0.95,)), horizon=10**9)


def test_portfolio_tuning_worst_case():
    scenarios = ["swap-storm", "bursty-serving"]
    small = [get_scenario(s).replace(n_nodes=24, n_intervals=200)
             for s in scenarios]
    result = tune_portfolio(small, budget=16, aggregate="worst", seed=1)
    assert result.score >= result.baseline_score
    assert set(result.scenario_scores) == {s.name for s in small}
    # worst-case aggregate: the reported score is the winner's minimum
    assert result.score == pytest.approx(
        min(result.scenario_scores.values()), rel=1e-6)
    mean_r = tune_portfolio(small, budget=16, aggregate="mean", seed=1)
    assert mean_r.score >= result.score - 1e-9   # mean >= min pointwise
    with pytest.raises(ValueError):
        tune_portfolio([], budget=4)
    with pytest.raises(ValueError):
        tune_portfolio(small, aggregate="median")
