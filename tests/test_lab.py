"""ScenarioLab tests: registry, sweep-engine parity, scoring, tuning."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dynims import PAPER_TABLE_I, tuned_params, tuned_scenarios
from repro.core import GiB, MemoryPlane
from repro.core.cluster_sim import paper_controller_params, simulate_fleet
from repro.core.traces import fleet_demand_traces
from repro.lab import (FleetStats, GainSet, ScenarioSpec, compute_fleet_stats,
                       default_score, get_scenario, grid_gains,
                       list_scenarios, random_gains, register_scenario,
                       run_sweep, stats_to_dict, sweep_demand, tune_gains)
from repro.lab.sweep import paper_law_mask, stage_demand


# ---------------------------------------------------------------------------
# Scenario registry
# ---------------------------------------------------------------------------

def test_registry_ships_paper_and_stress_scenarios():
    names = list_scenarios()
    assert len(names) >= 8
    for c in (1, 2, 3, 4):
        assert any(n.startswith(f"paper-c{c}") for n in names)
    for stress in ("bursty-serving", "hetero-fleet", "swap-storm",
                   "phase-replay"):
        assert stress in names


def test_scenarios_compile_to_dense_demand():
    for name in list_scenarios():
        spec = get_scenario(name)
        demand = spec.build_demand(seed=0)
        assert demand.shape == (spec.n_nodes, spec.n_intervals), name
        assert np.isfinite(demand).all() and (demand >= 0).all(), name
        m = spec.build_node_memory(seed=0)
        assert m.shape == (spec.n_nodes,) and (m > 0).all(), name


def test_scenario_determinism_and_seed_sensitivity():
    spec = get_scenario("bursty-serving")
    np.testing.assert_array_equal(spec.build_demand(seed=5),
                                  spec.build_demand(seed=5))
    assert not np.array_equal(spec.build_demand(seed=5),
                              spec.build_demand(seed=6))


def test_scenario_knobs():
    hetero = get_scenario("hetero-fleet")
    m = hetero.build_node_memory(seed=0)
    assert m.std() > 0, "memory_jitter must spread per-node budgets"
    churn = get_scenario("failover-churn")
    demand = churn.build_demand(seed=0)
    # some nodes collapse to the failure remnant at some point
    assert (demand.min(axis=1) < 0.2 * demand.max(axis=1)).any()
    with pytest.raises(ValueError):
        ScenarioSpec(name="bad", family="nope")
    with pytest.raises(KeyError):
        get_scenario("no-such-scenario")


def test_register_scenario_no_silent_overwrite():
    spec = ScenarioSpec(name="tmp-test-scenario", n_nodes=2, n_intervals=8)
    register_scenario(spec, overwrite=True)
    with pytest.raises(ValueError):
        register_scenario(spec)
    assert get_scenario("tmp-test-scenario") is spec


# ---------------------------------------------------------------------------
# Sweep engine: parity with the Python-loop fleet sim
# ---------------------------------------------------------------------------

PARITY_KEYS = ("mean_utilization", "p99_utilization", "max_utilization",
               "mean_capacity_gib", "capacity_std_gib",
               "frac_intervals_over_r0", "max_over_r0")

# The device-resident engine estimates p99 with the streaming fixed-bin
# quantile (12-level bisection over 65536 bins): worst-case bracket
# error is QUANT_RANGE span * 2^-13 ~= 2.4e-4 plus half a bin, so p99
# gets its own parity tolerance; every other metric stays exact to
# float32 ulps.
P99_ATOL = 5e-4


def assert_engine_parity(lab, ref):
    for k in PARITY_KEYS:
        atol = P99_ATOL if k == "p99_utilization" else 1e-5
        np.testing.assert_allclose(lab[k], ref[k], rtol=1e-4, atol=atol,
                                   err_msg=k)


def test_sweep_parity_with_python_fleet_sim():
    """A 1-gain, paper-config sweep reproduces simulate_fleet's stability
    metrics within float32 tolerance."""
    ref = simulate_fleet(n_nodes=128, n_intervals=400, seed=2,
                         engine="python")
    lab = simulate_fleet(n_nodes=128, n_intervals=400, seed=2, engine="lab")
    assert_engine_parity(lab, ref)


def test_engine_parity_beyond_paper_knobs():
    """Both engines must run the same law for asymmetric/deadband/
    feedforward params, not just the paper-faithful defaults."""
    p = paper_controller_params(lam_grant=0.2, deadband=0.005,
                                feedforward=0.5)
    ref = simulate_fleet(48, 200, seed=5, params=p, engine="python")
    lab = simulate_fleet(48, 200, seed=5, params=p, engine="lab")
    assert_engine_parity(lab, ref)


def test_sweep_demand_matches_direct_gainset_call():
    p = paper_controller_params()
    demand = fleet_demand_traces(32, 200, p.interval_s, seed=7)
    stats = sweep_demand(demand, GainSet.from_params(p),
                         node_memory=p.total_memory, interval_s=p.interval_s)
    ref = simulate_fleet(n_nodes=32, n_intervals=200, seed=7,
                         engine="python")
    assert stats.mean_utilization.shape == (1,)
    np.testing.assert_allclose(float(stats.p99_utilization[0]),
                               ref["p99_utilization"], rtol=1e-4,
                               atol=P99_ATOL)


def test_sweep_chunking_invariant():
    """Chunk size is an implementation detail: stats must not change."""
    p = paper_controller_params()
    gains = grid_gains(p, lam=(0.3, 0.6, 0.9), r0=(0.92, 0.95, 0.97))
    a = run_sweep("swap-storm", gains, seed=1, chunk=2)
    b = run_sweep("swap-storm", gains, seed=1, chunk=16)
    for f in FleetStats._fields:
        np.testing.assert_allclose(getattr(a.stats, f), getattr(b.stats, f),
                                   rtol=1e-6, err_msg=f)


# ---------------------------------------------------------------------------
# Demand staging
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", ["float64", "float32", "horizon_view"])
def test_staged_demand_equals_host_transpose(layout, dtype):
    """Cast on the host, transpose on the device: the operand is the
    host transpose bit for bit (then rounded on the device for bf16)."""
    demand = np.random.default_rng(4).uniform(20, 90, (12, 70)) * GiB
    if layout == "float32":
        demand = demand.astype(np.float32)
    elif layout == "horizon_view":
        demand = demand[:, :45]           # a strided view, as horizon= cuts
    host = np.ascontiguousarray(demand.T, np.float32)
    want = np.asarray(jnp.asarray(host).astype(dtype))
    got = np.asarray(stage_demand(demand, jnp.dtype(dtype)))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_mixed_law_sweep_equals_stitched_class_calls(engine):
    """Both law classes run on the demand staged once; each class's
    stats equal its own single-class call's, bit for bit."""
    p = paper_controller_params()
    demand = fleet_demand_traces(24, 150, p.interval_s, seed=9)
    lam = np.linspace(0.3, 1.5, 7)
    grant = lam.copy()
    grant[[1, 4, 5]] = 0.25
    gains = GainSet(r0=0.95, lam=lam, lam_grant=grant, u_min=0.0,
                    u_max=p.u_max)
    kw = dict(node_memory=p.total_memory, interval_s=p.interval_s,
              engine=engine)
    mixed = sweep_demand(demand, gains, **kw)
    mask = paper_law_mask(gains)
    for idx in (np.flatnonzero(mask), np.flatnonzero(~mask)):
        part = sweep_demand(demand, gains.take(idx), **kw)
        for f in FleetStats._fields:
            np.testing.assert_array_equal(getattr(mixed, f)[idx],
                                          getattr(part, f), err_msg=f)


def test_gain_set_construction_and_roundtrip():
    p = paper_controller_params(lam=0.7, r0=0.93, lam_grant=0.2,
                                deadband=0.01, feedforward=0.5)
    g = GainSet.from_params(p)
    assert len(g) == 1
    assert g.params_at(0, PAPER_TABLE_I) == PAPER_TABLE_I.replace(
        lam=0.7, r0=0.93, lam_grant=0.2, deadband=0.01, feedforward=0.5)
    sym = GainSet.from_params(paper_controller_params())
    assert sym.params_at(0, PAPER_TABLE_I).lam_grant is None
    grid = grid_gains(lam=(0.2, 0.5), r0=(0.9, 0.95), lam_grant=(None, 0.1))
    assert len(grid) == 8
    rnd = random_gains(17, seed=3)
    assert len(rnd) == 17
    assert (rnd.lam > 0).all() and (rnd.lam < 2).all()
    with pytest.raises(ValueError):
        GainSet(r0=np.ones(2), lam=np.ones(3), lam_grant=np.ones(2),
                u_min=np.zeros(2), u_max=np.ones(2))


def test_sweep_honours_deadband_and_feedforward():
    """The loop a tune run scores is the loop the tuned params deploy:
    the beyond-paper knobs must change sweep output."""
    p = paper_controller_params()
    demand = fleet_demand_traces(16, 200, p.interval_s, seed=9)
    frozen = sweep_demand(
        demand, GainSet.from_params(p.replace(deadband=10.0)),
        node_memory=p.total_memory, interval_s=p.interval_s)
    # |r - r0| <= 10 always holds, so the law never moves u off u_max
    assert float(frozen.mean_capacity_gib[0]) == pytest.approx(
        p.u_max / GiB, rel=1e-6)
    assert float(frozen.capacity_std_gib[0]) == pytest.approx(0.0, abs=1e-6)
    base = sweep_demand(demand, GainSet.from_params(p),
                        node_memory=p.total_memory, interval_s=p.interval_s)
    ff = sweep_demand(demand, GainSet.from_params(p.replace(feedforward=1.0)),
                      node_memory=p.total_memory, interval_s=p.interval_s)
    assert float(ff.mean_capacity_gib[0]) != float(base.mean_capacity_gib[0])
    # slope feedforward acts ahead of ramps: it must not hurt overshoot
    assert float(ff.max_over_r0[0]) <= float(base.max_over_r0[0]) + 1e-6


# ---------------------------------------------------------------------------
# Scoring
# ---------------------------------------------------------------------------

def test_fleet_stats_on_known_history():
    # 2 intervals x 2 nodes, hand-checkable
    utils = np.array([[0.5, 0.9], [1.1, 0.96]], np.float32)
    caps = np.array([[10.0, 20.0], [30.0, 40.0]], np.float32) * GiB
    s = compute_fleet_stats(utils, caps, r0=0.95, interval_s=0.1)
    d = stats_to_dict(s)
    assert d["max_utilization"] == pytest.approx(1.1)
    assert d["frac_intervals_over_r0"] == pytest.approx(0.5)   # 1.1, 0.96
    assert d["pressure_violation_rate"] == pytest.approx(0.25)
    assert d["max_over_r0"] == pytest.approx(0.15, abs=1e-6)
    assert d["mean_capacity_gib"] == pytest.approx(25.0)
    assert d["granted_volume_gib_s"] == pytest.approx(5.0)  # (15+35)*0.1
    assert d["settle_intervals"] == 2.0      # last interval still over band
    calm = compute_fleet_stats(np.full((4, 2), 0.5, np.float32), caps=caps.repeat(2, 0),
                               r0=0.95, interval_s=0.1)
    assert stats_to_dict(calm)["settle_intervals"] == 0.0


def test_default_score_prefers_safe_high_grant():
    caps_hi = np.full((4, 2), 50.0, np.float32) * GiB
    caps_lo = np.full((4, 2), 20.0, np.float32) * GiB
    safe_hi = compute_fleet_stats(np.full((4, 2), 0.9, np.float32), caps_hi,
                                  r0=0.95, interval_s=0.1)
    safe_lo = compute_fleet_stats(np.full((4, 2), 0.9, np.float32), caps_lo,
                                  r0=0.95, interval_s=0.1)
    swapping = compute_fleet_stats(np.full((4, 2), 1.05, np.float32), caps_hi,
                                   r0=0.95, interval_s=0.1)
    assert float(default_score(safe_hi)) > float(default_score(safe_lo))
    assert float(default_score(safe_hi)) > float(default_score(swapping))


# ---------------------------------------------------------------------------
# Tuning
# ---------------------------------------------------------------------------

def test_tuned_gains_beat_paper_defaults_on_stress_scenario():
    """>= 64-point sweep returns gains that beat Table I off-testbed."""
    result = tune_gains("swap-storm", budget=64, seed=0)
    assert result.sweep.n_configs >= 64
    assert result.score > result.baseline_score
    assert result.params != result.baseline_params
    # the tuned params are deployable as-is
    assert 0 < result.params.lam < 2 and 0 < result.params.r0 <= 1


def test_tune_never_below_baseline_and_random_method():
    result = tune_gains("paper-c3-dynims60", method="random", budget=16,
                        seed=1)
    assert result.score >= result.baseline_score
    assert result.sweep.n_configs == 17      # budget + appended baseline


def test_tuned_presets_exposed_through_configs_and_plane():
    assert set(tuned_scenarios()) >= {"bursty-serving", "swap-storm",
                                      "hetero-fleet"}
    for name in tuned_scenarios():
        p = tuned_params(name)
        assert p != PAPER_TABLE_I, name
        assert 0 < p.lam < 2
    assert tuned_params("paper-c3-dynims60") == PAPER_TABLE_I
    assert tuned_params("swap-storm", u_max=30 * GiB).u_max == 30 * GiB
    with pytest.raises(KeyError):
        tuned_params("unknown-scenario")

    plane = MemoryPlane.for_scenario("bursty-serving")
    assert plane.spec.params == tuned_params("bursty-serving")
    assert plane.nodes() == []


# ---------------------------------------------------------------------------
# Batched trace generation (core/traces.py)
# ---------------------------------------------------------------------------

def test_fleet_demand_traces_shape_and_determinism():
    d = fleet_demand_traces(16, 300, 0.1, seed=4)
    assert d.shape == (16, 300)
    np.testing.assert_array_equal(d, fleet_demand_traces(16, 300, 0.1,
                                                         seed=4))
    flat = fleet_demand_traces(4, 100, 0.1, seed=4, amp_range=(1.0, 1.0),
                               phase_shift=False)
    np.testing.assert_array_equal(flat[0], flat[3])


def test_fleet_demand_traces_tiles_short_base():
    base = np.arange(10, dtype=np.float64)
    d = fleet_demand_traces(2, 25, 0.1, seed=0, base=base,
                            amp_range=(1.0, 1.0), phase_shift=False)
    assert d.shape == (2, 25)
    np.testing.assert_array_equal(d[0, :10], d[0, 10:20])
