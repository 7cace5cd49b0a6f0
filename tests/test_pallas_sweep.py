"""PallasSweep: the fused engine pinned to the XLA path.

Four pins hold the PR-9 engine in place:

* **cross-engine parity** -- ``engine="pallas"`` must reproduce
  ``engine="xla"`` stat for stat on the registry scenarios, bit for
  bit on the saturated-store and the cache paths alike;
* **lowering parity** -- the production CPU scan and the true
  ``pallas_call`` interpret-mode kernel lower the same step
  (``repro.lab.step``), so they must agree bit for bit,
  deterministically across runs;
* **in-scan halving identity** -- the device-side successive-halving
  program must select the same survivors and return the same tuned
  params as the host-loop ``halving_tune`` it replaces;
* **API surface** -- ``engine=`` is uniform across the sweep and tune
  entry points, old spellings warn exactly once through the ``_compat``
  shims, and unknown engines fail fast.
"""

import warnings

import numpy as np
import pytest

import repro.lab as lab
from repro.core.cluster_sim import paper_controller_params
from repro.core.traces import GiB
from repro.fleet import fleet_sweep_demand
from repro.lab import (FleetStats, GainSet, get_scenario, grid_gains,
                       halving_tune, run_sweep, sweep_demand, tune_gains)
from repro.lab import pallas_sweep
from repro.lab._compat import reset_warnings
from repro.lab.pallas_sweep import (halving_schedule, halving_sweep,
                                    pallas_sweep_demand)

P = paper_controller_params()


def _scenario(name, n_nodes, n_intervals, cache=True, seed=3):
    spec = get_scenario(name).replace(n_nodes=n_nodes,
                                      n_intervals=n_intervals)
    if not cache:
        spec = spec.replace(cache=None)
    return (spec.build_demand(seed=seed), spec.build_node_memory(seed=seed),
            spec.cache)


def _gains(n_lam=3, n_r0=2):
    return grid_gains(P, lam=np.linspace(0.2, 1.7, n_lam),
                      r0=np.linspace(0.88, 0.97, n_r0))


def _stats_dict(stats):
    return {k: np.asarray(v, np.float64) for k, v in stats._asdict().items()}


def _assert_stats_close(a, b, rtol_default=1e-4, rtol_p99=5e-4,
                        loose=()):
    da, db = _stats_dict(a), _stats_dict(b)
    assert set(da) == set(db)
    for name in da:
        rtol = rtol_p99 if name == "p99_utilization" else rtol_default
        if name in loose:
            rtol = max(rtol, 5e-2)
        np.testing.assert_allclose(
            da[name], db[name], rtol=rtol, atol=1e-12,
            err_msg=f"engine mismatch on {name}")


# ---------------------------------------------------------------------------
# Cross-engine parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["bursty-serving", "hetero-fleet",
                                  "swap-storm"])
def test_engine_parity_saturated_store(name):
    """Non-cache scenarios: the fused step is the XLA step bit for bit."""
    demand, m, _ = _scenario(name, n_nodes=16, n_intervals=120, cache=False)
    gains = _gains()
    kw = dict(node_memory=m, interval_s=P.interval_s)
    ref = sweep_demand(demand, gains, engine="xla", **kw)
    got = sweep_demand(demand, gains, engine="pallas", **kw)
    da, db = _stats_dict(ref), _stats_dict(got)
    for field in FleetStats._fields:
        np.testing.assert_array_equal(
            da[field], db[field],
            err_msg=f"{name}: {field} not bit-identical across engines")


def test_engine_parity_cacheloop():
    """CacheLoop scenario: the hit curve's pow included, bit for bit."""
    demand, m, cache = _scenario("spark-iterative-cache", 12, 150)
    assert cache is not None
    gains = _gains()
    kw = dict(node_memory=m, interval_s=P.interval_s, cache=cache)
    ref = sweep_demand(demand, gains, engine="xla", **kw)
    got = sweep_demand(demand, gains, engine="pallas", **kw)
    da, db = _stats_dict(ref), _stats_dict(got)
    for field in FleetStats._fields:
        np.testing.assert_array_equal(
            da[field], db[field],
            err_msg=f"cache path: {field} not bit-identical")


def test_run_sweep_engine_kwarg_roundtrip():
    """run_sweep(engine=...) carries parity through the result object."""
    spec = get_scenario("swap-storm").replace(n_nodes=12, n_intervals=100)
    a = run_sweep(spec, _gains(2, 2), engine="xla", seed=5)
    b = run_sweep(spec, _gains(2, 2), engine="pallas", seed=5)
    np.testing.assert_array_equal(a.scores(), b.scores())
    assert a.best() == b.best()


# ---------------------------------------------------------------------------
# Lowering parity + determinism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_nodes,n_intervals,n_side", [
    (8, 48, 2),
    # two gain tiles x two node tiles: the Mosaic grid's block indexing
    (2 * pallas_sweep.NODE_TILE, 40, 4)])
def test_scan_matches_interpret_kernel(n_nodes, n_intervals, n_side):
    """The production scan and the pallas_call interpret kernel share
    one jaxpr; both lowerings must agree bit for bit."""
    demand, m, cache = _scenario("spark-iterative-cache", n_nodes,
                                 n_intervals, seed=1)
    gains = _gains(n_side, n_side)
    kw = dict(node_memory=m, interval_s=P.interval_s, cache=cache)
    a = pallas_sweep_demand(demand, gains, **kw)
    b = pallas_sweep_demand(demand, gains, force_interpret=True, **kw)
    da, db = _stats_dict(a), _stats_dict(b)
    for field in FleetStats._fields:
        np.testing.assert_array_equal(
            da[field], db[field],
            err_msg=f"scan vs interpret: {field} diverged")


def test_interpret_mode_deterministic():
    demand, m, _ = _scenario("bursty-serving", 8, 48, cache=False, seed=2)
    gains = _gains(2, 2)
    kw = dict(node_memory=m, interval_s=P.interval_s, force_interpret=True)
    a = pallas_sweep_demand(demand, gains, **kw)
    b = pallas_sweep_demand(demand, gains, **kw)
    for field in FleetStats._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)))


def test_chunk_invariance():
    """Lane-chunked dispatch must not change any stat."""
    demand, m, _ = _scenario("hetero-fleet", 12, 80, cache=False)
    gains = _gains(3, 3)
    kw = dict(node_memory=m, interval_s=P.interval_s)
    whole = pallas_sweep_demand(demand, gains, **kw)
    chunked = pallas_sweep_demand(demand, gains, chunk=8, **kw)
    for field in FleetStats._fields:
        np.testing.assert_array_equal(np.asarray(getattr(whole, field)),
                                      np.asarray(getattr(chunked, field)))


def test_horizon_and_bf16():
    """horizon= truncates identically to a sliced trace; bf16 demand
    storage stays within loose tolerance of the f32 reference."""
    demand, m, _ = _scenario("swap-storm", 12, 120, cache=False)
    gains = _gains(2, 2)
    kw = dict(node_memory=m, interval_s=P.interval_s)
    a = sweep_demand(demand, gains, engine="pallas", horizon=64, **kw)
    b = sweep_demand(demand[:, :64], gains, engine="pallas", **kw)
    for field in FleetStats._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)))
    lo = pallas_sweep_demand(demand, gains, precision="bf16", **kw)
    _assert_stats_close(
        sweep_demand(demand, gains, engine="pallas", **kw), lo,
        rtol_default=5e-2, rtol_p99=5e-2,
        loose=FleetStats._fields)


# ---------------------------------------------------------------------------
# In-scan halving
# ---------------------------------------------------------------------------

def _random_gains(n, seed=7):
    rng = np.random.default_rng(seed)
    return GainSet(
        r0=rng.uniform(0.85, 0.98, n).astype(np.float32),
        lam=rng.uniform(0.2, 1.8, n).astype(np.float32),
        lam_grant=np.full(n, 0.5, np.float32),
        u_min=np.full(n, float(8 * GiB), np.float32),
        u_max=np.full(n, float(125 * GiB), np.float32),
        deadband=np.zeros(n, np.float32),
        feedforward=np.zeros(n, np.float32))


def test_halving_schedule_matches_host_arithmetic():
    horizons, keeps = halving_schedule(160, 24, (0.125, 0.5, 1.0), 0.25, 4)
    assert horizons == [20, 80, 160]
    assert keeps == [6, 4]
    horizons, keeps = halving_schedule(100, 8, (0.5, 1.0), 0.5, 2)
    assert horizons == [50, 100]
    assert keeps == [4]


def test_in_scan_halving_matches_host_tuner():
    """engine="pallas" halving_tune = the host loop: same survivors,
    same tuned params, same baseline score."""
    spec = get_scenario("swap-storm").replace(n_nodes=16, n_intervals=160)
    gains = _random_gains(24)
    a = halving_tune(spec, gains=gains, seed=5, engine="xla")
    b = halving_tune(spec, gains=gains, seed=5, engine="pallas")
    assert a.params == b.params
    assert np.isclose(a.score, b.score)
    assert np.isclose(a.baseline_score, b.baseline_score)
    assert [r["horizon"] for r in a.rounds] == \
        [r["horizon"] for r in b.rounds]
    assert [r["n_candidates"] for r in a.rounds] == \
        [r["n_candidates"] for r in b.rounds]


def test_halving_sweep_single_dispatch_masks_dead_lanes():
    """The in-scan program returns final-round stats for survivors plus
    the baseline lane, and survivor indices point into the candidates."""
    demand, m, cache = _scenario("spark-iterative-cache", 10, 96, seed=4)
    gains = _random_gains(12, seed=9)
    base = GainSet.from_params(P)
    hs = halving_sweep(demand, gains, base, node_memory=m,
                       interval_s=P.interval_s, cache=cache)
    n_final = len(hs.scores)
    assert n_final == len(hs.survivor_idx) + 1      # + baseline lane
    assert np.all(hs.survivor_idx >= 0)
    assert np.all(hs.survivor_idx < 12)
    assert len(set(hs.survivor_idx.tolist())) == len(hs.survivor_idx)
    assert np.asarray(hs.stats.mean_utilization).shape == (n_final,)
    assert hs.elapsed_s > 0.0
    assert [r["lanes"] for r in hs.rounds] == [16, 8, 8]
    # Survivors' final stats equal a plain full-horizon sweep of the
    # same lanes: masking dead lanes must not perturb live ones.
    survivors = gains.take(hs.survivor_idx).concat(base)
    ref = pallas_sweep_demand(demand, survivors, node_memory=m,
                              interval_s=P.interval_s, cache=cache)
    np.testing.assert_array_equal(
        np.asarray(ref.mean_utilization),
        np.asarray(hs.stats.mean_utilization))


# ---------------------------------------------------------------------------
# API surface: engine=, shims, fallbacks
# ---------------------------------------------------------------------------

def test_unknown_engine_raises():
    demand, m, _ = _scenario("swap-storm", 8, 40, cache=False)
    with pytest.raises(ValueError, match="engine"):
        sweep_demand(demand, _gains(2, 2), node_memory=m,
                     interval_s=P.interval_s, engine="tpu")
    spec = get_scenario("swap-storm").replace(n_nodes=8, n_intervals=40)
    with pytest.raises(ValueError, match="engine"):
        tune_gains(spec, budget=4, engine="mosaic")


def test_fleet_pallas_falls_back_to_xla_with_warning():
    rng = np.random.default_rng(0)
    k, n, t = 2, 6, 60
    demand = (rng.uniform(10.0, 30.0, (k, n, t)) * GiB)
    kw = dict(node_memory=float(125 * GiB),
              weights=np.array([2.0, 1.0]),
              floors=np.array([8.0, 0.0]) * GiB,
              epoch_intervals=30, interval_s=0.1)
    gains = _gains(2, 2)
    reset_warnings()
    with pytest.warns(RuntimeWarning, match="falling back"):
        got, _ = fleet_sweep_demand(demand, gains, engine="pallas", **kw)
    ref, _ = fleet_sweep_demand(demand, gains, engine="xla", **kw)
    for field in FleetStats._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(ref, field)))


def test_score_fn_kwarg_warns_once_and_routes():
    spec = get_scenario("swap-storm").replace(n_nodes=8, n_intervals=40)
    reset_warnings()
    with pytest.warns(DeprecationWarning, match="score_fn"):
        old = tune_gains(spec, budget=4, score_fn="runtime")
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # warn-once: second is clean
        again = tune_gains(spec, budget=4, score_fn="runtime")
    new = tune_gains(spec, budget=4, objective="runtime")
    assert old.params == new.params == again.params
    assert np.isclose(old.score, new.score)


def test_renamed_module_attrs_warn_through_shims():
    import repro.lab.sweep as sweep_mod
    import repro.lab.tune as tune_mod
    reset_warnings()
    with pytest.warns(DeprecationWarning, match="XLA_DEFAULT_CHUNK"):
        assert lab.DEFAULT_CHUNK == lab.XLA_DEFAULT_CHUNK
    reset_warnings()
    with pytest.warns(DeprecationWarning, match="XLA_DEFAULT_CHUNK"):
        assert sweep_mod.DEFAULT_CHUNK == sweep_mod.XLA_DEFAULT_CHUNK
    reset_warnings()
    with pytest.warns(DeprecationWarning, match="Objective"):
        assert tune_mod.ScoreFn is tune_mod.Objective
    with pytest.raises(AttributeError):
        lab.NOT_A_REAL_NAME
