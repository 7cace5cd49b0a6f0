"""The benchmark's references agree with the program at toy sizes."""

import numpy as np
import pytest

from bench import fleet, generator, peaks
from bench.reference import replay as ref

TUNE = generator.kind("tune")

CFG = dict(fleet.load_json("configs", "hpcc-fleet-4096"), n_nodes=48,
           n_intervals=240)
CACHE_CFG = dict(fleet.load_json("configs", "spark-cache-fleet-4096"),
                 n_nodes=48, n_intervals=240)
GROUPS = [{"n": 4, "lam": [0.1, 1.8], "r0": [0.88, 0.98]},
          {"n": 2, "lam": [0.3, 1.6], "r0": [0.9, 0.97], "lam_grant": 0.25},
          {"n": 2, "lam": [0.3, 1.6], "r0": [0.9, 0.97], "deadband": 0.005},
          {"n": 2, "lam": [0.3, 1.6], "r0": [0.9, 0.97],
           "feedforward": 0.5}]


def _sweep(cfg, gains, demand, m):
    from repro.lab import GainSet, sweep_demand
    return generator.stats_dict(sweep_demand(
        demand, GainSet(**gains), node_memory=m, interval_s=0.1,
        devices=1, cache=generator.cache_spec(cfg)))


@pytest.mark.parametrize("cfg", [CFG, CACHE_CFG], ids=["saturated", "cache"])
def test_replay_matches_program(cfg):
    demand, m = fleet.build_fleet(cfg, 2**31 + 7)
    law = fleet.controller(cfg)
    gains = fleet.draw_gains(GROUPS, law, np.random.default_rng(3))
    want = ref.replay_stats(demand, m, gains, interval_s=0.1,
                            cache=cfg["cache"], workers=2)
    gap, where = generator.stats_gap(_sweep(cfg, gains, demand, m), want)
    # within the repo's own parity units (p99 bracket, 1e-4 elsewhere)
    assert gap <= (1.0 if cfg["cache"] is None else 5.0), where


def test_grant_history_matches_live_plane():
    cfg = dict(CFG, n_nodes=16)
    d = generator.make(cfg, fleet.load_json("traffic", "paced-ticks"),
                     5, 1)
    d.setup()
    for _ in range(20):
        d.call()
    got = np.stack(d.results)
    assert d.grant_gap(got, d.reference(got.shape[0])) < 1e-6


def test_reference_halving_matches_program():
    cfg = dict(CACHE_CFG, n_nodes=32, n_intervals=160)
    traffic = dict(fleet.load_json("traffic", "halving-512"),
                   gain_groups=[dict(g, n=g["n"] // 16)
                                for g in fleet.load_json(
                                    "traffic", "halving-512")["gain_groups"]])
    d = generator.make(cfg, traffic, 11, 1)
    d.setup()
    d.mark_window()
    d.call()
    parts = d.compare(d.drawn[-1], d.results[-1])
    assert parts["rank"][0] <= 1.0, parts
    assert sorted(d.results[-1]["lanes"]) == sorted(
        int(x) for x in d.want["final_lanes"][:-1])


def test_schedule_updates_count_lanes_alive_per_rung():
    cfg = fleet.load_json("configs", "spark-cache-fleet-4096")
    traffic = fleet.load_json("traffic", "halving-512")
    assert TUNE.halving_schedule(3000, 512, traffic) == (
        [375, 1500, 3000], [128, 32])
    assert TUNE.schedule_updates(cfg, traffic) == \
        (513 * 375 + 129 * 1125 + 33 * 1500) * 4096


def test_demand_copy_matches_program_trace():
    from repro.core.traces import hpcc_trace
    got = fleet.hpcc_trace(3000, 0.1, np.random.default_rng(5), 0.5)
    np.testing.assert_array_equal(got, hpcc_trace(300.0, 0.1, seed=5))


def test_peaks_table_is_the_programs_and_refuses_unknown_kinds():
    from repro.roofline import constants
    assert dict(peaks.CHIPS) == {k: tuple(v) for k, v in
                                 constants.CHIPS.items()}
    with pytest.raises(KeyError):
        peaks.chip_peaks("TPU v99")


def test_score_weights_are_the_objectives():
    """The score tolerance weighs each field as the objective does."""
    base = {f: np.array([0.5]) for f in TUNE.SCORE_WEIGHTS}
    for f, w in TUNE.SCORE_WEIGHTS.items():
        moved = dict(base, **{f: base[f] + 1.0})
        assert abs(ref.default_score(moved) - ref.default_score(base)) == \
            pytest.approx(w)
    assert TUNE.score_tol(base)[0] > 0
