"""``correct`` comes out false when the timed path is broken or replaced.

Each test drives a whole run of a cell at toy sizes on the CPU (the
harness's look for a chip skipped), with one fault planted underneath
the timed path, or with the cell's lower-precision control in the
program's place; the run must report ``correct: false``.
"""

import io
import json
import time

import numpy as np
import pytest

from bench import fleet, generator, harness

TINY = {"sweep.hpcc": dict(n_nodes=64, n_intervals=240),
        "tune.cache": dict(n_nodes=64, n_intervals=1200),
        "plane.hpcc": dict(n_nodes=32, n_intervals=240)}
TUNE_TRAFFIC = {"gain_groups": [
    dict(g, n=g["n"] // 16)
    for g in fleet.load_json("traffic", "halving-512")["gain_groups"]]}


def run(workload, hook=None, seconds=1.0):
    out = io.StringIO()
    harness.run_cell(workload, 2**31 + 99, seconds, False,
                     t_start=time.perf_counter(),
                     config_override=TINY[workload],
                     traffic_override=(TUNE_TRAFFIC
                                       if workload == "tune.cache" else {}),
                     allow_cpu=True, out=out, err=io.StringIO(),
                     program_hook=hook)
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", sorted(TINY))
def test_sound_run_is_correct(workload):
    res = run(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


# -- faults planted in the timed path ----------------------------------------

def _alter_sweep(gen):
    inner = gen.__class__.call

    def call(self):
        work = inner(self)
        self.results[-1]["mean_capacity_gib"] = \
            self.results[-1]["mean_capacity_gib"] * 1.01
        return work
    gen.call = call.__get__(gen)


def _half_fleet_sweep(gen):
    setup = gen.setup

    def half():
        setup()
        n = gen.demand.shape[0] // 2
        sweep = gen._sweep
        gen._sweep = lambda demand, gains, node_memory, **kw: sweep(
            demand[:n], gains, node_memory=node_memory[:n], **kw)
    gen.setup = half


def _alter_tune_winner(gen):
    inner = gen.__class__.call

    def call(self):
        work = inner(self)
        res = self.results[-1]
        scores = np.asarray(res["stats"]["mean_capacity_gib"])
        res["winner"] = int(np.argmin(scores[:-1]))
        return work
    gen.call = call.__get__(gen)


def _half_candidates_tune(gen):
    """Every other candidate left out of the ranking: the program sees
    it with no gain at all, so it never survives a rung."""
    run = gen._run

    def half(gains):
        g = {k: v.copy() for k, v in gains.items()}
        g["lam"][1::2] = 0.0
        g["lam_grant"][1::2] = 0.0
        return run(g)
    gen._run = half


def _paper_group_wrong_tune(monkeypatch):
    """One lane group wrong: the paper-law candidates (the first group)
    run at half their gain, and the tune answers with the candidates as
    they were drawn."""
    import dataclasses
    from repro.lab import pallas_sweep
    orig = pallas_sweep.halving_sweep
    n = TUNE_TRAFFIC["gain_groups"][0]["n"]

    def wrong(demand, gains, base, **kw):
        lam, lam_grant = np.array(gains.lam), np.array(gains.lam_grant)
        lam[:n] *= 0.5
        lam_grant[:n] *= 0.5
        return orig(demand, dataclasses.replace(gains, lam=lam,
                                                lam_grant=lam_grant),
                    base, **kw)
    monkeypatch.setattr(pallas_sweep, "halving_sweep", wrong)


def _half_fleet_tune(gen):
    setup = gen.setup

    def half():
        setup()
        n = gen.demand.shape[0] // 2
        spec = gen.spec
        from repro.lab.scenarios import ReplayTrace
        gen.spec = spec.replace(n_nodes=n, replay=ReplayTrace(
            gen.demand[:n], gen.m[:n], interval_s=spec.interval_s))
    gen.setup = half


def _frozen_plane(monkeypatch):
    from repro.core.plane import ArrayController
    flush = ArrayController.flush

    def frozen(self):
        u = self._u.copy()
        actions = flush(self)
        self._u[:] = u                       # the step leaves state as it was
        return actions
    monkeypatch.setattr(ArrayController, "flush", frozen)


def _half_plane(monkeypatch):
    from repro.core.plane import ArrayController
    flush = ArrayController.flush

    def half(self):
        u = self._u.copy()
        actions = flush(self)
        k = u.size // 2
        self._u[k:] = u[k:]                  # half the nodes never step
        return actions
    monkeypatch.setattr(ArrayController, "flush", half)


def _altered_grants(monkeypatch):
    from repro.core.plane import ArrayController
    flush = ArrayController.flush

    def altered(self):
        actions = flush(self)
        for a in actions:
            object.__setattr__(a, "u_next", a.u_next * 1.01)
        return actions
    monkeypatch.setattr(ArrayController, "flush", altered)


@pytest.mark.parametrize("workload,hook", [
    ("sweep.hpcc", _alter_sweep), ("sweep.hpcc", _half_fleet_sweep),
    ("tune.cache", _alter_tune_winner), ("tune.cache", _half_fleet_tune),
    ("tune.cache", _half_candidates_tune),
], ids=["sweep-answer-altered", "sweep-half-fleet", "tune-winner-altered",
        "tune-half-fleet", "tune-half-candidates"])
def test_planted_fault_is_not_correct(workload, hook):
    assert not run(workload, hook)["correct"]


def test_planted_lane_group_fault_is_not_correct(monkeypatch):
    _paper_group_wrong_tune(monkeypatch)
    assert not run("tune.cache")["correct"]


@pytest.mark.parametrize("plant", [_frozen_plane, _half_plane,
                                   _altered_grants],
                         ids=["state-unchanged", "half-the-nodes",
                              "grant-altered"])
def test_planted_plane_fault_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    assert not run("plane.hpcc")["correct"]


# -- the lower-precision control ----------------------------------------------

def test_control_is_not_correct_sweep():
    cfg = dict(fleet.load_json("configs", "hpcc-fleet-4096"),
               **TINY["sweep.hpcc"])
    traffic = fleet.load_json("traffic", "grid-sweep-64")
    law = fleet.controller(cfg)
    d = generator.make(cfg, traffic, 123, 1)
    d.demand, d.m = fleet.build_fleet(cfg, 123)
    gains = fleet.draw_gains(traffic["gain_groups"], law,
                             np.random.default_rng(1))
    d.sampled = {k: v[::8] for k, v in gains.items()}
    d.want = d.reference(d.sampled)
    assert d.control()["stats_gap"] > traffic["limits"]["stats_gap"]


def test_control_is_not_correct_plane():
    cfg = dict(fleet.load_json("configs", "hpcc-fleet-4096"),
               **TINY["plane.hpcc"])
    traffic = fleet.load_json("traffic", "paced-ticks")
    d = generator.make(cfg, traffic, 5, 1)
    d.demand, d.m = fleet.build_fleet(cfg, 5)
    d.subset = np.arange(cfg["n_nodes"])
    d.results = [None] * 60
    d.want = d.reference(60)
    assert d.control()["grant_gap"] > traffic["limits"]["grant_gap"]


def test_control_is_not_correct_tune():
    """The program's own bf16 demand stream, at the fleet size of the
    planted faults above."""
    cfg = dict(fleet.load_json("configs", "spark-cache-fleet-4096"),
               **TINY["tune.cache"])
    traffic = dict(fleet.load_json("traffic", "halving-512"),
                   **TUNE_TRAFFIC)
    d = generator.make(cfg, traffic, 2**31 + 5, 1)
    d.setup()
    d.mark_window()
    d.call()
    d.checked = len(d.results) - 1
    d.compare(d.drawn[-1], d.results[-1])
    limits = traffic["limits"]
    control = d.control()
    assert any(v > limits[k] for k, v in control.items()), control
