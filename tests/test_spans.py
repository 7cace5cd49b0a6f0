"""Spans and counters: the runtime helpers and the host paths that open
them."""

import threading
import time

import numpy as np
import pytest

from repro.analysis import runtime
from repro.core import (HealthPolicy, MemoryPlane, NodeSpec, PlaneSpec,
                        SimulatedMonitor, StoreRegistry)
from repro.core.cluster_sim import paper_controller_params
from repro.core.plane import TICK_PHASES
from repro.lab import GainSet, sweep_demand
from repro.lab.pallas_sweep import halving_sweep

TICK_SPANS = tuple(f"plane.tick.{p}" for p in TICK_PHASES)


@pytest.fixture(autouse=True)
def fresh_counters():
    runtime.reset_counters()
    yield
    runtime.reset_counters()


def test_span_nests_and_keeps_count_total_max_last():
    for pause in (0.002, 0.004):
        with runtime.span("outer"):
            with runtime.span("inner"):
                time.sleep(pause)
    st = runtime.span_stats()
    assert set(st) == {"outer", "inner"}
    assert st["inner"].count == st["outer"].count == 2
    assert st["inner"].max_s >= 0.004 and st["inner"].last_s >= 0.004
    assert 0.006 <= st["inner"].total_s <= st["outer"].total_s
    outer, inner = runtime.span_log("outer"), runtime.span_log("inner")
    for o, i in zip(outer, inner):
        assert o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns
        assert i.thread == threading.get_ident()


def test_span_works_without_profiler_and_records_on_error():
    with pytest.raises(ValueError):
        with runtime.span("boom"):
            raise ValueError("x")
    assert runtime.span_stats()["boom"].count == 1


def test_counters_add_filter_and_reset():
    runtime.count("a.x")
    runtime.count("a.x", 4)
    runtime.count("b.y", 2)
    assert runtime.counts() == {"a.x": 5, "b.y": 2}
    assert runtime.counts("a.") == {"a.x": 5}
    with runtime.span("s"):
        pass
    runtime.reset_counters()
    assert runtime.counts() == {} and runtime.span_stats() == {}
    assert runtime.span_log("s") == []


def test_span_log_is_bounded():
    for _ in range(runtime.SPAN_LOG + 5):
        with runtime.span("many"):
            pass
    assert runtime.span_stats()["many"].count == runtime.SPAN_LOG + 5
    assert len(runtime.span_log("many")) == runtime.SPAN_LOG


def _demand(n=8, t=40, seed=0):
    rng = np.random.default_rng(seed)
    return rng.uniform(20, 90, size=(n, t)) * 2**30


def _gains(n, asym=0):
    lam = np.linspace(0.2, 1.5, n)
    grant = lam.copy()
    grant[:asym] = 0.25
    return GainSet(r0=np.full(n, 0.95), lam=lam, lam_grant=grant,
                   u_min=0.0, u_max=60 * 2**30)


@pytest.mark.parametrize("n_gains,chunk,run", [(5, 2, 6), (4, 4, 4),
                                               (7, 3, 9)])
def test_sweep_counts_chunks_and_padded_lane_steps(n_gains, chunk, run):
    demand = _demand()
    sweep_demand(demand, _gains(n_gains), node_memory=125 * 2**30,
                 chunk=chunk, devices=1, horizon=30)
    c = runtime.counts("lab.sweep.")
    assert c["lab.sweep.chunks"] == run // chunk
    assert c["lab.sweep.lane_steps.live"] == n_gains * 30
    assert c["lab.sweep.lane_steps.run"] == run * 30
    st = runtime.span_stats()
    assert {k: v.count for k, v in st.items()} == {
        "lab.sweep.stage": 1, "lab.sweep.dispatch": 1,
        "lab.sweep.drain": 1, "lab.sweep.merge": 1}


def test_mixed_law_classes_pad_per_class_and_never_nest_stages():
    # 3 asymmetric-grant gains and 4 paper-law gains, chunk 3: the
    # classes pad to 3 and 6 lanes.
    sweep_demand(_demand(), _gains(7, asym=3), node_memory=125 * 2**30,
                 chunk=3, devices=1)
    c = runtime.counts("lab.sweep.")
    assert c["lab.sweep.chunks"] == 3
    assert c["lab.sweep.lane_steps.live"] == 7 * 40
    assert c["lab.sweep.lane_steps.run"] == 9 * 40
    stages = sorted(runtime.span_log("lab.sweep.stage"))
    assert len(stages) == 3                 # the split, then one per class
    for a, b in zip(stages, stages[1:]):
        assert a.end_ns <= b.start_ns
    assert runtime.span_stats()["lab.sweep.merge"].count == 3


@pytest.mark.parametrize("entry", ["xla", "pallas", "halving"])
def test_demand_staged_once_per_call(entry):
    # Mixed law classes on the sweeps; the halving runs them as one.
    demand = _demand(n=8, t=64)
    for _ in range(2):
        if entry == "halving":
            halving_sweep(demand, _gains(12, asym=3), _gains(1),
                          node_memory=125 * 2**30)
        else:
            sweep_demand(demand, _gains(7, asym=3), node_memory=125 * 2**30,
                         devices=1, engine=entry)
    assert runtime.counts("lab.demand.") == {
        "lab.demand.staged": 2,
        "lab.demand.staged_bytes": 2 * demand.size * 4}


def test_halving_counts_live_and_padded_lanes_per_rung():
    demand = _demand(n=8, t=64)
    hs = halving_sweep(demand, _gains(12), _gains(1), node_memory=125 * 2**30,
                       rounds=(0.125, 0.5, 1.0), keep=0.25, min_survivors=4)
    # 13 lanes alive on 16 for 8 intervals, then 5 on 8 for 24 and 8
    assert [r["lanes"] for r in hs.rounds] == [16, 8, 8]
    assert [r["horizon"] for r in hs.rounds] == [8, 32, 64]
    c = runtime.counts("lab.halving.")
    assert c["lab.halving.lane_steps.live"] == 13 * 8 + 5 * 24 + 5 * 32
    assert c["lab.halving.lane_steps.run"] == 16 * 8 + 8 * 24 + 8 * 32
    assert {k: v.count for k, v in runtime.span_stats().items()} == {
        "lab.halving.stage": 1, "lab.halving.dispatch": 1,
        "lab.halving.drain": 1}


def _plane(n, backend="array", policy=None):
    params = paper_controller_params()
    nodes = []
    for i in range(n):
        reg = StoreRegistry()
        mon = SimulatedMonitor(f"n{i}", total=params.total_memory,
                               usage=np.full(16, 40 * 2**30))
        nodes.append(NodeSpec(f"n{i}", monitor=mon, registry=reg))
    return MemoryPlane(PlaneSpec(params=params, nodes=tuple(nodes),
                                 backend=backend, health=policy))


def test_tick_opens_each_phase_span_once_and_counts_the_fleet():
    plane = _plane(6)
    actions = plane.tick()
    st = runtime.span_stats()
    assert {k: v.count for k, v in st.items()} == {s: 1 for s in TICK_SPANS}
    assert runtime.counts("plane.tick.") == {
        "plane.tick.nodes_sampled": 6, "plane.tick.actions": len(actions)}
    assert len(actions) == 6
    spans = sorted((runtime.span_log(s)[0], s) for s in TICK_SPANS)
    assert [s for _, s in spans] == list(TICK_SPANS)    # in phase order
    for (a, _), (b, _) in zip(spans, spans[1:]):
        assert a.end_ns <= b.start_ns                    # none nested


def test_scalar_tick_times_its_drain_as_the_law_phase():
    plane = _plane(3, backend="scalar")
    plane.tick()
    st = runtime.span_stats()
    assert {k: v.count for k, v in st.items()} == {
        s: 1 for s in TICK_SPANS if s != "plane.tick.actuate"}


def test_tick_deadline_fault_lists_the_phase_times():
    plane = _plane(2, policy=HealthPolicy(tick_deadline_s=1e-9))
    plane.tick()
    (event,) = plane.fault_log.snapshot(kind="tick-deadline")
    for phase in TICK_PHASES:
        assert f"{phase} " in event.detail
    assert event.detail.startswith("interval took ")
