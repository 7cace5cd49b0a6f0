"""Monitoring/control plane: bus, aggregator, MemoryPlane end-to-end,
scalar vs array backend parity, lifecycle, and the legacy shim."""

import time

import numpy as np
import pytest

from repro.analysis.runtime import counts, reset_counters
from repro.core import (AGG_TOPIC, RAW_TOPIC, ControlPlane, ControllerParams,
                        GiB, MemoryPlane, MemorySample, MessageBus,
                        MetricAggregator, NodeSpec, PlaneSpec, ShardCache,
                        Signal, SimulatedMonitor, StoreRegistry, StoreSpec)
from repro.core.cluster_sim import paper_controller_params


class Blob:
    def __init__(self, nbytes):
        self.nbytes = nbytes


def test_bus_pubsub_and_poll():
    bus = MessageBus()
    seen = []
    unsub = bus.subscribe("t", seen.append)
    bus.publish("t", 1)
    bus.publish("t", 2)
    assert seen == [1, 2]
    assert bus.poll("t", group="g1") == [1, 2]
    assert bus.poll("t", group="g1") == []
    bus.publish("t", 3)
    assert bus.poll("t", group="g1") == [3]
    unsub()
    bus.publish("t", 4)
    assert seen == [1, 2, 3] or seen == [1, 2]  # unsubscribed


def test_bus_isolates_subscriber_exceptions():
    bus = MessageBus()
    bus.subscribe("t", lambda m: 1 / 0)
    bus.publish("t", "x")              # must not raise
    assert len(bus.errors) == 1


def test_bus_publish_many_matches_single_publishes():
    """Log, offsets, retention, depth and poll cursors end as N single
    publishes would leave them."""
    one, many = MessageBus(retention=5), MessageBus(retention=5)
    assert one.poll("t", group="g") == many.poll("t", group="g") == []
    for m in range(3):
        one.publish("t", m)
    many.publish_many("t", range(3))
    assert one.poll("t", group="g", max_items=2) == \
        many.poll("t", group="g", max_items=2) == [0, 1]
    for m in range(3, 9):
        one.publish("t", m)
    many.publish_many("t", list(range(3, 9)))
    many.publish_many("t", [])
    assert one.depth("t") == many.depth("t") == 5
    assert one.poll("t", group="g") == many.poll("t", group="g") == \
        [4, 5, 6, 7, 8]                         # retention dropped 2, 3
    assert one.poll("t", group="h") == many.poll("t", group="h")
    one.publish("t", 9)
    many.publish_many("t", [9])
    assert one.poll("t", group="g") == many.poll("t", group="g") == [9]


def test_bus_publish_many_delivers_per_message_and_batch():
    bus = MessageBus()
    seen, batches = [], []
    bus.subscribe("t", seen.append)
    unsub = bus.subscribe("t", batches.append, batch=True)
    bus.publish_many("t", ["a", "b", "c"])
    bus.publish("t", "d")
    assert seen == ["a", "b", "c", "d"]
    assert batches == [["a", "b", "c"], ["d"]]
    unsub()
    bus.publish_many("t", ["e"])
    assert seen[-1] == "e" and len(batches) == 2


def test_bus_publish_many_isolates_subscriber_exceptions():
    bus = MessageBus()
    seen = []
    bus.subscribe("t", lambda m: 1 / m)
    bus.subscribe("t", lambda ms: [][len(ms)], batch=True)
    bus.subscribe("t", seen.append)
    bus.publish_many("t", [1, 0, 2, 0])             # must not raise
    assert seen == [1, 0, 2, 0]
    kinds = sorted(type(e).__name__ for _, e in bus.errors)
    assert kinds == ["IndexError", "ZeroDivisionError", "ZeroDivisionError"]
    assert all(t == "t" for t, _ in bus.errors)


def test_sample_json_roundtrip():
    s = MemorySample(node="n0", timestamp=1.5, used=10.0, total=100.0,
                     storage_used=4.0)
    assert MemorySample.from_json(s.to_json()) == s


def test_aggregator_window_and_slope():
    agg = MetricAggregator(window=4)
    out = None
    for i, used in enumerate([10, 20, 30, 40]):
        out = agg.update(MemorySample("n", float(i), used, 100.0))
    assert out.used_latest == 40
    assert out.used_mean == 25
    assert out.used_max == 40
    assert abs(out.slope_per_interval - 10.0) < 1e-9


def test_control_plane_closed_loop_burst():
    """Full pipeline: burst -> cache shrinks within intervals; burst
    clears -> cache regrows (paper Fig. 7 behaviour)."""
    p = paper_controller_params()
    plane = ControlPlane(p)
    cache = ShardCache(capacity=60 * GiB, sizeof=lambda v: v.nbytes)
    for i in range(60):
        cache.put(i, Blob(1 * GiB))
    reg = StoreRegistry()
    reg.register(cache, max_bytes=60 * GiB)

    usage = ([20 * GiB] * 10) + ([95 * GiB] * 20) + ([20 * GiB] * 40)
    mon = SimulatedMonitor("n0", total=125 * GiB, usage=usage,
                           storage_used_fn=cache.used)
    plane.attach("n0", mon, reg, u0=60 * GiB)

    caps = []
    for _ in range(len(usage)):
        plane.tick()
        caps.append(cache.capacity() / GiB)
    # burst (compute 95 GiB): u* = 0.95*125 - 95 = 23.75 GiB
    assert min(caps[10:30]) < 30
    # recovery: back to u_max
    assert caps[-1] > 55
    # actual evictions happened and usage tracked capacity
    assert cache.used() <= cache.capacity()
    assert cache.stats.evictions >= 25


def test_control_actions_published():
    p = paper_controller_params()
    plane = ControlPlane(p)
    cache = ShardCache(capacity=0, sizeof=lambda v: 1.0)
    reg = StoreRegistry()
    reg.register(cache, max_bytes=60 * GiB)
    mon = SimulatedMonitor("n0", total=125 * GiB, usage=[50 * GiB] * 5)
    plane.attach("n0", mon, reg)
    for _ in range(5):
        plane.tick()
    from repro.core import CONTROL_TOPIC
    actions = plane.bus.poll(CONTROL_TOPIC, group="test")
    assert len(actions) == 5
    assert all(a.node == "n0" for a in actions)


# ---------------------------------------------------------------------------
# MemoryPlane: declarative API, backends, lifecycle
# ---------------------------------------------------------------------------

def test_signal_enum_coercion():
    assert Signal.coerce("latest") is Signal.LATEST
    assert Signal.coerce(Signal.EWMA) is Signal.EWMA
    with pytest.raises(ValueError):
        Signal.coerce("p99")
    with pytest.raises(ValueError):
        PlaneSpec(params=paper_controller_params(), signal="bogus")


def test_plane_spec_rejects_unknown_backend():
    with pytest.raises(ValueError):
        PlaneSpec(params=paper_controller_params(), backend="quantum")


def test_memory_plane_array_backend_closed_loop():
    """The fused array backend drives a real cache through the paper's
    burst/shrink/recover scenario, same as the scalar reference."""
    cache = ShardCache(capacity=60 * GiB, sizeof=lambda v: v.nbytes)
    for i in range(60):
        cache.put(i, Blob(1 * GiB))
    usage = ([20 * GiB] * 10) + ([95 * GiB] * 20) + ([20 * GiB] * 40)
    plane = MemoryPlane(PlaneSpec(
        params=paper_controller_params(),
        backend="array",
        nodes=(NodeSpec(
            "n0",
            monitor=SimulatedMonitor("n0", total=125 * GiB, usage=usage,
                                     storage_used_fn=cache.used),
            stores=(StoreSpec(cache, max_bytes=60 * GiB),),
            u0=60 * GiB),),
    ))
    caps = []
    for _ in range(len(usage)):
        actions = plane.tick()
        assert len(actions) == 1
        caps.append(cache.capacity() / GiB)
    assert min(caps[10:30]) < 30          # shrunk during the burst
    assert caps[-1] > 55                  # recovered to u_max
    assert cache.used() <= cache.capacity()
    assert cache.stats.evictions >= 25
    assert plane.capacity("n0") == pytest.approx(caps[-1] * GiB, rel=1e-6)


def _heterogeneous_fleet(backend, base, M, u_min, u_max, u0, demand):
    """One plane with per-node capacity overrides and trace monitors."""
    n = len(M)
    nodes = tuple(
        NodeSpec(
            f"n{i}",
            monitor=SimulatedMonitor(f"n{i}", total=M[i], usage=demand[i]),
            registry=StoreRegistry(),
            u0=u0[i],
            params=base.replace(total_memory=M[i], u_min=u_min[i],
                                u_max=u_max[i]))
        for i in range(n))
    return MemoryPlane(PlaneSpec(params=base, backend=backend, nodes=nodes))


@pytest.mark.parametrize("variant", ["paper", "extended"])
def test_array_scalar_parity_256_heterogeneous_nodes(variant):
    """Acceptance: ArrayController matches the scalar reference within
    1e-4 relative tolerance across a mixed fleet (mixed M, u_min/u_max,
    feedforward/deadband both off and on)."""
    rng = np.random.default_rng(42)
    n, t = 256, 30
    M = rng.uniform(64, 256, n) * GiB
    u_max = rng.uniform(20, 60, n) * GiB
    u_min = rng.uniform(0, 5, n) * GiB
    u0 = rng.uniform(u_min, u_max)
    base = ControllerParams(total_memory=125 * GiB)
    if variant == "paper":
        demand = rng.uniform(0.5, 1.05, (n, t)) * M[:, None]
    else:
        base = base.replace(feedforward=0.5, deadband=0.015, lam_grant=0.25)
        # piecewise-constant demand on a coarse utilization grid keeps
        # float32-vs-float64 rounding away from the deadband boundary
        offsets = np.array([-0.25, -0.10, -0.04, 0.02, 0.06, 0.12])
        levels = rng.choice(offsets, size=(n, t // 5 + 1))
        demand = (base.r0 + np.repeat(levels, 5, axis=1)[:, :t]) * M[:, None]
    planes = {b: _heterogeneous_fleet(b, base, M, u_min, u_max, u0, demand)
              for b in ("scalar", "array")}
    for _ in range(t):
        for plane in planes.values():
            plane.tick()
    ref = np.array([planes["scalar"].capacity(f"n{i}") for i in range(n)])
    got = np.array([planes["array"].capacity(f"n{i}") for i in range(n)])
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e4)


@pytest.mark.parametrize("backend", ["array", "scalar"])
def test_tick_aggregates_the_fleet_in_one_pass(backend):
    """One tick of an N-node plane is one aggregator pass of N rows, and
    the controller still sees every node's aggregate, in node order."""
    rng = np.random.default_rng(7)
    n, t = 48, 4
    M = np.full(n, 125 * GiB)
    base = ControllerParams(total_memory=125 * GiB)
    demand = rng.uniform(0.3, 0.9, (n, t)) * M[:, None]
    plane = _heterogeneous_fleet(backend, base, M, np.zeros(n),
                                 np.full(n, 60 * GiB), np.full(n, 30 * GiB),
                                 demand)
    plane.tick()
    reset_counters()
    actions = plane.tick()
    assert counts("stream.agg.") == {"stream.agg.batches": 1,
                                     "stream.agg.rows": n}
    assert [a.node for a in actions] == [f"n{i}" for i in range(n)]
    aggs = plane.bus.poll(AGG_TOPIC, group="audit", max_items=4 * n)
    assert [a.node for a in aggs] == [f"n{i}" for i in range(n)] * 2
    assert all(a.n_samples == 2 for a in aggs[n:])


def test_memory_plane_lifecycle_restart():
    """attach -> tick -> start -> stop -> re-start: the plane is
    restartable and keeps collecting actions."""
    params = paper_controller_params(interval_s=0.01)
    plane = MemoryPlane(PlaneSpec(params=params, backend="array"))
    plane.attach("n0",
                 SimulatedMonitor("n0", total=125 * GiB,
                                  usage=lambda i: 80 * GiB),
                 registry=StoreRegistry(), u0=30 * GiB)
    assert plane.nodes() == ["n0"]
    assert len(plane.tick()) == 1
    assert not plane.running

    plane.start()
    assert plane.running
    time.sleep(0.15)
    plane.stop()
    assert not plane.running
    n1 = len(plane.actions())
    assert n1 > 1

    plane.start()                      # restart after stop
    time.sleep(0.15)
    plane.stop()
    assert len(plane.actions()) > n1

    with plane:                        # context-manager lifecycle
        assert plane.running
        time.sleep(0.05)
    assert not plane.running


def test_action_history_is_bounded():
    plane = MemoryPlane(PlaneSpec(
        params=paper_controller_params(), backend="array", history=8,
        nodes=(NodeSpec("n0",
                        monitor=SimulatedMonitor(
                            "n0", total=125 * GiB,
                            usage=lambda i: 100 * GiB),
                        registry=StoreRegistry(), u0=30 * GiB),)))
    for _ in range(40):
        plane.tick()
    assert len(plane.actions()) == 8
    assert len(plane.actions(limit=3)) == 3
    # scalar backend honors the same bound
    shim = ControlPlane(paper_controller_params(), max_history=8)
    shim.attach("n0", SimulatedMonitor("n0", total=125 * GiB,
                                       usage=lambda i: 100 * GiB),
                StoreRegistry(), u0=30 * GiB)
    for _ in range(40):
        shim.tick()
    assert len(shim.controller.actions) == 8


def test_squeeze_clamps_without_moving_control_state():
    cache = ShardCache(capacity=40 * GiB, sizeof=lambda v: v.nbytes)
    for i in range(40):
        cache.put(i, Blob(1 * GiB))
    plane = MemoryPlane(PlaneSpec(
        params=paper_controller_params(), backend="array",
        nodes=(NodeSpec("n0",
                        monitor=SimulatedMonitor(
                            "n0", total=125 * GiB,
                            usage=lambda i: 40 * GiB,
                            storage_used_fn=cache.used),
                        stores=(StoreSpec(cache, 60 * GiB),),
                        u0=40 * GiB),)))
    assert plane.squeeze("n0", 0.25)
    assert cache.capacity() == pytest.approx(10 * GiB)
    assert plane.capacity("n0") == pytest.approx(40 * GiB)   # u untouched
    plane.tick()                       # law re-grants from slack
    assert cache.capacity() > 10 * GiB
    assert not plane.squeeze("ghost", 0.5)


def test_per_node_gain_override_rejected_on_array_backend():
    from repro.core import ArrayController
    base = paper_controller_params()
    ac = ArrayController(base)
    with pytest.raises(ValueError):
        ac.attach_node("n0", StoreRegistry(), u0=0.0,
                       params=base.replace(lam=1.5))
    ac.attach_node("n1", StoreRegistry(), u0=0.0,
                   params=base.replace(u_max=10 * GiB))   # capacities ok


def test_control_plane_shim_is_deprecated_memory_plane():
    with pytest.warns(DeprecationWarning):
        shim = ControlPlane(paper_controller_params())
    assert isinstance(shim, MemoryPlane)
    from repro.core.controller import ControlPlane as legacy_path
    assert legacy_path is ControlPlane


def test_scalar_tick_returns_full_fleet_despite_small_history():
    """tick() must return every node's action even when the retained
    history bound is smaller than the fleet (both backends)."""
    for backend in ("scalar", "array"):
        plane = MemoryPlane(PlaneSpec(
            params=paper_controller_params(), backend=backend, history=4,
            nodes=tuple(
                NodeSpec(f"n{i}",
                         monitor=SimulatedMonitor(
                             f"n{i}", total=125 * GiB,
                             usage=lambda t: 90 * GiB),
                         registry=StoreRegistry(), u0=30 * GiB)
                for i in range(12))))
        actions = plane.tick()
        assert len(actions) == 12, backend
        assert len(plane.actions()) == 4          # retained log stays bounded


def test_attach_rejects_registry_and_stores_together():
    plane = MemoryPlane(PlaneSpec(params=paper_controller_params()))
    cache = ShardCache(capacity=1 * GiB)
    with pytest.raises(ValueError):
        plane.attach("n0",
                     SimulatedMonitor("n0", total=125 * GiB,
                                      usage=lambda i: 50 * GiB),
                     registry=StoreRegistry(),
                     stores=(StoreSpec(cache, 1 * GiB),))


def test_idle_engine_still_ticks_plane():
    """A fully-idle (e.g. fully-preempted) serving engine must keep
    ticking its plane or a reclaimed pool can never be re-granted."""
    import repro.serving.engine as E

    class _Plane:
        ticks = 0
        def attach(self, *a, **k):
            return StoreRegistry()
        def tick(self):
            self.ticks += 1
            return []

    eng = E.ServingEngine.__new__(E.ServingEngine)
    eng.steps = 0
    eng.plane = _Plane()
    eng.queue = []
    eng.finished = {}
    eng.slots = [E._Slot()]
    eng.pool = type("P", (), {"drain_preempted": staticmethod(lambda: []),
                              "num_free_blocks": staticmethod(lambda: 0)})()
    eng.cfg = E.ServingConfig(max_batch=1)
    eng.step()
    assert eng.plane.ticks == 1
