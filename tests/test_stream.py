"""MetricAggregator (core/stream.py): window aggregates, slope, bus wiring."""

import os
import sys
import threading
from collections import deque

import numpy as np
import pytest

from repro.analysis.runtime import counts, reset_counters
from repro.core.bus import MessageBus
from repro.core.monitor import MemorySample, SimulatedMonitor
from repro.core.stream import (AGG_TOPIC, AggregatedMetrics, MetricAggregator,
                               RAW_TOPIC)

GiB = float(2**30)


def sample(used, node="n0", i=0, total=125 * GiB, storage=0.0, swap=0.0):
    return MemorySample(node=node, timestamp=i * 0.1, used=used, total=total,
                        storage_used=storage, swap_used=swap)


def test_single_sample_aggregates():
    agg = MetricAggregator(window=4)
    a = agg.update(sample(10 * GiB))
    assert a.used_latest == a.used_mean == a.used_max == 10 * GiB
    assert a.used_ewma == 10 * GiB          # EWMA seeds at first sample
    assert a.slope_per_interval == 0.0      # no slope from one point
    assert a.n_samples == 1
    assert a.utilization == pytest.approx(10 / 125)


def test_window_mean_max_and_eviction():
    agg = MetricAggregator(window=3)
    for i, used in enumerate([10.0, 20.0, 30.0, 40.0]):
        a = agg.update(sample(used, i=i))
    # window holds the last 3: [20, 30, 40]
    assert a.used_latest == 40.0
    assert a.used_mean == pytest.approx(30.0)
    assert a.used_max == 40.0
    assert a.n_samples == 3


def test_ewma_recursion():
    alpha = 0.25
    agg = MetricAggregator(window=8, ewma_alpha=alpha)
    values = [10.0, 50.0, 30.0]
    expected = values[0]
    for i, used in enumerate(values):
        a = agg.update(sample(used, i=i))
        expected = alpha * used + (1 - alpha) * expected if i else values[0]
    assert a.used_ewma == pytest.approx(expected)


def test_slope_least_squares():
    agg = MetricAggregator(window=8)
    # exact ramp: slope == step
    for i in range(5):
        a = agg.update(sample(100.0 + 7.0 * i, i=i))
    assert a.slope_per_interval == pytest.approx(7.0)
    # flat tail pulls the fitted slope below the ramp's
    for i in range(5, 10):
        a = agg.update(sample(128.0, i=i))
    assert 0.0 <= a.slope_per_interval < 7.0
    # least squares on a noisy-but-linear window stays close
    rng = np.random.default_rng(0)
    agg2 = MetricAggregator(window=8)
    for i in range(8):
        a2 = agg2.update(sample(5.0 * i + float(rng.normal(0, 1e-3)), i=i))
    assert a2.slope_per_interval == pytest.approx(5.0, abs=1e-2)


def test_per_node_isolation():
    agg = MetricAggregator(window=4)
    agg.update(sample(10.0, node="a"))
    b = agg.update(sample(99.0, node="b"))
    a = agg.update(sample(20.0, node="a", i=1))
    assert a.used_mean == pytest.approx(15.0)
    assert b.used_mean == pytest.approx(99.0)
    assert agg.latest("a").used == 20.0
    assert agg.latest("b").used == 99.0
    assert agg.latest("missing") is None


def test_bus_raw_to_agg_pipeline():
    bus = MessageBus()
    MetricAggregator(window=4, bus=bus)
    got = []
    bus.subscribe(AGG_TOPIC, got.append)
    mon = SimulatedMonitor("n0", total=125 * GiB,
                           usage=[10 * GiB, 20 * GiB])
    bus.publish(RAW_TOPIC, mon.sample())
    bus.publish(RAW_TOPIC, mon.sample())
    assert len(got) == 2
    assert isinstance(got[-1], AggregatedMetrics)
    assert got[-1].node == "n0"
    assert got[-1].used_latest == 20 * GiB
    assert got[-1].used_max == 20 * GiB
    assert got[-1].n_samples == 2


def test_window_validation():
    with pytest.raises(ValueError):
        MetricAggregator(window=0)


class _ReferenceAggregator:
    """The one-sample-at-a-time update: a deque per node, np.polyfit."""

    def __init__(self, window, alpha):
        self.window, self.alpha = window, alpha
        self.q, self.ewma = {}, {}

    def update(self, s):
        q = self.q.setdefault(s.node, deque(maxlen=self.window))
        q.append(s.used)
        prev = self.ewma.get(s.node, s.used)
        self.ewma[s.node] = self.alpha * s.used + (1 - self.alpha) * prev
        used = np.array(q, dtype=np.float64)
        slope = (float(np.polyfit(np.arange(len(used), dtype=np.float64),
                                  used, 1)[0]) if len(used) >= 2 else 0.0)
        return dict(used_latest=s.used, used_ewma=self.ewma[s.node],
                    used_mean=float(used.mean()), used_max=float(used.max()),
                    slope_per_interval=slope, n_samples=len(used),
                    scale=float(np.abs(used).max()))


def _batches(rng):
    """Ticks over six nodes: some skipped (ragged histories), one batch
    repeating a node, a flat node and an exact ramp."""
    t = 0
    for tick in range(14):
        batch = []
        for j in range(6):
            node = f"n{j}"
            if j == 0:
                used = 37.5 * GiB                       # flat window
            elif j == 1:
                used = 10 * GiB + 0.75 * GiB * tick     # exact ramp
            elif rng.random() < 0.3:
                continue                                 # skipped this tick
            else:
                used = float(rng.uniform(5, 120)) * GiB
            batch.append(sample(used, node=node, i=t))
            t += 1
        if tick % 5 == 2:
            batch.append(sample(float(rng.uniform(5, 120)) * GiB,
                                node="n3", i=t))         # repeats n3
            batch.append(sample(float(rng.uniform(5, 120)) * GiB,
                                node="n4", i=t + 1))
            t += 2
        yield batch


@pytest.mark.parametrize("window", [1, 3, 8])
def test_update_many_matches_one_at_a_time_polyfit(window):
    rng = np.random.default_rng(window)
    agg = MetricAggregator(window=window, ewma_alpha=0.3)
    ref = _ReferenceAggregator(window, 0.3)
    n_checked = 0
    for batch in _batches(rng):
        got = agg.update_many(batch)
        assert [a.node for a in got] == [s.node for s in batch]
        for s, a in zip(batch, got):
            want = ref.update(s)
            for field in ("used_latest", "used_max", "used_ewma",
                          "n_samples"):
                assert getattr(a, field) == want[field], field
            tol = 1e-9 * want["scale"]
            assert a.used_mean == pytest.approx(want["used_mean"],
                                                rel=1e-9, abs=tol)
            assert a.slope_per_interval == pytest.approx(
                want["slope_per_interval"], rel=1e-9, abs=tol)
            assert (a.timestamp, a.total) == (s.timestamp, s.total)
            n_checked += 1
        last = {s.node: s for s in batch}
        for node, s in last.items():
            assert agg.latest(node) is s
    assert n_checked > 60
    if window >= 2:
        ramp = agg.update_many([sample(10 * GiB + 0.75 * GiB * 14,
                                       node="n1", i=999)])[0]
        assert ramp.slope_per_interval == pytest.approx(0.75 * GiB,
                                                        rel=1e-12)
        flat = agg.update_many([sample(37.5 * GiB, node="n0", i=999)])[0]
        assert flat.slope_per_interval == 0.0


def test_update_is_update_many_of_one():
    a, b = MetricAggregator(window=4), MetricAggregator(window=4)
    for i, used in enumerate([3.0, 9.0, 4.0, 12.0, 7.0]):
        one = a.update(sample(used, i=i))
        (many,) = b.update_many([sample(used, i=i)])
        assert one == many


def test_bus_batch_makes_one_pass_and_one_agg_publish():
    reset_counters()
    bus = MessageBus()
    MetricAggregator(window=4, bus=bus)
    per_msg, batches = [], []
    bus.subscribe(AGG_TOPIC, per_msg.append)
    bus.subscribe(AGG_TOPIC, batches.append, batch=True)
    raw = [sample(float(10 + j), node=f"n{j}") for j in range(5)]
    bus.publish_many(RAW_TOPIC, raw)
    assert [a.node for a in per_msg] == [s.node for s in raw]
    assert len(batches) == 1 and batches[0] == per_msg
    assert counts("stream.agg.") == {"stream.agg.batches": 1,
                                     "stream.agg.rows": 5}
    assert bus.errors == []


def test_bus_batch_with_a_malformed_message_keeps_the_rest():
    bus = MessageBus()
    MetricAggregator(window=4, bus=bus)
    got = []
    bus.subscribe(AGG_TOPIC, got.append)
    good = [sample(10.0, node="a"), sample(20.0, node="b")]
    bus.publish_many(RAW_TOPIC, [good[0], "{not json", good[1].to_json()])
    assert [a.node for a in got] == ["a", "b"]
    assert len(bus.errors) == 1 and bus.errors[0][0] == RAW_TOPIC


def test_concurrent_update_many_loses_no_row_or_sample():
    """Threads that add nodes and update a shared one at once: every
    node keeps its own row, its last sample and its window count."""
    agg = MetricAggregator(window=4)
    n_threads, rounds, per = max(8, (os.cpu_count() or 1) + 1), 200, 4
    errors = []

    def work(t):
        try:
            for r in range(rounds):
                agg.update_many(
                    [sample(float(t * 1000 + r), node=f"t{t}-{r}-{j}", i=r)
                     for j in range(per)]
                    + [sample(float(t), node=f"t{t}", i=r),
                       sample(1.0, node="shared", i=r)])
        except Exception as exc:  # surfaced by the assertion below
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert errors == []
    for t in range(n_threads):
        for r in range(rounds):
            for j in range(per):
                node = f"t{t}-{r}-{j}"
                assert agg.latest(node).node == node
                a = agg.update(sample(0.0, node=node, i=rounds))
                assert (a.n_samples, a.used_max) == (2, float(t * 1000 + r))
        a = agg.update(sample(float(t), node=f"t{t}", i=rounds))
        assert (a.n_samples, a.used_mean) == (4, float(t))
    assert agg.update(sample(1.0, node="shared")).n_samples == 4
