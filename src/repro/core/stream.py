"""Streaming metric aggregation (the paper's Flink analogue).

Consumes raw :class:`~repro.core.monitor.MemorySample` messages from the
bus topic ``metrics``, maintains a per-node sliding window, and publishes
an :class:`AggregatedMetrics` record to topic ``metrics.agg`` for the
controller.  The paper's stream job computes "the optimized in-memory
storage space for each node online"; here the aggregation (smoothing,
slope) is separated from the control law so either can be swapped.

Aggregations per node over a window of the last ``window`` samples:
latest / mean / max / EWMA (alpha) / slope (d usage / d interval, by
least-squares over the window) -- the slope feeds the beyond-paper
feedforward term of the control law.

The windows live in packed per-node arrays, so one pass of numpy updates
every node of a batch: the plane publishes a tick's samples with one
``publish_many`` and the aggregator answers with one.  Each pass counts
``stream.agg.batches`` and ``stream.agg.rows`` (``repro.analysis.runtime``).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..analysis.runtime import count
from .bus import MessageBus
from .monitor import MemorySample

RAW_TOPIC = "metrics"
AGG_TOPIC = "metrics.agg"


@dataclass(frozen=True)
class AggregatedMetrics:
    node: str
    timestamp: float
    total: float
    used_latest: float
    used_ewma: float
    used_mean: float
    used_max: float
    slope_per_interval: float     # least-squares d(used)/d(sample)
    storage_used: float
    swap_used: float
    n_samples: int

    @property
    def utilization(self) -> float:
        return self.used_latest / self.total if self.total else 0.0


class MetricAggregator:
    """Per-node sliding-window aggregation; bus-attached or standalone.

    Row ``r`` of the ``(rows, window)`` buffer holds one node's last
    ``n[r]`` samples in time order, right-aligned (the newest in the last
    column).  ``update_many`` updates every row of a batch in one pass;
    the slope is the closed-form degree-1 least-squares fit over
    x = 0..n-1, the fit ``np.polyfit(x, used, 1)`` computes.
    """

    def __init__(self, window: int = 8, ewma_alpha: float = 0.5,
                 bus: Optional[MessageBus] = None):
        if window < 1:
            raise ValueError("window must be >= 1")
        self.window = window
        self.alpha = ewma_alpha
        self._lock = threading.Lock()
        self._row: Dict[str, int] = {}                # guarded-by: _lock
        self._used = np.zeros((0, window))            # guarded-by: _lock
        self._n = np.zeros(0, np.int64)               # guarded-by: _lock
        self._ewma = np.zeros(0)                      # guarded-by: _lock
        self._latest: List[MemorySample] = []         # guarded-by: _lock
        self._bus = bus
        if bus is not None:
            bus.subscribe(RAW_TOPIC, self._on_batch, batch=True)

    def _on_batch(self, msgs: List) -> None:
        """Aggregate a RAW_TOPIC batch; a malformed message is raised to
        the bus (which records it) after the rest are published."""
        samples, bad = [], []
        for m in msgs:
            try:
                samples.append(m if isinstance(m, MemorySample)
                               else MemorySample.from_json(m))
            except (ValueError, TypeError) as exc:
                bad.append(exc)
        aggs = self.update_many(samples)
        if self._bus is not None:
            self._bus.publish_many(AGG_TOPIC, aggs)
        if bad:
            raise bad[0]

    def update(self, sample: MemorySample) -> AggregatedMetrics:
        return self.update_many([sample])[0]

    def update_many(self, samples: Sequence[MemorySample]
                    ) -> List[AggregatedMetrics]:
        """Aggregate ``samples`` in order; one answer per sample.

        A node that appears twice starts a new pass, so the answers are
        those of one-at-a-time updates."""
        out: List[AggregatedMetrics] = []
        start, seen = 0, set()
        with self._lock:
            for i, s in enumerate(samples):
                if s.node in seen:
                    out += self._pass(samples[start:i])
                    start, seen = i, set()
                seen.add(s.node)
            if start < len(samples):
                out += self._pass(samples[start:])
        return out

    def _rows(self, samples: Sequence[MemorySample]  # locklint: holds _lock
              ) -> np.ndarray:
        """Each sample's row, adding rows for nodes not seen before."""
        row = self._row
        for s in samples:
            if s.node not in row:
                row[s.node] = len(self._latest)
                self._latest.append(s)
        grow = len(self._latest) - self._n.size
        if grow > 0:
            grow = max(grow, self._n.size)        # amortised doubling
            self._used = np.concatenate(
                [self._used, np.zeros((grow, self.window))])
            self._n = np.concatenate([self._n, np.zeros(grow, np.int64)])
            self._ewma = np.concatenate([self._ewma, np.zeros(grow)])
        return np.fromiter((row[s.node] for s in samples), np.int64,
                           len(samples))

    def _pass(self, samples: Sequence[MemorySample]  # locklint: holds _lock
              ) -> List[AggregatedMetrics]:
        """One vectorised update of distinct nodes' windows."""
        rows = self._rows(samples)
        k, w = len(samples), self.window
        used = np.fromiter((s.used for s in samples), np.float64, k)
        n_old = self._n[rows]
        prev = np.where(n_old == 0, used, self._ewma[rows])
        ewma = self.alpha * used + (1 - self.alpha) * prev
        win = np.empty((k, w))
        win[:, :-1] = self._used[rows, 1:]
        win[:, -1] = used
        n = np.minimum(n_old + 1, w)
        self._used[rows] = win
        self._n[rows] = n
        self._ewma[rows] = ewma
        for r, s in zip(rows.tolist(), samples):
            self._latest[r] = s
        count("stream.agg.batches")
        count("stream.agg.rows", k)

        # x = 0..n-1 over the valid (right-aligned) columns.
        x = np.arange(w) - (w - n)[:, None]
        valid = x >= 0
        nf = n.astype(np.float64)
        mean = np.where(valid, win, 0.0).sum(axis=1) / nf
        top = np.where(valid, win, -np.inf).max(axis=1)
        dx = np.where(valid, x - (nf[:, None] - 1) / 2, 0.0)
        sxx = np.where(n >= 2, nf * (nf * nf - 1) / 12, 1.0)
        slope = np.where(
            n >= 2, (dx * (win - mean[:, None])).sum(axis=1) / sxx, 0.0)
        return [
            AggregatedMetrics(
                node=s.node, timestamp=s.timestamp, total=s.total,
                used_latest=s.used, used_ewma=e, used_mean=mu, used_max=mx,
                slope_per_interval=sl, storage_used=s.storage_used,
                swap_used=s.swap_used, n_samples=c)
            for s, e, mu, mx, sl, c in zip(
                samples, ewma.tolist(), mean.tolist(), top.tolist(),
                slope.tolist(), n.tolist())]

    def latest(self, node: str) -> Optional[MemorySample]:
        with self._lock:
            r = self._row.get(node)
            return None if r is None else self._latest[r]
