"""Memory monitoring agents (the paper's collectd analogue).

Each agent samples one node's memory state and emits a ``MemorySample``;
``to_json``/``from_json`` mirror the paper's JSON-over-Kafka metric
encoding so samples can travel the :mod:`repro.core.bus` unchanged.

Three agents:

* :class:`HostMemoryMonitor` -- the real thing, reads ``/proc/meminfo``
  (psutil fallback).  On a TPU worker this is the host-RAM view that
  governs the dataset shard cache.
* :class:`DeviceMemoryMonitor` -- per-accelerator HBM view via
  ``device.memory_stats()`` (present on TPU/GPU backends; returns None
  fields on CPU).  Governs the serving KV-block pool.
* :class:`SimulatedMonitor` -- trace- or callback-driven, used by the
  cluster simulator and by every deterministic test.
"""

from __future__ import annotations

import json
import time
import zlib
from dataclasses import dataclass, asdict
from typing import Callable, Iterator, Mapping, Optional, Protocol, Sequence


class MonitorFault(RuntimeError):
    """A monitor failed to produce a sample (dropout / crash / timeout).

    The health layer in :mod:`repro.core.plane` catches this (and any
    other exception from ``sample()``) and degrades to the last-good
    holdover instead of letting one dead sensor take the interval down.
    ``repro.runtime.chaos`` raises it from injected fault proxies.
    """


@dataclass(frozen=True)
class MemorySample:
    """One observation of a node's memory state (bytes)."""

    node: str
    timestamp: float
    used: float           # v_i: total used incl. in-memory storage
    total: float          # M
    storage_used: float = 0.0   # portion attributable to managed stores
    swap_used: float = 0.0

    @property
    def utilization(self) -> float:
        return self.used / self.total if self.total else 0.0

    @property
    def compute_used(self) -> float:
        """Usage attributable to the priority (compute) tenant."""
        return max(self.used - self.storage_used, 0.0)

    def to_json(self) -> str:
        return json.dumps(asdict(self))

    @staticmethod
    def from_json(payload: str) -> "MemorySample":
        return MemorySample(**json.loads(payload))


class MemoryMonitor(Protocol):
    def sample(self) -> MemorySample: ...


def _read_proc_meminfo() -> Optional[dict]:
    try:
        with open("/proc/meminfo") as fh:
            fields = {}
            for line in fh:
                key, _, rest = line.partition(":")
                fields[key.strip()] = int(rest.strip().split()[0]) * 1024
            return fields
    except (OSError, ValueError, IndexError):
        return None


class HostMemoryMonitor:
    """Samples host RAM from /proc/meminfo (psutil fallback)."""

    def __init__(self, node: str = "localhost",
                 storage_used_fn: Optional[Callable[[], float]] = None):
        self.node = node
        self._storage_used_fn = storage_used_fn or (lambda: 0.0)

    def sample(self) -> MemorySample:
        info = _read_proc_meminfo()
        if info is not None:
            total = float(info["MemTotal"])
            avail = float(info.get("MemAvailable", info.get("MemFree", 0)))
            swap = float(info.get("SwapTotal", 0) - info.get("SwapFree", 0))
            used = total - avail
        else:  # pragma: no cover - psutil fallback path
            import psutil
            vm = psutil.virtual_memory()
            total, used = float(vm.total), float(vm.total - vm.available)
            swap = float(psutil.swap_memory().used)
        return MemorySample(
            node=self.node, timestamp=time.time(), used=used, total=total,
            storage_used=float(self._storage_used_fn()), swap_used=swap,
        )


class DeviceMemoryMonitor:
    """Samples one accelerator's HBM via ``device.memory_stats()``.

    The CPU backend reports no memory stats; there ``total`` is the
    configured ``assumed_total`` and ``used`` is 0, so control logic
    stays exercisable.  An accelerator must report ``bytes_limit`` and
    ``bytes_in_use``: construction raises ``ValueError`` if it does not,
    and a later sample without them raises :class:`MonitorFault`.
    """

    _KEYS = ("bytes_limit", "bytes_in_use")

    def __init__(self, device, node: Optional[str] = None,
                 assumed_total: float = 16 * 2**30,
                 storage_used_fn: Optional[Callable[[], float]] = None):
        self.device = device
        self.node = node or f"{device.platform}:{device.id}"
        self.assumed_total = assumed_total
        self._storage_used_fn = storage_used_fn or (lambda: 0.0)
        if device.platform != "cpu":
            missing = self._missing(device.memory_stats())
            if missing:
                raise ValueError(
                    f"{device.platform} device {device.id} reports no "
                    f"{missing} in memory_stats()")

    def _missing(self, stats) -> list:
        return [k for k in self._KEYS if k not in (stats or {})]

    def sample(self) -> MemorySample:
        stats = self.device.memory_stats()
        if self.device.platform == "cpu":
            stats = stats or {}
            total = float(stats.get("bytes_limit", self.assumed_total))
            used = float(stats.get("bytes_in_use", 0.0))
        else:
            missing = self._missing(stats)
            if missing:
                raise MonitorFault(f"{self.node}: memory_stats() lacks "
                                   f"{missing}")
            total = float(stats["bytes_limit"])
            used = float(stats["bytes_in_use"])
        return MemorySample(
            node=self.node, timestamp=time.time(), used=used, total=total,
            storage_used=float(self._storage_used_fn()),
        )


#: Fault modes a SimulatedMonitor can deterministically inject.
SIM_FAULT_KINDS = ("dropout", "freeze", "nan")


class SimulatedMonitor:
    """Trace- or callback-driven monitor for simulation and tests.

    ``faults`` turns on deterministic fault injection: a mapping from
    fault kind (``"dropout"`` raises :class:`MonitorFault`,
    ``"freeze"`` re-delivers the previous sample verbatim, ``"nan"``
    corrupts ``used``) to a per-tick probability.  Whether tick ``i``
    faults -- and which kind fires -- is a pure function of
    ``(fault_seed, node, i)``, so chaos tests replay bit-identically
    with no wall-clock timing involved.
    """

    def __init__(
        self,
        node: str,
        total: float,
        usage: Sequence[float] | Callable[[int], float],
        storage_used_fn: Optional[Callable[[], float]] = None,
        dt: float = 0.1,
        faults: Optional[Mapping[str, float]] = None,
        fault_seed: int = 0,
    ):
        self.node = node
        self.total = float(total)
        self._usage = usage
        self._storage_used_fn = storage_used_fn or (lambda: 0.0)
        self._dt = dt
        self._i = 0
        if faults:
            unknown = set(faults) - set(SIM_FAULT_KINDS)
            if unknown:
                raise ValueError(
                    f"unknown fault kinds {sorted(unknown)}; "
                    f"choose from {SIM_FAULT_KINDS}")
        self._faults = dict(faults or {})
        self._fault_seed = int(fault_seed)
        self._last: Optional[MemorySample] = None

    def _fault_at(self, i: int) -> Optional[str]:
        """Which fault (if any) fires at tick ``i`` -- pure, seeded."""
        if not self._faults:
            return None
        import numpy as np
        rng = np.random.default_rng(
            [self._fault_seed, zlib.crc32(self.node.encode()), i])
        for kind in SIM_FAULT_KINDS:          # fixed order: deterministic
            p = self._faults.get(kind, 0.0)
            if p > 0.0 and rng.random() < p:
                return kind
        return None

    def sample(self) -> MemorySample:
        i = self._i
        self._i += 1
        if callable(self._usage):
            used = float(self._usage(i))
        else:
            used = float(self._usage[min(i, len(self._usage) - 1)])
        s = MemorySample(
            node=self.node, timestamp=i * self._dt,
            used=used + self._storage_used_fn(),
            total=self.total, storage_used=float(self._storage_used_fn()),
            swap_used=max(0.0, used + self._storage_used_fn() - self.total),
        )
        kind = self._fault_at(i)
        if kind == "dropout":
            raise MonitorFault(f"{self.node}: simulated dropout at tick {i}")
        if kind == "freeze" and self._last is not None:
            return self._last                  # stuck sensor: stale repeat
        if kind == "nan":
            s = MemorySample(
                node=s.node, timestamp=s.timestamp, used=float("nan"),
                total=s.total, storage_used=s.storage_used,
                swap_used=s.swap_used)
            return s                           # corrupt: not cached as good
        self._last = s
        return s

    def __iter__(self) -> Iterator[MemorySample]:
        while True:
            yield self.sample()
