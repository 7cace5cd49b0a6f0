"""Reduction of a profiler trace (``.xplane.pb``) to device time.

The layout it reads (jax 0.9 TPU traces): each chip is a plane named
``/device:TPU:<i>``; its line ``XLA Ops`` holds one event per executed
operation (named by its HLO text), and ``XLA Modules`` one per
executable run, read only where a device has no ``XLA Ops`` line.
Executables are not matched by name: a jitted ``functools.partial``
compiles as ``jit__unknown``.  The host is ``/host:CPU``; the
benchmark's ``jax.profiler.TraceAnnotation`` spans sit on the line of the
thread that opened them, on the same clock as the device events.

From that the reduction takes, inside the traced window (the host span
``window``):

* busy time per device: the union of its operation intervals;
* device time per operation name, over all devices;
* idle gaps per device, each named by the innermost benchmark span the
  host was in at the gap's midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"


@dataclasses.dataclass
class Summary:
    """Everything the per-layer readers take from one trace."""

    window: Tuple[int, int]                          # ns
    busy_ns: Dict[str, int]                          # device -> busy
    ops_ns: Dict[str, int]                           # op name -> time
    ops_meta: Dict[str, str]                         # op name -> its stats
    gaps: List[Tuple[str, int]]                      # (host span, ns)
    spans: List[Tuple[str, int, int]]                # host spans in window
    op_intervals: Dict[str, List[Tuple[int, int]]]   # device -> merged

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, devices: Optional[Iterable[str]] = None) -> float:
        """Busy seconds averaged over ``devices`` (default: all traced)."""
        devs = list(devices) if devices is not None else list(self.busy_ns)
        if not devs:
            return 0.0
        return sum(self.busy_ns.get(d, 0) for d in devs) / len(devs) * 1e-9

    def busy_in(self, start: int, end: int,
                devices: Optional[Iterable[str]] = None) -> float:
        """Busy seconds inside ``[start, end)``, averaged over ``devices``
        (default: the first traced device)."""
        devs = (list(devices) if devices is not None
                else sorted(self.op_intervals)[:1])
        if not devs:
            return 0.0
        total = 0
        for dev in devs:
            for s, e in self.op_intervals.get(dev, ()):
                total += max(0, min(e, end) - max(s, start))
        return total / len(devs) * 1e-9

    def span_busy_s(self, name: str,
                    devices: Optional[Iterable[str]] = None) -> float:
        """Busy seconds inside every host span called ``name``, averaged
        over ``devices``: the device time of the calls that span wraps,
        where the call waits for its device work before it returns."""
        return sum(self.busy_in(s, e, devices)
                   for n, s, e in self.spans if n == name)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of ``[start, end)`` intervals, sorted and disjoint."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def summarize(path: str, spans_of_interest: Iterable[str]) -> Summary:
    """Read ``path`` and reduce it (see the module docstring)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    wanted = set(spans_of_interest) | {WINDOW_SPAN}
    spans: List[Tuple[str, int, int]] = []
    devices: Dict[str, Dict[str, list]] = {}
    meta: Dict[str, str] = {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
        elif plane.name.startswith(DEVICE_PREFIX):
            lines = {}
            for ln in plane.lines:
                if ln.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = []
                for ev in ln.events:
                    if ln.name == OPS_LINE and ev.name not in meta:
                        meta[ev.name] = " ".join(
                            str(v) for _, v in ev.stats
                            if isinstance(v, str))
                    evs.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns) + int(ev.duration_ns)))
                lines[ln.name] = evs
            if lines.get(OPS_LINE) or lines.get(MODULES_LINE):
                devices[plane.name] = lines
    wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no '{WINDOW_SPAN}' span in {path}")
    lo, hi = wins[0]
    spans = [x for x in spans if x[0] != WINDOW_SPAN and x[2] > lo
             and x[1] < hi]
    busy, ops, gaps, merged_by = {}, {}, [], {}
    for dev, lines in devices.items():
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        for name, s, e in op_events:
            if e > lo and s < hi:
                ops[name] = ops.get(name, 0) + min(e, hi) - max(s, lo)
        merged = merge(clip([(s, e) for _, s, e in op_events], lo, hi))
        merged_by[dev] = merged
        busy[dev] = sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((_span_at(spans, (gs + ge) // 2), ge - gs))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window=(lo, hi), busy_ns=busy, ops_ns=ops,
                   ops_meta=meta, gaps=gaps, spans=spans,
                   op_intervals=merged_by)


def _span_at(spans: List[Tuple[str, int, int]], t: int) -> str:
    """The innermost (shortest) span covering ``t``, or ``host``."""
    best: Optional[Tuple[str, int, int]] = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "host"


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line."""
    ops = sorted(summary.ops_ns.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in summary.gaps[:top]]}
