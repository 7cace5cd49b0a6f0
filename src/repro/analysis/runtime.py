"""Runtime instrumentation: spans, counters, and the PlaneCheck sanitizers.

Two always-on instruments say where a host path spends its time and
how much work it decided to do:

* **Spans** -- :func:`span` opens a ``jax.profiler.TraceAnnotation``,
  so under a profiler session the span lands in the same trace as the
  device ops, on the same clock.  It also keeps, per name, a count, a
  total, a max and the last duration (:func:`span_stats`) and a
  bounded log of the latest intervals (:func:`span_log`), for
  operators who run no profiler.  Spans are opened once per call,
  phase or chunk, never per node, interval or lane.
* **Counters** -- :func:`count` adds to a plain named counter
  (:func:`counts`).  :func:`reset_counters` clears spans and counters.

Names are dotted by layer: ``lab.sweep.stage``, ``plane.tick.sample``,
``lab.halving.lane_steps.live``.  ``docs/OPERATIONS.md`` lists them.

The static passes prove properties of the source; the sanitizers
check the two invariants that only manifest at run time:

* **Recompile counters** -- :func:`record_trace` is called *inside*
  jitted function bodies, so it executes exactly once per trace (Python
  in a traced body runs at trace time only).  A hot path that silently
  retraces -- a non-hashable static arg, a shape drifting per call --
  shows up as a count > 1 for the same key, with no dependence on any
  version-fragile jit-cache introspection API.
  ``benchmarks/lab_bench.py --smoke`` and the pytest sanitizer hooks
  assert one executable per counter key from these counts, so every
  call site must key on the *full* specialization its executable cache
  uses (shapes plus static args/devices), not a projection of it.

* **Transfer guard** -- :func:`dispatch_guard` wraps the sweep's chunk
  dispatch loop in ``jax.transfer_guard_host_to_device("disallow")``
  when sanitizers are enabled, so an accidental per-chunk host->device
  transfer (the regression class PR 3 hand-audited) raises instead of
  silently serializing every dispatch.

Both are no-ops unless ``PLANECHECK_SANITIZERS`` is set to a truthy
value (``1``/``true``/``on``), so production and benchmark hot paths
pay nothing.  This module must stay importable without jax -- jax is
imported lazily, inside :func:`span` and :func:`dispatch_guard` only.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque
from typing import Deque, Dict, Iterator, List, NamedTuple, Optional, Tuple

_ENV_VAR = "PLANECHECK_SANITIZERS"

_counts_lock = threading.Lock()
_counts: Dict[Tuple[str, Tuple[Tuple[str, object], ...]], int] = {}


#: Intervals kept per span name by :func:`span_log`.
SPAN_LOG = 1024


class SpanStats(NamedTuple):
    """Totals of every closed span of one name, in seconds."""

    count: int
    total_s: float
    max_s: float
    last_s: float


class SpanRecord(NamedTuple):
    """One closed span on the ``time.perf_counter_ns`` clock."""

    start_ns: int
    end_ns: int
    thread: int           # ``threading.get_ident()`` of the opener


_spans_lock = threading.Lock()
_span_totals: Dict[str, List[int]] = {}   # name -> [n, total, max, last] ns
_span_logs: Dict[str, Deque[SpanRecord]] = {}
_counters: Dict[str, int] = {}
_annotation = None                         # jax.profiler.TraceAnnotation


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Time the block as ``name``, in a profiler trace and in memory."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    with _annotation(name):
        start = time.perf_counter_ns()
        try:
            yield
        finally:
            _record(name, start, time.perf_counter_ns())


def _record(name: str, start: int, end: int) -> None:
    ns = end - start
    with _spans_lock:
        tot = _span_totals.get(name)
        if tot is None:
            tot = _span_totals[name] = [0, 0, 0, 0]
            _span_logs[name] = deque(maxlen=SPAN_LOG)
        tot[0] += 1
        tot[1] += ns
        tot[2] = max(tot[2], ns)
        tot[3] = ns
        _span_logs[name].append(
            SpanRecord(start, end, threading.get_ident()))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _spans_lock:
        _counters[name] = _counters.get(name, 0) + int(n)


def span_stats() -> Dict[str, SpanStats]:
    """Count, total, max and last duration of every span name."""
    with _spans_lock:
        items = [(k, tuple(v)) for k, v in _span_totals.items()]
    return {k: SpanStats(n, tot * 1e-9, mx * 1e-9, last * 1e-9)
            for k, (n, tot, mx, last) in items}


def span_log(name: str) -> List[SpanRecord]:
    """The latest :data:`SPAN_LOG` closed spans called ``name``."""
    with _spans_lock:
        return list(_span_logs.get(name, ()))


def counts(prefix: str = "") -> Dict[str, int]:
    """Snapshot of every counter whose name starts with ``prefix``."""
    with _spans_lock:
        return {k: v for k, v in _counters.items() if k.startswith(prefix)}


def reset_counters() -> None:
    """Clear every span total, span log and counter."""
    with _spans_lock:
        _span_totals.clear()
        _span_logs.clear()
        _counters.clear()


def sanitizers_enabled() -> bool:
    """Are the runtime sanitizers switched on (``PLANECHECK_SANITIZERS``)?"""
    return os.environ.get(_ENV_VAR, "").strip().lower() in (
        "1", "true", "yes", "on")


def record_trace(name: str, **dims) -> None:
    """Count one tracing of the call site keyed by ``(name, dims)``.

    Call from inside a jitted/scanned function body with *concrete*
    dims (shapes, flags -- never traced values); each retrace of the
    surrounding program increments the key once.  A no-op with
    sanitizers off, so a long-lived production process never grows the
    count dict (``plane.fused_step`` records one key per fleet size).
    The flag is read at *trace* time: enable it before the first
    dispatch (as the CI env, the pytest fixture, and ``lab_bench
    --smoke`` all do), because an executable compiled while it was off
    sits in the jit cache and is never re-traced, hence never counted.
    """
    if not sanitizers_enabled():
        return
    key = (name, tuple(sorted(dims.items())))
    with _counts_lock:
        _counts[key] = _counts.get(key, 0) + 1


def trace_counts(prefix: Optional[str] = None) -> Dict[str, int]:
    """Snapshot of recompile counts, formatted ``name{k=v,...}`` -> n."""
    with _counts_lock:
        items = list(_counts.items())
    out = {}
    for (name, dims), n in items:
        if prefix is not None and not name.startswith(prefix):
            continue
        label = name
        if dims:
            label += "{" + ",".join(f"{k}={v}" for k, v in dims) + "}"
        out[label] = n
    return out


def reset_trace_counts() -> None:
    with _counts_lock:
        _counts.clear()


def excess_traces(prefix: str) -> Dict[str, int]:
    """Keys under ``prefix`` traced more than once (retrace suspects)."""
    return {k: n for k, n in trace_counts(prefix).items() if n > 1}


@contextlib.contextmanager
def dispatch_guard():
    """Disallow implicit transfers around a dispatch loop (when enabled).

    With sanitizers off this is a free no-op; with them on, any
    implicit host<->device transfer inside the block raises.  Callers
    must stage every operand device-side (and warm the executable)
    before entering.
    """
    if not sanitizers_enabled():
        yield
        return
    import jax
    # Host->device only: the sharded sweep legitimately reshards
    # replicated operands across the mesh (device-to-device) at
    # dispatch, and results come back device-to-host.  The regression
    # class this guards against is per-chunk host staging.
    with jax.transfer_guard_host_to_device("disallow"):
        yield
