"""The program's own spans and counters, as the per-layer readers take them.

``repro.analysis.runtime`` keeps the latest intervals of every span on
the ``time.perf_counter_ns`` clock, the clock the harness times each
call on (``gen.calls``, ``time.perf_counter`` seconds), and a total of
every counter.  The same spans are in a profiler trace, where one was
recorded.  A program that predates them has neither: every function
here then gives None, and the reader leaves its metric out.
"""

from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace as tr


def _runtime():
    try:
        from repro.analysis import runtime
    except ImportError:
        return None
    if not all(hasattr(runtime, f) for f in ("span_log", "span_stats",
                                             "counts")):
        return None
    return runtime


def per_call_ms(calls: Sequence[Tuple[int, int]],
                spans: Iterable[Tuple[int, int]]) -> Optional[List[float]]:
    """Per call ``[start, end)``, milliseconds of the union of the spans'
    parts inside it; None where no span falls inside any call."""
    spans = list(spans)
    out, found = [], False
    for lo, hi in calls:
        inside = tr.merge(tr.clip(spans, lo, hi))
        found = found or bool(inside)
        out.append(sum(e - s for s, e in inside) * 1e-6)
    return out if found else None


def median_ms(gen, match) -> Optional[float]:
    """Median over the window's calls of :func:`per_call_ms`, over the
    program's spans whose name ``match`` accepts."""
    rt = _runtime()
    if rt is None or not gen.calls:
        return None
    spans = [(r.start_ns, r.end_ns) for name in rt.span_stats()
             if match(name) for r in rt.span_log(name)]
    calls = [(int(s * 1e9), int(e * 1e9)) for s, e in gen.calls]
    per = per_call_ms(calls, spans)
    return statistics.median(per) if per else None


def counts(prefix: str) -> Optional[Dict[str, int]]:
    """The program's counters under ``prefix``, or None."""
    rt = _runtime()
    if rt is None:
        return None
    return rt.counts(prefix)
