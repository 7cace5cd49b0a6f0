"""Device busy time inside one live tick, median over the traced ticks.

The fused law of ``ArrayController`` is the only device work a tick
does; this is what it costs the chip per control interval.
"""

import statistics


def read(ctx):
    t = ctx["trace"]
    ticks = [(s, e) for name, s, e in t.spans if name == "tick"]
    if not ticks or not ctx["devices"]:
        return None
    return 1e3 * statistics.median(t.busy_in(s, e, ctx["devices"])
                                         for s, e in ticks)
