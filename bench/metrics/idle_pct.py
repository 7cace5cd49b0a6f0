"""Share of the traced window in which no operation ran on the device.

Averaged over the chips the cell uses.  Read for every ``idle_pct.<cell
kind>`` metric: idle chip time is time the host holds the chip back, so
each moves its own cell's end-to-end metric.
"""


def read(ctx):
    t = ctx["trace"]
    if not ctx["devices"] or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s(ctx["devices"]) / t.window_s)
