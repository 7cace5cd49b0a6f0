"""Node-interval updates per second of the XLA engine on the device.

The updates of the sweeps in the traced window over the device busy
time inside their ``sweep_demand`` calls (the kind's host span), averaged
over the chips used: a rate of the engine's device work alone, free of
host time.  Each call returns numpy statistics, so its device work ends
inside its span, and the gain draw between calls runs on the host.  The
chunk program is not matched by name: it is jitted from a
``functools.partial`` and compiles as ``jit__unknown``.
"""


def read(ctx):
    gen = ctx["gen"]
    seconds = ctx["trace"].span_busy_s(gen.label, ctx["devices"])
    if seconds <= 0:
        return None
    return sum(gen.updates) / seconds
