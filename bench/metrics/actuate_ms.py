"""Actuation time of one live tick, median over the traced ticks, in ms.

The ``plane.tick.actuate`` span: each observed node's store resize, its
``ControlAction``, the action history and the CONTROL_TOPIC publishes.
"""

from bench import program_spans


def read(ctx):
    return program_spans.median_ms(ctx["gen"],
                                   lambda name: name == "plane.tick.actuate")
