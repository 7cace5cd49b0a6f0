"""Sampling time of one live tick, median over the traced ticks, in ms.

The ``plane.tick.sample`` span: every node's monitor sample, its
validation and the node's health state machine.
"""

from bench import program_spans


def read(ctx):
    return program_spans.median_ms(ctx["gen"],
                                   lambda name: name == "plane.tick.sample")
