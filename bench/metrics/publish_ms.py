"""Publishing time of one live tick, median over the traced ticks, in ms.

The ``plane.tick.publish`` span: the RAW_TOPIC publishes, which run the
stream aggregation and the controller's ``observe`` in line.
"""

from bench import program_spans


def read(ctx):
    return program_spans.median_ms(ctx["gen"],
                                   lambda name: name == "plane.tick.publish")
