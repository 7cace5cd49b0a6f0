"""Share of the dispatched lane-steps that carry a real gain, in %.

100 x ``<engine>.lane_steps.live`` / ``<engine>.lane_steps.run`` from the
program's counters: gains (or halving lanes alive) times intervals,
over the padded lanes the programs run times the same intervals.  The
engine follows the cell's entry point: ``lab.sweep`` for
``sweep_demand``, ``lab.halving`` for ``halving_tune``.  Shapes are
fixed in a cell, so the process total is the window's ratio.
"""

from bench import program_spans

ENGINE = {"sweep_demand": "lab.sweep", "halving_tune": "lab.halving"}


def read(ctx):
    prefix = ENGINE.get(ctx["gen"].label)
    c = program_spans.counts(prefix) if prefix else None
    if not c or not c.get(prefix + ".lane_steps.run"):
        return None
    return (100.0 * c.get(prefix + ".lane_steps.live", 0)
            / c[prefix + ".lane_steps.run"])
