"""Host staging time of one call, median over the traced calls, in ms.

Per call of the window: the union of the program's spans whose name
ends in ``.stage`` that fall inside it (``lab.sweep.stage``: horizon
slice, law-class split, f64 -> f32 transpose, padding, device puts;
``lab.tune.stage`` and ``lab.halving.stage``: the scenario build, the
same transpose and puts).  Read for every ``stage_ms.<cell kind>``.
"""

from bench import program_spans


def read(ctx):
    return program_spans.median_ms(ctx["gen"],
                                   lambda name: name.endswith(".stage"))
