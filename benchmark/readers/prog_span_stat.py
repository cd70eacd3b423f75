"""Mean of one stat of the program's spans named ``span`` over the spans
inside the traced window that carry it: ``stat`` ``expert_load_max`` of
``serve.decode`` is the most tokens one held expert met in a step, a
dispatch at a time. Spans without the stat (a program that records none)
give nothing to read."""

from ..lib import program_trace


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    values = [float(s.stats[params["stat"]]) for s in pt.named(params["span"])
              if params["stat"] in s.stats]
    if not values:
        return None
    return sum(values) / len(values)
