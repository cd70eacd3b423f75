"""Spans of one name, grouped by their parent span: mean over the groups of
the summed duration, times ``scale`` (the prefill time of one admission cycle
is the sum of the prefill dispatches inside one ``sched.step``)."""


def read(ctx, params):
    w = ctx.window
    groups = {}
    for s in ctx.spans.named(params["span"], w.t_open, w.t_close):
        groups[s.parent] = groups.get(s.parent, 0.0) + s.dur
    if not groups:
        return None
    return sum(groups.values()) / len(groups) * float(params.get("scale", 1.0))
