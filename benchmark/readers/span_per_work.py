"""Summed duration of the spans of one name inside the window over the summed
work they carried (``work`` names the span's count), times ``scale``."""


def read(ctx, params):
    w = ctx.window
    spans = ctx.spans.named(params["span"], w.t_open, w.t_close)
    work = sum(s.meta.get(params["work"], 0) for s in spans)
    if not work:
        return None
    return sum(s.dur for s in spans) / work * float(params.get("scale", 1.0))
