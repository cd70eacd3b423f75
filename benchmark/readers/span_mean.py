"""Mean duration of the spans of one name inside the window, times ``scale``.
With ``self``: less the spans recorded inside each (its self time)."""


def read(ctx, params):
    log, w = ctx.spans, ctx.window
    picked = [(i, s) for i, s in enumerate(log.spans)
              if s.name == params["span"]
              and s.t0 >= w.t_open and s.t1 <= w.t_close]
    if not picked:
        return None
    total = 0.0
    for i, s in picked:
        total += s.dur
        if params.get("self"):
            total -= sum(c.dur for c in log.children(i))
    return total / len(picked) * float(params.get("scale", 1.0))
