"""A percentile over the requests whose measured interval lies inside the
window. params: ``quantity`` (``ttft`` = t_first_token - t_submit, over
requests sent and given their first token inside; ``tpot`` = (t_done -
t_first_token) / (n - 1), over requests sent and finished inside, those of one
token left out), ``p``, ``scale``."""

from ..lib.window import percentile


def read(ctx, params):
    w = ctx.window
    if params["quantity"] == "ttft":
        values = [r.t_first_token - r.t_submit for r in ctx.requests
                  if w.inside(r.t_submit, r.t_first_token)]
    elif params["quantity"] == "tpot":
        values = [(r.t_done - r.t_first_token) / (len(r.tokens) - 1)
                  for r in ctx.requests
                  if w.inside(r.t_submit, r.t_done) and len(r.tokens) > 1]
    else:
        raise ValueError(f"unknown quantity {params['quantity']!r}")
    if not values:
        return None
    return percentile(values, float(params["p"])) * float(
        params.get("scale", 1.0))
