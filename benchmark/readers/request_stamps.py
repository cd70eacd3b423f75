"""A percentile of one request stamp less another, over the requests whose
interval ``inside`` (two stamps) lies inside the window. params: ``from``,
``to`` (``t_submit``, ``t_admit``, ``t_first_token``, ``t_done``: the
scheduler stamps them on the clock it is given), ``inside``, ``p``,
``scale``. ``t_admit - t_submit`` over the requests ``ttft_p95_ms`` counts is
the part of the wait for a first token spent in the queue, before the
admission cycle that prefills the prompt."""

from ..lib.window import percentile


def read(ctx, params):
    first, last = params["inside"]
    values = []
    for r in ctx.requests:
        a, b = getattr(r, params["from"]), getattr(r, params["to"])
        if (ctx.window.inside(getattr(r, first), getattr(r, last))
                and a is not None and b is not None):
            values.append(b - a)
    if not values:
        return None
    return percentile(values, float(params["p"])) * float(
        params.get("scale", 1.0))
