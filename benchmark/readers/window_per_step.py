"""Seconds of the window per step inside it, times ``scale``: the step as the
user pays for it, host time between steps included."""


def read(ctx, params):
    return (ctx.window.seconds / len(ctx.window.steps)
            * float(params.get("scale", 1.0)))
