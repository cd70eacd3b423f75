"""Work of the steps inside the window over the time between its boundaries.
params: ``work`` (the step's work key), ``per_chip`` (divide by the chips)."""


def read(ctx, params):
    rate = ctx.window.rate(params["work"])
    return rate / ctx.chips if params.get("per_chip") else rate
