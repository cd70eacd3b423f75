"""Share of the traced window, in percent, in which no operation ran on the
device (mean over the chips used)."""


def read(ctx, params):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
