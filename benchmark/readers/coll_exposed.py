"""Share of the traced window, in percent, in which a collective runs on a
device and no compute does (mean over the chips)."""


def read(ctx, params):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.exposed_collective_s / ctx.trace.window_s
