"""Process start to the opening of the window, less the time to bring up the
TPU runtime (``run.py`` times it around the first ``jax.devices()``)."""


def read(ctx, params):
    return ctx.setup_s
