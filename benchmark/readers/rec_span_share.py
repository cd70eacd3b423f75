"""Share of the window, in percent, that the program spent inside its spans
named ``span``: their summed seconds in the program's own record
(``lib/record.py``) over ``window.seconds``. The whole window, profiler off
or on, not the traced slice. The span names are the program's
(``deepspeed_tpu/profiling/trace.py``): part of this metric's yardstick
though they live outside ``benchmark/``."""

from ..lib import record


def read(ctx, params):
    rec = record.of(ctx)
    if rec is None:
        return None
    spans = rec.named(params["span"])
    if not spans:
        return None
    return 100.0 * sum(s.dur for s in spans) / ctx.window.seconds
