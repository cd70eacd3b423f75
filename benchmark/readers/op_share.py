"""Share of the device's busy time, in percent, taken by the operations whose
trace names match ``pattern`` (a regular expression, searched)."""

import re


def read(ctx, params):
    if ctx.trace is None or not ctx.trace.busy_s:
        return None
    pattern = re.compile(params["pattern"])
    hit = sum(s for name, s in ctx.trace.op_seconds.items()
              if pattern.search(name))
    return 100.0 * hit / ctx.trace.busy_s
