"""Device time of the programs whose names on the device's "XLA Modules" line
match ``pattern``, milliseconds per execution; with ``steps_group``, per step,
the steps of one execution read from that group of the pattern
(``jit_decode_block_(\\d+)``)."""

import re

from ..lib import program_trace


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or not pt.modules:
        return None
    pattern = re.compile(params["pattern"])
    group = params.get("steps_group")
    secs = units = 0.0
    for name, (s, n) in pt.program_seconds.items():
        m = pattern.search(name)
        if m:
            secs += s
            units += n * (int(m.group(group)) if group else 1)
    if not units:
        return None
    return 1000.0 * secs / units
