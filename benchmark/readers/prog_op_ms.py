"""Self time on the device of the operations whose short names match
``pattern``, per unit of the program's own work: the spans named ``span``
inside the traced window, each counted once or, with ``count``, as that stat
of it (``steps`` of ``serve.decode``). Milliseconds."""

import re

from ..lib import program_trace


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or pt.reduced is None:
        return None
    spans = pt.named(params["span"])
    units = (sum(s.stats.get(params["count"], 0) for s in spans)
             if params.get("count") else len(spans))
    pattern = re.compile(params["pattern"])
    secs = sum(v for name, v in pt.reduced.op_seconds.items()
               if pattern.search(name))
    if not units or not secs:
        return None
    return 1000.0 * secs / units
