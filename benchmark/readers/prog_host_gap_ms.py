"""Time per step in which no operation ran on the device, milliseconds, over
the ``span`` steps of the traced window (``train.step``). A ``[bench]`` line
says which of the program's spans the host was in when each gap began."""

from ..lib import program_trace
from ..lib.device import say


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or pt.reduced is None:
        return None
    steps = len(pt.named(params["span"]))
    if not steps:
        return None
    gaps = pt.reduced.gaps_by_span
    idle = sum(gaps.values())
    say("idle on the device by the program's innermost span, ms a step: "
        + ", ".join(f"{name} {1000 * secs / steps:.3f}"
                    for name, secs in pt.reduced.top_gaps(8)))
    return 1000.0 * idle / steps
