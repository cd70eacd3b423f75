"""Mean duration of the program's spans named ``span`` inside the traced
window, milliseconds; with ``less``, less the spans of those names inside each
(``serve.step`` less its prefill and decode dispatches: the scheduler's own
host time, on the profiler's clock)."""

from ..lib import program_trace


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    spans = pt.named(params["span"])
    if not spans:
        return None
    less = set(params.get("less", ()))
    total = sum(s.dur - sum(c.dur for c in pt.inside(s, less))
                for s in spans)
    return 1000.0 * total / len(spans)
