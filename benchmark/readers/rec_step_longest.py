"""The longest step span ``span`` of the window over the median one, from the
program's own record (``lib/record.py``): 1.0 in a run whose steps are all
alike, the admission's share in a cell that admits now and then, far over
that in a run that stalled. A ``[bench]`` line gives the program's own
account of that step (``trace.slowest``): the seconds of the spans inside it
by name and whatever compiled in it, which says whether the host waited
(``engine.decode.fetch``, ``train.sync``) or worked (``serve.admit.claim``,
``train.dispatch``). With ``less`` (the spans in which a step waits for the
device), a second line says the step's host time inside the traced slice and
outside it."""

import statistics

from ..lib import record
from ..lib.device import say


def read(ctx, params):
    rec = record.of(ctx)
    if rec is None:
        return None
    span = params["span"]
    steps = rec.named(span)
    if not steps:
        return None
    median = statistics.median(s.dur for s in steps)
    longest = max(steps, key=lambda s: s.dur)
    from deepspeed_tpu.profiling import trace as names

    w = ctx.window
    (slow,) = names.slowest(span, n=1, since=w.t_open) or [None]
    if slow is not None and slow.step.t0 == longest.t0:
        inside = ", ".join(f"{name} {secs:.4f}" for name, secs in sorted(
            slow.seconds.items(), key=lambda kv: -kv[1]))
        say(f"longest {span} {longest.dur:.4f} s (step_num {longest.step}, "
            f"{longest.t0 - w.t_open:.2f} s after the window opened, "
            f"{longest.dur / median:.2f} x the median {median:.4f}): "
            f"{inside or 'no span inside it'}; compiled in it: "
            f"{', '.join(slow.compiled) or 'nothing'}")
    if params.get("less"):
        record.say_traced_split(
            ctx, f"{span} less {' and '.join(params['less'])}",
            rec.self_seconds(span, params["less"]))
    return longest.dur / median
