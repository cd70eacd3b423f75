"""Mean over every step span ``span`` of the window of its duration less the
spans named ``less`` inside it, milliseconds, from the program's own record
(``lib/record.py``): ``serve.step`` less ``serve.admit.prefill`` and
``serve.decode`` is ``sched_host_ms`` over the whole window, the profiler
off for all of it but the traced slice. A ``[bench]`` line says the mean
inside the traced slice and outside it: what the profiler costs the host."""

from ..lib import record
from ..lib.device import say


def read(ctx, params):
    rec = record.of(ctx)
    if rec is None:
        return None
    per_step = rec.self_seconds(params["span"], params["less"])
    if not per_step:
        return None
    mean = sum(s for _, s in per_step) / len(per_step)
    what = f"{params['span']} less {' and '.join(params['less'])}"
    say(f"{what}: {1000 * mean:.3f} ms a step over the {len(per_step)} steps "
        f"of the window, {100 * mean * len(per_step) / ctx.window.seconds:.2f}"
        "% of it")
    record.say_traced_split(ctx, what, per_step)
    return 1000.0 * mean
