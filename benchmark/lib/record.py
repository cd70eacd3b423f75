"""The program's own record of its spans, over the whole window.

``deepspeed_tpu/profiling/trace.py`` keeps every host span it writes
(``serve.*``, ``engine.*``, ``train.*``) and every compile (``xla.compile``)
in a bounded ring in memory, on ``time.perf_counter``'s clock: the clock
``run.py`` hands the scheduler and stamps the window with, so an entry lies
against ``window.t_open .. t_close``, ``ctx.traced`` and a request's stamps
as it is. The profiler need not run: where ``lib/program_trace.py`` reads the
same names from the few traced seconds, this reads them from all of them,
in-process, after the run.

Where the program keeps no record (the parent of the PR that added it),
where the record is empty, or where its oldest entry is younger than the
window's opening (the ring wrapped: part of the window is gone), there is
nothing to read: ``of`` says so on a ``[bench]`` line and the readers return
None. Never a partial answer.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional

from .device import say


@dataclasses.dataclass
class Record:
    entries: List                  # trace.Recorded, whole inside the window,
    #                                by start (an outer span before its inner)

    def named(self, name: str) -> List:
        return [e for e in self.entries if e.name == name]

    def self_seconds(self, span: str, less: Iterable[str]) -> List[list]:
        """[[entry, seconds]] of every ``span``: its duration less the spans
        named in ``less`` that lie inside it."""
        less = set(less)
        out: List[list] = []
        for e in self.entries:
            if e.name == span:
                out.append([e, e.dur])
            elif (e.name in less and out and e.step == out[-1][0].step
                  and e.t1 <= out[-1][0].t1):
                out[-1][1] -= e.dur
        return out


def _read(window) -> Optional[Record]:
    try:
        from deepspeed_tpu.profiling import trace as names
    except ImportError:
        return None
    if not hasattr(names, "recorded"):
        say("the program keeps no record of its spans: no metric is read "
            "over the whole window")
        return None
    entries = names.recorded()
    if not entries:
        say("the program's record of its spans is empty: no metric is read "
            "over the whole window")
        return None
    if entries[0].t0 > window.t_open:
        say(f"the program's record wrapped: its oldest entry began "
            f"{entries[0].t0 - window.t_open:.3f} s after the window opened; "
            "no metric is read over the whole window")
        return None
    return Record([e for e in entries
                   if e.t0 >= window.t_open and e.t1 <= window.t_close])


def of(ctx) -> Optional[Record]:
    """The record's entries inside this run's window, or None (see above).
    Read once a run."""
    if not hasattr(ctx, "_record"):
        ctx._record = _read(ctx.window)
    return ctx._record


def say_traced_split(ctx, what: str, per_step: List[list]) -> None:
    """What the profiler does to a host time: the mean of ``per_step``
    ([[entry, seconds]]) over the steps inside the traced slice, where the
    profiler ran, beside the mean over the steps outside it."""
    if ctx.traced is None or not per_step:
        return
    t0, t1 = ctx.traced
    inside = [s for e, s in per_step if e.t0 >= t0 and e.t1 <= t1]
    outside = [s for e, s in per_step if e.t1 <= t0 or e.t0 >= t1]
    if inside and outside:
        a, b = sum(inside) / len(inside), sum(outside) / len(outside)
        say(f"{what}: {1000 * a:.3f} ms over the {len(inside)} steps inside "
            f"the traced slice (the profiler on), {1000 * b:.3f} ms over "
            f"the {len(outside)} outside it ({a / b:.3f} x)")
