"""Share of the window, in percent, in which the program's engine had nothing
queued on the device: the summed seconds of its ``device.starved`` events
(``deepspeed_tpu/profiling/trace.py``: from the exit of the wait in which the
host read back the last thing it had queued, ``after``, to the return of the
next program's dispatch, ``by``) over ``window.seconds``, from the program's
own record (``lib/record.py``). The whole window, the profiler off for all of
it but the traced slice; the device cannot have begun before the dispatch
returned, so this is the idle that is the host's turnaround and no more. An
event that straddles an edge of the traced slice holds the harness's own
pause there (the profiler's start or stop) and is left out.

Two ``[bench]`` lines. **The split**: the starved seconds by the span of the
record the host was in (the innermost: ``serve.commit``, ``serve.admit.claim``,
the feeding dispatch's own span, a ``host.gc``; ``outside any step``: the
callers between two steps), milliseconds a step ``step``, largest first.
**The check against the device**: inside the traced slice the events'
seconds beside the slice's idle seconds that began under the same waits
(``ctx.trace.gaps_by_span``) and the idle that began elsewhere, which no
wait explains (programs shorter than their own dispatch); what that idle is
made of beside the events, gap by gap on the profiler's clock (the device had
ended before the host had its result: the read-back; the device began after
the dispatch had returned: the launch); and the mean starvation a step inside
the slice over that outside it, what the profiler does to the host's
turnaround."""

import statistics

from ..lib import program_trace, record
from ..lib import trace as T
from ..lib.device import say

NAME = "device.starved"
OUTSIDE = "outside any step"


def events(ctx, rec):
    """The window's ``device.starved`` events, without those that straddle
    an edge of the traced slice; None where the program writes none (the
    parent of the PR that added them)."""
    from deepspeed_tpu.profiling import trace as names  # record.of read it

    if getattr(names, "DEVICE_STARVED", None) != NAME:
        return None
    out = rec.named(NAME)
    if ctx.traced is not None:
        out = [e for e in out
               if not any(e.t0 < edge < e.t1 for edge in ctx.traced)]
    return out


def split(rec, starved):
    """{span name: seconds} of ``starved`` by the innermost span of the
    record that covers each instant; ``OUTSIDE`` where none does."""
    dry = T.union((e.t0, e.t1) for e in starved)
    pieces = T.self_times([(e.name, e.t0, e.t1) for e in rec.entries
                           if e.name != NAME])
    by_name = {}
    for name, t0, t1 in pieces:
        by_name.setdefault(name, []).append((t0, t1))
    out = {name: T.intersect(T.union(spans), dry)
           for name, spans in by_name.items()}
    out = {name: secs for name, secs in out.items() if secs > 0}
    out[OUTSIDE] = T.measure(dry) - sum(out.values())
    return out


def per_step(rec, starved, step):
    """[[step entry, starved seconds]]: an event goes to the step whose
    number it carries, the one its feeding dispatch lies in."""
    steps = {s.step: [s, 0.0] for s in rec.named(step)}
    for e in starved:
        if e.step in steps:
            steps[e.step][1] += e.dur
    return list(steps.values())


def _say_split(rec, starved, step):
    n = len(rec.named(step)) or 1
    parts = sorted(split(rec, starved).items(), key=lambda kv: -kv[1])
    say(f"device.starved {1000 * sum(e.dur for e in starved) / n:.3f} ms a "
        f"{step} over {len(starved)} events, by the span the host was in: "
        + ", ".join(f"{name} {1000 * secs / n:.3f}" for name, secs in parts))


def made_of(pt, steps, inside, waits, step):
    """The device's gaps that began under ``waits`` in the trace ``pt``, each
    beside the event of ``inside`` that overlaps it most, the record's clock
    laid on the profiler's by the slice's ``steps``: (gaps an event met,
    seconds the device had ended before the event began, seconds both hold,
    seconds after the event ended until the device began; gaps no event met,
    between the operations of a running program, and their seconds), means
    over the devices; None where the two do not hold the same steps."""
    theirs = pt.named(step)
    if not theirs or len(theirs) != len(steps):
        return None
    shift = statistics.median(a.t0 - b.t0 for a, b in zip(theirs, steps))
    events = [(e.t0 + shift, e.t1 + shift) for e in inside]
    held = [(s.t0, s.t1) for s in pt.spans if s.name in waits]
    devices = [ops for ops in pt.ops.values() if T.clip(ops, *pt.window)]
    out = [0.0] * 6
    for ops in devices:
        busy = T.union((a, b) for _, a, b in T.clip(ops, *pt.window))
        for g0, g1 in T.complement(busy, *pt.window):
            if not any(a <= g0 < b for a, b in held):
                continue
            e0, e1 = max(events, default=(g1, g1), key=lambda e: min(
                e[1], g1) - max(e[0], g0))
            both = max(0.0, min(e1, g1) - max(e0, g0))
            parts = ((1, max(0.0, min(e0, g1) - g0), both,
                      max(0.0, g1 - max(e1, g0)), 0, 0.0) if both
                     else (0, 0.0, 0.0, 0.0, 1, g1 - g0))
            out = [x + y / len(devices) for x, y in zip(out, parts)]
    return out


def _say_check(ctx, rec, starved, step):
    if ctx.traced is None or ctx.trace is None:
        return
    t0, t1 = ctx.traced
    inside = [e for e in starved if e.t0 >= t0 and e.t1 <= t1]
    waits = sorted({e.counts["after"] for e in starved})
    gaps = ctx.trace.gaps_by_span
    idle = ctx.trace.window_s - ctx.trace.busy_s
    under = sum(gaps.get(w, 0.0) for w in waits)
    ours = sum(e.dur for e in inside)
    other = sorted(((n, s) for n, s in gaps.items() if n not in waits),
                   key=lambda kv: -kv[1])[:4]
    say(f"device.starved inside the traced slice: {ours:.4f} s in "
        f"{len(inside)} events, for {under:.4f} s of the device's idle that "
        f"began under {' or '.join(waits) or 'no wait'} "
        f"({ours / under if under else float('nan'):.3f} of it) and "
        f"{idle:.4f} s of idle in all; the other {idle - under:.4f} s began "
        "with the host at work, the device ahead of its dispatches: "
        + (", ".join(f"{n} {s:.4f}" for n, s in other) or "none"))
    pt = program_trace.of(ctx)
    parts = pt and made_of(pt, [s for s in rec.named(step)
                                if s.t0 >= t0 and s.t1 <= t1],
                           inside, waits, step)
    if parts and parts[0]:
        n, lead, both, tail, short, unmet = parts
        say(f"of that idle, by the profiler's clock, {n:.0f} gaps met an "
            f"event: the device had ended {lead:.4f} s before the host had "
            f"its result (the read-back, {1e6 * lead / n:.0f} us a gap), "
            f"device and event were both dry {both:.4f} s, the device began "
            f"{tail:.4f} s after the dispatch had returned (the launch, "
            f"{1e6 * tail / n:.0f} us a gap) and {ours - both:.4f} s of the "
            f"events lay where it was at work again; {short:.0f} gaps met "
            f"none, {unmet:.4f} s between the operations of a running "
            "program")
    steps = per_step(rec, starved, step)
    if any(s for e, s in steps if e.t1 <= t0 or e.t0 >= t1):
        record.say_traced_split(ctx, f"device.starved a {step}", steps)


def read(ctx, params):
    rec = record.of(ctx)
    if rec is None:
        return None
    starved = events(ctx, rec)
    if starved is None:
        return None
    _say_split(rec, starved, params["step"])
    _say_check(ctx, rec, starved, params["step"])
    return 100.0 * sum(e.dur for e in starved) / ctx.window.seconds
