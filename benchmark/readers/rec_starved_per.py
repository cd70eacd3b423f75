"""Milliseconds a span ``per`` of the window in which the engine had nothing
queued on the device after the wait ``after``: the summed seconds of the
``device.starved`` events (``readers/rec_starved_share.py``) whose ``after``
is that span's name, over the window's spans named ``per``, from the
program's own record (``lib/record.py``). ``engine.decode.fetch`` over
``serve.step``: the host between two steps (it commits the block's tokens,
lets the callers submit, sweeps, claims the next admission, and the first
dispatch returns). ``engine.prefill.sample`` over ``serve.admit.prefill``,
with ``holding``: over those admissions only in which such an event began,
the chip dry between two prompts of one admission, each waited for where it
ends. None where no such event was written."""

from ..lib import record
from .rec_starved_share import events


def read(ctx, params):
    rec = record.of(ctx)
    if rec is None:
        return None
    starved = events(ctx, rec)
    if starved is None:
        return None
    starved = [e for e in starved if e.counts["after"] == params["after"]]
    spans = rec.named(params["per"])
    if params.get("holding"):
        spans = [s for s in spans
                 if any(s.t0 <= e.t0 < s.t1 for e in starved)]
    if not starved or not spans:
        return None
    return 1000.0 * sum(e.dur for e in starved) / len(spans)
