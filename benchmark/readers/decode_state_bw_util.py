"""``decode_bw_util`` for a model that keeps a state a decode slot: the bytes
a decode step must move (the reference's ``decode_step_bytes`` with the live
keys and values AND ``state_slots``, the slots whose states the step reads
and writes, and ``active``, the tokens that touch the held experts) over the
chip's HBM bandwidth, over the device time of one decode step: the busy time
the trace shows inside the ``decode`` spans of the traced window, per step.
Percent. ``decode_bw_util`` hands ``decode_step_bytes`` the live tokens only
and cannot see the states. The counts come from the program's
``serve.decode`` spans (``state_slots``); a program without them (the parent
of the PR that brought them) gives nothing to read."""

from ..lib import program_trace
from ..lib.peaks import device_peaks


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or ctx.trace is None:
        return None
    spans = [s for s in pt.named("serve.decode") if "state_slots" in s.stats]
    steps = sum(s.stats["steps"] for s in spans)
    busy = ctx.trace.busy_in_span.get("decode", 0.0)
    if not steps or not busy:
        return None
    count = ctx.count("decode_step_bytes")
    need = sum(
        count(ctx.model, s.stats["live_kv_tokens"] + s.stats["active"] * j,
              state_slots=s.stats["state_slots"], active=s.stats["active"])
        for s in spans for j in range(int(s.stats["steps"])))
    floor_s = need / device_peaks(ctx.device_kind).hbm_bytes_per_s
    return 100.0 * floor_s / busy
