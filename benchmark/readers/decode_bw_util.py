"""Bytes a decode step must read (the configuration's ``decode_step_bytes``:
its reference's where that has one, else ``lib/flops``'s; weights plus the
live keys and values the block tables name) over the chip's HBM
bandwidth, over the device time of one decode step: the busy time the trace
shows inside the ``decode`` spans of the traced window, per step. Percent."""

from ..lib.peaks import device_peaks


def read(ctx, params):
    if ctx.trace is None or ctx.traced is None:
        return None
    spans = ctx.spans.named("decode", *ctx.traced)
    steps = sum(s.meta["steps"] for s in spans)
    busy = ctx.trace.busy_in_span.get("decode", 0.0)
    if not steps or not busy:
        return None
    # a block of k steps reads k times; its live tokens grow by the active
    # slots each step
    live = sum(s.meta["steps"] * (s.meta["live_kv_tokens"]
                                  + s.meta["active"] * (s.meta["steps"] - 1) / 2)
               for s in spans) / steps
    need = ctx.count("decode_step_bytes")(ctx.model, live)
    floor_s = need / device_peaks(ctx.device_kind).hbm_bytes_per_s
    return 100.0 * floor_s / (busy / steps)
