"""A Pallas kernel's share of its roofline, percent: the least time the chip
could take for the calls the trace shows (``lib/kernel_cost``: operations over
the bf16 peak or bytes over the HBM peak, whichever is larger, by the cell's
shapes) over the self time of those calls. ``kernel``: ``flash_fwd``,
``flash_bwd`` (delta, dq and dkv together) or ``paged_decode`` (live keys and
values from the ``live_kv_tokens`` of the program's ``serve.decode`` spans).
Says on a ``[bench]`` line which peak bounds the kernel."""

import re

from ..lib import kernel_cost, program_trace
from ..lib.device import say
from ..lib.peaks import device_peaks


def _time_and_calls(pt, pattern):
    rx = re.compile(pattern)
    secs = sum(v for n, v in pt.reduced.op_seconds.items() if rx.search(n))
    calls = sum(v for n, v in pt.op_counts.items() if rx.search(n))
    return secs, calls


def _flash(ctx, pt, peaks, kernel):
    model = ctx.model
    engine = ctx.cell["config_file"].get("engine", {})
    split = {k: v for k, v in {
        "gradient_accumulation_steps":
            engine.get("gradient_accumulation_steps", 1),
        **{a: engine.get("mesh", {}).get(a, 1)
           for a in ("tp", "sp", "pp", "ep")}}.items() if v != 1}
    if split:
        # a call then sees a part of the step's tokens or heads that the
        # cell's facts do not give: no floor is better than a wrong one
        say(f"{kernel}: no roofline share, the step is split by {split}")
        return None
    t = int(ctx.facts["seq_len"])
    heads = model["n_head"]
    # one call a layer over the chip's whole micro-batch, causal, keys as
    # long as queries: data parallel only, one micro-batch a step
    bh = int(ctx.facts["tokens_per_step"]) // t // ctx.chips * heads
    dh = model["d_model"] // heads
    parts = {
        "flash_fwd": {"^flash_fwd": kernel_cost.flash_fwd},
        "flash_bwd": {
            "^flash_bwd_delta": lambda bh, t, s, dh, **kw:
                kernel_cost.flash_bwd_delta(bh, t, dh, **kw),
            "^flash_bwd_dq": kernel_cost.flash_bwd_dq,
            "^flash_bwd_dkv": kernel_cost.flash_bwd_dkv},
    }[kernel]
    floor = moved_floor = secs = 0.0
    total = kernel_cost.Cost(0.0, 0.0)
    for pattern, cost_of in parts.items():
        part_s, calls = _time_and_calls(pt, pattern)
        if not calls:
            return None
        need = cost_of(bh, t, t, dh)
        floor += calls * need.floor_s(peaks)
        moved_floor += calls * cost_of(bh, t, t, dh,
                                       lse_lanes=128).floor_s(peaks)
        total = total + need
        secs += part_s
    say(f"{kernel}: {total.flops / 1e9:.2f} GFLOP and {total.bytes / 1e6:.1f}"
        f" MB a layer, bound by {total.bound(peaks)}; {100 * floor / secs:.2f}"
        f"% of its roofline, {100 * moved_floor / secs:.2f}% with the "
        "lane-padded log-sum-exp and delta rows counted as bytes")
    return 100.0 * floor / secs


def _paged_decode(ctx, pt, peaks):
    model = ctx.model
    secs, calls = _time_and_calls(pt, "^paged_decode")
    spans = pt.named("serve.decode")
    if not calls or not spans:
        return None
    heads = model["n_head"]
    dh = model["d_model"] // heads
    # one call a cache layer: a model that runs its layers again walks a
    # layer's keys and values once a loop (its reference counts them)
    calls_a_step = ctx.count("cache_layers")(model)
    floor = 0.0
    for s in spans:     # a block of k steps: the live tokens grow each step
        for j in range(int(s.stats["steps"])):
            live = s.stats["live_kv_tokens"] + s.stats["active"] * (j + 1)
            floor += calls_a_step * kernel_cost.paged_decode(
                live, heads, dh).floor_s(peaks)
    return 100.0 * floor / secs


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or pt.reduced is None:
        return None
    peaks = device_peaks(ctx.device_kind)
    if params["kernel"] == "paged_decode":
        return _paged_decode(ctx, pt, peaks)
    return _flash(ctx, pt, peaks, params["kernel"])
