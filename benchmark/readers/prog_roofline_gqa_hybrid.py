"""The grouped-query decode kernel's share of its roofline, percent, for a
model in which EVERY layer attends beside a state-space mixer (a
``falcon_h1`` layer), beside ``prog_roofline_gqa_pattern`` (which counts a
layer for every ``*`` of ``hybrid_pattern``: none here, the pattern says the
mixers) and ``prog_roofline_gqa`` (a layer for every entry of
``layer_types``): the least time the chip could take for the
``paged_decode_gqa`` calls the trace shows (``lib/kernel_cost_gqa``:
operations over the bf16 peak or bytes over the HBM peak, whichever is
larger) over the self time of those calls. The rows are the program's own
count: ``kv_rows`` of its ``serve.decode`` spans, the rows of keys and values
a dispatch's steps read over every cache layer (``live_kv_tokens`` and what
the block's earlier steps appended, in each of ``cache_layers``), which a
program whose slots keep a state says beside ``state_slots``; 2 bytes a
number. Says on a ``[bench]`` line which peak bounds the kernel. A program
without the kernel or the count (the parent of the PR that brought it, a
model that keeps no state) gives nothing to read."""

from ..lib import kernel_cost_gqa, program_trace
from ..lib.device import say
from ..lib.peaks import device_peaks
from .prog_roofline import _time_and_calls


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or pt.reduced is None:
        return None
    secs, calls = _time_and_calls(pt, "^" + params["kernel"])
    spans = [s for s in pt.named("serve.decode") if "kv_rows" in s.stats]
    model = ctx.model
    if not calls or not secs or not spans or "n_kv_head" not in model:
        return None
    peaks = device_peaks(ctx.device_kind)
    need = kernel_cost_gqa.paged_decode_gqa(
        sum(s.stats["kv_rows"] for s in spans), model["n_head"],
        model["n_kv_head"], model["head_dim"])
    floor = need.floor_s(peaks)
    say(f"{params['kernel']}: {need.flops / need.bytes:.1f} operations a "
        f"byte, bound by {need.bound(peaks)}; {need.bytes / 1e9:.2f} GB over "
        f"{calls:g} calls; {100 * floor / secs:.2f}% of its roofline")
    return 100.0 * floor / secs
