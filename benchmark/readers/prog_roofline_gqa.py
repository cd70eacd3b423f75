"""The grouped-query decode kernel's share of its roofline, percent, beside
``prog_roofline``: the least time the chip could take for the
``paged_decode_gqa`` calls the trace shows (``lib/kernel_cost_gqa``:
operations over the bf16 peak or bytes over the HBM peak, whichever is
larger) over the self time of those calls. The rows come from the program's
``serve.decode`` spans: ``kv_rows_full`` in each full layer (the live lengths,
and the tokens a block's earlier steps appended), ``kv_rows_window`` in each
window layer (``min(length, sliding_window)``; a slot still inside its window
grows by a row a step, which is left out: under 1% at a window of 512). Says
on a ``[bench]`` line which peak bounds the kernel. A program without the
kernel or the counts (the parent of the PR that brought them) gives nothing
to read."""

from ..lib import kernel_cost_gqa, program_trace
from ..lib.device import say
from ..lib.peaks import device_peaks
from .prog_roofline import _time_and_calls


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or pt.reduced is None:
        return None
    secs, calls = _time_and_calls(pt, "^" + params["kernel"])
    spans = [s for s in pt.named("serve.decode") if "kv_rows_full" in s.stats]
    model = ctx.model
    if not calls or not secs or not spans or "layer_types" not in model:
        return None
    peaks = device_peaks(ctx.device_kind)
    floor, total = 0.0, kernel_cost_gqa.Cost(0.0, 0.0)
    for s in spans:     # a block of k steps: the live tokens grow each step
        for j in range(int(s.stats["steps"])):
            full = s.stats["kv_rows_full"] + s.stats["active"] * (j + 1)
            for kind, heads in zip(model["layer_types"],
                                   model["num_attention_heads_per_layer"]):
                rows = (s.stats["kv_rows_window"]
                        if kind == "sliding_attention" else full)
                need = kernel_cost_gqa.paged_decode_gqa(
                    rows, heads, model["n_kv_head"], model["head_dim"])
                floor += need.floor_s(peaks)
                total = total + need
    say(f"{params['kernel']}: {total.flops / total.bytes:.1f} operations a "
        f"byte, bound by {total.bound(peaks)}; {100 * floor / secs:.2f}% of "
        "its roofline")
    return 100.0 * floor / secs
