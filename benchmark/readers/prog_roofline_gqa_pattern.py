"""The grouped-query decode kernel's share of its roofline, percent, for a
model whose attention layers lie where a pattern says (``hybrid_pattern``:
a ``*`` a layer), beside ``prog_roofline_gqa``, which counts a layer for
every entry of ``layer_types``: the least time the chip could take for the
``paged_decode_gqa`` calls the trace shows (``lib/kernel_cost_gqa``:
operations over the bf16 peak or bytes over the HBM peak, whichever is
larger) over the self time of those calls. Every ``*`` layer reads every
live row: ``live_kv_tokens`` of the program's ``serve.decode`` spans and the
tokens a block's earlier steps appended, a row 4 bytes a number where the
configuration keeps keys and values in float32 (``attention_float32``), 2
elsewhere. Says on a ``[bench]`` line which peak bounds the kernel. A program
without the kernel or the counts gives nothing to read."""

from ..lib import kernel_cost_gqa, program_trace
from ..lib.device import say
from ..lib.peaks import device_peaks
from .prog_roofline import _time_and_calls


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or pt.reduced is None:
        return None
    secs, calls = _time_and_calls(pt, "^" + params["kernel"])
    spans = [s for s in pt.named("serve.decode")
             if "live_kv_tokens" in s.stats]
    model = ctx.model
    layers = model.get("hybrid_pattern", "").count("*")
    if not calls or not secs or not spans or not layers:
        return None
    peaks = device_peaks(ctx.device_kind)
    rows = sum(s.stats["live_kv_tokens"] + s.stats["active"] * (j + 1)
               for s in spans for j in range(int(s.stats["steps"])))
    need = kernel_cost_gqa.paged_decode_gqa(
        layers * rows, model["n_head"], model["n_kv_head"],
        model["head_dim"], 4 if model.get("attention_float32") else 2)
    floor = need.floor_s(peaks)
    say(f"{params['kernel']}: {need.flops / need.bytes:.1f} operations a "
        f"byte, bound by {need.bound(peaks)}; {need.bytes / 1e9:.2f} GB over "
        f"{calls:g} calls; {100 * floor / secs:.2f}% of its roofline")
    return 100.0 * floor / secs
