"""The latent-attention decode kernel's share of its roofline, percent,
beside ``prog_roofline``: the least time the chip could take for the
``paged_decode_mla`` calls the trace shows (``lib/kernel_cost_mla``:
operations over the bf16 peak or bytes over the HBM peak, whichever is
larger, from the ``live_kv_tokens`` of the program's ``serve.decode`` spans,
one call a latent layer a step) over the self time of those calls. Says on a
``[bench]`` line which peak bounds the kernel. A program without the kernel
(the parent of the PR that brought it) gives nothing to read."""

from ..lib import kernel_cost_mla, program_trace
from ..lib.device import say
from ..lib.peaks import device_peaks
from .prog_roofline import _time_and_calls


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or pt.reduced is None:
        return None
    secs, calls = _time_and_calls(pt, "^" + params["kernel"])
    spans = pt.named("serve.decode")
    model = ctx.model
    if not calls or not secs or not spans or "kv_lora_rank" not in model:
        return None
    peaks = device_peaks(ctx.device_kind)
    layers = ctx.count("cache_layers")(model)
    floor, total = 0.0, kernel_cost_mla.Cost(0.0, 0.0)
    for s in spans:     # a block of k steps: the live tokens grow each step
        for j in range(int(s.stats["steps"])):
            live = s.stats["live_kv_tokens"] + s.stats["active"] * (j + 1)
            need = kernel_cost_mla.paged_decode_mla(
                live, model["n_head"], model["kv_lora_rank"],
                model["qk_rope_head_dim"])
            floor += layers * need.floor_s(peaks)
            total = total + need
    say(f"{params['kernel']}: {total.flops / total.bytes:.1f} operations a "
        f"byte, bound by {total.bound(peaks)}; {100 * floor / secs:.2f}% of "
        "its roofline")
    return 100.0 * floor / secs
