"""The latent decode kernel's share of its roofline where a model's latent
layers are of two kinds, percent, beside ``prog_roofline_mla``: the least time
the chip could take for the rows the mathematics asks a kind's decode steps to
attend over (``lib/kernel_cost_mla_select.attended_rows``: operations over the
bf16 peak or bytes over the HBM peak, whichever is larger) over the self time
of the calls of ``kernel`` the trace shows. ``kind`` names the configuration's
group of that kind's geometry (``full`` or ``sliding``); ``rows`` the stat of
the program's ``serve.decode`` spans that counts the rows:
``selected_rows`` (``trace.SELECT_STATS``: summed over slots, full layers and
the dispatch's steps) or ``kv_rows_window`` (a window layer's rows of one
step: times the steps and the window layers here). Says on a ``[bench]`` line
which peak bounds the kernel. A program without the kernel's name or the stat
(the parent of the PR that brought them) gives nothing to read."""

from ..lib import kernel_cost_mla_select, program_trace
from ..lib.device import say
from ..lib.peaks import device_peaks
from .prog_roofline import _time_and_calls


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or pt.reduced is None:
        return None
    secs, calls = _time_and_calls(pt, "^" + params["kernel"])
    spans = [s for s in pt.named("serve.decode") if params["rows"] in s.stats]
    group = ctx.model.get(params["kind"])
    if not calls or not secs or not spans or not isinstance(group, dict):
        return None
    rows = sum(float(s.stats[params["rows"]]) for s in spans)
    if params["rows"] == "kv_rows_window":  # a step's, a layer's
        layers = sum(t == "sliding_attention"
                     for t in ctx.model["layer_types"])
        rows = layers * sum(float(s.stats[params["rows"]])
                            * float(s.stats["steps"]) for s in spans)
    need = kernel_cost_mla_select.attended_rows(
        rows, group["n_head"], group["kv_lora_rank"],
        group["qk_rope_head_dim"])
    peaks = device_peaks(ctx.device_kind)
    floor = need.floor_s(peaks)
    say(f"{params['kernel']}: {need.flops / need.bytes:.1f} operations a "
        f"byte, bound by {need.bound(peaks)}; {100 * floor / secs:.2f}% of "
        "its roofline")
    return 100.0 * floor / secs
