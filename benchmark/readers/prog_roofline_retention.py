"""The power-retention decode kernel's share of its roofline, percent, beside
``prog_roofline_kda``: the least time the chip could take for the
``retention_decode`` calls the trace shows (``lib/kernel_cost_retention``: the
live slots' states and normalisers read and written, at the symmetric map's
width, plus the step's inputs, over the HBM peak; the operations are 150 times
under the ridge) over the self time of those calls. The live slots come from
the program's ``serve.decode`` spans: ``state_slots`` slots a step, ``steps``
steps a dispatch, one call a layer a step. Says on a ``[bench]`` line which
peak bounds the kernel. A program without the kernel or the counts (the
parent of the PR that brought them) gives nothing to read."""

from ..lib import kernel_cost_retention, program_trace
from ..lib.device import say
from ..lib.peaks import device_peaks
from .prog_roofline import _time_and_calls


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or pt.reduced is None:
        return None
    secs, calls = _time_and_calls(pt, "^" + params["kernel"])
    spans = [s for s in pt.named("serve.decode") if "state_slots" in s.stats]
    model = ctx.model
    if not calls or not secs or not spans or "retention_degree" not in model:
        return None
    peaks = device_peaks(ctx.device_kind)
    slot_steps = sum(s.stats["state_slots"] * s.stats["steps"] for s in spans)
    need = kernel_cost_retention.retention_decode(
        model["n_layer"] * slot_steps, model["n_kv_head"],
        model["n_head"] // model["n_kv_head"], model["head_dim"])
    floor = need.floor_s(peaks)
    say(f"{params['kernel']}: {need.flops / need.bytes:.2f} operations a "
        f"byte, bound by {need.bound(peaks)}; {need.bytes / 1e9:.2f} GB over "
        f"{calls:g} calls; {100 * floor / secs:.2f}% of its roofline")
    return 100.0 * floor / secs
