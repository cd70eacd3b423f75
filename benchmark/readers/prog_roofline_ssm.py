"""The Mamba-2 decode kernel's share of its roofline, percent, beside
``prog_roofline``: the least time the chip could take for the ``ssm_decode``
calls the trace shows (``lib/kernel_cost_ssm``: the live slots' states and
convolution windows read and written plus the step's inputs, over the HBM
peak; the operations are
500 times under the ridge) over the self time of those calls. The live slots
come from the program's ``serve.decode`` spans: ``state_slots`` slots a step,
``steps`` steps a dispatch, one call a Mamba layer (the ``M`` of the model's
``hybrid_pattern``) a step. Says on a ``[bench]`` line which peak bounds the
kernel. A program without the kernel or the counts (the parent of the PR that
brought them) gives nothing to read."""

from ..lib import kernel_cost_ssm, program_trace
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
    if not calls or not secs or not spans or "hybrid_pattern" not in model:
        return None
    peaks = device_peaks(ctx.device_kind)
    layers = model["hybrid_pattern"].count("M")
    slot_steps = sum(s.stats["state_slots"] * s.stats["steps"] for s in spans)
    need = kernel_cost_ssm.ssm_decode(
        layers * slot_steps, model["mamba_num_heads"],
        model["mamba_head_dim"], model["ssm_state_size"], model["n_groups"],
        model["conv_kernel"] - 1)
    floor = need.floor_s(peaks)
    say(f"{params['kernel']}: {need.flops / need.bytes:.2f} operations a "
        f"byte, bound by {need.bound(peaks)}; {need.bytes / 1e9:.2f} GB over "
        f"{calls:g} calls; {100 * floor / secs:.2f}% of its roofline")
    return 100.0 * floor / secs
