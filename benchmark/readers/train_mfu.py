"""Model FLOP/s utilization: tokens per second per chip times the operations
a token needs forward and backward (the configuration's
``train_flops_per_token``: its reference's where that has one, else
``lib/flops``'s; recomputation not counted), over the chip's bf16 peak.
Percent."""

from ..lib.peaks import device_peaks


def read(ctx, params):
    tok_s_chip = ctx.window.rate("tokens") / ctx.chips
    need = ctx.count("train_flops_per_token")(
        ctx.model, int(ctx.facts["seq_len"]))
    return 100.0 * tok_s_chip * need / device_peaks(ctx.device_kind).bf16_flops
