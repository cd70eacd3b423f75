"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The one table every utilization or roofline number of the program divides by
(``runtime/aot.py``). A device that is not listed raises: a utilization computed
against a guessed peak is not a measurement.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float


# Source: Google Cloud TPU documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16, 819 GB/s HBM2e per chip).
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(bf16_flops=197e12, hbm_bytes_per_s=819e9),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add the chip to "
            "deepspeed_tpu/accelerator/peaks.py with its source") from None
