"""Accelerator discovery & selection.

Parity with the reference's ``accelerator/real_accelerator.py:37,55``
(``get_accelerator()`` / ``set_accelerator()``): a process-global accelerator object
picked automatically (TPU if present, else CPU) or forced via the
``DS_TPU_ACCELERATOR`` environment variable (values: ``tpu`` | ``cpu``).
"""

from __future__ import annotations

import os
from typing import Optional

from .abstract_accelerator import Accelerator

_accelerator: Optional[Accelerator] = None


class _JaxAccelerator(Accelerator):
    """Concrete accelerator backed by the active JAX backend."""

    def __init__(self, platform: str):
        self._platform = platform
        self._name = platform

    def platform(self) -> str:
        return self._platform

    def is_available(self) -> bool:
        import jax

        try:
            return len(jax.devices(self._platform)) > 0
        except RuntimeError:
            return False

    def devices(self):
        import jax

        return jax.local_devices()

    def device_count(self) -> int:
        import jax

        return jax.local_device_count()

    def global_device_count(self) -> int:
        import jax

        return jax.device_count()

    def process_index(self) -> int:
        import jax

        return jax.process_index()

    def process_count(self) -> int:
        import jax

        return jax.process_count()

    def memory_stats(self) -> dict:
        d = self.current_device()
        try:
            return dict(d.memory_stats() or {})
        except Exception:
            return {}

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16 if self._platform != "cpu" else jnp.float32


class TPUAccelerator(_JaxAccelerator):
    def __init__(self):
        super().__init__("tpu")


class CPUAccelerator(_JaxAccelerator):
    def __init__(self):
        super().__init__("cpu")


def _detect() -> Accelerator:
    forced = os.environ.get("DS_TPU_ACCELERATOR", "").lower()
    if forced == "cpu":
        return CPUAccelerator()
    if forced == "tpu":
        return TPUAccelerator()
    import jax

    # NOTE: "cpu" is also what JAX reports when libtpu fails to initialize.
    # Right for tests; entry points that must run on the chip (chip_smoke.py,
    # benchmark/run.py) check jax.devices()[0].platform themselves and refuse.
    platform = jax.default_backend()
    if platform == "cpu":
        return CPUAccelerator()
    return _JaxAccelerator(platform)


def get_accelerator() -> Accelerator:
    global _accelerator
    if _accelerator is None:
        _accelerator = _detect()
    return _accelerator


def set_accelerator(acc: Accelerator) -> None:
    global _accelerator
    _accelerator = acc
