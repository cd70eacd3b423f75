"""The device the run is on, the compile counter and the compile cache."""

from __future__ import annotations

import os

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT, _CACHE_MISS = ("/jax/compilation_cache/cache_hits",
                           "/jax/compilation_cache/cache_misses")


class NoChip(RuntimeError):
    pass


class CompileLog:
    """Per-program backend compile seconds and persistent-cache hits/misses,
    from jax.monitoring (a cache hit still reports its retrieval time). A copy
    of ``chip_smoke.CompileLog``: the yardstick does not import the smoke."""

    def __init__(self):
        import jax

        self.programs, self.hits, self.misses = [], 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == _COMPILE_EVENT:
            self.programs.append((str(kw.get("fun_name", "?")), float(secs)))

    def _event(self, event, **kw):
        self.hits += event == _CACHE_HIT
        self.misses += event == _CACHE_MISS

    def mark(self):
        return len(self.programs), self.hits, self.misses

    def since(self, mark) -> dict:
        n0, h0, m0 = mark
        progs = self.programs[n0:]
        return {"programs": len(progs),
                "seconds": sum(s for _, s in progs),
                "cache_hits": self.hits - h0,
                "cache_misses": self.misses - m0,
                "names": [n for n, _ in progs]}


def place_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where it is set, else a fixed directory in
    the checkout; every program is cached, not only the slow ones. Goes through
    the program's own ``place_compile_cache`` so the two agree."""
    from deepspeed_tpu.utils.compile_cache import place_compile_cache as place

    return place()


def require_devices(chips: int, rehearsal: bool):
    """The devices this cell runs on. A listed cell needs a TPU with at least
    ``chips`` chips; a rehearsal cell runs on whatever JAX has."""
    import jax

    devices = jax.devices()
    if rehearsal:
        if len(devices) < chips:
            raise NoChip(f"rehearsal needs {chips} devices, JAX has "
                         f"{len(devices)} (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={chips})")
        return devices[:chips]
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r}); "
                     "a listed cell is measured on the chip only")
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX has {len(devices)}")
    return devices[:chips]


def describe(devices, memory_peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak_bytes)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it. On
    this runtime the figure leaves out a program's temporaries (PERF.md,
    PR 21): it is what stays resident, weights, state and pages."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def pin_environment() -> None:
    """Before jax is imported: libtpu logs under /tmp/tpu_logs by default, a
    fixed path outside the checkout; turn that off."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
