"""Collective-communication facade.

Capability parity with the reference's ``deepspeed/comm/comm.py:224-662`` (module-level
``all_reduce``/``all_gather``/``reduce_scatter``/``all_to_all_single``/``send``/``recv``
wrappers, each instrumented by ``timed_op`` at ``comm/comm.py:112``) and
``comm/backend.py:21`` / ``comm/torch.py:11`` (backend objects).

TPU-native design: there are no eager NCCL calls. Collectives are ``jax.lax``
primitives traced inside ``jit``/``shard_map`` over named mesh axes; XLA schedules
them on ICI/DCN. This facade exists for the same two reasons the reference kept one:

1. a single choke point every collective goes through, so byte/op accounting
   (the reference's ``CommsLogger``, ``utils/comms_logging.py:56``) works uniformly;
2. symmetric naming so code reads like the reference (``comm.all_reduce(x, axis)``).

Accounting happens at *trace time*: inside ``jit`` a collective executes once per
trace, so counts are per-compiled-program. ``CommsLogger.scale`` lets callers fold
in the number of executions if they want totals.

``init_distributed`` maps to ``jax.distributed.initialize`` (multi-host rendezvous —
the analog of the reference's ``init_distributed`` env/MPI discovery at
``comm/comm.py:599-790``).
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.logging import log_dist, logger

AxisName = Union[str, Sequence[str]]


# --------------------------------------------------------------------------- logger
@dataclass
class _OpRecord:
    count: int = 0
    bytes: int = 0       # logical bytes (full-precision payload)
    wire_bytes: int = 0  # bytes actually on the wire (== bytes unless quantized)


@dataclass
class CommsLogger:
    """Per-op count/byte accounting. Parity: ``utils/comms_logging.py:56``.

    Quantized collectives (``comm/quantized.py``) record both the logical
    payload and the compressed wire bytes, so the summary shows the per-op
    compression ratio next to the counts."""

    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    prof_ops: list = field(default_factory=list)
    records: Dict[str, _OpRecord] = field(default_factory=dict)

    def record(self, op_name: str, nbytes: int,
               wire_bytes: Optional[int] = None) -> None:
        if not self.enabled:
            return
        if not self.prof_all and self.prof_ops and not any(
                op_name.startswith(p) for p in self.prof_ops):
            return  # prof_ops filter (parity: comms config prof_all/prof_ops)
        rec = self.records.setdefault(op_name, _OpRecord())
        rec.count += 1
        rec.bytes += int(nbytes)
        rec.wire_bytes += int(wire_bytes if wire_bytes is not None else nbytes)
        if self.verbose:
            wire = (f" wire {wire_bytes}" if wire_bytes is not None
                    and wire_bytes != nbytes else "")
            logger.info(f"comm: {op_name} {nbytes} bytes{wire} (trace-time)")

    def log_summary(self, scale: int = 1) -> str:
        """Per-op summary. ``scale``: number of executions of the compiled
        program(s) — trace-time counts times ``scale`` estimate the RUN totals
        (closes the per-compiled-program footgun: pass the engine's step count,
        or use ``engine.comms_summary()`` which does)."""
        hdr = ("comm op summary (trace-time counts"
               + (f" x {scale} executions)" if scale != 1 else ")") + ":")
        lines = [hdr]
        for name, rec in sorted(self.records.items()):
            line = (f"  {name:<24} count={rec.count * scale:<8} "
                    f"bytes={rec.bytes * scale}")
            if rec.wire_bytes != rec.bytes:
                ratio = rec.bytes / max(1, rec.wire_bytes)
                line += f" wire={rec.wire_bytes * scale} ({ratio:.2f}x)"
            lines.append(line)
        out = "\n".join(lines)
        log_dist(out)
        return out

    def reset(self) -> None:
        self.records.clear()


comms_logger = CommsLogger()


def configure(enabled: bool = True, verbose: bool = False,
              prof_all: bool = True, prof_ops: Optional[Sequence[str]] = None
              ) -> None:
    comms_logger.enabled = enabled
    comms_logger.verbose = verbose
    comms_logger.prof_all = prof_all
    comms_logger.prof_ops = list(prof_ops or [])


def _nbytes(x: Any) -> int:
    try:
        leaves = jax.tree_util.tree_leaves(x)
        return sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)
    except Exception:
        return 0


# --------------------------------------------------------------------------- init
_initialized = False


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    **kwargs,
) -> None:
    """Multi-host rendezvous. Parity: ``comm/comm.py:599`` (init_distributed).

    Single-process (the common TPU-VM and test case) is a no-op: JAX is already
    initialized. Multi-host: forwards to ``jax.distributed.initialize`` which
    discovers peers via the coordinator (env-based auto-discovery on TPU pods).
    """
    global _initialized
    if _initialized:
        return
    num_processes = num_processes or int(os.environ.get("WORLD_SIZE", "1"))
    if num_processes > 1 and jax.process_count() == 1:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id if process_id is not None else int(os.environ.get("RANK", "0")),
            **kwargs,
        )
    _initialized = True


def is_initialized() -> bool:
    return _initialized


def get_world_size() -> int:
    """Process-level world size (pairs with :func:`get_rank`). For the device-level
    extent use :func:`get_device_count` or the mesh."""
    return jax.process_count()


def get_rank() -> int:
    return jax.process_index()


def get_device_count() -> int:
    return jax.device_count()


def get_local_rank() -> int:
    return 0


# --------------------------------------------------------------------------- collectives
# All of these are *traced* collectives: valid inside jit/shard_map with the given
# mesh axis name(s) bound. Outside a trace they raise, exactly like torch.distributed
# ops raise without an initialized process group.

def all_reduce(x, axis_name: AxisName, op: str = "sum"):
    """Parity: ``comm/comm.py:494`` (all_reduce). sum/max/min/mean over a mesh axis."""
    comms_logger.record(f"all_reduce[{axis_name}]", _nbytes(x))
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unsupported reduce op {op!r}")


def all_gather(x, axis_name: AxisName, axis: int = 0, tiled: bool = True):
    """Parity: ``comm/comm.py:284`` (all_gather) / ``all_gather_base``.

    ``tiled=True`` concatenates along ``axis`` (the flat-bucket style the reference's
    ``_all_gather_base`` uses); ``tiled=False`` stacks a new leading axis.
    """
    comms_logger.record(f"all_gather[{axis_name}]", _nbytes(x))
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: AxisName, axis: int = 0):
    """Parity: ``comm/comm.py:351`` (reduce_scatter_base). psum_scatter over a mesh axis."""
    comms_logger.record(f"reduce_scatter[{axis_name}]", _nbytes(x))
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def all_to_all(x, axis_name: AxisName, split_axis: int = 0, concat_axis: int = 0):
    """Parity: ``comm/comm.py:378`` (all_to_all_single). The MoE dispatch primitive."""
    comms_logger.record(f"all_to_all[{axis_name}]", _nbytes(x))
    return lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def broadcast(x, axis_name: AxisName, src_index: int = 0):
    """Parity: ``comm/comm.py:224`` (broadcast). Everyone takes src's value."""
    comms_logger.record(f"broadcast[{axis_name}]", _nbytes(x))
    # select src's shard on every member of the axis
    full = lax.all_gather(x, axis_name, axis=0, tiled=False)
    return jax.tree_util.tree_map(lambda f: f[src_index], full)


def reduce(x, axis_name: AxisName, dst_index: int = 0, op: str = "sum"):
    """Parity: ``comm/comm.py`` (reduce): the reduction lands on ``dst``;
    other ranks get zeros. SPMD form: full psum masked by axis index."""
    full = all_reduce(x, axis_name, op=op)
    on_dst = lax.axis_index(axis_name) == dst_index
    return jax.tree_util.tree_map(
        lambda f: jnp.where(on_dst, f, jnp.zeros_like(f)), full)


def gather(x, axis_name: AxisName, dst_index: int = 0, axis: int = 0):
    """Parity: ``comm/comm.py`` (gather): dst holds the concatenation; other
    ranks get zeros of the gathered shape. Pytrees supported like the other
    collectives."""
    full = all_gather(x, axis_name, axis=axis, tiled=True)
    on_dst = lax.axis_index(axis_name) == dst_index
    return jax.tree_util.tree_map(
        lambda f: jnp.where(on_dst, f, jnp.zeros_like(f)), full)


def scatter(x, axis_name: AxisName, src_index: int = 0, axis: int = 0):
    """Parity: ``comm/comm.py`` (scatter): each rank takes its chunk of
    src's array along ``axis``. Pytrees supported."""
    comms_logger.record(f"scatter[{axis_name}]", _nbytes(x))
    src = broadcast(x, axis_name, src_index)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    return jax.tree_util.tree_map(
        lambda s: lax.dynamic_slice_in_dim(
            s, idx * (s.shape[axis] // n), s.shape[axis] // n, axis=axis),
        src)


def ppermute(x, axis_name: AxisName, perm):
    """Point-to-point send/recv ring. Parity: ``comm/comm.py:430-470`` (send/recv) and
    the pipeline's p2p exchange (``runtime/pipe/p2p.py:48``): on TPU, neighbor
    exchange is ``lax.ppermute`` riding ICI."""
    comms_logger.record(f"ppermute[{axis_name}]", _nbytes(x))
    return lax.ppermute(x, axis_name, perm=perm)


def send_recv_next(x, axis_name: AxisName, axis_size: int):
    """Shift +1 along a ring (pipeline forward direction)."""
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    return ppermute(x, axis_name, perm)


def send_recv_prev(x, axis_name: AxisName, axis_size: int):
    """Shift -1 along a ring (pipeline backward direction)."""
    perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]
    return ppermute(x, axis_name, perm)


def axis_index(axis_name: AxisName):
    return lax.axis_index(axis_name)


def axis_size(axis_name: AxisName):
    return lax.axis_size(axis_name)


# --------------------------------------------------------------------------- host-side
def barrier(name: str = "barrier") -> None:
    """Host-level barrier across processes. Parity: ``comm/comm.py:472`` (barrier).

    Single-process: no-op. Multi-host: sync_global_devices.
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def monitored_barrier(name: str = "monitored_barrier",
                      timeout_s: float = 300.0) -> float:
    """Parity: ``comm/comm.py`` (monitored_barrier): a barrier that reports
    how long the slowest participant made everyone wait; the debugging tool
    for straggling hosts. Returns the wait in seconds."""
    t0 = time.perf_counter()
    barrier(name)
    dt = time.perf_counter() - t0
    if dt > timeout_s:
        logger.warning(f"monitored_barrier '{name}': waited {dt:.1f}s "
                       f"(> timeout {timeout_s:.0f}s)")
    elif dt > 1.0:
        log_dist(f"monitored_barrier '{name}': waited {dt:.1f}s")
    return dt


@contextmanager
def timed(name: str):
    """Wall-clock timing of a dispatch+sync region (the ``timed_op`` analog for
    host-visible timing; device-side overlap is XLA's job)."""
    t0 = time.perf_counter()
    yield
    jax.effects_barrier()
    dt = time.perf_counter() - t0
    if comms_logger.enabled:
        logger.info(f"comm timed region {name}: {dt*1e3:.3f} ms")
