"""Streamed host<->HBM offload: double-buffered DMA pipelined against the
layer scan.

The ZeRO-Infinity result (PAPERS.md) is that host/NVMe offload is near-free
once transfers overlap compute. ``runtime/zero/infinity.py`` already streams
layer units through HBM, but fetch-on-demand exposes every host->HBM DMA on
the critical path. This module is the streaming engine that hides it:

- :class:`UnitFetchStream` — the software-pipelined fetch queue. It runs the
  same prologue/steady/epilogue schedule PR 4's ``zero3_layer_scan`` traces
  into its scan carry (:func:`~deepspeed_tpu.runtime.zero.gather
  .prefetch_schedule`), with ``jax.device_put``'s async dispatch as the
  hidden latency instead of a ``qall_gather``: consuming unit ``i`` first
  *issues* unit ``i+d``'s fetch, then blocks (watchdog-bracketed, chaos-
  injectable) only on unit ``i``, which has had ``d`` units of compute time
  to land. Consume order is unchanged, so streamed numerics are bitwise-
  identical to fetch-on-demand.
- :class:`PinnedHostStage` — pinned host staging for the push path. On
  runtimes whose device API exposes the ``pinned_host`` memory space, push
  buffers are parked there so the HBM copy is a true zero-copy DMA;
  elsewhere (the CPU backend) it degrades to plain ``device_put`` from the
  persistent numpy staging arrays (probed once per backend).
- :func:`quantized_push` — the host side of the quantized fetch path: block-
  int8/int4 quantize on host (``comm/quantized.np_quantize_blockwise``),
  DMA the int payload + per-block scales, dequantize on device in a cached
  jitted program. Every push records logical-vs-wire bytes in the
  :data:`~deepspeed_tpu.comm.runtime_accounting.wire_ledger`
  (op ``qpush[host-dma]``), so the host DMA ratio renders in
  ``engine.comms_summary()`` next to the collective wire.
- :func:`flush_host_shards` / :func:`load_host_shards` — the PR 3 commit
  protocol extended to host-side master/optimizer state: the flush writes
  per-unit ``shard_<k>.npz`` files (each atomic, ``fault_point
  ("host-shard", k)`` between them) under the tag directory, so the
  manifest/COMMIT machinery covers them and a SIGKILL mid-flush leaves the
  previous committed tag loadable, never torn host state.

Watchdog phases: every blocking host<->HBM wait is bracketed as
``offload_fetch`` and the host optimizer pass / shard flush as
``offload_flush`` (:data:`~deepspeed_tpu.resilience.watchdog
.OFFLOAD_PHASES`), so a wedged DMA is named precisely in the stall report.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

import jax

from ...comm.runtime_accounting import HostDmaStats, wire_ledger
from ...resilience.chaos import fault_point, offload_fetch_fault
from ...utils.logging import logger
from .gather import prefetch_schedule

HOST_STATE_DIRNAME = "host_state"
_HOST_META = "host_meta.json"

# process-wide blocking-wait counter: the chaos stall_offload_at index
_fetch_wait_index = 0


def _next_wait_index() -> int:
    global _fetch_wait_index
    i = _fetch_wait_index
    _fetch_wait_index += 1
    return i


def fetch_fault_point() -> None:
    """The chaos hook for ONE blocking host<->HBM wait: advances the
    process-wide wait index and fires an armed ``stall_offload_at`` plan.
    Every blocking DMA wait — unit-fetch takes, gradient drains, the
    optimizer-offload grad fetch — calls this inside its ``offload_fetch``
    watchdog bracket, so the documented index counts them all."""
    offload_fetch_fault(_next_wait_index())


# --------------------------------------------------------------- pinned stage
# pinned_host support is a RUNTIME capability: probed once per backend name
# (never keyed on mesh identity — an id() key could hand a recycled address
# a stale probe result), and the sharding is built fresh per mesh
_PINNED_SUPPORTED: Dict[str, bool] = {}


def pinned_sharding_for(mesh):
    """A replicated ``pinned_host`` sharding for ``mesh``, or None when the
    runtime rejects the memory kind (CPU backend). The probe
    runs ONCE per backend — the fallback must not pay a failed probe per
    push."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    backend = jax.default_backend()
    if backend not in _PINNED_SUPPORTED:
        try:
            cand = NamedSharding(mesh, P(), memory_kind="pinned_host")
            probe = jax.device_put(np.zeros((2,), np.float32), cand)
            jax.block_until_ready(probe)
            _PINNED_SUPPORTED[backend] = True
        except Exception as e:  # noqa: BLE001 — any rejection = no pinning
            logger.info(f"offload stream: pinned_host staging unavailable "
                        f"({type(e).__name__}); plain device_put fallback")
            _PINNED_SUPPORTED[backend] = False
    if not _PINNED_SUPPORTED[backend]:
        return None
    return NamedSharding(mesh, P(), memory_kind="pinned_host")


class PinnedHostStage:
    """Host staging for the push path: pinned when the runtime supports it.

    ``put(arr, device_sharding)`` stages ``arr`` (a persistent numpy push
    buffer) and issues the async host->HBM copy. With pinned memory the
    array transits ``pinned_host`` space so the device copy is a DMA from
    pinned pages; without it this is a plain ``device_put`` from numpy —
    same values either way.
    """

    def __init__(self, mesh):
        self._pinned = pinned_sharding_for(mesh)

    @property
    def pinned(self) -> bool:
        return self._pinned is not None

    def put(self, arr: np.ndarray, device_sharding):
        if self._pinned is not None:
            staged = jax.device_put(arr, self._pinned)
            return jax.device_put(staged, device_sharding)
        return jax.device_put(arr, device_sharding)


# ------------------------------------------------------------- fetch pipeline
class UnitFetchStream:
    """Software-pipelined host->HBM unit fetcher.

    ``fetch_fn(name)`` must *issue* the (async) transfer for one unit and
    return the device tree; :meth:`take` blocks — watchdog-bracketed as
    ``offload_fetch`` and chaos-injectable — only on the consumed unit.
    ``depth == 0`` is fetch-on-demand (the inline baseline: issue at the
    consume point, wait immediately).

    Driven by :func:`~deepspeed_tpu.runtime.zero.gather.prefetch_schedule`,
    the same prologue/steady/epilogue skeleton the device-wire pipelined
    gather scan traces into its carry; because consume order never changes,
    a streamed run is value-identical to an inline one.
    """

    def __init__(self, fetch_fn: Callable[[str], Any], order: Iterable[str],
                 depth: int, stats: Optional[HostDmaStats] = None,
                 watch: Optional[Callable[[str], Any]] = None):
        self._fetch = fetch_fn
        self.order: List[str] = list(order)
        self.depth = max(0, int(depth))
        self.stats = stats
        self._watch = watch or (lambda name: contextlib.nullcontext())
        self._staged: Dict[str, Any] = {}
        self._events = prefetch_schedule(len(self.order), self.depth)
        self._consumed = 0
        self._primed = False

    def prime(self) -> None:
        """Issue the prologue's ``depth`` fetches now, ahead of the first
        :meth:`take` — lets the transfers stream in under whatever compute
        runs before the first consume (e.g. the cached tail layers of the
        backward pass). Idempotent; a no-op at depth 0."""
        if self._primed:
            return
        self._primed = True
        for _ in range(min(self.depth, len(self.order))):
            kind, idx = next(self._events)
            assert kind == "issue", kind
            self._issue(idx)

    def _issue(self, idx: int) -> None:
        t0 = time.perf_counter()
        self._staged[self.order[idx]] = self._fetch(self.order[idx])
        if self.stats is not None:
            self.stats.issue_s += time.perf_counter() - t0

    def take(self, name: str) -> Any:
        """Consume ``name`` (must follow the declared order): runs the
        schedule's issues up to this consume point (for depth ``d``, unit
        ``i+d``'s fetch goes out before unit ``i``'s wait), then blocks on
        ``name``'s transfer."""
        if self._consumed >= len(self.order) \
                or self.order[self._consumed] != name:
            expect = (self.order[self._consumed]
                      if self._consumed < len(self.order) else "<drained>")
            raise ValueError(
                f"UnitFetchStream: out-of-order take({name!r}); the schedule "
                f"expects {expect!r} next")
        self._primed = True  # a late prime() must not eat steady-state events
        for kind, idx in self._events:
            if kind == "issue":
                self._issue(idx)
            else:
                assert idx == self._consumed, (idx, self._consumed)
                break
        self._consumed += 1
        tree = self._staged.pop(name)
        with self._watch("offload_fetch"):
            fetch_fault_point()
            t0 = time.perf_counter()
            jax.block_until_ready(tree)
            wait = time.perf_counter() - t0
        if self.stats is not None:
            self.stats.record_wait(wait)
        return tree


# ---------------------------------------------------------- quantized pushes
@functools.lru_cache(maxsize=None)
def _dequant_jit(bits: int, orig_size: int, dtype_name: str):
    """One jitted device-side dequantizer per (bits, trailing size, dtype);
    the jit cache handles the remaining shape variation (layer units are
    shape-identical, so this stays a handful of programs)."""
    import jax.numpy as jnp

    from ...comm.quantized import dequantize_blockwise

    dt = jnp.dtype(dtype_name)

    def deq(q, s, z):
        return dequantize_blockwise(q, s, z, bits=bits,
                                    orig_size=orig_size).astype(dt)

    return jax.jit(deq)


def quantized_push(arr: np.ndarray, stage: PinnedHostStage, device_sharding,
                   bits: int, block_size: int, compute_dtype,
                   stats: Optional[HostDmaStats] = None,
                   op_name: str = "qpush[host-dma]"):
    """Push one host leaf over the quantized host->HBM wire.

    Host-quantizes ``arr`` (fp32 numpy) into a block-int payload + per-block
    scales, DMAs those, and returns the device-side dequantized array in
    ``compute_dtype``. Rows too short to shrink ship full precision in the
    compute dtype (the same veto ``quantized_reshard`` applies). Records
    logical-vs-wire bytes in the wire ledger so the host-DMA compression
    ratio is observable per step.
    """
    import jax.numpy as jnp

    from ...comm.quantized import np_quantize_blockwise, quantization_shrinks

    cd = jnp.dtype(compute_dtype)
    logical = arr.size * cd.itemsize
    if arr.ndim == 0 or not quantization_shrinks(
            arr.shape[-1], bits, block_size, cd.itemsize):
        if stats is not None:
            stats.record_push(logical, logical)
        return stage.put(np.ascontiguousarray(arr).astype(cd),
                         device_sharding)
    q, s, z = np_quantize_blockwise(np.asarray(arr, np.float32), bits=bits,
                                    block_size=block_size)
    wire = q.nbytes + s.nbytes + z.nbytes
    wire_ledger.record(op_name, logical, wire)
    if stats is not None:
        stats.record_push(logical, wire)
    qd = stage.put(q, device_sharding)
    sd = stage.put(s, device_sharding)
    zd = stage.put(z, device_sharding)
    return _dequant_jit(bits, int(arr.shape[-1]), cd.name)(qd, sd, zd)


# --------------------------------------------------- crash-consistent flush
def flush_host_shards(dir_path: str,
                      shards: Iterable[Tuple[str, Dict[str, np.ndarray]]],
                      meta: Optional[Dict[str, Any]] = None,
                      writer=None) -> None:
    """Write host master/optimizer state as per-shard ``.npz`` files under
    ``dir_path`` (inside a checkpoint tag directory).

    Each shard is written atomically (tmp + ``os.replace`` via
    :class:`~deepspeed_tpu.resilience.retry.RetryingWriter`), with
    ``fault_point("host-shard", k)`` fired after shard ``k`` lands — the
    chaos hook that proves a SIGKILL mid-flush cannot tear a committed tag:
    the enclosing save only writes MANIFEST/COMMIT after every shard is on
    disk, so a mid-flush kill leaves an uncommitted tag the loader rejects
    in favor of the newest committed one.
    """
    from ...resilience.retry import RetryingWriter

    writer = writer or RetryingWriter()
    os.makedirs(dir_path, exist_ok=True)
    names = []
    for k, (shard_name, arrays) in enumerate(shards):
        fname = f"shard_{k:05d}.npz"
        writer.atomic_write(
            os.path.join(dir_path, fname),
            lambda f, arrs=arrays: np.savez(f, **arrs),
            fsync=False,  # the commit protocol's durability pass fsyncs
            describe=f"host shard {shard_name}")
        names.append({"file": fname, "name": shard_name,
                      "keys": sorted(arrays)})
        fault_point("host-shard", index=k)
    meta_doc = {"format_version": 1, "shards": names, **(meta or {})}
    writer.atomic_write(
        os.path.join(dir_path, _HOST_META),
        lambda f: f.write(json.dumps(meta_doc, indent=1).encode()),
        fsync=False, describe="host shard meta")


def load_host_shards(dir_path: str) -> Tuple[Dict[str, np.ndarray], Dict]:
    """Merge the per-shard files back into one flat state dict. The commit
    manifest already verified bytes/checksums; this only re-assembles."""
    with open(os.path.join(dir_path, _HOST_META)) as f:
        meta = json.load(f)
    out: Dict[str, np.ndarray] = {}
    for shard in meta["shards"]:
        with np.load(os.path.join(dir_path, shard["file"])) as d:
            for key in d.files:
                out[key] = d[key]
    return out, meta


__all__ = ["UnitFetchStream", "PinnedHostStage", "HostDmaStats",
           "quantized_push", "flush_host_shards", "load_host_shards",
           "pinned_sharding_for", "fetch_fault_point", "HOST_STATE_DIRNAME"]
