"""Collective-communication benchmarks: measured busbw over the device mesh.

Capability parity with the reference's ``benchmarks/communication/run_all.py``
(+ per-op ``all_reduce.py``/``all_gather.py``/``all_to_all.py``/
``broadcast.py``/``pt2pt.py`` and the ``ds_bench`` CLI): sweep message sizes
per collective, report latency, algorithmic bandwidth, and bus bandwidth.

TPU-native: each collective is a ``shard_map``-wrapped ``jax.lax`` primitive
jitted over a one-axis mesh of all local devices, so the measured path is the
exact ICI program XLA emits for training — not a backend shim. Bus-bandwidth
factors are the standard ring-algorithm corrections (NCCL-tests convention):
all_reduce 2(n-1)/n, all_gather/reduce_scatter/all_to_all (n-1)/n,
broadcast/pt2pt 1.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "bench"

OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
       "broadcast", "pt2pt", "qall_gather", "qreduce_scatter")


def _busbw_factor(op: str, n: int) -> float:
    if n <= 1:
        return 1.0
    if op == "all_reduce":
        return 2.0 * (n - 1) / n
    if op in ("all_gather", "reduce_scatter", "all_to_all",
              "qall_gather", "qreduce_scatter"):
        return (n - 1) / n
    return 1.0  # broadcast / pt2pt


def _collective_fn(op: str, mesh: Mesh):
    """Jitted shard_map program for one collective over the bench axis.

    Input is the PER-DEVICE shard [elems]; the global array is [n, elems].
    """
    spec = P(AXIS)

    def ar(x):
        return jax.lax.psum(x, AXIS)

    def ag(x):
        return jax.lax.all_gather(x, AXIS, tiled=True)

    def rs(x):
        return jax.lax.psum_scatter(x, AXIS, tiled=True)

    def a2a(x):
        n = jax.lax.psum(1, AXIS)
        return jax.lax.all_to_all(
            x.reshape(n, -1), AXIS, split_axis=0, concat_axis=0).reshape(-1)

    def bc(x):
        # broadcast rank 0's shard to all (masked psum)
        idx = jax.lax.axis_index(AXIS)
        return jax.lax.psum(jnp.where(idx == 0, x, jnp.zeros_like(x)), AXIS)

    def p2p(x):
        n = jax.lax.psum(1, AXIS)
        perm = [(i, (i + 1) % n) for i in range(n)]
        return jax.lax.ppermute(x, AXIS, perm)

    def qag(x):
        # block-int8 wire (comm/quantized.py): algbw from LOGICAL bytes over
        # measured time, so quantized rows report EFFECTIVE bandwidth — the
        # apples-to-apples comparison against the full-precision row above
        from ..comm.quantized import qall_gather

        return qall_gather(x, AXIS, axis=0, tiled=True)

    def qrs(x):
        from ..comm.quantized import qreduce_scatter

        return qreduce_scatter(x, AXIS, axis=0)

    inner = {"all_reduce": ar, "all_gather": ag, "reduce_scatter": rs,
             "all_to_all": a2a, "broadcast": bc, "pt2pt": p2p,
             "qall_gather": qag, "qreduce_scatter": qrs}[op]

    def body(x):  # shard arrives as [1, elems]; collectives want flat payloads
        return inner(x.reshape(-1))

    # (q)all_gather's result is replicated (every device holds the full
    # payload); everything else hands back a per-device payload on the axis
    out_specs = P(None) if op in ("all_gather", "qall_gather") else P(AXIS)
    fn = shard_map(body, mesh=mesh, in_specs=spec, out_specs=out_specs,
                   check_vma=False)
    return jax.jit(fn)


def _payload(mesh: Mesh, size_bytes: int, dtype) -> jnp.ndarray:
    """The benched payload for a GLOBAL byte size: per-device shard sized and
    128-lane-aligned so timings reflect steady-state transfers, not padding.
    Single source of truth for the bench AND --verify paths — they must time
    the identical payload for est-vs-measured to mean anything."""
    n = mesh.devices.size
    itemsize = jnp.dtype(dtype).itemsize
    elems_per_dev = max(n, size_bytes // itemsize // n)
    elems_per_dev = max(128, (elems_per_dev // 128) * 128)
    return jax.device_put(jnp.ones((n, elems_per_dev), dtype),
                          NamedSharding(mesh, P(AXIS)))


def run_collective_bench(
    op: str,
    sizes_bytes: Sequence[int],
    dtype=jnp.bfloat16,
    trials: int = 20,
    warmups: int = 3,
    devices: Optional[Sequence] = None,
) -> List[Dict]:
    """Measure one collective across message sizes. Sizes are GLOBAL payload
    bytes (the reference's convention); returns one record per size."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), (AXIS,))
    itemsize = jnp.dtype(dtype).itemsize
    fn = _collective_fn(op, mesh)
    out = []
    for size in sizes_bytes:
        x = _payload(mesh, size, dtype)
        elems_per_dev = x.shape[1]
        for _ in range(warmups):
            r = fn(x)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(trials):
            r = fn(x)
        jax.block_until_ready(r)
        dt = (time.perf_counter() - t0) / trials
        nbytes = n * elems_per_dev * itemsize
        algbw = nbytes / dt
        out.append({
            "op": op, "bytes": nbytes, "world": n,
            "latency_us": round(dt * 1e6, 1),
            # 6 decimals: tiny payloads on a loaded host must not round to 0
            "algbw_GBps": round(algbw / 1e9, 6),
            "busbw_GBps": round(algbw * _busbw_factor(op, n) / 1e9, 6),
        })
    return out


def verify_collective(op: str, size_bytes: int, dtype=jnp.bfloat16,
                      trials: int = 5, devices=None) -> Dict:
    """Measured-vs-estimated for one collective (``ds_bench --verify``): the
    wall-clock latency the bench reports vs the device-timeline collective
    time a ``jax.profiler`` trace actually records (see
    ``comm/runtime_accounting.py`` — the runtime analog of the reference's
    ``utils/comms_logging.py:56`` per-op log). On the CPU backend shard_map
    collectives execute as host rendezvous callbacks and leave no device
    thunks — ``measured_ops`` fills in on TPU."""
    from ..comm.runtime_accounting import profile_collectives

    est = run_collective_bench(op, [size_bytes], dtype=dtype, trials=trials,
                               devices=devices)[0]
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), (AXIS,))
    fn = _collective_fn(op, mesh)
    x = _payload(mesh, size_bytes, dtype)
    jax.block_until_ready(fn(x))  # compile outside the trace
    prof = profile_collectives(lambda: [fn(x) for _ in range(trials)],
                               n_devices=n)
    dev_us = sum(st.time_us for st in prof.ops.values())
    counts = {k: st.count for k, st in sorted(prof.ops.items())}
    return {
        "op": op, "bytes": est["bytes"], "world": n, "trials": trials,
        "est_latency_us": est["latency_us"],
        # device collective time per trial per device: the transfer itself,
        # minus dispatch/sync overhead the wall clock includes
        "measured_device_us": round(dev_us / max(1, prof.n_devices)
                                    / max(1, trials), 1),
        "measured_ops": counts,
    }


def run_all(ops: Sequence[str] = OPS, min_bytes: int = 1 << 12,
            max_bytes: int = 1 << 26, dtype=jnp.bfloat16, trials: int = 20,
            devices=None) -> List[Dict]:
    """Sweep every requested collective over power-of-two sizes. Parity:
    ``benchmarks/communication/run_all.py``."""
    sizes = []
    b = min_bytes
    while b <= max_bytes:
        sizes.append(b)
        b *= 4
    results = []
    for op in ops:
        results.extend(run_collective_bench(
            op, sizes, dtype=dtype, trials=trials, devices=devices))
    return results


def main(argv=None) -> int:
    """``ds_bench`` CLI (parity: the reference's ``bin/ds_bench``)."""
    import argparse
    import json

    p = argparse.ArgumentParser("ds_bench")
    p.add_argument("--ops", default="all", help=f"comma list of {OPS} or 'all'")
    p.add_argument("--minsize", type=int, default=1 << 12, help="min global bytes")
    p.add_argument("--maxsize", type=int, default=1 << 26, help="max global bytes")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--trials", type=int, default=20)
    p.add_argument("--json", action="store_true", help="emit one JSON line")
    p.add_argument("--verify", action="store_true",
                   help="profile each op and print measured device-timeline "
                        "collective time vs the wall-clock estimate")
    args = p.parse_args(argv)
    ops = OPS if args.ops == "all" else tuple(args.ops.split(","))
    for op in ops:
        if op not in OPS:
            raise SystemExit(f"unknown op {op!r}; choose from {OPS}")
    if args.verify:
        rows = [verify_collective(op, args.maxsize,
                                  dtype=jnp.dtype(args.dtype),
                                  trials=min(args.trials, 5)) for op in ops]
        if args.json:
            print(json.dumps({"verify": rows}))
        else:
            hdr = (f"{'op':<16}{'bytes':>12}{'est wall(us)':>14}"
                   f"{'measured dev(us)':>18}  collectives")
            print(hdr)
            print("-" * len(hdr))
            for r in rows:
                print(f"{r['op']:<16}{r['bytes']:>12}{r['est_latency_us']:>14}"
                      f"{r['measured_device_us']:>18}  {r['measured_ops']}")
        return 0
    results = run_all(ops, args.minsize, args.maxsize,
                      dtype=jnp.dtype(args.dtype), trials=args.trials)
    if args.json:
        print(json.dumps({"world": results[0]["world"] if results else 0,
                          "results": results}))
    else:
        hdr = f"{'op':<16}{'bytes':>12}{'latency(us)':>14}{'algbw(GB/s)':>14}{'busbw(GB/s)':>14}"
        print(hdr)
        print("-" * len(hdr))
        for r in results:
            print(f"{r['op']:<16}{r['bytes']:>12}{r['latency_us']:>14}"
                  f"{r['algbw_GBps']:>14}{r['busbw_GBps']:>14}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
