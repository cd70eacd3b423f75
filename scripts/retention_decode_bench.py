"""Times ``ops/pallas/retention_decode`` alone at ``brumby-14b-serve``'s shape
and slot count, beside a kernel that only hands the block back (through
``chiprun``; a TPU only).

    chiprun -- python scripts/retention_decode_bench.py ['{"slots": [40]}']

The stack is (layers, slots, G, D / 2 + 2, D, D) = (2, slots, 8, 66, 128, 128)
(two layers of the cell's five: the kernel takes a layer a call), every slot
live, 5 query heads a key-value head. Prints, a slot count and walk: the
kernel's milliseconds a call, read off a trace of its own (the
``retention_decode`` events of the device's operation line: the XLA
operations around the kernel are in ``jit_ms``, the host's clock around
``reps`` calls, and not in it); us a slot; GB/s and the share of the v5e's 819
GB/s over ``benchmark/lib/kernel_cost_retention.py``'s bytes (the symmetric
map's 8,256 features: what any form of the algorithm keeps); how far state
and ``o`` of two slots lie from ``retention_decode_reference`` (run on the
host), each over its largest entry. ``copy``: a
kernel with the same blocks that only hands the state back, the pace of the
block's two copies. ``"still": true`` names slot 0 in every grid step, so
that a slot's blocks move only with the key-value head: not the walk alone,
the heads still turn. ``"compile_only": true`` compiles both for a described
v5e here, no chip.
"""

import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

from benchmark.lib import kernel_cost_retention  # noqa: E402
from deepspeed_tpu.ops.pallas import retention_decode as rd  # noqa: E402
from deepspeed_tpu.ops.pallas.ssm_decode import live_slots  # noqa: E402

L, H, G, D = 2, 40, 8, 128
HBM_BYTES_S = 819e9


def _copy_kernel(_rows, n_ref, _layer, s_ref, in_ref, v_ref, o_ref, y_ref,
                 *scratch, **_):
    o_ref[...] = s_ref[...]
    y_ref[...] = jnp.zeros_like(y_ref)


def _compile_only(S):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"     # Mosaic, on a CPU host
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shape(*s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one)

    for walk, kernel in (("walk", rd._kernel), ("copy", _copy_kernel)):
        rd._kernel = kernel
        t = time.perf_counter()
        compiled = jax.jit(
            lambda s, q, k, v, g, rows, n: rd.retention_decode(
                s, jnp.int32(1), q, k, v, g, jnp.ones((S,), bool),
                impl="kernel", live=(rows, n)), donate_argnums=(0,)).lower(
            shape(L, S, G, D // 2 + 2, D, D), shape(S, H, D), shape(S, G, D),
            shape(S, G, D), shape(S, G), shape(S, dtype=jnp.int32),
            shape(1, dtype=jnp.int32)).compile()
        print(json.dumps(dict(
            slots=S, walk=walk, compiled_s=round(time.perf_counter() - t, 1),
            peak_gb=round(compiled.memory_analysis().peak_memory_in_bytes
                          / 1e9, 3))), flush=True)


def main():
    spec = json.loads(sys.argv[1]) if len(sys.argv) > 1 else {}
    if spec.get("compile_only"):
        for S in spec.get("slots", [40]):
            _compile_only(S)
        return
    if jax.default_backend() != "tpu":
        sys.exit("a TPU only: a CPU's time is not the device's")
    from ssm_decode_bench import kernel_ms

    reps = spec.get("reps", 10)
    kernel = rd._kernel
    for S in spec.get("slots", [40]):
        key = jax.random.split(jax.random.PRNGKey(spec.get("seed", 0)), 6)
        q = jax.random.normal(key[1], (S, H, D))
        k, v = (jax.random.normal(kk, (S, G, D)) for kk in key[2:4])
        gate = jax.random.uniform(key[4], (S, G), minval=0.97, maxval=0.999)
        active = jnp.ones((S,), bool)
        live = live_slots(active)
        if spec.get("still"):
            live = (jnp.zeros((S,), jnp.int32), live[1])
        cost = kernel_cost_retention.retention_decode(S, G, H // G, D)
        few = slice(0, 2)
        shape = (L, S, G, D // 2 + 2, D, D)
        # the recurrence on the host: op by op on the chip, its padding of a
        # 65-row block aborts the TPU compiler (an unaligned update in place)
        first = np.asarray(jax.random.normal(key[0], shape)[:, few])
        with jax.default_device(jax.devices("cpu")[0]):
            want = rd.retention_decode_reference(*(
                jnp.asarray(np.asarray(a)) for a in (
                    first, 1, q[few], k[few], v[few], gate[few],
                    active[few])))
            want = np.asarray(want[0]), np.asarray(want[1][1])
        for walk, walk_kernel in (("walk", kernel), ("copy", _copy_kernel)):
            rd._kernel = walk_kernel
            fn = jax.jit(lambda s: rd.retention_decode(
                s, jnp.int32(1), q, k, v, gate, active, impl="kernel",
                live=live), donate_argnums=(0,))
            o, state = fn(jax.random.normal(key[0], shape))
            got = tuple(np.asarray(a) for a in (o[few], state[1, few]))
            took = []
            for _ in range(3):
                t = time.perf_counter()
                for _ in range(reps):
                    o, state = fn(state)
                o.block_until_ready()
                took.append((time.perf_counter() - t) / reps)
            trace_dir = os.path.join(REPO, "chiprun_out",
                                     ".retention_decode_trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            with jax.profiler.trace(trace_dir):
                for _ in range(5):
                    o, state = fn(state)
                o.block_until_ready()
            ms, calls = kernel_ms(trace_dir, "retention_decode")
            shutil.rmtree(trace_dir, ignore_errors=True)
            line = dict(
                slots=S, walk=walk, still=bool(spec.get("still")),
                kernel_ms=round(ms, 4), calls=calls,
                us_slot=round(ms * 1e3 / S, 3),
                gbs=round(cost.bytes / ms / 1e6, 1),
                roofline_pct=round(cost.bytes / HBM_BYTES_S / ms * 1e5, 2),
                jit_ms=round(float(np.median(took)) * 1e3, 4))
            if walk != "copy" and not spec.get("still"):
                line["o_err"] = float(np.abs(got[0] - want[0]).max()
                                      / np.abs(want[0]).max())
                n = D // 2 + 1      # the rows the recurrence keeps: a seeded
                # start fills the normaliser's block past them too, which
                # the kernel hands back as they were
                apart = np.abs(got[1] - want[1])
                line["state_err"] = float(max(
                    apart[:, :, :n].max(), apart[:, :, n, :n].max()) / np.abs(
                        want[1]).max())
            print(json.dumps(line), flush=True)
            del o, state
        rd._kernel = kernel


if __name__ == "__main__":
    main()
