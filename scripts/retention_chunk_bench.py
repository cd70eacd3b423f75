"""Times ``ops/pallas/retention_chunk`` alone at ``brumby-14b-serve``'s
prefill shapes, beside the plain form it stands in for
(``models/retention.scan_chunks``), through ``chiprun``; a TPU only.

    chiprun -- python scripts/retention_chunk_bench.py ['{"rows": [1, 2]}']

One layer's chunked form over a dispatch ``[rows, 2048]``: 40 query heads
over 8 key-value heads of 128, chunks of 128, a gate near 1 and a state that
already holds a prompt. Prints, a row count and form: milliseconds a call
(``kernel``: the ``retention_chunk`` events of the device's operation line in
a trace of its own; ``plain``: the host's clock around ``reps`` calls, the
form being hundreds of fusions), ms a 1,000 tokens and a chunk, the share of
the six passes' MXU floor (:func:`floor_ms`: the read of the state, the
write and the chunk's quadratic part, float32 products counted as six bf16
passes at the v5e's 197 TFLOP/s), and how far the kernel's output and state
lie from the plain form's, each over its largest entry. ``"compile_only":
true`` compiles the kernel for a described v5e here, no chip.
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

from deepspeed_tpu.models import retention  # noqa: E402
from deepspeed_tpu.ops.pallas.retention_chunk import retention_chunk  # noqa: E402

T, H, G, D, CHUNK = 2048, 40, 8, 128, 128
MXU_FLOPS_S, PASSES = 197e12, 6


def floor_ms(rows: int, tokens: int = T) -> float:
    """The least the MXU takes for ``rows x tokens`` positions of one layer:
    a chunk and key-value head reads ``r C`` queries off ``(D / 2 + 1) D``
    features of ``D`` values, writes ``C`` keys into them, and takes its own
    ``C x C`` scores and their product with the values a query head."""
    r, n = H // G, D // 2 + 1
    chunk = (2 * r * CHUNK * n * D * D + 2 * CHUNK * n * D * D
             + r * 4 * CHUNK * CHUNK * D)
    return 1e3 * PASSES * rows * (tokens // CHUNK) * G * chunk / MXU_FLOPS_S


def _compile_only(rows):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"     # Mosaic, on a CPU host
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)

    m = retention.RetentionMixer(H, G, D, chunk=CHUNK)
    t = time.perf_counter()
    compiled = jax.jit(
        lambda *a: retention_chunk(m, *a, impl="kernel"),
        donate_argnums=(4,)).lower(
        shape(rows, T, H, D), shape(rows, T, G, D), shape(rows, T, G, D),
        shape(rows, T, G), shape(rows, *m.state_shape())).compile()
    print(json.dumps(dict(
        rows=rows, compiled_s=round(time.perf_counter() - t, 1),
        peak_gb=round(compiled.memory_analysis().peak_memory_in_bytes / 1e9,
                      3))), flush=True)


def main():
    spec = json.loads(sys.argv[1]) if len(sys.argv) > 1 else {}
    rows_of = spec.get("rows", [1, 2])
    if spec.get("compile_only"):
        for rows in rows_of:
            _compile_only(rows)
        return
    if jax.default_backend() != "tpu":
        sys.exit("a TPU only: a CPU's time is not the device's")
    from ssm_decode_bench import kernel_ms

    reps = spec.get("reps", 5)
    m = retention.RetentionMixer(H, G, D, chunk=CHUNK)

    def unit(kk, *shape):   # rows of norm sqrt(D) / 2, as QK-norm's
        a = jax.random.normal(kk, shape)
        return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True)) / 2

    for rows in rows_of:
        key = jax.random.split(jax.random.PRNGKey(spec.get("seed", 0)), 6)
        q, k = unit(key[0], rows, T, H, D), unit(key[1], rows, T, G, D)
        v = jax.random.normal(key[2], (rows, T, G, D))
        log_g = jnp.log(jax.random.uniform(key[3], (rows, T, G), minval=0.99,
                                           maxval=0.9999))
        got = {}
        for form in spec.get("forms", ["kernel", "plain"]):
            fn = jax.jit(lambda s, form=form: retention_chunk(
                m, q, k, v, log_g, s, impl=form), donate_argnums=(0,))
            # a first prompt's worth of state, then the call that is timed
            _, state = fn(jnp.zeros((rows,) + m.state_shape(), jnp.float32))
            o, state = fn(state)
            got[form] = (np.asarray(o), np.asarray(state))
            took = []
            for _ in range(3):
                t = time.perf_counter()
                for _ in range(reps):
                    o, state = fn(state)
                o.block_until_ready()
                took.append((time.perf_counter() - t) / reps)
            ms, calls = float(np.median(took)) * 1e3, reps
            if form == "kernel":
                trace_dir = os.path.join(REPO, "chiprun_out",
                                         ".retention_chunk_trace")
                shutil.rmtree(trace_dir, ignore_errors=True)
                with jax.profiler.trace(trace_dir):
                    for _ in range(reps):
                        o, state = fn(state)
                    o.block_until_ready()
                jit_ms = ms
                ms, calls = kernel_ms(trace_dir, "retention_chunk")
                shutil.rmtree(trace_dir, ignore_errors=True)
            line = dict(
                rows=rows, form=form, ms=round(ms, 3), calls=calls,
                ms_ktok=round(ms * 1e3 / (rows * T), 3),
                us_chunk=round(ms * 1e3 / (rows * T // CHUNK), 2),
                mxu_floor_pct=round(100 * floor_ms(rows) / ms, 2))
            if form == "kernel":
                line["jit_ms"] = round(jit_ms, 3)
            print(json.dumps(line), flush=True)
            del o, state
        if len(got) == 2:
            (o1, s1), (o2, s2) = got["kernel"], got["plain"]
            print(json.dumps(dict(
                rows=rows,
                o_err=float(np.abs(o1 - o2).max() / np.abs(o2).max()),
                state_err=float(np.abs(s1 - s2).max() / np.abs(s2).max()))),
                flush=True)


if __name__ == "__main__":
    main()
