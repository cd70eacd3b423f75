#!/usr/bin/env python
"""Flash-attention tile autotune on the real chip.

Times fwd+bwd (all three grads — both backward kernels) for each
(block_q, block_k) pair at the flagship geometries. N iterations ride ONE
dispatch via lax.fori_loop with a data-dependent carry, so per-dispatch host
latency amortizes to noise. Prints one JSON line: per-tile ms + winner.
One process: run it alone on the chip.

Usage: python scripts/flash_tile_tune.py ['{"geom": "760m", "iters": 8}']
"""

import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

GEOMS = {
    # [B, T, H, D] at the gpt2 presets' train shapes
    "760m": (16, 1024, 16, 96),   # gpt2-760m: d_model 1536, 16 heads
    "350m": (16, 1024, 16, 64),   # gpt2-350m: d_model 1024, 16 heads
    "8k": (2, 8192, 16, 64),      # long-context row
    "tiny": (1, 256, 2, 64),      # CPU interpret-mode smoke only
}

TILES = [(128, 128), (128, 256), (256, 128), (256, 256),
         (256, 512), (512, 256), (512, 512), (1024, 512)]


def main():
    spec = json.loads(sys.argv[1]) if len(sys.argv) > 1 else {}
    geom = spec.get("geom", "760m")
    iters = int(spec.get("iters", 8))
    B, T, H, D = GEOMS[geom]

    compile_only = bool(spec.get("compile_only"))
    if spec.get("force_cpu") or compile_only:
        # before jax is imported: this process must not take the chip
        os.environ["DS_TPU_ACCELERATOR"] = "cpu"
        os.environ["JAX_PLATFORMS"] = "cpu"
    if compile_only:
        os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt import PRESETS  # noqa: F401 (repo path check)
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, H, D), jnp.bfloat16)

    rows = {}
    best = None
    for bq, bk in TILES:
        if T % bq or T % bk or bq > T or bk > T:
            continue
        fa = functools.partial(flash_attention, causal=True,
                               block_q=bq, block_k=bk)

        def loss(q, k, v, fa=fa):
            return fa(q, k, v).astype(jnp.float32).sum()

        grads = jax.grad(loss, argnums=(0, 1, 2))

        def body(i, carry, grads=grads):
            q, k, v = carry
            dq, dk, dv = grads(q, k, v)
            # data-dependent carry: serializes iterations, defeats DCE
            return (q + 1e-6 * dq.astype(q.dtype),
                    k + 1e-6 * dk.astype(k.dtype),
                    v + 1e-6 * dv.astype(v.dtype))

        f = jax.jit(lambda q, k, v, body=body: jax.lax.fori_loop(
            0, iters, body, (q, k, v)))
        tag = f"{bq}x{bk}"
        if compile_only:
            # Mosaic-compile against the v5e topology (no chips): validates
            # every tile variant BEFORE the tuner spends chip time on it
            from jax.experimental import topologies
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            td = topologies.get_topology_desc(platform="tpu",
                                              topology_name="v5e:2x2")
            mesh = Mesh(list(td.devices)[:1], ("d",))
            rep = NamedSharding(mesh, P())
            ab = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
                a.shape, a.dtype, sharding=rep)
            try:
                t0 = time.perf_counter()
                f.lower(ab(q), ab(k), ab(v)).compile()
                rows[tag] = {"compile_ok": True,
                             "compile_s": round(time.perf_counter() - t0, 1)}
            except Exception as e:  # noqa: BLE001
                rows[tag] = {"compile_ok": False, "error": str(e)[:160]}
            print(f"[tile] {geom} {tag}: {rows[tag]}", file=sys.stderr,
                  flush=True)
            continue
        try:
            r = f(q, k, v)
            jax.block_until_ready(r)  # compile + warm
            t0 = time.perf_counter()
            jax.block_until_ready(f(q, k, v))
            ms = (time.perf_counter() - t0) / iters * 1e3
        except Exception as e:  # noqa: BLE001 — a bad tile must not kill the sweep
            rows[tag] = {"error": str(e)[:160]}
            continue
        rows[tag] = {"ms": round(ms, 2)}
        if best is None or ms < best[1]:
            best = (tag, ms)
        print(f"[tile] {geom} {tag}: {ms:.2f} ms", file=sys.stderr, flush=True)

    out = {"tag": f"flash-tile-{geom}", "geom": list(GEOMS[geom]),
           "iters": iters, "tiles": rows,
           "best": best[0] if best else None,
           "best_ms": round(best[1], 2) if best else None}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
