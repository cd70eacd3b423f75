#!/usr/bin/env python
"""Flash-attention kernel times and tile autotune on the real chip.

For each geometry and each (block_q, block_k) pair: fwd+bwd (all three grads,
so all four kernels) on the host clock, N iterations riding ONE dispatch via
lax.fori_loop with a data-dependent carry so per-dispatch host latency
amortizes to noise; then one traced dispatch, from which the per-kernel line
comes: milliseconds a call of ``flash_fwd``, ``flash_bwd_delta``,
``flash_bwd_dq`` and ``flash_bwd_dkv`` by the names the kernels carry on the
device's operation line. Prints one JSON line a geometry and writes them to
``chiprun_out/flash_tile_tune.jsonl``. One process: run it alone on the chip.

Usage: python scripts/flash_tile_tune.py \
    ['{"geom": ["gpt2", "pythia"], "tiles": [[256, 256]], "iters": 8}']
"""

import collections
import functools
import glob
import json
import os
import re
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

GEOMS = {
    # [B, T, H, D]: a chip's micro-batch in the benchmark's two train cells
    "gpt2": (16, 1024, 16, 64),     # gpt2-medium-train.steady-1k
    "pythia": (8, 2048, 16, 128),   # pythia-1.4b-train-dp4.steady-2k
    "760m": (16, 1024, 16, 96),     # gpt2-760m: d_model 1536, 16 heads
    "8k": (2, 8192, 16, 64),        # long-context row: dkv must stream
    "tiny": (1, 256, 2, 64),        # CPU interpret-mode smoke only
}

# (None, None): the tile each kernel takes where the caller names none
TILES = [(None, None), (128, 128), (128, 256), (256, 128), (256, 256),
         (256, 512), (512, 256), (512, 512), (1024, 512), (512, 1024),
         (1024, 1024)]

KERNEL = re.compile(r"(flash_fwd|flash_bwd_delta|flash_bwd_dq|flash_bwd_dkv)")


def kernel_ms(trace_dir):
    """Milliseconds a call of each flash kernel on the first TPU's operation
    line of the newest trace under ``trace_dir``, and the calls counted."""
    import jax

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    ns, calls = collections.Counter(), collections.Counter()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                m = KERNEL.search(ev.name.split(" = ")[0])
                if m and ev.duration_ns > 0:
                    ns[m.group(1)] += ev.duration_ns
                    calls[m.group(1)] += 1
    return {k: {"ms": round(ns[k] / calls[k] * 1e-6, 4), "calls": calls[k]}
            for k in sorted(ns)}


def tune(geom, tiles, iters, compile_only, stochastic_mode):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, T, H, D = GEOMS[geom]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, H, D), jnp.bfloat16)

    rows = {}
    best = None
    for bq, bk in tiles:
        if bq is not None and (T % bq or T % bk or bq > T or bk > T):
            continue
        fa = functools.partial(flash_attention, causal=True, block_q=bq,
                               block_k=bk, stochastic_mode=stochastic_mode)

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
        tag = "auto" if bq is None else f"{bq}x{bk}"
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
                rows[tag] = {"compile_ok": False, "error": str(e)[:400]}
            print(f"[tile] {geom} {tag}: {rows[tag]}", file=sys.stderr,
                  flush=True)
            continue
        try:
            t0 = time.perf_counter()
            jax.block_until_ready(f(q, k, v))  # compile + warm
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.block_until_ready(f(q, k, v))
            ms = (time.perf_counter() - t0) / iters * 1e3
            trace_dir = os.path.join(REPO, "chiprun_out", ".flash_tune_trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            with jax.profiler.trace(trace_dir):
                jax.block_until_ready(f(q, k, v))
            kernels = kernel_ms(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
        except Exception as e:  # noqa: BLE001 — a bad tile must not kill the sweep
            rows[tag] = {"error": str(e)[:400]}
            print(f"[tile] {geom} {tag}: {rows[tag]}", file=sys.stderr,
                  flush=True)
            continue
        rows[tag] = {"ms": round(ms, 3), "compile_s": round(compile_s, 1),
                     "kernels": kernels}
        if best is None or ms < best[1]:
            best = (tag, ms)
        print(f"[tile] {geom} {tag}: {ms:.3f} ms fwd+bwd; a call: " + ", ".join(
            f"{name} {r['ms']:.3f}" for name, r in kernels.items()),
            file=sys.stderr, flush=True)

    return {"tag": f"flash-tile-{geom}", "geom": list(GEOMS[geom]),
            "iters": iters, "stochastic_mode": stochastic_mode, "tiles": rows,
            "best": best[0] if best else None,
            "best_ms": round(best[1], 3) if best else None}


def main():
    spec = json.loads(sys.argv[1]) if len(sys.argv) > 1 else {}
    geoms = spec.get("geom", ["gpt2", "pythia"])
    geoms = [geoms] if isinstance(geoms, str) else geoms
    tiles = [tuple(t) for t in spec.get("tiles", TILES)]  # [null, null]: auto
    iters = int(spec.get("iters", 8))

    compile_only = bool(spec.get("compile_only"))
    if spec.get("force_cpu") or compile_only:
        # before jax is imported: this process must not take the chip
        os.environ["DS_TPU_ACCELERATOR"] = "cpu"
        os.environ["JAX_PLATFORMS"] = "cpu"
    if compile_only:
        os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")

    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "flash_tile_tune.jsonl"),
              "a") as log:
        for geom in geoms:
            out = tune(geom, tiles, iters, compile_only,
                       bool(spec.get("stochastic_mode")))
            print(json.dumps(out), flush=True)
            log.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
