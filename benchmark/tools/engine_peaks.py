#!/usr/bin/env python3
"""Compile, for a described v5e and with no chip, the programs a serve cell's
engine really dispatches where a slot carries a state (``hybrid_pattern``),
and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 benchmark/tools/engine_peaks.py \\
        nemotron-3-nano-serve.chat-decode [program ...]

``tools/compile_only.py`` lowers the prefill programs without their slot
argument and an admission batch of every slot (``[512,128]`` here, 18.4 GB,
where it stops), neither of which this engine runs (PERF.md section 7: a
``benchmark`` PR's). This lowers, with the engine's own jitted functions and
its own argument lists: ``decode1`` / ``decode2`` / ``decode4``
(``jit_decode_block_<k>``), ``check`` (the comparison's step,
``lib/correct.check_step`` over the family's ``paged_decode_step``),
``fused<chunk>`` (a prompt of one chunk), ``batch<rows>x<chunk>`` (the
admission ladder, up to 512 tokens a dispatch), ``chunk<chunk>`` (a serial
chunk over the dense scratch cache) and ``scatter``. The pool and the states
are shapes only (``jax.eval_shape``): nothing of the cell's 5.5 GB of cache is
made on the host. One JSON line a program; ``SLOTS=<n>`` tries another slot
count (pages follow, 16 a slot and the sink); ``DUMP=<dir>`` writes each
program's optimised HLO there. Nothing runs: no time, rate or utilization
comes from here.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"      # lower the real Mosaic kernels
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import correct, manifest  # noqa: E402


def programs(cell: dict, slots=None):
    """(name -> (jitted function, abstract arguments on the described chip),
    the engine's slots)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models import gpt

    config, traffic = cell["config_file"], cell["traffic_file"]
    family = manifest.family_of(config)
    # auto flash resolves by the default backend, which is the CPU here
    cfg = family.config(dict(config["model"], use_flash=True))
    slots = int(slots or traffic["slots"])
    pages = (int(traffic["pages"]) - 1) // int(traffic["slots"]) * slots + 1
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    params = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), family.init_params(cfg, k)))(
            jax.random.PRNGKey(0))
    make_cache = gpt.init_paged_cache
    gpt.init_paged_cache = lambda *a, **k: jax.eval_shape(     # shapes only
        lambda: make_cache(*a, **k))
    try:
        engine = ServingEngine(cfg, params, ServingConfig(
            num_slots=slots, num_pages=pages,
            **dict(config["engine"], kernel_impl="kernel")))
    finally:
        gpt.init_paged_cache = make_cache

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    s = engine.serving
    weights, pool = on_chip(engine.params), on_chip(engine.paged_cache)
    n, width, chunk = engine.num_slots, s.pages_per_seq, s.prefill_chunk
    step_args = (weights, pool, i32(n), i32(n, width), i32(n))
    out = {f"decode{k}": (engine._get_decode(k), step_args)
           for k in (1, 2, 4) if k <= s.decode_block}
    out["check"] = (correct.check_step(
        family, manifest.reference_of(config), cfg, "kernel"), step_args)
    for b in sorted({min(b, chunk) for b in traffic["prompt_lens"]}):
        out[f"fused{b}"] = (engine._get_prefill_fused(b), (
            weights, i32(1, b), pool, i32(width), i32(), i32(), i32()))
        for rows in (2, 4):
            if rows * b <= 512:
                out[f"batch{rows}x{b}"] = (engine._get_prefill_batch(b), (
                    weights, i32(rows, b), pool, i32(rows, width), i32(rows),
                    i32(rows), i32(rows)))
    dense = on_chip(jax.eval_shape(lambda: family.init_cache(
        cfg, 1, engine._dense_S, engine.dtype)))
    out[f"chunk{chunk}"] = (engine._get_prefill(chunk), (
        weights, i32(1, chunk), dense, i32()))
    out["scatter"] = (engine._get_scatter(), (
        pool, dense, i32(width), i32(), i32(), i32()))
    return out, n


def main(argv) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    progs, slots = programs(manifest.load_cell(argv[0]),
                            os.environ.get("SLOTS"))
    unknown = [name for name in argv[1:] if name not in progs]
    if unknown:
        print(f"no program {unknown}; there are {sorted(progs)}",
              file=sys.stderr)
        return 2
    rc = 0
    for name in argv[1:] or progs:
        fn, args = progs[name]
        t0 = time.perf_counter()
        try:
            compiled = fn.lower(*args).compile()
        except Exception as e:      # the compiler's refusal is the finding
            print(json.dumps({"program": name, "refused": str(e)[:400]}),
                  flush=True)
            rc = 1
            continue
        m = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "program": name, "slots": slots,
            "compile_s": round(time.perf_counter() - t0, 1),
            "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
            "arguments_gb": round(m.argument_size_in_bytes / 1e9, 3),
            "aliased_gb": round(m.alias_size_in_bytes / 1e9, 3),
            "temporaries_gb": round(m.temp_size_in_bytes / 1e9, 3),
            "peak_gb": round(getattr(m, "peak_memory_in_bytes", 0) / 1e9, 3),
        }), flush=True)
        if os.environ.get("DUMP"):
            os.makedirs(os.environ["DUMP"], exist_ok=True)
            with open(os.path.join(os.environ["DUMP"], f"{name}.hlo"),
                      "w") as f:
                f.write(text)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
