#!/usr/bin/env python3
"""Compile a cell's step programs at real size for a described v5e:2x2, with
no chip (the `on-chip-measurement` guide, section 2, third rehearsal).

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_only.py <cell> [<cell> ...]

It says whether the chip's compiler takes each program and what it needs per
device. Nothing runs: no time, rate or utilization comes from here. A serve
cell builds its engine on the host at full size (weights and pages in host
RAM) and lowers the engine's own jitted programs against shapes placed on the
described chip; a train cell goes through ``runtime/aot.train_program_report``,
a re-statement of the engine's step with the engine's placement rules (its
peak read 0.2-0.6 GB under the executable's on the chip: PERF.md, PR 21).
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


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)  # noqa: E731
    return {"arguments_gb": gb(m.argument_size_in_bytes),
            "outputs_gb": gb(m.output_size_in_bytes),
            "aliased_gb": gb(m.alias_size_in_bytes),
            "temporaries_gb": gb(m.temp_size_in_bytes),
            "peak_gb": gb(getattr(m, "peak_memory_in_bytes", 0))}


def serve(cell: dict) -> list:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    config, traffic = cell["config_file"], cell["traffic_file"]
    family = manifest.family_of(config)
    # auto flash resolves by the default backend, which is the CPU here
    cfg = family.config(dict(config["model"], use_flash=True))
    eng = dict(config["engine"], kernel_impl="kernel")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    params = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), family.init_params(cfg, k)))(
            jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, ServingConfig(
        num_slots=int(traffic["slots"]), num_pages=int(traffic["pages"]),
        **eng))
    s = engine.serving
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    a_params, a_pool = on_chip(engine.params), on_chip(engine.paged_cache)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                              sharding=chip)
    n, pps = engine.num_slots, s.pages_per_seq
    lengths = sorted(set(traffic["prompt_lens"]))
    buckets = engine._chunk_buckets
    from deepspeed_tpu.inference.serving.buckets import bucket_for

    # the reference check's own step over the engine's pool (lib/correct.py)
    programs = {f"check step [{n}]": (
        correct.check_step(family, manifest.reference_of(config), cfg,
                           eng["kernel_impl"]),
        (a_params, a_pool, i32(n), i32(n, pps), i32(n)))}
    k = 1
    while k <= s.decode_block:
        programs[f"decode x{k} [{n}]"] = (
            engine._get_decode(k), (a_params, a_pool, i32(n), i32(n, pps),
                                    i32(n)))
        k *= 2
    short = sorted({bucket_for(t, buckets) for t in lengths
                    if t <= s.prefill_chunk})
    for b in short:
        programs[f"prefill fused [1,{b}]"] = (
            engine._get_prefill_fused(b),
            (a_params, i32(1, b), a_pool, i32(pps), i32(), i32()))
        programs[f"prefill batch [{n},{b}]"] = (
            engine._get_prefill_batch(b),
            (a_params, i32(n, b), a_pool, i32(n, pps), i32(n), i32(n)))
    if any(t > s.prefill_chunk for t in lengths):
        dense = on_chip(jax.eval_shape(
            lambda: family.init_cache(cfg, 1, engine._dense_S, engine.dtype)))
        rems = sorted({s.prefill_chunk} | {
            bucket_for(t % s.prefill_chunk, buckets) for t in lengths
            if t > s.prefill_chunk and t % s.prefill_chunk})
        for b in rems:
            programs[f"prefill chunk [1,{b}]"] = (
                engine._get_prefill(b), (a_params, i32(1, b), dense))
        programs["scatter"] = (engine._get_scatter(),
                               (a_pool, dense, i32(pps), i32(), i32()))
    rows = []
    for name, (fn, args) in programs.items():
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        rows.append({"program": name, "compile_s": round(
            time.perf_counter() - t0, 1),
            "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
            **_mem(compiled)})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def train(cell: dict) -> list:
    config, traffic = cell["config_file"], cell["traffic_file"]
    family = manifest.family_of(config)
    model = dict(config["model"])
    policy = model.pop("remat_policy", None)
    model.pop("remat", None)
    opt = config["engine"]["optimizer"]
    rep = family.train_program_report(
        family.config(model), dp=int(cell["chips"]),
        stage=int(config["engine"]["zero_optimization"]["stage"]),
        micro_bs=int(traffic["micro_batch_per_chip"]),
        seq=int(traffic["seq_len"]), remat_policy=policy,
        optimizer=(opt["type"], opt["params"]))
    rep.pop("trace", None)
    print(json.dumps(rep, default=str), flush=True)
    return [rep]


def main(argv) -> int:
    for name in argv:
        cell = manifest.load_cell(name)
        print(f"== {name}", flush=True)
        {"serve": serve, "train": train}[cell["config_file"]["mode"]](cell)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
