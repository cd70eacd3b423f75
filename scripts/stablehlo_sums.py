#!/usr/bin/env python3
"""StableHLO of a cell's programs at real size, lowered for a described v5e
(nothing compiles, nothing runs): one file a program under <out>/<cell>/ and
a line ``<sha256, 16 hex> <cell> <program> <length>``. What a PR that must
leave programs untouched compares between its parent and its change:

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \\
        python3 scripts/stablehlo_sums.py <out> <cell> [<cell> ...] | grep -E '^[0-9a-f]{16} ' | sort -k2 > sums

Run it from the root of ONE directory that holds first the parent's
``git archive``, then the change's: a Mosaic kernel's serialized body carries
source paths, so two directories never compare equal. A serve cell builds its
engine on the host at full size, as ``benchmark/tools/compile_only.py`` does,
and lowers the reference check's step, every decode block, and for every
chunk bucket the fused, batch (2 and 4 rows), dense chunk and, where the
engine has one, page-writing chunk program, and the scatter; a train cell
its forward and its loss and gradient. The process may linger after its last
line (the host arrays' teardown): kill it once every cell has printed.
"""
import hashlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"
sys.path.insert(0, os.getcwd())
from benchmark.lib import correct, manifest  # noqa: E402


def emit(out, cell, name, lowered):
    text = lowered.as_text()
    d = os.path.join(out, cell)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, name.replace(" ", "_").replace("/", "_") + ".mlir"), "w") as f:
        f.write(text)
    print(hashlib.sha256(text.encode()).hexdigest()[:16], cell, name, len(text), flush=True)


def serve(out, name, cell):
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.inference.serving.buckets import bucket_for

    config, traffic = cell["config_file"], cell["traffic_file"]
    family = manifest.family_of(config)
    cfg = family.config(dict(config["model"], use_flash=True))
    eng = dict(config["engine"], kernel_impl="kernel")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    params = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), family.init_params(cfg, k)))(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, ServingConfig(
        num_slots=int(traffic["slots"]), num_pages=int(traffic["pages"]), **eng))
    s = engine.serving
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    a_params, a_pool = on_chip(engine.params), on_chip(engine.paged_cache)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
    n, pps = engine.num_slots, s.pages_per_seq
    buckets = engine._chunk_buckets
    programs = {f"check step": (
        correct.check_step(family, manifest.reference_of(config), cfg, eng["kernel_impl"]),
        (a_params, a_pool, i32(n), i32(n, pps), i32(n)))}
    k = 1
    while k <= s.decode_block:
        programs[f"decode x{k}"] = (engine._get_decode(k), (a_params, a_pool, i32(n), i32(n, pps), i32(n)))
        k *= 2
    for b in buckets:
        programs[f"prefill fused {b}"] = (engine._get_prefill_fused(b),
                                          (a_params, i32(1, b), a_pool, i32(pps), i32(), i32()))
        for rows in (2, 4):
            if rows <= n:
                programs[f"prefill batch {rows}x{b}"] = (
                    engine._get_prefill_batch(b),
                    (a_params, i32(rows, b), a_pool, i32(rows, pps), i32(rows), i32(rows)))
    if s.max_model_len > s.prefill_chunk:
        try:
            dense = on_chip(jax.eval_shape(lambda: family.init_cache(cfg, 1, engine._dense_S, engine.dtype)))
        except ValueError:  # rows of several shapes a token (dots3-note): its chunks go to pages alone
            dense = None
        for b in buckets if dense is not None else ():
            programs[f"prefill chunk dense {b}"] = (engine._get_prefill(b), (a_params, i32(1, b), dense))
        if dense is not None:
            programs["scatter"] = (engine._get_scatter(), (a_pool, dense, i32(pps), i32(), i32()))
        if getattr(engine, "_chunk_to_pages", False):
            for b in buckets:
                programs[f"prefill chunk paged {b}"] = (
                    engine._get_prefill_to_pages(b),
                    (a_params, i32(1, b), a_pool, i32(pps), i32(), i32(), i32()))
    for pname, (fn, args) in programs.items():
        emit(out, name, pname, fn.lower(*args))


def train(out, name, cell):
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.models import gpt as G

    config, traffic = cell["config_file"], cell["traffic_file"]
    family = manifest.family_of(config)
    model = dict(config["model"])
    model.pop("remat_policy", None); model.pop("remat", None)
    cfg = family.config(dict(model, use_flash=True))
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k), jax.random.PRNGKey(0))
    a_params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=chip), shapes)
    mb, seq = int(traffic["micro_batch_per_chip"]), int(traffic["seq_len"])
    batch = {"input_ids": jax.ShapeDtypeStruct((mb, seq), jnp.int32, sharding=chip)}
    emit(out, name, "forward", jax.jit(lambda p, b: G.forward(cfg, p, b["input_ids"], train=False)).lower(a_params, batch))
    emit(out, name, "loss and grad", jax.jit(jax.value_and_grad(
        lambda p, b: G.loss_fn(cfg, p, b, train=True), has_aux=True)).lower(a_params, batch))


def main(argv) -> int:
    import jax

    # a Mosaic kernel's serialized body carries its operations' source
    # locations: keep the innermost frame only (the kernel's own file), not
    # the callers' line numbers in models/gpt.py, which an edit above them
    # shifts
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    for name in argv[1:]:
        cell = manifest.load_cell(name)
        {"serve": serve, "train": train}[cell["config_file"]["mode"]](
            argv[0], name, cell)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
