"""Times ``ops/pallas/decode_attention.paged_decode_gqa`` alone at the three
cells' shapes and lengths, a grid step a GROUP of a request's pages beside a
step a page (through ``chiprun``; a TPU only).

    chiprun -- python scripts/gqa_decode_bench.py ['{"shapes": ["long-answer"], "groups": [1, 4, 8]}']

A shape is one attention layer's call of a cell: slots, query and key-value
heads of 128, the table's width, the pool's pages and layers, the types, and
the cell's prompts and outputs, from which every slot draws a length (a
prompt, and a uniform share of its output). Prints, a shape, form and group
``g``: the kernel's milliseconds a call, read off a trace of its own (the
``paged_decode_gqa`` events of the device's operation line); the grid's
steps; us a live page and a step; GB/s over the live rows' bytes and their
share of the v5e's 819 GB/s (``benchmark/lib/kernel_cost_gqa.py``); the live
pages over the page tiles the groups fetch; whether the output is the first
form's bit for bit (the walk at ``g`` = 1, a step a page), else how far, and
how far from ``_gqa_gather_attention``. Forms: ``walk`` the kernel as shipped, at
each of ``groups`` and at ``gqa_pages_per_step``'s own answer (``shipped``);
``copy`` a kernel with the same blocks that computes nothing, the pace of
the tiles' copies; ``still`` the walk with page 1 named for every tile, so
that no block moves after the first: the arithmetic alone; ``parent``, where
``"parent": "<a checkout's root>"`` names one (``git archive`` of the parent
commit under ``_checkouts/``), that checkout's own kernel over its own list,
which the outputs are then held to. ``"compile_only": true`` compiles every
form for a described v5e here, without the chip.
"""

import importlib.util
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import kernel_cost_gqa  # noqa: E402
from deepspeed_tpu.ops.pallas import decode_attention as da  # noqa: E402
from scripts.ssm_decode_bench import kernel_ms  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
MIXED = ((512, 2048, 8192), (512, 1024))
# slots, query heads, key-value heads, table width, pages, layers, query and
# pool types, ring, (prompts, outputs): a full and a window layer of
# laguna-xs.2-serve.mixed-decode, a layer of falcon-h1-34b-serve.long-answer,
# the attention layer of nemotron-3-nano-serve.chat-decode
SHAPES = {
    "mixed-full": (48, 48, 8, 144, 6913, 2, F32, BF16, None, MIXED),
    "mixed-window": (48, 64, 8, 8, 384, 3, F32, BF16, (512, 512), MIXED),
    "long-answer": (96, 20, 4, 24, 2305, 6, F32, BF16, None,
                    ((128, 256, 512), (512, 768, 1024))),
    "chat-decode": (512, 32, 2, 16, 8193, 1, F32, F32, None,
                    ((128, 256, 512), (256, 384, 512))),
}
DH, PS = 128, 64
HBM_BYTES_S = 819e9


def _copy_kernel(_len, _start, _row, _page, _layer, q_ref, *refs, rep, group,
                 **_):
    """The walk's blocks, nothing computed: a corner of every tile summed
    into the output, so that each is waited for."""
    o_ref = refs[2 * group]
    o_ref[0, 0] = sum(r[:, 0, :rep, :].astype(jnp.float32)
                      for r in refs[:2 * group]).astype(o_ref.dtype)


def _lengths(rng, slots, mix):
    prompts, outputs = mix
    b = np.arange(slots)
    prompt = np.asarray(prompts)[b % len(prompts)]
    out = np.asarray(outputs)[(b // len(prompts)) % len(outputs)]
    return (prompt + (rng.uniform(size=slots) * out).astype(np.int64) + 1
            ).astype(np.int32)


def main():
    spec = json.loads(sys.argv[1]) if len(sys.argv) > 1 else {}
    compile_only = bool(spec.get("compile_only"))
    if not compile_only and jax.default_backend() != "tpu":
        sys.exit("a TPU only: a CPU's time is not the device's")
    place = {}
    if compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        place = dict(sharding=SingleDeviceSharding(topo.devices[0]))
    reps = spec.get("reps", 10)
    rng = np.random.default_rng(spec.get("seed", 0))
    walk, asked = da._gqa_kernel, da.gqa_pages_per_step
    parent = None
    if spec.get("parent"):      # that checkout's kernel, beside this tree's
        at = importlib.util.spec_from_file_location(
            da.__package__ + "._parent_decode_attention", os.path.join(
                spec["parent"], os.path.relpath(da.__file__, REPO)))
        parent = importlib.util.module_from_spec(at)
        at.loader.exec_module(parent)
    for name in spec.get("shapes", list(SHAPES)):
        B, H, G, width, P, L, q_dt, pool_dt, ring, mix = SHAPES[name]
        lens = _lengths(rng, B, mix)
        held = np.minimum(lens, ring[0]) if ring else lens
        live = int((-(-held // PS)).sum())
        if ring:
            tables = np.arange(B * width).reshape(B, width)
        else:
            tables = (rng.permutation(B * width) + 1).reshape(B, width)
        lens_d, tables_d = jnp.asarray(lens), jnp.asarray(tables, jnp.int32)
        cost = kernel_cost_gqa.paged_decode_gqa(
            float(held.sum()), H, G, DH, jnp.dtype(pool_dt).itemsize)
        own = asked(G, PS, DH, pool_dt, width, ring is not None)
        forms = [("parent", 1)] if parent else []
        forms += [(form, g) for g in spec.get("groups", [1, 2, 4, 8])
                  if g <= width for form in ("walk", "copy", "still")]
        forms = [f for f in forms + [("shipped", own)] if f[0] in spec.get(
            "forms", ["parent", "walk", "shipped", "copy", "still"])]
        q_shape, pool_shape = (B, 1, H, DH), (L, G, P, PS, DH)
        if not compile_only:
            key = jax.random.split(jax.random.PRNGKey(spec.get("seed", 0)), 3)
            k = jax.random.normal(key[0], pool_shape, pool_dt)
            v = jax.random.normal(key[1], pool_shape, pool_dt)
            q = jax.random.normal(key[2], q_shape, q_dt)
            gathered = np.asarray(da.paged_decode_gqa(
                q, k, v, lens_d, tables_d, impl="gather",
                layer=jnp.int32(L - 1), ring=ring), np.float32)
        first = None
        for form, g in forms:
            da.gqa_pages_per_step = lambda *a, g=g: g
            da._gqa_kernel = _copy_kernel if form == "copy" else walk
            cap = jnp.minimum(lens_d, ring[0]) if ring else lens_d
            work = da.paged_work_list(cap, tables_d, PS, g)._replace(
                lens=lens_d)
            items = int(work.n_items)
            if form == "still":
                work = work._replace(pages=jnp.ones_like(work.pages))
            fn = jax.jit(lambda q, k, v, work=work, form=form: (
                parent.paged_decode_gqa(
                    q, k, v, lens_d, tables_d, impl="kernel",
                    layer=jnp.int32(L - 1), ring=ring) if form == "parent"
                else da.paged_decode_gqa(
                    q, k, v, lens_d, tables_d, impl="kernel",
                    layer=jnp.int32(L - 1), work=work, ring=ring)))
            line = dict(shape=name, form=form, g=g, steps=items,
                        live_pages=live,
                        fill_pct=round(100.0 * live / (items * g), 2))
            if compile_only:
                fn.lower(*(jax.ShapeDtypeStruct(s, d, **place) for s, d in (
                    (q_shape, q_dt), (pool_shape, pool_dt),
                    (pool_shape, pool_dt)))).compile()
                print(json.dumps(dict(line, compiled=True)), flush=True)
                continue
            got = np.asarray(fn(q, k, v), np.float32)
            trace_dir = os.path.join(REPO, "chiprun_out", ".gqa_decode_trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            with jax.profiler.trace(trace_dir):
                for _ in range(reps):
                    out = fn(q, k, v)
                out.block_until_ready()
            ms, calls = kernel_ms(trace_dir, "paged_decode_gqa")
            shutil.rmtree(trace_dir, ignore_errors=True)
            line.update(
                kernel_ms=round(ms, 4), calls=calls,
                us_page=round(ms * 1e3 / live, 4),
                us_step=round(ms * 1e3 / items, 4),
                gbs=round(cost.bytes / ms / 1e6, 1),
                roofline_pct=round(cost.bytes / HBM_BYTES_S / ms * 1e5, 2))
            if form in ("parent", "walk", "shipped"):
                if first is None:
                    first = got
                line["bit_equal_to_the_first"] = bool((got == first).all())
                line["far_from_the_first"] = float(np.abs(got - first).max())
                line["far_from_gather"] = float(
                    np.abs(got - gathered).max())
            print(json.dumps(line), flush=True)
        da.gqa_pages_per_step, da._gqa_kernel = asked, walk


if __name__ == "__main__":
    main()
