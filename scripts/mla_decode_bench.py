"""Times ``ops/pallas/decode_attention.paged_decode_mla`` alone at the two
latent cells' shapes and lengths, part by part (through ``chiprun``; a TPU
only).

    chiprun -- python scripts/mla_decode_bench.py ['{"shapes": ["long-decode"], "groups": [4, 8], "subs": [2, 8], "parent": "_checkouts/parent"}']

A shape is one latent layer's call of a cell: slots, heads, the row's width
and rank, the table's width, the pool's pages and layers, ring, whether a
selection masks the rows, and the cell's prompts and outputs, from which
every slot draws a length (a prompt, and a uniform share of its output):
``long-decode`` (``paged_decode_mla`` of ``deepseek-v2-serve.long-decode``),
``notes-select`` and ``notes-ring`` (``paged_decode_mla_select`` under a
top-2048 selection and ``paged_decode_mla_ring`` of
``dots3-note-serve.long-notes``). The pool is bf16; ``passes`` 2 is the
cells' float32 query over it (two products a product), 1 a bf16 query.
Prints, a shape, form, ``g`` (pages a grid step) and ``sub`` (pages a link of
the step's chain): the kernel's milliseconds a call, read off a trace of its
own; the grid's steps and those that hold rows; us a live step; GB/s over the
attended rows' bytes and the share of the floor ``benchmark/lib/
kernel_cost_mla.py`` counts (operations over 197 TFLOP/s or bytes over 819
GB/s, whichever is larger); the live pages over the page tiles the steps
fetch; whether the output is the parent's bit for bit, else how far, and how
far from ``_mla_gather_attention``. Forms: ``walk`` the kernel as shipped, at
each of ``groups`` x ``subs`` and at its own answer (``shipped``); ``copy`` a
kernel that makes the walk's copies of the items' tiles (``_mla_fetch``) and
computes nothing: their pace (once a ``g``); ``still`` the walk without its
copies, over whatever its buffers hold: the arithmetic alone; ``parent``,
``parent-copy`` and ``parent-still`` (page 1 named for every tile, so that
no block moves after the first), where ``"parent": "<a checkout's root>"``
names one (``git archive`` of the parent commit under ``_checkouts/``), that
checkout's own kernel over its own grid. ``"compile_only": true`` compiles
every form for a described v5e here, without the chip. Every line is also
appended to ``chiprun_out/mla_decode_bench.jsonl``.
"""

import importlib.util
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import kernel_cost_mla  # noqa: E402
from benchmark.lib.peaks import device_peaks  # noqa: E402
from deepspeed_tpu.ops.pallas import decode_attention as da  # noqa: E402
from scripts.ssm_decode_bench import kernel_ms  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
NOTES = ((4096, 8192, 16384), (512, 1024))
# slots, heads, row width, rank, table width, pages, layers, ring, rows a
# selection keeps, (prompts, outputs)
SHAPES = {
    "long-decode": (128, 128, 640, 512, 48, 6145, 5, None, 0,
                    ((1024, 1536, 2048), (512, 768, 1024))),
    "notes-select": (32, 128, 640, 512, 272, 8705, 2, None, 2048, NOTES),
    "notes-ring": (32, 64, 1152, 1024, 9, 288, 3, (576, 513), 0, NOTES),
}
PS, ROPE = 64, 64
PEAKS = device_peaks("TPU v5 lite")


def _parent_copy_kernel(*refs, group, **_):
    """The parent's blocks, nothing computed: a row of every tile summed into
    the output, so that each is waited for. Its page tiles are the ``group``
    refs before the output and the three accumulators."""
    o_ref = refs[-4]
    rank = o_ref.shape[-1]
    o_ref[0] = jnp.broadcast_to(
        sum(r[0, :1, :rank].astype(jnp.float32)
            for r in refs[-4 - group:-4]), o_ref.shape[1:]).astype(o_ref.dtype)


def _copy_kernel(_lens, _starts, _rows, page_ref, layer_ref, n_ref, _q,
                 *refs, group, page_size, masked=False, **_):
    """The walk's copies (``_mla_fetch``), nothing computed: a row of the
    arrived item's tiles goes to the output."""
    pool_ref, o_ref, buf, sem = refs[masked:][:4]
    w = pl.program_id(0)
    da._mla_fetch(w, n_ref[0], page_ref, layer_ref, pool_ref, buf, sem,
                  group=group, page_size=page_size)
    o_ref[0] = jnp.broadcast_to(
        buf[jax.lax.rem(w, 3), :1, :o_ref.shape[-1]].astype(jnp.float32),
        o_ref.shape[1:]).astype(o_ref.dtype)


def _lengths(rng, slots, mix):
    prompts, outputs = mix
    b = np.arange(slots)
    prompt = np.asarray(prompts)[b % len(prompts)]
    out = np.asarray(outputs)[(b // len(prompts)) % len(outputs)]
    return (prompt + (rng.uniform(size=slots) * out).astype(np.int64) + 1
            ).astype(np.int32)


def _load(root):
    at = importlib.util.spec_from_file_location(
        da.__package__ + "._parent_decode_attention", os.path.join(
            root, os.path.relpath(da.__file__, REPO)))
    module = importlib.util.module_from_spec(at)
    at.loader.exec_module(module)
    return module


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
    kept = os.path.join(REPO, "chiprun_out", "mla_decode_bench.jsonl")
    os.makedirs(os.path.dirname(kept), exist_ok=True)

    def say(line):      # a call shows the end of its output only
        print(json.dumps(line), flush=True)
        with open(kept, "a") as f:
            f.write(json.dumps(line) + "\n")

    rng = np.random.default_rng(spec.get("seed", 0))
    walk, fetch, asked, asked_sub = (da._mla_kernel, da._mla_fetch,
                                     da.mla_pages_per_step, da._mla_sub_tile)
    parent = _load(spec["parent"]) if spec.get("parent") else None
    parent_walk = parent and parent._mla_kernel
    for name in spec.get("shapes", list(SHAPES)):
        B, H, C, rank, width, P, L, ring, topk, mix = SHAPES[name]
        lens = _lengths(rng, B, mix)
        held = np.minimum(lens, ring[0]) if ring else lens
        live = int((-(-held // PS)).sum())
        attended = (np.minimum(lens, ring[1]) if ring
                    else np.minimum(lens, topk) if topk else lens)
        if ring:
            tables = np.arange(B * width).reshape(B, width)
        else:
            tables = (rng.permutation(B * width) + 1).reshape(B, width)
        allowed = None
        if topk:    # the rows a selection kept: topk of the live ones
            allowed = np.zeros((B, width * PS), np.int32)
            for b, n in enumerate(lens):
                allowed[b, rng.permutation(int(n))[:topk]] = 1
            allowed = jnp.asarray(allowed)
        lens_d, tables_d = jnp.asarray(lens), jnp.asarray(tables, jnp.int32)
        cost = kernel_cost_mla.paged_decode_mla(
            float(attended.sum()), H, rank, ROPE)
        own = asked(PS, C, BF16, width, ring is not None)
        own_sub = asked_sub(own, PS)
        forms = [(f, 0, 0) for f in ("parent", "parent-copy", "parent-still")
                 if parent]
        for g in spec.get("groups", [4, 8]):    # sub = g: one link a step
            subs = sorted({s for s in spec.get("subs", [2]) if s < g} | {g})
            forms += [(form, g, sub) for sub in subs
                      for form in ("walk", "still", "copy")
                      if g <= width and (form != "copy" or sub == subs[0])]
        forms = [f for f in forms + [("shipped", own, own_sub)]
                 if f[0] in spec.get("forms", [
                     "parent", "parent-copy", "parent-still", "walk",
                     "shipped", "copy", "still"])]
        q_shape, pool_shape = (B, 1, H, C), (L, 1, P, PS, C)
        for passes in spec.get("passes", [2]):
            q_dt = F32 if passes == 2 else BF16
            if not compile_only:
                key = jax.random.split(
                    jax.random.PRNGKey(spec.get("seed", 0)), 2)
                pool = jax.random.normal(key[0], pool_shape, BF16)
                # scores of a few units, as a trained model's: a softmax
                # that neither one row wins nor all share
                q = jax.random.normal(key[1], q_shape, q_dt)
                gathered = np.asarray(da.paged_decode_mla(
                    q, pool, lens_d, tables_d, rank, C ** -0.5,
                    impl="gather", layer=jnp.int32(L - 1), ring=ring,
                    allowed=allowed, out_dtype=F32), np.float32)
            first = None
            for form, g, sub in forms:
                of_parent = form.startswith("parent")
                mod = parent if of_parent else da
                if of_parent:
                    parent._mla_kernel = (
                        _parent_copy_kernel if form == "parent-copy"
                        else parent_walk)
                    most = max(1, parent._MLA_PAGES_PER_STEP * 640
                               // max(C, 640))
                    g = max(x for x in range(1, most + 1) if width % x == 0)
                    items = B * (width // g)
                    live_steps = int((-(-held // (g * PS))).sum())
                    still = dict(block_tables=jnp.ones_like(tables_d))
                    kw = {}
                else:
                    da.mla_pages_per_step = lambda *a, g=g: g
                    da._mla_sub_tile = lambda *a, sub=sub: sub
                    da._mla_kernel = (_copy_kernel if form == "copy"
                                      else walk)
                    da._mla_fetch = ((lambda *a, **kw: None)
                                     if form == "still" else fetch)
                    cap = jnp.minimum(lens_d, ring[0]) if ring else lens_d
                    work = da.paged_work_list(
                        cap, tables_d, PS, g)._replace(lens=lens_d)
                    items = live_steps = int(work.n_items)
                    kw = dict(work=work)
                if form == "parent-still":
                    kw.update(still)
                tbl = kw.pop("block_tables", tables_d)
                fn = jax.jit(lambda q, pool, mod=mod, kw=kw, tbl=tbl: (
                    mod.paged_decode_mla(
                        q, pool, lens_d, tbl, rank, C ** -0.5,
                        impl="kernel", layer=jnp.int32(L - 1), ring=ring,
                        allowed=allowed, out_dtype=F32, **kw)))
                line = dict(shape=name, form=form, passes=passes, g=g,
                            sub=sub, steps=items, live_steps=live_steps,
                            live_pages=live,
                            fill_pct=round(100.0 * live / (items * g), 2))
                if compile_only:
                    fn.lower(*(jax.ShapeDtypeStruct(s, d, **place)
                               for s, d in ((q_shape, q_dt),
                                            (pool_shape, BF16)))).compile()
                    say(dict(line, compiled=True))
                    continue
                got = np.asarray(fn(q, pool), np.float32)
                trace_dir = os.path.join(REPO, "chiprun_out",
                                         ".mla_decode_trace")
                shutil.rmtree(trace_dir, ignore_errors=True)
                with jax.profiler.trace(trace_dir):
                    for _ in range(reps):
                        out = fn(q, pool)
                    out.block_until_ready()
                ms, calls = kernel_ms(trace_dir, "paged_decode_mla")
                shutil.rmtree(trace_dir, ignore_errors=True)
                line.update(
                    kernel_ms=round(ms, 4), calls=calls,
                    us_live_step=round(ms * 1e3 / live_steps, 4),
                    gbs=round(cost.bytes / ms / 1e6, 1),
                    roofline_pct=round(
                        cost.floor_s(PEAKS) / ms * 1e5, 2))
                if form in ("parent", "walk", "shipped"):
                    if first is None:
                        first = got
                    line["bit_equal_to_the_first"] = bool(
                        (got == first).all())
                    line["far_from_the_first"] = float(
                        np.abs(got - first).max())
                    line["far_from_gather"] = float(
                        np.abs(got - gathered).max())
                say(line)
            da.mla_pages_per_step, da._mla_kernel = asked, walk
            da._mla_sub_tile, da._mla_fetch = asked_sub, fetch
            if parent:
                parent._mla_kernel = parent_walk


if __name__ == "__main__":
    main()
