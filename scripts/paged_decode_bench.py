"""Times ``ops/pallas/decode_attention.paged_decode_attention`` alone at the
two cells' shapes and lengths, a grid step a GROUP of a request's pages on
the MXU beside the step a page on the VPU (through ``chiprun``; a TPU only).

    chiprun -- python scripts/paged_decode_bench.py ['{"shapes": ["batch-decode"], "groups": [1, 2, 4], "parent": "_checkouts/parent"}']

A shape is one layer's call of a cell: slots, heads of 128, the table's
width, the pool's pages, the types, and the cell's prompts and outputs, from
which every slot draws a length as ``scripts/gqa_decode_bench.py`` draws it
(the stack holds ``LAYERS`` layers, of which a call reads the last).
Prints, a shape, form and group ``g``: the kernel's milliseconds a call,
read off a trace of its own (the ``paged_decode`` events of the device's
operation line); the grid's steps; us a live page and a step; GB/s over the
live rows' bytes and their share of the v5e's 819 GB/s
(``benchmark/lib/kernel_cost.paged_decode``); the live pages over the page
tiles the groups score; how far the output lies from the first form's and
from ``_paged_gather_attention``. Forms: ``walk`` the shipped kernel
(``_gqa_kernel`` at a group of one query over ``paged_held_list``, whose
tiles past a request's end stand still) at each of ``groups`` and at
``paged_pages_per_step``'s own answer (``shipped``); ``walk-repeat`` the
same over ``paged_work_list``, whose tiles past a request's end name its
last page again and are copied again; ``copy`` and ``copy-repeat`` a kernel
with the same blocks that computes nothing, the pace of the tiles' copies
over either list; ``still`` the walk with page 1 named for every tile, so
that no block moves after the first: the arithmetic alone; ``one-pass`` the
walk with a bfloat16 query and the probabilities rounded once
(``paged_decode_gqa`` at as many key-value heads: its pace only);
``stand`` the form ISSUE 54 asked for first and the bench overturned
(``_stand_kernel`` below: the query stands as the MXU's weights and a
head's rows stream past it, ``p * v`` on the VPU), and ``stand-still``;
``vpu`` the step a page with both products on the VPU (``_paged_kernel``,
what a quantized pool and heads of 64 still take, and what every call took
before PR 54), ``vpu-still`` the same standing still, and
``vpu-still-no-k`` / ``-no-exp`` / ``-no-pv`` with one stage of it taken
out (the K product and its lane reduction; the exponentials; ``p * v`` and
its sum): what each stage holds of the step; ``one`` a call of one slot of
one live page: what a call costs beside its steps; ``parent``, where
``"parent": "<a checkout's root>"`` names one (``git archive`` of the parent
commit under ``_checkouts/``), that checkout's own kernel over its own list.
``"compile_only": true`` compiles every form for a described v5e here,
without the chip.
"""

import functools
import importlib.util
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import kernel_cost  # noqa: E402
from deepspeed_tpu.ops.pallas import decode_attention as da  # noqa: E402
from deepspeed_tpu.ops.pallas.flash_attention import NEG_INF  # noqa: E402
from scripts.gqa_decode_bench import _copy_kernel, _lengths  # noqa: E402
from scripts.ssm_decode_bench import kernel_ms  # noqa: E402

BF16 = jnp.bfloat16
# slots, heads, table width, pages, (prompts, outputs): a layer's call of
# pythia-1.4b-serve.batch-decode (24 cache layers) and of
# ouro-2.6b-serve.reason-decode (192)
SHAPES = {
    "batch-decode": (96, 16, 32, 481, (
        (64, 96, 128, 160, 192, 224, 256), (16, 32, 48, 64, 80))),
    "reason-decode": (10, 16, 12, 81, ((64, 96, 128), (256, 384, 512))),
}
DH, PS, LAYERS = 128, 64, 4
HBM_BYTES_S = 819e9
SUBLANES = 8
FORMS = ["parent", "vpu", "vpu-still", "vpu-still-no-k", "vpu-still-no-exp",
         "vpu-still-no-pv", "walk", "walk-repeat", "copy", "copy-repeat",
         "still", "stand", "stand-still", "shipped", "one-pass", "one"]


def _vpu_kernel(len_ref, start_ref, row_ref, _page_ref, _layer_ref, q_ref,
                k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, sm_scale,
                page_size, heads, skip, **_):
    """``_paged_kernel``'s step over a dense pool, one stage left out:
    ``skip`` = "k" (the scores are K's first lane: no product, no
    reduction over lanes), "exp" (the probabilities are the scores less the
    maximum), "pv" (the accumulator takes the probabilities' sum)."""
    w = pl.program_id(1)
    b = row_ref[w]
    cur = len_ref[b]
    i = w - start_ref[b]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * page_size < cur)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale
        v = v_ref[:, 0].astype(jnp.float32)
        if skip == "k":
            s = k_ref[:, 0, :, :1].astype(jnp.float32)
        else:
            s = jnp.sum(q[:, None, :] * k_ref[:, 0].astype(jnp.float32),
                        axis=-1, keepdims=True)
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (heads, page_size, 1), 1)
        s = jnp.where(pos < cur, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        if skip == "exp":
            alpha, p = m_prev - m_new, s - m_new
        else:
            alpha, p = jnp.exp(m_prev - m_new), jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        if skip == "pv":
            acc_ref[...] = acc_ref[...] * alpha + l_ref[...] + v[:, :1]
        else:
            acc_ref[...] = (acc_ref[...] * alpha
                            + jnp.sum(p * v, axis=1, keepdims=True))

    @pl.when((i + 1) * page_size >= cur)
    def _finalize():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l_safe)[:, 0, :].astype(o_ref.dtype)


def _stand_call(q, k_pages, v_pages, _lens, _tables, scale, layer, work):
    """The ``pallas_call`` of :func:`_stand_kernel`: the blocks of
    :func:`paged_decode_attention`'s, ``group`` tiles of K and of V a
    step, a head's query and output a row of their own."""
    B, _, H, Dh = q.shape
    page_size = k_pages.shape[-2]
    group = work.pages.shape[0] // work.rows.shape[0]
    heads = da._heads_per_step(H, page_size, Dh, k_pages.dtype.itemsize)

    def kv_spec(j):     # tile j of item w: the page the list names for it
        return pl.BlockSpec(
            (None, heads, 1, page_size, Dh),
            lambda hb, w, lens, starts, rows, pages, layer: (
                layer[0], hb, pages[w * group + j], 0, 0))

    qo_spec = pl.BlockSpec(
        (1, 1, heads, 1, Dh),
        lambda hb, w, lens, starts, rows, *_p: (rows[w], hb, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,      # lens, starts, rows, pages, layer
        grid=(H // heads, work.n_items),
        in_specs=[qo_spec] + 2 * [kv_spec(j) for j in range(group)],
        out_specs=qo_spec,
        scratch_shapes=[pltpu.VMEM((heads, SUBLANES, Dh), jnp.float32)] * 3,
    )
    kernel = functools.partial(_stand_kernel, sm_scale=scale,
                               page_size=page_size, group=group)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H // heads, heads, 1, Dh),
                                       q.dtype),
        interpret=da._interpret(),
        name="paged_decode",
    )(work.lens, work.starts, work.rows, work.pages,
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(B, H // heads, heads, 1, Dh),
      *([k_pages] * group), *([v_pages] * group))
    return out.reshape(B, 1, H, Dh)


def _stand_kernel(len_ref, start_ref, row_ref, _page_ref, _layer_ref,
                         q_ref, *refs, sm_scale: float, page_size: int,
                         group: int):
    """One (block of heads, work item) step of the online softmax where a
    query head has a key head of its own: item ``w`` is table slots ``group
    i .. group i + group - 1`` of request ``b = row_ref[w]``, ``i = w -
    start_ref[b]``; ``refs`` are their tiles [heads, page_size, 128],
    ``group`` of K then ``group`` of V, then the output and the
    accumulators.

    The scores are a product the MXU streams: head ``h``'s query stands as
    the weights, its 128 numbers down every one of 128 columns, and the
    head's rows of the group's pages pass through, so a row's score comes
    out on all 128 lanes at once, rows on the sublanes: what ``p * v`` wants
    beside a row of V, with no reduction over lanes and no broadcast back.
    The second product and the sums stay float32 on the VPU. The running
    maximum, sum and accumulator are kept a SUBLANE apart ([heads, 8, 128]:
    row ``r`` of a page belongs to ``r mod 8``), eight softmaxes a head that
    never meet inside a step: a step has no reduction over sublanes either,
    and the last one of a request folds the eight into one. The heads are a
    loop (a head's scores, values and sums are a few vectors, which stay in
    registers), not sixteen bodies. A page past the request's end is masked
    whole and changes nothing."""
    k_refs, v_refs = refs[:group], refs[group:2 * group]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * group:]
    heads, lanes = q_ref.shape[2], q_ref.shape[4]
    w = pl.program_id(1)
    b = row_ref[w]
    cur = len_ref[b]
    first = (w - start_ref[b]) * (group * page_size)    # the item's first row

    @pl.when(first == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def apart(a):       # [page_size, 128] -> its rows a sublane apart
        return [a[at:at + SUBLANES] for at in range(0, page_size, SUBLANES)]

    @pl.when(first < cur)  # the one item of an empty row: no work
    def _tiles():
        kind = jnp.promote_types(q_ref.dtype, k_refs[0].dtype)
        row = first + jax.lax.broadcasted_iota(jnp.int32,
                                               (page_size, lanes), 0)

        def head(h, _):
            stands = jnp.broadcast_to(q_ref[0, 0, h].astype(jnp.float32),
                                      (lanes, lanes)).astype(kind)
            scores, m_prev = [], m_ref[h]
            for j, k_ref in enumerate(k_refs):
                k = k_ref[h, 0].astype(kind)            # [ps, Dh]
                s = jax.lax.dot_general(
                    k, stands, (((1,), (1,)), ((), ())), precision=da._exact(k),
                    preferred_element_type=jnp.float32) * sm_scale
                scores += apart(jnp.where(row + j * page_size < cur, s,
                                          NEG_INF))
            m = functools.reduce(jnp.maximum, scores, m_prev)
            alpha = jnp.exp(m_prev - m)
            l, acc = alpha * l_ref[h], alpha * acc_ref[h]
            values = [r for v_ref in v_refs
                      for r in apart(v_ref[h, 0].astype(jnp.float32))]
            for s, v in zip(scores, values):
                p = jnp.exp(s - m)
                l, acc = l + p, acc + p * v
            m_ref[h], l_ref[h], acc_ref[h] = m, l, acc
            return _

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(first + group * page_size >= cur)  # the request's last item
    def _finalize():
        m = m_ref[...]
        share = jnp.exp(m - jnp.max(m, axis=1, keepdims=True))
        l = jnp.sum(share * l_ref[...], axis=1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (jnp.sum(share * acc_ref[...], axis=1, keepdims=True)
                       / l_safe).astype(o_ref.dtype)


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
    seed = spec.get("seed", 0)
    own = {n: getattr(da, n) for n in (
        "_paged_on_mxu", "paged_pages_per_step", "gqa_pages_per_step",
        "_gqa_kernel", "_paged_kernel", "_paged_group_call")}
    parent = None
    if spec.get("parent"):      # that checkout's kernel, beside this tree's
        at = importlib.util.spec_from_file_location(
            da.__package__ + "._parent_decode_attention", os.path.join(
                spec["parent"], os.path.relpath(da.__file__, REPO)))
        parent = importlib.util.module_from_spec(at)
        at.loader.exec_module(parent)
    for name in spec.get("shapes", list(SHAPES)):
        B, H, width, P, mix = SHAPES[name]
        pool_shape = (LAYERS, H, P, PS, DH)
        asked = da.paged_pages_per_step(H, PS, DH, BF16, width)
        forms = [("parent", 1)] if parent else []
        forms += [(f, 1) for f in FORMS if f.startswith("vpu")]
        forms += [(form, g) for g in spec.get("groups", [1, 2, 4])
                  if g <= width for form in FORMS[6:13]]
        forms += [(f, asked) for f in FORMS[13:]]
        forms = [f for f in forms if f[0] in spec.get("forms", FORMS)]
        if not compile_only:
            key = jax.random.split(jax.random.PRNGKey(seed), 3)
            k = jax.random.normal(key[0], pool_shape, BF16)
            v = jax.random.normal(key[1], pool_shape, BF16)
        first = None
        for form, g in forms:
            slots = 1 if form == "one" else B
            lens = (np.ones(1, np.int32) if form == "one"
                    else _lengths(np.random.default_rng(seed), B, mix))
            live = int((-(-lens // PS)).sum())
            tables = np.zeros((slots, width), np.int32)     # 0: the sink
            held = iter(np.random.default_rng(seed + 1).permutation(P - 1)
                        + 1)
            for b, n in enumerate(-(-lens // PS)):
                tables[b, :n] = [next(held) for _ in range(n)]
            lens_d, tables_d = jnp.asarray(lens), jnp.asarray(tables,
                                                              jnp.int32)
            cost = kernel_cost.paged_decode(float(lens.sum()), H, DH, 2)
            q_shape = (slots, 1, H, DH)
            da._paged_on_mxu = ((lambda *a: False) if form.startswith("vpu")
                                 else own["_paged_on_mxu"])
            da.paged_pages_per_step = da.gqa_pages_per_step = (
                lambda *a, g=g: g)
            da._gqa_kernel = (_copy_kernel if form.startswith("copy")
                              else own["_gqa_kernel"])
            da._paged_kernel = (functools.partial(
                _vpu_kernel, skip=form.rsplit("-", 1)[-1])
                if "-no-" in form else own["_paged_kernel"])
            da._paged_group_call = (_stand_call if form.startswith("stand")
                                    else own["_paged_group_call"])
            listed = (da.paged_work_list if form.endswith("-repeat")
                      or form == "one-pass" else da.paged_held_list)
            work = listed(lens_d, tables_d, PS, g)
            items = int(work.n_items)
            copied = int(sum(       # a tile whose page stands still is not
                (np.diff(column, prepend=-1) != 0).sum()   # copied again
                for column in np.asarray(work.pages).reshape(
                    -1, g)[:items].T))
            if "still" in form:
                work = work._replace(pages=jnp.ones_like(work.pages))
            layer = jnp.int32(LAYERS - 1)
            if form == "parent":
                def call(q, k, v):
                    return parent.paged_decode_attention(
                        q, k, v, lens_d, tables_d, impl="kernel",
                        layer=layer)
            else:
                def call(q, k, v, work=work, fn=(
                        da.paged_decode_gqa if form == "one-pass"
                        else da.paged_decode_attention)):
                    return fn(q, k, v, lens_d, tables_d, impl="kernel",
                              layer=layer, work=work)
            fn = jax.jit(call)
            line = dict(shape=name, form=form, g=g, steps=items,
                        live_pages=live, tiles_copied=copied,
                        fill_pct=round(100.0 * live / (items * g), 2))
            if compile_only:
                fn.lower(*(jax.ShapeDtypeStruct(s, BF16, **place) for s in (
                    q_shape, pool_shape, pool_shape))).compile()
                print(json.dumps(dict(line, compiled=True)), flush=True)
                continue
            q = jax.random.normal(key[2], q_shape, BF16)
            got = np.asarray(fn(q, k, v), np.float32)
            trace_dir = os.path.join(REPO, "chiprun_out",
                                     ".paged_decode_trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            with jax.profiler.trace(trace_dir):
                for _ in range(reps):
                    out = fn(q, k, v)
                out.block_until_ready()
            ms, calls = kernel_ms(trace_dir, "paged_decode")
            shutil.rmtree(trace_dir, ignore_errors=True)
            line.update(
                kernel_ms=round(ms, 4), calls=calls,
                us_page=round(ms * 1e3 / live, 4),
                us_step=round(ms * 1e3 / items, 4),
                gbs=round(cost.bytes / ms / 1e6, 1),
                roofline_pct=round(cost.bytes / HBM_BYTES_S / ms * 1e5, 2))
            if form in ("parent", "vpu", "walk", "walk-repeat", "stand",
                        "shipped", "one-pass"):
                if first is None:
                    first = got
                gathered = np.asarray(da.paged_decode_attention(
                    q, k, v, lens_d, tables_d, impl="gather", layer=layer),
                    np.float32)
                line["far_from_the_first"] = float(np.abs(got - first).max())
                line["far_from_gather"] = float(
                    np.abs(got - gathered).max())
            print(json.dumps(line), flush=True)
        for n, f in own.items():
            setattr(da, n, f)


if __name__ == "__main__":
    main()
