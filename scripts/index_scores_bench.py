"""Times ``ops/pallas/index_scores`` alone at the two shapes
``dots3-note-serve.long-notes`` calls it with, part by part (through
``chiprun``; a TPU only).

    chiprun -- python scripts/index_scores_bench.py ['{"shapes": ["notes-decode"], "forms": ["walk", "still", "copy", "xla"]}']

A shape is one full layer's call of the cell: ``notes-decode`` a decode step
(32 slots, one query each, 64 heads of 128, float32 keys in pages of 64 under
tables 272 wide, every slot at a length drawn from the cell's prompts and
outputs), ``notes-chunk`` a chunk of 1,024 queries of one request at each of
``chunk_live`` live keys (the chunk's own included). Prints, a shape and
form: the kernel's milliseconds a call, read off a trace of its own (``xla``:
the wall time a call, its operations being many); the grid's steps; us a
step; GB/s over the live keys' bytes (once a tile of queries) and the share
of the floor (six-pass operations over 197 TFLOP/s or those bytes over 819
GB/s, whichever is larger); how far from the plain form over gathered keys.
Forms: ``walk`` the kernel as shipped; ``still`` the same walk with page 1
named for every tile, so that no block moves after the first: the
arithmetic alone; ``copy`` a kernel that takes the walk's blocks and computes
nothing: their pace; ``xla`` what the call sites ran before the kernel (a
decode step: the slots' keys gathered through the table, then
``models/gpt._index_scores``; a chunk: the table's keys gathered whole, then
the plain form a block of 1,024 keys at a time up to the live ones).
``queries`` / ``keys`` try other tiles than the module's.
``"compile_only": true`` compiles every form for a described v5e here,
without the chip. Every line is also appended to
``chiprun_out/index_scores_bench.jsonl``.
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

from benchmark.lib.peaks import device_peaks  # noqa: E402
from deepspeed_tpu.models import gpt  # noqa: E402
from deepspeed_tpu.ops.pallas import decode_attention as da  # noqa: E402
from deepspeed_tpu.ops.pallas import index_scores as ix  # noqa: E402
from scripts.mla_decode_bench import NOTES, PS, _lengths  # noqa: E402
from scripts.ssm_decode_bench import kernel_ms  # noqa: E402

F32 = jnp.float32
HEADS, DIM, WIDTH, PAGES, LAYERS = 64, 128, 272, 8705, 2
PEAKS = device_peaks("TPU v5 lite")


def _copy_decode(_lens, _starts, _rows, _pages, _layer, _q, _w, *refs,
                 group, **_):
    """The walk's blocks, nothing computed: a row of every tile goes to the
    output, so that each is waited for."""
    o_ref = refs[-1]
    o_ref[0] = jnp.concatenate([r[0, :1, :PS] for r in refs[:group]], axis=1)


def _copy_chunk(_lens, _starts, _rows, _pages, _layer, _q, _w, *refs,
                group, **_):
    o_ref = refs[-1]
    row = jnp.concatenate([r[0, :1, :PS] for r in refs[:group]], axis=1)
    o_ref[0] = jnp.broadcast_to(row, o_ref.shape[1:])


def _xla(q, weights, pool, lens, tables, layer):
    """The call sites' form before the kernel."""
    B, T = q.shape[:2]
    S = tables.shape[1] * PS
    keys = pool[layer, 0][tables].reshape(B, S, -1)
    if T == 1:
        live = jnp.arange(S)[None, :] < lens[:, None]
        return jnp.where(live[:, None], gpt._index_scores(q, weights, keys),
                         -jnp.inf)
    block = 1024

    def body(j, scores):
        keys_j = jax.lax.dynamic_slice_in_dim(keys, j * block, block, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            scores, gpt._index_scores(q, weights, keys_j), j * block, 2)

    return jax.lax.fori_loop(0, -(-lens[0] // block), body,
                             jnp.full((B, T, S), -jnp.inf, F32))


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
    kept = os.path.join(REPO, "chiprun_out", "index_scores_bench.jsonl")
    os.makedirs(os.path.dirname(kept), exist_ok=True)

    def say(line):      # a call shows the end of its output only
        print(json.dumps(line), flush=True)
        with open(kept, "a") as f:
            f.write(json.dumps(line) + "\n")

    rng = np.random.default_rng(spec.get("seed", 0))
    shipped = (ix._KEYS, ix._QUERIES)
    bodies = (ix._decode_kernel, ix._chunk_kernel)
    cases = []
    for name in spec.get("shapes", ["notes-decode", "notes-chunk"]):
        if name == "notes-decode":
            cases.append((name, 32, 1, _lengths(rng, 32, NOTES)))
        else:
            cases += [(name, 1, 1024, np.asarray([n], np.int32))
                      for n in spec.get("chunk_live", [1024, 6144, 16384])]
    pool_shape = (LAYERS, 1, PAGES, PS, DIM)
    if not compile_only:
        pool = jax.random.normal(jax.random.PRNGKey(spec.get("seed", 0)),
                                 pool_shape, F32)
    for name, B, T, lens in cases:
        tables = jnp.asarray(
            (rng.permutation(B * WIDTH) + 1).reshape(B, WIDTH), jnp.int32)
        lens_d, layer = jnp.asarray(lens), jnp.int32(LAYERS - 1)
        shapes = ((B, T, HEADS, DIM), (B, T, HEADS))
        live, tiles = int(lens.sum()), -(-T // ix._QUERIES)
        # six bf16 passes a float32 product; the live keys once a tile of
        # queries
        floor_s = max(6 * 2.0 * T * HEADS * DIM * live / PEAKS.bf16_flops,
                      live * DIM * 4.0 * tiles / PEAKS.hbm_bytes_per_s)
        want = None
        if not compile_only:
            key = jax.random.split(jax.random.PRNGKey(1 + len(lens)), 2)
            q = jax.random.normal(key[0], shapes[0], F32)
            weights = jax.random.normal(key[1], shapes[1], F32) / 8
        tried = [shipped] + [
            (k, tq) for k in spec.get("keys", [shipped[0]])
            for tq in (spec.get("queries", [shipped[1]]) if T > 1
                       else [shipped[1]]) if (k, tq) != shipped]
        for form in spec.get("forms", ["xla", "walk", "still", "copy"]):
            for tile in (tried if form != "xla" else [shipped]):
                ix._KEYS, ix._QUERIES = tile
                ix._decode_kernel, ix._chunk_kernel = (
                    (_copy_decode, _copy_chunk) if form == "copy" else bodies)
                group = ix.index_pages_per_step(PS, WIDTH)
                work = da.paged_work_list(lens_d, tables, PS, group)
                steps = int(work.n_items) * -(-T // ix._QUERIES)
                if form == "still":
                    work = work._replace(pages=jnp.ones_like(work.pages))
                if form == "xla":
                    fn = jax.jit(lambda q, w, pool: _xla(
                        q, w, pool, lens_d, tables, layer))
                else:
                    fn = jax.jit(lambda q, w, pool, work=work: (
                        ix.index_scores(q, w, pool, lens_d, tables, layer,
                                        work=work)))
                line = dict(shape=name, form=form, live=live, keys=tile[0],
                            queries=tile[1] if T > 1 else 1, steps=steps)
                if compile_only:
                    fn.lower(*(jax.ShapeDtypeStruct(s, F32, **place)
                               for s in shapes + (pool_shape,))).compile()
                    say(dict(line, compiled=True))
                    continue
                got = np.asarray(fn(q, weights, pool))
                trace_dir = os.path.join(REPO, "chiprun_out",
                                         ".index_scores_trace")
                shutil.rmtree(trace_dir, ignore_errors=True)
                with jax.profiler.trace(trace_dir):
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        out = fn(q, weights, pool)
                    out.block_until_ready()
                    wall = (time.perf_counter() - t0) / reps * 1e3
                ms, calls = kernel_ms(trace_dir, "index_scores")
                shutil.rmtree(trace_dir, ignore_errors=True)
                if form == "xla":
                    ms, want = wall, got
                line.update(
                    kernel_ms=round(ms, 4), calls=calls,
                    wall_ms=round(wall, 4),
                    us_step=round(ms * 1e3 / max(steps, 1), 4),
                    gbs=round(live * DIM * 4.0 * tiles / ms / 1e6, 1),
                    floor_pct=round(floor_s / ms * 1e5, 2))
                if form == "walk" and want is not None:
                    seen = np.isfinite(want)
                    line["far_from_xla"] = float(
                        np.abs(got[seen] - want[seen]).max())
                    line["spread_of_xla"] = float(want[seen].std())
                    line["inf_alike"] = bool(
                        (np.isfinite(got) == seen).all())
                say(line)
        ix._KEYS, ix._QUERIES = shipped
        ix._decode_kernel, ix._chunk_kernel = bodies


if __name__ == "__main__":
    main()
