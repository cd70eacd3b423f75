"""The indexer's scores of a model that SELECTS the rows its full layers read
(``GPTConfig.index_topk``), against the index keys where they lie: a cache
layer's pages of the index-key pool, reached through the block table.

``index_scores(q [B, T, Hi, Di], weights [B, T, Hi], pool [L, 1, P,
page_size, Di], lengths [B], block_tables [B, W], layer)``: ``I(t, s) = sum_j
w_tj relu(qI_tj . kI_s)``, float32 ``[B, T, W x page_size]``, ``-inf`` at and
past a row's ``lengths`` (the keys it holds). What ``models/gpt._index_scores``
computes over keys gathered into one array, which stays the plain form and
this kernel's check. Two things are the kernel's point:

- A tile of per-head scores lives and dies in VMEM. The plain form writes
  ``[T, Hi, keys]`` float32 products to HBM and reads them back for the
  weighted sum over the heads: 268 MB a block of 1,024 keys at a chunk of
  1,024 queries and 64 heads, for 4 MB of result. Here a grid step forms the
  heads' products against one group of pages, applies ``relu`` and the head's
  weight and sums over the heads in float32, and writes the sum alone.
- Only live keys are read, from their pages. The grid walks the batch's live
  groups of pages (``decode_attention.paged_work_list(.., group=)``, a traced
  bound), the key tiles' block indices come from the list, and the output is
  aliased onto a ``-inf`` fill, so a place past a row's last live group is
  never visited: no ``pool[tables]`` gather of every place of the table.

Products at full precision where the pool is float32 (``Precision.HIGHEST``:
six bf16 passes, as the plain form's), one pass in the pool's type where it
is bf16; the weighting and the sum over the heads in float32 on the VPU.

Two bodies under one name, chosen by ``T``. A decode step (``T`` = 1 a
slot): the heads are the product's rows, ``[Hi, Di] x [Di, keys]``, the sum
over the heads a reduction over sublanes. A chunk (``T`` queries of a
request, padded to whole tiles): a tile of queries against the group's keys
a head at a time, ``[keys, Di] x [Di, queries]``, the head's weights a row
broadcast over sublanes and the sum over the heads plain additions of
tiles, transposed once a step when every head is in.
Forward only: serving.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (PagedWork, _as_lengths, _exact, _interpret,
                               paged_work_list)

# keys a grid step takes (eight pages of 64: 256 KiB of float32 keys, the
# size ``paged_decode_mla`` found right for its copies) and queries a tile of
# a chunk, in whole lanes of 128. A chunk's step is bound by the MXU, not by
# its copies (``scripts/index_scores_bench.py`` on the v5e, PR 56: ``still``
# reads what ``walk`` reads, ``copy`` a hundredth), and a head's product
# fills it better the larger it is: against 512 keys, tiles of 128 / 256 /
# 512 queries read 75.6 / 79.2 / 81.7% of the six passes' floor (links of 128
# keys 46.7%); at 64 heads of 128 a tile of 512 float32 queries is 16 MiB,
# twice for the pipeline. A decode step's item takes 0.72 us, its copies 0.49
_KEYS, _QUERIES, _LANES = 512, 512, 128


def index_pages_per_step(page_size: int, table: int) -> int:
    """Pages of a request a grid step takes over block tables ``table`` wide:
    ``_KEYS`` keys' worth, no more than the table holds."""
    return max(1, min(_KEYS // page_size, table))


def index_scores(
    q: jnp.ndarray,             # [B, T, Hi, Di]
    weights: jnp.ndarray,       # [B, T, Hi] float32
    pool: jnp.ndarray,          # [L, 1, P, page_size, Di] the index keys
    lengths: jnp.ndarray,       # [B] int32: the keys a row holds
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32 page ids (pad: 0)
    layer,                      # the cache layer, traced
    work: Optional[PagedWork] = None,
) -> jnp.ndarray:
    """Float32 [B, T, pages_per_seq x page_size] (the module docstring).
    ``lengths``: the new token's key and the chunk's own included.
    ``work``: ``paged_work_list(lengths, block_tables, page_size,
    index_pages_per_step(..))`` where the caller has it."""
    B, T, Hi, Di = q.shape
    if pool.ndim != 5 or pool.shape[1] != 1 or pool.shape[-1] != Di \
            or weights.shape != (B, T, Hi):
        raise ValueError(
            f"an index-key pool is [L, 1, P, page_size, Di], Di the queries' "
            f"width {Di}, the weights [B, T, Hi]: got {pool.shape}, q "
            f"{q.shape} and weights {weights.shape}")
    ps, width = pool.shape[-2], block_tables.shape[1]
    tables = jnp.asarray(block_tables, jnp.int32)
    group = index_pages_per_step(ps, width)
    tokens, groups = group * ps, -(-width // group)
    if work is None:
        work = paged_work_list(_as_lengths(lengths, B), tables, ps, group)
    elif work.pages.shape[0] != work.rows.shape[0] * group:
        raise ValueError(
            f"a step of this call takes {group} pages, the work list "
            f"{work.pages.shape[0]} for {work.rows.shape[0]} items")
    prefetch = (work.lens, work.starts, work.rows, work.pages,
                jnp.asarray(layer, jnp.int32).reshape(1))
    q, weights = q.astype(pool.dtype), weights.astype(jnp.float32)

    def key_spec(j):    # tile j of item w: the page the list names for it
        def at(*ids):   # the grid's indices (w the last), then the list
            w, (pages, at_layer) = ids[-len(prefetch) - 1], ids[-2:]
            return at_layer[0], 0, pages[w * group + j], 0, 0
        return pl.BlockSpec((None, None, 1, ps, Di), at)

    def slot(w, starts, rows):  # item w's place among its request's
        return w - starts[rows[w]]

    keys = [key_spec(j) for j in range(group)]
    fill = pl.BlockSpec(memory_space=pl.ANY)    # never read: the output's
    if T == 1:
        rows_out, grid = 1, (work.n_items,)
        operands = (q.reshape(B, Hi, Di), weights.reshape(B, Hi, 1))
        in_specs = [
            pl.BlockSpec((1, Hi, Di),
                         lambda w, lens, starts, rows, *_: (rows[w], 0, 0)),
            pl.BlockSpec((1, Hi, 1),
                         lambda w, lens, starts, rows, *_: (rows[w], 0, 0))]
        out_spec = pl.BlockSpec(
            (1, 1, tokens), lambda w, lens, starts, rows, *_: (
                rows[w], 0, slot(w, starts, rows)))
        kernel = functools.partial(_decode_kernel, page_size=ps, group=group)
    else:
        tq = min(_QUERIES, -(-T // _LANES) * _LANES)
        rows_out = -(-T // tq) * tq     # whole tiles of queries
        grid = (rows_out // tq, work.n_items)
        pad = ((0, 0), (0, 0), (0, rows_out - T))
        operands = (jnp.pad(q.transpose(0, 2, 1, 3), pad + ((0, 0),)),
                    jnp.pad(weights.transpose(0, 2, 1), pad))
        in_specs = [
            pl.BlockSpec((1, Hi, tq, Di), lambda i, w, lens, starts, rows,
                         *_: (rows[w], 0, i, 0)),
            pl.BlockSpec((1, Hi, tq), lambda i, w, lens, starts, rows, *_: (
                rows[w], 0, i))]
        out_spec = pl.BlockSpec(
            (1, tq, tokens), lambda i, w, lens, starts, rows, *_: (
                rows[w], i, slot(w, starts, rows)))
        kernel = functools.partial(_chunk_kernel, page_size=ps, group=group)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid,
            in_specs=in_specs + keys + [fill], out_specs=out_spec),
        out_shape=jax.ShapeDtypeStruct((B, rows_out, groups * tokens),
                                       jnp.float32),
        # the fill is the operand after the list, the two of the queries and
        # the group's tiles
        input_output_aliases={len(prefetch) + 2 + group: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=_interpret(),
        name="index_scores",
    )(*prefetch, *operands, *([pool] * group),
      jnp.full((B, rows_out, groups * tokens), -jnp.inf, jnp.float32))
    return out[:, :T, :width * ps]


def _group_keys(k_refs):
    """The item's tiles as one [group x page_size, Di] block of keys."""
    return jnp.concatenate([k_ref[0] for k_ref in k_refs], axis=0)


def _decode_kernel(len_ref, start_ref, row_ref, _page_ref, _layer_ref, q_ref,
                   w_ref, *refs, page_size: int, group: int):
    """One work item of a decode step: table slots ``group i .. group i +
    group - 1`` of request ``b = row_ref[w]``, ``i = w - start_ref[b]``,
    against the request's one query. The heads are the product's rows
    ``[Hi, Di] x [Di, keys]``; ``relu``, the head's weight (a column, spread
    over lanes) and the sum over sublanes leave one row of scores. A page
    past the request's end repeats its last and is written ``-inf``, as the
    one item of an empty row is."""
    k_refs, o_ref = refs[:group], refs[-1]
    w = pl.program_id(0)
    b = row_ref[w]
    first = (w - start_ref[b]) * (group * page_size)
    keys = _group_keys(k_refs)                              # [keys, Di]
    s = jax.lax.dot_general(
        q_ref[0], keys, (((1,), (1,)), ((), ())), precision=_exact(keys),
        preferred_element_type=jnp.float32)                 # [Hi, keys]
    total = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0, keepdims=True)
    pos = first + jax.lax.broadcasted_iota(jnp.int32, total.shape, 1)
    o_ref[0] = jnp.where(pos < len_ref[b], total, -jnp.inf)


def _chunk_kernel(len_ref, start_ref, row_ref, _page_ref, _layer_ref, q_ref,
                  w_ref, *refs, page_size: int, group: int):
    """One (tile of queries, work item) step of a chunk: the item's keys
    against the tile's queries a head at a time, keys the rows ``[keys, Di] x
    [Di, queries]``, so that a head's weights are a row [1, queries] spread
    over sublanes and the sum over the heads plain additions of [keys,
    queries] tiles; transposed once, every head in, masked past the
    request's live keys and written."""
    k_refs, o_ref = refs[:group], refs[-1]
    w = pl.program_id(1)
    b = row_ref[w]
    first = (w - start_ref[b]) * (group * page_size)
    keys = _group_keys(k_refs)                              # [keys, Di]

    def head(h, acc):
        s = jax.lax.dot_general(
            keys, q_ref[0, h], (((1,), (1,)), ((), ())),
            precision=_exact(keys),
            preferred_element_type=jnp.float32)             # [keys, tq]
        return acc + jnp.maximum(s, 0.0) * w_ref[0, pl.ds(h, 1), :]

    total = jax.lax.fori_loop(
        0, q_ref.shape[1], head,
        jnp.zeros((keys.shape[0], q_ref.shape[2]), jnp.float32)).T
    pos = first + jax.lax.broadcasted_iota(jnp.int32, total.shape, 1)
    o_ref[0] = jnp.where(pos < len_ref[b], total, -jnp.inf)
