"""Single-token decode attention over a KV cache, in Pallas.

Capability parity with the reference's fused decode kernels — the
``softmax_context`` KV-cache attention (``csrc/transformer/inference/csrc/
softmax.cu`` + ``pt_binding.cpp`` attention bindings, workspace layout
``inference_context.h``): one new query token attends over the cache with a
validity mask, in one kernel, without materializing [B, H, S] probabilities in
HBM.

Two cache layouts, one online softmax:

- **Contiguous** (:func:`decode_attention`): K/V are [B, H, S, Dh] — sequence
  in the sublane dimension, head_dim in the lane dimension — so every block
  the kernel touches is Mosaic-tileable: K/V stream as (block_k, Dh) tiles
  and the q/out blocks are full-dim (1, Dh) slices. Grid = (B, H,
  S/block_k): the sequence dimension is a GRID axis, one [block_k, Dh] tile
  of one head in VMEM per step, the softmax state in VMEM scratch across the
  (sequential) innermost axis.
- **Paged** (:func:`paged_decode_attention`): K/V live in a shared page pool
  [H, P, page_size, Dh] (one layer's, or the whole stack [L, H, P, page_size,
  Dh] with a layer index); each request owns a *block table* row naming its
  pages in order. Grid = (H/Hb, live groups of pages of the batch): the
  innermost axis walks a work list (:func:`paged_work_list`), the requests'
  pages in request order, a few of one request a step and none for the table
  slots past its length; a step takes EVERY head of a block of ``Hb`` (all of
  them where their tiles fit ``_PAGED_KV_VMEM_BYTES``; under tensor
  parallelism the shard's), so the steps grow neither with H nor with the
  table's width. The list rides scalar prefetch and the K/V ``index_map``
  reads the page ids from it: the gather happens in the BlockSpec, and the
  number of steps is a traced grid bound.
- :func:`paged_decode_gqa`: fewer key-value heads than query heads, the
  same work list in groups; a step is several pages of a request, every
  key-value head's tile against its group of queries on the MXU. A window
  layer's ring a slot is read through it as that slot's pages.
- :func:`paged_verify_attention` (speculation) walks a block table on a
  (B, H, table slots + 1) grid, one head a step.

Per-request valid lengths ride scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), NOT a VMEM operand. The previous revision
fed the length as a (1, 1) float-tiled VMEM array with no memory space and a
q ``index_map`` that disagreed with the transposed [B, H, 1, Dh] layout —
Mosaic rejected the block-shape/array-shape/index_map triple once the batch
grid axis was wide enough to matter (decode at batch 16 on the v5e:
"Blocked(1), Blocked(1), Blocked(1), Blocked(64) ... in memory space None").
Scalar prefetch puts lengths (and the paged block tables) in SMEM where the
index maps and ``@pl.when`` guards can consume them, which is also exactly
what continuous batching needs: every batch row decodes at its OWN length.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _interpret


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, sm_scale: float, block_k: int, num_blocks: int):
    """One (batch row, head, K/V tile) step of the online softmax.

    ``len_ref`` is the scalar-prefetched [B] lengths vector in SMEM."""
    b = pl.program_id(0)
    ki = pl.program_id(2)
    cur = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * block_k < cur)  # tiles wholly past the valid length: no work
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # [1, Dh]
        k = k_ref[0, 0].astype(jnp.float32)  # [Bk, Dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [1, Bk]
        s_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(s_pos < cur, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)

    @pl.when(ki == num_blocks - 1)
    def _finalize():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _as_lengths(cur_len, batch: int) -> jnp.ndarray:
    """Accept the legacy scalar (one length for the whole batch) or a [B]
    per-request vector (continuous batching: every slot at its own length)."""
    lens = jnp.asarray(cur_len, jnp.int32)
    if lens.ndim == 0:
        return jnp.broadcast_to(lens, (batch,))
    if lens.shape != (batch,):
        raise ValueError(f"cur_len must be a scalar or [batch]={batch} vector, "
                         f"got shape {lens.shape}")
    return lens


def decode_attention(
    q: jnp.ndarray,  # [B, 1, H, Dh] — the new token's query
    k_cache: jnp.ndarray,  # [B, H, S, Dh]
    v_cache: jnp.ndarray,
    cur_len: jnp.ndarray,  # int32 scalar or [B]: valid entries INCLUDING the new token
    softmax_scale: Optional[float] = None,
    block_k: int = 512,
) -> jnp.ndarray:
    """Returns [B, 1, H, Dh]. The new token's k/v must already be in the cache."""
    B, one, H, Dh = q.shape
    assert one == 1
    S = k_cache.shape[2]
    # largest power-of-two tile that divides S (engines should pad the cache to
    # a 128-multiple so tiles stay sublane-aligned)
    block_k = min(block_k, S)
    while block_k > 1 and S % block_k:
        block_k //= 2
    num_blocks = S // block_k
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(Dh)
    lens = _as_lengths(cur_len, B)
    qh = q.transpose(0, 2, 1, 3)  # [B, H, 1, Dh] — heads lead, like the cache

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # lens -> SMEM, readable by index maps + body
        grid=(B, H, num_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Dh), lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, ki, lens: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, ki, lens: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Dh), lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, Dh), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=scale, block_k=block_k,
                          num_blocks=num_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, Dh), q.dtype),
        interpret=_interpret(),
        name="decode_attn",
    )(lens, qh, k_cache, v_cache)
    return out.transpose(0, 2, 1, 3)  # back to [B, 1, H, Dh]


# ------------------------------------------------------------------ paged path
def unpack_kv_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Two int4 values per int8 byte, half-split along the last dim — THE
    ``int8_matmul.pack_int4`` layout (one canonical nibble format for
    weights and KV; delegating keeps them from ever desynchronizing).
    Float output, shared by the kernel body and the XLA fallback so both
    dequantize bit-identically."""
    from .int8_matmul import unpack_int4

    return unpack_int4(packed).astype(jnp.float32)


def paged_decode_attention(
    q: jnp.ndarray,           # [B, 1, H, Dh]
    k_pages: jnp.ndarray,     # [H, P, page_size, Dh] — shared page pool
    v_pages: jnp.ndarray,     #   (or [L, H, P, page_size, Dh] with `layer`)
    lengths: jnp.ndarray,     # [B] int32: valid tokens INCLUDING the new one
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32 page ids (pad: 0)
    softmax_scale: Optional[float] = None,
    impl: Optional[str] = None,  # None=auto | "kernel" | "gather"
    k_scales: Optional[jnp.ndarray] = None,  # [H, P] f32: per-page scales
    v_scales: Optional[jnp.ndarray] = None,  #   (or [L, H, P] with `layer`)
    layer=None,               # int32 scalar: which layer of a 5-D pool
    work: Optional["PagedWork"] = None,  # paged_work_list(lengths, tables, ps)
) -> jnp.ndarray:
    """Decode attention reading K/V through a block table.

    **Two call forms**, told apart by the pool's rank. A 4-D pool is one
    layer's. A 5-D pool is every layer's stack and comes with ``layer``,
    which may be traced: the index rides scalar prefetch beside the lengths
    and the tables and the K/V ``index_map`` leads with it, so the kernel
    reads that layer's pages out of the whole stack where it lies (the
    fallback gathers them the same way) and a layer loop that carries the
    stack never slices it. Scales follow the pool: [H, P], or the [L, H, P]
    stack, of which the one layer (a few KiB) is sliced for SMEM.

    Each request's cache is a list of fixed-size pages scattered through the
    pool; the grid walks the batch's live pages alone (``work``: request
    ``b``'s table slots ``0 .. ceil(lengths[b] / page_size) - 1``, request
    after request, :func:`paged_pages_per_step` of them a step) and the K/V
    ``index_map`` takes the page ids from that list in SMEM, for every head
    of a block at once, whatever the pool's fragmentation and the table's
    width. The list depends on the lengths and the tables alone: a caller
    with many layers builds it once a decode step (``models/gpt.paged_work``)
    for every layer's call; without ``work`` the call builds its own. Table
    slots past a request's length are never visited; they must still hold a
    VALID page id (page 0, the allocator's sink: the fallback gathers them).
    A length of 0 gives 0: such a row keeps one masked step, which writes it.

    **The step**: :func:`_paged_group_call` for dense heads of 128 (two pages
    of a request on the MXU), :func:`_paged_kernel` (a page, the VPU) else.

    **Quantized pools**: pass ``k_scales``/``v_scales`` ([H, P] fp32, one
    symmetric scale per head x page) and int8 pools, plain ([..., Dh]) or
    nibble-packed int4 ([..., Dh // 2], :func:`unpack_kv_int4`'s layout).
    Scales ride scalar prefetch next to the work list, and a tile dequantizes
    inside the step: HBM moves 2x or 4x fewer bytes than bf16 and no
    dequantized copy of the pool ever exists.

    ``impl``: "kernel" forces the Pallas path (Mosaic on TPU, interpret
    elsewhere), "gather" the XLA fallback (the same payload dequantized with
    the same arithmetic: they agree to fp tolerance); auto follows the backend.
    """
    B, one, H, Dh = q.shape
    assert one == 1
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    if k_pages.ndim not in (4, 5) or (k_pages.ndim == 5) != (layer is not None):
        raise ValueError(
            "a [H, P, page_size, Dh] pool is one layer's and takes no layer "
            "index; a [L, H, P, page_size, Dh] pool needs one: got a "
            f"{k_pages.ndim}-D pool and layer={layer!r}")
    quantized = k_scales is not None
    packed = quantized and k_pages.shape[-1] * 2 == Dh
    if quantized and not packed and k_pages.shape[-1] != Dh:
        raise ValueError(
            f"quantized pool last dim {k_pages.shape[-1]} matches neither "
            f"int8 ({Dh}) nor packed int4 ({Dh // 2})")
    page_size = k_pages.shape[-2]
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(Dh)
    lens = _as_lengths(lengths, B)
    tables = jnp.asarray(block_tables, jnp.int32)
    if impl is None:
        impl = "kernel" if jax.default_backend() == "tpu" else "gather"
    if impl == "gather":
        return _paged_gather_attention(q, k_pages, v_pages, lens, tables,
                                       scale, k_scales, v_scales, layer)
    if impl != "kernel":
        raise ValueError(f"impl must be None, 'kernel' or 'gather': {impl!r}")

    layer, k_pages, v_pages, k_scales, v_scales = _as_stack(
        layer, k_pages, v_pages, k_scales, v_scales)
    Dp = k_pages.shape[-1]  # Dh, or Dh//2 nibble-packed
    heads = _heads_per_step(H, page_size, Dp, k_pages.dtype.itemsize)
    group = paged_pages_per_step(H, page_size, Dp, k_pages.dtype,
                                 tables.shape[1], quantized)
    work = _paged_listed(work, lens, tables, page_size, group)
    if _paged_on_mxu(page_size, Dp, k_pages.dtype, quantized):
        return _paged_group_call(q, k_pages, v_pages, lens, tables, scale,
                                 layer, work)
    kv_spec = pl.BlockSpec(
        (None, heads, 1, page_size, Dp),
        # the paged gather IS this index_map: work item w reads the page the
        # list names, in the layer's pool (args: grid ids, every prefetch ref)
        lambda hb, w, lens, starts, rows, pages, layer, *_s: (
            layer[0], hb, pages[w], 0, 0))
    # [B, H/Hb, Hb, Dh]: a (Hb, Dh) block is the array's own last two dims,
    # so every divisor of H is a legal Hb
    qo_spec = pl.BlockSpec(
        (1, 1, heads, Dh),
        lambda hb, w, lens, starts, rows, *_prefetch: (rows[w], hb, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # (lens, starts, rows, pages, layer[, k_scales, v_scales])
        num_scalar_prefetch=7 if quantized else 5,
        grid=(H // heads, work.n_items),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((heads, 1, Dh), jnp.float32),
            pltpu.VMEM((heads, 1, 1), jnp.float32),
            pltpu.VMEM((heads, 1, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, sm_scale=scale, page_size=page_size, heads=heads,
        quantized=quantized, packed=packed)
    # SMEM takes the one layer's [H, P] scales, a few KiB of the stack
    scales = tuple(s[layer].astype(jnp.float32)
                   for s in ((k_scales, v_scales) if quantized else ()))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H // heads, heads, Dh), q.dtype),
        interpret=_interpret(),
        name="paged_decode_q" if quantized else "paged_decode",
    )(work.lens, work.starts, work.rows, work.pages,
      jnp.asarray(layer, jnp.int32).reshape(1), *scales,
      q.reshape(B, H // heads, heads, Dh), k_pages, v_pages)
    return out.reshape(B, 1, H, Dh)


class PagedWork(NamedTuple):
    """A batch's live pages in request order (:func:`paged_work_list`)."""
    lens: jnp.ndarray     # [B] int32: valid tokens, at most a table's worth
    starts: jnp.ndarray   # [B] int32: the first work item of each request
    rows: jnp.ndarray     # [items] int32: item w's request
    pages: jnp.ndarray    # [items * group] int32: item w's page ids, flat
    n_items: jnp.ndarray  # int32 scalar: the items that are live


def paged_work_list(lengths: jnp.ndarray, block_tables: jnp.ndarray,
                    page_size: int, group: int = 1) -> PagedWork:
    """The (request, ``group`` pages) items a paged decode call has to visit.

    Request ``b`` of ``lengths[b]`` tokens (the new one included) owns table
    slots ``0 .. ceil(max(lengths[b], 1) / page_size) - 1``, ``group`` an
    item, request after request: item ``w`` is item ``w - starts[rows[w]]`` of
    request ``rows[w]`` and reads ``pages[group w .. group w + group - 1]``;
    past the request's last page they repeat it, for the kernel to mask. A
    length of 0 keeps one item, masked whole, so that its output block is
    written as 0. ``n_items`` counts them; entries from there to the arrays'
    static end repeat the last item and are never visited. Built on the
    device from the lengths and the tables alone: once for every layer."""
    tables = jnp.asarray(block_tables, jnp.int32)
    B, pages_per_seq = tables.shape
    lens = jnp.minimum(_as_lengths(lengths, B), pages_per_seq * page_size)
    owned = -(-jnp.maximum(lens, 1) // page_size)
    items = owned if group == 1 else -(-owned // group)
    ends = jnp.cumsum(items)
    starts = ends - items
    w = jnp.arange(B * -(-pages_per_seq // group), dtype=jnp.int32)
    # the request whose items end after w: B compares, which fuse (a binary
    # search would be a loop of gathers)
    rows = jnp.sum(w[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    rows = jnp.minimum(rows, B - 1)
    slots = jnp.minimum(w - starts[rows], items[rows] - 1)
    pages = tables[rows, slots] if group == 1 else tables[
        rows[:, None], jnp.minimum(slots[:, None] * group + jnp.arange(group),
                                   owned[rows, None] - 1)].reshape(-1)
    return PagedWork(lens, starts, rows, pages, ends[-1])


def _as_stack(layer, *arrays):
    """(layer, *arrays) with one layer's pool and scales ([H, P, ...], no
    index) made a stack of one, layer 0: one code path serves both call forms."""
    if layer is not None:
        return (layer, *arrays)
    return (0, *(a if a is None else a[None] for a in arrays))


# VMEM one grid step of the paged kernel may give to its K and V page tiles,
# two pipeline buffers each. 16 heads of 128 over pages of 64 in bf16 take
# 1 MiB; the float32 working copies of a tile pair come to as much again.
_PAGED_KV_VMEM_BYTES = 2 * 1024 * 1024


def _heads_per_step(n_head: int, page_size: int, dp: int, itemsize: int) -> int:
    """The largest divisor of ``n_head`` whose double-buffered K and V tiles
    fit ``_PAGED_KV_VMEM_BYTES`` (1 where not even one head's do)."""
    fit = _PAGED_KV_VMEM_BYTES // (2 * 2 * page_size * dp * itemsize)
    return max(d for d in range(1, n_head + 1)
               if n_head % d == 0 and d <= max(fit, 1))


def _paged_kernel(len_ref, start_ref, row_ref, page_ref, _layer_ref, *refs,
                  sm_scale: float, page_size: int, heads: int,
                  quantized: bool, packed: bool):
    """One (block of ``heads`` heads, work item) step of the online softmax
    over an [heads, page_size, Dh] tile of K and one of V: item ``w`` of
    :func:`paged_work_list` is table slot ``w - start_ref[b]`` of request
    ``b = row_ref[w]``. The accumulators open on a request's slot 0 and the
    output is written on its last slot. Which layer's pool and which page
    the tiles come from is the index maps' business (``_layer_ref``,
    ``page_ref``), not the body's.

    Both products run on the VPU in float32 (q . K reduced over lanes, p . V
    over sublanes; of a step's 0.95 us at 16 heads of 128 the first holds
    0.30 and the second 0.25, ``scripts/paged_decode_bench.py``): what dense
    heads of 128 left in PR 54 for :func:`_paged_group_call`'s MXU step.

    Quantized pools (``paged_decode_q``): the tile is int8 (or nibble-packed
    int4) and dequantizes against its per-(head, page) scales, read from SMEM
    next to the work list, inside the same step."""
    ks_ref = vs_ref = None
    if quantized:
        ks_ref, vs_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    hb = pl.program_id(0)
    w = pl.program_id(1)
    b = row_ref[w]
    cur = len_ref[b]
    i = w - start_ref[b]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(ref, scale_ref):
        t = ref[:, 0]  # [heads, page_size, Dp]
        if not quantized:
            return t.astype(jnp.float32)
        t = unpack_kv_int4(t) if packed else t.astype(jnp.float32)
        page = page_ref[w]
        head = jax.lax.broadcasted_iota(jnp.int32, (heads, 1, 1), 0)
        s = jnp.zeros((heads, 1, 1), jnp.float32)
        for j in range(heads):  # [heads] scalars in SMEM -> one vector
            s = jnp.where(head == j, scale_ref[hb * heads + j, page], s)
        return t * s  # per-(head, page) symmetric dequant

    @pl.when(i * page_size < cur)  # the one item of an empty row: no work
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # [heads, Dh]
        k = tile(k_ref, ks_ref)
        v = tile(v_ref, vs_ref)
        s = jnp.sum(q[:, None, :] * k, axis=-1, keepdims=True)  # [Hb, ps, 1]
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (heads, page_size, 1), 1)
        s = jnp.where(pos < cur, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jnp.sum(p * v, axis=1, keepdims=True))

    @pl.when((i + 1) * page_size >= cur)  # the request's last item
    def _finalize():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l_safe)[:, 0, :].astype(o_ref.dtype)


# ------------------------------------------- fewer key-value heads (GQA)
def _ring_seen(pos, n, ring):
    """Which rows of a slot's ring a step reads: ``pos`` the row, ``n`` the
    slot's tokens with the new one, ``ring`` = (R, W). Row ``r`` holds the
    newest position ``p <= n - 1`` with ``p mod R == r``; it is read where
    that position exists and lies inside the window, ``n - 1 - p < min(W,
    n)``."""
    rows, window = ring
    d = jax.lax.rem(n - 1, rows) - pos
    age = jnp.where(d >= 0, d, d + rows)
    return age < jnp.minimum(window, n)


def paged_decode_gqa(
    q: jnp.ndarray,           # [B, 1, H, Dh]
    k_pages: jnp.ndarray,     # [G, P, page_size, Dh], G key-value heads
    v_pages: jnp.ndarray,     #   (or [L, G, P, page_size, Dh] with `layer`)
    lengths: jnp.ndarray,     # [B] int32: valid tokens INCLUDING the new one
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32 page ids (pad: 0)
    softmax_scale: Optional[float] = None,
    impl: Optional[str] = None,  # None=auto | "kernel" | "gather"
    layer=None,
    work: Optional["PagedWork"] = None,
    ring: Optional[Tuple[int, int]] = None,     # (R, W): the pages are rings
    out_dtype=None, name=None,  # None: the query's; the kernel's own name
) -> jnp.ndarray:
    """Decode attention of ``H`` query heads over ``G`` heads of keys and
    values read through a block table: query head ``i`` reads key-value head
    ``i // (H / G)``. The pool's call forms, the table, the sink page and
    ``impl`` are :func:`paged_decode_attention`'s; what differs is the grid
    step. It is a GROUP of ``g`` consecutive table slots of one request
    (:func:`gqa_pages_per_step`: as many as make the step's tiles about a
    megabyte, no more than the table's width bears), ``g`` tiles of K and
    ``g`` of V with every key-value head of a block (``_heads_per_step``) in
    each, taken in the table's order: one key-value head's page of
    ``page_size`` rows meets its whole group of ``H / G`` queries, ``[H / G,
    Dh] x [Dh, page_size]`` and ``[H / G, page_size] x [page_size, Dh]``, two
    products the MXU can take; a page is read once for its group of queries,
    so a token costs ``G`` rows and not ``H``. A step a page spent 0.5 us a
    page in that chain of product, reduction, exponential and product, at 8,
    4 and 2 heads alike, for 0.16-0.31 us of HBM time: a group's pages run
    it side by side (:func:`_gqa_kernel`). ``work`` is ``paged_work_list(..,
    group=g)``, built once a step for every layer (``models/gpt.gqa_work``). A
    request that ends inside a group fetches its last page again, masked.

    ``ring`` = (R, W): the pool is a stack of rings a slot, ``R`` rows each,
    position ``t`` at row ``t mod R``, read as pages (slot ``b``'s are
    ``block_tables[b]``, ``R / page_size`` of them) for a layer whose query
    sees its last ``W <= R`` positions only: ``lengths`` stay the true
    lengths, the rows read are those of :func:`_ring_seen`, and ``work``
    lists ``min(lengths, R)`` rows a slot.

    Scores and the running softmax are float32. A float32 query over bf16
    rows takes both products in two passes (its bf16 rounding and what that
    left, side by side, as :func:`paged_decode_mla`)."""
    B, one, H, Dh = q.shape
    assert one == 1
    if k_pages.ndim not in (4, 5) or (k_pages.ndim == 5) != (
            layer is not None):
        raise ValueError(
            "a [G, P, page_size, Dh] pool is one layer's and takes no layer "
            "index; a [L, G, P, page_size, Dh] pool needs one: got a "
            f"{k_pages.ndim}-D pool and layer={layer!r}")
    G, page_size = k_pages.shape[-4], k_pages.shape[-2]
    if H % G or k_pages.shape[-1] != Dh:
        raise ValueError(f"{H} query heads of {Dh} do not divide over a pool "
                         f"{k_pages.shape}")
    rep = H // G
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(Dh)
    lens = _as_lengths(lengths, B)
    tables = jnp.asarray(block_tables, jnp.int32)
    out_dtype = q.dtype if out_dtype is None else out_dtype
    if impl is None:
        impl = "kernel" if jax.default_backend() == "tpu" else "gather"
    layer, k_pages, v_pages = _as_stack(layer, k_pages, v_pages)
    if impl == "gather":
        return _gqa_gather_attention(q, k_pages, v_pages, lens, tables, scale,
                                     layer, ring).astype(out_dtype)
    if impl != "kernel":
        raise ValueError(f"impl must be None, 'kernel' or 'gather': {impl!r}")

    two_pass = q.dtype == jnp.float32 and k_pages.dtype == jnp.bfloat16
    heads = _heads_per_step(G, page_size, Dh, k_pages.dtype.itemsize)
    group = gqa_pages_per_step(G, page_size, Dh, k_pages.dtype,
                               tables.shape[1], ring is not None)
    if work is None:
        cap = lens if ring is None else jnp.minimum(lens, ring[0])
        work = paged_work_list(cap, tables, page_size, group)._replace(
            lens=lens)
    elif work.pages.shape[0] != work.rows.shape[0] * group:
        raise ValueError(
            f"a step of this call takes {group} pages, the work list "
            f"{work.pages.shape[0]} for {work.rows.shape[0]} items")
    qg = q.reshape(B, G // heads, heads, rep, Dh)
    rows = 2 * rep if two_pass else rep
    if two_pass:
        hi = jax.lax.reduce_precision(qg, exponent_bits=8, mantissa_bits=7)
        qg = jnp.concatenate([hi, qg - hi], axis=3).astype(k_pages.dtype)

    def kv_spec(j):     # tile j of item w: the page the list names for it
        return pl.BlockSpec(
            (None, heads, 1, page_size, Dh),
            lambda hb, w, lens, starts, rows, pages, layer: (
                layer[0], hb, pages[w * group + j], 0, 0))

    def qo_spec(n):
        return pl.BlockSpec(
            (1, 1, heads, n, Dh),
            lambda hb, w, lens, starts, rows, *_p: (rows[w], hb, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,      # lens, starts, rows, pages, layer
        grid=(G // heads, work.n_items),
        in_specs=[qo_spec(rows)] + 2 * [kv_spec(j) for j in range(group)],
        out_specs=qo_spec(rep),
        scratch_shapes=[
            pltpu.VMEM((heads, rep, Dh), jnp.float32),
            pltpu.VMEM((heads, rep, 1), jnp.float32),
            pltpu.VMEM((heads, rep, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _gqa_kernel, sm_scale=scale, page_size=page_size, rep=rep,
        two_pass=two_pass, ring=ring, group=group)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G // heads, heads, rep, Dh),
                                       out_dtype),
        interpret=_interpret(),
        name=name or "paged_decode_gqa",
    )(work.lens, work.starts, work.rows, work.pages,
      jnp.asarray(layer, jnp.int32).reshape(1), qg,
      *([k_pages] * group), *([v_pages] * group))
    return out.reshape(B, 1, H, Dh)


def _gqa_kernel(len_ref, start_ref, row_ref, _page_ref, _layer_ref, q_ref,
                *refs, sm_scale: float, page_size: int, rep: int,
                two_pass: bool, ring, group: int):
    """One (block of key-value heads, work item) step of the online softmax:
    item ``w`` is table slots ``group i .. group i + group - 1`` of request
    ``b = row_ref[w]``, ``i = w - start_ref[b]``; ``refs`` are their tiles
    [heads, page_size, Dh], ``group`` of K then ``group`` of V, then the
    output and the accumulators. Each head's page meets that head's ``rep``
    queries [heads, rep, Dh] as a batched product, and the running softmax
    takes the pages one after another in the table's order: the arithmetic,
    and so the output, is that of a step a page. But it is written stage by
    stage over the group (every page's scores, then the running maxima, then
    probabilities and sums), so that only the maxima and the two running
    sums wait on the page before: a page's own chain of product, reduction,
    exponential and product is what a step a page spent its time waiting
    in. A page past the request's end is masked whole and changes nothing.
    ``two_pass``: the query block is ``[q_hi; q_lo]`` along the group's axis
    and the probabilities are split likewise, the halves of each product
    added."""
    k_refs, v_refs = refs[:group], refs[group:2 * group]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * group:]
    w = pl.program_id(1)
    b = row_ref[w]
    n = len_ref[b]
    cur = n if ring is None else jnp.minimum(n, ring[0])
    first = (w - start_ref[b]) * (group * page_size)    # the item's first row

    @pl.when(first == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def folded(a):      # [heads, 2 rep, n] -> the two passes' sum
        return a[:, :rep] + a[:, rep:] if two_pass else a

    @pl.when(first < cur)  # the one item of an empty row: no work
    def _tiles():
        q = q_ref[0, 0]                                 # [heads, rows, Dh]
        scores, seen, m = [], [], [m_ref[...]]
        for j, k_ref in enumerate(k_refs):
            k = k_ref[:, 0]                             # [heads, ps, Dh]
            s = folded(jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))), precision=_exact(k),
                preferred_element_type=jnp.float32)) * sm_scale
            pos = first + j * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 2)                  # [heads, rep, ps]
            seen.append(pos < cur if ring is None
                        else _ring_seen(pos, n, ring))
            scores.append(jnp.where(seen[j], s, NEG_INF))
        for s in scores:
            m.append(jnp.maximum(m[-1], jnp.max(s, axis=2, keepdims=True)))
        l, acc = l_ref[...], acc_ref[...]
        for j, v_ref in enumerate(v_refs):
            v = v_ref[:, 0]
            alpha = jnp.exp(m[j] - m[j + 1])
            p = jnp.where(seen[j], jnp.exp(scores[j] - m[j + 1]), 0.0)
            l = alpha * l + jnp.sum(p, axis=2, keepdims=True)
            if two_pass:
                p_hi = p.astype(v.dtype)
                p = jnp.concatenate(
                    [p_hi, (p - p_hi.astype(jnp.float32)).astype(v.dtype)],
                    axis=1)
            acc = acc * alpha + folded(jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                precision=_exact(v), preferred_element_type=jnp.float32))
        m_ref[...], l_ref[...], acc_ref[...] = m[-1], l, acc

    @pl.when(first + group * page_size >= cur)  # the request's last item
    def _finalize():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


# ------------------------------------------------------- latent pages (MLA)
# what a grid step of the latent kernel aims at: this many bytes of page
# tiles (eight pages of 64 rows of 640 in bf16, four of 1152; the kernel
# keeps three items' tiles, 1.9 MiB of VMEM, and two items' scores), in no
# more pages than this, and the rows of a link of the step's chain
# (:func:`_mla_kernel`): a whole lane tile of scores. On the v5e
# (``scripts/mla_decode_bench.py``, 128 heads over rows of 640, a float32
# query in two passes, whose products are 1.53 us of MXU time at its peak for
# eight pages) a step that takes an item's scores, its softmax and its sum
# one after the other costs 2.35 us with its tiles moving or standing still:
# 0.47 us of it is what an update of the running softmax waits for with the
# MXU idle (the maximum, the first exponentials, the accumulators), whatever
# its rows. Taking item w's scores and item w - 1's sum in one step, link by
# link, hides most of that: 1.96 us for eight pages, 1.12 for four (more
# steps), 3.75 for sixteen (a fifth of their tiles past a request's end for
# a ninth); links of 128 rows run 1% under one link over the group
_MLA_STEP_BYTES = 640 * 1024
_MLA_MAX_PAGES = 8
_MLA_SUB_ROWS = 128


def mla_pages_per_step(page_size: int, width: int, dtype, table: int,
                       ring: bool) -> int:
    """Pages a grid step of :func:`paged_decode_mla` takes of one request,
    from the call's static shapes alone (the kernel and whoever builds its
    work list, ``models/gpt.mla_work``, both ask here): as many tiles
    [page_size, width] of ``dtype`` as fit ``_MLA_STEP_BYTES``, at most
    ``_MLA_MAX_PAGES``. Over a block table ``table`` slots wide that is a
    power of two and no more than a quarter of the table, because a group
    fetches and scores the tiles past a request's last page too (as
    :func:`gqa_pages_per_step`). A ring is read whole once its request
    passes the window: there the group is the fewest pages that keep the
    ring's number of steps (a ring of 9 pages, four a step: three steps of
    three)."""
    fit = max(1, min(_MLA_STEP_BYTES // (
        page_size * width * jnp.dtype(dtype).itemsize), _MLA_MAX_PAGES))
    if ring:
        return -(-table // -(-table // fit))
    return 1 << max(min(fit, table // 4), 1).bit_length() - 1


def _mla_sub_tile(group: int, page_size: int) -> int:
    """Pages a link of a step's chain takes (:func:`_mla_kernel`): as many
    as hold ``_MLA_SUB_ROWS`` rows where that divides the group, else the
    whole group in one link (a ring's three pages of 64: a link of 64 rows
    fills half the lanes of its scores)."""
    sub = max(1, _MLA_SUB_ROWS // page_size)
    return sub if group % sub == 0 else group


def paged_decode_mla(
    q: jnp.ndarray,          # [B, 1, H, C]: W_kvb absorbed, [q_abs | q_rope]
    pool: jnp.ndarray,       # [1, P, page_size, C] one layer's latent pool
    lengths: jnp.ndarray,    #   (or [L, 1, P, page_size, C] with `layer`)
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32 page ids (pad: 0)
    rank: int,               # the values are a row's first `rank` columns
    softmax_scale: float,
    impl: Optional[str] = None,  # None=auto | "kernel" | "gather"
    layer=None,
    out_dtype=None,          # None: the query's; float32 keeps the sum's
    ring=None,               # (R, W): the tables read a slot's ring
    allowed=None,            # [B, pages_per_seq * page_size] rows to read
    work: Optional["PagedWork"] = None,
) -> jnp.ndarray:
    """Decode attention of all ``H`` query heads over ONE cached row a token,
    read through a block table: latent attention (MLA) with the key-value
    up-projection absorbed into the query, so a cached row ``[c_kv | k_rope]``
    (``rank + rope`` numbers, zeros after them up to ``C``, whole lanes of
    128) is every head's key and its first ``rank`` columns every head's
    value. Returns the attention over the latent,
    [B, 1, H, rank]; the caller applies ``W_kvb``'s value part.

    Where :func:`paged_decode_attention` scores one query head against its
    own key head on the VPU, here ``H`` heads meet one row: both products of
    a step are matrix products for the MXU, ``[H, C] x [C, tokens]`` and
    ``[H, tokens] x [tokens, rank]``, a page read once for all heads. At 128
    heads of 576 that is 278,528 operations for 1,152 bytes a cached token,
    242 a byte: on the v5e's ridge (240.5). The pool's call forms, the table,
    the sink page and ``impl`` are :func:`paged_decode_attention`'s; the pool
    has no head axis to split (its second axis is 1) and no value pool.

    A work item is a GROUP of ``g`` consecutive table slots of one request
    (:func:`mla_pages_per_step`) and the grid walks the batch's live groups
    only: ``work`` is ``paged_work_list(.., group=g)``, built once a decode
    step for every latent layer of a kind (``models/gpt.mla_work``; without
    it the call builds its own). A request that ends inside a group fetches
    its last page again, masked; a length of 0 keeps one item, which writes
    0. The grid is one step longer than the list: step ``w`` takes item
    ``w``'s scores and item ``w - 1``'s weighted sum (:func:`_mla_kernel`),
    and the kernel copies the items' page tiles itself, an item ahead.

    ``ring`` = (R, W): the table names the pages of a slot's ring of ``R``
    rows, position ``t`` at row ``t mod R``, and a step reads the rows whose
    position lies inside the window ``W`` (:func:`_ring_seen`, as
    :func:`paged_decode_gqa` reads a ring; ``work`` lists ``min(lengths,
    R)`` rows a slot). ``allowed`` (int32, a place of
    the table a column, nonzero: read): a learned selection of the live
    rows; a row outside it never enters the softmax. The kernel keeps one
    name a use, for a trace to tell a model's calls apart by:
    ``paged_decode_mla`` over pages as they lie, ``paged_decode_mla_ring``
    over rings, ``paged_decode_mla_select`` under a selection (``allowed``)."""
    B, one, H, C = q.shape
    assert one == 1
    # a float32 query over bf16 rows takes both products in two passes, its
    # bf16 rounding and what that left side by side as 2 H rows: the kernel
    # adds the halves, and splits the probabilities the same way
    two_pass = q.dtype == jnp.float32 and pool.dtype == jnp.bfloat16
    if pool.ndim not in (4, 5) or (pool.ndim == 5) != (layer is not None) \
            or pool.shape[-4] != 1 or pool.shape[-1] != C:
        raise ValueError(
            "a latent pool is [1, P, page_size, C] (one layer's, no layer "
            "index) or [L, 1, P, page_size, C] with one, C the query's "
            f"width {C}: got {pool.shape} and layer={layer!r}")
    page_size = pool.shape[-2]
    pages_per_seq = block_tables.shape[1]
    lens = _as_lengths(lengths, B)
    tables = jnp.asarray(block_tables, jnp.int32)
    if impl is None:
        impl = "kernel" if jax.default_backend() == "tpu" else "gather"
    layer, pool = _as_stack(layer, pool)
    out_dtype = q.dtype if out_dtype is None else out_dtype
    if allowed is not None:
        allowed = jnp.asarray(allowed, jnp.int32).reshape(
            B, 1, pages_per_seq * page_size)
    if impl == "gather":
        return _mla_gather_attention(q, pool, lens, tables, softmax_scale,
                                     rank, layer, ring, allowed
                                     ).astype(out_dtype)
    if impl != "kernel":
        raise ValueError(f"impl must be None, 'kernel' or 'gather': {impl!r}")

    group = mla_pages_per_step(page_size, C, pool.dtype, pages_per_seq,
                               ring is not None)
    if work is None:
        cap = lens if ring is None else jnp.minimum(lens, ring[0])
        work = paged_work_list(cap, tables, page_size, group)._replace(
            lens=lens)
    elif work.pages.shape[0] != work.rows.shape[0] * group:
        raise ValueError(
            f"a step of this call takes {group} pages, the work list "
            f"{work.pages.shape[0]} for {work.rows.shape[0]} items")
    tokens = group * page_size
    if allowed is not None and pages_per_seq % group:
        # the last group of a table no multiple of it: masked by the lengths
        allowed = jnp.pad(allowed, ((0, 0), (0, 0), (
            0, -pages_per_seq % group * page_size)))
    rows = 2 * H if two_pass else H
    if two_pass:
        hi = jax.lax.reduce_precision(q, exponent_bits=8, mantissa_bits=7)
        q = jnp.concatenate([hi, q - hi], axis=2).astype(pool.dtype)

    # step w scores item w (the last step the last item again) and sums
    # item w - 1: the query and the mask follow the one, the output the other
    def q_block(w, lens, starts, rows, pages, layer, items):
        return rows[jnp.minimum(w, items[0] - 1)], 0, 0

    def allowed_block(w, lens, starts, rows, pages, layer, items):
        item = jnp.minimum(w, items[0] - 1)
        return rows[item], 0, item - starts[rows[item]]

    def out_block(w, lens, starts, rows, *_p):
        return rows[jnp.maximum(w - 1, 0)], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,      # lens, starts, rows, pages, layer, items
        grid=(work.n_items + 1,),
        in_specs=[pl.BlockSpec((1, rows, C), q_block)]
        + ([] if allowed is None else [
            pl.BlockSpec((1, 1, tokens), allowed_block)])
        + [pl.BlockSpec(memory_space=pl.ANY)],  # the pool: the kernel copies
        out_specs=pl.BlockSpec((1, H, rank), out_block),
        scratch_shapes=[
            pltpu.VMEM((3, tokens, C), pool.dtype),     # three items' tiles
            pltpu.SemaphoreType.DMA((3, group)),
            pltpu.VMEM((2, H, tokens), jnp.float32),    # two items' scores
            pltpu.VMEM((2, H, 1), jnp.float32),         # and their maxima
            pltpu.VMEM((H, rank), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mla_kernel, sm_scale=softmax_scale, page_size=page_size,
        group=group, sub=_mla_sub_tile(group, page_size), rank=rank,
        two_pass=two_pass, ring=ring, masked=allowed is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), out_dtype),
        interpret=_interpret(),
        name=("paged_decode_mla_ring" if ring is not None
              else "paged_decode_mla_select" if allowed is not None
              else "paged_decode_mla"),
    )(work.lens, work.starts, work.rows, work.pages,
      jnp.asarray(layer, jnp.int32).reshape(1), work.n_items.reshape(1),
      q.reshape(B, rows, C), *(() if allowed is None else (allowed,)),
      pool)
    return out.reshape(B, 1, H, rank)


def _mla_fetch(w, n_items, page_ref, layer_ref, pool_ref, buf, sem, *,
               group: int, page_size: int, opening=None):
    """The kernel's own copies of the items' page tiles, an item ahead: item
    ``i``'s ``group`` tiles go into ``buf[i mod 3]`` (item ``w - 1``'s are
    still read in step ``w``). Step ``w`` starts item ``w + 1``'s copies
    (step 0 its own too) and waits for item ``w``'s. ``opening``: what else
    step 0 does, in the block that starts its copies (a block of its own
    costs every step 0.07 us: ``scripts/mla_decode_bench.py``)."""
    def copies(item):
        slot = jax.lax.rem(item, 3)
        return [pltpu.make_async_copy(
            pool_ref.at[layer_ref[0], 0, page_ref[item * group + j]],
            buf.at[slot, pl.ds(j * page_size, page_size)], sem.at[slot, j])
            for j in range(group)]

    @pl.when(w == 0)
    def _open():
        for tile in copies(0):
            tile.start()
        if opening is not None:
            opening()

    @pl.when(w + 1 < n_items)
    def _ahead():
        for tile in copies(w + 1):
            tile.start()

    @pl.when(w < n_items)
    def _arrived():
        for tile in copies(w):
            tile.wait()


def _mla_kernel(len_ref, start_ref, row_ref, page_ref, layer_ref, n_ref,
                q_ref, *refs, sm_scale: float, page_size: int, group: int,
                sub: int, rank: int, two_pass: bool, ring=None,
                masked: bool = False):
    """One step of the online softmax over the work list's items, item ``i``
    being table slots ``group j .. group j + group - 1`` of request ``b =
    row_ref[i]``, ``j = i - start_ref[b]``. A tile's rows are the keys of
    ``page_size`` tokens and, in their first ``rank`` columns, the values;
    scores [H, tokens] and the weighted sum [H, rank] are MXU products with
    float32 accumulation.

    Step ``w`` takes TWO items' halves: the scores of item ``w`` (product,
    mask, the rows' maxima, kept in VMEM for the next step) and the update
    of the running softmax with item ``w - 1`` (the new maximum, the
    probabilities of the kept scores, their sum and second product). Neither
    half reads what the other writes, and they are written link by link,
    ``sub`` pages each, a link of scores beside a link of the sum, so the
    MXU takes one half's product while the VPU and XLU work on the other's:
    an item's scores, softmax and sum taken one after the other leave the MXU
    idle 0.47 us an item (the bench, above). The arithmetic a row is the
    same. The last step scores the last item again, for nobody; the first
    sums a block of masked scores (``nothing_scored``). A page past the
    request's end, and the one item of an empty row, are masked whole and
    change nothing.

    ``refs``: the selection's mask of the scored item where ``masked`` ([1,
    tokens] int32), the pool (in HBM: :func:`_mla_fetch`), the output block
    of the summed item's request, three items' tiles and their semaphores,
    two items' scores and maxima, the accumulators. ``two_pass``: the query
    block is ``[q_hi; q_lo]`` and the probabilities are split likewise, the
    halves of each product added. ``ring``: the rows are a slot's ring and a
    row is read where its position lies in the window
    (:func:`_ring_seen`)."""
    allow_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    pool_ref, o_ref, buf, sem, s_ref, top_ref, acc_ref, m_ref, l_ref = refs
    tokens = group * page_size
    heads = acc_ref.shape[0]
    w = pl.program_id(0)
    n_items = n_ref[0]

    def nothing_scored():   # step 0 sums a block of masked scores
        s_ref[1] = jnp.full(s_ref.shape[1:], NEG_INF, s_ref.dtype)
        top_ref[1] = jnp.full(top_ref.shape[1:], NEG_INF, top_ref.dtype)

    _mla_fetch(w, n_items, page_ref, layer_ref, pool_ref, buf, sem,
               group=group, page_size=page_size, opening=nothing_scored)

    def item(i):        # its request's length, rows held, its first row
        b = row_ref[i]
        n = len_ref[b]
        cur = n if ring is None else jnp.minimum(n, ring[0])
        return n, cur, (i - start_ref[b]) * tokens

    scored, summed = jnp.minimum(w, n_items - 1), jnp.maximum(w - 1, 0)
    n, cur, first = item(scored)
    _, cur_sum, first_sum = item(summed)
    # step w keeps its scores in s_ref[w mod 2] and sums the other's
    kept, read = jax.lax.rem(w, 2), jax.lax.rem(w + 1, 2)
    tiles, tiles_sum = jax.lax.rem(scored, 3), jax.lax.rem(summed, 3)

    @pl.when(first_sum == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def folded(a):      # [2 H, n] -> the two passes' sum [H, n]
        return a[:heads] + a[heads:] if two_pass else a

    q = q_ref[0]                                        # [H, C]
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, top_ref[read])
    alpha = jnp.exp(m_prev - m_new)
    l, acc = alpha * l_ref[...], acc_ref[...] * alpha
    top = jnp.full_like(m_prev, NEG_INF)
    for at in range(0, tokens, sub * page_size):
        link = slice(at, at + sub * page_size)
        # the scored item's link: product, mask, the rows' maxima
        rows = buf[tiles, link]
        s = folded(jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)) * sm_scale
        pos = first + at + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = pos < cur if ring is None else _ring_seen(pos, n, ring)
        if ring is not None and ring[0] // page_size % group:
            ok = ok & (pos < ring[0])   # the last group's tiles past it
        if masked:
            ok = ok & (allow_ref[0, :, link] != 0)
        s = jnp.where(ok, s, NEG_INF)
        top = jnp.maximum(top, jnp.max(s, axis=1, keepdims=True))
        # the summed item's: probabilities, their sum, the second product
        seen = s_ref[read, :, link]
        rows = buf[tiles_sum, link]
        p = jnp.where(seen == NEG_INF, 0.0, jnp.exp(seen - m_new))
        l = l + jnp.sum(p, axis=1, keepdims=True)
        if two_pass:
            p_hi = p.astype(rows.dtype)
            p = jnp.concatenate(
                [p_hi, (p - p_hi.astype(jnp.float32)).astype(rows.dtype)],
                axis=0)
        acc = acc + folded(jnp.dot(
            p.astype(rows.dtype), rows[:, :rank],
            preferred_element_type=jnp.float32))
        s_ref[kept, :, link] = s
    top_ref[kept] = top
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l, acc

    @pl.when(first_sum + tokens >= cur_sum)     # the request's last item
    def _finalize():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _mla_gather_attention(q, pool, lens, tables, scale, rank, layer,
                          ring=None, allowed=None):
    """XLA fallback of :func:`paged_decode_mla`: each request's pages
    gathered contiguously, then the masked softmax over the latent rows with
    the kernel's rounding points (float32 scores, probabilities rounded to
    the pool's type for the second product)."""
    B = q.shape[0]
    rows = pool[layer, 0][tables]                   # [B, pages, ps, C]
    rows = rows.reshape(B, -1, rows.shape[-1])
    precise = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
               else None)       # the kernel's two passes
    s = jnp.einsum("bhc,bsc->bhs", q[:, 0], rows, precision=precise,
                   preferred_element_type=jnp.float32) * scale
    at = jnp.arange(rows.shape[1])[None, None, :]
    mask = (at < lens[:, None, None] if ring is None
            else _ring_seen(at, lens[:, None, None], ring))
    if allowed is not None:
        mask = mask & (allowed != 0)
    p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1)
    # a length of 0 attends to nothing and gives 0, as the kernel does
    p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
    if precise is None:
        p = p.astype(rows.dtype)
    return jnp.einsum("bhs,bsr->bhr", p, rows[..., :rank], precision=precise,
                      preferred_element_type=jnp.float32)[:, None]


# ---------------------------------------------------------- multi-token verify
def paged_verify_attention(
    q: jnp.ndarray,           # [B, W, H, Dh] — the speculation window's queries
    k_pages: jnp.ndarray,     # [H, P, page_size, Dh] (or int8/int4 quantized)
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,     # [B] int32: tokens already in the POOL (the
    #                           window is NOT in the pool — it rides win_k/v)
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    win_k: jnp.ndarray,       # [B, W, H, Dh] dense post-rope window keys
    win_v: jnp.ndarray,
    softmax_scale: Optional[float] = None,
    impl: Optional[str] = None,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Speculative-decoding verification attention: score a ``W``-token
    window (the verified last token + k drafted tokens) in ONE pass.

    Window position ``i`` sits at absolute position ``lengths[b] + i`` and
    attends to the pool history (positions ``< lengths[b]``, read through the
    block table and dequantized exactly like :func:`paged_decode_attention`)
    plus window positions ``0..i`` (causal within the window). The window's
    K/V never touch the pool here — they arrive DENSE as ``win_k``/``win_v``
    and are committed separately, only up to the accepted prefix
    (``models/gpt.commit_window_kv``), which is what makes rejected-suffix
    rollback a no-op instead of an undo.

    The XLA ``gather`` fallback scatters the window K/V into the gathered
    pool copy at their true absolute positions and then runs EXACTLY the
    single-token fallback's masked softmax per window position — for dense
    pools the position-``i`` value stream is structurally identical to what
    ``i`` sequential :func:`paged_decode_attention` fallback calls would
    compute: the same values at the same positions reduced over the same
    axis, differing only by how XLA tiles the reduction for a different
    ``W`` (observed <=1e-7 on fp32 — argmax-stable, which is what the
    spec-on == spec-off greedy-equivalence gate measures at 1.0). The
    Pallas kernel streams pool pages like the single-token kernel and
    handles the window as one extra (causal) tile on the same online-softmax
    state; kernel vs fallback agree to fp tolerance (tested).
    """
    B, W, H, Dh = q.shape
    if win_k.shape != (B, W, H, Dh) or win_v.shape != (B, W, H, Dh):
        raise ValueError(
            f"win_k/win_v must be [B, W, H, Dh]={(B, W, H, Dh)}, got "
            f"{win_k.shape} / {win_v.shape}")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    quantized = k_scales is not None
    packed = quantized and k_pages.shape[-1] * 2 == Dh
    if quantized and not packed and k_pages.shape[-1] != Dh:
        raise ValueError(
            f"quantized pool last dim {k_pages.shape[-1]} matches neither "
            f"int8 ({Dh}) nor packed int4 ({Dh // 2})")
    page_size = k_pages.shape[2]
    pages_per_seq = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(Dh)
    lens = _as_lengths(lengths, B)
    tables = jnp.asarray(block_tables, jnp.int32)
    if not quantized:
        # mirror the sequential append's pool cast, so the fallback reads
        # the same bits a committed-then-read window token would have
        win_k = win_k.astype(k_pages.dtype)
        win_v = win_v.astype(v_pages.dtype)
    if impl is None:
        impl = "kernel" if jax.default_backend() == "tpu" else "gather"
    if impl == "gather":
        return _paged_verify_gather(q, k_pages, v_pages, lens, tables,
                                    win_k, win_v, scale, k_scales, v_scales)
    if impl != "kernel":
        raise ValueError(f"impl must be None, 'kernel' or 'gather': {impl!r}")

    qh = q.transpose(0, 2, 1, 3)        # [B, H, W, Dh]
    wkh = win_k.transpose(0, 2, 1, 3)   # [B, H, W, Dh]
    wvh = win_v.transpose(0, 2, 1, 3)
    Dp = k_pages.shape[-1]
    n_prefetch = 4 if quantized else 2
    # grid walks the table's pages, then ONE extra step for the window tile;
    # the pool index_map clamps at the last table slot for that step (its
    # fetch is unused — the body only reads the window operands there)
    kv_spec = pl.BlockSpec(
        (1, 1, page_size, Dp),
        lambda b, h, i, lens, tbl, *_s: (
            h, tbl[b, jnp.minimum(i, pages_per_seq - 1)], 0, 0))
    win_spec = pl.BlockSpec((1, 1, W, Dh),
                            lambda b, h, i, lens, tbl, *_s: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, H, pages_per_seq + 1),
        in_specs=[win_spec, kv_spec, kv_spec, win_spec, win_spec],
        out_specs=pl.BlockSpec((1, 1, W, Dh),
                               lambda b, h, i, lens, tbl, *_s: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((W, Dh), jnp.float32),
            pltpu.VMEM((W, 1), jnp.float32),
            pltpu.VMEM((W, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _verify_kernel, sm_scale=scale, page_size=page_size,
        num_pages=pages_per_seq, window=W, quantized=quantized,
        packed=packed)
    operands = ((lens, tables, k_scales.astype(jnp.float32),
                 v_scales.astype(jnp.float32), qh, k_pages, v_pages, wkh, wvh)
                if quantized else (lens, tables, qh, k_pages, v_pages,
                                   wkh, wvh))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, W, Dh), q.dtype),
        interpret=_interpret(),
        name="paged_verify",
    )(*operands)
    return out.transpose(0, 2, 1, 3)    # back to [B, W, H, Dh]


def _verify_kernel(len_ref, tbl_ref, *refs, sm_scale: float, page_size: int,
                   num_pages: int, window: int, quantized: bool,
                   packed: bool):
    """Online softmax over (pool pages ++ the causal window tile), with a
    [W, ·] state row per window position. Pool tiles mask at the POOL length
    (every window query sees the whole history); the final grid step scores
    the window against itself with the in-window causal mask and
    finalizes."""
    if quantized:
        (ks_ref, vs_ref, q_ref, k_ref, v_ref, wk_ref, wv_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    else:
        ks_ref = vs_ref = None
        (q_ref, k_ref, v_ref, wk_ref, wv_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    ki = pl.program_id(2)
    cur = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _online_update(s, v):
        """s: [W, bk] masked scores; v: [bk, Dh] values."""
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)

    @pl.when(jnp.logical_and(ki < num_pages, ki * page_size < cur))
    def _pool_tile():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale   # [W, Dh]
        kq = k_ref[0, 0]
        vq = v_ref[0, 0]
        if quantized:
            if packed:
                k = unpack_kv_int4(kq)
                v = unpack_kv_int4(vq)
            else:
                k = kq.astype(jnp.float32)
                v = vq.astype(jnp.float32)
            page = tbl_ref[b, ki]
            k = k * ks_ref[h, page]
            v = v * vs_ref[h, page]
        else:
            k = kq.astype(jnp.float32)
            v = vq.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [W, ps]
        s_pos = (ki * page_size
                 + jax.lax.broadcasted_iota(jnp.int32, (window, page_size), 1))
        # pool history is valid for EVERY window query: the window itself
        # never lives in the pool during verification
        s = jnp.where(s_pos < cur, s, NEG_INF)
        _online_update(s, v)

    @pl.when(ki == num_pages)
    def _window_tile():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale   # [W, Dh]
        wk = wk_ref[0, 0].astype(jnp.float32)            # [W, Dh]
        wv = wv_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, wk, (((1,), (1,)), ((), ())))  # [W, W]
        row = jax.lax.broadcasted_iota(jnp.int32, (window, window), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (window, window), 1)
        s = jnp.where(col <= row, s, NEG_INF)  # causal within the window
        _online_update(s, wv)
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _paged_verify_gather(q, k_pages, v_pages, lens, tables, win_k, win_v,
                         scale, k_scales=None, v_scales=None):
    """XLA fallback for :func:`paged_verify_attention`: gather the pool like
    the single-token fallback, scatter the dense window K/V at their true
    absolute positions (``lengths[b] + i`` maps to gathered index
    ``lengths[b] + i`` because gathered order IS table order), then run the
    identical masked softmax once per window position via one einsum. For a
    dense pool the per-position arithmetic is bit-identical to ``W``
    sequential single-token fallback calls over a pool holding the same
    committed tokens."""
    B, W, H, Dh = q.shape

    def gather(pages, scales):
        g = pages[:, tables]          # [H, B, n, ps, Dp]
        if scales is not None:
            g = (unpack_kv_int4(g) if g.shape[-1] * 2 == Dh
                 else g.astype(jnp.float32))
            g = g * scales[:, tables][..., None, None]
        g = g.transpose(1, 0, 2, 3, 4)
        return g.reshape(B, g.shape[1], -1, g.shape[-1])  # [B, H, S, Dh]

    k = gather(k_pages, k_scales)
    v = gather(v_pages, v_scales)
    S = k.shape[2]
    # window position i lives at absolute (= gathered) position lens + i;
    # positions past the table capacity DROP (never clip: clipping would
    # overwrite an earlier window token's K/V at S-1 for a request whose
    # final window touches the capacity edge — a committable query would
    # then attend a rejected draft's K/V at its own position). Dropped
    # positions can never be committed: budget caps n at max_new, and
    # admission bounds prompt+max_new to the table.
    pos = lens[:, None] + jnp.arange(W)[None, :]              # [B, W]
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, W))
    k = k.at[bidx, :, pos, :].set(win_k.astype(k.dtype), mode="drop")
    v = v.at[bidx, :, pos, :].set(win_v.astype(v.dtype), mode="drop")
    s = jnp.einsum("bwhd,bhsd->bhws", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    # query i sees positions < lens + i + 1 (history + window prefix + self)
    limit = lens[:, None] + jnp.arange(1, W + 1)[None, :]      # [B, W]
    mask = jnp.arange(S)[None, None, :] < limit[:, :, None]    # [B, W, S]
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhws,bhsd->bwhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _paged_gather_attention(q, k_pages, v_pages, lens, tables, scale,
                            k_scales=None, v_scales=None, layer=None):
    """XLA fallback: materialize each request's pages contiguously (one
    gather), then the same masked softmax the dense reference computes — the
    value stream is arithmetically identical to attending over a contiguous
    cache holding the same tokens, so tests check it BITWISE against the
    dense path (dense pools) and against dequantize-then-dense (quantized
    pools: the fallback consumes the identical int payload, so the only
    difference from a dense cache is the quantization itself). With a
    ``layer`` index the pools (and scales) are whole stacks, and the one
    gather takes that layer's pages out of them."""
    B = q.shape[0]
    Dh = q.shape[-1]
    layer, k_pages, v_pages, k_scales, v_scales = _as_stack(
        layer, k_pages, v_pages, k_scales, v_scales)

    def of_tables(a):  # a request's pages (or their scales): [B, H, n, ...]
        # a scalar and an index array around a slice: the indexed axes lead
        return jnp.moveaxis(a[layer, :, tables], 2, 1)

    # [B, H, pages, ps, Dp] -> [B, H, pages*ps, Dh]
    def gather(pages, scales):
        g = of_tables(pages)
        if scales is not None:
            g = (unpack_kv_int4(g) if g.shape[-1] * 2 == Dh
                 else g.astype(jnp.float32))
            g = g * of_tables(scales)[..., None, None]
        return g.reshape(B, g.shape[1], -1, g.shape[-1])

    k = gather(k_pages, k_scales)
    v = gather(v_pages, v_scales)
    s = jnp.einsum("bthd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    S = k.shape[2]
    mask = jnp.arange(S)[None, None, None, :] < lens[:, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhts,bhsd->bthd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _exact(rows):
    """The precision of a kernel's products over the cached ``rows``: float32
    pages (``GPTConfig.attn_float32``) take the MXU's full precision, without
    which it takes float32 operands in one bf16 pass; None for any other
    type. (From here to the file's end: what was added beside standing
    kernels. A Mosaic kernel's serialized body carries its lines' numbers,
    and a line added above one would change every program that holds it,
    ``scripts/stablehlo_sums.py``.)"""
    return jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None


# what a grid step of the GQA kernel aims at: this many bytes of K and V
# tiles (two pipeline buffers of them are 2 MiB of VMEM, as
# ``_PAGED_KV_VMEM_BYTES``), in no more pages than this (a page is two
# operands of the call). On the v5e (``scripts/gqa_decode_bench.py``) four
# pages of 256 KB a step move at their copies' pace, 0.37 us a page for 0.67
# a page a step, and eight no faster
_GQA_STEP_BYTES = 1024 * 1024
_GQA_MAX_PAGES = 8


def gqa_pages_per_step(n_kv_head: int, page_size: int, head_dim: int, dtype,
                       width: int, ring: bool) -> int:
    """Pages a grid step of :func:`paged_decode_gqa` takes of one request,
    from the call's static shapes alone (the kernel and whoever builds its
    work list, ``models/gpt.gqa_work``, both ask here): the power of two
    whose K and V tiles, every key-value head of a step's block in each,
    come nearest under ``_GQA_STEP_BYTES``, at most ``_GQA_MAX_PAGES``; and
    no more than a quarter of the table's ``width`` slots, because a group
    fetches and scores the tiles past a request's last page too, and a table
    is sized for the longest request (at 11.5 pages a request of 24, 8 a
    step mask 23-39% of their tiles and 4 12%; 8 were 3-8% faster at tiles of
    128 KB all the same, but a request of a page or two would pay all
    eight). A ring is read whole once its request passes the window, which
    is what a window layer is for: there the width itself bounds the
    group."""
    itemsize = jnp.dtype(dtype).itemsize
    heads = _heads_per_step(n_kv_head, page_size, head_dim, itemsize)
    fit = min(_GQA_STEP_BYTES // (2 * heads * page_size * head_dim * itemsize),
              _GQA_MAX_PAGES, width if ring else width // 4)
    return 1 << max(fit, 1).bit_length() - 1


def _gqa_gather_attention(q, k_pages, v_pages, lens, tables, scale, layer,
                          ring):
    """XLA fallback of :func:`paged_decode_gqa`: each request's pages (or
    its ring) gathered contiguously, then the masked softmax with the
    kernel's rounding points (float32 scores, probabilities rounded to the
    pool's type for the second product unless the query is float32)."""
    B, _, H, Dh = q.shape
    G = k_pages.shape[1]

    def gather(pages):          # [B, G, pages * ps, Dh]
        g = jnp.moveaxis(pages[layer, :, tables], 2, 1)
        return g.reshape(B, G, -1, Dh)

    k, v = gather(k_pages), gather(v_pages)
    precise = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
               else None)       # the kernel's two passes
    s = jnp.einsum("bgrd,bgsd->bgrs", q.reshape(B, G, H // G, Dh), k,
                   precision=precise,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(k.shape[2])[None, :]
    n = lens[:, None]
    seen = pos < n if ring is None else _ring_seen(pos, n, ring)
    p = jax.nn.softmax(jnp.where(seen[:, None, None], s, NEG_INF), axis=-1)
    # a length of 0 attends to nothing and gives 0, as the kernel does
    p = jnp.where((lens > 0)[:, None, None, None], p, 0.0)
    if precise is None:
        p = p.astype(v.dtype)
    out = jnp.einsum("bgrs,bgsd->bgrd", p, v, precision=precise,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, Dh)


# ---------------------------------- a key head a query head (``paged_decode``)
def _paged_on_mxu(page_size: int, head_dim: int, dtype,
                   quantized: bool) -> bool:
    """Whether a call's shapes take a step of :func:`_gqa_kernel`'s, a group
    of one query a key-value head (:func:`_paged_group_call`): heads of one
    vector's 128 lanes, pages of whole tiles of rows, a dense pool of
    bfloat16 or float32. A quantized tile (its packed nibbles, its scales in
    SMEM) keeps :func:`_paged_kernel`'s step a page, as heads of 64 do."""
    return (not quantized and head_dim == 128
            and dtype in (jnp.bfloat16, jnp.float32)
            and page_size % (32 // jnp.dtype(dtype).itemsize) == 0)


def paged_pages_per_step(n_head: int, page_size: int, head_dim: int, dtype,
                         table: int, quantized: bool = False) -> int:
    """Pages a grid step of :func:`paged_decode_attention` takes of one
    request, from the call's static shapes alone (the kernel and whoever
    builds its work list, ``models/gpt.paged_work``, both ask here): 1 where
    the step is :func:`_paged_kernel`'s, else what
    :func:`gqa_pages_per_step` answers for as many key-value heads."""
    if not _paged_on_mxu(page_size, head_dim, dtype, quantized):
        return 1
    return gqa_pages_per_step(n_head, page_size, head_dim, dtype, table,
                              False)


def paged_held_list(lengths: jnp.ndarray, block_tables: jnp.ndarray,
                    page_size: int, group: int) -> PagedWork:
    """:func:`paged_work_list` for :func:`paged_decode_attention`, whose
    tiles past a request's last page name, tile for tile, the page that tile
    of the item before named (or of the last item that had one live there):
    a block whose index stands still is not copied again, so a request that
    ends inside a group costs its masked tiles' arithmetic and none of their
    bytes. What such a tile holds is another request's rows, masked by the
    lengths as the repeated last page was."""
    work = paged_work_list(lengths, block_tables, page_size, group)
    if group == 1:
        return work
    owned = -(-jnp.maximum(work.lens, 1) // page_size)
    w = jnp.arange(work.rows.shape[0], dtype=jnp.int32)
    slot = (w - work.starts[work.rows])[:, None] * group + jnp.arange(group)
    live = slot < owned[work.rows][:, None]             # [items, group]
    last = jax.lax.cummax(jnp.where(live, w[:, None], -1), axis=0)
    pages = work.pages.reshape(-1, group)
    held = jnp.take_along_axis(pages, jnp.maximum(last, 0), axis=0)
    return work._replace(
        pages=jnp.where(last >= 0, held, pages).reshape(-1))


def paged_pool_list(lengths, block_tables, pool, quantized: bool) -> PagedWork:
    """:func:`paged_held_list` in the groups a grid step of
    :func:`paged_decode_attention` takes over ``pool`` ([.., H, P, page_size,
    Dh]: the heads a tensor-parallel shard holds): what a caller with many
    layers builds once a decode step (``models/gpt.paged_work``)."""
    H, _, page_size, width = pool.shape[-4:]
    return paged_held_list(lengths, block_tables, page_size,
                           paged_pages_per_step(
                               H, page_size, width, pool.dtype,
                               block_tables.shape[1], quantized))


def _paged_listed(work, lens, tables, page_size: int, group: int) -> PagedWork:
    """The caller's list where it handed one, in the groups the call's step
    takes; else the call's own."""
    if work is None:
        return paged_held_list(lens, tables, page_size, group)
    if work.pages.shape[0] != work.rows.shape[0] * group:
        raise ValueError(
            f"a step of this call takes {group} pages, the work list "
            f"{work.pages.shape[0]} for {work.rows.shape[0]} items")
    return work


def _paged_group_call(q, k_pages, v_pages, lens, tables, scale, layer, work):
    """``paged_decode`` as :func:`paged_decode_gqa` with a key-value head a
    query head, a group of ONE query: a head's page of rows is the MXU's
    standing operand for its one query and for its row of probabilities.
    The query goes in as float32, so that over bfloat16 pages both products
    take that kernel's two passes (the query's bfloat16 rounding and what
    that left, nothing for a bfloat16 query; the probabilities' likewise, so
    they are never rounded once) and float32 pages the MXU's full precision
    (:func:`_exact`); the kernel keeps ``paged_decode``'s name.

    On the v5e (``scripts/paged_decode_bench.py``, 16 heads of 128 over bf16
    pages of 64 at ``batch-decode``'s lengths, 326 live pages a call; PR 54)
    a step of two pages takes 0.79 us a live page, its copies alone 0.71 and
    its arithmetic alone 0.48; a step a page 0.91 for 0.72 and 0.61 (a step
    costs its arithmetic and 0.29 us of starting and awaiting its copies,
    where that is more than the copies take), four pages 0.84 (a third of
    their tiles past a request's end, scored for nothing); with the tiles
    past a request's end copied again, 0.82 at two pages. The step a page on
    the VPU (:func:`_paged_kernel`) took 1.06 for an arithmetic of 0.95, and
    the form that keeps the QUERY standing (its 128 numbers down every
    column of the weights, a head's rows streaming past, so that the scores
    come out on every lane and ``p * v`` stays on the VPU) 1.96: a weight
    load and a chain a head a step, 2.3 us a step before its first page."""
    return paged_decode_gqa(
        q.astype(jnp.float32), k_pages, v_pages, lens, tables, scale,
        "kernel", layer, work, out_dtype=q.dtype, name="paged_decode")
