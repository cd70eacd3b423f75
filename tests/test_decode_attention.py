"""Pallas decode-attention kernel vs dense reference; generate-path integration."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.decode_attention import decode_attention


def _dense_decode(q, k_cache, v_cache, cur_len):
    """q: [B, 1, H, Dh]; k_cache/v_cache: [B, H, S, Dh]."""
    B, _, H, Dh = q.shape
    S = k_cache.shape[2]
    s = jnp.einsum("bthd,bhsd->bhts", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / np.sqrt(Dh)
    mask = jnp.arange(S)[None, None, None, :] < cur_len
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bhsd->bthd", p, v_cache.astype(jnp.float32))


@pytest.mark.parametrize("cur_len", [1, 7, 16, 32])
def test_decode_matches_dense(rng, cur_len):
    B, S, H, Dh = 2, 32, 4, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, Dh)), jnp.float32)
    out = decode_attention(q, k, v, jnp.int32(cur_len), block_k=8)
    ref = _dense_decode(q, k, v, cur_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("batch", [1, 8, 16, 32])
def test_decode_wide_batch(rng, batch):
    """Regression for the b16 BlockSpec/index_map Mosaic rejection of the
    old decode kernel: the (b, h, ki) grid must run at every batch width.
    The scalar length operand now rides scalar prefetch (SMEM), not a
    memory-space-less VMEM block."""
    S, H, Dh = 64, 4, 16
    q = jnp.asarray(rng.normal(size=(batch, 1, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(batch, H, S, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(batch, H, S, Dh)), jnp.float32)
    out = decode_attention(q, k, v, jnp.int32(40), block_k=16)
    ref = _dense_decode(q, k, v, 40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_decode_per_row_lengths(rng):
    """Continuous batching: every batch row decodes at its OWN cache length
    (a [B] lengths vector instead of the legacy scalar)."""
    B, S, H, Dh = 16, 64, 4, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, Dh)), jnp.float32)
    lens = jnp.asarray(rng.integers(1, S + 1, size=(B,)), jnp.int32)
    out = np.asarray(decode_attention(q, k, v, lens, block_k=16))
    for b in range(B):
        ref = _dense_decode(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                            int(lens[b]))
        np.testing.assert_allclose(out[b:b + 1], np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)
    with pytest.raises(ValueError, match="scalar or"):
        decode_attention(q, k, v, lens[: B // 2], block_k=16)


def _scatter_pool(rng, k, v, page_size, num_pages):
    """Place a contiguous [B, H, S, Dh] cache into a shuffled page pool;
    returns (k_pages [H, P, ps, Dh], v_pages, tables [B, S/ps])."""
    B, H, S, Dh = k.shape
    per_seq = S // page_size
    assert B * per_seq <= num_pages - 1
    ids = list(range(1, num_pages))
    rng.shuffle(ids)
    k_pages = np.zeros((H, num_pages, page_size, Dh), np.float32)
    v_pages = np.zeros((H, num_pages, page_size, Dh), np.float32)
    tables = np.zeros((B, per_seq), np.int32)
    for b in range(B):
        for i in range(per_seq):
            pg = ids.pop()
            tables[b, i] = pg
            sl = slice(i * page_size, (i + 1) * page_size)
            k_pages[:, pg] = k[b, :, sl, :]
            v_pages[:, pg] = v[b, :, sl, :]
    return jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(tables)


@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_paged_decode_matches_dense(rng, impl):
    """The block-table gather (kernel index_map or XLA fallback) must be
    invisible: paged output == dense contiguous-cache attention at mixed
    per-row lengths."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention

    B, S, H, Dh, ps = 8, 64, 4, 16, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, Dh)), jnp.float32)
    lens = jnp.asarray(rng.integers(1, S + 1, size=(B,)), jnp.int32)
    k_pages, v_pages, tables = _scatter_pool(rng, np.asarray(k),
                                             np.asarray(v), ps, 64)
    out = paged_decode_attention(q, k_pages, v_pages, lens, tables,
                                 impl=impl)
    ref = _dense_decode(q, k, v, lens.reshape(B, 1, 1, 1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def _paged_case(rng, lens, H, Dh, ps, table, dtype=jnp.float32):
    """A shuffled pool holding ``lens[b]`` tokens a request behind a table
    ``table`` slots wide (slots past a request's pages name the sink, page 0).
    Returns (q, k_pages, v_pages, lens, tables) and the dense reference."""
    B = len(lens)
    need = [-(-n // ps) for n in lens]
    assert max(need) <= table
    S = table * ps
    q = rng.normal(size=(B, 1, H, Dh)).astype(np.float32)
    k = rng.normal(size=(B, H, S, Dh)).astype(np.float32)
    v = rng.normal(size=(B, H, S, Dh)).astype(np.float32)
    P = sum(need) + 1
    ids = list(range(1, P))
    rng.shuffle(ids)
    k_pages = rng.normal(size=(H, P, ps, Dh)).astype(np.float32)  # the sink
    v_pages = rng.normal(size=(H, P, ps, Dh)).astype(np.float32)  # holds junk
    tables = np.zeros((B, table), np.int32)
    for b in range(B):
        for i in range(need[b]):
            pg = tables[b, i] = ids.pop()
            k_pages[:, pg] = k[b, :, i * ps:(i + 1) * ps]
            v_pages[:, pg] = v[b, :, i * ps:(i + 1) * ps]
    cast = lambda x: jnp.asarray(x).astype(dtype)  # noqa: E731
    q, k_pages, v_pages = cast(q), cast(k_pages), cast(v_pages)
    ref = _dense_decode(q, cast(k), cast(v),
                        jnp.asarray(lens).reshape(B, 1, 1, 1))
    return (q, k_pages, v_pages, jnp.asarray(lens, jnp.int32),
            jnp.asarray(tables)), ref


# what the paged grid has to get right beyond mixed lengths: (lens, H, Dh,
# page size, table width, pool dtype, heads a grid step)
PAGED_CASES = {
    # a table four times wider than any request needs: 12 of 16 slots dead
    "dead_slots": ([5, 16, 33, 64], 4, 16, 16, 16, jnp.float32, 4),
    # an idle slot (all sink), one token, exactly a page, a page and one
    "len_0": ([0, 9], 4, 16, 8, 4, jnp.float32, 4),
    "len_1": ([1, 1], 4, 16, 8, 4, jnp.float32, 4),
    "len_page": ([8, 16], 4, 16, 8, 4, jnp.float32, 4),
    "len_page_plus_1": ([9, 17], 4, 16, 8, 4, jnp.float32, 4),
    "heads_3": ([7, 30, 12], 3, 16, 8, 4, jnp.float32, 3),
    "heads_16": ([7, 30, 12], 16, 16, 8, 4, jnp.float32, 16),
    "bf16_pool": ([5, 16, 33, 64], 4, 16, 16, 8, jnp.bfloat16, 4),
    # 16 heads of 128 over float32 pages of 128 are 4 MiB of K and V
    # buffers: over the budget, so a grid step takes 8 heads and there are 2
    "head_blocks": ([130, 256], 16, 128, 128, 2, jnp.float32, 8),
    # what a grid that walks the live pages alone has to get right: idle rows
    # first, between and last (each keeps one masked step, and answers 0)
    # beside rows of one token; every row as long as its table (nothing to
    # skip); heads of 64
    "len_0_and_1": ([0, 1, 0, 9, 1, 0], 4, 16, 8, 4, jnp.float32, 4),
    "full_tables": ([64, 64, 64], 4, 16, 16, 4, jnp.float32, 4),
    "head_dim_64": ([0, 70, 1, 200], 4, 64, 16, 16, jnp.bfloat16, 4),
    # heads of 128: the query stands still and a GROUP of a request's pages
    # streams past it (``STREAM_GROUPS``: the pages a step). Idle rows, one
    # token, a page, a page and one, requests that end inside a group and at
    # its end; a table four times wider than any request needs; 3 and 16
    # heads; bf16 pages of 16 rows (of 8 they keep the step a page)
    "stream_lens": ([0, 1, 8, 9, 17, 40, 0, 64], 4, 128, 8, 8, jnp.float32,
                    4),
    "stream_dead_slots": ([5, 16, 33, 64], 4, 128, 16, 16, jnp.float32, 4),
    "stream_heads_3": ([7, 30, 12], 3, 128, 8, 8, jnp.float32, 3),
    "stream_heads_16": ([7, 30, 12, 0], 16, 128, 8, 8, jnp.float32, 16),
    "stream_bf16": ([5, 16, 33, 64, 0, 1], 4, 128, 16, 8, jnp.bfloat16, 4),
    "bf16_pages_of_8": ([5, 16, 33], 4, 128, 8, 8, jnp.bfloat16, 4),
}
# the cases whose shapes take ``_paged_stream_kernel``, and the pages a step
STREAM_GROUPS = {"head_blocks": 1, "stream_lens": 2, "stream_dead_slots": 4,
                 "stream_heads_3": 2, "stream_heads_16": 2, "stream_bf16": 2}


@pytest.mark.parametrize("form", ["pool_4d", "stack_5d"])
@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_kernel_cases(rng, case, form):
    """The kernel (interpret mode) against the gather path and the dense
    reference, at the present tolerances, in both call forms: one layer's
    pool, and layer 1 of a stack of two whose layer 0 holds junk."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        _heads_per_step, _paged_on_mxu, paged_decode_attention,
        paged_pages_per_step)

    lens, H, Dh, ps, table, dtype, heads = PAGED_CASES[case]
    args, ref = _paged_case(rng, lens, H, Dh, ps, table, dtype)
    assert _heads_per_step(H, ps, Dh, jnp.dtype(dtype).itemsize) == heads
    assert _paged_on_mxu(ps, Dh, dtype, False) == (case in STREAM_GROUPS)
    assert paged_pages_per_step(H, ps, Dh, dtype, table) == \
        STREAM_GROUPS.get(case, 1)
    kw = {}
    if form == "stack_5d":
        q, k_pages, v_pages, lengths, tables = args
        junk = jnp.asarray(rng.normal(size=k_pages.shape), k_pages.dtype)
        args = (q, jnp.stack([junk, k_pages]), jnp.stack([junk, v_pages]),
                lengths, tables)
        kw = dict(layer=jnp.int32(1))
    out = paged_decode_attention(*args, impl="kernel", **kw)
    gathered = paged_decode_attention(*args, impl="gather", **kw)
    assert out.dtype == args[0].dtype and out.shape == args[0].shape
    live = np.asarray(lens) > 0
    # a bfloat16 output is one rounding of the same float32 sum: 2**-8
    tol = (dict(atol=2e-5, rtol=1e-4) if dtype == jnp.float32
           else dict(atol=4e-3, rtol=4e-3))
    out, gathered, ref = (np.asarray(x, np.float32)
                          for x in (out, gathered, ref))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[live], gathered[live], **tol)
    np.testing.assert_allclose(out[live], ref[live], **tol)
    # nothing attended: the kernel answers 0, never the sink page's junk
    assert not out[~live].any()


WORK_LISTS = {
    # lengths, page size, table width
    "mixed": ([5, 16, 33, 64, 17], 16, 6),
    "idle_rows": ([0, 1, 0, 9, 0], 8, 4),
    "full_tables": ([32, 32], 8, 4),
    "one_row": ([23], 4, 8),
    # the latent kernel's: eight pages of 64 an item over tables of 48, three
    # over a ring of 9 (lengths inside, at and past a group, and the ring's)
    "latent_tables": ([1771, 0, 3072, 512, 513, 1], 64, 48),
    "a_ring_of_9_pages": ([576, 0, 1, 200, 576], 64, 9),
}


@pytest.mark.parametrize("group", [1, 2, 3, 4, 8])
@pytest.mark.parametrize("case", sorted(WORK_LISTS))
def test_paged_work_list(rng, case, group):
    """The list the paged grid walks: each row's table slots 0 .. pages - 1
    in row order, ``group`` of them an item, an idle row's one masked item
    among them, their count the sum, and each item's pages the table's; past
    the row's last page an item names that page again."""
    from deepspeed_tpu.ops.pallas.decode_attention import paged_work_list

    lens, ps, table = WORK_LISTS[case]
    B = len(lens)
    tables = rng.integers(1, 99, size=(B, table)).astype(np.int32)
    work = jax.jit(paged_work_list, static_argnums=(2, 3))(
        jnp.asarray(lens, jnp.int32), jnp.asarray(tables), ps, group)
    owned = [max(1, -(-n // ps)) for n in lens]
    items = [-(-p // group) for p in owned]
    n = int(work.n_items)
    assert n == sum(items)
    rows = np.asarray(work.rows)
    assert rows.shape == (B * -(-table // group),)
    pages = np.asarray(work.pages).reshape(-1, group)
    assert len(pages) == len(rows)
    at = np.arange(len(rows)) - np.asarray(work.starts)[rows]
    want = [(b, i) for b in range(B) for i in range(items[b])]
    assert list(zip(rows[:n].tolist(), at[:n].tolist())) == want
    assert pages[:n].tolist() == [
        [int(tables[b, min(group * i + j, owned[b] - 1)])
         for j in range(group)] for b, i in want]
    # past the count the arrays stay in range: nothing there is visited
    assert (rows[n:] == B - 1).all()
    np.testing.assert_array_equal(work.lens, lens)


@pytest.mark.parametrize("case", sorted(WORK_LISTS))
def test_a_list_a_page_an_item_is_what_it_was(rng, case):
    """``group=1`` (``paged_decode`` and ``paged_decode_q``: the default) is
    the list before groups, entry for entry to the arrays' static end: every
    slot of every table, the tail repeating the last item."""
    from deepspeed_tpu.ops.pallas.decode_attention import paged_work_list

    lens, ps, table = WORK_LISTS[case]
    B = len(lens)
    tables = rng.integers(1, 99, size=(B, table)).astype(np.int32)
    owned = np.asarray([max(1, -(-n // ps)) for n in lens])
    ends = np.cumsum(owned)
    w = np.arange(B * table)
    rows = np.minimum((w[:, None] >= ends[None, :]).sum(axis=1), B - 1)
    slots = np.minimum(w - (ends - owned)[rows], owned[rows] - 1)
    for work in (paged_work_list(jnp.asarray(lens), jnp.asarray(tables), ps),
                 paged_work_list(jnp.asarray(lens), jnp.asarray(tables), ps,
                                 group=1)):
        np.testing.assert_array_equal(work.starts, ends - owned)
        np.testing.assert_array_equal(work.rows, rows)
        np.testing.assert_array_equal(work.pages, tables[rows, slots])
        assert int(work.n_items) == ends[-1]
        assert {a.dtype for a in work} == {jnp.dtype(jnp.int32)}


@pytest.mark.parametrize("case", ["dead_slots", "stream_dead_slots",
                                  "stream_lens"])
def test_paged_kernel_takes_the_callers_work_list(rng, case):
    """A caller with many layers builds the list once and hands it to every
    call: the outputs are those of a call that builds its own, bit for bit;
    a list in groups of another size than the call's step is refused."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention, paged_work_list)

    lens, H, Dh, ps, table, _, _ = PAGED_CASES[case]
    group = STREAM_GROUPS.get(case, 1)
    args, _ = _paged_case(rng, lens, H, Dh, ps, table)
    work = paged_work_list(args[3], args[4], ps, group)
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(*args, impl="kernel", work=work)),
        np.asarray(paged_decode_attention(*args, impl="kernel")))
    with pytest.raises(ValueError, match=f"takes {group} pages"):
        paged_decode_attention(*args, impl="kernel", work=paged_work_list(
            args[3], args[4], ps, group + 1))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_decode_step_lists_its_pages_once_for_every_layer(rng, dtype):
    """``models/gpt.paged_work`` groups a step's list as the kernel's step
    takes it (heads of 128: two pages of a request), and
    ``append_and_attend`` over it is the gather path's answer with the new
    token in the pool, layer by layer of a stack."""
    from deepspeed_tpu.models import gpt as G

    H, Dh, ps, table, L = 2, 128, 16, 8, 2
    lens = [0, 15, 16, 40, 127]       # cached: the step appends one to each
    args, _ = _paged_case(rng, [n + 1 for n in lens], H, Dh, ps, table, dtype)
    q, k_pages, v_pages, _, tables = args
    pools = (jnp.stack([k_pages] * L), jnp.stack([v_pages] * L))
    lengths = jnp.asarray(lens, jnp.int32)
    work = G.paged_work({"k_pages": pools[0]}, tables, lengths)
    assert work.pages.shape[0] == 2 * work.rows.shape[0]
    new = [jnp.asarray(rng.normal(size=q.shape), dtype) for _ in range(2)]
    for layer in range(L):
        out = {impl: G.append_and_attend(
            pools, jnp.int32(layer), q, *new, tables, lengths, Dh ** -0.5,
            impl=impl, work=work if impl == "kernel" else None)
            for impl in ("kernel", "gather")}
        for a, b in zip(out["kernel"][1], out["gather"][1]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        tol = (dict(atol=2e-5, rtol=1e-4) if dtype == jnp.float32
               else dict(atol=4e-3, rtol=4e-3))
        np.testing.assert_allclose(
            np.asarray(out["kernel"][0], np.float32),
            np.asarray(out["gather"][0], np.float32), **tol)


def _paged_grid(H, table):
    """The grid of the ``paged_decode`` ``pallas_call``, read off the jaxpr."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention

    q = jnp.zeros((6, 1, H, 16), jnp.float32)
    pages = jnp.zeros((H, 9, 8, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, p: paged_decode_attention(
        q, p, p, jnp.full((6,), 5, jnp.int32),
        jnp.zeros((6, table), jnp.int32), impl="kernel"))(q, pages)
    grids = [eqn.params["grid_mapping"].grid for eqn in jaxpr.jaxpr.eqns
             if eqn.primitive.name == "pallas_call"]
    assert len(grids) == 1, grids
    return grids[0]


def test_paged_grid_does_not_grow_with_heads():
    """One grid step covers every head of a request's page: while the heads
    fit one block, the grid is one head block by the batch's live pages, a
    traced bound, whatever H and the table's width are."""
    for H, table in ((3, 4), (16, 4), (16, 8)):
        head_blocks, items = _paged_grid(H, table)
        assert head_blocks == 1 and not isinstance(items, int), items


def test_paged_gather_fallback_bitwise_vs_dense(rng):
    """The XLA fallback is the same arithmetic as attending over a
    contiguous cache holding the same tokens — BITWISE, not just close
    (the paged layout must introduce zero numerical drift off-TPU)."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        _paged_gather_attention

    B, S, H, Dh, ps = 4, 32, 2, 8, 8
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, Dh)), jnp.float32)
    lens = jnp.asarray(rng.integers(1, S + 1, size=(B,)), jnp.int32)
    k_pages, v_pages, tables = _scatter_pool(rng, np.asarray(k),
                                             np.asarray(v), ps, 32)
    scale = 1.0 / np.sqrt(Dh)
    paged = _paged_gather_attention(q, k_pages, v_pages, lens, tables, scale)
    # identity layout: a contiguous pool whose table is [0, 1, 2, ...]
    ident_k = jnp.asarray(np.asarray(k).transpose(1, 0, 2, 3).reshape(
        H, B * S // ps, ps, Dh))
    ident_v = jnp.asarray(np.asarray(v).transpose(1, 0, 2, 3).reshape(
        H, B * S // ps, ps, Dh))
    ident_t = jnp.arange(B * (S // ps), dtype=jnp.int32).reshape(B, S // ps)
    dense = _paged_gather_attention(q, ident_k, ident_v, lens, ident_t, scale)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))


def _quantize_pool(pool, qmax):
    """Per-(head, page) symmetric quantization of a [H, P, ps, Dh] pool."""
    amax = np.abs(pool).max(axis=(2, 3))
    scales = np.where(amax > 0, amax / qmax, 1.0).astype(np.float32)
    q = np.clip(np.round(pool / scales[:, :, None, None]),
                -qmax - 1, qmax).astype(np.int8)
    return q, scales


def _pack4(q):
    xi = q.astype(np.int32)
    Dh = q.shape[-1]
    return ((xi[..., :Dh // 2] & 0xF) | (xi[..., Dh // 2:] << 4)).astype(
        np.int8)


@pytest.mark.parametrize("batch", [1, 8, 16])
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_quantized_paged_decode_matches_dequant_dense(rng, batch, bits, impl):
    """The quantized paged kernel (dequant fused into the online-softmax
    body, scales on scalar prefetch) must equal the dequantize-then-dense
    reference to fp tolerance, at mixed per-row lengths, for int8 and
    nibble-packed int4, across a batch sweep (the b16 BlockSpec regression
    class must not come back with the extra prefetch operands)."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention

    S, H, Dh, ps = 64, 4, 16, 16
    q = jnp.asarray(rng.normal(size=(batch, 1, H, Dh)), jnp.float32)
    k = rng.normal(size=(batch, H, S, Dh)).astype(np.float32)
    v = rng.normal(size=(batch, H, S, Dh)).astype(np.float32)
    lens = jnp.asarray(rng.integers(1, S + 1, size=(batch,)), jnp.int32)
    k_pages, v_pages, tables = _scatter_pool(rng, k, v, ps,
                                             batch * (S // ps) + 1)
    qmax = 127.0 if bits == 8 else 7.0
    kq, ks = _quantize_pool(np.asarray(k_pages), qmax)
    vq, vs = _quantize_pool(np.asarray(v_pages), qmax)
    # dequantize-then-dense reference over the SAME payload
    kd = (kq.astype(np.float32) * ks[:, :, None, None])
    vd = (vq.astype(np.float32) * vs[:, :, None, None])
    ref = paged_decode_attention(q, jnp.asarray(kd), jnp.asarray(vd), lens,
                                 tables, impl="gather")
    if bits == 4:
        kq, vq = _pack4(kq), _pack4(vq)
    out = paged_decode_attention(q, jnp.asarray(kq), jnp.asarray(vq), lens,
                                 tables, impl=impl,
                                 k_scales=jnp.asarray(ks),
                                 v_scales=jnp.asarray(vs))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("head_dim", [16, 64])
@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_paged_kernel_skips_dead_slots(rng, bits, head_dim):
    """``paged_decode_q`` over tables four times wider than a request needs,
    an idle row among them: the kernel against the fallback on the same int8
    or nibble-packed payload, whose scales it finds by the work list's page."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention

    lens = [5, 0, 33, 64, 1]
    (q, k_pages, v_pages, lengths, tables), _ = _paged_case(
        rng, lens, 4, head_dim, 16, 16)
    qmax = 127.0 if bits == 8 else 7.0
    kq, ks = _quantize_pool(np.asarray(k_pages), qmax)
    vq, vs = _quantize_pool(np.asarray(v_pages), qmax)
    if bits == 4:
        kq, vq = _pack4(kq), _pack4(vq)
    out, ref = (np.asarray(paged_decode_attention(
        q, jnp.asarray(kq), jnp.asarray(vq), lengths, tables, impl=impl,
        k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs)))
        for impl in ("kernel", "gather"))
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(out[live], ref[live], atol=3e-5, rtol=1e-4)
    assert not out[~live].any()


def test_quantized_gather_fallback_bitwise_vs_dequant(rng):
    """Off-TPU the quantized fallback consumes the int payload with the
    exact arithmetic of dequantize-then-dense — BITWISE, so the XLA path
    introduces zero drift beyond the quantization itself."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        _paged_gather_attention, unpack_kv_int4)

    B, S, H, Dh, ps = 4, 32, 2, 8, 8
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    k = rng.normal(size=(B, H, S, Dh)).astype(np.float32)
    v = rng.normal(size=(B, H, S, Dh)).astype(np.float32)
    lens = jnp.asarray(rng.integers(1, S + 1, size=(B,)), jnp.int32)
    k_pages, v_pages, tables = _scatter_pool(rng, k, v, ps, 32)
    kq, ks = _quantize_pool(np.asarray(k_pages), 7.0)
    vq, vs = _quantize_pool(np.asarray(v_pages), 7.0)
    scale = 1.0 / np.sqrt(Dh)
    out = _paged_gather_attention(q, jnp.asarray(_pack4(kq)),
                                  jnp.asarray(_pack4(vq)), lens, tables,
                                  scale, jnp.asarray(ks), jnp.asarray(vs))
    # reference: unpack + dequantize by hand, then the dense fallback
    kd = np.asarray(unpack_kv_int4(jnp.asarray(_pack4(kq))))
    vd = np.asarray(unpack_kv_int4(jnp.asarray(_pack4(vq))))
    assert np.array_equal(kd, kq.astype(np.float32))  # pack roundtrip exact
    ref = _paged_gather_attention(
        q, jnp.asarray(kd * ks[:, :, None, None]),
        jnp.asarray(vd * vs[:, :, None, None]), lens, tables, scale)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_quantized_paged_rejects_mismatched_payload(rng):
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention

    q = jnp.zeros((1, 1, 2, 8), jnp.float32)
    bad = jnp.zeros((2, 4, 8, 5), jnp.int8)  # neither Dh nor Dh//2
    scales = jnp.ones((2, 4), jnp.float32)
    with pytest.raises(ValueError, match="matches neither"):
        paged_decode_attention(q, bad, bad, jnp.ones(1, jnp.int32),
                               jnp.zeros((1, 1), jnp.int32),
                               k_scales=scales, v_scales=scales)
    with pytest.raises(ValueError, match="both"):
        paged_decode_attention(q, bad, bad, jnp.ones(1, jnp.int32),
                               jnp.zeros((1, 1), jnp.int32),
                               k_scales=scales)


def _layer_stack(rng, layers, pool):
    """``layers`` differing pools behind one table: (q, k [L, H, P, ps, Dp],
    v, lens, tables, scales) with ``pool`` one of "dense", "int8", "int4"
    (scales (None, None) for a dense stack, else [L, H, P] each)."""
    lens = [5, 16, 0, 27]
    (q, k0, v0, _, tables), _ = _paged_case(rng, lens, 4, 16, 8, 4)
    # one table for every layer; what the pages hold differs layer by layer
    k = np.stack([np.asarray(k0)] + [
        rng.normal(size=k0.shape).astype(np.float32)
        for _ in range(layers - 1)])
    v = np.stack([np.asarray(v0)] + [
        rng.normal(size=v0.shape).astype(np.float32)
        for _ in range(layers - 1)])
    scales = (None, None)
    if pool != "dense":
        qmax = 127.0 if pool == "int8" else 7.0
        kq, ks = zip(*(_quantize_pool(x, qmax) for x in k))
        vq, vs = zip(*(_quantize_pool(x, qmax) for x in v))
        k, v = np.stack(kq), np.stack(vq)
        if pool == "int4":
            k, v = _pack4(k), _pack4(v)
        scales = (jnp.asarray(np.stack(ks)), jnp.asarray(np.stack(vs)))
    return (q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(lens, jnp.int32),
            tables, scales)


@pytest.mark.parametrize("pool", ["dense", "int8", "int4"])
@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_paged_layer_of_a_stack_equals_that_layers_pool(rng, impl, pool):
    """The 5-D call form: layer ``l`` of a stack [L, H, P, ps, Dp], read where
    it lies, is bitwise the 4-D call on ``stack[l]``, for every layer of a
    stack whose layers differ and with the index traced (one program serves
    every layer, as a layer loop needs)."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention

    q, k, v, lens, tables, (ks, vs) = _layer_stack(rng, 3, pool)
    of_stack = jax.jit(lambda layer: paged_decode_attention(
        q, k, v, lens, tables, impl=impl, k_scales=ks, v_scales=vs,
        layer=layer))
    outs = []
    for layer in range(3):
        one = paged_decode_attention(
            q, k[layer], v[layer], lens, tables, impl=impl,
            k_scales=None if ks is None else ks[layer],
            v_scales=None if vs is None else vs[layer])
        outs.append(np.asarray(one))
        np.testing.assert_array_equal(
            np.asarray(of_stack(jnp.int32(layer))), outs[-1])
    assert not np.array_equal(outs[0], outs[1])  # the layers do differ
    assert not np.array_equal(outs[1], outs[2])


def test_paged_pool_rank_and_layer_index_go_together(rng):
    """A 4-D pool is one layer's and takes no index; a 5-D one needs it."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention

    q, k, v, lens, tables, _ = _layer_stack(rng, 2, "dense")
    for impl in ("kernel", "gather"):
        with pytest.raises(ValueError, match="needs one"):
            paged_decode_attention(q, k, v, lens, tables, impl=impl)
        with pytest.raises(ValueError, match="takes no layer"):
            paged_decode_attention(q, k[0], v[0], lens, tables, impl=impl,
                                   layer=0)
        with pytest.raises(ValueError, match="takes no layer"):
            paged_decode_attention(q, k[0, 0], v[0, 0], lens, tables,
                                   impl=impl)


def test_decode_length_is_traced(rng):
    """One compiled kernel must serve every decode step (length as data)."""
    B, S, H, Dh = 1, 16, 2, 8
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, Dh)), jnp.float32)

    f = jax.jit(lambda q, k, v, n: decode_attention(q, k, v, n, block_k=8))
    for n in (1, 5, 12):
        out = f(q, k, v, jnp.int32(n))
        ref = _dense_decode(q, k, v, n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


@pytest.mark.slow
def test_decode_kernel_path_matches_dense_logits(rng):
    """The cached forward with the kernel (use_flash=True) matches the dense
    cached path to float tolerance — per-step logits, not argmax chains (two
    softmax implementations may differ by ulps)."""
    import dataclasses

    from deepspeed_tpu.models import gpt as G
    from deepspeed_tpu.models.gpt import GPTConfig, init_params

    cfg = GPTConfig(vocab_size=64, d_model=32, n_layer=2, n_head=4,
                    max_seq_len=32, use_flash=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ids = rng.integers(0, 64, size=(2, 8)).astype(np.int32)

    def run(cfg_):
        cache = G.init_cache(cfg_, 2, 32, jnp.float32)
        _, cache = G.forward_with_cache(cfg_, params, jnp.asarray(ids), cache)
        # three decode steps
        outs = []
        for t in range(3):
            tok = jnp.full((2, 1), t + 1, jnp.int32)
            logits, cache = G.forward_with_cache(cfg_, params, tok, cache)
            outs.append(np.asarray(logits))
        return np.concatenate(outs, axis=1)

    out_kernel = run(cfg)
    out_dense = run(dataclasses.replace(cfg, use_flash=False))
    np.testing.assert_allclose(out_kernel, out_dense, atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("kv_bits", [None, 8])
def test_work_list_follows_lengths_through_a_decode_block(rng, kv_bits):
    """A decode block of 4: the lengths grow inside one program, rows cross
    a page boundary at different steps and an idle row stays idle, so the
    list of live pages is rebuilt at every step, on the device. The kernel
    against the gather fallback, step by step, through
    ``paged_decode_step`` as the engine's ``jit_decode_block_4`` scans it."""
    from deepspeed_tpu.models import gpt as G

    cfg = G.GPTConfig(vocab_size=64, d_model=32, n_layer=2, n_head=4,
                      max_seq_len=32)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    ps, table, steps = 4, 8, 4
    lengths = np.array([3, 0, 7, 1, 12], np.int32)
    B = len(lengths)
    tables = np.zeros((B, table), np.int32)
    free = list(range(1, 24))
    rng.shuffle(free)
    for b in range(B):
        if lengths[b]:
            for i in range(-(-(int(lengths[b]) + steps) // ps)):
                tables[b, i] = free.pop()
    cache = G.init_paged_cache(cfg, 24, ps, jnp.float32, kv_bits=kv_bits)
    # what the requests already hold: any values will do, the same both ways
    cache = {k: (jnp.asarray(rng.normal(size=v.shape), v.dtype)
                 if jnp.issubdtype(v.dtype, jnp.floating) and v.ndim == 5
                 else v) for k, v in cache.items()}
    toks = jnp.asarray(rng.integers(0, 64, size=B), jnp.int32)

    def block(impl):
        def body(carry, _):
            toks, lens, cache = carry
            logits, cache = G.paged_decode_step(
                cfg, params, toks, cache, jnp.asarray(tables), lens,
                impl=impl)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, jnp.where(lens > 0, lens + 1, 0), cache), logits

        (_, lens, cache_out), logits = jax.jit(lambda: jax.lax.scan(
            body, (toks, jnp.asarray(lengths), cache), None,
            length=steps))()
        return np.asarray(logits), np.asarray(lens), cache_out

    got, lens, got_cache = block("kernel")
    want, _, want_cache = block("gather")
    np.testing.assert_array_equal(lens, np.where(lengths > 0,
                                                 lengths + steps, 0))
    live = lengths > 0
    np.testing.assert_allclose(got[:, live], want[:, live],
                               atol=2e-4, rtol=2e-3)
    # the same tokens went into the same pages (page 0 is the sink; an int8
    # payload may round one step apart)
    for key in got_cache:
        a, b = (np.asarray(c[key], np.float32)[:, :, 1:]
                for c in (got_cache, want_cache))
        np.testing.assert_allclose(a, b, atol=1.01 if kv_bits else 2e-4,
                                   rtol=2e-3)
