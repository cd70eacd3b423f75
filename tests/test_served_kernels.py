"""The kernels and routers the served families bring, each against its plain
form, none of them through a model: ``paged_decode_gqa`` against a dense
masked attention, ``paged_decode_mla`` against its gather fallback and plain
softmax attention, ``ssm_decode`` and the chunked scan against the recurrence
written out, a float32 activation over bf16 operands in two passes, the
routers and the ungated expert by hand and against the references' own. They
need no family's engine, so they are a file of their own: a unit ``--dist
loadfile`` deals apart from the family files, which are the run's longest.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import nemotron_h
from benchmark.reference import deepseek_v2_ref, laguna_ref
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.models import ssm
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import decode_attention as DA
from deepspeed_tpu.ops.pallas import ssm_decode as SD
from served_contract import config_file

LAGUNA = config_file("tiny-laguna-serve")["model"]
DEEPSEEK = config_file("tiny-deepseek-v2-serve")["model"]
NEMOTRON = nemotron_h.config(config_file("tiny-nemotron-h-serve")["model"])


# ------------------------------------------------- key-value heads (Laguna)
def _dense_attention(q, k, v, length, window):
    """Masked softmax attention of one token's heads ``q`` [H, Dh] at
    position ``length - 1`` over ``k``, ``v`` [G, S, Dh] in numpy."""
    H, Dh = q.shape
    G_ = k.shape[0]
    out = np.zeros((H, Dh))
    t = length - 1
    lo = max(0, t - window + 1) if window else 0
    for i in range(H):
        g = i // (H // G_)
        s = (k[g, lo:t + 1] @ q[i]) / np.sqrt(Dh)
        p = np.exp(s - s.max())
        out[i] = (p / p.sum()) @ v[g, lo:t + 1]
    return out


# case -> (query heads, key-value heads, window, table width in pages,
# lengths, the pages a grid step takes there). Pages of 8 rows: a table of 6
# keeps a step a page; one of 18 takes four, no multiple of them, so that a
# request ends inside a group (41: six pages), is shorter than one (1, 5),
# fills whole groups (64) or the table (144); a ring of 8 pages is one step
GQA_CASES = {
    "a group of 6 over pages": (48, 8, 0, 6, [0, 5, 16, 23, 41], 1),
    "a group of 8 over pages": (64, 8, 0, 6, [0, 5, 16, 23, 41], 1),
    "a group of 8 over rings": (64, 8, 16, 2, [0, 5, 16, 23, 41], 2),
    "a group of 6 over rings wider than the window": (
        48, 8, 12, 2, [0, 5, 16, 23, 41], 2),
    "four pages a step over a table of 18": (
        48, 8, 0, 18, [0, 1, 5, 41, 64, 97, 144], 4),
    "two pages a step, 4 key-value heads for 20": (
        20, 4, 0, 8, [0, 1, 9, 16, 17, 40, 64], 2),
    "four pages a step, 2 key-value heads for 32": (
        32, 2, 0, 16, [0, 1, 30, 33, 64, 100, 128], 4),
    "a ring of 8 pages a step": (64, 8, 64, 8, [0, 1, 5, 64, 70, 200], 8),
    "a ring of 8 pages wider than the window": (
        48, 8, 60, 8, [0, 1, 59, 60, 61, 64, 65, 131], 8),
}


@pytest.mark.parametrize("case", sorted(GQA_CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "two passes"])
def test_the_gqa_kernel_equals_a_dense_masked_attention(case, dtype):
    """``paged_decode_gqa`` in interpret mode and its gather fallback against
    a dense masked attention in numpy: 2, 4 and 8 key-value heads, lengths
    that are 0 and 1, inside a page, a whole number of pages, inside and at
    the end of a group of pages, past the window and past the ring; over
    scattered pages, and over rings read as the slots' pages. ``two
    passes``: a float32 query over bf16 rows."""
    H, G_, window, width, lengths, group = GQA_CASES[case]
    Dh, ps, B = 32, 8, len(lengths)
    rng = np.random.default_rng(len(case))
    pool_dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    q_dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    assert DA.gqa_pages_per_step(G_, ps, Dh, pool_dt, width,
                                 bool(window)) == group
    lengths = np.asarray(lengths, np.int32)
    S = max(width * ps, int(lengths.max()))
    k = rng.normal(size=(B, G_, S, Dh)).astype(np.float32)
    v = rng.normal(size=(B, G_, S, Dh)).astype(np.float32)
    k, v = (np.asarray(jnp.asarray(a, pool_dt).astype(jnp.float32))
            for a in (k, v))
    q = np.asarray(jnp.asarray(rng.normal(size=(B, 1, H, Dh)), q_dt)
                   .astype(jnp.float32))
    if window:
        R = width * ps
        assert R == -(-window // ps) * ps
        pool_k, pool_v = (np.zeros((2, G_, B, R, Dh), np.float32)
                          for _ in range(2))
        for b, n in enumerate(lengths):
            for t in range(n):      # position t at ring row t mod R
                pool_k[1, :, b, t % R], pool_v[1, :, b, t % R] = \
                    k[b, :, t], v[b, :, t]
        pool_k, pool_v = (a.reshape(2, G_, B * R // ps, ps, Dh)
                          for a in (pool_k, pool_v))
        tables = (np.arange(B)[:, None] * (R // ps)
                  + np.arange(R // ps)[None, :]).astype(np.int32)
        ring = (R, window)
    else:
        order = rng.permutation(B * width) + 1      # page 0 is the sink
        tables = order.reshape(B, width).astype(np.int32)
        pool_k, pool_v = (np.zeros((2, G_, B * width + 1, ps, Dh),
                                   np.float32) for _ in range(2))
        for b in range(B):
            for j in range(width):
                pool_k[1, :, tables[b, j]] = k[b, :, j * ps:(j + 1) * ps]
                pool_v[1, :, tables[b, j]] = v[b, :, j * ps:(j + 1) * ps]
        ring = None
    want = np.stack([_dense_attention(q[b, 0], k[b], v[b], int(n), window)
                     if n else np.zeros((H, Dh))
                     for b, n in enumerate(lengths)])
    tol = 2e-5 if dtype == "float32" else 2e-2 if dtype == "bfloat16" \
        else 8e-3   # probabilities rounded to bf16, once or in two halves
    for impl in ("kernel", "gather"):
        got = DA.paged_decode_gqa(
            jnp.asarray(q, q_dt), jnp.asarray(pool_k, pool_dt),
            jnp.asarray(pool_v, pool_dt), jnp.asarray(lengths),
            jnp.asarray(tables), impl=impl, layer=jnp.int32(1), ring=ring)
        assert got.dtype == q_dt
        err = np.abs(np.asarray(got.astype(jnp.float32))[:, 0] - want).max()
        assert err < tol, (impl, err)


def test_a_step_of_many_pages_is_the_step_a_page_bit_for_bit(monkeypatch):
    """The group's pages are taken one after another, one past the request's
    end skipped: whatever ``gqa_pages_per_step`` answers, the output is the
    walk's a page a step, to the bit. And a work list of another group than
    the call's is refused."""
    rng = np.random.default_rng(5)
    lengths = jnp.asarray([0, 1, 30, 41, 64, 88], jnp.int32)
    tables = jnp.asarray((rng.permutation(66) + 1).reshape(6, 11), jnp.int32)
    k, v = (jnp.asarray(rng.normal(size=(2, 4, 67, 8, 32)), jnp.bfloat16)
            for _ in range(2))
    q = jnp.asarray(rng.normal(size=(6, 1, 8, 32)), jnp.float32)
    got = {}
    for g in (1, 2, 4, 8):
        monkeypatch.setattr(DA, "gqa_pages_per_step", lambda *a, g=g: g)
        got[g] = np.asarray(DA.paged_decode_gqa(
            q, k, v, lengths, tables, impl="kernel", layer=jnp.int32(1)))
    assert all((got[g] == got[1]).all() for g in got)
    with pytest.raises(ValueError, match="8 pages, the work list"):
        DA.paged_decode_gqa(q, k, v, lengths, tables, impl="kernel",
                            layer=jnp.int32(1),
                            work=DA.paged_work_list(lengths, tables, 8, 2))


@pytest.mark.parametrize("name", ["tiny-laguna-serve", "tiny-nemotron-h-serve",
                                  "tiny-falcon-h1-serve",
                                  "tiny-deepseek-v2-serve",
                                  "tiny-dots3-note-serve"])
def test_a_decode_block_across_a_groups_edge_is_the_gather_paths(name):
    """Four decode steps of each family with key-value heads, and of each
    with latent layers (pages as they lie; pages under a selection and
    rings), through ``paged_decode_step``, the kernel (interpret mode)
    against the gather path over the same cache: tables of 8 pages of 16
    take two pages a grid step, and the slots' lengths cross a page's, a
    group's and no edge, one slot idle."""
    from benchmark.lib import manifest

    config = config_file(name)
    family = manifest.family_of(config)
    cfg = family.config(config["model"])
    assert (G.gqa_pages_per_step(cfg, 16, 8, jnp.float32),
            G.mla_pages_per_step(cfg, 16, 8, jnp.float32)) == (
                (0, 2) if cfg.attn_kind == "mla" else (2, 0))
    params = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), family.init_params(cfg, key)))(
            jax.random.PRNGKey(0))
    slots, pages = 4, 8
    pool = G.init_paged_cache(cfg, slots * pages + 1, 16, jnp.float32,
                              ring_slots=slots)
    keys = jax.random.split(jax.random.PRNGKey(1), len(pool))
    pool = {name: a if a.dtype == jnp.int32     # a slot's last selection
            else 0.5 * jax.random.normal(key, a.shape, a.dtype)
            for key, (name, a) in zip(keys, pool.items())}
    tables = jnp.arange(1, slots * pages + 1, dtype=jnp.int32).reshape(
        slots, pages)
    tokens = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (4, slots)).astype(np.int32)
    start = np.asarray([30, 0, 62, 5], np.int32)
    logits = {}
    for impl in ("kernel", "gather"):
        step = jax.jit(lambda ids, pool, lens, impl=impl: G.paged_decode_step(
            cfg, params, ids, pool, tables, lens, impl=impl))
        held, out = pool, []
        for t in range(4):
            lens = np.where(start > 0, start + t, 0)
            got, held = step(jnp.asarray(tokens[t]), held, jnp.asarray(lens))
            out.append(np.asarray(got))
        logits[impl] = np.stack(out)[:, start > 0]
    assert np.isfinite(logits["kernel"]).all()
    assert np.abs(logits["kernel"] - logits["gather"]).max() < 2e-4


@pytest.mark.parametrize("logits", ["random", "ties"])
def test_the_router_renormalises_what_it_took_as_the_reference(logits):
    rng = np.random.default_rng(2)
    r = rng.normal(size=(12, 16)).astype(np.float32)
    if logits == "ties":
        r = np.round(r)         # many equal: ties go to the lower index
    chosen, gates = dropless.route(jnp.asarray(r), 4, scale=2.5,
                                   norm_topk=True)
    # the reference's router over h = r, W_r = 1: its logits are r
    want, own, _ = laguna_ref.route(dict(LAGUNA), jnp.asarray(r), jnp.eye(16),
                             jnp.zeros((12, 4), jnp.int32),
                             jnp.zeros(12, bool))
    assert [sorted(row) for row in np.asarray(chosen).tolist()] == \
        [sorted(row) for row in np.asarray(own).tolist()]
    dense = np.zeros((12, 16), np.float32)
    np.put_along_axis(dense, np.asarray(chosen), np.asarray(gates), axis=1)
    assert np.abs(dense - np.asarray(want)).max() < 1e-6
    assert np.allclose(np.asarray(gates).sum(axis=1), 2.5, atol=1e-5)
    plain = dropless.route(jnp.asarray(r), 4, scale=2.5)[1]
    assert (np.asarray(plain).sum(axis=1) < 2.5 - 1e-3).all()


# ------------------------------------------- latent attention (DeepSeek-V2)
LATENT_CASES = {
    "batch of equal lengths": [40, 40, 40],
    "mixed lengths and an empty slot": [1, 0, 17, 64, 33],
    "a full table": [96, 5],
}


@pytest.mark.parametrize("case", sorted(LATENT_CASES))
@pytest.mark.parametrize("stacked", [False, True])
def test_the_latent_kernel_equals_the_gather_fallback(case, stacked):
    lens = LATENT_CASES[case]
    rng = np.random.default_rng(4)
    b, heads, width, rank, ps, pps = len(lens), 4, 128, 64, 16, 6
    pool = jnp.asarray(rng.normal(size=(3, 1, 1 + b * pps, ps, width)),
                       jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(b * pps).reshape(b, pps),
                         jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, heads, width)), jnp.float32)
    args = ((pool, jnp.asarray(lens, jnp.int32), tables) if stacked
            else (pool[2], jnp.asarray(lens, jnp.int32), tables))
    kw = dict(rank=rank, softmax_scale=0.2,
              layer=jnp.int32(2) if stacked else None)
    got = DA.paged_decode_mla(q, *args, impl="kernel", **kw)
    want = DA.paged_decode_mla(q, *args, impl="gather", **kw)
    assert got.shape == (b, 1, heads, rank)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
    # and the fallback is softmax attention over the rows the table names
    for j, n in enumerate(lens):
        rows = np.asarray(pool[2, 0])[np.asarray(tables[j])].reshape(
            -1, width)[:n]
        if not n:
            assert not np.asarray(want[j]).any()
            continue
        s = np.asarray(q[j, 0]) @ rows.T * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        ref_out = (p / p.sum(-1, keepdims=True)) @ rows[:, :rank]
        assert np.abs(np.asarray(want[j, 0]) - ref_out).max() < 2e-5


RING_CASES = {
    # lengths with the new token, over a ring of 32 rows and a window of 21
    "under the window": [1, 5, 20],
    "past the window, under the ring": [22, 30, 32],
    "wrapped, an empty slot among them": [33, 0, 64, 100],
}


@pytest.mark.parametrize("case", sorted(RING_CASES))
@pytest.mark.parametrize("heads, width, rank", [(4, 128, 64),
                                               (64, 1152, 1024)])
def test_the_latent_kernel_reads_a_ring_inside_its_window(case, heads, width,
                                                          rank):
    """A slot's latent ring read as its pages (``ring`` = (R, W)): the rows
    whose position lies in the window, at the tiny shape and at the window
    kind's published one (64 heads over rank 1024, rows of 1152)."""
    lens = RING_CASES[case]
    rng = np.random.default_rng(7)
    b, ps, R, W = len(lens), 16, 32, 21
    ring = jnp.asarray(rng.normal(size=(2, 1, b, R, width)), jnp.float32)
    tables = (jnp.arange(b, dtype=jnp.int32)[:, None] * (R // ps)
              + jnp.arange(R // ps, dtype=jnp.int32))
    q = jnp.asarray(rng.normal(size=(b, 1, heads, width)) * 0.1, jnp.float32)
    args = (ring.reshape(2, 1, b * (R // ps), ps, width),
            jnp.asarray(lens, jnp.int32), tables)
    kw = dict(rank=rank, softmax_scale=0.2, layer=jnp.int32(1), ring=(R, W))
    got = DA.paged_decode_mla(q, *args, impl="kernel", **kw)
    want = DA.paged_decode_mla(q, *args, impl="gather", **kw)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    for j, n in enumerate(lens):
        if not n:
            assert not np.asarray(want[j]).any()
            continue
        seen = [p % R for p in range(max(0, n - W), n)]
        rows = np.asarray(ring[1, 0, j])[seen]
        s = np.asarray(q[j, 0]) @ rows.T * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        ref_out = (p / p.sum(-1, keepdims=True)) @ rows[:, :rank]
        assert np.abs(np.asarray(want[j, 0]) - ref_out).max() < 5e-5


SELECTED_CASES = {
    "a short request, length 0 and length 1": [5, 0, 1],
    "a selection inside long requests": [96, 70, 33],
}


@pytest.mark.parametrize("case", sorted(SELECTED_CASES))
def test_the_latent_kernel_admits_the_selected_rows_only(case):
    """``allowed``: a row outside the selection never enters the softmax, a
    tile with no selected row changes nothing, a request whose every live
    row is selected reads what the kernel reads without a selection."""
    lens = SELECTED_CASES[case]
    rng = np.random.default_rng(9)
    b, heads, width, rank, ps, pps = len(lens), 4, 128, 64, 16, 6
    pool = jnp.asarray(rng.normal(size=(1, 1 + b * pps, ps, width)),
                       jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(b * pps).reshape(b, pps),
                         jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, heads, width)), jnp.float32)
    allowed = rng.random((b, pps * ps)) < 0.2
    allowed[:, 0] = True
    allowed[0, 16:48] = False           # whole tiles without a selected row
    if max(lens) < 16:
        allowed[:] = True               # everything live is selected
    kw = dict(rank=rank, softmax_scale=0.2)
    n = jnp.asarray(lens, jnp.int32)
    got = DA.paged_decode_mla(q, pool, n, tables, impl="kernel",
                              allowed=jnp.asarray(allowed), **kw)
    want = DA.paged_decode_mla(q, pool, n, tables, impl="gather",
                               allowed=jnp.asarray(allowed), **kw)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
    if max(lens) < 16:
        plain = DA.paged_decode_mla(q, pool, n, tables, impl="kernel", **kw)
        assert np.array_equal(np.asarray(got), np.asarray(plain))
    for j, length in enumerate(lens):
        if not length:
            assert not np.asarray(got[j]).any()
            continue
        rows = np.asarray(pool[0])[np.asarray(tables[j])].reshape(-1, width)
        keep = np.flatnonzero(allowed[j, :length])
        s = np.asarray(q[j, 0]) @ rows[keep].T * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        ref_out = (p / p.sum(-1, keepdims=True)) @ rows[keep][:, :rank]
        assert np.abs(np.asarray(got[j, 0]) - ref_out).max() < 2e-5


# case -> (heads, row width, rank, table width in pages, lengths, ring, the
# share of the live rows a selection keeps (0: none), the pages a grid step
# takes there). Pages of 8 rows. A table of 18 takes four a step: a request
# ends inside a group (41: six pages), is shorter than one (1, 5), fills
# whole groups (64) or the table (144). A table of 11 takes two, which do
# not divide it: the last group names a slot past the table. A ring of 9
# pages is read in two steps of five, the tenth tile past the ring
MLA_CASES = {
    "four pages a step over a table of 18": (
        4, 128, 64, 18, [0, 1, 5, 41, 64, 97, 144], None, 0, 4),
    "two pages a step over a table of 11": (
        8, 128, 64, 11, [0, 1, 9, 16, 17, 40, 88], None, 0, 2),
    "a selection over a table of 11": (
        4, 128, 64, 11, [0, 1, 9, 16, 17, 40, 88], None, 0.3, 2),
    "a selection that masks whole groups": (
        4, 128, 64, 18, [0, 1, 33, 64, 97, 144], None, -1, 4),
    "a ring of 9 pages": (
        4, 128, 64, 9, [0, 1, 5, 64, 65, 72, 73, 200], (72, 65), 0, 5),
    "the window kind's rows over a ring of 9 pages": (
        64, 1152, 1024, 9, [0, 1, 70, 131], (72, 60), 0, 5),
}
MLA_TYPES = {"float32": (jnp.float32, jnp.float32, 2e-5),
             "bfloat16": (jnp.bfloat16, jnp.bfloat16, 2e-2),
             "two passes": (jnp.float32, jnp.bfloat16, 8e-3)}


def _mla_case(case, dtype):
    """The arguments of a ``paged_decode_mla`` call of ``MLA_CASES[case]``
    over layer 1 of a stack of two, and the pages a step takes."""
    H, C, rank, width, lengths, ring, select, group = MLA_CASES[case]
    q_dt, pool_dt, tol = MLA_TYPES[dtype]
    ps, B = 8, len(lengths)
    rng = np.random.default_rng(len(case))
    assert DA.mla_pages_per_step(ps, C, pool_dt, width,
                                 ring is not None) == group
    lens = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, C)), q_dt)
    if ring:    # slot b's ring is pages b width .. of the pool
        tables = np.arange(B * width).reshape(B, width)
        pool = rng.normal(size=(2, 1, B * width, ps, C))
    else:       # page 0 is the sink
        tables = (rng.permutation(B * width) + 1).reshape(B, width)
        pool = rng.normal(size=(2, 1, B * width + 1, ps, C))
    allowed = None
    if select:
        allowed = rng.random((B, width * ps)) < abs(select)
        allowed[:, 0] = True
        if select < 0:      # groups of 32 rows with no selected row, or few
            allowed[:, 32:64] = False
            allowed[-1, 96:] = False
        allowed = jnp.asarray(allowed)
    kw = dict(rank=rank, softmax_scale=C ** -0.5, layer=jnp.int32(1),
              ring=ring, allowed=allowed, out_dtype=jnp.float32)
    return (q, jnp.asarray(pool, pool_dt), lens,
            jnp.asarray(tables, jnp.int32)), kw, group, tol


@pytest.mark.parametrize("case", sorted(MLA_CASES))
@pytest.mark.parametrize("dtype", sorted(MLA_TYPES))
@pytest.mark.parametrize("link", ["the group", "two pages"])
def test_the_latent_kernel_walks_live_groups_as_the_gather_reads(
        case, dtype, link, monkeypatch):
    """``paged_decode_mla`` in interpret mode against its gather fallback,
    a grid step a group of a request's live pages: lengths 0 and 1, inside,
    short of and at a group's end, a table no group divides, a ring of 9
    pages, a selection that leaves whole groups without a row; float32 and
    bf16 pools, and a float32 query over bf16 rows in two passes. ``link``:
    the step's chain as one link over the group's rows (what 128 rows a link
    come to at pages of 8) and in links of two pages, as pages of 64 take
    it (a group of five pages stays one link)."""
    if link == "two pages":
        monkeypatch.setattr(DA, "_MLA_SUB_ROWS", 16)
    group = MLA_CASES[case][-1]
    assert DA._mla_sub_tile(group, 8) == (
        2 if link == "two pages" and group % 2 == 0 else group)
    args, kw, _, tol = _mla_case(case, dtype)
    got = np.asarray(DA.paged_decode_mla(*args, impl="kernel", **kw))
    want = np.asarray(DA.paged_decode_mla(*args, impl="gather", **kw))
    assert np.isfinite(got).all()
    assert not got[np.asarray(args[2]) == 0].any()
    assert np.abs(got - want).max() < tol


@pytest.mark.parametrize("case", sorted(MLA_CASES))
def test_the_live_groups_are_the_whole_tables_walk_bit_for_bit(case):
    """The work list alone changes no bit: the kernel over a list of every
    group of every table (the grid before the list: a request's dead groups
    visited and skipped) gives what it gives over the live groups, and what
    it gives where the caller hands it that list. A list of another group
    than the call's is refused."""
    args, kw, group, _ = _mla_case(case, "two passes")
    q, pool, lens, tables = args
    ring = kw["ring"]
    own = np.asarray(DA.paged_decode_mla(*args, impl="kernel", **kw))
    cap = lens if ring is None else jnp.minimum(lens, ring[0])
    live = DA.paged_work_list(cap, tables, 8, group)._replace(lens=lens)
    whole = DA.paged_work_list(
        jnp.full_like(lens, tables.shape[1] * 8), tables, 8,
        group)._replace(lens=lens)
    assert int(whole.n_items) == len(lens) * -(-tables.shape[1] // group)
    assert int(live.n_items) < int(whole.n_items)
    for work in (live, whole):
        got = np.asarray(DA.paged_decode_mla(*args, impl="kernel", work=work,
                                             **kw))
        assert np.array_equal(got, own)
    with pytest.raises(ValueError, match=f"{group} pages, the work list"):
        DA.paged_decode_mla(*args, impl="kernel", **kw,
                            work=DA.paged_work_list(lens, tables, 8,
                                                    group + 1))


CHUNK_CASES = {
    # heads, queries, keys, a head's key width, the shared key's, values', live
    "every tile live": (8, 64, 128, 24, 8, 12, 128),
    "live ends inside a tile": (4, 32, 64, 24, 8, 12, 40),
    "one live key": (2, 16, 48, 8, 8, 8, 1),
    "tiles of their own (8 x 512 x 1024)": (8, 512, 1024, 128, 64, 128, 700),
}


@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
@pytest.mark.parametrize("shared", [False, True])
def test_the_chunk_kernel_equals_the_plain_masked_attention(case, shared):
    """``masked_chunk_attention``: a chunk's queries over expanded keys under
    a mask a query, the rotated key one row for all heads (``shared``); a
    query whose selection admits nothing gives 0, a key at or past ``live``
    is never scored though the mask admits it."""
    from deepspeed_tpu.ops.pallas import chunk_attention as CA

    H, T, S, Dq, Ds, Dv, live = CHUNK_CASES[case]
    rng = np.random.default_rng(5)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q, k, v = draw(H, T, Dq), draw(H, S, Dq), draw(H, S, Dv)
    part = (draw(H, T, Ds), draw(S, Ds)) if shared else None
    allowed = rng.random((T, S)) < 0.3
    allowed[:, 0] = True
    allowed[3, :] = False
    got = CA.masked_chunk_attention(q, k, v, jnp.asarray(allowed), live, 0.2,
                                    shared=part, impl="kernel")
    want = CA.masked_chunk_attention(q, k, v, jnp.asarray(allowed), live, 0.2,
                                     shared=part, impl="plain")
    assert got.shape == (H, T, Dv)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    assert not np.asarray(got[:, 3]).any()
    # the plain form is softmax attention over the admitted live keys
    t = 5
    keep = np.flatnonzero(allowed[t, :live])
    s = np.asarray(q[0, t]) @ np.asarray(k[0])[keep].T
    if shared:
        s = s + np.asarray(part[0][0, t]) @ np.asarray(part[1])[keep].T
    p = np.exp(0.2 * s - (0.2 * s).max())
    ref_out = (p / p.sum()) @ np.asarray(v[0])[keep]
    assert np.abs(np.asarray(want[0, t]) - ref_out).max() < 2e-5


def test_a_float32_activation_meets_a_bf16_matrix_in_two_passes():
    """``stream_float32``: ``_wm`` and the latent kernel give a float32
    activation 16 bits of mantissa against bf16 weights or rows; one pass
    (the activation rounded to bf16) is 100 times further from the float32
    product."""
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(2, 1, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 96)), jnp.bfloat16)
    exact = np.asarray(h, np.float64) @ np.asarray(w.astype(jnp.float32),
                                                   np.float64)
    two = np.abs(np.asarray(G._wm(h, w)) - exact).max()
    one = np.abs(np.asarray(G._wm(h.astype(jnp.bfloat16), w), np.float64)
                 - exact).max()
    assert G._wm(h, w).dtype == jnp.float32 and two < 2e-3 and one > 20 * two
    hi, lo = G.split_bf16(h)
    assert hi.dtype == lo.dtype == jnp.bfloat16
    assert np.abs(np.asarray(hi, np.float32) + np.asarray(lo, np.float32)
                  - np.asarray(h)).max() < 2.0 ** -15 * 4
    # the kernel: a float32 query over bf16 rows, against the fallback at
    # the highest precision, and further from the query rounded to bf16
    pool = jnp.asarray(rng.normal(size=(2, 1, 13, 16, 128)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 128)), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(12).reshape(3, 4), jnp.int32)
    lens = jnp.asarray([5, 64, 33], jnp.int32)
    kw = dict(rank=64, softmax_scale=0.2, layer=jnp.int32(1))
    a = DA.paged_decode_mla(q, pool, lens, tables, impl="kernel", **kw)
    b = DA.paged_decode_mla(q, pool, lens, tables, impl="gather", **kw)
    c = DA.paged_decode_mla(q.astype(jnp.bfloat16), pool, lens, tables,
                            impl="kernel", **kw)
    assert a.dtype == b.dtype == jnp.float32 and c.dtype == jnp.bfloat16
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 5e-5
    assert np.abs(np.asarray(a) - np.asarray(c, np.float32)).max() > 2e-3


def test_a_latent_pool_of_another_shape_is_refused():
    q = jnp.zeros((2, 1, 4, 128))
    with pytest.raises(ValueError, match="latent pool"):
        DA.paged_decode_mla(q, jnp.zeros((2, 9, 16, 128)), jnp.ones(2),
                            jnp.zeros((2, 2), jnp.int32), rank=64,
                            softmax_scale=1.0)


def _tied_logits():
    """Router logits with ties: between two groups' best members, and inside
    a kept group across the k-th place."""
    r = np.full((3, 16), -4.0, np.float32)
    r[0, [0, 4, 8]] = 2.0            # three groups tie for two places
    r[0, [1, 5]] = 1.0               # then 0, 4 and a tie for the third
    r[1, [3, 2, 1, 0]] = 1.5         # one group holds four equal experts
    r[1, 12] = 3.0
    r[2] = 0.0                       # everything ties
    return r


@pytest.mark.parametrize("logits", ["random", "ties"])
def test_the_dropless_router_picks_the_references_sets(logits):
    r = (np.random.default_rng(7).normal(size=(200, 16)).astype(np.float32)
         if logits == "random" else _tied_logits())
    chosen, gates = dropless.route(jnp.asarray(r), 3, 4, 2, 16.0)
    p = jax.nn.softmax(jnp.asarray(r), axis=-1)
    member, top = deepseek_v2_ref.own_choice(DEEPSEEK, p)
    assert [sorted(row) for row in np.asarray(chosen).tolist()] == \
        [sorted(row) for row in np.asarray(top).tolist()]
    want = np.take_along_axis(np.asarray(p), np.asarray(chosen), 1) * 16.0
    assert np.abs(np.asarray(gates) - want).max() < 1e-6
    # inside two groups, and the handed own set shows no slack
    assert (np.asarray(member).reshape(-1, 4, 4).any(-1).sum(-1) <= 2).all()
    assert not np.asarray(deepseek_v2_ref.choice_slack(DEEPSEEK, jnp.asarray(r),
                                           member)).any()
    if logits == "ties":
        assert sorted(np.asarray(chosen[0]).tolist()) == [0, 1, 4]
        assert sorted(np.asarray(chosen[1]).tolist()) == [0, 1, 12]
        assert sorted(np.asarray(chosen[2]).tolist()) == [0, 1, 2]


# ------------------------------- a state a slot, ungated experts (Nemotron-H)
ROUTER_CASES = {
    "the bias moves the choice, not the gate": (
        [[2.0, 1.0, 0.9, 0.0, -1.0, -3.0]], [0, 0, 0.3, 0, 0, 0], [0, 2]),
    "a tie goes to the lower index": (
        [[0.5, 1.5, 1.5, 1.5, -2.0, 0.0]], [0.0] * 6, [1, 2]),
    "a negative bias drops the strongest": (
        [[3.0, 0.1, 0.0, -0.1, -0.2, -0.3]], [-2, 0, 0, 0, 0, 0], [1, 2]),
}


@pytest.mark.parametrize("case", sorted(ROUTER_CASES))
def test_the_sigmoid_router_by_hand(case):
    """Choice by score plus bias, gates the scores without it, divided by
    their sum, times the scale; against hand-written ``jax.numpy``."""
    logits, bias, want = ROUTER_CASES[case]
    logits = jnp.asarray(logits, jnp.float32)
    bias = jnp.asarray(bias, jnp.float32)
    chosen, gates = dropless.route(logits, 2, scale=2.5, norm_topk=True,
                                   score="sigmoid", bias=bias)
    assert sorted(np.asarray(chosen)[0]) == want
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits)[0]))
    taken = s[np.asarray(chosen)[0]]
    assert np.abs(np.asarray(gates)[0] - 2.5 * taken / taken.sum()
                  ).max() < 1e-6
    # without the rules: not renormalised, no scale, and the softmax path
    _, plain = dropless.route(logits, 2, score="sigmoid", bias=bias)
    assert np.abs(np.asarray(plain)[0] - taken).max() < 1e-6
    soft, _ = dropless.route(logits, 2)
    assert sorted(np.asarray(soft)[0]) == sorted(
        np.argsort(-np.asarray(logits)[0], kind="stable")[:2])
    with pytest.raises(ValueError, match="score"):
        dropless.route(logits, 2, score="tanh")


def test_the_ungated_expert_by_hand():
    """``down(relu(up(x))^2)``, two products an expert, through the grouped
    products, over a share of the experts and a stack with a layer index."""
    key = jax.random.split(jax.random.PRNGKey(2), 4)
    h = jax.random.normal(key[0], (7, 16))
    up = jax.random.normal(key[1], (2, 5, 16, 12)) * 0.3
    down = jax.random.normal(key[2], (2, 5, 12, 16)) * 0.3
    chosen = jnp.asarray(np.random.default_rng(0).integers(0, 8, (7, 2)),
                         jnp.int32)
    chosen = chosen.at[:, 1].set((chosen[:, 0] + 3) % 8)
    gates = jax.random.uniform(key[3], (7, 2))

    def act(a):
        return jnp.square(jax.nn.relu(a))

    got = dropless.held_experts_ffn(h, chosen, gates, None, up, down, (2, 5),
                                    act, layer=jnp.int32(1))
    want = np.zeros((7, 16))
    for n in range(7):
        for j in range(2):
            e = int(chosen[n, j]) - 2
            if 0 <= e < 5:
                mid = np.maximum(np.asarray(h[n] @ up[1, e]), 0.0) ** 2
                want[n] += float(gates[n, j]) * (mid @ np.asarray(down[1, e]))
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    # float32 rows over bf16 matrices go in two halves through the one
    # product: 16 bits of the rows' mantissa, where a rounding keeps 8
    up16, down16 = up.astype(jnp.bfloat16), down.astype(jnp.bfloat16)
    exact = dropless.held_experts_ffn(
        h, chosen, gates, None, up16.astype(jnp.float32),
        down16.astype(jnp.float32), (2, 5), act, layer=jnp.int32(1))
    halves = dropless.held_experts_ffn(
        h, chosen, gates, None, up16, down16, (2, 5), act,
        layer=jnp.int32(1), out=jnp.float32, split=G.split_bf16)
    rounded = dropless.held_experts_ffn(
        h.astype(jnp.bfloat16), chosen, gates, None, up16, down16, (2, 5),
        act, layer=jnp.int32(1), out=jnp.float32)
    scale = np.abs(np.asarray(exact)).max()
    assert halves.dtype == jnp.float32
    assert np.abs(np.asarray(halves - exact)).max() < 1e-4 * scale
    assert np.abs(np.asarray(rounded - exact)).max() > 1e-3 * scale
    # matrices laid out taller and wider than the model's, zeros there
    # (``moe_rows``, ``moe_width``): the same rows come back, 16 wide
    tall = dropless.held_experts_ffn(
        h, chosen, gates, None,
        jnp.pad(up, ((0, 0), (0, 0), (0, 4), (0, 3))),
        jnp.pad(down, ((0, 0), (0, 0), (0, 3), (0, 4))), (2, 5), act,
        layer=jnp.int32(1))
    assert tall.shape == got.shape
    assert np.abs(np.asarray(tall - got)).max() < 1e-6
    cfg = dataclasses.replace(NEMOTRON, activation="relu2")
    assert np.allclose(np.asarray(G._act(cfg, jnp.asarray([-1.0, 0.5, 2.0]))),
                       [0.0, 0.25, 4.0])


# -------------------------------------------------------------- the kernel
SSM_CASES = {"every slot live": [1, 1, 1, 1, 1],
                "idle slots between live ones": [0, 1, 0, 1, 1],
                "one live slot, the last": [0, 0, 0, 0, 1],
                "no live slot": [0, 0, 0, 0, 0]}
# (heads, head_dim, state, groups) -> what ``ssm_decode._plan`` answers
SSM_SHAPES = {
    "Nemotron's ratio: 8 heads a group, state twice the head":
        ((16, 64, 128, 2), SD.Walk(8, 512, 4)),
    "Falcon-H1's: 16 heads a group in 2 groups":
        ((32, 64, 128, 2), SD.Walk(16, 1024, 8)),
    "a state of two lane tiles, half a group a pass of 1 MB":
        ((16, 128, 256, 1), SD.Walk(8, 1024, 8)),
    "a group of one head": ((8, 128, 128, 8), SD.Walk(1, 128, 1)),
    "heads of 8 rows: a head at a time": ((4, 8, 128, 2), None),
    "rows that fill no tile of dt x: a head at a time":
        ((8, 64, 128, 2), None),
    "a head_dim that is no multiple of 8: a head at a time":
        ((4, 12, 128, 2), None),
}


# ``ssm_decode(impl="kernel")`` on layer 1, without and with the windows,
# jitted over every array so that a shape compiles once for its four cases
# of liveness
_SSM_KERNEL = {
    False: jax.jit(lambda s, x, a, b, c, live: SD.ssm_decode(
        s, jnp.int32(1), x, a, b, c, live, impl="kernel")),
    True: jax.jit(lambda s, x, a, b, c, live, w, row: SD.ssm_decode(
        s, jnp.int32(1), x, a, b, c, live, impl="kernel", windows=w,
        new_row=row))}


@pytest.mark.parametrize("case", sorted(SSM_CASES))
@pytest.mark.parametrize("windows", [False, True])
@pytest.mark.parametrize("shape", sorted(SSM_SHAPES))
def test_ssm_decode_equals_the_recurrence(shape, windows, case):
    """The Pallas kernel in interpret mode, by each walk ``_plan`` tells
    apart, against the recurrence written out in numpy and against
    ``ssm_decode_reference``: a live slot's state of the named layer decays
    and takes the outer product (head ``h`` with the ``B`` and ``C`` of group
    ``h // (H / G)``), its output is read off the new state, its window
    shifts in the same call; an idle slot's and every other layer's are bit
    for bit what they were."""
    (H, P, N, Gr), walk = SSM_SHAPES[shape]
    assert SD._plan(H, P, N, Gr) == walk
    active = np.asarray(SSM_CASES[case], bool)
    L, S, K1, C = 3, 5, 3, H * P + 2 * Gr * N
    k = jax.random.split(jax.random.PRNGKey(len(case) + H), 7)
    state = jax.random.normal(k[0], (L, S, H, P, N))
    dtx = jax.random.normal(k[1], (S, H, P))
    decay = jax.random.uniform(k[2], (S, H))
    b, c = (jax.random.normal(kk, (S, Gr, N)) for kk in k[3:5])
    more = ()
    if windows:
        more = (jax.random.normal(k[5], (L, S, K1, C)),
                jax.random.normal(k[6], (S, C)))
    got = _SSM_KERNEL[windows](state, dtx, decay, b, c, jnp.asarray(active),
                               *more)
    want = SD.ssm_decode(state, 1, dtx, decay, b, c, jnp.asarray(active),
                         impl="gather", **dict(zip(("windows", "new_row"),
                                                   more)))
    assert len(got) == len(want) == 2 + windows
    assert np.abs(np.asarray(got[0] - want[0])).max() < 1e-4
    assert np.abs(np.asarray(got[1] - want[1])).max() < 1e-5
    y, new, old = np.asarray(got[0]), np.asarray(got[1]), np.asarray(state)
    assert (new[[0, 2]] == old[[0, 2]]).all()
    assert (new[1, ~active] == old[1, ~active]).all()
    assert (y[~active] == 0).all()
    bh, ch = (np.repeat(np.asarray(a), H // Gr, axis=1) for a in (b, c))
    assert Gr == 1 or np.abs(bh[0, H // Gr] - bh[0, H // Gr - 1]).max() > 0.1
    step = (old[1] * np.asarray(decay)[:, :, None, None]
            + np.asarray(dtx)[..., None] * bh[:, :, None, :])
    assert np.abs(new[1, active] - step[active]).max(initial=0) < 1e-5
    assert np.abs(y[active] - np.einsum("shpn,shn->shp", step, ch)[active]
                  ).max(initial=0) < 1e-4
    if windows:
        w, was = np.asarray(got[2]), np.asarray(more[0])
        assert (w == np.asarray(want[2])).all()
        assert (w[[0, 2]] == was[[0, 2]]).all()
        assert (w[1, ~active] == was[1, ~active]).all()
        assert (w[1, active, -1] == np.asarray(more[1])[active]).all()
        assert (w[1, active, :-1] == was[1, active, 1:]).all()
    live, n = SD.live_slots(jnp.asarray(active))
    assert int(n[0]) == active.sum()
    assert list(np.asarray(live)[:active.sum()]) == list(
        np.flatnonzero(active))


def _kernel_eqns(jaxpr, name):
    """The equations of a jaxpr and of every jaxpr inside it whose primitive
    is ``name``."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == name:
                found.append(eqn)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("config", ["nemotron-3-nano-serve",
                                    "falcon-h1-34b-serve"])
def test_ssm_decode_plans_the_benchmark_shapes_and_rounds_nothing(config):
    """``_plan`` at the two state-space configurations' sizes: eight heads a
    pass, whole tiles, no fallback. And nothing in the kernel's jaxpr rounds
    a state or a read-out to bfloat16: its only products are the one-hot
    layouts of ``dt x`` (``[24, 128]`` pieces that bfloat16 holds exactly,
    times 1, 128 state rows each), and ``y`` is a float32 sum along the
    lanes of a pass's rows: a product over the state, which the MXU would
    take in one bfloat16 pass unless told, would have to ask for
    ``Precision.HIGHEST``."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", config + ".json")
    model = json.load(open(path))
    H, P, N, Gr = (next(model[k] for k in keys if k in model) for keys in (
        ("mamba_num_heads", "mamba_n_heads"),
        ("mamba_head_dim", "mamba_d_head"),
        ("ssm_state_size", "mamba_d_state"), ("n_groups", "mamba_n_groups")))
    walk = SD._plan(H, P, N, Gr)
    assert walk == {"nemotron-3-nano-serve": SD.Walk(8, 512, 4),
                    "falcon-h1-34b-serve": SD.Walk(8, 1024, 8)}[config]
    assert (H // Gr) % walk.heads == 0 and walk.rows == walk.heads * P
    assert walk.rows * N * 4 <= SD._PASS_BYTES
    spec = jax.ShapeDtypeStruct
    f32 = jnp.float32
    jaxpr = jax.make_jaxpr(lambda s, x, a, b, c: SD.ssm_decode(
        s, jnp.int32(0), x, a, b, c, jnp.ones((2,), bool), impl="kernel"))(
            spec((1, 2, H, P, N), f32), spec((2, H, P), f32),
            spec((2, H), f32), spec((2, Gr, N), f32), spec((2, Gr, N), f32))
    calls = _kernel_eqns(jaxpr, "pallas_call")
    assert [e.params["name"] for e in calls] == ["ssm_decode"]
    dots = _kernel_eqns(jaxpr, "dot_general")
    assert len(dots) == H * P // 128
    for eqn in dots:
        assert [v.aval.shape for v in eqn.invars] == [(24, 128), (24, 128)]
        assert eqn.params["dimension_numbers"] == (((0,), (0,)), ((), ()))
        assert eqn.outvars[0].aval.dtype == f32
        # a product that took the state would have walk.rows rows, and would
        # have to say HIGHEST
        assert eqn.params["precision"] is None
    sums = [e for e in _kernel_eqns(jaxpr, "reduce_sum")
            if e.invars[0].aval.shape == (walk.rows, 128)]
    assert len(sums) == H // walk.heads
    assert all(e.invars[0].aval.dtype == f32 for e in sums)
    x = jax.random.normal(jax.random.PRNGKey(48), (8, 128)) * jnp.exp(
        jax.random.normal(jax.random.PRNGKey(49), (8, 128)) * 8)
    pieces = SD._pieces(x)
    for piece in pieces:
        assert (piece.astype(jnp.bfloat16).astype(f32) == piece).all()
    assert ((pieces[0] + pieces[1]) + pieces[2] == x).all()


def test_the_chunked_scan_equals_the_recurrence_from_a_given_state():
    m = ssm.SsmMixer(heads=4, head_dim=8, state=16, groups=2, chunk=8)
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    T = 21
    x = jax.random.normal(k[0], (2, T, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, T, 4)))
    A = -jnp.exp(jax.random.normal(k[2], (4,)))
    b, c = (jax.random.normal(kk, (2, T, 2, 16)) for kk in k[3:5])
    s0 = jax.random.normal(k[5], (2, 4, 8, 16))
    y, s = ssm.scan_chunks(m, x, dt, A, b, c, s0)
    want_s, want_y = np.asarray(s0).astype(np.float64), []
    for t in range(T):
        bh = np.repeat(np.asarray(b[:, t]), 2, axis=1)
        ch = np.repeat(np.asarray(c[:, t]), 2, axis=1)
        want_s = (np.exp(np.asarray(dt[:, t]) * np.asarray(A))[..., None, None]
                  * want_s + (np.asarray(dt[:, t])[..., None]
                              * np.asarray(x[:, t]))[..., None]
                  * bh[:, :, None, :])
        want_y.append(np.einsum("bhpn,bhn->bhp", want_s, ch))
    assert np.abs(np.asarray(s) - want_s).max() < 1e-4
    assert np.abs(np.asarray(y) - np.stack(want_y, 1)).max() < 1e-4
