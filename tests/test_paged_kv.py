"""Paged KV cache: allocator free-list + copy-on-write refcount properties,
prefix-index hash chains, prompt-KV scatter semantics (dense and quantized),
and paged-vs-dense logits equivalence at mixed lengths."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving.paging import (PageAllocator,
                                                    PrefixIndex,
                                                    RESERVED_PAGE, pages_for,
                                                    prefix_chain_hashes)
from deepspeed_tpu.models import gpt as G

CFG = G.GPTConfig(vocab_size=64, d_model=32, n_layer=2, n_head=4,
                  max_seq_len=128)


@pytest.fixture(scope="module")
def params():
    return G.init_params(CFG, jax.random.PRNGKey(0))


# ---------------------------------------------------------------- allocator
def test_pages_for():
    assert pages_for(0, 8) == 0
    assert pages_for(1, 8) == 1
    assert pages_for(8, 8) == 1
    assert pages_for(9, 8) == 2


def test_allocator_never_double_allocates():
    """Property test: random alloc/free interleavings never hand out a page
    twice, never lose a page, and never touch the reserved sink."""
    rng = np.random.default_rng(0)
    alloc = PageAllocator(64)
    held = []  # lists of page ids we own
    for _ in range(2000):
        if held and rng.random() < 0.45:
            pages = held.pop(rng.integers(len(held)))
            alloc.free(pages)
        else:
            n = int(rng.integers(1, 6))
            pages = alloc.alloc(n)
            if pages is None:
                assert alloc.free_pages < n  # refusal only under pressure
                continue
            assert len(pages) == n
            held.append(pages)
        outstanding = [p for ps in held for p in ps]
        assert len(outstanding) == len(set(outstanding)), "double allocation"
        assert RESERVED_PAGE not in outstanding
        assert alloc.free_pages + len(outstanding) == 63  # conservation
    for ps in held:
        alloc.free(ps)
    assert alloc.free_pages == 63
    assert alloc.allocated_pages == 0


def test_allocator_free_is_checked():
    alloc = PageAllocator(8)
    pages = alloc.alloc(3)
    alloc.free(pages)
    with pytest.raises(ValueError, match="double-free"):
        alloc.free(pages)
    with pytest.raises(ValueError, match="reserved"):
        alloc.free([RESERVED_PAGE])
    with pytest.raises(ValueError):
        PageAllocator(1)  # nothing left after the sink


def test_allocator_all_or_nothing():
    alloc = PageAllocator(6)  # 5 usable
    assert alloc.alloc(7) is None
    assert alloc.free_pages == 5  # a failed alloc takes nothing
    got = alloc.alloc(5)
    assert got is not None and alloc.free_pages == 0


def test_allocator_audit_conservation():
    """audit() is clean through arbitrary alloc/free churn, and names the
    violated invariant when the ledger is corrupted."""
    rng = np.random.default_rng(3)
    alloc = PageAllocator(32)
    held = []
    for _ in range(300):
        if held and rng.random() < 0.5:
            alloc.free(held.pop(rng.integers(len(held))))
        else:
            pages = alloc.alloc(int(rng.integers(1, 4)))
            if pages is not None:
                held.append(pages)
        rep = alloc.audit()
        assert rep["ok"], rep
        assert rep["free"] + rep["allocated"] == rep["total"] == 31
    assert alloc.allocated_ids == frozenset(p for ps in held for p in ps)

    # corruptions the audit must name: a page leaked out of both sets,
    # a duplicate in the free list, and a page in both sets at once
    a = PageAllocator(8)
    del a._ref[a.alloc(2)[0]]
    rep = a.audit()
    assert not rep["ok"] and any("conservation" in e for e in rep["errors"])
    b = PageAllocator(8)
    b._free.append(b._free[0])
    assert any("duplicate" in e for e in b.audit()["errors"])
    c = PageAllocator(8)
    c._ref[c._free[0]] = 1
    assert any("both free and allocated" in e for e in c.audit()["errors"])


# ------------------------------------------------------------ copy-on-write
def test_cow_share_free_materialize_cycles():
    """Property test: random alloc/share/free/materialize interleavings
    conserve pages, a page only returns to the free list when its LAST
    reference dies, and materialize trades a shared reference for a fresh
    private page."""
    rng = np.random.default_rng(7)
    alloc = PageAllocator(48)
    held = []  # independent references: [pages]
    for _ in range(1500):
        r = rng.random()
        if held and r < 0.30:
            released = alloc.free(held.pop(rng.integers(len(held))))
            for p in released:
                assert alloc.refcount(p) == 0
        elif held and r < 0.55:  # share an existing reference
            ref = held[rng.integers(len(held))]
            alloc.share(ref)
            held.append(list(ref))
        elif held and r < 0.65:  # copy-on-write a random held page
            ref = held[rng.integers(len(held))]
            i = rng.integers(len(ref))
            before = alloc.refcount(ref[i])
            got = alloc.materialize(ref[i])
            if got is None:
                assert alloc.free_pages == 0  # refusal only when empty
            elif before == 1:
                assert got == ref[i]  # already private
            else:
                assert got != ref[i] and alloc.refcount(got) == 1
                assert alloc.refcount(ref[i]) == before - 1
                ref[i] = got
        else:
            pages = alloc.alloc(int(rng.integers(1, 4)))
            if pages is not None:
                held.append(pages)
        rep = alloc.audit()
        assert rep["ok"], rep
        # every held reference is backed by exactly that many refcounts
        from collections import Counter

        want = Counter(p for ref in held for p in ref)
        assert all(alloc.refcount(p) == n for p, n in want.items())
        assert set(want) == set(alloc.allocated_ids)
    for ref in held:
        alloc.free(ref)
    assert alloc.allocated_pages == 0 and alloc.free_pages == 47


def test_cow_double_free_on_shared_pages():
    """A shared page survives its first free (the other holder's reference
    is live) and only over-freeing past the refcount raises."""
    alloc = PageAllocator(8)
    pages = alloc.alloc(2)
    alloc.share(pages)  # refcount 2 on both
    assert alloc.free(pages) == []      # nothing released yet
    assert all(alloc.refcount(p) == 1 for p in pages)
    assert sorted(alloc.free(pages)) == sorted(pages)  # last refs die
    with pytest.raises(ValueError, match="double-free"):
        alloc.free(pages)
    with pytest.raises(ValueError, match="unallocated"):
        alloc.share(pages)
    with pytest.raises(ValueError, match="unallocated"):
        alloc.materialize(pages[0])
    with pytest.raises(ValueError, match="reserved"):
        alloc.share([RESERVED_PAGE])


def test_cow_audit_catches_leaked_refcount():
    """A refcount that leaks to < 1 while the page stays in the allocated
    set must be named by the audit (the bug class where a free path
    decrements without recycling)."""
    alloc = PageAllocator(8)
    p = alloc.alloc(1)[0]
    alloc._ref[p] = 0  # corrupt: allocated but zero references
    rep = alloc.audit()
    assert not rep["ok"]
    assert any("refcount" in e for e in rep["errors"]), rep["errors"]


# ------------------------------------------------------------- prefix index
def test_prefix_chain_hashes_commit_to_whole_prefix():
    ps = 4
    a = prefix_chain_hashes([1, 2, 3, 4, 5, 6, 7, 8], ps)
    b = prefix_chain_hashes([1, 2, 3, 4, 9, 9, 9, 9], ps)
    c = prefix_chain_hashes([0, 2, 3, 4, 5, 6, 7, 8], ps)
    assert len(a) == 2
    assert a[0] == b[0]          # same first block
    assert a[1] != b[1]          # diverging second block
    assert a[0] != c[0]          # block 0 differs -> whole chain differs
    assert a[1] != c[1]          # ... even where block 1's tokens match
    assert prefix_chain_hashes([1, 2, 3], ps) == []  # partial block: none


def test_prefix_index_register_lookup_forget():
    ps = 4
    idx = PrefixIndex(ps)
    prompt = np.arange(10, dtype=np.int32)  # 2 full blocks + partial
    assert idx.lookup(prompt) == []
    idx.register(prompt, [5, 9, 13])  # page 13 covers the partial block:
    assert len(idx) == 2              # never indexed
    assert idx.lookup(prompt) == [5, 9]
    # longest-prefix semantics: same first block, new second block
    other = np.concatenate([prompt[:4], np.full(6, 50, np.int32)])
    assert idx.lookup(other) == [5]
    # first writer wins; a second registration cannot steal the chain
    idx.register(prompt, [21, 22])
    assert idx.lookup(prompt) == [5, 9]
    # forget only invalidates the released page's entry
    idx.forget([9])
    assert idx.lookup(prompt) == [5]
    idx.forget([5])
    assert idx.lookup(prompt) == [] and len(idx) == 0


# ---------------------------------------------------------------- scatter
def test_write_prompt_kv_drops_padding_and_respects_tables(params):
    """Bucket padding past `length` must not touch the pool; valid tokens
    land exactly in the pages the table names."""
    ps, P = 8, 16
    paged = G.init_paged_cache(CFG, P, ps, jnp.float32)
    dense = G.init_cache(CFG, 1, 32, jnp.float32)
    ids = jnp.asarray(np.arange(32, dtype=np.int32)[None] % 64)
    _, dense = G.forward_with_cache(CFG, params, ids, dense)
    table = jnp.asarray(np.array([3, 9, 0, 0], np.int32))
    length = 11  # pages 3 (8 tokens) + 9 (3 tokens)
    out = G.write_prompt_kv(paged, dense, table, jnp.int32(length))
    k_pages = np.asarray(out["k_pages"])  # [L, H, P, ps, Dh]
    k_dense = np.asarray(dense["k"])      # [L, 1, H, S, Dh]
    np.testing.assert_array_equal(k_pages[:, :, 3], k_dense[:, 0, :, :8])
    np.testing.assert_array_equal(k_pages[:, :, 9, :3], k_dense[:, 0, :, 8:11])
    # everything else (including rest of page 9 and the whole pool) untouched
    assert (k_pages[:, :, 9, 3:] == 0).all()
    mask = np.ones(16, bool)
    mask[[3, 9]] = False
    assert (k_pages[:, :, mask] == 0).all()


# ------------------------------------------------------ paged == dense logits
@pytest.mark.parametrize("rotary", [False, True])
def test_paged_decode_logits_match_dense_cache(params, rotary, rng):
    """The paged decode step must reproduce the contiguous-cache decode
    logits at mixed sequence lengths — per row, to fp tolerance."""
    cfg = CFG if not rotary else G.GPTConfig(
        vocab_size=64, d_model=32, n_layer=2, n_head=4, max_seq_len=128,
        rotary=True, rotary_pct=0.5)
    p = params if not rotary else G.init_params(cfg, jax.random.PRNGKey(0))
    B, ps, MP, P = 3, 8, 4, 16
    prompt_lens = [5, 9, 3]
    paged = G.init_paged_cache(cfg, P, ps, jnp.float32)
    tables = np.zeros((B, MP), np.int32)
    free = list(range(1, P))
    lengths = np.zeros(B, np.int32)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
               for n in prompt_lens]
    for b in range(B):
        ids = np.zeros((1, 16), np.int32)
        ids[0, :prompt_lens[b]] = prompts[b]
        dense = G.init_cache(cfg, 1, 16, jnp.float32)
        _, dense = G.forward_with_cache(cfg, p, jnp.asarray(ids), dense)
        for i in range(pages_for(prompt_lens[b] + 4, ps)):
            tables[b, i] = free.pop()
        paged = G.write_prompt_kv(paged, dense, jnp.asarray(tables[b]),
                                  jnp.int32(prompt_lens[b]))
        lengths[b] = prompt_lens[b]

    toks = rng.integers(0, 64, (B, 3)).astype(np.int32)
    paged_logits = []
    for t in range(3):
        lg, paged = G.paged_decode_step(cfg, p, jnp.asarray(toks[:, t]),
                                        paged, jnp.asarray(tables),
                                        jnp.asarray(lengths), impl="gather")
        paged_logits.append(np.asarray(lg))
        lengths += 1

    for b in range(B):
        dense = G.init_cache(cfg, 1, 32, jnp.float32)
        _, dense = G.forward_with_cache(cfg, p, jnp.asarray(prompts[b][None]),
                                        dense)
        for t in range(3):
            lg, dense = G.forward_with_cache(
                cfg, p, jnp.asarray(toks[b:b + 1, t:t + 1]), dense)
            np.testing.assert_allclose(paged_logits[t][b],
                                       np.asarray(lg)[0, 0],
                                       atol=2e-4, rtol=2e-3)


def test_paged_decode_rejects_alibi():
    cfg = G.GPTConfig(vocab_size=32, d_model=16, n_layer=1, n_head=2,
                      alibi=True)
    p = G.init_params(cfg, jax.random.PRNGKey(0))
    paged = G.init_paged_cache(cfg, 4, 8, jnp.float32)
    with pytest.raises(ValueError, match="alibi"):
        G.paged_decode_step(cfg, p, jnp.zeros(2, jnp.int32), paged,
                            jnp.zeros((2, 2), jnp.int32),
                            jnp.zeros(2, jnp.int32))


def test_paged_decode_quantized_stack(params, rng):
    """The int8 weight stack (decode's weight-bandwidth lever) must flow
    through the paged step exactly like the contiguous one: quantized paged
    logits == quantized dense-cache logits."""
    qparams = G.quantize_for_inference(CFG, params, bits=8, group_size=128)
    assert G._is_qleaf(qparams["blocks"]["qkv_w"])  # the stack did quantize
    B, ps, P = 2, 8, 16
    prompts = [rng.integers(0, 64, (6,)).astype(np.int32) for _ in range(B)]
    paged = G.init_paged_cache(CFG, P, ps, jnp.float32)
    tables = np.zeros((B, 4), np.int32)
    free = list(range(1, P))
    for b in range(B):
        ids = np.zeros((1, 8), np.int32)
        ids[0, :6] = prompts[b]
        dense = G.init_cache(CFG, 1, 8, jnp.float32)
        _, dense = G.forward_with_cache(CFG, qparams, jnp.asarray(ids), dense)
        tables[b, 0] = free.pop()
        paged = G.write_prompt_kv(paged, dense, jnp.asarray(tables[b]),
                                  jnp.int32(6))
    lengths = np.full(B, 6, np.int32)
    tok = rng.integers(0, 64, (B,)).astype(np.int32)
    lg, _ = G.paged_decode_step(CFG, qparams, jnp.asarray(tok), paged,
                                jnp.asarray(tables), jnp.asarray(lengths),
                                impl="gather")
    for b in range(B):
        dense = G.init_cache(CFG, 1, 16, jnp.float32)
        _, dense = G.forward_with_cache(CFG, qparams,
                                        jnp.asarray(prompts[b][None]), dense)
        ref, _ = G.forward_with_cache(CFG, qparams,
                                      jnp.asarray(tok[b:b + 1][None]), dense)
        np.testing.assert_allclose(np.asarray(lg)[b], np.asarray(ref)[0, 0],
                                   atol=2e-4, rtol=2e-3)


# ------------------------------------- the pool through a decode step's loop
POOL_BITS = {"dense": None, "kv8": 8, "kv4": 4}


def _decode_fixture(params, rng, pool, weights):
    """(params, cache, tables, lengths): a pool of 16 pages of 8 filled from
    three prompts of mixed lengths (row 1 crosses into a fresh page on its
    second decode step) and one idle slot on the sink; the weight stack
    dense or int8."""
    p = params if weights == "dense" else G.quantize_for_inference(
        CFG, params, bits=8, group_size=128)
    B, ps, MP, P = 4, 8, 4, 16
    prompt_lens = np.array([5, 7, 12, 0], np.int32)
    ids = rng.integers(0, 64, (B, 16)).astype(np.int32)
    dense = G.init_cache(CFG, B, 16, jnp.float32)
    _, dense = G.forward_with_cache(CFG, p, jnp.asarray(ids), dense)
    tables = np.zeros((B, MP), np.int32)
    free = list(range(1, P))
    for b in range(B - 1):
        for i in range(pages_for(int(prompt_lens[b]) + 4, ps)):
            tables[b, i] = free.pop()
    cache = G.init_paged_cache(CFG, P, ps, jnp.float32,
                               kv_bits=POOL_BITS[pool])
    cache = G.write_prompt_kv_batch(cache, dense, jnp.asarray(tables),
                                    jnp.asarray(prompt_lens))
    return p, cache, jnp.asarray(tables), jnp.asarray(prompt_lens)


@pytest.mark.parametrize("weights", ["dense", "int8"])
@pytest.mark.parametrize("pool", sorted(POOL_BITS))
def test_decode_step_carries_the_pool_through_its_layer_loop(params, rng,
                                                             pool, weights):
    """Every array of the pool is a CARRY of the layer scan, and nothing of
    a pool's shape is scanned in or stacked out: a scan's stacked output is
    a second pool, which the compiled step then copies and slices."""
    p, cache, tables, lengths = _decode_fixture(params, rng, pool, weights)
    jaxpr = jax.make_jaxpr(lambda c: G.paged_decode_step(
        CFG, p, jnp.zeros(4, jnp.int32), c, tables, lengths,
        impl="gather"))(cache)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == CFG.n_layer]
    assert len(scans) == 1, [e.primitive.name for e in jaxpr.jaxpr.eqns]
    scan = scans[0]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    sig = lambda v: (tuple(v.aval.shape), str(v.aval.dtype))  # noqa: E731
    pools = sorted((tuple(a.shape), str(a.dtype)) for a in cache.values())
    assert len(pools) == (2 if pool == "dense" else 4)
    carried = [sig(v) for v in scan.invars[n_consts:n_consts + n_carry]]
    carried_out = [sig(v) for v in scan.outvars[:n_carry]]
    for one in set(pools):
        assert carried.count(one) == pools.count(one), (one, carried)
        assert carried_out.count(one) == pools.count(one), (one, carried_out)
    elsewhere = ([sig(v) for v in scan.invars[:n_consts]]
                 + [sig(v) for v in scan.invars[n_consts + n_carry:]]
                 + [sig(v) for v in scan.outvars[n_carry:]])
    shapes = {shape for shape, _ in pools}
    assert not [s for s in elsewhere if s[0] in shapes], elsewhere


def _step_by_layer_slices(cfg, p, ids, cache, tables, lengths, impl):
    """``paged_decode_step`` one layer slice at a time through the 4-D call
    forms (a layer's [H, P, ps, Dh] pool appended to, handed to
    ``paged_decode_attention`` and stacked back): what the step computed
    before the pool became a carry of its loop."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention

    B, H, Dh = ids.shape[0], cfg.n_head, cfg.head_dim
    quantized = "k_scales" in cache
    ps = cache["k_pages"].shape[3]
    x = G._embed(cfg, p, ids[:, None], lengths[:, None])
    qleaf = G._is_qleaf(p["blocks"]["qkv_w"])
    x = x.astype(p["lnf_scale"].dtype if qleaf
                 else p["blocks"]["qkv_w"].dtype)
    page = jnp.take_along_axis(tables, (lengths // ps)[:, None],
                               axis=1)[:, 0]
    off = lengths % ps
    out = {k: [] for k in cache}
    for layer in range(cfg.n_layer):
        w = jax.tree_util.tree_map(lambda a: a[layer], p["blocks"])
        h = G.layer_norm(x, w["ln1_scale"], w["ln1_bias"],
                         cfg.layer_norm_eps)
        q, k_, v = jnp.split(G._wm(h, w["qkv_w"]) + w["qkv_b"], 3, axis=-1)
        q, k_, v = (a.reshape(B, 1, H, Dh) for a in (q, k_, v))
        kp, vp = cache["k_pages"][layer], cache["v_pages"][layer]
        ks = vs = None
        if not quantized:
            kp = kp.at[:, page, off, :].set(
                k_[:, 0].astype(kp.dtype).transpose(1, 0, 2))
            vp = vp.at[:, page, off, :].set(
                v[:, 0].astype(vp.dtype).transpose(1, 0, 2))
        else:
            bits = G.paged_cache_bits(cache, Dh)
            kp, ks = G._append_kv_token(
                kp, cache["k_scales"][layer],
                k_[:, 0].transpose(1, 0, 2).astype(jnp.float32), page, off,
                bits)
            vp, vs = G._append_kv_token(
                vp, cache["v_scales"][layer],
                v[:, 0].transpose(1, 0, 2).astype(jnp.float32), page, off,
                bits)
        attn = paged_decode_attention(
            q.astype(x.dtype if quantized else kp.dtype), kp, vp,
            lengths + 1, tables, softmax_scale=1.0 / np.sqrt(Dh), impl=impl,
            k_scales=ks, v_scales=vs)
        attn = attn.reshape(B, 1, H * Dh).astype(x.dtype)
        y = x + G._wm(attn, w["attn_out_w"]) + w["attn_out_b"]
        x = y + G._mlp_delta(cfg, y, w)
        for key, val in (("k_pages", kp), ("v_pages", vp), ("k_scales", ks),
                         ("v_scales", vs)):
            if val is not None:
                out[key].append(val)
    logits = G._lm_logits(cfg, p, x)[:, 0, :]
    return logits, {k: jnp.stack(val) for k, val in out.items()}


@pytest.mark.parametrize("weights", ["dense", "int8"])
@pytest.mark.parametrize("pool", sorted(POOL_BITS))
def test_carried_pool_equals_layer_slices_over_a_decode_block(params, rng,
                                                              pool, weights):
    """Four steps (a ``decode_block``) from a filled pool: the carried loop's
    logits and pool are what the same steps give one layer slice at a time
    through the 4-D call forms. Payloads and the live rows' logits bitwise;
    scales to the last ULP, where one program may compile ``amax / qmax`` as
    a reciprocal multiply and the other not (``_append_kv_token``)."""
    p, cache, tables, lengths = _decode_fixture(params, rng, pool, weights)
    step = jax.jit(lambda c, t, ln: G.paged_decode_step(
        CFG, p, t, c, tables, ln, impl="gather"))
    sliced = jax.jit(lambda c, t, ln: _step_by_layer_slices(
        CFG, p, t, c, tables, ln, "gather"))
    ref_cache = cache
    toks = jnp.asarray(rng.integers(0, 64, (4, 4)).astype(np.int32))
    for t in range(4):
        logits, cache = step(cache, toks[t], lengths)
        ref_logits, ref_cache = sliced(ref_cache, toks[t], lengths)
        lengths = lengths + (lengths > 0)
        assert sorted(cache) == sorted(ref_cache)
        for key in cache:
            assert cache[key].shape == ref_cache[key].shape
            got, ref = np.asarray(cache[key]), np.asarray(ref_cache[key])
            if key.endswith("pages"):
                np.testing.assert_array_equal(got, ref, err_msg=key)
            else:
                np.testing.assert_allclose(got, ref, rtol=2e-7, atol=0,
                                           err_msg=key)
        np.testing.assert_array_equal(np.asarray(logits)[:3],
                                      np.asarray(ref_logits)[:3])
    assert int(lengths[1]) == 11  # row 1 opened a page on the way


# ------------------------------------------------ quantized KV pools (kv_bits)
def _dequant_cache(paged, bits):
    """Rebuild a DENSE paged cache from a quantized one's payload — the
    dequantize-then-dense reference the quantized step is judged against."""
    from deepspeed_tpu.ops.pallas.decode_attention import unpack_kv_int4

    def side(pages, scales):
        q = np.asarray(pages)
        if bits == 4:
            q = np.asarray(unpack_kv_int4(jnp.asarray(q)))
        return jnp.asarray(q.astype(np.float32)
                           * np.asarray(scales)[..., None, None])

    return {"k_pages": side(paged["k_pages"], paged["k_scales"]),
            "v_pages": side(paged["v_pages"], paged["v_scales"])}


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("rotary", [False, True])
@pytest.mark.slow
def test_quantized_paged_decode_matches_dequant_dense(params, rng, bits,
                                                      rotary):
    """The quantized paged step == paged decode over DEQUANTIZED pools, to
    fp tolerance, at mixed per-row lengths ± rotary — the only difference
    between quantized and dense serving is the quantization itself (the
    appended token additionally quantizes in the quantized step, so the
    comparison carries the per-page quantization tolerance)."""
    cfg = CFG if not rotary else G.GPTConfig(
        vocab_size=64, d_model=32, n_layer=2, n_head=4, max_seq_len=128,
        rotary=True, rotary_pct=0.5)
    p = params if not rotary else G.init_params(cfg, jax.random.PRNGKey(0))
    B, ps, MP, P = 3, 8, 4, 16
    prompt_lens = [5, 9, 3]
    paged = G.init_paged_cache(cfg, P, ps, jnp.float32, kv_bits=bits)
    assert paged["k_pages"].dtype == jnp.int8
    assert paged["k_pages"].shape[-1] == (4 if bits == 4 else 8)
    tables = np.zeros((B, MP), np.int32)
    free = list(range(1, P))
    lengths = np.zeros(B, np.int32)
    for b in range(B):
        prompt = rng.integers(0, 64, (prompt_lens[b],)).astype(np.int32)
        ids = np.zeros((1, 16), np.int32)
        ids[0, :prompt_lens[b]] = prompt
        dense = G.init_cache(cfg, 1, 16, jnp.float32)
        _, dense = G.forward_with_cache(cfg, p, jnp.asarray(ids), dense)
        for i in range(pages_for(prompt_lens[b] + 4, ps)):
            tables[b, i] = free.pop()
        paged = G.write_prompt_kv(paged, dense, jnp.asarray(tables[b]),
                                  jnp.int32(prompt_lens[b]))
        lengths[b] = prompt_lens[b]
    toks = rng.integers(0, 64, (B, 3)).astype(np.int32)
    tol = dict(atol=2e-2, rtol=2e-2) if bits == 4 else dict(atol=2e-3,
                                                            rtol=2e-3)
    for t in range(3):
        ref, _ = G.paged_decode_step(cfg, p, jnp.asarray(toks[:, t]),
                                     _dequant_cache(paged, bits),
                                     jnp.asarray(tables),
                                     jnp.asarray(lengths), impl="gather")
        lg, paged = G.paged_decode_step(cfg, p, jnp.asarray(toks[:, t]),
                                        paged, jnp.asarray(tables),
                                        jnp.asarray(lengths), impl="gather")
        np.testing.assert_allclose(np.asarray(lg), np.asarray(ref), **tol)
        # greedy choices must agree — the bar serving equivalence rides on
        np.testing.assert_array_equal(np.argmax(np.asarray(lg), -1),
                                      np.argmax(np.asarray(ref), -1))
        lengths += 1


def test_quantized_scatter_handles_scratch_longer_than_table(params, rng):
    """The quantized scatter must survive a dense scratch spanning MORE
    pages than the block table (the engine's chunked long-prompt path pads
    its scratch to whole prefill chunks, which overshoots max_model_len
    whenever it is not chunk-divisible). Regression: the per-page scale
    scatter used to raise a broadcast error at trace time."""
    ps, P = 8, 16
    paged = G.init_paged_cache(CFG, P, ps, jnp.float32, kv_bits=8)
    dense = G.init_cache(CFG, 1, 32, jnp.float32)  # 4 pages of scratch
    ids = jnp.asarray(rng.integers(0, 64, (1, 32)).astype(np.int32))
    _, dense = G.forward_with_cache(CFG, params, ids, dense)
    table = jnp.asarray(np.array([3, 9], np.int32))  # only 2 table columns
    out = G.write_prompt_kv(paged, dense, table, jnp.int32(12))
    k_dense = np.asarray(dense["k"])
    ks = np.asarray(out["k_scales"])
    kq = np.asarray(out["k_pages"]).astype(np.float32)
    # pages 3 and 9 hold quantized positions 0..11; everything else untouched
    deq3 = kq[:, :, 3] * ks[:, :, 3][..., None, None]
    np.testing.assert_allclose(deq3, k_dense[:, 0, :, :8], atol=3e-2,
                               rtol=3e-2)
    mask = np.ones(P, bool)
    mask[[3, 9]] = False
    assert (np.asarray(out["k_pages"])[:, :, mask] == 0).all()


def test_quantized_append_grows_scale_without_clipping(params):
    """A decode append whose K/V absmax exceeds the page's prefill-time
    scale must GROW the scale (requantizing the page) instead of clipping
    the new token — the scale monotonically covers every token written."""
    cfg = CFG
    ps, P = 8, 8
    paged = G.init_paged_cache(cfg, P, ps, jnp.float32, kv_bits=8)
    # page 1 starts with a tiny-scale fill: scatter a 1-token prompt
    dense = G.init_cache(cfg, 1, 8, jnp.float32)
    ids = jnp.zeros((1, 8), jnp.int32)
    _, dense = G.forward_with_cache(cfg, params, ids, dense)
    table = jnp.asarray(np.array([1, 0], np.int32))
    paged = G.write_prompt_kv(paged, dense, table, jnp.int32(1))
    s_before = np.asarray(paged["k_scales"])[:, :, 1].copy()
    # one decode step appends token KV into page 1 at offset 1
    lg, paged2 = G.paged_decode_step(
        cfg, params, jnp.asarray(np.array([13], np.int32)), paged,
        table[None], jnp.asarray(np.array([1], np.int32)), impl="gather")
    s_after = np.asarray(paged2["k_scales"])[:, :, 1]
    assert (s_after >= s_before - 1e-7).all()  # scales never shrink
    assert np.isfinite(np.asarray(lg)).all()


def test_quantized_append_resets_scale_when_opening_a_page(params, rng):
    """A decode token OPENING a fresh page (page-aligned context) must
    establish the page scale from its own absmax — not max() against the
    pool's garbage there (the 1.0 init, or a recycled page's previous
    tenant). Regression: a page-aligned prompt used to decode its first
    tokens at scale >= 1.0, quantizing K/V of magnitude ~0.1-1 to {-1,0,1}
    and flipping greedy argmax."""
    cfg = CFG
    ps, P = 8, 16
    prompt = rng.integers(0, 64, (8,)).astype(np.int32)  # exactly one page
    paged = G.init_paged_cache(cfg, P, ps, jnp.float32, kv_bits=8)
    # poison page 2's scale as if a previous tenant left a huge value
    paged["k_scales"] = paged["k_scales"].at[:, :, 2].set(37.0)
    paged["v_scales"] = paged["v_scales"].at[:, :, 2].set(37.0)
    dense = G.init_cache(cfg, 1, 8, jnp.float32)
    _, dense = G.forward_with_cache(cfg, params, jnp.asarray(prompt[None]),
                                    dense)
    tables = np.array([[1, 2, 0, 0]], np.int32)
    paged = G.write_prompt_kv(paged, dense, jnp.asarray(tables[0]),
                              jnp.int32(8))
    lengths = np.array([8], np.int32)
    toks = rng.integers(0, 64, (4,)).astype(np.int32)
    for t in range(4):
        ref, _ = G.paged_decode_step(cfg, params, jnp.asarray(toks[t:t + 1]),
                                     _dequant_cache(paged, 8),
                                     jnp.asarray(tables),
                                     jnp.asarray(lengths), impl="gather")
        lg, paged = G.paged_decode_step(cfg, params, jnp.asarray(toks[t:t + 1]),
                                        paged, jnp.asarray(tables),
                                        jnp.asarray(lengths), impl="gather")
        np.testing.assert_array_equal(np.argmax(np.asarray(lg), -1),
                                      np.argmax(np.asarray(ref), -1))
        lengths += 1
    # the opened page's scales were re-established from real tokens, not
    # inherited: far below both the poison and the 1.0 init ceiling
    k_s = np.asarray(paged["k_scales"])[:, :, 2]
    assert (k_s < 1.0).all(), k_s.max()
    assert np.isfinite(np.asarray(lg)).all()


def test_scatter_start_skips_shared_prefix_pages(params, rng):
    """write_prompt_kv with ``start`` must leave pages below the start
    position untouched (they are BORROWED shared-prefix pages) and place
    positions >= start exactly as a start-less scatter would."""
    ps, P = 8, 16
    paged = G.init_paged_cache(CFG, P, ps, jnp.float32)
    # pre-poison page 3 so an illegal write would be visible
    poison = jnp.full((2, 4, ps, 8), 7.0, jnp.float32)  # [L, H, ps, Dh]
    paged["k_pages"] = paged["k_pages"].at[:, :, 3].set(poison)
    dense = G.init_cache(CFG, 1, 32, jnp.float32)
    ids = jnp.asarray(rng.integers(0, 64, (1, 32)).astype(np.int32))
    _, dense = G.forward_with_cache(CFG, params, ids, dense)
    table = jnp.asarray(np.array([3, 9, 11, 0], np.int32))
    out = G.write_prompt_kv(paged, dense, table, jnp.int32(20),
                            start=jnp.int32(8))
    k_pages = np.asarray(out["k_pages"])
    k_dense = np.asarray(dense["k"])
    # page 3 (positions 0..7, below start) keeps its poison bytes
    np.testing.assert_array_equal(k_pages[:, :, 3], np.asarray(poison))
    # pages 9/11 hold positions 8..19 exactly
    np.testing.assert_array_equal(k_pages[:, :, 9], k_dense[:, 0, :, 8:16])
    np.testing.assert_array_equal(k_pages[:, :, 11, :4],
                                  k_dense[:, 0, :, 16:20])


def test_batch_scatter_matches_serial(params, rng):
    """write_prompt_kv_batch == per-row write_prompt_kv (the admission-batch
    prefill path must place identical bytes)."""
    ps, P, F, S = 8, 32, 3, 16
    dense = G.init_cache(CFG, F, S, jnp.float32)
    ids = jnp.asarray(rng.integers(0, 64, (F, S)).astype(np.int32))
    _, dense = G.forward_with_cache(CFG, params, ids, dense)
    lengths = np.array([5, 16, 1], np.int32)
    tables = np.zeros((F, 2), np.int32)
    free = list(range(1, P))
    for f in range(F):
        for i in range(pages_for(int(lengths[f]), ps)):
            tables[f, i] = free.pop()
    batch = G.write_prompt_kv_batch(
        G.init_paged_cache(CFG, P, ps, jnp.float32), dense,
        jnp.asarray(tables), jnp.asarray(lengths))
    serial = G.init_paged_cache(CFG, P, ps, jnp.float32)
    for f in range(F):
        serial = G.write_prompt_kv(serial, dense, jnp.asarray(tables[f]),
                                   jnp.int32(lengths[f]), row=f)
    np.testing.assert_array_equal(np.asarray(batch["k_pages"]),
                                  np.asarray(serial["k_pages"]))
    np.testing.assert_array_equal(np.asarray(batch["v_pages"]),
                                  np.asarray(serial["v_pages"]))