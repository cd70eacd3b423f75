"""Speculative decoding: drafter properties, the multi-token paged verify
kernel vs the dense formula, accept/reject commit semantics vs sequential
decode, scheduler-level rollback/audit under rejection and mid-window
preemption, and end-to-end greedy equivalence spec-on vs spec-off."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving import (AdaptiveSpecK,
                                             ContinuousBatchingScheduler,
                                             NGramDrafter, Request,
                                             RequestState, ServingConfig,
                                             ServingEngine,
                                             make_open_loop_workload,
                                             run_continuous, spec_k_ladder)
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.ops.pallas.decode_attention import (
    paged_decode_attention, paged_verify_attention)


# ------------------------------------------------------------------ drafters
def test_ngram_suffix_match():
    d = NGramDrafter(max_n=3)
    # context ... [7, 8, 9] ... ends with [7, 8]: propose what followed the
    # earlier [7, 8], i.e. [9, 4, 5]
    prompt = np.array([1, 7, 8, 9, 4, 5, 6, 7, 8], np.int32)
    out = d.draft(0, 0, prompt, [], 3)
    assert out.tolist() == [9, 4, 5]


def test_ngram_spans_prompt_and_generated():
    d = NGramDrafter(max_n=2)
    # the suffix match crosses the prompt/generated boundary
    out = d.draft(0, 0, np.array([5, 6, 7], np.int32), [8, 5, 6], 2)
    assert out.tolist() == [7, 8]


def test_ngram_empty_and_tiny_history():
    d = NGramDrafter()
    assert d.draft(0, 0, np.array([3], np.int32), [], 4).size == 0
    assert d.draft(0, 0, np.array([], np.int32), [], 4).size == 0
    assert d.draft(0, 0, np.array([1, 2], np.int32), [], 0).size == 0


def test_ngram_no_match():
    d = NGramDrafter()
    out = d.draft(0, 0, np.arange(10, dtype=np.int32), [], 3)
    assert out.size == 0  # strictly increasing: no repeated suffix


def test_ngram_degenerate_repeats():
    d = NGramDrafter(max_n=3)
    out = d.draft(0, 0, np.full(10, 5, np.int32), [], 3)
    assert out.tolist() == [5, 5, 5]
    # period-2 cycle: the continuation respects the phase
    ctx = np.array([1, 2] * 5, np.int32)          # ends ... 1, 2
    assert d.draft(0, 0, ctx, [], 3).tolist() == [1, 2, 1]


def test_ngram_prefers_full_continuation():
    d = NGramDrafter(max_n=2)
    # two [1, 2] matches, both with k tokens after them: the MOST RECENT
    # full continuation wins
    out = d.draft(0, 0, np.array([1, 2, 9, 8, 7, 1, 2, 3, 1, 2], np.int32),
                  [], 3)
    assert out.tolist() == [3, 1, 2]
    # only the early occurrence has any continuation at all
    out = d.draft(0, 0, np.array([1, 2, 9, 8, 7, 1, 2], np.int32), [], 3)
    assert out.tolist() == [9, 8, 7]


def test_spec_k_ladder():
    assert spec_k_ladder(4) == (1, 2, 4)
    assert spec_k_ladder(1) == (1,)
    assert spec_k_ladder(6) == (1, 2, 4)
    with pytest.raises(ValueError):
        spec_k_ladder(0)


def test_adaptive_k_backoff_and_climb():
    ctl = AdaptiveSpecK(spec_k_ladder(4))
    assert ctl.k == 4                      # starts optimistic
    for _ in range(10):
        ctl.observe(8, 0)                  # nothing accepted
    assert ctl.k == 1                      # collapsed to the floor
    for _ in range(20):
        ctl.observe(8, 8)                  # everything accepted
    assert ctl.k == 4                      # climbed back
    frozen = AdaptiveSpecK(spec_k_ladder(4), adaptive=False)
    for _ in range(10):
        frozen.observe(8, 0)
    assert frozen.k == 4                   # adaptivity off: k pinned


# ------------------------------------------------- verify kernel vs formula
def _dense_verify_ref(q, k_pages, v_pages, lens, tables, wk, wv,
                      k_scales=None, v_scales=None):
    """Materialize history + window per position; plain masked softmax."""
    B, W, H, Dh = q.shape
    ps = k_pages.shape[2]

    def depage(pages, scales, b, t):
        pg = int(tables[b, t // ps])
        off = t % ps
        x = np.asarray(pages[:, pg, off, :], np.float32)
        if scales is not None:
            if x.shape[-1] * 2 == Dh:  # unpack int4 half-split
                lo = (x.astype(np.int8).astype(np.int32) << 28) >> 28
                hi = x.astype(np.int8).astype(np.int32) >> 4
                x = np.concatenate([lo, hi], -1).astype(np.float32)
            x = x * np.asarray(scales)[:, pg, None]
        return x

    out = np.zeros((B, W, H, Dh), np.float32)
    for b in range(B):
        hist_k = [depage(k_pages, k_scales, b, t) for t in range(int(lens[b]))]
        hist_v = [depage(v_pages, v_scales, b, t) for t in range(int(lens[b]))]
        for i in range(W):
            ks = np.stack(hist_k + [np.asarray(wk[b, j], np.float32)
                                    for j in range(i + 1)], 1)
            vs = np.stack(hist_v + [np.asarray(wv[b, j], np.float32)
                                    for j in range(i + 1)], 1)
            s = np.einsum("hd,hsd->hs", np.asarray(q[b, i], np.float32),
                          ks) / np.sqrt(Dh)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, i] = np.einsum("hs,hsd->hd", p, vs)
    return out


@pytest.mark.parametrize("W", [2, 3, 5])
def test_verify_attention_vs_dense(rng, W):
    B, H, Dh, ps, npages, pps = 3, 4, 16, 8, 32, 4
    k_pages = jnp.asarray(rng.normal(size=(H, npages, ps, Dh)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(H, npages, ps, Dh)), jnp.float32)
    lens = jnp.asarray([0, 5, 17], jnp.int32)   # per-row, incl. empty
    tables = jnp.asarray(
        rng.permutation(np.arange(1, npages))[:B * pps].reshape(B, pps),
        jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, W, H, Dh)), jnp.float32)
    wk = jnp.asarray(rng.normal(size=(B, W, H, Dh)), jnp.float32)
    wv = jnp.asarray(rng.normal(size=(B, W, H, Dh)), jnp.float32)
    ref = _dense_verify_ref(q, k_pages, v_pages, lens, tables, wk, wv)
    got_g = paged_verify_attention(q, k_pages, v_pages, lens, tables,
                                   wk, wv, impl="gather")
    got_k = paged_verify_attention(q, k_pages, v_pages, lens, tables,
                                   wk, wv, impl="kernel")
    np.testing.assert_allclose(np.asarray(got_g), ref, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_k), ref, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("bits", [8, 4])
def test_verify_attention_quantized(rng, bits):
    """int8/int4 pools: kernel and gather dequantize identically; both
    match the dequantize-then-dense reference."""
    B, H, Dh, ps, npages, pps, W = 2, 4, 16, 8, 16, 3, 3
    Dq = Dh // 2 if bits == 4 else Dh
    k_pages = jnp.asarray(rng.integers(-7, 8, (H, npages, ps, Dq)), jnp.int8)
    v_pages = jnp.asarray(rng.integers(-7, 8, (H, npages, ps, Dq)), jnp.int8)
    k_scales = jnp.asarray(rng.uniform(0.05, 0.3, (H, npages)), jnp.float32)
    v_scales = jnp.asarray(rng.uniform(0.05, 0.3, (H, npages)), jnp.float32)
    lens = jnp.asarray([6, 13], jnp.int32)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, npages))[:B * pps].reshape(B, pps),
        jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, W, H, Dh)), jnp.float32)
    wk = jnp.asarray(rng.normal(size=(B, W, H, Dh)), jnp.float32)
    wv = jnp.asarray(rng.normal(size=(B, W, H, Dh)), jnp.float32)
    ref = _dense_verify_ref(q, k_pages, v_pages, lens, tables, wk, wv,
                            k_scales, v_scales)
    got_g = paged_verify_attention(q, k_pages, v_pages, lens, tables, wk, wv,
                                   impl="gather", k_scales=k_scales,
                                   v_scales=v_scales)
    got_k = paged_verify_attention(q, k_pages, v_pages, lens, tables, wk, wv,
                                   impl="kernel", k_scales=k_scales,
                                   v_scales=v_scales)
    np.testing.assert_allclose(np.asarray(got_g), ref, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(got_g),
                               atol=2e-5, rtol=1e-4)


def test_verify_w1_bitwise_vs_single_token_fallback(rng):
    """W=1 verification must reproduce the single-token paged fallback
    BITWISE once the window token is where the pool write would have put
    it — the structural property the greedy-equivalence gate leans on."""
    B, H, Dh, ps, npages, pps = 3, 4, 16, 8, 16, 4
    k_pages = np.asarray(rng.normal(size=(H, npages, ps, Dh)), np.float32)
    v_pages = np.asarray(rng.normal(size=(H, npages, ps, Dh)), np.float32)
    lens = np.asarray([4, 9, 0], np.int32)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, npages))[:B * pps].reshape(B, pps),
        jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    wk = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    wv = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    got = paged_verify_attention(q, jnp.asarray(k_pages),
                                 jnp.asarray(v_pages), jnp.asarray(lens),
                                 tables, wk, wv, impl="gather")
    # sequential path: append the window token into the pool, lengths + 1
    kp2, vp2 = k_pages.copy(), v_pages.copy()
    for b in range(B):
        pg = int(tables[b, int(lens[b]) // ps])
        off = int(lens[b]) % ps
        kp2[:, pg, off, :] = np.asarray(wk[b, 0])
        vp2[:, pg, off, :] = np.asarray(wv[b, 0])
    ref = paged_decode_attention(q, jnp.asarray(kp2), jnp.asarray(vp2),
                                 jnp.asarray(lens + 1), tables,
                                 impl="gather")
    assert np.array_equal(np.asarray(got[:, 0]), np.asarray(ref[:, 0]))


# ------------------------------------------- verify step + commit semantics
def _tiny(vocab=64):
    return G.GPTConfig(vocab_size=vocab, d_model=32, n_layer=2, n_head=4,
                       max_seq_len=128)


@pytest.mark.parametrize("rotary", [False, True])
def test_verify_step_matches_sequential(rng, rotary):
    """One W-token verify dispatch reproduces W sequential decode steps'
    logits to XLA reduction-tiling noise (different-W executables may tile
    the same reductions differently — observed ~3e-8 on CPU) with every
    argmax IDENTICAL, and committing all W reproduces the sequential pool
    to the same tolerance — speculation is invisible in outputs by
    construction."""
    cfg = G.GPTConfig(vocab_size=64, d_model=32, n_layer=2, n_head=4,
                      max_seq_len=128, rotary=rotary, rotary_pct=0.5)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), G.init_params(cfg, jax.random.PRNGKey(1)))
    B, ps, npages, pps, W = 3, 8, 32, 6, 3
    paged = G.init_paged_cache(cfg, npages, ps, jnp.float32)
    tables = jnp.asarray(
        np.random.default_rng(3).permutation(
            np.arange(1, npages))[:B * pps].reshape(B, pps), jnp.int32)
    lens = jnp.asarray([4, 7, 2], jnp.int32)
    ids = jnp.asarray(rng.integers(0, 64, (B,)), jnp.int32)
    seq_cache, toks, cur, seq_logits = paged, ids, lens, []
    for _ in range(W):
        lg, seq_cache = G.paged_decode_step(cfg, params, toks, seq_cache,
                                            tables, cur, impl="gather")
        seq_logits.append(lg)
        toks = jnp.argmax(lg, -1).astype(jnp.int32)
        cur = cur + 1
    win = jnp.stack([ids] + [jnp.argmax(seq_logits[i], -1).astype(jnp.int32)
                             for i in range(W - 1)], axis=1)
    vlog, wk, wv = G.paged_verify_step(cfg, params, win, paged, tables,
                                       lens, impl="gather")
    for i in range(W):
        np.testing.assert_allclose(np.asarray(vlog[:, i]),
                                   np.asarray(seq_logits[i]),
                                   atol=1e-5, rtol=1e-5)
        assert bool(jnp.all(jnp.argmax(vlog[:, i], -1)
                            == jnp.argmax(seq_logits[i], -1))), f"pos {i}"
    committed = G.commit_window_kv(paged, wk, wv, tables, lens,
                                   jnp.full(B, W, jnp.int32))
    np.testing.assert_allclose(np.asarray(committed["k_pages"]),
                               np.asarray(seq_cache["k_pages"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(committed["v_pages"]),
                               np.asarray(seq_cache["v_pages"]), atol=1e-5)


def test_commit_partial_matches_sequential_prefix(rng):
    """Rejection = NOT committing: per-row n_commit writes exactly the
    accepted prefix; the pool equals n sequential appends, bitwise, and
    positions past the frontier stay untouched."""
    cfg = _tiny()
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        G.init_params(cfg, jax.random.PRNGKey(2)))
    B, ps, npages, pps, W = 3, 8, 32, 6, 4
    paged = G.init_paged_cache(cfg, npages, ps, jnp.float32)
    tables = jnp.asarray(
        np.random.default_rng(5).permutation(
            np.arange(1, npages))[:B * pps].reshape(B, pps), jnp.int32)
    lens = jnp.asarray([3, 6, 10], jnp.int32)
    win = jnp.asarray(rng.integers(0, 64, (B, W)), jnp.int32)
    _, wk, wv = G.paged_verify_step(cfg, params, win, paged, tables, lens,
                                    impl="gather")
    n = jnp.asarray([0, 2, 4], jnp.int32)
    got = G.commit_window_kv(paged, wk, wv, tables, lens, n)
    # row 0 committed nothing: its pages must be bit-identical to the init
    for j in range(pps):
        pg = int(tables[0, j])
        assert bool(jnp.all(got["k_pages"][:, :, pg] ==
                            paged["k_pages"][:, :, pg]))
    # the one-shot commit equals committing each window step separately
    # (token i at position lens+i for rows still inside their prefix)
    ref = paged
    for i in range(W):
        ref = G.commit_window_kv(
            ref, wk[:, :, i:i + 1], wv[:, :, i:i + 1], tables, lens + i,
            (n > i).astype(jnp.int32))
    assert bool(jnp.all(got["k_pages"] == ref["k_pages"]))
    assert bool(jnp.all(got["v_pages"] == ref["v_pages"]))


@pytest.mark.parametrize("kv_bits", [8, 4])
@pytest.mark.slow
def test_commit_quantized_matches_sequential_appends(rng, kv_bits):
    """Quantized pools: GIVEN the same window K/V values, the one-shot
    commit reproduces per-token sequential ``_append_kv_token`` calls
    BITWISE — payloads AND page scales (opening offsets re-establish,
    mid-page grows requantize; the shared writer cannot drift). Large
    outlier values force actual scale growth mid-page."""
    cfg = _tiny()
    L, H, Dh = cfg.n_layer, cfg.n_head, cfg.head_dim
    B, ps, npages, pps, W = 3, 8, 16, 4, 4
    paged = G.init_paged_cache(cfg, npages, ps, jnp.float32, kv_bits=kv_bits)
    tables = jnp.asarray(
        np.random.default_rng(7).permutation(
            np.arange(1, npages))[:B * pps].reshape(B, pps), jnp.int32)
    # mid-page, page-opening, and page-crossing rows
    lens = jnp.asarray([5, 8, 14], jnp.int32)
    # seed the pool with real prior appends so requantization has payload
    # to move (positions 0..lens-1)
    for t in range(int(jnp.max(lens))):
        live = (t < lens).astype(jnp.int32)
        pos = jnp.minimum(jnp.full((B,), t, jnp.int32), lens - 1)
        page = jnp.where(live > 0, jnp.take_along_axis(
            tables, (pos // ps)[:, None], axis=1)[:, 0], 0)
        tok_k = jnp.asarray(rng.normal(size=(L, H, B, Dh)), jnp.float32)
        tok_v = jnp.asarray(rng.normal(size=(L, H, B, Dh)), jnp.float32)
        for li in range(L):
            kp, ks = G._append_kv_token(paged["k_pages"][li],
                                        paged["k_scales"][li], tok_k[li],
                                        page, pos % ps, kv_bits)
            vp, vs = G._append_kv_token(paged["v_pages"][li],
                                        paged["v_scales"][li], tok_v[li],
                                        page, pos % ps, kv_bits)
            paged = {
                "k_pages": paged["k_pages"].at[li].set(kp),
                "v_pages": paged["v_pages"].at[li].set(vp),
                "k_scales": paged["k_scales"].at[li].set(ks),
                "v_scales": paged["v_scales"].at[li].set(vs)}
    # window values with outliers that grow mid-page scales
    wk = jnp.asarray(rng.normal(size=(L, B, W, H, Dh)) * 3.0, jnp.float32)
    wv = jnp.asarray(rng.normal(size=(L, B, W, H, Dh)) * 3.0, jnp.float32)
    n = jnp.asarray([1, 3, 4], jnp.int32)
    got = G.commit_window_kv(paged, wk, wv, tables, lens, n)
    # sequential reference: per-step _append_kv_token, masked rows -> sink
    ref = {k: v for k, v in paged.items()}
    for i in range(W):
        write = (i < n).astype(jnp.int32)
        pos = lens + i
        pidx = jnp.clip(pos // ps, 0, pps - 1)
        page = jnp.where(write > 0, jnp.take_along_axis(
            tables, pidx[:, None], axis=1)[:, 0], 0)
        off = pos % ps
        for li in range(L):
            kp, ks = G._append_kv_token(
                ref["k_pages"][li], ref["k_scales"][li],
                wk[li, :, i].transpose(1, 0, 2), page, off, kv_bits)
            vp, vs = G._append_kv_token(
                ref["v_pages"][li], ref["v_scales"][li],
                wv[li, :, i].transpose(1, 0, 2), page, off, kv_bits)
            ref = {"k_pages": ref["k_pages"].at[li].set(kp),
                   "v_pages": ref["v_pages"].at[li].set(vp),
                   "k_scales": ref["k_scales"].at[li].set(ks),
                   "v_scales": ref["v_scales"].at[li].set(vs)}
    # page 0 is the reserved sink: masked rows redirect there, and
    # duplicate-index scatters make its (never-read) contents order-
    # dependent — every REAL page's payload must match bitwise; scales to
    # ULP (the compiled scan may fuse amax/qmax into a reciprocal multiply
    # where the eager reference divides — a last-ULP artifact)
    for key in ("k_pages", "v_pages"):
        assert bool(jnp.all(got[key][:, :, 1:] == ref[key][:, :, 1:])), key
    for key in ("k_scales", "v_scales"):
        np.testing.assert_allclose(np.asarray(got[key][:, :, 1:]),
                                   np.asarray(ref[key][:, :, 1:]),
                                   rtol=1e-6, err_msg=key)


# --------------------------------------------------- scheduler-level (fake)
class SpecFakeExecutor:
    """Deterministic device-free executor with the verify protocol: the
    'model' continues any token as prev+1 (mod 97) — matching
    tests/test_serving.FakeExecutor — and acceptance/eos/budget semantics
    mirror the real in-program logic."""

    def __init__(self):
        self.verify_calls = 0
        self.decode_calls = 0

    def prefill(self, slot, tokens, table_row, start=0):
        return (int(tokens[-1]) + 1) % 97

    def decode(self, tokens, tables, lengths, active, steps=1):
        self.decode_calls += 1
        return np.stack([(tokens + k + 1) % 97 for k in range(steps)])

    def verify(self, tokens, tables, lengths, active, eos, budget):
        self.verify_calls += 1
        S, W = tokens.shape
        outs = (tokens + 1) % 97
        agree = (tokens[:, 1:] == outs[:, :-1]).astype(np.int64)
        n = 1 + np.cumprod(agree, axis=1).sum(axis=1)
        is_eos = (outs == eos[:, None]) & (eos[:, None] >= 0)
        has = is_eos.any(axis=1)
        first = np.argmax(is_eos, axis=1)
        n = np.where(has, np.minimum(n, first + 1), n)
        n = np.clip(n, 0, np.maximum(budget, 0))
        return outs, n.astype(np.int64)


class ChainDrafter:
    """Perfect drafter for the fake chain model."""

    kind = "chain"

    def __init__(self):
        self.released = []

    def draft(self, slot, rid, prompt, tokens, k):
        last = tokens[-1] if tokens else int(prompt[-1])
        # the chain model continues t -> t+1, so the tokens after `last`
        # are last+1, last+2, ...
        return np.asarray([(last + 1 + i) % 97 for i in range(k)], np.int32)

    def release(self, slot):
        self.released.append(slot)


class WrongDrafter:
    """Always-wrong drafter: every window is a full reject."""

    kind = "wrong"

    def draft(self, slot, rid, prompt, tokens, k):
        return np.full(k, 96, np.int32)

    def release(self, slot):
        pass


def _sched(ex, drafter=None, num_slots=2, num_pages=32, page_size=4,
           pages_per_seq=8, **kw):
    return ContinuousBatchingScheduler(
        ex, num_slots=num_slots, num_pages=num_pages, page_size=page_size,
        pages_per_seq=pages_per_seq, drafter=drafter, **kw)


def test_spec_scheduler_outputs_match_plain():
    reqs = lambda: [Request(prompt=np.arange(1, n + 2, dtype=np.int32),  # noqa: E731
                            max_new_tokens=m)
                    for n, m in [(3, 9), (6, 4), (2, 7)]]
    plain = reqs()
    s0 = _sched(SpecFakeExecutor())
    for r in plain:
        s0.submit(r)
    s0.run_to_completion()
    spec = reqs()
    ex = SpecFakeExecutor()
    s1 = _sched(ex, drafter=ChainDrafter(), spec_k=4)
    for r in spec:
        s1.submit(r)
    s1.run_to_completion()
    for a, b in zip(plain, spec):
        assert a.tokens == b.tokens
    assert ex.verify_calls > 0
    assert s1.spec_stats["accepted"] > 0
    # the perfect drafter finishes in strictly fewer device dispatches
    assert (ex.verify_calls + ex.decode_calls
            < s0.executor.decode_calls)
    assert s1.audit()["ok"] and s1.allocator.allocated_pages == 0


def test_spec_full_reject_still_progresses_and_audits_clean():
    ex = SpecFakeExecutor()
    s = _sched(ex, drafter=WrongDrafter(), spec_k=4)
    r = Request(prompt=np.array([1, 2], np.int32), max_new_tokens=6)
    s.submit(r)
    s.run_to_completion()
    assert r.tokens == [3, 4, 5, 6, 7, 8]   # chain continuation, unchanged
    assert s.spec_stats["full_reject_windows"] > 0
    assert s.spec_stats["accepted"] == 0
    # adaptive k collapsed to the floor under full rejection
    assert s._spec_ctl.k == 1
    assert s.audit()["ok"] and s.allocator.allocated_pages == 0
    assert r.spec_drafted > 0 and r.spec_accepted == 0


def test_spec_eos_truncates_window():
    ex = SpecFakeExecutor()
    s = _sched(ex, drafter=ChainDrafter(), spec_k=4)
    # chain from 10: 11, 12, 13... eos at 13 must cut generation short
    r = Request(prompt=np.array([10], np.int32), max_new_tokens=20,
                eos_token_id=13)
    s.submit(r)
    s.run_to_completion()
    assert r.tokens[-1] == 13
    assert len(r.tokens) == 3
    assert s.audit()["ok"] and s.allocator.allocated_pages == 0


def test_spec_budget_truncates_window():
    ex = SpecFakeExecutor()
    s = _sched(ex, drafter=ChainDrafter(), spec_k=4)
    r = Request(prompt=np.array([1, 2, 3], np.int32), max_new_tokens=2)
    s.submit(r)
    s.run_to_completion()
    assert r.tokens == [4, 5]               # never past max_new
    assert s.audit()["ok"] and s.allocator.allocated_pages == 0


def test_spec_drafter_released_on_finish():
    d = ChainDrafter()
    s = _sched(SpecFakeExecutor(), drafter=d)
    r = Request(prompt=np.array([1], np.int32), max_new_tokens=3)
    s.submit(r)
    s.run_to_completion()
    assert d.released  # slot state dropped when the request left


def test_spec_no_drafts_falls_back_to_decode():
    class SilentDrafter:
        kind = "silent"

        def draft(self, slot, rid, prompt, tokens, k):
            return np.empty(0, np.int32)

        def release(self, slot):
            pass

    ex = SpecFakeExecutor()
    s = _sched(ex, drafter=SilentDrafter())
    r = Request(prompt=np.array([1, 2], np.int32), max_new_tokens=4)
    s.submit(r)
    s.run_to_completion()
    assert r.tokens == [3, 4, 5, 6]
    assert ex.verify_calls == 0 and ex.decode_calls > 0
    assert s.spec_stats["fallback_steps"] > 0


def test_spec_mid_window_dispatch_failure_heals():
    """A verify episode whose every retry raises: preempt-and-requeue with
    kept tokens, audit clean, outputs identical to a fault-free run."""
    from deepspeed_tpu.resilience import FaultPlan, install_plan

    clean = Request(prompt=np.array([1, 2], np.int32), max_new_tokens=8)
    s0 = _sched(SpecFakeExecutor(), drafter=ChainDrafter())
    s0.submit(clean)
    s0.run_to_completion()

    faulty = Request(prompt=np.array([1, 2], np.int32), max_new_tokens=8)
    s = _sched(SpecFakeExecutor(), drafter=ChainDrafter(),
               dispatch_retries=1)
    s.submit(faulty)
    # dispatch 0 is the prefill; fail the SECOND verify window entirely
    install_plan(FaultPlan(dispatch_raise_at=2, dispatch_raise_times=2))
    try:
        s.run_to_completion()
    finally:
        install_plan(None)
    assert faulty.tokens == clean.tokens
    assert faulty.preemptions >= 1
    assert s.counters.get("dispatch_failed", 0) >= 1
    assert s.audit()["ok"] and s.allocator.allocated_pages == 0


def test_spec_preemption_under_pool_pressure():
    """Mid-window page exhaustion preempts the newest slot (kept tokens)
    and the run still completes with the exact chain outputs."""
    ex = SpecFakeExecutor()
    s = _sched(ex, drafter=ChainDrafter(), num_slots=2, num_pages=8,
               page_size=2, pages_per_seq=8, spec_k=4)
    a = Request(prompt=np.array([1, 2, 3], np.int32), max_new_tokens=8)
    b = Request(prompt=np.array([50, 51, 52], np.int32), max_new_tokens=8)
    s.submit(a)
    s.submit(b)
    s.run_to_completion()
    assert a.tokens == [4, 5, 6, 7, 8, 9, 10, 11]
    assert b.tokens == [53, 54, 55, 56, 57, 58, 59, 60]
    assert a.preemptions + b.preemptions >= 1
    assert s.audit()["ok"] and s.allocator.allocated_pages == 0


# ----------------------------------------------------- engine end to end
@pytest.fixture(scope="module")
def tiny_setup():
    cfg = _tiny()
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **spec_kw):
    draft = spec_kw.pop("_draft", None)
    return ServingEngine(cfg, params, ServingConfig(
        num_slots=2, page_size=8, max_model_len=64, prefill_chunk=16,
        dtype="float32", decode_block=2, max_queue=16, **spec_kw),
        draft=draft)


def _run_wl(eng, seed=11):
    wl = make_open_loop_workload(5, rate_rps=500.0, prompt_len=(3, 20),
                                 max_new=(4, 12), vocab_size=64, seed=seed)
    rep = run_continuous(eng, wl)
    assert rep["finished"] == len(wl)
    return wl, rep


def test_engine_spec_greedy_equivalence(tiny_setup):
    cfg, params = tiny_setup
    off_wl, _ = _run_wl(_engine(cfg, params))
    on_wl, rep = _run_wl(_engine(cfg, params, spec_drafter="ngram",
                                 spec_k=4))
    for a, b in zip(off_wl, on_wl):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
    assert rep["spec"]["windows"] > 0
    assert rep["pool_audit_ok"]


@pytest.mark.slow
def test_engine_spec_kv8_greedy_equivalence(tiny_setup):
    """Quantized pools: spec-on vs spec-off at kv_bits=8 stay identical —
    the window's dense-context verification plus sequential-exact commit
    does not move any argmax on this model."""
    cfg, params = tiny_setup
    off_wl, _ = _run_wl(_engine(cfg, params, kv_bits=8))
    on_wl, rep = _run_wl(_engine(cfg, params, kv_bits=8,
                                 spec_drafter="ngram", spec_k=4))
    for a, b in zip(off_wl, on_wl):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
    assert rep["spec"]["windows"] > 0


@pytest.mark.slow
def test_engine_draft_model_drafter(tiny_setup):
    """draft == target: near-total acceptance, strictly fewer dispatches
    than the n-gram run, identical outputs."""
    cfg, params = tiny_setup
    off_wl, off_rep = _run_wl(_engine(cfg, params))
    on_wl, rep = _run_wl(_engine(cfg, params, spec_drafter="draft_model",
                                 _draft=(cfg, params), spec_k=4))
    for a, b in zip(off_wl, on_wl):
        assert a.tokens == b.tokens
    assert rep["spec"]["accept_rate"] > 0.5
    assert rep["decode_steps"] < off_rep["decode_steps"]


@pytest.mark.slow
def test_engine_spec_under_chaos(tiny_setup):
    """End-to-end greedy equivalence holds across an injected verify
    dispatch failure (mid-window preemption on the real engine)."""
    from deepspeed_tpu.resilience import FaultPlan, install_plan

    cfg, params = tiny_setup
    off_wl, _ = _run_wl(_engine(cfg, params))
    eng = _engine(cfg, params, spec_drafter="ngram", spec_k=4)
    eng.warmup()
    install_plan(FaultPlan(dispatch_raise_at=6, dispatch_raise_times=3))
    try:
        on_wl, rep = _run_wl(eng)
    finally:
        install_plan(None)
    for a, b in zip(off_wl, on_wl):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
    assert rep["recovery_counters"].get("dispatch_error", 0) > 0
    assert rep["pool_audit_ok"]


def test_engine_verify_shapes_bounded_and_rule_silent(tiny_setup):
    """Warmup compiles one verify program per ladder entry and the
    unbucketed-decode-shape rule stays silent on the full compile log."""
    from deepspeed_tpu.analysis import analyze_compile_log

    cfg, params = tiny_setup
    eng = _engine(cfg, params, spec_drafter="ngram", spec_k=4)
    n = eng.warmup()
    verify_shapes = [tuple(e["shape"]) for e in eng.compile_log
                     if e["kind"] == "serving_verify"]
    assert verify_shapes == [(2, 2), (3, 2), (5, 2)]
    _run_wl(eng)
    assert len(eng.compile_log) == n  # traffic compiled NOTHING new
    assert not analyze_compile_log(eng).findings


def test_spec_window_at_table_capacity(tiny_setup):
    """A request whose prompt+max_new EQUALS max_model_len speculates right
    up to the table edge: out-of-range window scatter positions must DROP,
    never clip onto a committable position (a clipped rejected-draft K/V at
    S-1 would flip the final committed token). Regression for the gather
    fallback's capacity-edge overwrite."""
    cfg, params = tiny_setup
    max_len = 64

    def run(spec):
        eng = _engine(cfg, params,
                      **(dict(spec_drafter="ngram", spec_k=4) if spec
                         else {}))
        # prompt + max_new == max_model_len, page-aligned table
        req = Request(prompt=(np.arange(32, dtype=np.int32) % 7 + 1),
                      max_new_tokens=max_len - 32)
        sched = eng.make_scheduler()
        assert sched.submit(req)
        sched.run_to_completion()
        assert sched.audit()["ok"]
        return req

    off = run(False)
    on = run(True)
    assert len(on.tokens) == len(off.tokens) == 32
    assert on.tokens == off.tokens


def test_verify_phase_rides_decode_deadline(tiny_setup):
    """Arming decode_deadline_s must also arm the verify phase — with a
    drafter configured nearly every dispatch is a verify, and a wedged one
    has to trip the same PR 7 stall ladder a wedged decode does."""
    from deepspeed_tpu.resilience.watchdog import SERVING_PHASES

    assert "serving_verify" in SERVING_PHASES
    cfg, params = tiny_setup
    eng = _engine(cfg, params, spec_drafter="ngram", spec_k=2,
                  decode_deadline_s=5.0)
    sched = eng.make_scheduler()
    try:
        assert sched.watchdog is not None
        assert sched.watchdog.deadlines.get("serving_verify") == 5.0
    finally:
        sched.close()


def test_auto_slots_prices_explicit_draft_pair(monkeypatch, tiny_setup):
    """num_slots='auto' with ServingEngine(draft=(cfg, params)) must charge
    the PASSED draft model's params+cache, not silently skip them because
    no spec_draft_model preset name was set."""
    from deepspeed_tpu.runtime import aot

    cfg, params = tiny_setup
    seen = {}
    real = aot.speculation_hbm_bytes

    def spy(model, **kw):
        out = real(model, **kw)
        seen.update(out)
        return out

    def fake_report(model, *, batch=1, **kw):
        peak = int(0.05 * aot.HBM_BYTES * batch)
        fit = aot.fit_verdict(peak)
        return {"model": model, "batch": batch, "cache_dtype": "bfloat16",
                "per_device_bytes": {"peak": peak}, "fit": fit,
                "fits_v5e_hbm": fit["confidence"] != "oom"}

    monkeypatch.setattr(aot, "decode_program_report", fake_report)
    monkeypatch.setattr(aot, "speculation_hbm_bytes", spy)
    eng = ServingEngine(cfg, params, ServingConfig(
        num_slots="auto", model_name="gpt2-125m", page_size=8,
        max_model_len=64, prefill_chunk=16, dtype="float32",
        spec_drafter="draft_model", spec_k=2), draft=(cfg, params))
    assert eng.num_slots >= 1
    assert seen["parts"]["draft_params"] > 0   # the PAIR's config priced
    assert seen["parts"]["draft_cache"] > 0


def test_engine_rejects_nonzero_temperature(tiny_setup):
    cfg, params = tiny_setup
    with pytest.raises(NotImplementedError):
        ServingEngine(cfg, params, ServingConfig(max_model_len=64,
                                                 sampling_temperature=0.7))


# ------------------------------------------------------------------ dslint
def test_spec_rule_fire_and_silent():
    import types

    from deepspeed_tpu.analysis import analyze_compile_log

    def duck(**kw):
        base = dict(spec_drafter="ngram", sampling_temperature=0.0,
                    spec_acceptance="greedy", max_queue=8)
        base.update(kw)
        return types.SimpleNamespace(
            serving=types.SimpleNamespace(**base), compile_log=[])

    hot = analyze_compile_log(duck(sampling_temperature=0.8)).findings
    assert any(f.rule_id == "serving/speculation-without-greedy-gate"
               for f in hot)
    hot2 = analyze_compile_log(duck(spec_acceptance="topk")).findings
    assert any(f.rule_id == "serving/speculation-without-greedy-gate"
               for f in hot2)
    # silent: greedy path; no drafter
    assert not [f for f in analyze_compile_log(duck()).findings
                if f.rule_id == "serving/speculation-without-greedy-gate"]
    assert not [f for f in analyze_compile_log(
        duck(spec_drafter=None, sampling_temperature=0.8)).findings
        if f.rule_id == "serving/speculation-without-greedy-gate"]


# --------------------------------------------------------------- aot + fleet
def test_speculation_hbm_bytes_accounting():
    from deepspeed_tpu.runtime.aot import speculation_hbm_bytes

    ng = speculation_hbm_bytes("gpt2-125m", num_slots=8, spec_k=4,
                               max_model_len=512)
    assert ng["total"] == ng["parts"]["verify_window"] > 0
    dm = speculation_hbm_bytes("gpt2-760m", draft_model="gpt2-125m",
                               num_slots=8, spec_k=4, max_model_len=512)
    assert dm["parts"]["draft_params"] > 0
    assert dm["parts"]["draft_cache"] > 0
    assert dm["total"] > ng["total"]
    # the draft cache scales with slots; params do not
    dm2 = speculation_hbm_bytes("gpt2-760m", draft_model="gpt2-125m",
                                num_slots=16, spec_k=4, max_model_len=512)
    assert dm2["parts"]["draft_cache"] == 2 * dm["parts"]["draft_cache"]
    assert dm2["parts"]["draft_params"] == dm["parts"]["draft_params"]


def test_admission_limit_charges_speculation(monkeypatch):
    """num_slots='auto' with a drafter armed admits no MORE than without:
    the probe's peak is topped up with speculation bytes before the fit
    verdict (decode_program_report faked — no TPU compiler needed)."""
    from deepspeed_tpu.runtime import aot

    hbm = aot.HBM_BYTES

    def fake_report(model, *, batch=1, **kw):
        peak = int(0.04 * hbm * batch)   # fits up to ~24 slots bare
        fit = aot.fit_verdict(peak)
        return {"model": model, "batch": batch, "cache_dtype": "bfloat16",
                "per_device_bytes": {"peak": peak}, "fit": fit,
                "fits_v5e_hbm": fit["confidence"] != "oom"}

    monkeypatch.setattr(aot, "decode_program_report", fake_report)
    bare = aot.serving_admission_limit("gpt2-125m", hi=32)
    spec = aot.serving_admission_limit("gpt2-125m", hi=32,
                                       draft_model="gpt2-125m", spec_k=4,
                                       spec_max_len=2048)
    assert spec["max_slots"] <= bare["max_slots"]
    assert spec["speculation"]["total"] > 0
    # and the fleet plan consumes the same reduced verdict
    plan = aot.fleet_replica_plan("gpt2-125m", target_total_slots=32, hi=32,
                                  draft_model="gpt2-125m", spec_k=4,
                                  spec_max_len=2048)
    assert plan["slots_per_replica"] == spec["max_slots"]


def test_summarize_events_merges_spec_rows():
    from deepspeed_tpu.inference.fleet import summarize_events

    now = 1000.0
    events = [
        {"unix_time": 995.0, "event": "request_routed"},
        {"unix_time": 996.0, "event": "spec_window", "value": 6.0,
         "drafted": 8, "accepted": 5},
        {"unix_time": 997.0, "event": "spec_window", "value": 2.0,
         "drafted": 8, "accepted": 1},
        {"unix_time": 900.0, "event": "spec_window", "value": 9.0,
         "drafted": 8, "accepted": 8},   # outside the window: ignored
    ]
    s = summarize_events(events, now, 10.0)
    assert s["spec_windows"] == 2
    assert s["spec_accept_rate"] == pytest.approx(6 / 16)
    assert s["spec_tokens_per_dispatch"] == pytest.approx(4.0)
    quiet = summarize_events([{"unix_time": 999.0,
                               "event": "request_routed"}], now, 10.0)
    assert "spec_windows" not in quiet
