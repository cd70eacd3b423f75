"""A prompt longer than one chunk goes into its pages chunk by chunk
(``ServingEngine._chunk_to_pages``: ``gpt.paged_prefill_step`` with
``chunk=(pos, align)``), held to the path it replaces, which the same engine
takes with the switch off: a dense scratch cache of every layer through
``forward_with_cache``, whose head runs on the prompt's last real token in the
chunk where the prompt ends and in no other, then ``jit_scatter``. Same
rows in the same type at the same places, so where the table is read whole
everything is compared bit for bit: the pool on the request's pages and
everywhere else, the first token, the states of the real rows. A wide table is
read a block at a time under a running softmax, another order of the same
sums: within a tolerance there. And which engines take which path: a latent, a
key-value-head, a quantized or a tensor-parallel one keeps the dense chunk.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving import (Request, ServingConfig,
                                             ServingEngine)
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.profiling import trace

PLAIN = dataclasses.replace(G.PRESETS["tiny"], max_seq_len=256,
                            state_layers=(1, 2))
LOOPED = dataclasses.replace(           # 2 layers x 4 passes: 8 cache layers
    PLAIN, ut_steps=4, loop_norm=True, norm="rmsnorm", rotary=True,
    rotary_pct=1.0, post_norm=True)
PAGE, CHUNK, MAX_LEN = 16, 64, 256      # chunk buckets 32 and 64
PAGES = 40


def _engine(cfg, paged: bool, seed: int = 0, **serving):
    """An engine over ``cfg`` whose pool holds something everywhere; with
    ``paged`` off it takes the dense chunk and the scatter."""
    serving = dict(dict(num_slots=4, page_size=PAGE, max_model_len=MAX_LEN,
                        prefill_chunk=CHUNK, num_pages=PAGES,
                        dtype="float32"), **serving)
    engine = ServingEngine(cfg, G.init_params(cfg, jax.random.PRNGKey(seed)),
                           ServingConfig(**serving))
    assert engine._chunk_to_pages
    engine._chunk_to_pages = paged
    rng = np.random.default_rng(7)
    engine.paged_cache = {
        k: jnp.asarray(rng.standard_normal(v.shape).astype(np.float32))
        for k, v in engine.paged_cache.items()}
    return engine


def _table(pages, width=MAX_LEN // PAGE):
    row = np.zeros(width, np.int32)
    row[:len(pages)] = pages
    return row


def _pool(engine):
    return {k: np.asarray(v) for k, v in engine.paged_cache.items()}


def _prompt(n, seed=1):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


def _same_prefill(paged, dense, prompt, table, start=0):
    """Prefill ``prompt`` on both engines; first tokens, states of the real
    rows and whole pools equal bit for bit (the sink page apart: padding
    and masked pieces land there). Returns the pool."""
    toks = [e.prefill(0, prompt, table, start) for e in (paged, dense)]
    assert toks[0] == toks[1]
    states = [np.concatenate([np.asarray(s[0]) for s in e.prefill_states],
                             axis=1)[:, :len(prompt)] for e in (paged, dense)]
    assert states[0].shape[1:] == (len(prompt), PLAIN.d_model)
    assert states[0].shape[0] == 1 + 2 * paged.cfg.ut_steps
    assert np.array_equal(*states)
    assert len(paged.prefill_states) == len(dense.prefill_states)
    have, want = _pool(paged), _pool(dense)
    for k in want:
        assert np.array_equal(have[k][:, :, 1:], want[k][:, :, 1:]), k
    return have


# prompt lengths: 2 chunks, 3 chunks, 2 chunks and a tail in the bucket of
# 32, 3 chunks and a tail in the bucket of 64, one token past a chunk
LENGTHS = {"two_chunks": 128, "three_chunks": 192, "tail_of_22": 150,
           "tail_of_40": 232, "one_token_past": 65}


@pytest.mark.parametrize("cfg", [PLAIN, LOOPED], ids=["plain", "looped"])
@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_a_chunked_prompt_fills_its_pages_as_the_dense_path_did(case, cfg):
    n = LENGTHS[case]
    paged, dense = _engine(cfg, True), _engine(cfg, False)
    before = _pool(paged)
    pages = [9, 3, 17, 5, 30, 2, 11, 8, 21, 6, 14, 39, 1, 25, 19][:-(-n // PAGE)]
    have = _same_prefill(paged, dense, _prompt(n), _table(pages))
    # what the request does not own holds what it held; its own pages hold
    # its rows up to its length and what they held past it
    other = np.setdiff1d(np.arange(1, PAGES), pages)
    for k, pool in have.items():
        assert pool.shape[0] == cfg.n_layer * cfg.ut_steps
        assert np.array_equal(pool[:, :, other], before[k][:, :, other])
        rows = pool[:, :, pages].reshape(pool.shape[:2] + (-1, pool.shape[-1]))
        old = before[k][:, :, pages].reshape(rows.shape)
        assert np.array_equal(rows[:, :, n:], old[:, :, n:])
        assert not np.array_equal(rows[:, :, :n], old[:, :, :n])


WIDE = dataclasses.replace(PLAIN, max_seq_len=2048)
TOL = 2e-5      # float32 both sides: test_looped_model.py says what it covers


@pytest.mark.parametrize("n", [600, 1100, 1537])
def test_a_wide_table_is_read_a_block_of_pages_at_a_time(n):
    """A table wider than two key blocks (128 pages of 16 here, blocks of
    512 places): a chunk reads the blocks under its last position and no
    further, under a running softmax. Another order of the same sums than
    the dense path's one softmax over every place, so within ``TOL``: the
    rows of the first layer equal bit for bit (they see no attention), what
    the request does not own is untouched."""
    geometry = dict(max_model_len=2048, num_pages=140)
    paged, dense = (_engine(WIDE, on, **geometry) for on in (True, False))
    before = _pool(paged)
    pages = list(range(1, 1 + -(-n // PAGE)))[::-1]
    table = _table(pages, 2048 // PAGE)
    toks = [e.prefill(0, _prompt(n), table) for e in (paged, dense)]
    assert toks[0] == toks[1]
    states = [np.concatenate([np.asarray(s[0]) for s in e.prefill_states],
                             axis=1)[:, :n] for e in (paged, dense)]
    assert np.abs(states[0] - states[1]).max() <= TOL
    other = np.setdiff1d(np.arange(1, 140), pages)
    for k, want in _pool(dense).items():
        have = _pool(paged)[k]
        assert np.array_equal(have[:, :, other], before[k][:, :, other])
        assert np.array_equal(have[0, :, pages], want[0, :, pages])
        assert np.abs(have[:, :, pages] - want[:, :, pages]).max() <= TOL
        assert not np.array_equal(have[:, :, pages], before[k][:, :, pages])


@pytest.mark.parametrize("start", [16, 64, 80])
def test_a_borrowed_prefix_page_is_read_and_never_written(start):
    """The lender's prompt fills pages 4..; the borrower names the first
    ``start // PAGE`` of them at the head of its own table and starts there:
    it reads them (its rows past ``start`` equal the dense path's, which
    computed the prefix afresh) and leaves them as they were."""
    paged, dense = _engine(PLAIN, True), _engine(PLAIN, False)
    lender = _prompt(150, seed=2)
    lent = [4, 7, 12, 13, 20, 22, 23, 24, 26, 27]
    _same_prefill(paged, dense, lender, _table(lent))
    before = _pool(paged)
    n_shared = start // PAGE
    borrower = np.concatenate([lender[:start], _prompt(141 - start, seed=3)])
    own = [31, 32, 33, 34, 35, 36, 37, 38, 10][:9 - n_shared]
    have = _same_prefill(paged, dense, borrower,
                         _table(lent[:n_shared] + own), start)
    for k, pool in have.items():
        assert np.array_equal(pool[:, :, lent], before[k][:, :, lent])
        assert not np.array_equal(pool[:, :, own], before[k][:, :, own])


def _run(engine, prompts, max_new):
    sched = engine.make_scheduler()
    reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
    for r in reqs:
        assert sched.submit(r)
    sched.run_to_completion(max_steps=400)
    assert sched.audit()["ok"] and sched.allocator.allocated_pages == 0
    sched.close()
    return reqs


def test_a_preempted_request_admitted_again_decodes_the_same_tokens():
    """Two chunked prompts in a pool that cannot hold both to their ends:
    the younger is preempted and admitted again with the tokens it kept, a
    chunked prompt once more, into other pages. Tokens equal the dense
    path's, request by request."""
    prompts = [_prompt(100, seed=4), _prompt(90, seed=5)]
    runs = []
    for paged in (True, False):
        engine = _engine(PLAIN, paged, num_slots=2, num_pages=15)
        runs.append(_run(engine, prompts, max_new=40))
    assert sum(r.preemptions for r in runs[0]) >= 1
    assert ([r.preemptions for r in runs[0]]
            == [r.preemptions for r in runs[1]])
    assert [r.tokens for r in runs[0]] == [r.tokens for r in runs[1]]


def test_a_cycle_of_chunked_prompts_is_fetched_once():
    """``prefill_many`` over two chunked prompts, a lone short one beside
    them: every dispatch goes out before the one wait, and the tokens are
    those of ``prefill`` one by one."""
    paged, dense = _engine(PLAIN, True), _engine(PLAIN, False)
    items = [(0, _prompt(150, seed=6), _table(range(1, 11))),
             (1, _prompt(40, seed=7), _table(range(11, 14))),
             (2, _prompt(70, seed=8), _table(range(14, 19)))]
    want = {slot: dense.prefill(slot, t, row) for slot, t, row in items}
    paged.warmup()
    trace.clear()
    assert paged.prefill_many(items) == want
    names = [e.name for e in trace.recorded()
             if e.name.startswith("engine.prefill.")]
    assert names.count(trace.ENGINE_PREFILL_CHUNK) == 3 + 2
    assert names.count(trace.ENGINE_PREFILL_FUSED) == 1
    assert names[-1] == trace.ENGINE_PREFILL_SAMPLE
    assert names.count(trace.ENGINE_PREFILL_SAMPLE) == 1
    for k, pool in _pool(dense).items():
        assert np.array_equal(_pool(paged)[k][:, :, 1:], pool[:, :, 1:])


def test_a_plain_engine_never_builds_the_scratch_cache_or_the_scatter(
        monkeypatch):
    engine = _engine(PLAIN, True)

    def refuse(*a, **k):
        raise AssertionError("the dense path ran")

    monkeypatch.setattr(G, "init_cache", refuse)
    monkeypatch.setattr(engine, "_get_scatter", refuse)
    monkeypatch.setattr(engine, "_get_prefill", refuse)
    engine.warmup()
    programs = len(engine.compile_log)
    kinds = {e["kind"] for e in engine.compile_log}
    assert "serving_scatter" not in kinds and "serving_prefill" in kinds
    trace.clear()
    engine.prefill(0, _prompt(150), _table(range(1, 11)))
    engine.prefill_many([(0, _prompt(200), _table(range(1, 14))),
                         (1, _prompt(129), _table(range(14, 23)))])
    assert len(engine.compile_log) == programs      # warm-up reached them all
    spans = [e for e in trace.recorded()
             if e.name.startswith("engine.prefill.")]
    assert {e.name for e in spans} == {trace.ENGINE_PREFILL_CHUNK,
                                     trace.ENGINE_PREFILL_SAMPLE}
    chunks = [e.counts for e in spans if e.name == trace.ENGINE_PREFILL_CHUNK]
    assert all(s["paged_tokens"] == s["padded_tokens"] for s in chunks)
    assert sum(s["real_tokens"] for s in chunks) == 150 + 200 + 129


def _latent():
    import test_latent_routed_model as T
    return T.CFG, {}


def _kv_heads():
    import test_window_gqa_model as T
    return T.CFG, dict(page_size=16)


DENSE = {
    "latent": _latent,
    "key_value_heads": _kv_heads,
    "quantized_pool": lambda: (G.PRESETS["tiny"], dict(kv_bits=8)),
    "tp2": lambda: (G.PRESETS["tiny"], dict(tp=2)),
}


def _dense_engine(kind):
    """An engine of ``kind`` that takes the dense chunk, over a vocabulary
    that is no other width of its model."""
    if kind == "plain":
        return _engine(dataclasses.replace(PLAIN, vocab_size=251), False,
                       prefill_chunk=32, max_model_len=128)
    cfg, serving = DENSE[kind]()
    cfg = dataclasses.replace(cfg, vocab_size=251)
    serving = dict(dict(num_slots=2, page_size=PAGE, max_model_len=128,
                        prefill_chunk=32, dtype="float32"), **serving)
    return ServingEngine(cfg, G.init_params(cfg, jax.random.PRNGKey(0)),
                         ServingConfig(**serving))


@pytest.mark.parametrize("kind", sorted(DENSE))
def test_the_other_kinds_keep_the_dense_chunk_and_the_scatter(kind):
    engine = _dense_engine(kind)
    assert not engine._chunk_to_pages
    trace.clear()
    engine.prefill(0, _prompt(70) % engine.cfg.vocab_size,
                   _table(range(1, 6), engine.serving.pages_per_seq))
    kinds = [e["kind"] for e in engine.compile_log]
    assert "serving_scatter" in kinds and not engine._prefill_paged_fns
    spans = [e for e in trace.recorded()
             if e.name.startswith("engine.prefill.")]
    assert [e.name for e in spans] == [
        trace.ENGINE_PREFILL_SCRATCH, *[trace.ENGINE_PREFILL_CHUNK] * 3,
        trace.ENGINE_PREFILL_SCATTER, trace.ENGINE_PREFILL_SAMPLE]
    assert [(e.counts["paged_tokens"], e.counts["head_tokens"]) for e in spans
            if e.name == trace.ENGINE_PREFILL_CHUNK] == [(0, 0), (0, 0), (0, 1)]


@pytest.mark.parametrize("case", ["two_chunks", "tail_of_22"])
def test_the_dense_chunks_first_token_is_the_full_forwards(case):
    """Exactly two chunks, and two chunks and a tail in the bucket of 32:
    the token the last chunk's program sampled is the greedy one of the
    logits of the whole prompt in one forward, and the spans say where the
    head ran: in the chunk where the prompt ends, on one position."""
    n = LENGTHS[case]
    dense, prompt = _engine(PLAIN, False), _prompt(n)
    trace.clear()
    tok = dense.prefill(0, prompt, _table(range(1, 1 - (-n // PAGE))))
    want = G.forward(PLAIN, dense.params, jnp.asarray(prompt[None]),
                     train=False)[0, -1]
    assert tok == int(jnp.argmax(want))
    chunks = [e.counts for e in trace.recorded()
              if e.name == trace.ENGINE_PREFILL_CHUNK]
    assert [c["head_tokens"] for c in chunks] == [0] * (len(chunks) - 1) + [1]
    assert sum(c["real_tokens"] for c in chunks) == n


def _arrays_ending(jaxpr, tail) -> int:
    """The arrays of ``jaxpr`` and every program nested in it whose shape
    ends in ``tail``."""
    n = 0
    for eqn in jaxpr.eqns:
        n += sum(tuple(v.aval.shape[-len(tail):]) == tuple(tail)
                 for v in eqn.outvars)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _arrays_ending(sub, tail)
    return n


@pytest.mark.parametrize("kind", ["plain", *sorted(DENSE)])
def test_the_dense_chunk_program_holds_no_logits_of_every_position(kind):
    """The guard: no array of ``[.., chunk, vocab]`` anywhere in
    ``prefill_chunk_<n>`` as the engine dispatches it, counted as
    ``program_counts`` counts kernels; the forward that is asked for every
    position holds one, so the count sees what it guards against."""
    engine = _dense_engine(kind)
    tail = (32, engine.cfg.vocab_size)
    ids = np.zeros((1, 32), np.int32)
    cache = engine.model.dense_cache(1, engine._dense_S)
    program = engine._get_prefill(32)
    assert program.__name__ == "prefill_chunk_32"
    jaxpr = program.trace(engine.params, ids, cache, np.int32(32),
                          np.bool_(False)).jaxpr
    assert _arrays_ending(jaxpr.jaxpr, tail) == 0
    every = jax.jit(engine.model.forward_with_cache).trace(
        engine.params, ids, cache).jaxpr
    assert _arrays_ending(every.jaxpr, tail) >= 1


@pytest.mark.parametrize("kind", ["plain", *sorted(DENSE)])
def test_the_dense_chunk_program_lowers_from_fewer_arguments(kind):
    """``benchmark/tools`` hand the program ``(params, ids, cache)`` or one
    scalar more: every token is real and the prompt ends in the chunk, so
    the token is the one the engine's own five arguments give."""
    engine = _dense_engine(kind)
    ids = _prompt(32)[None] % engine.cfg.vocab_size
    program = engine._get_prefill(32)
    toks = []
    for said in ((), (np.int32(32),), (np.int32(32), np.bool_(True))):
        cache = engine.model.dense_cache(1, engine._dense_S)
        out = program.lower(engine.params, ids, cache, *said).out_info
        assert (out[0].shape, out[0].dtype) == ((), jnp.int32)
        toks.append(int(program(engine.params, ids, cache, *said)[0]))
    assert toks[0] == toks[1] == toks[2]
    cache = engine.model.dense_cache(1, engine._dense_S)
    assert int(program(engine.params, ids, cache, np.int32(32),
                       np.bool_(False))[0]) == 0


def test_a_chunk_of_another_kind_is_refused_by_name():
    cfg, _ = _latent()
    with pytest.raises(ValueError, match="attn_kind='mha' only"):
        G._attend_prompt_pages(cfg, (), 0, None, None, None, None,
                               chunk=(0, 32))
