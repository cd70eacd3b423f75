"""The admission batch's row count is bucketed as its chunk length is
(``ServingEngine._batch_rows``, ``prefill_many``): a cycle's short prompts go
out as ``[rows, chunk]`` dispatches, and what reaches the pool and which token
each request gets are those of the one ``[num_slots, chunk]`` program the
engine used to dispatch, on the same inputs. CPU, float32, bit for bit: a row
of a prompt program meets no other row.

Three models pass through the same ladder: a plain stack, a looped one (the
rehearsal ``tiny-ouro-serve``: the stack runs four times, a cache layer a loop
and layer) and the latent routed one (``tiny-deepseek-v2-serve``: a latent page
pool, a router over held experts).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import manifest
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.inference.serving import engine as engine_mod
from deepspeed_tpu.inference.serving.buckets import bucket_for
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.profiling import trace

SLOTS, PAGE, CHUNK = 10, 8, 64
# chunk buckets 32 and 64; with 256 tokens a dispatch the ladders are
# (2, 4, 8) rows of 32 and (2, 4) rows of 64
TOKENS = 256
COUNTS = ["2", "3", "5", "top", "top+1", "num_slots"]


def _config(name):
    if name == "plain":
        return G.GPTConfig(vocab_size=64, d_model=32, n_layer=2, n_head=4,
                           max_seq_len=64)
    with open(os.path.join(manifest.ROOT, "configs", f"{name}.json")) as f:
        config = json.load(f)
    return manifest.family_of(config).config(config["model"])


def _engine(name):
    cfg = _config(name)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    return ServingEngine(cfg, params, ServingConfig(
        num_slots=SLOTS, page_size=PAGE, max_model_len=CHUNK,
        prefill_chunk=CHUNK, dtype="float32", decode_block=2))


@pytest.fixture(scope="module")
def small_dispatches():
    """``BATCH_TOKENS`` is sized for a chip; at this test's sizes the ladder
    has to end under ``num_slots`` to show what lies beyond its top."""
    patch = pytest.MonkeyPatch()
    patch.setattr(engine_mod, "BATCH_TOKENS", TOKENS)
    yield
    patch.undo()


@pytest.fixture(scope="module", params=["plain", "tiny-ouro-serve",
                                        "tiny-deepseek-v2-serve"])
def engine(request, small_dispatches):
    return _engine(request.param)


def _count(engine, name, chunk):
    top = engine._batch_rows(chunk)[-1]
    return {"top": top, "top+1": top + 1,
            "num_slots": engine.num_slots}.get(name) or int(name)


def _cycle(engine, n, longest, seed):
    """``n`` short prompts, each with pages of its own; every third borrows
    its first page (``start`` = a page)."""
    rng = np.random.default_rng(seed)
    pps = engine.serving.pages_per_seq
    items = []
    for j in range(n):
        t = rng.integers(1, 64, int(rng.integers(PAGE + 1, longest + 1)))
        items.append((j, t.astype(np.int32),
                      1 + j * pps + np.arange(pps, dtype=np.int32),
                      PAGE if j % 3 == 2 else 0))
    return items


def _fresh_pool(engine, seed=7):
    """A pool with something in every page, so that a write which should not
    have happened shows."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(engine.paged_cache))
    return {name: jax.random.normal(k, x.shape, x.dtype)
            for k, (name, x) in zip(keys, sorted(engine.paged_cache.items()))}


def _whole_slot_array(engine, items, chunk):
    """What ``prefill_many`` dispatched before the rows were bucketed."""
    s = engine.serving
    ids = np.zeros((engine.num_slots, chunk), np.int32)
    tables = np.zeros((engine.num_slots, s.pages_per_seq), np.int32)
    lengths = np.zeros(engine.num_slots, np.int32)
    starts = np.zeros(engine.num_slots, np.int32)
    for j, (_, t, row, start) in enumerate(items):
        ids[j, :len(t)], tables[j] = t, row
        lengths[j], starts[j] = len(t), start
    toks, pool, _ = engine._get_prefill_batch(chunk)(
        engine.params, jnp.asarray(ids), _fresh_pool(engine),
        jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(starts))
    return np.asarray(toks), jax.device_get(pool)


@pytest.mark.parametrize("count", COUNTS)
def test_a_cycle_gets_the_tokens_and_pages_of_the_whole_slot_array(
        engine, count):
    n = _count(engine, count, 32)
    items = _cycle(engine, n, longest=32, seed=n)
    want_toks, want_pool = _whole_slot_array(engine, items, 32)
    engine.paged_cache = _fresh_pool(engine)
    got = engine.prefill_many(items)
    assert [got[j] for j in range(n)] == want_toks[:n].tolist()
    pool = jax.device_get(engine.paged_cache)
    for name in want_pool:
        # page 0 is the sink: rows that hold no prompt point there
        assert np.array_equal(pool[name][:, :, 1:], want_pool[name][:, :, 1:])
    # one states array a dispatch, a row a prompt or a padded row
    ladder = engine._batch_rows(32)
    assert [s.shape[0] for s in engine.prefill_states] == [
        bucket_for(min(ladder[-1], n - at), ladder)
        for at in range(0, n, ladder[-1])]


def test_the_ladder_follows_the_chunk_and_the_slots(engine):
    assert engine._batch_rows(32) == (2, 4, 8)
    assert engine._batch_rows(64) == (2, 4)
    logged = [e["shape"] for e in engine.compile_log
              if e["kind"] == "serving_prefill_batch"]
    assert sorted(logged) == [(2, 32), (2, 64), (4, 32), (4, 64), (8, 32)]
    # a cycle whose longest prompt needs the wider chunk takes its ladder
    items = _cycle(engine, 5, longest=64, seed=3)
    items[0] = (0, np.ones(64, np.int32)) + items[0][2:]
    want_toks, want_pool = _whole_slot_array(engine, items, 64)
    engine.paged_cache = _fresh_pool(engine)
    got = engine.prefill_many(items)
    assert [got[j] for j in range(5)] == want_toks[:5].tolist()
    assert [s.shape[0] for s in engine.prefill_states] == [4, 2]
    pool = jax.device_get(engine.paged_cache)
    for name in want_pool:
        assert np.array_equal(pool[name][:, :, 1:], want_pool[name][:, :, 1:])


def test_fewer_slots_than_the_top_bucket_end_the_ladder():
    cfg = _config("plain")
    small = ServingEngine(cfg, G.init_params(cfg, jax.random.PRNGKey(0)),
                          ServingConfig(num_slots=3, page_size=PAGE,
                                        max_model_len=32, prefill_chunk=16,
                                        dtype="float32"))
    assert engine_mod.BATCH_TOKENS // 16 > 3
    assert small._batch_rows(16) == (2,)
    sink = np.zeros(small.serving.pages_per_seq, np.int32)
    t = np.ones(5, np.int32)
    assert len(small.prefill_many([(j, t, sink) for j in range(3)])) == 3
    assert [s.shape[0] for s in small.prefill_states] == [2, 2]


# ------------------------------------------------ nothing compiles afterwards
def _warm_as_the_harness_does(engine):
    """``benchmark/lib/mode_serve.warm_shapes``' call: two rows a short
    length, nothing wider."""
    sink = np.zeros(engine.serving.pages_per_seq, np.int32)
    for n in (20, 40, 64):
        t = np.zeros(n, np.int32)
        engine.prefill_many([(0, t, sink), (1, t, sink)])


WARM = {"warmup": ServingEngine.warmup,
        "the two-row call": _warm_as_the_harness_does}


@pytest.fixture(scope="module", params=sorted(WARM))
def warmed(request, small_dispatches):
    engine = _engine("plain")
    WARM[request.param](engine)
    return engine


@pytest.mark.parametrize("count", COUNTS)
def test_no_number_of_short_prompts_compiles_after_the_warm_calls(
        warmed, count):
    logged = len(warmed.compile_log)
    built = {c: fn._cache_size()
             for c, fn in warmed._prefill_batch_fns.items()}
    assert built == {32: 3, 64: 2}
    for chunk in (32, 64):
        n = _count(warmed, count, chunk)
        warmed.prefill_many(_cycle(warmed, n, longest=chunk, seed=n))
    assert len(warmed.compile_log) == logged
    assert {c: fn._cache_size()
            for c, fn in warmed._prefill_batch_fns.items()} == built


def test_every_dispatched_shape_answers_for_its_scopes(warmed):
    """One name, a registration a shape: a trace names a module, and
    ``program_scopes`` tries every live registration of the name for the one
    that compiles to it, so each bucket that ran has to be among them."""
    for chunk, ladder in ((32, (2, 4, 8)), (64, (2, 4))):
        name = f"prefill_batch_{chunk}"
        mine = [p for p in trace._live(name)
                if p.jitted() is warmed._prefill_batch_fns[chunk]]
        assert sorted(p.args[1].shape for p in mine) == [
            (rows, chunk) for rows in ladder]
        for prog in mine:
            trace._compile(prog, None)
            found = {trace.phase_of(v)[1] for v in prog.scopes.values()}
            assert found >= {"embed", "blocks", "attn", "mlp", "head_loss"}
        assert trace.program_scopes(name)
