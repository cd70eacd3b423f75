"""``ops/pallas/index_scores``: the indexer's scores over the index keys
where they lie (pages under a shuffled block table), in interpret mode,
against the plain form ``models/gpt._index_scores`` over the same keys
gathered into one array: a decode step's body (one query a slot, slots of
every kind of length in one call) and a chunk's (tiles of queries, a request
whose live keys end inside a page and inside a group), float32 pools at
float32's noise (so that a dropped pass of the six fails) and bf16 pools in
one pass; ``-inf`` exactly at and past a row's live keys.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.ops.pallas import decode_attention as DA
from deepspeed_tpu.ops.pallas import index_scores as IX

PS, HEADS, DIM, LAYERS = 8, 4, 32, 3


def _laid(rng, lens, width, dtype, T):
    """Keys of ``lens`` rows laid through a shuffled table ``width`` pages
    wide into layer 1 of a pool whose other layers and free pages hold
    other numbers; queries, weights; the keys in order [B, S, DIM]."""
    B = len(lens)
    pool = rng.normal(size=(LAYERS, 1, B * width + 1, PS, DIM))
    tables = (rng.permutation(B * width) + 1).reshape(B, width)
    keys = pool[1, 0][tables].reshape(B, width * PS, DIM)
    q = rng.normal(size=(B, T, HEADS, DIM))
    weights = rng.normal(size=(B, T, HEADS)) / HEADS
    as32 = lambda a: jnp.asarray(a, jnp.float32)    # noqa: E731
    return (as32(q), as32(weights), jnp.asarray(pool, dtype),
            jnp.asarray(tables, jnp.int32), jnp.asarray(keys, dtype))


def _check(got, q, weights, keys, lens, dtype):
    want = np.asarray(G._index_scores(q.astype(dtype), weights, keys))
    got = np.asarray(got)
    assert got.shape == want.shape and got.dtype == np.float32
    live = np.broadcast_to(
        (np.arange(keys.shape[1])[None, :] < np.asarray(lens)[:, None]
         )[:, None], got.shape)
    assert (got[~live] == -np.inf).all()
    assert np.isfinite(got[live]).all()
    # float32's noise over sums of DIM x HEADS products; one bf16 pass of
    # six dropped reads 1e-3 of the spread and more
    far = np.abs(got[live] - want[live]).max()
    assert far <= 1e-5 * want[live].std(), (far, want[live].std())


# one query a slot: empty, one key, inside a page, whole pages, the table's
# full width (6 pages: a group of 4 and one of 2)
DECODE_LENS = [0, 1, 5, 16, 48]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("keys_a_step", [32, 16, 512],
                         ids=["groups of 4", "groups of 2", "one group"])
def test_a_decode_step_scores_every_slots_live_keys(dtype, keys_a_step,
                                                    monkeypatch):
    monkeypatch.setattr(IX, "_KEYS", keys_a_step)
    rng = np.random.default_rng(0)
    q, weights, pool, tables, keys = _laid(rng, DECODE_LENS, 6, dtype, 1)
    lens = jnp.asarray(DECODE_LENS, jnp.int32)
    got = jax.jit(IX.index_scores)(q, weights, pool, lens, tables,
                                  jnp.int32(1))
    _check(got, q, weights, keys, DECODE_LENS, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("T, lens, tile", [
    (24, [77], 16),         # ends inside a page, inside a group of 4
    (24, [96], 8),          # a whole number of groups
    (40, [300, 37], 16),    # two requests; tiles of queries, one padded
    (3, [13], 512),         # the module's own tile, cut to whole lanes
], ids=["inside a page", "whole groups", "two requests", "own tile"])
def test_a_chunk_scores_its_requests_live_keys(dtype, T, lens, tile,
                                               monkeypatch):
    monkeypatch.setattr(IX, "_KEYS", 32)
    monkeypatch.setattr(IX, "_QUERIES", tile)
    monkeypatch.setattr(IX, "_LANES", min(tile, 128))
    rng = np.random.default_rng(1)
    q, weights, pool, tables, keys = _laid(rng, lens, 40, dtype, T)
    got = jax.jit(IX.index_scores)(
        q, weights, pool, jnp.asarray(lens, jnp.int32), tables, jnp.int32(1))
    _check(got, q, weights, keys, lens, dtype)


@pytest.mark.parametrize("T", [1, 20], ids=["a decode step", "a chunk"])
def test_a_handed_work_list_is_walked_and_a_wrong_one_refused(T):
    """A work list the caller built is walked as the call's own; one of
    another group's size, and a pool of another form, are refused."""
    rng = np.random.default_rng(2)
    lens = [9, 40][:1 if T > 1 else 2]
    q, weights, pool, tables, keys = _laid(rng, lens, 5, jnp.float32, T)
    lens_d = jnp.asarray(lens, jnp.int32)
    group = IX.index_pages_per_step(PS, 5)
    work = DA.paged_work_list(lens_d, tables, PS, group)
    got = IX.index_scores(q, weights, pool, lens_d, tables, 1, work=work)
    _check(got, q, weights, keys, lens, jnp.float32)
    with pytest.raises(ValueError, match="a step of this call takes"):
        IX.index_scores(q, weights, pool, lens_d, tables, 1,
                        work=DA.paged_work_list(lens_d, tables, PS, 2))
    with pytest.raises(ValueError, match="an index-key pool is"):
        IX.index_scores(q, weights, pool[1], lens_d, tables, 1)


def test_the_call_sites_form_is_the_kernels_off_and_on():
    """``models/gpt._index_page_scores``: the plain form over gathered keys
    and the kernel give one answer, ``-inf`` at the same places."""
    rng = np.random.default_rng(3)
    lens = [0, 30, 48]
    q, weights, pool, tables, _ = _laid(rng, lens, 6, jnp.float32, 1)
    lens_d = jnp.asarray(lens, jnp.int32)
    plain, kernel = (np.asarray(G._index_page_scores(
        q, weights, pool, jnp.int32(1), tables, lens_d, how))
        for how in (False, True))
    assert (np.isfinite(plain) == np.isfinite(kernel)).all()
    seen = np.isfinite(plain)
    assert np.abs(plain[seen] - kernel[seen]).max() <= 1e-5 * plain[seen].std()
