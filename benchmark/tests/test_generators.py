"""The generators: the multiset of lengths never depends on the seed."""

import collections

import numpy as np
import pytest

from benchmark.generators import closed_grid, token_batches

GRID = {"prompt_lens": [64, 96, 128], "output_lens": [16, 32], "stagger_cap": 8}


def lengths(params, seed, n):
    return closed_grid.Traffic(params, 1000, seed).lengths(n)


def test_by_seed_same_multiset_other_order():
    a, b = lengths(GRID, 1, 18), lengths(GRID, 2 ** 31 + 7, 18)
    assert collections.Counter(a) == collections.Counter(b)
    assert a != b
    for cycle in (a[:6], a[6:12], a[12:]):     # each cycle is the whole grid
        assert sorted(cycle) == sorted(
            (p, o) for p in GRID["prompt_lens"] for o in GRID["output_lens"])


def test_fixed_order_same_order_other_tokens():
    params = dict(GRID, order="fixed", order_seed=5)
    assert lengths(params, 1, 18) == lengths(params, 2, 18)
    t1 = closed_grid.Traffic(params, 1000, 1)
    t2 = closed_grid.Traffic(params, 1000, 2)
    (p1, o1), (p2, o2) = t1.next(), t2.next()
    assert len(p1) == len(p2) and o1 == o2 and not np.array_equal(p1, p2)
    f1, f2 = t1.first_fill(4), t2.first_fill(4)
    assert [(len(p), o) for p, o in f1] == [(len(p), o) for p, o in f2]


def test_same_seed_same_requests():
    a = closed_grid.Traffic(GRID, 1000, 3_000_000_019)
    b = closed_grid.Traffic(GRID, 1000, 3_000_000_019)
    for _ in range(7):
        (pa, oa), (pb, ob) = a.next(), b.next()
        assert oa == ob and np.array_equal(pa, pb)


def test_first_fill_is_staggered_and_short():
    t = closed_grid.Traffic(GRID, 1000, 4)
    cuts = [o for _, o in t.first_fill(64)]
    assert min(cuts) >= 1 and max(cuts) <= GRID["stagger_cap"]
    assert len(set(cuts)) > 3
    assert t.prompt_lengths() == [64, 96, 128]


def test_unknown_order_is_an_error():
    with pytest.raises(ValueError):
        closed_grid.Traffic(dict(GRID, order="random"), 1000, 1)


def test_token_batches_shape_and_law():
    params = {"seq_len": 32, "micro_batch_per_chip": 3, "skew": 3.0}
    a = token_batches.Traffic(params, 500, 1)
    b = token_batches.Traffic(params, 500, 2)
    xa, xb = a.batch(4), b.batch(4)
    assert xa.shape == xb.shape == (12, 32) and xa.dtype == np.int32
    assert not np.array_equal(xa, xb)
    assert 0 <= xa.min() and xa.max() < 500
    # the skewed law: half of the ids fall under V / 8, whatever the seed
    big_a = token_batches.Traffic(dict(params, seq_len=4096), 500, 1).batch(4)
    big_b = token_batches.Traffic(dict(params, seq_len=4096), 500, 9).batch(4)
    for x in (big_a, big_b):
        assert abs(float(np.mean(x < 500 / 8)) - 0.5) < 0.02


def test_sample_batch_repeats_a_few_sequences():
    t = token_batches.Traffic({"seq_len": 16, "micro_batch_per_chip": 2}, 50, 1)
    x = t.sample_batch(4, 4)
    assert x.shape == (8, 16)
    assert np.array_equal(x[:4], x[4:])
