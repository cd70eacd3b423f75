"""The writers of prompt keys and values into a dense page pool, each held to
a plain numpy loop (``pages_by_hand``): ``write_prompt_kv_batch`` (what the
engine's ``jit_scatter`` runs after the last chunk of a long prompt), its tp
wrapper on four virtual devices, and ``paged_prefill_step`` (short prompts,
written in the layer loop). All three end in ``gpt._write_prompt_pages``.

And the property the TPU compiler's layout choice hangs on, read from the
jaxpr of the engine's scatter program where no chip is at hand: the pool is
scattered in [piece, Dh] windows with layer, head, page and piece all named,
and nothing of the pool's size exists beside the carried pool.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt as G
from pages_by_hand import pages_by_hand

TOL = 2e-5     # float32 both sides: test_looped_model.py says what it covers
PLAIN = G.PRESETS["tiny"]                         # 2 layers, 4 heads of 16
LOOPED = dataclasses.replace(                     # 2 layers x 3 passes
    PLAIN, ut_steps=3, loop_norm=True, norm="rmsnorm", rotary=True,
    rotary_pct=1.0, post_norm=True)

# S: the dense cache's (or the padded prompt's) length; page: the pool's page
# size; tables: a row of page ids a prompt row, out of order
CASES = {
    "s_a_multiple_of_the_page": dict(
        S=32, page=8, tables=[[5, 2, 7, 9], [8, 1, 4, 3]],
        lengths=[32, 19], starts=[0, 0]),
    "gcd_of_s_and_page_under_the_page": dict(      # gcd(40, 16) = 8
        S=40, page=16, tables=[[5, 2, 7], [6, 1, 4]],
        lengths=[40, 21], starts=[0, 0]),
    "scratch_wider_than_the_table": dict(          # 48 positions, 32 in pages
        S=48, page=8, tables=[[5, 2, 7, 9], [8, 1, 4, 3]],
        lengths=[32, 9], starts=[0, 0]),
    "start_inside_a_page": dict(
        S=32, page=8, tables=[[5, 2, 7, 9], [8, 1, 4, 3]],
        lengths=[30, 32], starts=[11, 8]),
    "a_row_of_length_0": dict(
        S=32, page=8, tables=[[5, 2, 7, 9], [8, 1, 4, 3], [0, 0, 0, 0]],
        lengths=[0, 13, 0], starts=[0, 0, 0]),
    "more_cache_layers_than_n_layer": dict(
        cfg=LOOPED, S=24, page=16, tables=[[3, 1], [2, 5]],
        lengths=[24, 17], starts=[0, 16]),
}
PAGES = 11


def _setup(case, seed):
    c = CASES[case]
    cfg = c.get("cfg", PLAIN)
    rng = np.random.default_rng(seed)
    shape = G.init_paged_cache(cfg, PAGES, c["page"],
                               jnp.float32)["k_pages"].shape
    # a pool that holds something everywhere: what is not written must stay
    pool = {side: rng.standard_normal(shape).astype(np.float32)
            for side in ("k_pages", "v_pages")}
    return (cfg, c["S"], pool, np.asarray(c["tables"], np.int32),
            np.asarray(c["lengths"], np.int32),
            np.asarray(c["starts"], np.int32), rng)


def _random_dense(cfg, rows, S, rng):
    shape = (G.cache_layers(cfg), rows, cfg.n_head, S, cfg.head_dim)
    return {side: rng.standard_normal(shape).astype(np.float32)
            for side in ("k", "v")}


def _scatter(cfg, S, pool, tables, lengths, starts, rng):
    dense = _random_dense(cfg, len(lengths), S, rng)
    got = G.write_prompt_kv_batch(
        jax.tree_util.tree_map(jnp.asarray, pool), dense,
        jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(starts))
    return dense, got, 0.0


def _scatter_tp4(cfg, S, pool, tables, lengths, starts, rng):
    from jax.sharding import Mesh

    from deepspeed_tpu.inference.serving.tp import (
        TP_AXIS, tp_write_prompt_kv_batch)

    dense = _random_dense(cfg, len(lengths), S, rng)
    mesh = Mesh(np.asarray(jax.devices()[:4]), (TP_AXIS,))  # a head a device
    got = tp_write_prompt_kv_batch(
        jax.tree_util.tree_map(jnp.asarray, pool), dense, tables, lengths,
        starts, mesh)
    return dense, got, 0.0


def _prefill(cfg, S, pool, tables, lengths, starts, rng):
    params = G.init_params(cfg, jax.random.PRNGKey(3))
    ids = rng.integers(0, cfg.vocab_size, (len(lengths), S)).astype(np.int32)
    _, dense = G.forward_with_cache(
        cfg, params, jnp.asarray(ids),
        G.init_cache(cfg, len(lengths), S, jnp.float32))
    _, got, _ = G.paged_prefill_step(
        cfg, params, jnp.asarray(ids),
        jax.tree_util.tree_map(jnp.asarray, pool), jnp.asarray(tables),
        jnp.asarray(lengths), jnp.asarray(starts))
    return dense, got, TOL


WRITERS = {"scatter": _scatter, "scatter_tp4": _scatter_tp4,
           "prefill_to_pages": _prefill}


@pytest.mark.parametrize("writer", sorted(WRITERS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_writer_puts_a_prompt_where_the_loop_by_hand_puts_it(case, writer):
    """Positions ``start <= pos < length`` of every row, layer and head land
    at ``[table[pos // page], pos % page]``; every other place of the pool
    holds what it held, bit for bit. The scatter moves values, so it equals
    the loop exactly; the prefill computes them in another program than the
    dense forward, so within ``TOL``."""
    cfg, S, pool, tables, lengths, starts, rng = _setup(
        case, sorted(CASES).index(case))
    dense, got, tol = WRITERS[writer](cfg, S, pool, tables, lengths, starts,
                                      rng)
    assert set(got) == {"k_pages", "v_pages"}
    for side in ("k", "v"):
        want, written = pages_by_hand(pool[f"{side}_pages"], dense[side],
                                      tables, lengths, starts)
        have = np.asarray(got[f"{side}_pages"])
        assert written.any()
        assert np.array_equal(have[~written], want[~written])
        assert np.abs(have[written] - want[written]).max() <= tol


def _eqns(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _eqns(inner, found)
    return found


@pytest.mark.parametrize("cfg", [PLAIN, LOOPED], ids=["plain", "looped"])
def test_the_scatter_program_names_every_index_and_holds_one_pool(cfg):
    """``jit_scatter`` as the engine builds it. Every ``scatter`` into
    something of the pool's size updates [piece, Dh] windows: the other four
    dimensions (layer, head, page, piece in the page) are all scattered
    indices. With layer and head left in the window the TPU compiler re-lays
    the pool head-minor and back (four copies of 3.03 GB at 481 pages:
    PERF.md, PR 33). And the pool is the carry of the loop over the cache
    layers: only the loop, the scatters and reshapes (views) put out
    anything of its size."""
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    engine = ServingEngine(cfg, G.init_params(cfg, jax.random.PRNGKey(0)),
                           ServingConfig(num_slots=2, num_pages=PAGES,
                                         page_size=8, max_model_len=40,
                                         prefill_chunk=16, dtype="float32"))
    layers = G.cache_layers(cfg)
    dense = G.init_cache(cfg, 1, engine._dense_S, jnp.float32)
    pool_shape = engine.paged_cache["k_pages"].shape
    size = int(np.prod(pool_shape))
    assert pool_shape == (layers, 4, PAGES, 8, 16)
    assert dense["k"].shape == (layers, 1, 4, 48, 16) and dense["k"].size != size
    jaxpr = jax.make_jaxpr(engine._get_scatter())(
        engine.paged_cache, dense, jnp.zeros(5, jnp.int32), jnp.int32(33),
        jnp.int32(0))
    eqns = _eqns(jaxpr.jaxpr, [])
    scatters = [e for e in eqns if e.primitive.name.startswith("scatter")
                and e.invars[0].aval.size == size]
    assert len(scatters) == 2                            # keys and values
    for eqn in scatters:
        operand, _, updates = (v.aval.shape for v in eqn.invars)
        dims = eqn.params["dimension_numbers"]
        assert operand == (layers, 4, PAGES, 1, 8, 16)   # gcd(48, 8) = 8
        assert tuple(dims.inserted_window_dims) == (0, 1, 2, 3)
        assert tuple(dims.scatter_dims_to_operand_dims) == (0, 1, 2, 3)
        assert [updates[d] for d in dims.update_window_dims] == [8, 16]
    loops = [e for e in eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in loops] == [layers]
    first, n = loops[0].params["num_consts"], loops[0].params["num_carry"]
    carried = [v.aval.shape for v in loops[0].invars[first:first + n]]
    assert carried.count(pool_shape) == 2
    assert [v.aval.shape for v in loops[0].outvars].count(pool_shape) == 2
    makers = {e.primitive.name for e in eqns
              for v in e.outvars if v.aval.size == size}
    assert makers <= {"scatter", "reshape", "scan", "jit", "pjit"}, makers
