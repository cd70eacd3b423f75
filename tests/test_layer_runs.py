"""``gpt.layer_runs`` is the one place that says what a layer is and where its
cache lies: for every configuration the benchmark serves or trains, at the
rehearsal sizes of ``benchmark/configs/tiny-*.json``, for the GPT-2 default
and for a looped stack with a window (no family's: the one configuration
whose rings are counted a pass), the runs partition the layers in order, each
stack's runs tile it,
the counts every cache is sized by are what ``init_cache`` and
``init_paged_cache`` allocate, and the tree ``init_params`` draws from key 0
is bit for bit the one recorded here (a rewrite of ``_init_kinds`` that moves
a key split fails here, not as a shifted ``expert_load_max`` on the chip).
"""

import glob
import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import manifest
from deepspeed_tpu.models import gpt as G
from served_contract import CONFIGS, config_file

# sha256 over (path, type, shape, bytes) of every leaf, by path; recorded at
# the parent of PR 45 (commit e4f3ca8)
DRAWN = {"tiny-deepseek-v2-serve": "164a29fd182e5889",
         "tiny-dots3-note-serve": "a32cc93e9c52b350",  # PR 51
         "tiny-laguna-serve": "6f09814e66a8899f",
         "tiny-moe-train": "1a9e000f8644746a",
         "tiny-nemotron-h-serve": "82844f51c77d2060",
         "tiny-falcon-h1-serve": "27b73027eea99d86",
         "tiny-kimi-linear-serve": "e28a4c09c6b9172e",  # PR 55
         "tiny-brumby-serve": "272fb6ec85d74af1",  # PR 58
         "tiny-longcat-flash-serve": "5f60d7a54a92ce69",  # PR 63
         "tiny-ouro-serve": "450e26e53fbba5e7",
         "tiny-serve": "09daf96e0d7e02cc",
         "tiny-train": "4b6dcd2115a80eb9",
         "the GPT-2 default": "ffca1acb6e70feb1",
         "a looped stack with a window": "5b925f8437117e57"}
# configurations of no family: name -> config
OWN = {"the GPT-2 default": G.GPTConfig(
           vocab_size=128, n_layer=3, n_head=2, d_model=32, max_seq_len=32),
       "a looped stack with a window": G.GPTConfig(
           vocab_size=128, n_layer=2, n_head=4, d_model=32, max_seq_len=64,
           attn_kind="gqa", n_kv_head=2, head_width=8, attn_window=8,
           rotary=True, norm="rmsnorm", linear_bias=False, mlp_gated=True,
           ut_steps=2)}


def test_every_rehearsal_configuration_is_held():
    assert sorted(DRAWN) == sorted(
        [os.path.basename(f)[:-5] for f in glob.glob(
            os.path.join(CONFIGS, "tiny-*.json"))] + list(OWN))


def _family(name):
    """(the family's module, its configuration object) of a case."""
    if name in OWN:
        return G, OWN[name]
    config = config_file(name)
    family = manifest.family_of(config)
    return family, family.config(dict(config["model"]))


@pytest.fixture(params=sorted(DRAWN))
def case(request):
    family, cfg = _family(request.param)
    # the expert-bank model (``gpt_moe``) walks its base's dense blocks
    return request.param, family, cfg, getattr(cfg, "base", cfg)


def test_the_runs_partition_the_layers_and_tile_each_stack(case):
    _, family, cfg, base = case
    runs = G.layer_runs(base)
    at, in_stack = 0, {}
    for r in runs:
        assert r.first == at and r.count >= 1
        assert r.offset == in_stack.get(r.name, 0)
        assert r.mixer in ("attn", "ssm", "kda", "retention", "attn+ssm",
                           "") and r.ffn in (
            "dense", "routed", "shortcut", "") and (r.mixer or r.ffn)
        assert r.subs == (2 if r.ffn == "shortcut" else 1)
        assert (r.attends, r.mixes) == ("attn" in r.mixer, "ssm" in r.mixer
                                        or r.mixer in ("kda", "retention"))
        assert not r.ring or r.mixer == "attn"
        at, in_stack[r.name] = at + r.count, r.offset + r.count
    assert at == base.n_layer
    assert dict(G.stack_names(base)) == in_stack
    if base is cfg:     # the tree holds a stack's every layer
        shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        for name, layers in in_stack.items():
            assert {a.shape[0] for a in jax.tree_util.tree_leaves(
                shapes[name])} == {layers}


def test_the_counts_are_what_the_caches_allocate(case):
    _, _, _, cfg = case
    runs = G.layer_runs(cfg)
    pages, rings = G.paged_layers(cfg)
    states = G.ssm_layers(cfg)
    assert pages + rings == G.cache_layers(cfg)
    # every cache layer of a kind is some layer's, in some pass, once; a
    # layer with both mixers has a page layer AND a state layer
    places = {"pages": [], "rings": [], "states": []}
    for r in runs:
        layers = range(r.first, r.first + r.count)
        if r.attends:
            places["rings" if r.ring else "pages"] += [
                r.cache_layer(i, u) + j for u in range(cfg.ut_steps)
                for i in layers for j in range(r.subs)]
        if r.mixes:
            places["states"] += [r.state_layer(i) for i in layers]
    assert {k: sorted(v) for k, v in places.items()} == {
        "pages": list(range(pages)), "rings": list(range(rings)),
        "states": list(range(states))}
    pool = jax.eval_shape(lambda: G.init_paged_cache(
        cfg, 9, 8, jnp.bfloat16, ring_slots=2))
    caches = (pool,)
    if cfg.attn_kind == "mla" and G.chunks_to_pages(cfg):
        # latent rows of several kinds: no dense cache of one row shape
        with pytest.raises(ValueError, match="attn_period="):
            G.init_cache(cfg, 2, 16, jnp.bfloat16)
    else:
        dense = jax.eval_shape(lambda: G.init_cache(cfg, 2, 16, jnp.bfloat16))
        assert {a.shape[0] for a in G.dense_caches(dense)} == {pages + rings}
        caches += (dense,)
    assert pool["k_pages"].shape[0] == pages
    assert (G.RING_KEYS[0] in pool) == bool(rings)
    assert (G.ring_rows(cfg, 8) > 0) == bool(rings) == bool(G.window_of(cfg))
    for key in (k for k in G.RING_KEYS if k in pool):
        assert pool[key].shape[0] == rings
        assert pool[key].shape[3] == G.ring_rows(cfg, 8)
        assert pool[key].shape[4] == G.cache_row(cfg, ring=True)[2]
    # index keys in pages beside the rows, and each slot's last selection
    selecting = G.index_layers(cfg)
    assert all((key in pool) == bool(selecting) for key in G.INDEX_KEYS)
    if selecting:
        assert pool[G.INDEX_KEYS[0]].shape[:4] == (selecting, 1, 9, 8)
        assert pool[G.INDEX_KEYS[1]].shape == (selecting, 2,
                                               G.index_topk_of(cfg))
    for cache in caches:
        assert (G.SSM_KEYS[0] in cache) == bool(states)
        for key in G.SSM_KEYS if states else ():
            assert cache[key].shape[0] == states


def test_the_tree_drawn_from_key_0_is_the_recorded_one(case):
    name, family, cfg, _ = case
    leaves = jax.tree_util.tree_flatten_with_path(
        family.init_params(cfg, jax.random.PRNGKey(0)))[0]
    h = hashlib.sha256()
    for path, leaf in sorted(((jax.tree_util.keystr(p), l)
                              for p, l in leaves), key=lambda t: t[0]):
        a = np.asarray(leaf)
        h.update(f"{path} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest()[:16] == DRAWN[name]


def test_a_run_with_two_mixers_names_a_page_layer_and_a_state_layer():
    """``ssm`` and no ``layer_pattern``: ONE run whose every layer attends
    and mixes, so it has two cache kinds at once; beside it, a pattern's runs
    keep one each, and a state layer is counted among the states alone."""
    _, cfg = _family("tiny-falcon-h1-serve")
    (run,) = G.layer_runs(cfg)
    assert (run.mixer, run.attends, run.mixes, run.ring) == (
        "attn+ssm", True, True, False)
    assert [(run.cache_layer(i, 0), run.state_layer(i))
            for i in range(cfg.n_layer)] == [(i, i)
                                             for i in range(cfg.n_layer)]
    assert (G.cache_layers(cfg), G.ssm_layers(cfg), G.paged_layers(cfg)) == (
        cfg.n_layer, cfg.n_layer, (cfg.n_layer, 0))
    _, pattern = _family("tiny-nemotron-h-serve")      # MEM*EME
    by_mixer = {}
    for r in G.layer_runs(pattern):
        by_mixer.setdefault(r.mixer, []).append(
            (r.first, r.cache_first, r.state_first))
    assert by_mixer == {"ssm": [(0, 0, 0), (2, 0, 1), (5, 1, 2)],
                        "attn": [(3, 0, 2)],
                        "": [(1, 0, 1), (4, 1, 2), (6, 1, 3)]}
    assert [r.state_layer(r.first) for r in G.layer_runs(pattern)
            if r.mixes] == [0, 1, 2]
    assert [r.cache_layer(r.first, 0) for r in G.layer_runs(pattern)
            if r.attends] == [0]


def test_a_layer_says_its_mixer_and_its_feed_forward():
    """``kda`` and ``kda_layers``: a layer is a KDA or a latent mixer AND a
    dense or a routed feed-forward, a stack a pairing; a KDA layer counts a
    state layer, a latent one a page layer, whatever its feed-forward."""
    _, cfg = _family("tiny-kimi-linear-serve")
    layers = [(r.name, r.offset + i, r.mixer, r.ffn,
               r.cache_layer(l, 0) if r.attends else None,
               r.state_layer(l) if r.mixes else None)
              for r in G.layer_runs(cfg)
              for i, l in enumerate(range(r.first, r.first + r.count))]
    assert layers == [
        ("blocks_kda", 0, "kda", "dense", None, 0),
        ("moe_blocks_kda", 0, "kda", "routed", None, 1),
        ("moe_blocks_kda", 1, "kda", "routed", None, 2),
        ("moe_blocks", 0, "attn", "routed", 0, None),
        ("moe_blocks_kda", 2, "kda", "routed", None, 3),
        ("moe_blocks_kda", 3, "kda", "routed", None, 4),
        ("moe_blocks_kda", 4, "kda", "routed", None, 5),
        ("moe_blocks", 1, "attn", "routed", 1, None),
        ("moe_blocks_kda", 5, "kda", "routed", None, 6)]
    assert (G.cache_layers(cfg), G.ssm_layers(cfg), G.paged_layers(cfg)) == (
        2, 7, (2, 0))
    assert G.state_mixer(cfg) is cfg.kda and G.ssm_bytes_per_slot(cfg) == \
        7 * cfg.kda.slot_bytes()
    assert dict(G.stack_names(cfg)) == {"blocks_kda": 1, "moe_blocks_kda": 6,
                                        "moe_blocks": 2}


def test_a_looped_stack_with_a_window_reads_the_rings_of_its_own_pass():
    """Pass ``u`` of layer ``i`` keeps ring layer ``n_layer * u + i``, so the
    pool holds ``ut_steps`` rings a window layer: a prompt straight to rings,
    then decode steps that wrap them, give the full forward's logits."""
    cfg = OWN["a looped stack with a window"]
    assert G.paged_layers(cfg) == (0, 4) and G.cache_layers(cfg) == 4
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        G.init_params(cfg, jax.random.PRNGKey(0)))
    ids = np.random.default_rng(0).integers(0, 128, (1, 24)).astype(np.int32)
    want = np.asarray(G.forward(cfg, params, jnp.asarray(ids), train=False))
    pool = G.init_paged_cache(cfg, 9, 8, jnp.float32, ring_slots=1)
    tables = jnp.arange(1, 4, dtype=jnp.int32)[None]
    first, pool, _ = G.paged_prefill_step(
        cfg, params, jnp.asarray(ids[:, :12]), pool, tables,
        jnp.asarray([12]), jnp.asarray([0]), jnp.asarray([0]))
    step = jax.jit(lambda token, pool, t: G.paged_decode_step(
        cfg, params, token, pool, tables, t, impl="gather"))
    for t in range(12, 24):
        logits, pool = step(jnp.asarray(ids[:, t]), pool, jnp.asarray([t]))
        assert np.abs(np.asarray(logits[0]) - want[0, t]).max() < 2e-5, t
