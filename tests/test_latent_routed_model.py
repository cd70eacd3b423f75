"""A model with latent attention and routed layers on the normal path
(``models/gpt.py`` with its attention sublayer, rotary scaling, router and
kinds of layer said as data; ``moe/dropless.py``; the latent page pool and
``paged_decode_mla``) against the benchmark's plain reference of those
equations, ``benchmark/reference/deepseek_v2_ref.py``.

Seeded random weights at the rehearsal configuration's size
(``benchmark/configs/tiny-deepseek-v2-serve.json``: a leading dense layer and
two routed ones, 16 experts in 4 groups of which 2 groups and 3 experts a
token, a shared expert, YaRN on, nope 16 / rope 8 / value 12), in float32 on
the CPU. ``TOL`` = 2e-5 on logits of size 1: both sides are float32 and sum
in another order; what was read is 2e-6 at most. A fault of the kinds planted
at the end moves a logit by 2e-4 or more (a router rounded to bf16, the
least of them; the others 1e-3 to 1).
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import deepseek_v2 as family
from benchmark.reference import deepseek_v2_ref as ref
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import decode_attention as DA

TOL = 2e-5
with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs", "tiny-deepseek-v2-serve.json")) as f:
    MODEL = json.load(f)["model"]
WHOLE = dict(MODEL, held_experts=[0, MODEL["n_routed_experts"]])
CFG = family.config(MODEL)          # experts 0-7 of 16 held
CFG_WHOLE = family.config(WHOLE)
PAGE = 16


def _moved(params, seed=8, by=0.05):
    """Every leaf off its initial value: unit gains would hide a norm applied
    with another layer's gain, and N(0, 0.02) router weights barely route."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + by * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def params():
    return _moved(G.init_params(CFG, jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def whole():
    return _moved(G.init_params(CFG_WHOLE, jax.random.PRNGKey(0)))


def _ids(n, t, seed=0):
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], (n, t)).astype(np.int32)


def test_the_parameter_tree_is_the_references(params):
    assert sorted(params) == ["blocks", "lm_head", "lnf_scale", "moe_blocks",
                              "wte"]
    attention = ["attn_out_w", "kv_a_norm_scale", "kv_a_w", "kv_b_w",
                 "ln1_scale", "ln2_scale", "q_a_norm_scale", "q_a_w", "q_b_w"]
    assert sorted(params["blocks"]) == sorted(
        attention + ["mlp_down_w", "mlp_gate_w", "mlp_up_w"])
    assert sorted(params["moe_blocks"]) == sorted(attention + [
        "router_w", "experts_gate_w", "experts_up_w", "experts_down_w",
        "shared_gate_w", "shared_up_w", "shared_down_w"])
    assert params["blocks"]["mlp_up_w"].shape == (1, 64, 96)
    assert params["moe_blocks"]["router_w"].shape == (2, 64, 16)
    assert params["moe_blocks"]["experts_gate_w"].shape == (2, 8, 64, 20)
    assert params["moe_blocks"]["shared_down_w"].shape == (2, 40, 64)
    assert params["blocks"]["kv_b_w"].shape == (1, 32, 4 * (16 + 12))
    matrices = sum(x.size for x in jax.tree_util.tree_leaves(params)
                   if x.ndim > 1 and x.shape[-1] > 1 and x.ndim >= 2
                   and not (x.ndim == 2 and x.shape[0] in (1, 2)))
    assert matrices == ref.held_params(MODEL)
    specs = G.partition_specs(CFG, None)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda _: 0, specs, is_leaf=lambda s: not isinstance(s, dict)))


def test_a_large_leaf_is_drawn_in_pieces_in_the_served_type(monkeypatch):
    monkeypatch.setattr(G, "_PIECE", 1 << 10)
    got = family.init_params(CFG, jax.random.PRNGKey(3))
    assert got["moe_blocks"]["experts_up_w"].dtype == jnp.bfloat16
    assert got["wte"].dtype == jnp.bfloat16
    assert got["lnf_scale"].dtype == jnp.float32
    flat = np.asarray(got["moe_blocks"]["experts_up_w"], np.float32)
    assert abs(flat.std() - 0.02) < 2e-3 and abs(flat.mean()) < 1e-3
    # no two pieces alike
    assert not np.array_equal(flat[0, 0], flat[0, 1])


@pytest.mark.parametrize("held", ["a share", "whole"])
def test_forward_logits_equal_the_references(held, params, whole):
    cfg, model, p = ((CFG, MODEL, params) if held == "a share"
                     else (CFG_WHOLE, WHOLE, whole))
    ids = _ids(2, 40)
    got = np.asarray(G.forward(cfg, p, jnp.asarray(ids), train=False))
    want = np.stack([ref.logits(model, p, row) for row in ids])
    assert np.abs(got - want).max() < TOL


def _engine(params, slots=4, chunk=32, dtype="float32"):
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    return ServingEngine(CFG, params, ServingConfig(
        num_slots=slots, page_size=PAGE, max_model_len=128,
        prefill_chunk=chunk, dtype=dtype, decode_block=2,
        kernel_impl="kernel"))


PATHS = {"fused": [20], "batch": [9, 30], "chunked": [70]}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_engines_prefill_then_decode_equal_the_full_forward(path, params):
    """Logits, not tokens: each prefill path (a prompt of one chunk straight
    to pages, two that share the admission batch, serial chunks through the
    dense latent cache and the scatter), then decode through the pages with
    the kernel (interpret mode here)."""
    engine = _engine(params)
    lens = PATHS[path]
    prompts = [row[:n] for row, n in zip(_ids(len(lens), 80, seed=5), lens)]
    pps = engine.serving.pages_per_seq
    tables = np.zeros((engine.num_slots, pps), np.int32)
    for j in range(len(lens)):
        tables[j] = 1 + j * pps + np.arange(pps)
    first = engine.prefill_many([(j, p, tables[j])
                                 for j, p in enumerate(prompts)])
    n = engine.num_slots
    lengths, nxt = np.zeros(n, np.int32), np.zeros(n, np.int32)
    active = np.zeros(n, bool)
    seqs = []
    for j, p in enumerate(prompts):
        lengths[j], nxt[j], active[j] = len(p), first[j], True
        seqs.append(list(p) + [int(first[j])])
    steps = 5
    for _ in range(steps):
        out = engine.decode(nxt.copy(), tables.copy(), lengths.copy(),
                            active, steps=1)
        lengths[active] += 1
        for j in range(len(lens)):
            nxt[j] = out[0, j]
            seqs[j].append(int(out[0, j]))
    assert engine.decode_routing.shape == (1, 4)
    logits, _, (chosen, counts) = G.paged_decode_step(
        CFG, engine.params, jnp.asarray(nxt), engine.paged_cache,
        jnp.asarray(tables), jnp.asarray(lengths), impl="kernel",
        return_routing=True)
    for j, p in enumerate(prompts):
        ids = np.asarray(seqs[j], np.int32)
        want = np.asarray(ref.logits(MODEL, params, ids))
        # the greedy tokens along the way, where the reference has no tie
        for t in range(len(p) - 1, len(ids) - 1):
            top = np.sort(want[t])[-2:]
            if top[1] - top[0] > 1e-4:
                assert ids[t + 1] == int(np.argmax(want[t])), (path, j, t)
        assert np.abs(np.asarray(logits[j]) - want[-1]).max() < TOL
        # the step's experts are the reference's own at that position
        own = np.asarray(ref.forward(MODEL, params, ids)[1])[-1]
        got = np.asarray(chosen[j])
        assert (got[0] == -1).all() and (own[0] == -1).all()
        assert [sorted(r) for r in got[1:].tolist()] == \
            [sorted(r) for r in own[1:].tolist()]
    live = int(active.sum())
    assert int(counts[0]) == live * 2 * MODEL["k"]
    assert 0 < int(counts[1]) <= int(counts[0])


def test_absorbed_attention_is_the_unabsorbed_function(params):
    """``_mla_attention`` both ways over the same cached rows, a chunk and a
    single token, against each other."""
    w = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    rng = np.random.default_rng(1)
    rows = jnp.asarray(rng.normal(size=(2, 24, CFG.latent_width)),
                       jnp.float32)
    for t in (1, 7):
        q = jnp.asarray(rng.normal(size=(2, t, 4, 24)), jnp.float32)
        positions = jnp.asarray([[23 - t + 1 + i for i in range(t)]] * 2)
        a = G._mla_attention(CFG, q, rows, w["kv_b_w"], positions, True)
        b = G._mla_attention(CFG, q, rows, w["kv_b_w"], positions, False)
        assert a.shape == (2, t, 4, 12)
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 2e-5
    # taken a group of heads at a time, the same
    old, G._MLA_SCORE_BYTES = G._MLA_SCORE_BYTES, 1
    try:
        c = G._mla_attention(CFG, q, rows, w["kv_b_w"], positions, False)
    finally:
        G._MLA_SCORE_BYTES = old
    assert np.abs(np.asarray(c) - np.asarray(b)).max() < 1e-6


KERNEL_CASES = {
    "batch of equal lengths": [40, 40, 40],
    "mixed lengths and an empty slot": [1, 0, 17, 64, 33],
    "a full table": [96, 5],
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
@pytest.mark.parametrize("stacked", [False, True])
def test_the_latent_kernel_equals_the_gather_fallback(case, stacked):
    lens = KERNEL_CASES[case]
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


def test_a_float32_activation_meets_a_bf16_matrix_in_two_passes():
    """``stream_float32``: ``_wm`` and the latent kernel give a float32
    activation 16 bits of mantissa against bf16 weights or rows; one pass (the activation rounded to bf16) is 100 times further
    from the float32 product."""
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


def test_a_float32_stream_over_bf16_weights_and_pages(params):
    """The served arrangement: bf16 weights and pages, the stream of the
    prompts' and the decode token's forwards in float32 (``stream_float32``).
    The pages stay bf16, the decode logits come back in float32, and the
    result stays the bf16 stream's to bf16's own accuracy (which of the two
    lies nearer the reference is a chip measurement: at this size the
    experts that flip between them decide it)."""
    served = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    ids = _ids(1, 41, seed=13)[0]
    tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    got = {}
    for name, cfg in (("float32", CFG), ("bf16", dataclasses.replace(
            CFG, stream_float32=False))):
        pool = G.init_paged_cache(cfg, 6, PAGE, jnp.bfloat16)
        first, pool, _ = G.paged_prefill_step(
            cfg, served, jnp.asarray(ids[None, :40]), pool, tables,
            jnp.asarray([40]), jnp.asarray([0]))
        logits, pool = G.paged_decode_step(
            cfg, served, jnp.asarray(ids[40:]), pool, tables,
            jnp.asarray([40]), impl="kernel")
        assert pool["k_pages"].dtype == first.dtype == jnp.bfloat16
        assert logits.dtype == (jnp.float32 if cfg.stream_float32
                                else jnp.bfloat16)
        got[name] = np.asarray(logits[0], np.float32)
    assert CFG.stream_float32 and CFG.linear_out_float32
    assert np.isfinite(got["float32"]).all()
    assert 0 < np.abs(got["float32"] - got["bf16"]).max() < 0.2


def test_a_latent_pool_of_another_shape_is_refused():
    q = jnp.zeros((2, 1, 4, 128))
    with pytest.raises(ValueError, match="latent pool"):
        DA.paged_decode_mla(q, jnp.zeros((2, 9, 16, 128)), jnp.ones(2),
                            jnp.zeros((2, 2), jnp.int32), rank=64,
                            softmax_scale=1.0)


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(whole):
    """The share test: the routed part of one layer's result that each of
    four chips gives from its quarter of the experts, summed, and the shared
    expert counted once, is the uncut reference's layer."""
    w = jax.tree_util.tree_map(lambda a: a[1], whole["moe_blocks"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 24, 64)),
                    jnp.float32)
    full, chosen = G._moe_delta(CFG_WHOLE, x, w)
    h = G.rms_norm(x, w["ln2_scale"], CFG.layer_norm_eps)[0]
    logits = h @ w["router_w"]
    picked, gates = dropless.route(logits, 3, 4, 2, CFG.moe_scale)
    assert np.array_equal(np.asarray(picked), np.asarray(chosen[0]))
    parts = []
    for first in range(0, 16, 4):
        held = (first, 4)
        parts.append(dropless.held_experts_ffn(
            h, picked, gates, w["experts_gate_w"][first:first + 4],
            w["experts_up_w"][first:first + 4],
            w["experts_down_w"][first:first + 4], held))
        # a share computes something, and not everything
        assert 0 < float(jnp.abs(parts[-1]).max())
    shared = G._mlp_on(CFG, h, w, "shared")
    total = sum(parts) + shared
    assert np.abs(np.asarray(total) - np.asarray(full[0])).max() < 2e-6
    # against the uncut reference's layer, its attention left out
    with jax.default_matmul_precision("highest"):
        g, _, _ = ref.route(WHOLE, h, w["router_w"],
                            jnp.zeros((24, 3), jnp.int32),
                            jnp.zeros((24,), bool))
        want = ref.held_experts(WHOLE, h, w, g) + ref.gated_mlp(
            h, w["shared_gate_w"], w["shared_up_w"], w["shared_down_w"])
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < TOL
    assert float(jnp.abs(parts[0] - want + shared).max()) > 1e-3


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
    member, top = ref.own_choice(MODEL, p)
    assert [sorted(row) for row in np.asarray(chosen).tolist()] == \
        [sorted(row) for row in np.asarray(top).tolist()]
    want = np.take_along_axis(np.asarray(p), np.asarray(chosen), 1) * 16.0
    assert np.abs(np.asarray(gates) - want).max() < 1e-6
    # inside two groups, and the handed own set shows no slack
    assert (np.asarray(member).reshape(-1, 4, 4).any(-1).sum(-1) <= 2).all()
    assert not np.asarray(ref.choice_slack(MODEL, jnp.asarray(r),
                                           member)).any()
    if logits == "ties":
        assert sorted(np.asarray(chosen[0]).tolist()) == [0, 1, 4]
        assert sorted(np.asarray(chosen[1]).tolist()) == [0, 1, 12]
        assert sorted(np.asarray(chosen[2]).tolist()) == [0, 1, 2]


def test_the_slack_asks_both_choices_and_refuses_what_the_rule_cannot_give(
        params):
    r = jnp.asarray(np.random.default_rng(9).normal(size=(1, 16)),
                    jnp.float32)
    member, top = ref.own_choice(MODEL, jax.nn.softmax(r, axis=-1))
    top = np.asarray(top[0])
    groups = sorted({int(e) // 4 for e in top})
    spread = float(jnp.std(r))

    def slack(experts):
        m = jnp.zeros((1, 16), bool).at[0, jnp.asarray(experts)].set(True)
        return float(ref.choice_slack(MODEL, r, m)[0])

    # an expert of a kept group left out for a weaker one of that group
    order = np.argsort(-np.asarray(r[0]))
    inside = [int(e) for e in order if int(e) // 4 in groups]
    swapped = inside[:2] + [inside[3]]
    assert abs(slack(swapped)
               - float(r[0, inside[2]] - r[0, inside[3]]) / spread) < 1e-5
    # a group left out for a weaker group
    score = np.asarray(r[0]).reshape(4, 4).max(-1)
    by_score = np.argsort(-score)
    weak = int(by_score[2])
    other = [int(e) for e in order if int(e) // 4 == weak][:1]
    kept = [int(e) for e in order if int(e) // 4 == int(by_score[0])][:2]
    assert slack(kept + other) >= float(
        score[by_score[1]] - score[weak]) / spread - 1e-5
    # three groups: no run of the rule gives that
    assert slack([0, 4, 8]) == float("inf")
    ids = _ids(1, 70)[0]
    own = np.asarray(ref.forward(MODEL, params, ids)[1])[69]
    out, got = ref.logits(MODEL, params, ids, positions=[69],
                          choices={69: own})
    assert not got[69].any() and got[69].shape == (3,)
    same = ref.logits(MODEL, params, ids, positions=[69])
    assert np.abs(np.asarray(out) - np.asarray(same)).max() < 1e-6
    names_dense = own.copy()
    names_dense[0] = [0, 1, 2]
    with pytest.raises(ValueError, match="dense layer"):
        ref.logits(MODEL, params, ids, choices={69: names_dense})


def test_one_function_sizes_every_cache():
    """``cache_row`` is the cache's kind at every sizing site, and the byte
    formula is held to the pool ``init_paged_cache`` really builds."""
    from deepspeed_tpu.runtime import aot

    assert G.cache_row(CFG) == (1, 1, 128) and CFG.latent_width == 128
    assert G.cache_row(G.PRESETS["tiny"]) == (2, 4, 16)
    real = family.config(json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs",
        "deepseek-v2-serve.json")))["model"])
    assert real.latent_width == 640 and G.cache_row(real) == (1, 1, 640)
    assert G.paged_kv_bytes_per_token(real) == 5 * 640 * 2
    for cfg in (CFG, G.PRESETS["tiny"]):
        pool = G.init_paged_cache(cfg, 9, PAGE, jnp.bfloat16)
        assert sorted(pool) == (["k_pages"] if cfg is CFG
                                else ["k_pages", "v_pages"])
        assert sum(a.nbytes for a in pool.values()) == \
            G.paged_kv_bytes_per_token(cfg, page_size=PAGE) * 9 * PAGE
        dense = G.init_cache(cfg, 2, 32, jnp.bfloat16)
        assert sum(a.nbytes for k, a in dense.items() if k != "pos") == \
            G.dense_kv_bytes(cfg, 2, 32)
    assert G.init_paged_cache(CFG, 9, PAGE)["k_pages"].shape == \
        (3, 1, 9, PAGE, 128)
    engine = _engine(G.init_params(CFG, jax.random.PRNGKey(0)))
    assert engine.kv_bytes_per_token() == 3 * 128 * 4
    assert sum(a.nbytes for a in engine.paged_cache.values()) == \
        engine.kv_bytes_per_token() * engine.num_pages * PAGE
    spec = aot.speculation_hbm_bytes("tiny", draft_model=CFG, num_slots=2,
                                     max_model_len=32, spec_k=2)
    assert spec["parts"]["draft_cache"] == 3 * 2 * 32 * 128 * 2
    # the algorithm's count is the reference's: the pool pads the row
    assert ref.kv_bytes_per_token(MODEL) == 3 * (32 + 8) * 2


def _engine_with(**serving):
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    def build():
        return ServingEngine(
            CFG, G.init_params(CFG, jax.random.PRNGKey(0)), ServingConfig(
                num_slots=2, page_size=PAGE, max_model_len=64,
                prefill_chunk=16, dtype="float32", **serving))
    return build


def _export():
    engine = _engine_with()()
    engine.export_pages([1])


def _verify():
    p = G.init_params(CFG, jax.random.PRNGKey(0))
    G.paged_verify_step(CFG, p, jnp.zeros((2, 3), jnp.int32),
                        G.init_paged_cache(CFG, 9, PAGE),
                        jnp.zeros((2, 4), jnp.int32), jnp.zeros(2, jnp.int32))


def _pipe():
    from deepspeed_tpu.models import gpt_pipe

    gpt_pipe.build(CFG, 2, 2)


REFUSALS = {
    "tp": (_engine_with(tp=2), "attn_kind"),
    "kv8 pool": (_engine_with(kv_bits=8), "attn_kind"),
    "kv4 pool": (_engine_with(kv_bits=4), "attn_kind"),
    "prefix cache": (_engine_with(enable_prefix_cache=True), "attn_kind"),
    "page fingerprints": (_engine_with(page_fingerprints=True), "attn_kind"),
    "a drafter": (_engine_with(spec_drafter="ngram"), "attn_kind"),
    "a prefill role": (_engine_with(role="prefill"), "attn_kind"),
    "page export": (_export, "attn_kind"),
    "verify": (_verify, "attn_kind"),
    "a quantized stack": (lambda: G.quantize_for_inference(
        CFG, G.init_params(CFG, jax.random.PRNGKey(0))), "attn_kind"),
    "GPTStream": (lambda: G.GPTStream(CFG), "attn_kind"),
    "gpt_pipe": (_pipe, "attn_kind"),
}


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_a_path_that_does_not_carry_the_kinds_refuses_by_the_fields_name(
        path):
    call, field = REFUSALS[path]
    with pytest.raises(ValueError, match=f"{field}="):
        call()


def test_each_kind_field_alone_is_named_and_a_wrong_one_is_refused():
    tiny = G.PRESETS["tiny"]
    routed = dataclasses.replace(
        tiny, norm="rmsnorm", linear_bias=False, mlp_gated=True,
        moe_experts=4, moe_k=1, moe_d_ff=8)
    for cfg, name in [(CFG, "attn_kind"), (routed, "moe_experts"),
                      (dataclasses.replace(routed, moe_held=(0, 2)),
                       "moe_experts")]:
        with pytest.raises(ValueError, match=f"{name}="):
            G.require_default_block(cfg, "here", G.KIND_FIELDS)
    G.require_default_block(tiny, "here", G.KIND_FIELDS)
    # a looped dense model passes the kinds' check and not the block's
    G.require_default_block(dataclasses.replace(tiny, ut_steps=2), "here",
                            G.KIND_FIELDS)
    for wrong in (dict(moe_held=(12, 8)), dict(moe_k=9),
                  dict(moe_topk_groups=5), dict(moe_dense_layers=3),
                  dict(linear_bias=True), dict(qk_rope_dim=7),
                  dict(attn_kind="mqa")):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **wrong)


def test_yarn_frequencies_and_scales_are_the_published_ones():
    published = G.YarnScaling(factor=40, original_max_len=4096, beta_fast=32,
                              beta_slow=1, mscale=0.707, mscale_all_dim=0.707)
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert abs(m - 1.2608) < 1e-4
    assert abs(published.softmax_factor - m * m) < 1e-12
    assert published.cos_sin_factor == 1.0
    freqs = published.inv_freq(32, 10000.0)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    # fast dimensions keep their frequency, slow ones are interpolated
    assert np.allclose(freqs[:10], plain[:10], rtol=1e-6)
    assert np.allclose(freqs[-8:], plain[-8:] / 40.0, rtol=1e-6)
    assert (np.diff(freqs) < 0).all()
    real = dict(MODEL, qk_rope_head_dim=64, rope_scaling=dict(
        MODEL["rope_scaling"], original_max_position_embeddings=4096))
    assert np.allclose(freqs, ref.yarn_inv_freq(real), rtol=1e-6)
    assert abs(ref.softmax_scale(dict(real, qk_nope_head_dim=128))
               - 192 ** -0.5 * m * m) < 1e-9
    assert abs(G._softmax_scale(CFG) - ref.softmax_scale(MODEL)) < 1e-9


# ------------------------------------------------------------ planted faults
def _bf16_router(monkeypatch):
    route = dropless.route
    monkeypatch.setattr(dropless, "route", lambda logits, *a, **kw: route(
        logits.astype(jnp.bfloat16).astype(jnp.float32), *a, **kw))
    return CFG


def _unnormed_latent(monkeypatch):
    norm = G.rms_norm
    monkeypatch.setattr(G, "rms_norm", lambda x, scale, eps: (
        x if scale.shape[-1] == CFG.kv_lora_rank else norm(x, scale, eps)))
    return CFG


def _renormalised_gates(monkeypatch):
    def route(logits, k, groups, topk_groups, scale, norm_topk=False):
        probs = jax.nn.softmax(logits, axis=-1)
        chosen = dropless.group_limited_topk(probs, k, groups, topk_groups)
        gates = jnp.take_along_axis(probs, chosen, axis=1)
        return chosen, gates / gates.sum(-1, keepdims=True) * scale
    monkeypatch.setattr(dropless, "route", route)
    return CFG


FAULTS = {
    "a router in bf16": _bf16_router,
    "no scaling factor": lambda mp: dataclasses.replace(CFG, moe_scale=1.0),
    "no shared expert": lambda mp: dataclasses.replace(CFG,
                                                       moe_shared_d_ff=0),
    "an un-normalised latent": _unnormed_latent,
    "a wrong YaRN scale": lambda mp: dataclasses.replace(
        CFG, rope_scaling=dataclasses.replace(CFG.rope_scaling,
                                              mscale_all_dim=0.0)),
    "renormalised gates": _renormalised_gates,
}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["a cache in bf16"])
def test_a_planted_fault_fails_the_comparison(fault, params, monkeypatch):
    """Each fault once, through prefill into pages and a decode step, under
    the step's own experts: the honest path passes ``TOL``, the fault does
    not."""
    ids = _ids(1, 41, seed=11)[0]
    tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

    def served(cfg, pool_dtype=jnp.float32):
        pool = G.init_paged_cache(cfg, 6, PAGE, pool_dtype)
        _, pool, _ = G.paged_prefill_step(
            cfg, params, jnp.asarray(ids[None, :40]), pool, tables,
            jnp.asarray([40]), jnp.asarray([0]))
        logits, _, (chosen, _) = G.paged_decode_step(
            cfg, params, jnp.asarray(ids[40:]), pool, tables,
            jnp.asarray([40]), impl="gather", return_routing=True)
        want, slack = ref.logits(MODEL, params, ids, positions=[40],
                                 choices={40: np.asarray(chosen[0])})
        return float(np.abs(np.asarray(logits[0]) - np.asarray(want[0])
                            ).max()), float(slack[40].max())

    honest, slack = served(CFG)
    assert honest < TOL and slack == 0.0
    if fault == "a cache in bf16":
        read = served(CFG, jnp.bfloat16)[0]
    else:
        read = served(FAULTS[fault](monkeypatch))[0]
    print(f"{fault}: {read:.3g} for the honest {honest:.3g}")
    assert read > 5 * TOL
