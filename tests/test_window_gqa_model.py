"""A model with fewer key-value heads than query heads, window and full
attention layers in a period (a head count, a rotary set and a mask a kind), a
gate a head and routed layers on the normal path (``models/gpt.py`` with those
said as data; the page pool for the full layers and a ring a slot for the
window layers; ``paged_decode_gqa``; ``moe/dropless.py`` with renormalised
gates) against the benchmark's plain reference of those equations,
``benchmark/reference/laguna_ref.py``.

Seeded random weights at the rehearsal configuration's size
(``benchmark/configs/tiny-laguna-serve.json``: d 64, 2 key-value heads for 4
query heads in a full layer and 6 in a window layer, window 8, a leading
dense layer and four routed ones of 16 experts, 4 a token), in float32 on the
CPU. ``TOL`` = 2e-5 on logits of size 1: both sides are float32 and sum in
another order; what was read is 1e-6 at most. Prompts of 5 (inside the
window), 20 and 30 (past it and past the ring) and 70 (serial chunks); pages
of 8 (a ring of exactly the window) and of 16 (a ring of 16 rows of which a
step reads the 8 inside the window).
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import laguna as family
from benchmark.reference import laguna_ref as ref
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import decode_attention as DA

TOL = 2e-5
CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs")
with open(os.path.join(CONFIGS, "tiny-laguna-serve.json")) as f:
    MODEL = json.load(f)["model"]
CFG = family.config(MODEL)
WINDOW = MODEL["sliding_window"]


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


def _ids(n, t, seed=0):
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], (n, t)).astype(np.int32)


def test_the_parameter_tree_is_the_references(params):
    assert sorted(params) == ["blocks_full", "lm_head", "lnf_scale",
                              "moe_blocks_full", "moe_blocks_window", "wte"]
    attention = ["attn_gate_w", "attn_out_w", "kv_w", "ln1_scale",
                 "ln2_scale", "q_w"]
    assert sorted(params["blocks_full"]) == sorted(
        attention + ["mlp_down_w", "mlp_gate_w", "mlp_up_w"])
    for name, layers, heads in (("moe_blocks_window", 3, 6),
                                ("moe_blocks_full", 1, 4)):
        stack = params[name]
        assert sorted(stack) == sorted(attention + [
            "router_w", "experts_gate_w", "experts_up_w", "experts_down_w",
            "shared_gate_w", "shared_up_w", "shared_down_w"])
        assert stack["q_w"].shape == (layers, 64, heads * 16)
        assert stack["kv_w"].shape == (layers, 64, 2 * 2 * 16)
        assert stack["attn_gate_w"].shape == (layers, 64, heads)
        assert stack["attn_out_w"].shape == (layers, heads * 16, 64)
        assert stack["experts_gate_w"].shape == (layers, 16, 64, 20)
    assert [ref.place(MODEL, l) for l in range(5)] == [
        ("blocks_full", 0), ("moe_blocks_window", 0),
        ("moe_blocks_window", 1), ("moe_blocks_window", 2),
        ("moe_blocks_full", 0)]
    runs = G.layer_runs(CFG)
    assert [(r.name, r.offset, r.count, r.first, r.cache_first, r.ring)
            for r in runs] == [
        ("blocks_full", 0, 1, 0, 0, False),
        ("moe_blocks_window", 0, 3, 1, 0, True),
        ("moe_blocks_full", 0, 1, 4, 1, False)]
    matrices = sum(v.size for k, v in jax.tree_util.tree_leaves_with_path(
        params) if not str(k[-1]).endswith("_scale']"))
    assert matrices == ref.held_params(MODEL)


def test_a_deeper_model_walks_its_stacks_in_runs(params):
    """Nine layers, two periods after the leading layer: a stack holds every
    layer of its kinds and the forward reads it a run at a time (the experts'
    stacks whole, a layer's own inside them)."""
    deep = dict(MODEL, n_layer=9,
                layer_types=(MODEL["layer_types"][:4] * 3)[:9],
                num_attention_heads_per_layer=([4, 6, 6, 6] * 3)[:9],
                mlp_layer_types=["dense"] + ["sparse"] * 8)
    cfg = family.config(deep)
    assert len(cfg.attn_period) == 4
    assert dict(G.stack_names(cfg)) == {
        "blocks_full": 1, "moe_blocks_window": 6, "moe_blocks_full": 2}
    assert [(r.name, r.offset, r.count) for r in G.layer_runs(cfg)] == [
        ("blocks_full", 0, 1), ("moe_blocks_window", 0, 3),
        ("moe_blocks_full", 0, 1), ("moe_blocks_window", 3, 3),
        ("moe_blocks_full", 1, 1)]
    p = _moved(G.init_params(cfg, jax.random.PRNGKey(1)))
    ids = _ids(1, 24, seed=3)
    want = np.asarray(ref.logits(deep, p, ids[0]))
    got = np.asarray(G.forward(cfg, p, jnp.asarray(ids), train=False))[0]
    assert np.abs(got - want).max() < TOL
    cache = G.init_cache(cfg, 1, 32, jnp.float32)
    stepped, _ = G.forward_with_cache(cfg, p, jnp.asarray(ids), cache)
    assert np.abs(np.asarray(stepped[0]) - want).max() < TOL


def test_forward_logits_equal_the_references(params):
    ids = _ids(2, 40)
    got = np.asarray(G.forward(CFG, params, jnp.asarray(ids), train=False))
    want = np.stack([ref.logits(MODEL, params, row) for row in ids])
    assert np.abs(got - want).max() < TOL


def _engine(params, page, slots=4, chunk=32, dtype="float32", **serving):
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    return ServingEngine(CFG, params, ServingConfig(
        num_slots=slots, page_size=page, max_model_len=128,
        prefill_chunk=chunk, dtype=dtype, decode_block=2,
        kernel_impl="kernel", **serving))


PATHS = {"fused, inside the window": ([5], 8),
         "fused, past the ring": ([20], 8),
         "batch": ([6, 30], 16),
         "chunked": ([70], 8),
         "chunked, a ring wider than the window": ([70], 16)}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_engines_prefill_then_decode_equal_the_full_forward(path, params):
    """Logits, not tokens: each prefill path (a prompt of one chunk straight
    to pages and the slot's ring, two that share the admission batch, serial
    chunks through the dense cache and the scatter), then decode through the
    pages and the rings with the kernel (interpret mode here), the ring
    wrapping under the decoded tokens."""
    lens, page = PATHS[path]
    engine = _engine(params, page)
    assert engine.paged_cache["k_ring"].shape == (
        3, 2, 4, -(-WINDOW // page) * page, 16)
    assert engine.paged_cache["k_pages"].shape[:2] == (2, 2)
    prompts = [row[:n] for row, n in zip(_ids(len(lens), 80, seed=5), lens)]
    pps = engine.serving.pages_per_seq
    tables = np.zeros((engine.num_slots, pps), np.int32)
    # the requests in the last slots: a ring is its slot's, whatever the row
    # of the prefill dispatch
    slots = [engine.num_slots - 1 - j for j in range(len(lens))]
    for j, slot in enumerate(slots):
        tables[slot] = 1 + j * pps + np.arange(pps)
    first = engine.prefill_many([(slot, p, tables[slot])
                                 for slot, p in zip(slots, prompts)])
    n = engine.num_slots
    lengths, nxt = np.zeros(n, np.int32), np.zeros(n, np.int32)
    active = np.zeros(n, bool)
    seqs = {}
    for slot, p in zip(slots, prompts):
        lengths[slot], nxt[slot], active[slot] = len(p), first[slot], True
        seqs[slot] = list(p) + [int(first[slot])]
    for _ in range(2 * WINDOW - 3):
        out = engine.decode(nxt.copy(), tables.copy(), lengths.copy(),
                            active, steps=1)
        lengths[active] += 1
        for slot in slots:
            nxt[slot] = out[0, slot]
            seqs[slot].append(int(out[0, slot]))
    assert engine.decode_routing.shape == (1, 4)
    logits, _, (chosen, counts) = G.paged_decode_step(
        CFG, engine.params, jnp.asarray(nxt), engine.paged_cache,
        jnp.asarray(tables), jnp.asarray(lengths), impl="kernel",
        return_routing=True)
    for slot, p in zip(slots, prompts):
        ids = np.asarray(seqs[slot], np.int32)
        want = np.asarray(ref.logits(MODEL, params, ids))
        # the greedy tokens along the way, where the reference has no tie
        for t in range(len(p) - 1, len(ids) - 1):
            top = np.sort(want[t])[-2:]
            if top[1] - top[0] > 1e-4:
                assert ids[t + 1] == int(np.argmax(want[t])), (path, slot, t)
        assert np.abs(np.asarray(logits[slot]) - want[-1]).max() < TOL
        # the step's experts are the reference's own at that position
        own = np.asarray(ref.forward(MODEL, params, ids)[1])[-1]
        got = np.asarray(chosen[slot])
        assert (got[0] == -1).all() and (own[0] == -1).all()
        assert [sorted(r) for r in got[1:].tolist()] == \
            [sorted(r) for r in own[1:].tolist()]
    assert int(counts[0]) == int(active.sum()) * 4 * MODEL["k"]
    assert int(counts[1]) == int(counts[0])     # every expert is held


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


KERNEL_CASES = {"a group of 6 over pages": (48, 0),
                "a group of 8 over pages": (64, 0),
                "a group of 8 over rings": (64, 16),
                "a group of 6 over rings wider than the window": (48, 12)}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "two passes"])
def test_the_gqa_kernel_equals_a_dense_masked_attention(case, dtype):
    """``paged_decode_gqa`` in interpret mode and its gather fallback against
    a dense masked attention in numpy: 8 key-value heads for 48 and 64 query
    heads, lengths that are 0, inside a page, a whole number of pages, past
    the window and past the ring; over scattered pages, and over rings read
    as the slots' pages. ``two passes``: a float32 query over bf16 rows."""
    H, window = KERNEL_CASES[case]
    G_, Dh, ps, B = 8, 32, 8, 5
    rng = np.random.default_rng(len(case))
    pool_dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    q_dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    lengths = np.asarray([0, 5, 16, 23, 41], np.int32)
    S = 48
    k = rng.normal(size=(B, G_, S, Dh)).astype(np.float32)
    v = rng.normal(size=(B, G_, S, Dh)).astype(np.float32)
    k, v = (np.asarray(jnp.asarray(a, pool_dt).astype(jnp.float32))
            for a in (k, v))
    q = np.asarray(jnp.asarray(rng.normal(size=(B, 1, H, Dh)), q_dt)
                   .astype(jnp.float32))
    if window:
        R = -(-window // ps) * ps
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
        pages = S // ps
        order = rng.permutation(B * pages) + 1      # page 0 is the sink
        tables = order.reshape(B, pages).astype(np.int32)
        pool_k, pool_v = (np.zeros((2, G_, B * pages + 1, ps, Dh),
                                   np.float32) for _ in range(2))
        for b in range(B):
            for j in range(pages):
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


@pytest.mark.parametrize("logits", ["random", "ties"])
def test_the_router_renormalises_what_it_took_as_the_reference(logits):
    rng = np.random.default_rng(2)
    r = rng.normal(size=(12, 16)).astype(np.float32)
    if logits == "ties":
        r = np.round(r)         # many equal: ties go to the lower index
    chosen, gates = dropless.route(jnp.asarray(r), 4, scale=2.5,
                                   norm_topk=True)
    # the reference's router over h = r, W_r = 1: its logits are r
    want, own, _ = ref.route(dict(MODEL), jnp.asarray(r), jnp.eye(16),
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


def test_a_ring_costs_a_slot_the_same_at_any_length():
    """The published widths: a window layer's cache a slot is the same for a
    pool sized for requests of 512 and of 9216 tokens, and the pool holds the
    full layers only."""
    with open(os.path.join(CONFIGS, "laguna-xs.2-serve.json")) as f:
        real = family.config(json.load(f)["model"])
    assert G.cache_row(real) == (2, 8, 128)
    assert G.cache_layers(real) == 5 and G.paged_layers(real) == (2, 3)
    assert G.ring_rows(real, 64) == 512
    assert G.paged_kv_bytes_per_token(real) == 2 * 4096
    assert G.ring_bytes_per_slot(real) == 3 * 512 * 4096 == 6291456
    shapes = {}
    for longest in (512, 9216):
        pages = 48 * (longest // 64) + 1
        cache = jax.eval_shape(lambda pages=pages: G.init_paged_cache(
            real, pages, 64, jnp.bfloat16, ring_slots=48))
        assert cache["k_pages"].shape == (2, 8, pages, 64, 128)
        shapes[longest] = cache["k_ring"].shape
    assert shapes[512] == shapes[9216] == (3, 8, 48, 512, 128)
    with pytest.raises(ValueError, match="ring_slots"):
        G.init_paged_cache(CFG, 9, 8)
    model = json.load(open(os.path.join(
        CONFIGS, "laguna-xs.2-serve.json")))["model"]
    assert ref.kv_bytes_per_token(model) == 8192
    assert ref.ring_bytes_per_slot(model) == 6291456
    # 3.870 B: a full layer's attention 29,458,432, a window layer's
    # 37,879,808, an expert 3,145,728 (256 + the shared one and the router a
    # routed layer), the dense MLP 50,331,648, embedding and head
    assert ref.held_params(model) == (
        2 * 29_458_432 + 3 * 37_879_808 + 50_331_648
        + 4 * (257 * 3_145_728 + 2048 * 256) + 2 * 100352 * 2048
    ) == 3_869_835_264
    dense = G.init_cache(CFG, 2, 32, jnp.bfloat16)
    assert sum(a.nbytes for k, a in dense.items() if k != "pos") == \
        G.dense_kv_bytes(CFG, 2, 32) == 2 * 5 * 2 * 16 * 2 * 2 * 32


def test_a_mixed_run_with_a_preemption_leaves_a_clean_audit(params):
    """Requests under and over the window through the scheduler with a pool
    too small for all: one is preempted and prefilled again into whatever
    slot comes free, every request's tokens are those of a run with room, a
    slot's ring stays its own size while its request grows, and the page
    audit is clean."""
    from deepspeed_tpu.inference.serving.scheduler import Request

    prompts = [row[:n] for row, n in zip(_ids(5, 64, seed=9),
                                         (5, 20, 40, 12, 33))]

    def run(num_pages):
        engine = _engine(params, 8, slots=3, num_pages=num_pages)
        ring = engine.paged_cache["k_ring"].shape
        sched = engine.make_scheduler()
        reqs = [Request(prompt=p, max_new_tokens=14) for p in prompts]
        for r in reqs:
            sched.submit(r)
        seen = set()
        for _ in range(2000):
            if sched.idle:
                break
            sched.step()
            seen.add(engine.paged_cache["k_ring"].shape)
        assert sched.idle and seen == {ring}
        audit = sched.audit()
        sched.close()
        return reqs, audit

    roomy, audit = run(3 * 16 + 1)
    assert audit["ok"] and not sum(r.preemptions for r in roomy)
    tight, audit = run(15)
    assert audit["ok"], audit
    assert sum(r.preemptions for r in tight) >= 1
    for a, b, p in zip(roomy, tight, prompts):
        assert len(a.tokens) == 14 and a.tokens == b.tokens
        want = np.asarray(ref.logits(
            MODEL, params, np.asarray(list(p) + a.tokens, np.int32)))
        for t in range(len(p) - 1, len(p) + 13):
            top = np.sort(want[t])[-2:]
            if top[1] - top[0] > 1e-4:
                assert a.tokens[t - len(p) + 1] == int(np.argmax(want[t]))


def test_a_decode_span_counts_the_rows_of_each_kind(params):
    engine = _engine(params, 8, slots=4)
    sched = engine.make_scheduler()
    sched.lengths[:] = [3, 0, 20, 8]
    mask = np.asarray([True, False, True, True])
    stats = sched._decode_stats(2, [0, 2, 3], mask)
    assert stats["live_kv_tokens"] == stats["kv_rows_full"] == 31
    assert stats["kv_rows_window"] == 3 + 8 + 8 and stats["ring_rows"] == 8
    sched.close()
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    tiny = G.PRESETS["tiny"]
    plain = ServingEngine(tiny, G.init_params(tiny, jax.random.PRNGKey(0)),
                          ServingConfig(num_slots=2, page_size=8,
                                        max_model_len=32, prefill_chunk=16,
                                        dtype="float32")).make_scheduler()
    assert "kv_rows_window" not in plain._decode_stats(
        1, [0], np.asarray([True, False]))
    plain.close()


def _engine_with(**serving):
    def build():
        return _engine(G.init_params(CFG, jax.random.PRNGKey(0)), 8, slots=2,
                       **serving)
    return build


def _export():
    _engine_with()().export_pages([1])


def _verify():
    p = G.init_params(CFG, jax.random.PRNGKey(0))
    G.paged_verify_step(CFG, p, jnp.zeros((2, 3), jnp.int32),
                        G.init_paged_cache(CFG, 9, 8, ring_slots=2),
                        jnp.zeros((2, 4), jnp.int32), jnp.zeros(2, jnp.int32))


def _pipe():
    from deepspeed_tpu.models import gpt_pipe

    gpt_pipe.build(CFG, 2, 2)


def _expert_model():
    from deepspeed_tpu.models import gpt_moe

    gpt_moe.build(gpt_moe.GPTMoEConfig(base=CFG, num_experts=2, moe_freq=1))


def _initialize():
    """Training through ``initialize``: the model's specs name no mesh axis
    (``partition_specs``: replicated, as PR 34's kinds), so a mesh that
    would shard it is what refuses; here the pipelined and the expert model,
    the two ``initialize`` shards by layer and by expert."""
    _pipe()


REFUSALS = {
    "tp": _engine_with(tp=2),
    "kv8 pool": _engine_with(kv_bits=8),
    "kv4 pool": _engine_with(kv_bits=4),
    "prefix cache": _engine_with(enable_prefix_cache=True),
    "page fingerprints": _engine_with(page_fingerprints=True),
    "a drafter": _engine_with(spec_drafter="ngram"),
    "a prefill role": _engine_with(role="prefill"),
    "page export": _export,
    "verify": _verify,
    "a quantized stack": lambda: G.quantize_for_inference(
        CFG, G.init_params(CFG, jax.random.PRNGKey(0))),
    "GPTStream": lambda: G.GPTStream(CFG),
    "gpt_pipe": _pipe,
    "gpt_moe": _expert_model,
    "initialize over pipeline stages": _initialize,
}


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_a_path_that_does_not_carry_the_kinds_refuses_by_the_fields_name(
        path):
    with pytest.raises(ValueError, match="attn_kind="):
        REFUSALS[path]()


NEW_FIELDS = {"n_kv_head": 2, "head_width": 16, "attn_window": 8,
              "attn_gate": True, "attn_period": CFG.attn_period,
              "moe_norm_topk": True}


@pytest.mark.parametrize("field", sorted(NEW_FIELDS))
def test_each_new_field_alone_is_named(field):
    """A config object that says one new field and nothing else (built past
    ``__post_init__``, which ties them to ``attn_kind``) is refused by that
    field's name on a path that carries neither kinds nor other blocks."""
    tiny = G.PRESETS["tiny"]
    cfg = dataclasses.replace(tiny)
    object.__setattr__(cfg, field, NEW_FIELDS[field])
    for fields in (G.KIND_FIELDS, G.BLOCK_FIELDS):
        with pytest.raises(ValueError, match=f"{field}="):
            G.require_default_block(cfg, "here", fields)
    G.require_default_block(tiny, "here", G.KIND_FIELDS)


def test_a_config_the_block_does_not_compute_is_refused():
    for wrong in (dict(n_kv_head=4), dict(head_width=0), dict(alibi=True),
                  dict(rotary_interleaved=True), dict(linear_bias=True),
                  dict(attn_window=4), dict(ut_steps=2),
                  dict(attn_period=CFG.attn_period[:1] + (dataclasses.replace(
                      CFG.attn_period[0], n_head=8),))):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **wrong)
    with pytest.raises(ValueError, match="gqa"):
        dataclasses.replace(G.PRESETS["tiny"], n_kv_head=2)
    with pytest.raises(ValueError, match="dense layers lead"):
        family.config(dict(MODEL, mlp_layer_types=["sparse", "dense"]
                           + ["sparse"] * 3))
    with pytest.raises(ValueError, match="laguna_ref reads"):
        family.config(dict(MODEL, norm_topk_prob=False))


def test_the_rotary_sets_are_the_published_ones():
    """YaRN over half a head at the published numbers: ``attention_factor``
    is 0.1 ln 64 + 1, the reference's frequencies are ``YarnScaling``'s, and
    the window kind rotates the whole head plainly."""
    with open(os.path.join(CONFIGS, "laguna-xs.2-serve.json")) as f:
        model = json.load(f)["model"]
    real = family.config(model)
    full, window = real.attn_period[0], real.attn_period[1]
    assert (full.n_head, full.window, full.rotary_pct) == (48, 0, 0.5)
    assert (window.n_head, window.window, window.rotary_pct) == (64, 512, 1.0)
    assert real.attn_period == (full, window, window, window)
    assert abs(full.rope_scaling.cos_sin_factor - 1.4158883083359672) < 1e-12
    assert full.rope_scaling.softmax_factor == 1.0
    assert np.array_equal(full.rope_scaling.inv_freq(32, 500000.0),
                          ref.inv_freq(model, "full_attention"))
    assert ref.rotated_dims(model, "full_attention") == 64
    assert ref.rotated_dims(model, "sliding_attention") == 128
    plain = ref.inv_freq(model, "sliding_attention")
    assert np.allclose(plain, 10000.0 ** (-np.arange(64) / 64))


def test_a_float32_stream_over_bf16_weights_pages_and_rings(params):
    """The served arrangement: bf16 weights, pages and rings, the stream of
    the prompts' and the decode token's forwards in float32
    (``stream_float32``): the kernel takes the float32 query in two passes,
    the pages and rings stay bf16, the decode logits come back in float32,
    and the result stays the bf16 stream's to bf16's own accuracy."""
    served = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    ids = _ids(1, 41, seed=13)[0]
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6]], jnp.int32)
    wide = dataclasses.replace(CFG, stream_float32=True,
                               linear_out_float32=True, rotary_float32=True)
    got = {}
    for name, cfg in (("float32", wide), ("bf16", CFG)):
        pool = G.init_paged_cache(cfg, 8, 8, jnp.bfloat16, ring_slots=1)
        first, pool, _ = G.paged_prefill_step(
            cfg, served, jnp.asarray(ids[None, :40]), pool, tables,
            jnp.asarray([40]), jnp.asarray([0]), jnp.asarray([0]))
        logits, pool = G.paged_decode_step(
            cfg, served, jnp.asarray(ids[40:]), pool, tables,
            jnp.asarray([40]), impl="kernel")
        assert {a.dtype for a in pool.values()} == {jnp.dtype(jnp.bfloat16)}
        assert first.dtype == jnp.bfloat16
        assert logits.dtype == (jnp.float32 if cfg.stream_float32
                                else jnp.bfloat16)
        got[name] = np.asarray(logits[0], np.float32)
    want = np.asarray(ref.logits(MODEL, served, ids))[-1]
    assert np.isfinite(got["float32"]).all()
    assert 0 < np.abs(got["float32"] - got["bf16"]).max() < 0.2
    assert np.abs(got["float32"] - want).max() < 0.2


def _full_rotary_everywhere(monkeypatch):
    period = tuple(dataclasses.replace(k, rotary_pct=1.0)
                   for k in CFG.attn_period)
    return dataclasses.replace(CFG, attn_period=period)


def _no_window(monkeypatch):
    attention = G._gqa_attention
    monkeypatch.setattr(G, "_gqa_attention", lambda cfg, *a, **kw: attention(
        dataclasses.replace(cfg, attn_window=0), *a, **kw))
    return CFG


def _window_off_by_one(monkeypatch):
    seen = DA._ring_seen
    monkeypatch.setattr(DA, "_ring_seen", lambda pos, n, ring: seen(
        pos, n, (ring[0], ring[1] - 1)))
    return CFG


def _ring_row_off_by_one(monkeypatch):
    append = G._ring_append
    monkeypatch.setattr(G, "_ring_append", lambda ring, layer, row, lengths:
                        append(ring, layer, row, jnp.where(
                            lengths > 0, lengths + 1, 0)))
    return CFG


FAULTS = {
    "no gate": lambda mp: dataclasses.replace(CFG, attn_gate=False),
    "gates not renormalised": lambda mp: dataclasses.replace(
        CFG, moe_norm_topk=False),
    "no scaling factor": lambda mp: dataclasses.replace(CFG, moe_scale=1.0),
    "the whole head rotated in a full layer": _full_rotary_everywhere,
    "no window in the prompt": _no_window,
    "a window of one less in the step": _window_off_by_one,
    "a ring row off by one": _ring_row_off_by_one,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(fault, params, monkeypatch):
    """Each fault once, through prefill into pages and rings and two decode
    steps, under the step's own experts: the honest path passes ``TOL``, the
    fault does not."""
    ids = _ids(1, 42, seed=11)[0]
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6]], jnp.int32)

    def served(cfg):
        pool = G.init_paged_cache(cfg, 8, 8, jnp.float32, ring_slots=1)
        _, pool, _ = G.paged_prefill_step(
            cfg, params, jnp.asarray(ids[None, :40]), pool, tables,
            jnp.asarray([40]), jnp.asarray([0]), jnp.asarray([0]))
        _, pool = G.paged_decode_step(
            cfg, params, jnp.asarray(ids[40:41]), pool, tables,
            jnp.asarray([40]), impl="gather")
        logits, _, (chosen, _) = G.paged_decode_step(
            cfg, params, jnp.asarray(ids[41:]), pool, tables,
            jnp.asarray([41]), impl="gather", return_routing=True)
        want, _ = ref.logits(MODEL, params, ids, positions=[41],
                             choices={41: np.asarray(chosen[0])})
        return float(np.abs(np.asarray(logits[0]) - np.asarray(want[0])
                            ).max())

    honest = served(CFG)
    assert honest < TOL
    read = served(FAULTS[fault](monkeypatch))
    print(f"{fault}: {read:.3g} for the honest {honest:.3g}")
    assert read > 5 * TOL
