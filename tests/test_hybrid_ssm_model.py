"""A model whose layers are ONE sublayer each by a pattern (a Mamba-2 mixer, a
routed feed-forward or attention), on the normal path (``models/gpt.py`` with
the pattern and the mixer's sizes said as data, ``models/ssm.py``; the page
pool for the attention layers and a state a decode slot for the mixers;
``ssm_decode``; ``moe/dropless.py`` with a sigmoid router that chooses by
score plus bias over ungated experts of which a share is held) against the
benchmark's plain reference of those equations,
``benchmark/reference/nemotron_h_ref.py``, which runs the mixer as the
token-by-token recurrence.

Seeded random weights at the rehearsal configuration's size
(``benchmark/configs/tiny-nemotron-h-serve.json``: d 64, seven layers
``MEM*EME``, a mixer of 8 heads of 8 with a state of 16 in 2 groups and scan
chunks of 8, 2 key-value heads for 4 query heads, 16 experts of which 8 are
held and 3 a token), in float32 on the CPU. ``TOL`` = 2e-5 on logits of size
1: both sides are float32 and sum in another order (the chunked scan against
the recurrence); what was read is 1e-6 at most.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import nemotron_h as family
from benchmark.reference import nemotron_h_ref as ref
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.models import ssm
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import ssm_decode as SD

TOL = 2e-5
CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs")
with open(os.path.join(CONFIGS, "tiny-nemotron-h-serve.json")) as f:
    MODEL = json.load(f)["model"]
with open(os.path.join(CONFIGS, "nemotron-3-nano-serve.json")) as f:
    REAL = json.load(f)
CFG = family.config(MODEL)
SLOTS, PAGE, CHUNK = 4, 16, 32


def _moved(params, seed=8, by=0.05):
    """Every leaf off its initial value, in float32: unit gains would hide a
    norm applied with another layer's gain, a zero convolution bias a bias
    left out, and N(0, 0.02) router weights barely route."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x.astype(jnp.float32) + by * jax.random.normal(k, x.shape)
        for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def params():
    return _moved(family.init_params(CFG, jax.random.PRNGKey(0)))


def _ids(n, t, seed=0):
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], (n, t)).astype(np.int32)


def _new_engine(params, **serving):
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    return ServingEngine(CFG, params, ServingConfig(**{**dict(
        num_slots=SLOTS, page_size=PAGE, max_model_len=128,
        prefill_chunk=CHUNK, dtype="float32", decode_block=2,
        kernel_impl="kernel"), **serving}))


@pytest.fixture(scope="module")
def engine(params):
    """ONE engine for the tests below, its slots used again and again: a
    request's state starts from what its own prefill wrote, whatever the slot
    held before."""
    return _new_engine(params)


def _tables(engine, slots):
    pps = engine.serving.pages_per_seq
    tables = np.zeros((engine.num_slots, pps), np.int32)
    for j, slot in enumerate(slots):
        tables[slot] = 1 + j * pps + np.arange(pps)
    return tables


def _serve(engine, prompts, slots, steps):
    """Prefill ``prompts`` into ``slots`` in one admission cycle, then
    ``steps`` decode steps; returns the sequences so far, and the logits and
    the experts of one more step, and the mixers' states and windows as that
    step left them (the engine's cache keeps what was there before it)."""
    tables = _tables(engine, slots)
    first = engine.prefill_many([(slot, p, tables[slot])
                                 for slot, p in zip(slots, prompts)])
    n = engine.num_slots
    lengths, nxt = np.zeros(n, np.int32), np.zeros(n, np.int32)
    active = np.zeros(n, bool)
    seqs = {}
    for slot, p in zip(slots, prompts):
        lengths[slot], nxt[slot], active[slot] = len(p), first[slot], True
        seqs[slot] = list(p) + [int(first[slot])]
    for _ in range(steps):
        out = engine.decode(nxt.copy(), tables.copy(), lengths.copy(),
                            active, steps=1)
        lengths[active] += 1
        for slot in slots:
            nxt[slot] = out[0, slot]
            seqs[slot].append(int(out[0, slot]))
    logits, left, (chosen, counts) = G.paged_decode_step(
        CFG, engine.params, jnp.asarray(nxt), dict(engine.paged_cache),
        jnp.asarray(tables), jnp.asarray(lengths), impl="kernel",
        return_routing=True)
    return (seqs, np.asarray(logits), np.asarray(chosen), np.asarray(counts),
            [left[name] for name in G.SSM_KEYS])


# ---------------------------------------------------------------- the tree
def test_the_parameter_tree_is_the_references(params):
    assert sorted(params) == ["attn_blocks", "lm_head", "lnf_scale",
                              "moe_blocks", "ssm_blocks", "wte"]
    assert sorted(params["ssm_blocks"]) == sorted([
        "ln1_scale", "ssm_in_w", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
        "ssm_A_log", "ssm_D", "ssm_norm_scale", "ssm_out_w"])
    assert sorted(params["attn_blocks"]) == ["attn_out_w", "kv_w",
                                             "ln1_scale", "q_w"]
    assert sorted(params["moe_blocks"]) == sorted([
        "ln2_scale", "router_w", "router_bias", "experts_up_w",
        "experts_down_w", "shared_up_w", "shared_down_w"])
    assert params["ssm_blocks"]["ssm_in_w"].shape == (
        3, 64, 2 * 64 + 2 * 2 * 16 + 8)
    assert params["ssm_blocks"]["ssm_conv_w"].shape == (3, 4, 64 + 64)
    assert params["moe_blocks"]["experts_up_w"].shape == (3, 8, 64, 24)
    assert params["moe_blocks"]["router_w"].shape == (3, 64, 16)
    assert [ref.place(MODEL, l) for l in range(7)] == [
        ("ssm_blocks", 0), ("moe_blocks", 0), ("ssm_blocks", 1),
        ("attn_blocks", 0), ("moe_blocks", 1), ("ssm_blocks", 2),
        ("moe_blocks", 2)]
    assert [(r.name, r.offset, r.count, r.first, r.cache_first, r.sub)
            for r in G.layer_runs(CFG)] == [
        ("ssm_blocks", 0, 1, 0, 0, "M"), ("moe_blocks", 0, 1, 1, 0, "E"),
        ("ssm_blocks", 1, 1, 2, 1, "M"), ("attn_blocks", 0, 1, 3, 0, "*"),
        ("moe_blocks", 1, 1, 4, 1, "E"), ("ssm_blocks", 2, 1, 5, 2, "M"),
        ("moe_blocks", 2, 1, 6, 2, "E")]
    assert (G.cache_layers(CFG), G.paged_layers(CFG), G.ssm_layers(CFG)) == (
        1, (1, 0), 3)
    assert sum(v.size for v in jax.tree_util.tree_leaves(params)) == \
        ref.held_params(MODEL)
    fresh = family.init_params(CFG, jax.random.PRNGKey(3))
    assert fresh["wte"].dtype == jnp.bfloat16
    bias = np.asarray(fresh["moe_blocks"]["router_bias"])
    assert 0 < np.abs(bias).max() < 0.1
    dt = np.asarray(jax.nn.softplus(fresh["ssm_blocks"]["ssm_dt_bias"]))
    assert dt.min() >= 1e-4 - 1e-7 and dt.max() <= 0.1 + 1e-6
    a = np.exp(np.asarray(fresh["ssm_blocks"]["ssm_A_log"]))
    assert a.min() >= 1 and a.max() <= 16


def test_the_published_sizes_and_the_cut_add_up():
    """The configuration file's arithmetic, from the reference's counts and
    the program's, at the published widths."""
    model = REAL["model"]
    d = model["d_model"]
    assert ref.mixer_params(model) + d == 38_744_896
    assert ref.attention_params(model) + d == 23_399_040
    assert ref.routed_params(model) + d == 658_885_376
    assert ref.routed_params(dict(model, held_experts=[0, 128])) + d == \
        1_297_468_160
    assert ref.held_params(model) == 3_166_244_352
    whole = dict(model, n_layer=52, held_experts=[0, 128], vocab_size=131072,
                 hybrid_pattern=REAL["published"]["hybrid_override_pattern"])
    assert (whole["hybrid_pattern"].count("M"),
            whole["hybrid_pattern"].count("E"),
            whole["hybrid_pattern"].count("*")) == (23, 23, 6)
    assert round(ref.held_params(whole) / 1e9, 3) == 31.578
    assert REAL["hybrid_override_pattern"] == model["hybrid_pattern"] == \
        whole["hybrid_pattern"][:9]
    cfg = family.config(model)
    assert cfg.ssm.layer_params(d) == 38_744_896
    assert (cfg.ssm.in_width, cfg.ssm.conv_width) == (10304, 6144)
    assert cfg.ssm.slot_bytes() == 2_170_880
    assert G.ssm_bytes_per_slot(cfg) == ref.state_bytes_per_slot(model) == \
        8_683_520
    # the one attention layer's keys and values stay float32 (attn_float32)
    assert G.paged_kv_bytes_per_token(cfg) == ref.kv_bytes_per_token(
        model) == 2048
    assert ref.kv_bytes_per_token(dict(model, attention_float32=False)) == \
        1024
    # an expert's matrices as the chip lays them out: zeros past 2688, 1856
    assert (cfg.moe_rows, cfg.moe_width) == (3072, 2048)
    small = dataclasses.replace(cfg, d_model=128, moe_d_ff=24)
    assert (small.moe_rows, small.moe_width) == (128, 24)
    shapes = jax.eval_shape(lambda: G.init_paged_cache(
        cfg, 8193, 64, jnp.bfloat16, ring_slots=512))
    assert shapes["k_pages"].shape == (1, 2, 8193, 64, 128)
    assert shapes["k_pages"].dtype == jnp.float32
    assert shapes["ssm_state"].shape == (4, 512, 64, 64, 128)
    assert shapes["ssm_conv"].shape == (4, 512, 3, 6144)
    assert shapes["ssm_state"].dtype == shapes["ssm_conv"].dtype == \
        jnp.float32
    with pytest.raises(ValueError, match="ring_slots"):
        G.init_paged_cache(cfg, 9, 64)
    # a step at 512 slots moves more bytes of state than of held experts
    step = ref.decode_step_bytes(model, 512 * 683, state_slots=512,
                                 active=512)
    assert 0.54 < 2 * 512 * 8_683_520 / step < 0.62


# ----------------------------------------------- against the reference
@pytest.mark.parametrize("length", [1, 7, 8, 21, 40])
def test_forward_logits_equal_the_references(length, params):
    """Whole sequences through the chunked scan (chunks of 8: lengths under,
    at and over a chunk, and no multiple of it) against the recurrence."""
    ids = _ids(2, length, seed=length)
    got = np.asarray(G.forward(CFG, params, jnp.asarray(ids), train=False))
    want = np.stack([ref.logits(MODEL, params, row) for row in ids])
    assert np.abs(got - want).max() < TOL


def test_the_dense_cache_carries_state_and_window(params):
    """Prefill of 13 then 8 single tokens through ``forward_with_cache``: the
    ``*`` layer's keys and values count one cache layer, the mixers' states
    three; a padded chunk told its real tokens leaves what they left."""
    ids = _ids(2, 21, seed=2)
    want = np.stack([ref.logits(MODEL, params, row) for row in ids])
    cache = G.init_cache(CFG, 2, 32, jnp.float32)
    assert cache["k"].shape[0] == 1 and cache["ssm_state"].shape[:2] == (3, 2)
    logits, cache = _cached(params, jnp.asarray(ids[:, :13]), cache)
    outs = [logits]
    for t in range(13, 21):
        logits, cache = _cached(params, jnp.asarray(ids[:, t:t + 1]), cache)
        outs.append(logits)
    assert np.abs(np.concatenate(outs, axis=1) - want).max() < TOL
    padded = np.concatenate([ids[:, :13], np.full((2, 3), 7, np.int32)], 1)
    fresh = G.init_cache(CFG, 2, 32, jnp.float32)
    _, told = _cached(params, jnp.asarray(padded), fresh, jnp.int32(13))
    _, exact = _cached(params, jnp.asarray(ids[:, :13]), fresh)
    _, untold = _cached(params, jnp.asarray(padded), fresh)
    for key in G.SSM_KEYS:
        assert np.abs(np.asarray(told[key]) - np.asarray(exact[key])
                      ).max() < 1e-6
        assert np.abs(np.asarray(untold[key]) - np.asarray(exact[key])
                      ).max() > 1e-4


@jax.jit
def _cached(params, ids, cache, real=None):
    return G.forward_with_cache(CFG, params, ids, cache, real=real)


PATHS = {"fused, 1 chunk": [21], "batch, rows padded": [6, 30],
         "chunked, 2 chunks": [45], "chunked, 3 chunks": [77],
         "a batch and a chunked prompt": [37, 11, 29]}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_engines_prefill_then_decode_equal_the_full_forward(path, params,
                                                                engine):
    """Logits, not tokens: each prefill path (a prompt of one chunk straight
    to pages and its slot's state, several that share the admission batch with
    padded rows, serial chunks through the dense cache with the state carried
    chunk to chunk and the scatter; lengths that are no multiple of the
    scan's chunk of 8), then 8 decode steps through the pages and the states
    with the kernel (interpret mode here). The engine is the module's: every
    case after the first finds its slots used."""
    lens = PATHS[path]
    prompts = [row[:n] for row, n in zip(_ids(len(lens), 80, seed=5), lens)]
    slots = [engine.num_slots - 1 - j for j in range(len(lens))]
    seqs, logits, chosen, counts, (states, windows) = _serve(
        engine, prompts, slots, 8)
    probes = ref.state_probes(MODEL)
    for slot, p in zip(slots, prompts):
        ids = np.asarray(seqs[slot], np.int32)
        want = np.asarray(ref.logits(MODEL, params, ids))
        for t in range(len(p) - 1, len(ids) - 1):
            top = np.sort(want[t])[-2:]
            if top[1] - top[0] > 1e-4:
                assert ids[t + 1] == int(np.argmax(want[t])), (path, slot, t)
        assert np.abs(logits[slot] - want[-1]).max() < TOL
        # the step's experts are the reference's own at that position, in
        # the pattern's routed layers; the others name nothing. The state
        # and the window the step left in the slot are the ones the
        # reference's recurrence leaves after the same tokens, by the
        # readings the benchmark's comparison holds them through
        own = np.asarray(ref.forward(MODEL, params, ids)[1])[-1]
        for l, kind in enumerate(MODEL["hybrid_pattern"]):
            if kind == "E":
                assert sorted(chosen[slot, l]) == sorted(own[l])
                continue
            assert (chosen[slot, l] == -1).all()
            if kind == "M":
                at = MODEL["hybrid_pattern"][:l].count("M")
                got = np.asarray(ref.read_state(probes, states[at, slot],
                                                windows[at, slot]))
                wanted = own[l].view(np.float32)
                assert np.abs(got - wanted).max() < 1e-4 * np.abs(
                    wanted).max(), (path, slot, l)
            else:
                assert (own[l] == -1).all()
    assert int(counts[0]) == len(lens) * 3 * MODEL["k"]
    assert 0 < int(counts[1]) < int(counts[0])      # half the experts held


def test_a_padded_row_leaves_the_unpadded_rows_state(params, engine):
    """Rows of 6 and 30 in a [2, 32] admission batch: each slot's states and
    windows are those of the same prompt prefilled alone, and through serial
    chunks; a row of length 0 writes no slot."""
    prompts = [row[:n] for row, n in zip(_ids(2, 80, seed=5), (6, 30))]
    tables = _tables(engine, [3, 2])
    before = {k: np.asarray(engine.paged_cache[k]) for k in G.SSM_KEYS}
    engine.prefill_many([(3, prompts[0], tables[3]),
                         (2, prompts[1], tables[2])])
    batch = {k: np.asarray(engine.paged_cache[k]) for k in G.SSM_KEYS}
    for k in G.SSM_KEYS:    # slots 0 and 1 held no row of the dispatch
        assert (batch[k][:, :2] == before[k][:, :2]).all()
    for slot, p in zip((1, 0), prompts):
        engine.prefill(slot, p, _tables(engine, [slot])[slot])
    alone = {k: np.asarray(engine.paged_cache[k]) for k in G.SSM_KEYS}
    for k in G.SSM_KEYS:
        assert np.abs(alone[k][:, 1] - batch[k][:, 3]).max() < 1e-6
        assert np.abs(alone[k][:, 0] - batch[k][:, 2]).max() < 1e-6
    # the recurrence's own state after the last real token
    want = _reference_state(params, prompts[0])
    assert np.abs(batch["ssm_state"][:, 3] - want).max() < 1e-5


def _reference_state(params, ids):
    """The mixers' states [M layers, H, P, N] after ``ids``, a token at a
    time through the dense cache (a scan of one position: the
    recurrence)."""
    cache = G.init_cache(CFG, 1, len(ids), jnp.float32)
    for t in ids:   # one token at a time: no chunk, no padding
        _, cache = _cached(params, jnp.asarray([[t]], jnp.int32), cache)
    return np.asarray(cache["ssm_state"])[:, 0]


def test_a_slot_used_again_gives_what_a_fresh_engine_gives(params, engine):
    """A second request in a slot that another filled and decoded in: its
    tokens and its states are those of an engine that never held the
    first."""
    first, second = (row[:n] for row, n in zip(_ids(2, 80, seed=11),
                                               (40, 19)))
    _serve(engine, [first], [1], 3)
    used, logits, *_ = _serve(engine, [second], [1], 4)
    state = {k: np.asarray(engine.paged_cache[k])[:, 1] for k in G.SSM_KEYS}
    clean = _new_engine(params, num_slots=2)
    new, fresh, *_ = _serve(clean, [second], [1], 4)
    assert used[1] == new[1]
    assert np.abs(logits[1] - fresh[1]).max() < 1e-6
    for k in G.SSM_KEYS:
        assert np.abs(state[k] - np.asarray(clean.paged_cache[k])[:, 1]
                      ).max() < 1e-6


def test_an_idle_row_leaves_its_neighbours_states_bit_equal(params, engine):
    """A decode step with slots 0 and 2 live: the idle slots' states and
    windows are bit for bit what they were (a prompt may already lie there),
    and the live slots' do not depend on what the idle rows carry."""
    prompts = [row[:n] for row, n in zip(_ids(3, 80, seed=13), (9, 17, 25))]
    tables = _tables(engine, [0, 1, 2])
    first = engine.prefill_many([(s, p, tables[s])
                                 for s, p in zip((0, 1, 2), prompts)])
    before = {k: np.asarray(engine.paged_cache[k]) for k in G.SSM_KEYS}
    lengths = np.asarray([9, 0, 25, 0], np.int32)
    toks = np.asarray([first[0], 5, first[2], 9], np.int32)
    active = lengths > 0
    out_a = engine.decode(toks, tables, lengths, active, steps=1)
    after = {k: np.asarray(engine.paged_cache[k]) for k in G.SSM_KEYS}
    for k in G.SSM_KEYS:
        assert (after[k][:, [1, 3]] == before[k][:, [1, 3]]).all()
        assert np.abs(after[k][:, [0, 2]] - before[k][:, [0, 2]]).max() > 0
    # the same step again from the same states, other tokens in the idle rows
    cache = dict(engine.paged_cache)
    cache.update({k: jnp.asarray(before[k]) for k in G.SSM_KEYS})
    engine.paged_cache = cache
    toks[[1, 3]] = (77, 3)
    out_b = engine.decode(toks, tables, lengths, active, steps=1)
    assert (out_a[0, [0, 2]] == out_b[0, [0, 2]]).all()
    for k in G.SSM_KEYS:
        assert (np.asarray(engine.paged_cache[k]) == after[k]).all()


def test_a_preempted_request_is_computed_again_into_a_zeroed_slot(params):
    """A pool too small for all: a request is preempted and prefilled again
    from its prompt and what it generated, into whatever slot comes free; its
    state starts over from zero there, so every request's tokens are those
    of a run with room, and the page audit is clean."""
    from deepspeed_tpu.inference.serving.scheduler import Request

    prompts = [row[:n] for row, n in zip(_ids(5, 64, seed=9),
                                         (5, 20, 40, 12, 33))]

    def run(num_pages):
        eng = _new_engine(params, num_slots=3, num_pages=num_pages,
                          page_size=8)
        sched = eng.make_scheduler()
        reqs = [Request(prompt=p, max_new_tokens=14) for p in prompts]
        for r in reqs:
            sched.submit(r)
        for _ in range(2000):
            if sched.idle:
                break
            sched.step()
        assert sched.idle
        audit = sched.audit()
        sched.close()
        return reqs, audit

    roomy, audit = run(3 * 16 + 1)
    assert audit["ok"] and not sum(r.preemptions for r in roomy)
    tight, audit = run(15)
    assert audit["ok"], audit
    assert sum(r.preemptions for r in tight) >= 1
    for a, b in zip(roomy, tight):
        assert len(a.tokens) == 14 and a.tokens == b.tokens


def test_a_decode_span_counts_the_slots_and_bytes_of_state(engine):
    sched = engine.make_scheduler()
    sched.lengths[:] = [3, 0, 20, 8]
    mask = np.asarray([True, False, True, True])
    stats = sched._decode_stats(2, [0, 2, 3], mask)
    per_slot = 3 * 4 * (8 * 8 * 16 + 3 * 128)
    assert per_slot == G.ssm_bytes_per_slot(CFG) == engine.slot_bytes()
    assert stats["state_slots"] == 3
    assert stats["state_bytes"] == 2 * per_slot * 3 * 2
    assert stats["cache_layers"] == 1 and stats["live_kv_tokens"] == 31
    sched.close()
    assert engine.kv_bytes_per_token() == 2 * 2 * 16 * 4
    from deepspeed_tpu.profiling import trace

    assert set(trace.STATE_STATS) <= set(stats)


# ---------------------------------------------------- the routed layer
def _layer_weights(params, i=1):
    return jax.tree_util.tree_map(lambda a: a[i], params["moe_blocks"])


def test_the_two_shares_add_up_to_the_uncut_layer(params):
    """Experts 0-7 and 8-15 of 16 on two chips, the shared expert counted
    once: the two layers' outputs add up to the layer that holds all 16, in
    the program and in the reference."""
    key = jax.random.PRNGKey(4)
    w = _layer_weights(params)
    other = {k: 0.05 * jax.random.normal(jax.random.fold_in(key, n), v.shape)
             for n, (k, v) in enumerate(w.items()) if k.startswith("experts")}
    x = jax.random.normal(key, (2, 9, 64))
    whole = {**w, **{k: jnp.concatenate([w[k], other[k]]) for k in other}}

    def layer(held, weights, shared=True):
        cfg = dataclasses.replace(CFG, moe_held=held,
                                  moe_shared_d_ff=40 if shared else 0)
        return np.asarray(G._moe_delta(cfg, x, weights)[0])

    first = layer((0, 8), w)
    second = layer((8, 8), {**w, **other}, shared=False)
    uncut = layer((0, 16), whole)
    assert np.abs(first + second - uncut).max() < 1e-5
    assert np.abs(second).max() > 1e-3
    h = ref.rms_norm(x[0], w["ln2_scale"], MODEL["rms_norm_eps"])
    no, use = np.zeros((9, 3), np.int32), np.zeros(9, bool)
    with jax.default_matmul_precision("highest"):
        gates, _, _ = ref.route(MODEL, h, w, no, use)
        mine = ref.experts(MODEL, h, w, gates) + ref.relu2_mlp(
            h, w["shared_up_w"], w["shared_down_w"])
        all16 = dict(MODEL, held_experts=[0, 16])
        every = ref.experts(all16, h, whole, gates) + ref.relu2_mlp(
            h, w["shared_up_w"], w["shared_down_w"])
    assert np.abs(first[0] - np.asarray(mine)).max() < TOL
    assert np.abs(uncut[0] - np.asarray(every)).max() < TOL


ROUTER_CASES = {
    "the bias moves the choice, not the gate": (
        [[2.0, 1.0, 0.9, 0.0, -1.0, -3.0]], [0, 0, 0.3, 0, 0, 0], [0, 2]),
    "a tie goes to the lower index": (
        [[0.5, 1.5, 1.5, 1.5, -2.0, 0.0]], [0.0] * 6, [1, 2]),
    "a negative bias drops the strongest": (
        [[3.0, 0.1, 0.0, -0.1, -0.2, -0.3]], [-2, 0, 0, 0, 0, 0], [1, 2]),
}


@pytest.mark.parametrize("case", sorted(ROUTER_CASES))
def test_the_sigmoid_router_by_hand(case):
    """Choice by score plus bias, gates the scores without it, divided by
    their sum, times the scale; against hand-written ``jax.numpy``."""
    logits, bias, want = ROUTER_CASES[case]
    logits = jnp.asarray(logits, jnp.float32)
    bias = jnp.asarray(bias, jnp.float32)
    chosen, gates = dropless.route(logits, 2, scale=2.5, norm_topk=True,
                                   score="sigmoid", bias=bias)
    assert sorted(np.asarray(chosen)[0]) == want
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits)[0]))
    taken = s[np.asarray(chosen)[0]]
    assert np.abs(np.asarray(gates)[0] - 2.5 * taken / taken.sum()
                  ).max() < 1e-6
    # without the rules: not renormalised, no scale, and the softmax path
    _, plain = dropless.route(logits, 2, score="sigmoid", bias=bias)
    assert np.abs(np.asarray(plain)[0] - taken).max() < 1e-6
    soft, _ = dropless.route(logits, 2)
    assert sorted(np.asarray(soft)[0]) == sorted(
        np.argsort(-np.asarray(logits)[0], kind="stable")[:2])
    with pytest.raises(ValueError, match="score"):
        dropless.route(logits, 2, score="tanh")


def test_the_ungated_expert_by_hand():
    """``down(relu(up(x))^2)``, two products an expert, through the grouped
    products, over a share of the experts and a stack with a layer index."""
    key = jax.random.split(jax.random.PRNGKey(2), 4)
    h = jax.random.normal(key[0], (7, 16))
    up = jax.random.normal(key[1], (2, 5, 16, 12)) * 0.3
    down = jax.random.normal(key[2], (2, 5, 12, 16)) * 0.3
    chosen = jnp.asarray(np.random.default_rng(0).integers(0, 8, (7, 2)),
                         jnp.int32)
    chosen = chosen.at[:, 1].set((chosen[:, 0] + 3) % 8)
    gates = jax.random.uniform(key[3], (7, 2))

    def act(a):
        return jnp.square(jax.nn.relu(a))

    got = dropless.held_experts_ffn(h, chosen, gates, None, up, down, (2, 5),
                                    act, layer=jnp.int32(1))
    want = np.zeros((7, 16))
    for n in range(7):
        for j in range(2):
            e = int(chosen[n, j]) - 2
            if 0 <= e < 5:
                mid = np.maximum(np.asarray(h[n] @ up[1, e]), 0.0) ** 2
                want[n] += float(gates[n, j]) * (mid @ np.asarray(down[1, e]))
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    # float32 rows over bf16 matrices go in two halves through the one
    # product: 16 bits of the rows' mantissa, where a rounding keeps 8
    up16, down16 = up.astype(jnp.bfloat16), down.astype(jnp.bfloat16)
    exact = dropless.held_experts_ffn(
        h, chosen, gates, None, up16.astype(jnp.float32),
        down16.astype(jnp.float32), (2, 5), act, layer=jnp.int32(1))
    halves = dropless.held_experts_ffn(
        h, chosen, gates, None, up16, down16, (2, 5), act,
        layer=jnp.int32(1), out=jnp.float32, split=G.split_bf16)
    rounded = dropless.held_experts_ffn(
        h.astype(jnp.bfloat16), chosen, gates, None, up16, down16, (2, 5),
        act, layer=jnp.int32(1), out=jnp.float32)
    scale = np.abs(np.asarray(exact)).max()
    assert halves.dtype == jnp.float32
    assert np.abs(np.asarray(halves - exact)).max() < 1e-4 * scale
    assert np.abs(np.asarray(rounded - exact)).max() > 1e-3 * scale
    # matrices laid out taller and wider than the model's, zeros there
    # (``moe_rows``, ``moe_width``): the same rows come back, 16 wide
    tall = dropless.held_experts_ffn(
        h, chosen, gates, None,
        jnp.pad(up, ((0, 0), (0, 0), (0, 4), (0, 3))),
        jnp.pad(down, ((0, 0), (0, 0), (0, 3), (0, 4))), (2, 5), act,
        layer=jnp.int32(1))
    assert tall.shape == got.shape
    assert np.abs(np.asarray(tall - got)).max() < 1e-6
    cfg = dataclasses.replace(CFG, activation="relu2")
    assert np.allclose(np.asarray(G._act(cfg, jnp.asarray([-1.0, 0.5, 2.0]))),
                       [0.0, 0.25, 4.0])


# -------------------------------------------------------------- the kernel
KERNEL_CASES = {"every slot live": [1, 1, 1, 1, 1],
                "idle slots between live ones": [0, 1, 0, 1, 1],
                "one live slot, the last": [0, 0, 0, 0, 1],
                "no live slot": [0, 0, 0, 0, 0]}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_ssm_decode_equals_the_recurrence(case):
    """The Pallas kernel in interpret mode against the recurrence written
    out in numpy: a live slot's state of the named layer decays and takes the
    outer product, its output is read off the new state; an idle slot's and
    every other layer's are bit for bit what they were."""
    active = np.asarray(KERNEL_CASES[case], bool)
    L, S, H, P, N, Gr = 3, 5, 4, 8, 128, 2
    k = jax.random.split(jax.random.PRNGKey(len(case)), 5)
    state = jax.random.normal(k[0], (L, S, H, P, N))
    dtx = jax.random.normal(k[1], (S, H, P))
    decay = jax.random.uniform(k[2], (S, H))
    b, c = (jax.random.normal(kk, (S, Gr, N)) for kk in k[3:])
    y, new = jax.jit(lambda s: SD.ssm_decode(
        s, jnp.int32(1), dtx, decay, b, c, jnp.asarray(active),
        impl="kernel"))(state)
    y, new, old = np.asarray(y), np.asarray(new), np.asarray(state)
    assert (new[[0, 2]] == old[[0, 2]]).all()
    for s in range(S):
        if not active[s]:
            assert (new[1, s] == old[1, s]).all() and (y[s] == 0).all()
            continue
        for h in range(H):
            g = h // (H // Gr)
            want = (old[1, s, h] * float(decay[s, h])
                    + np.asarray(dtx[s, h])[:, None]
                    * np.asarray(b[s, g])[None, :])
            assert np.abs(new[1, s, h] - want).max() < 1e-5
            assert np.abs(y[s, h] - want @ np.asarray(c[s, g])).max() < 1e-4
    y2, new2 = SD.ssm_decode(state, 1, dtx, decay, b, c, jnp.asarray(active),
                             impl="gather")
    assert np.abs(np.asarray(y2) - y).max() < 1e-4
    assert np.abs(np.asarray(new2) - new).max() < 1e-5
    live, n = SD.live_slots(jnp.asarray(active))
    assert int(n[0]) == active.sum()
    assert list(np.asarray(live)[:active.sum()]) == list(
        np.flatnonzero(active))


def test_the_chunked_scan_equals_the_recurrence_from_a_given_state():
    m = ssm.SsmMixer(heads=4, head_dim=8, state=16, groups=2, chunk=8)
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    T = 21
    x = jax.random.normal(k[0], (2, T, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, T, 4)))
    A = -jnp.exp(jax.random.normal(k[2], (4,)))
    b, c = (jax.random.normal(kk, (2, T, 2, 16)) for kk in k[3:5])
    s0 = jax.random.normal(k[5], (2, 4, 8, 16))
    y, s = ssm.scan_chunks(m, x, dt, A, b, c, s0)
    want_s, want_y = np.asarray(s0).astype(np.float64), []
    for t in range(T):
        bh = np.repeat(np.asarray(b[:, t]), 2, axis=1)
        ch = np.repeat(np.asarray(c[:, t]), 2, axis=1)
        want_s = (np.exp(np.asarray(dt[:, t]) * np.asarray(A))[..., None, None]
                  * want_s + (np.asarray(dt[:, t])[..., None]
                              * np.asarray(x[:, t]))[..., None]
                  * bh[:, :, None, :])
        want_y.append(np.einsum("bhpn,bhn->bhp", want_s, ch))
    assert np.abs(np.asarray(s) - want_s).max() < 1e-4
    assert np.abs(np.asarray(y) - np.stack(want_y, 1)).max() < 1e-4


# ---------------------------------------------------------------- refusals
def _engine_with(**serving):
    def build():
        return _new_engine(family.init_params(CFG, jax.random.PRNGKey(0)),
                           num_slots=2, **serving)
    return build


def _export():
    _engine_with()().export_pages([1])


def _verify():
    p = family.init_params(CFG, jax.random.PRNGKey(0))
    G.paged_verify_step(CFG, p, jnp.zeros((2, 3), jnp.int32),
                        G.init_paged_cache(CFG, 9, 8, ring_slots=2),
                        jnp.zeros((2, 4), jnp.int32), jnp.zeros(2, jnp.int32))


def _pipe():
    from deepspeed_tpu.models import gpt_pipe

    gpt_pipe.build(CFG, 2, 2)


def _expert_model():
    from deepspeed_tpu.models import gpt_moe

    gpt_moe.build(gpt_moe.GPTMoEConfig(base=CFG, num_experts=2, moe_freq=1))


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
        CFG, family.init_params(CFG, jax.random.PRNGKey(0))),
    "GPTStream": lambda: G.GPTStream(CFG),
    "gpt_pipe": _pipe,
    "gpt_moe": _expert_model,
}


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_a_path_that_does_not_carry_the_pattern_refuses_by_a_fields_name(
        path):
    with pytest.raises(ValueError, match=r"does not support \w+="):
        REFUSALS[path]()


NEW_FIELDS = {"layer_pattern": "M*", "ssm": CFG.ssm, "moe_score": "sigmoid",
              "moe_score_bias": True, "moe_two_pass": True,
              "attn_float32": True}


@pytest.mark.parametrize("field", sorted(NEW_FIELDS))
def test_each_new_field_alone_is_named(field):
    """A config object that says one new field and nothing else (built past
    ``__post_init__``, which ties them to one another) is refused by that
    field's name on a path that carries neither kinds nor other blocks."""
    tiny = G.PRESETS["tiny"]
    cfg = dataclasses.replace(tiny)
    object.__setattr__(cfg, field, NEW_FIELDS[field])
    for fields in (G.KIND_FIELDS, G.BLOCK_FIELDS):
        with pytest.raises(ValueError, match=f"{field}="):
            G.require_default_block(cfg, "here", fields)


def test_float32_attention_keeps_float32_keys_and_values():
    """``attn_float32``: the ``*`` layers' pools and the dense cache are
    float32 under a bf16 engine, a cached token costs twice the bytes, and
    quantized pools are refused."""
    exact = dataclasses.replace(CFG, attn_float32=True)
    for cfg, want in ((CFG, jnp.bfloat16), (exact, jnp.float32)):
        pool = G.init_paged_cache(cfg, 3, 16, jnp.bfloat16, ring_slots=2)
        dense = G.init_cache(cfg, 1, 16, jnp.bfloat16)
        assert pool["k_pages"].dtype == pool["v_pages"].dtype == want
        assert dense["k"].dtype == want
        assert pool[G.SSM_KEYS[0]].dtype == jnp.float32
    assert G.paged_kv_bytes_per_token(exact, None, 16, jnp.bfloat16) == (
        2 * G.paged_kv_bytes_per_token(CFG, None, 16, jnp.bfloat16))
    with pytest.raises(ValueError, match="attn_float32"):
        G.init_paged_cache(exact, 3, 16, jnp.bfloat16, kv_bits=8,
                           ring_slots=2)
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, attn_float32=True, layer_pattern="MEM",
                            n_layer=3, attn_kind="mha", n_kv_head=0)


def test_a_config_the_pattern_does_not_compute_is_refused(params):
    for wrong in (dict(layer_pattern="MEM*EM"), dict(layer_pattern="MEM-EME"),
                  dict(ssm=None), dict(layer_pattern="MEMMEME"),
                  dict(layer_pattern="M*M*M*M"), dict(ut_steps=2),
                  dict(moe_dense_layers=1), dict(parallel_residual=True),
                  dict(moe_score="tanh")):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **wrong)
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(G.PRESETS["tiny"], ssm=CFG.ssm)
    with pytest.raises(ValueError):
        ssm.SsmMixer(heads=6, head_dim=8, state=16, groups=4)
    with pytest.raises(ValueError, match="nemotron_h_ref reads"):
        family.config(dict(MODEL, scoring_func="softmax"))
    with pytest.raises(ValueError, match="hybrid_pattern"):
        family.config(dict(MODEL, hybrid_pattern="MEM*EM-"))
    # a chunk of a prompt does not go through the paged step, and a prompt
    # names its slot
    pool = G.init_paged_cache(CFG, 9, 8, jnp.float32, ring_slots=2)
    args = (CFG, params, jnp.zeros((1, 8), jnp.int32), pool,
            jnp.zeros((1, 4), jnp.int32), jnp.asarray([8]), jnp.asarray([0]))
    with pytest.raises(ValueError, match="layer_pattern="):
        G.paged_prefill_step(*args, slots=jnp.asarray([0]), chunk=(0, 8))
    with pytest.raises(ValueError, match="slots="):
        G.paged_prefill_step(*args)
