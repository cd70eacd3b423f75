"""A model whose layers are ONE sublayer each by a pattern (a Mamba-2 mixer, a
routed feed-forward or attention), on the normal path (``models/gpt.py`` with
the pattern and the mixer's sizes said as data, ``models/ssm.py``; the page
pool for the attention layers and a state a decode slot for the mixers;
``ssm_decode``; ``moe/dropless.py`` with a sigmoid router that chooses by
score plus bias over ungated experts of which a share is held) against the
benchmark's plain reference of those equations,
``benchmark/reference/nemotron_h_ref.py``, which runs the mixer as the
token-by-token recurrence: ``served_contract.py`` bound to the family, and
what is the family's own.

Seeded random weights at the rehearsal configuration's size
(``benchmark/configs/tiny-nemotron-h-serve.json``: d 64, seven layers
``MEM*EME``, a mixer of 8 heads of 8 with a state of 16 in 2 groups and scan
chunks of 8, 2 key-value heads for 4 query heads, 16 experts of which 8 are
held and 3 a token), in float32 on the CPU. ``TOL`` = 2e-5 on logits of size
1: both sides are float32 and sum in another order (the chunked scan against
the recurrence); what was read is 1e-6 at most.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import nemotron_h as family
from benchmark.reference import nemotron_h_ref as ref
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.models import ssm
from served_contract import (ServedFamilyContract, config_file, refuses,
                             tables_of)

MODEL = config_file("tiny-nemotron-h-serve")["model"]
REAL = config_file("nemotron-3-nano-serve")
CFG = family.config(MODEL)
TOL = ServedFamilyContract.TOL


@jax.jit
def _cached(params, ids, cache, real=None):
    return G.forward_with_cache(CFG, params, ids, cache, real=real)


def _reference_state(params, ids):
    """The mixers' states [M layers, H, P, N] after ``ids``, a token at a
    time through the dense cache (a scan of one position: the
    recurrence)."""
    cache = G.init_cache(CFG, 1, len(ids), jnp.float32)
    for t in ids:   # one token at a time: no chunk, no padding
        _, cache = _cached(params, jnp.asarray([[t]], jnp.int32), cache)
    return np.asarray(cache["ssm_state"])[:, 0]


def _layer_weights(params, i=1):
    return jax.tree_util.tree_map(lambda a: a[i], params["moe_blocks"])


class TestNemotronH(ServedFamilyContract):
    FAMILY, REF, CONFIG = family, ref, "tiny-nemotron-h-serve"
    INIT = staticmethod(family.init_params)
    # whole sequences through the chunked scan (chunks of 8: lengths under,
    # at and over a chunk, and no multiple of it) against the recurrence
    FORWARDS = {str(n): (n, n) for n in (1, 7, 8, 21, 40)}
    # lengths that are no multiple of the scan's chunk of 8; the state of a
    # chunked prompt is carried chunk to chunk through the dense cache
    PATHS = {"fused, 1 chunk": [21], "batch, rows padded": [6, 30],
             "chunked, 2 chunks": [45], "chunked, 3 chunks": [77],
             "chunked, exactly 2 chunks": [64],
             "a batch and a chunked prompt": [37, 11, 29]}
    NEW_FIELDS = {"layer_pattern": "M*", "ssm": CFG.ssm,
                  "moe_score": "sigmoid", "moe_score_bias": True,
                  "moe_two_pass": True, "attn_float32": True}
    # every path that cannot carry a state a slot refuses by that
    REFUSES = refuses("does not support ssm=",
                      but=("initialize over pipeline stages",))
    # a preempted request is computed again into a zeroed slot: pages of 8
    # and three slots for five requests. The reference is the token-by-token
    # recurrence: 20 s for five requests whose logits PATHS already holds to
    # it
    MIXED = dict(page_size=8, num_slots=3)
    PREEMPTED_AGAINST_REF = False
    test_a_float32_stream_over_bf16_weights_and_pages = None
    test_a_planted_fault_fails_the_comparison = None

    def the_tree(self, params):
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
        # a mixer's place among the states, attention's among the pages
        assert [(r.name, r.offset, r.count, r.first,
                 r.state_first if r.mixes else r.cache_first, r.mixer,
                 r.ffn) for r in G.layer_runs(CFG) if r.mixer] == [
            ("ssm_blocks", 0, 1, 0, 0, "ssm", ""),
            ("ssm_blocks", 1, 1, 2, 1, "ssm", ""),
            ("attn_blocks", 0, 1, 3, 0, "attn", ""),
            ("ssm_blocks", 2, 1, 5, 2, "ssm", "")]
        assert [(r.name, r.offset, r.first, r.ffn)
                for r in G.layer_runs(CFG) if not r.mixer] == [
            ("moe_blocks", 0, 1, "routed"), ("moe_blocks", 1, 4, "routed"),
            ("moe_blocks", 2, 6, "routed")]
        assert (G.cache_layers(CFG), G.paged_layers(CFG),
                G.ssm_layers(CFG)) == (1, (1, 0), 3)
        assert sum(v.size for v in jax.tree_util.tree_leaves(params)) == \
            ref.held_params(MODEL)
        fresh = jax.jit(lambda key: family.init_params(CFG, key))(
            jax.random.PRNGKey(3))
        assert fresh["wte"].dtype == jnp.bfloat16
        bias = np.asarray(fresh["moe_blocks"]["router_bias"])
        assert 0 < np.abs(bias).max() < 0.1
        dt = np.asarray(jax.nn.softplus(fresh["ssm_blocks"]["ssm_dt_bias"]))
        assert dt.min() >= 1e-4 - 1e-7 and dt.max() <= 0.1 + 1e-6
        a = np.exp(np.asarray(fresh["ssm_blocks"]["ssm_A_log"]))
        assert a.min() >= 1 and a.max() <= 16

    def the_sizes(self):
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
        whole = dict(
            model, n_layer=52, held_experts=[0, 128], vocab_size=131072,
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
        assert G.ssm_bytes_per_slot(cfg) == ref.state_bytes_per_slot(
            model) == 8_683_520
        # the one attention layer's keys and values stay float32 (attn_float32)
        assert G.paged_kv_bytes_per_token(cfg) == ref.kv_bytes_per_token(
            model) == 2048
        assert ref.kv_bytes_per_token(
            dict(model, attention_float32=False)) == 1024
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

    def check_counts(self, assigned, held):
        assert 0 < held < assigned      # half the experts held

    def check_state(self, params, ids, slot, own, left):
        """The state and the window the step left in the slot are the ones
        the reference's recurrence leaves after the same tokens, by the
        readings the benchmark's comparison holds them through; an
        attention layer names nothing."""
        super().check_state(params, ids, slot, own, left)
        probes = ref.state_probes(MODEL)
        states, windows = (left[k] for k in G.SSM_KEYS)
        for r in G.layer_runs(CFG):
            if r.mixer != "ssm":
                continue
            for l in range(r.first, r.first + r.count):
                at = r.state_layer(l)
                got = np.asarray(ref.read_state(probes, states[at, slot],
                                                windows[at, slot]))
                wanted = own[l].view(np.float32)
                assert np.abs(got - wanted).max() < 1e-4 * np.abs(
                    wanted).max(), (slot, l)

    def test_the_dense_cache_carries_state_and_window(self, params):
        """Prefill of 13 then 8 single tokens through ``forward_with_cache``:
        the ``*`` layer's keys and values count one cache layer, the mixers'
        states three; a padded chunk told its real tokens leaves what they
        left."""
        ids = self.ids(2, 21, seed=2)
        want = np.stack([ref.logits(MODEL, params, row) for row in ids])
        cache = G.init_cache(CFG, 2, 32, jnp.float32)
        assert cache["k"].shape[0] == 1
        assert cache["ssm_state"].shape[:2] == (3, 2)
        logits, cache = _cached(params, jnp.asarray(ids[:, :13]), cache)
        outs = [logits]
        for t in range(13, 21):
            logits, cache = _cached(params, jnp.asarray(ids[:, t:t + 1]),
                                    cache)
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

    def test_a_padded_row_leaves_the_unpadded_rows_state(self, params,
                                                         engines):
        """Rows of 6 and 30 in a [2, 32] admission batch: each slot's states
        and windows are those of the same prompt prefilled alone, and through
        serial chunks; a row of length 0 writes no slot."""
        engine = engines()
        prompts = [row[:n] for row, n in zip(self.ids(2, 80, seed=5), (6, 30))]
        tables = tables_of(engine, [3, 2])
        before = {k: np.asarray(engine.paged_cache[k]) for k in G.SSM_KEYS}
        engine.prefill_many([(3, prompts[0], tables[3]),
                             (2, prompts[1], tables[2])])
        batch = {k: np.asarray(engine.paged_cache[k]) for k in G.SSM_KEYS}
        for k in G.SSM_KEYS:    # slots 0 and 1 held no row of the dispatch
            assert (batch[k][:, :2] == before[k][:, :2]).all()
        for slot, p in zip((1, 0), prompts):
            engine.prefill(slot, p, tables_of(engine, [slot])[slot])
        alone = {k: np.asarray(engine.paged_cache[k]) for k in G.SSM_KEYS}
        for k in G.SSM_KEYS:
            assert np.abs(alone[k][:, 1] - batch[k][:, 3]).max() < 1e-6
            assert np.abs(alone[k][:, 0] - batch[k][:, 2]).max() < 1e-6
        # the recurrence's own state after the last real token
        want = _reference_state(params, prompts[0])
        assert np.abs(batch["ssm_state"][:, 3] - want).max() < 1e-5

    def test_a_slot_used_again_gives_what_a_fresh_engine_gives(self, params,
                                                               engines):
        """A second request in a slot that another filled and decoded in: its
        tokens and its states are those of an engine that never held the
        first."""
        engine = engines()
        first, second = (row[:n] for row, n in zip(self.ids(2, 80, seed=11),
                                                   (40, 19)))
        self.serve(engine, [first], [1], 3)
        used, logits, *_ = self.serve(engine, [second], [1], 4)
        state = {k: np.asarray(engine.paged_cache[k])[:, 1]
                 for k in G.SSM_KEYS}
        clean = self.new_engine(params, num_slots=2)
        new, fresh, *_ = self.serve(clean, [second], [1], 4)
        assert used[1] == new[1]
        assert np.abs(logits[1] - fresh[1]).max() < 1e-6
        for k in G.SSM_KEYS:
            assert np.abs(state[k] - np.asarray(clean.paged_cache[k])[:, 1]
                          ).max() < 1e-6

    def test_an_idle_row_leaves_its_neighbours_states_bit_equal(
            self, params, engines):
        """A decode step with slots 0 and 2 live: the idle slots' states and
        windows are bit for bit what they were (a prompt may already lie
        there), and the live slots' do not depend on what the idle rows
        carry."""
        engine = engines()
        prompts = [row[:n] for row, n in zip(self.ids(3, 80, seed=13),
                                             (9, 17, 25))]
        tables = tables_of(engine, [0, 1, 2])
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
        # the same step again from the same states, other tokens in the idle
        # rows
        cache = dict(engine.paged_cache)
        cache.update({k: jnp.asarray(before[k]) for k in G.SSM_KEYS})
        engine.paged_cache = cache
        toks[[1, 3]] = (77, 3)
        out_b = engine.decode(toks, tables, lengths, active, steps=1)
        assert (out_a[0, [0, 2]] == out_b[0, [0, 2]]).all()
        for k in G.SSM_KEYS:
            assert (np.asarray(engine.paged_cache[k]) == after[k]).all()

    def test_a_decode_span_counts_the_slots_and_bytes_of_state(self, engines):
        engine = engines()
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

    def test_the_two_shares_add_up_to_the_uncut_layer(self, params):
        """Experts 0-7 and 8-15 of 16 on two chips, the shared expert counted
        once: the two layers' outputs add up to the layer that holds all 16, in
        the program and in the reference."""
        key = jax.random.PRNGKey(4)
        w = _layer_weights(params)
        other = {k: 0.05 * jax.random.normal(jax.random.fold_in(key, n),
                                             v.shape)
                 for n, (k, v) in enumerate(w.items())
                 if k.startswith("experts")}
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

    def test_a_config_the_pattern_does_not_compute_is_refused(self, params):
        for wrong in (dict(layer_pattern="MEM*EM"),
                      dict(layer_pattern="MEM-EME"),
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
                jnp.zeros((1, 4), jnp.int32), jnp.asarray([8]),
                jnp.asarray([0]))
        with pytest.raises(ValueError, match="ssm="):
            G.paged_prefill_step(*args, slots=jnp.asarray([0]), chunk=(0, 8))
        with pytest.raises(ValueError, match="slots="):
            G.paged_prefill_step(*args)


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
