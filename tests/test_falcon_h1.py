"""A model whose every layer runs attention AND a Mamba-2 mixer on the same
normed input, side by side, on the normal path (``models/gpt.py`` with ``ssm``
and no ``layer_pattern``: one run whose mixer is ``attn+ssm``, a page layer
and a state layer each; ``models/ssm.py``; the muP multipliers as one value;
``paged_decode_gqa`` and ``ssm_decode``) against the benchmark's plain
reference of those equations, ``benchmark/reference/falcon_h1_ref.py``, which
runs the mixer as the token-by-token recurrence and folds no multiplier:
``served_contract.py`` bound to the family, and what is the family's own.

Seeded random weights at the rehearsal configuration's size
(``benchmark/configs/tiny-falcon-h1-serve.json``: d 64, three layers, 4 query
heads over 2 key-value heads of 16 rotated at base 1e11, a mixer of 8 heads
of 8 with a state of 16 in 2 groups and scan chunks of 8, a gated MLP of 96,
Falcon-H1-34B's own multipliers), in float32 on the CPU. ``TOL`` = 2e-5 on
logits of size 1: both sides are float32 and sum in another order (the
chunked scan against the recurrence); what was read is 1e-6 at most.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import falcon_h1 as family
from benchmark.reference import falcon_h1_ref as ref
from deepspeed_tpu.models import gpt as G
from served_contract import (ServedFamilyContract, config_file, decode_step,
                             moved, prefill_step, refuses, tables_of)

MODEL = config_file("tiny-falcon-h1-serve")["model"]
REAL = config_file("falcon-h1-34b-serve")
CFG = family.config(MODEL)
TOL = ServedFamilyContract.TOL
# the twelve published multipliers, the five of the projection one by one
MULTIPLIERS = (["embedding_multiplier", "lm_head_multiplier",
                "key_multiplier", "attention_in_multiplier",
                "attention_out_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier"]
               + [f"ssm_multipliers {s}" for s in "zxBCd"]
               + ["mlp_multipliers gate", "mlp_multipliers down"])


@jax.jit
def _cached(params, ids, cache, real=None):
    return G.forward_with_cache(CFG, params, ids, cache, real=real)


class TestFalconH1(ServedFamilyContract):
    FAMILY, REF, CONFIG = family, ref, "tiny-falcon-h1-serve"
    INIT = staticmethod(family.init_params)
    # whole sequences through the chunked scan (chunks of 8: lengths under,
    # at and over a chunk, and no multiple of it) against the recurrence
    FORWARDS = {str(n): (n, n) for n in (1, 7, 8, 21, 40)}
    # chunked prompts carry the state through the dense cache and the pages
    # through the scatter; lengths that are no multiple of the scan's chunk
    PATHS = {"fused, 1 chunk": [21], "batch, rows padded": [6, 30],
             "chunked, 2 chunks": [45], "chunked, 3 chunks": [77],
             "a batch and a chunked prompt": [37, 11, 29]}
    NEW_FIELDS = {"multipliers": G.Multipliers(key=0.5)}
    # a model that sets ``ssm`` and no ``layer_pattern``: every path that
    # cannot carry a state a slot says so, by that and nothing else
    REFUSES = refuses("does not support ssm=")
    # a preempted request is computed again into a zeroed slot: pages of 8
    # and three slots for five requests of mixed lengths
    MIXED = dict(page_size=8, num_slots=3)
    PREEMPTED_AGAINST_REF = False
    # bf16 pages beside float32 states: the family's own test below
    test_a_float32_stream_over_bf16_weights_and_pages = None
    # no router: the faults are the multipliers, each zeroed in turn below
    test_a_planted_fault_fails_the_comparison = None

    def the_tree(self, params):
        assert sorted(params) == ["blocks", "lm_head", "lnf_scale", "wte"]
        assert sorted(params["blocks"]) == sorted([
            "ln1_scale", "ln2_scale", "ssm_in_w", "ssm_conv_w", "ssm_conv_b",
            "ssm_dt_bias", "ssm_A_log", "ssm_D", "ssm_norm_scale",
            "ssm_out_w", "q_w", "kv_w", "attn_out_w", "mlp_gate_w",
            "mlp_up_w", "mlp_down_w"])
        blocks = params["blocks"]
        assert blocks["ssm_in_w"].shape == (3, 64, 2 * 64 + 2 * 2 * 16 + 8)
        assert blocks["q_w"].shape == (3, 64, 4 * 16)
        assert blocks["kv_w"].shape == (3, 64, 2 * 2 * 16)
        assert blocks["mlp_gate_w"].shape == (3, 64, 96)
        (run,) = G.layer_runs(CFG)
        assert (run.name, run.count, run.mixer, run.ffn, run.cache_first,
                run.state_first, run.ring) == ("blocks", 3, "attn+ssm",
                                               "dense", 0, 0, False)
        assert run.attends and run.mixes
        assert (G.cache_layers(CFG), G.paged_layers(CFG),
                G.ssm_layers(CFG)) == (3, (3, 0), 3)
        assert sum(v.size for v in jax.tree_util.tree_leaves(params)) == \
            ref.held_params(MODEL)
        # the seeded draw: a matrix over the multipliers that follow it,
        # taps as a Conv1d's
        key = jax.random.PRNGKey(3)
        plain = jax.jit(lambda k: G.init_params(CFG, k, dtype=jnp.bfloat16))(
            key)
        fresh = jax.jit(lambda k: family.init_params(CFG, k))(key)
        m = CFG.multipliers
        for name, by in (("mlp_gate_w", m.mlp_gate), ("ssm_out_w", m.ssm_out),
                         ("attn_out_w", m.attn_out)):
            assert np.allclose(
                np.asarray(fresh["blocks"][name], np.float32) * by,
                np.asarray(plain["blocks"][name], np.float32), rtol=1e-2)
        keys = np.asarray(fresh["blocks"]["kv_w"], np.float32)[..., :32]
        assert np.allclose(keys * m.key * m.attn_in, np.asarray(
            plain["blocks"]["kv_w"], np.float32)[..., :32], rtol=1e-2)
        assert fresh["wte"].dtype == jnp.bfloat16
        taps = np.asarray(fresh["blocks"]["ssm_conv_w"], np.float32)
        assert 0.4 < np.abs(taps).max() <= 0.5 and abs(taps.mean()) < 0.05

    def the_sizes(self):
        """The configuration file's arithmetic, from the reference's counts
        and the program's, at the published widths."""
        model = REAL["model"]
        d = model["d_model"]
        assert ref.mixer_params(model) == 68_351_072
        assert ref.attention_params(model) == 31_457_280
        assert ref.layer_params(model) == 430_120_032
        assert ref.held_params(model) == 5_254_594_112
        assert round(ref.held_params(dict(model, n_layer=72)) / 1e9, 2) == \
            33.64
        assert REAL["reduced"] == ["num_hidden_layers"]
        assert (REAL["num_hidden_layers"],
                REAL["published"]["num_hidden_layers"]) == (6, 72)
        cfg = family.config(model)
        # heads x head_dim is the published d_ssm, not expand x d_model
        assert cfg.ssm.d_inner == REAL["mamba_d_ssm"] == 4096 != 2 * d
        assert (cfg.ssm.in_width, cfg.ssm.conv_width) == (9248, 5120)
        assert cfg.ssm.layer_params(d) - d == 68_351_072
        assert cfg.ssm.slot_bytes() == 4_255_744
        assert G.ssm_bytes_per_slot(cfg) == ref.state_bytes_per_slot(
            model) == 25_534_464
        assert G.paged_kv_bytes_per_token(cfg) == ref.kv_bytes_per_token(
            model) == 12_288
        assert (cfg.n_head * cfg.head_dim, cfg.d_model) == (2560, 5120)
        assert cfg.rope_theta == 1e11
        m = cfg.multipliers
        assert (m.embed, m.head, m.ssm_in) == (
            REAL["embedding_multiplier"], 0.0078125, 0.25)
        assert m.ssm == tuple(REAL["ssm_multipliers"])
        shapes = jax.eval_shape(lambda: G.init_paged_cache(
            cfg, 2305, 64, jnp.bfloat16, ring_slots=96))
        assert shapes["k_pages"].shape == (6, 4, 2305, 64, 128)
        assert shapes["k_pages"].dtype == jnp.bfloat16
        assert shapes["ssm_state"].shape == (6, 96, 32, 128, 256)
        assert shapes["ssm_conv"].shape == (6, 96, 3, 5120)
        assert shapes["ssm_state"].dtype == shapes["ssm_conv"].dtype == \
            jnp.float32
        pool = sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in shapes.values())
        assert round((pool + 2 * ref.held_params(model)) / 1e9, 2) == 14.77
        with pytest.raises(ValueError, match="ring_slots"):
            G.init_paged_cache(cfg, 9, 64)

    def check_state(self, params, ids, slot, own, left):
        """The state and the window the step left in the slot, in EVERY
        layer, are the ones the reference's recurrence leaves after the same
        tokens, by the readings the benchmark's comparison holds them
        through."""
        probes = ref.state_probes(MODEL)
        states, windows = (left[k] for k in G.SSM_KEYS)
        (run,) = G.layer_runs(CFG)
        for l in range(CFG.n_layer):
            at = run.state_layer(l)
            got = np.asarray(ref.read_state(probes, states[at, slot],
                                            windows[at, slot]))
            wanted = np.asarray(own[l]).view(np.float32)
            assert np.abs(got - wanted).max() < 1e-4 * np.abs(
                wanted).max(), (slot, l)

    def test_the_dense_cache_carries_state_and_pages_rows(self, params):
        """Prefill of 13 then 8 single tokens through ``forward_with_cache``:
        keys and values and the mixers' states each count three layers; a
        padded chunk told its real tokens leaves what they left."""
        ids = self.ids(2, 21, seed=2)
        want = np.stack([ref.logits(MODEL, params, row) for row in ids])
        cache = G.init_cache(CFG, 2, 32, jnp.float32)
        assert cache["k"].shape[0] == cache["ssm_state"].shape[0] == 3
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

    def test_a_slot_used_again_gives_what_a_fresh_engine_gives(self, params,
                                                               engines):
        """A second request in a slot that another filled and decoded in: its
        tokens, its pages' effect and its states are those of an engine that
        never held the first."""
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
        windows, in every layer, are bit for bit what they were."""
        engine = engines()
        prompts = [row[:n] for row, n in zip(self.ids(3, 80, seed=13),
                                             (9, 17, 25))]
        tables = tables_of(engine, [0, 1, 2])
        first = engine.prefill_many([(s, p, tables[s])
                                     for s, p in zip((0, 1, 2), prompts)])
        before = {k: np.asarray(engine.paged_cache[k]) for k in G.SSM_KEYS}
        lengths = np.asarray([9, 0, 25, 0], np.int32)
        toks = np.asarray([first[0], 5, first[2], 9], np.int32)
        engine.decode(toks, tables, lengths, lengths > 0, steps=1)
        for k in G.SSM_KEYS:
            after = np.asarray(engine.paged_cache[k])
            assert (after[:, [1, 3]] == before[k][:, [1, 3]]).all()
            assert np.abs(after[:, [0, 2]] - before[k][:, [0, 2]]).max() > 0

    def test_a_decode_span_counts_states_and_rows_of_every_layer(self,
                                                                 engines):
        engine = engines()
        sched = engine.make_scheduler()
        sched.lengths[:] = [3, 0, 20, 8]
        mask = np.asarray([True, False, True, True])
        stats = sched._decode_stats(2, [0, 2, 3], mask)
        per_slot = 3 * 4 * (8 * 8 * 16 + 3 * 128)
        assert per_slot == G.ssm_bytes_per_slot(CFG) == engine.slot_bytes()
        assert stats["state_slots"] == 3 and stats["state_layers"] == 3
        assert stats["state_bytes"] == 2 * per_slot * 3 * 2
        assert stats["cache_layers"] == 3 and stats["live_kv_tokens"] == 31
        # every layer reads every live row, the step's own among them
        assert stats["kv_rows"] == 3 * ((31 + 3) + (31 + 6))
        sched.close()
        from deepspeed_tpu.profiling import trace

        assert set(trace.STATE_STATS) <= set(stats)
        assert {"ssm", "attn", "head_loss"} <= set(trace.MODEL_SCOPES)

    def test_bf16_weights_and_pages_beside_float32_states(self, params):
        """The served arrangement: bf16 weights and pages, the states and
        windows float32 whatever the served type, the stream float32
        (``stream_float32``) or bf16: the decode logits stay the reference's
        to bf16's own accuracy either way."""
        served = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                        params)
        ids = self.ids(1, 41, seed=13)[0]
        got = {}
        for name, over in (("float32", self.WIDE), ("bf16", self.NARROW)):
            cfg = dataclasses.replace(self.CFG, **over)
            first, pool, tables = self.prefilled(cfg, served, ids,
                                                 jnp.bfloat16, prefill_step)
            logits, pool = decode_step(
                cfg, served, jnp.asarray(ids[40:]), pool, tables,
                jnp.asarray([40]), impl="kernel")
            assert {k: a.dtype for k, a in pool.items()} == {
                "k_pages": jnp.bfloat16, "v_pages": jnp.bfloat16,
                "ssm_state": jnp.float32, "ssm_conv": jnp.float32}
            assert logits.dtype == (jnp.float32 if cfg.stream_float32
                                    else jnp.bfloat16)
            got[name] = np.asarray(logits[0], np.float32)
        want = np.asarray(ref.logits(MODEL, served, ids))[-1]
        assert 0 < np.abs(got["float32"] - got["bf16"]).max() < 0.2
        assert np.abs(got["float32"] - want).max() < 0.2

    def test_a_config_the_block_does_not_compute_is_refused(self, params):
        for wrong in (dict(attn_kind="mha", n_kv_head=0, head_width=0),
                      dict(mlp_gated=False), dict(ut_steps=2),
                      dict(parallel_residual=True), dict(attn_window=8)):
            with pytest.raises(ValueError):
                dataclasses.replace(CFG, **wrong)
        with pytest.raises(ValueError, match="falcon_h1_ref reads"):
            family.config(dict(MODEL, hidden_act="gelu"))
        with pytest.raises(ValueError, match="hybrid_pattern"):
            family.config(dict(MODEL, hybrid_pattern="M*M"))
        # a chunk of a prompt does not go through the paged step, and says so
        # by what it cannot carry; a prompt names its slot
        pool = G.init_paged_cache(CFG, 9, 8, jnp.float32, ring_slots=2)
        args = (CFG, params, jnp.zeros((1, 8), jnp.int32), pool,
                jnp.zeros((1, 4), jnp.int32), jnp.asarray([8]),
                jnp.asarray([0]))
        with pytest.raises(ValueError, match="ssm="):
            G.paged_prefill_step(*args, slots=jnp.asarray([0]), chunk=(0, 8))
        with pytest.raises(ValueError, match="slots="):
            G.paged_prefill_step(*args)


def test_a_wrong_multipliers_value_is_refused_where_it_is_built():
    with pytest.raises(ValueError, match="z | x | B | C | dt"):
        G.Multipliers(ssm=(1.0, 1.0))


@pytest.fixture(scope="module")
def served_logits():
    """A prompt of 21 straight to pages and states, then four decode steps
    through both kernels: the sequence and the last step's logits. The
    seeded draw moved leaf by leaf by 0.2: at a width of 64 the draw alone
    leaves ``x``, ``B`` and ``C`` near 0.05 and the state a hundredth of
    ``y`` (at the published widths it is the larger part:
    ``tools/falcon_h1_drift.py``), and ``dt_bias`` brought near 0."""
    params = jax.jit(lambda key: moved(family.init_params(CFG, key),
                                       by=0.2))(jax.random.PRNGKey(5))
    # a time step near 1, not the seeded 0.001 to 0.1: over 25 tokens the
    # state is then a part of ``y`` that B, C and dt can be seen through
    params["blocks"]["ssm_dt_bias"] = params["blocks"]["ssm_dt_bias"] * 0.1
    ids = np.random.default_rng(17).integers(0, 256, 25).astype(np.int32)
    tables = jnp.arange(1, 3, dtype=jnp.int32)[None]
    pool = G.init_paged_cache(CFG, 4, 16, jnp.float32, ring_slots=1)
    _, pool, _ = prefill_step(
        CFG, params, jnp.asarray(np.pad(ids[:21], (0, 11))[None]), pool,
        tables, jnp.asarray([21]), jnp.asarray([0]), jnp.asarray([0]))
    for t in range(21, 25):
        logits, pool = decode_step(CFG, params, jnp.asarray(ids[t:t + 1]),
                                   pool, tables, jnp.asarray([t]),
                                   impl="kernel")
    return params, ids, np.asarray(logits[0])


def test_the_served_logits_are_the_references(served_logits):
    params, ids, got = served_logits
    want = np.asarray(ref.logits(MODEL, params, ids))[-1]
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_no_multiplier_is_folded_away(name, served_logits):
    """Each published multiplier zeroed in turn IN THE REFERENCE: the served
    logits, which apply it, then disagree, so the program drops none and the
    seeded draw hides none (the weights are drawn as drawn for the cell: a
    matrix over its multipliers, taps of a ``Conv1d``)."""
    params, ids, got = served_logits
    key, _, part = name.partition(" ")
    value = MODEL[key]
    if part:
        at = {"z": 0, "x": 1, "B": 2, "C": 3, "d": 4, "gate": 0,
              "down": 1}[part]
        value = [0.0 if i == at else v for i, v in enumerate(value)]
    else:
        value = 0.0
    without = np.asarray(ref.logits(dict(MODEL, **{key: value}), params,
                                    ids))[-1]
    # the honest path lies within TOL (the test above); B, C and dt, which
    # reach the logits through the state alone, read 4e-4, the others more
    assert np.abs(got - without).max() > 10 * TOL, name
