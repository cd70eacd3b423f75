"""A model whose EVERY layer mixes by power retention (a normalised linear
attention through a feature map: a state a slot, queries and keys normed a
head and rotated, fewer key-value heads than query heads over one state, no
convolution window) and so caches no row a token, on the normal path
(``models/gpt.py`` with ``retention`` said as data, ``models/retention.py``;
a pool without layers and a state a decode slot; ``retention_decode``) against
the benchmark's plain reference of those equations,
``benchmark/reference/brumby_ref.py``, which runs the mixer as the quadratic
form and never builds the feature map: ``served_contract.py`` bound to the
family, and what is the family's own.

Seeded random weights at the rehearsal configuration's size
(``benchmark/configs/tiny-brumby-serve.json``: d 32, three layers, 4 query
heads over 2 key-value heads of 8 so that ``phi`` is 5 x 8 wide, prompt
chunks of 4), in float32 on the CPU.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import brumby as family
from benchmark.reference import brumby_ref as ref
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.models import retention
from deepspeed_tpu.ops.pallas.retention_decode import (
    retention_decode, retention_decode_reference)
from served_contract import (PAGES, ServedFamilyContract, config_file,
                             decode_step, prefill_step, refuses)

MODEL = config_file("tiny-brumby-serve")["model"]
REAL = config_file("brumby-14b-serve")
CFG = family.config(MODEL)


class TestBrumby(ServedFamilyContract):
    FAMILY, REF, CONFIG = family, ref, "tiny-brumby-serve"
    INIT = staticmethod(family.init_params)
    # whole sequences through the chunked form (chunks of 4: lengths under,
    # at and over a chunk, and no multiple of it) against the quadratic form
    FORWARDS = {str(n): (n, n) for n in (1, 4, 21)}
    # the state of a chunked prompt is carried chunk to chunk through the
    # dense cache; a prompt of one chunk goes straight to its slot
    PATHS = {"fused, 1 chunk": [21], "batch, rows padded": [6, 30],
             "chunked, 3 chunks": [77], "chunked, exactly 2 chunks": [64],
             "a batch and a chunked prompt": [37, 11, 29]}
    NEW_FIELDS = {"retention": CFG.retention}
    # every path that cannot carry a state a slot refuses by that, under the
    # mixer's own name as it names ssm and kda
    REFUSES = refuses("does not support retention=",
                      but=("initialize over pipeline stages",))
    MIXED = dict(page_size=8, num_slots=3)
    PREEMPTED_AGAINST_REF = False
    # float32 states beside no page at all: the family's own test below
    test_a_float32_stream_over_bf16_weights_and_pages = None
    # no router: each piece of the mixer is left out in turn below
    test_a_planted_fault_fails_the_comparison = None

    def the_tree(self, params):
        assert sorted(params) == ["blocks", "lm_head", "lnf_scale", "wte"]
        assert sorted(params["blocks"]) == sorted([
            "ln1_scale", "ln2_scale", "retention_q_w", "retention_kv_w",
            "retention_gate_w", "retention_q_norm_scale",
            "retention_k_norm_scale", "retention_out_w", "mlp_gate_w",
            "mlp_up_w", "mlp_down_w"])
        blocks = params["blocks"]
        assert blocks["retention_q_w"].shape == (3, 32, 4 * 8)
        assert blocks["retention_kv_w"].shape == (3, 32, 2 * 2 * 8)
        assert blocks["retention_gate_w"].shape == (3, 32, 2)
        assert sum(v.size for v in jax.tree_util.tree_leaves(params)) == \
            ref.held_params(MODEL)

    def the_sizes(self):
        """The configuration file's arithmetic, from the reference's counts
        and the program's, at the published widths."""
        model = REAL["model"]
        assert ref.mixer_params(model) == 62_955_776
        assert ref.layer_params(model) == 330_352_896
        assert ref.held_params(model) == 3_207_594_240
        assert round(ref.held_params(dict(model, n_layer=40)) / 1e9, 2) == \
            14.77
        assert REAL["reduced"] == ["num_hidden_layers"]
        assert (REAL["num_hidden_layers"],
                REAL["published"]["num_hidden_layers"]) == (5, 40)
        cfg = family.config(model)
        assert cfg.retention.mixer_params(model["d_model"]) == 62_955_776
        # the symmetric map's 8,256 is the least; the program keeps whole
        # lanes: 65 diagonals of 128 and a block for the normaliser
        assert ref.features(model) == 8256
        assert (cfg.retention.features, cfg.retention.state_shape()) == (
            8320, (8, 66, 128, 128))
        assert ref.state_bytes_per_slot(model) == 5 * 34_080_768
        assert G.ssm_bytes_per_slot(cfg) == 5 * 34_603_008 == 173_015_040
        assert G.paged_kv_bytes_per_token(cfg) == ref.kv_bytes_per_token(
            model) == 0
        shapes = jax.eval_shape(lambda: G.init_paged_cache(
            cfg, 5441, 64, jnp.bfloat16, ring_slots=40))
        assert shapes["k_pages"].shape[0] == 0
        assert shapes["ssm_state"].shape == (5, 40, 8, 66, 128, 128)
        assert shapes["ssm_conv"].shape == (5, 40, 0, 0)
        # a step at 40 slots: the states are three quarters of what it moves
        step = ref.decode_step_bytes(model, 40 * 5000, state_slots=40,
                                     active=40)
        assert 0.70 < 2 * 40 * ref.state_bytes_per_slot(model) / step < 0.78

    def check_engine(self, engine):
        """A pool of no bytes, states of the stated bytes, admitted by
        slots."""
        cache = engine.paged_cache
        assert sum(cache[k].nbytes for k in PAGES) == 0
        assert cache[G.SSM_KEYS[1]].nbytes == 0
        assert cache[G.SSM_KEYS[0]].nbytes == (
            engine.num_slots * G.ssm_bytes_per_slot(CFG))
        assert engine.kv_bytes_per_token() == 0
        assert not engine._chunk_to_pages

    def check_state(self, params, ids, slot, own, left):
        """The state the step left in the slot is the one the reference's
        sums name after the same tokens, by the readings the benchmark's
        comparison holds it through."""
        probes = ref.state_probes(MODEL)
        for layer in range(CFG.n_layer):
            got = np.asarray(family.read_state(
                CFG, probes, left[G.SSM_KEYS[0]][layer, slot]))
            wanted = np.asarray(jax.lax.bitcast_convert_type(
                jnp.asarray(own[layer]), jnp.float32))
            assert np.abs(got - wanted).max() < 1e-4 * np.abs(
                wanted).max(), (slot, layer)


# ------------------------------------------------- the mixer and its kernel
def test_the_feature_map_squares_the_inner_product():
    a, b = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 8))
    got = jnp.sum(retention.phi(a) * retention.phi(b), axis=(-1, -2))
    assert retention.phi(a).shape == (7, 5, 8)
    assert np.abs(np.asarray(got - jnp.sum(a * b, -1) ** 2)).max() < 1e-5


def _inputs(m, B, T, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, T, m.heads, m.head_dim))
    k, v = (jax.random.normal(key, (B, T, m.kv_heads, m.head_dim))
            for key in ks[1:3])
    log_g = jnp.log(jax.random.uniform(ks[3], (B, T, m.kv_heads),
                                       minval=0.5, maxval=1.0))
    return q, k, v, log_g, _state(m, ks[4], (B,))


def _state(m, key, lead):
    """A non-zero state as the recurrence leaves it (three tokens from
    nothing: the normaliser is then the state's own, and no quotient is taken
    over a sum near 0), ``lead`` = (layers, rows) or (rows,)."""
    rows = lead[-1]
    state = jnp.zeros((1, rows) + m.state_shape())
    for key in jax.random.split(key, 3):
        ks = jax.random.split(key, 3)
        k, v = (jax.random.normal(kk, (rows, m.kv_heads, m.head_dim))
                for kk in ks[:2])
        _, state = retention_decode_reference(
            state, 0, jnp.zeros((rows, m.heads, m.head_dim)), k, v,
            jax.random.uniform(ks[2], (rows, m.kv_heads), minval=0.5),
            jnp.ones(rows, bool))
    return jnp.broadcast_to(state[0], lead + m.state_shape())


def test_the_chunked_form_is_the_recurrence_from_a_state_with_padded_rows():
    """Eleven positions in chunks of 4 from a non-zero state, the second
    row's last four padding (``k`` 0 and ``log g`` 0 there, as
    ``mix_sequence`` makes them): the outputs and the state the last REAL
    token left are the token-by-token recurrence's."""
    m = retention.RetentionMixer(heads=4, kv_heads=2, head_dim=8, chunk=4)
    q, k, v, log_g, s0 = _inputs(m, 2, 11)
    real = np.asarray([11, 7])
    is_real = jnp.arange(11)[None, :, None] < real[:, None, None]
    log_g = jnp.where(is_real, log_g, 0.0)
    k = jnp.where(is_real[..., None], k, 0.0)
    o, s = retention.scan_chunks(m, q, k, v, log_g, s0)

    @jax.jit
    def recurrence(state):
        outs = []
        for t in range(11):
            o_t, state = retention_decode_reference(
                state, 0, q[:, t], k[:, t], v[:, t], jnp.exp(log_g[:, t]),
                jnp.asarray(t < real))
            outs.append(o_t)
        return jnp.stack(outs, axis=1), state

    want, state = recurrence(s0[None])
    assert np.abs(np.asarray(jnp.where(is_real[..., None], o - want,
                                       0.0))).max() < 2e-5
    assert np.abs(np.asarray(s - state[0])).max() < 2e-4 * np.abs(
        np.asarray(s)).max()


@pytest.mark.parametrize("shape", [(4, 2, 8, [True, False, True]),
                                   (5, 1, 48, [False, True])],
                         ids=["two key-value heads of 8",
                              "a head of 48, five trips"])
def test_retention_decode_in_interpret_mode_is_its_reference_with_dead_slots(
        shape):
    """Layer 1 of two, a slot dead: the live slots' outputs and states are
    ``retention_decode_reference``'s, the dead slot and the other layer come
    back as they were; with no live slot at all the stack comes back whole."""
    H, G_, D, live = shape
    n = len(live)
    m = retention.RetentionMixer(heads=H, kv_heads=G_, head_dim=D)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    state = _state(m, ks[0], (2, n))
    q = jax.random.normal(ks[1], (n, H, D))
    k, v = (jax.random.normal(key, (n, G_, D)) for key in ks[2:4])
    gate = jax.random.uniform(ks[4], (n, G_), minval=0.5)
    active = jnp.asarray(live)
    dead = live.index(False)
    got = retention_decode(state, 1, q, k, v, gate, active, impl="kernel")
    want = retention_decode(state, 1, q, k, v, gate, active, impl="gather")
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a - b)).max() < 1e-4 * np.abs(
            np.asarray(b)).max()
    assert (np.asarray(got[0][dead]) == 0).all()
    assert (np.asarray(got[1][0]) == np.asarray(state[0])).all()
    assert (np.asarray(got[1][1, dead]) == np.asarray(state[1, dead])).all()
    if D > 8:
        return
    o, same = retention_decode(state, 1, q, k, v, gate, jnp.zeros(n, bool),
                               impl="kernel")
    assert (np.asarray(same) == np.asarray(state)).all()
    assert (np.asarray(o) == 0).all()


def test_a_prompt_in_three_chunks_is_the_prompt_in_one():
    """The dense scratch cache carries the states from chunk to chunk, each
    chunk told its positions (queries and keys are rotated); the last
    chunk's tail is padding."""
    params = G.init_params(CFG, jax.random.PRNGKey(0))
    ids = jnp.asarray(TestBrumby.ids(1, 80, seed=3))
    run = jax.jit(lambda ids, cache, real: G.forward_with_cache(
        CFG, params, ids, cache, real=real))
    whole, one = run(ids[:, :75], G.init_cache(CFG, 1, 96, jnp.float32), 75)
    cache, got = G.init_cache(CFG, 1, 96, jnp.float32), []
    for at, real in ((0, 32), (32, 32), (64, 11)):
        logits, cache = run(jnp.pad(ids[:, at:at + real],
                                    ((0, 0), (0, 32 - real))), cache, real)
        got.append(logits[:, :real])
    assert np.abs(np.asarray(jnp.concatenate(got, 1) - whole)).max() < 2e-5
    assert np.abs(np.asarray(cache[G.SSM_KEYS[0]] - one[G.SSM_KEYS[0]])
                  ).max() < 2e-5
    assert cache[G.SSM_KEYS[1]].shape == (3, 1, 0, 0)


def _served(cfg, params, ids):
    """The logits after a prompt of 40 of ``ids`` straight to its slot and
    one decode step, and the state that step left: op by op, under whatever
    a test patched."""
    pool = G.init_paged_cache(cfg, 6, 16, jnp.float32, ring_slots=1)
    tables = jnp.arange(1, 5, dtype=jnp.int32)[None]
    _, pool, _ = G.paged_prefill_step(
        cfg, params, jnp.asarray(ids[None, :40]), pool, tables,
        jnp.asarray([40]), jnp.asarray([0]), jnp.asarray([0]))
    logits, pool = G.paged_decode_step(
        cfg, params, jnp.asarray(ids[40:41]), pool, tables,
        jnp.asarray([40]), impl="gather")
    return np.asarray(logits[0]), pool[G.SSM_KEYS[0]][:, 0]


FAULTS = {
    # the head norms of q and k left out of the reference
    "QK-norm": lambda mp: mp.setattr(ref, "rms_norm", lambda x, gain, eps: (
        x if np.shape(gain)[-1] == MODEL["head_dim"]
        else x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        * gain.astype(jnp.float32))),
    # the rotation left out of the reference
    "the rotation": lambda mp: mp.setattr(ref, "rotate", lambda model, x: x),
    # the program's quotient without its denominator
    "the normaliser": lambda mp: mp.setattr(
        retention, "_quotient", lambda num, den: num),
    # a query head reading another key-value head's state
    "the key-value head a query reads": lambda mp: mp.setattr(
        ref, "mixer", _wrong_head(ref.mixer)),
}


def _wrong_head(mixer):
    def wrong(model, h, w, probes, read_at):
        q = w["retention_q_w"]
        half = q.shape[-1] // 2     # the two groups of query heads swapped
        return mixer(model, h, dict(w, retention_q_w=jnp.concatenate(
            [q[..., half:], q[..., :half]], axis=-1)), probes, read_at)
    return wrong


@pytest.fixture(scope="module")
def moved_params():
    from served_contract import moved

    return jax.jit(lambda key: moved(family.init_params(CFG, key)))(
        jax.random.PRNGKey(0))


def test_the_honest_path_is_the_references(moved_params):
    ids = TestBrumby.ids(1, 41, seed=11)[0]
    got, _ = _served(CFG, moved_params, ids)
    want = np.asarray(ref.logits(MODEL, moved_params, ids))[-1]
    assert np.abs(got - want).max() < TestBrumby.TOL


@pytest.mark.parametrize("piece", sorted(FAULTS))
def test_a_piece_left_out_fails_the_comparison(piece, moved_params,
                                               monkeypatch):
    """QK-norm, the rotation, the normaliser and the grouping of the query
    heads are each held by the logits: with the piece left out of one side
    the two differ by far more than ``TOL``. (The decode step's quotient is
    the kernel wrapper's own and stays; the prompt's goes.)"""
    ids = TestBrumby.ids(1, 41, seed=11)[0]
    FAULTS[piece](monkeypatch)
    ref._block_at.clear_cache()     # a jitted block keeps what it traced
    got, _ = _served(CFG, moved_params, ids)
    want = np.asarray(ref.logits(MODEL, moved_params, ids))[-1]
    ref._block_at.clear_cache()
    read = np.abs(got - want).max()
    print(f"{piece}: {read:.3g}")
    assert read > 5 * TestBrumby.TOL


def test_a_state_in_bf16_or_a_gate_after_the_write_fails_by_the_state_alone(
        moved_params):
    """No logit shows a state's precision, and a gate applied after the
    write cancels in the quotient: both are held by the readings of the
    state (``brumby_ref.STATE_TOL``), which the honest path passes."""
    ids = TestBrumby.ids(1, 41, seed=11)[0]
    _, states = _served(CFG, moved_params, ids)
    probes = ref.state_probes(MODEL)

    def distances(states):
        handed = np.stack([np.asarray(jax.lax.bitcast_convert_type(
            family.read_state(CFG, probes, s), jnp.int32)) for s in states])
        return ref.forward(MODEL, moved_params, ids, {40: handed},
                           distances=True)[2][0]

    assert distances(states).max() < 0.1 * ref.STATE_TOL
    rounded = states.astype(jnp.bfloat16).astype(jnp.float32)
    assert distances(rounded).min() > 2 * ref.STATE_TOL
    # layer 0's state decayed once more by the last token's gate: what a gate
    # applied after the write does to the last term, done to every term
    h = np.asarray(ref.embed(MODEL, moved_params, ids))
    w = jax.tree_util.tree_map(lambda a: a[0], moved_params["blocks"])
    gate = jax.nn.sigmoid(ref.rms_norm(jnp.asarray(h), w["ln1_scale"],
                                       MODEL["rms_norm_eps"])
                          @ w["retention_gate_w"] + MODEL["gate_offset"])
    after = states.at[0].multiply(gate[-1][:, None, None, None])
    assert distances(after)[0] > 2 * ref.STATE_TOL


def test_a_float32_stream_over_bf16_weights_and_float32_states(moved_params):
    """The served arrangement: bf16 weights, the stream of the prompts' and
    the decode token's forwards in float32 (``stream_float32``), states in
    float32 whatever the served type; no page holds anything."""
    served = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                    moved_params)
    ids = TestBrumby.ids(1, 41, seed=13)[0]
    cfg = dataclasses.replace(CFG, stream_float32=True,
                              linear_out_float32=True)
    pool = G.init_paged_cache(cfg, 6, 16, jnp.bfloat16, ring_slots=1)
    tables = jnp.arange(1, 5, dtype=jnp.int32)[None]
    _, pool, _ = prefill_step(
        cfg, served, jnp.asarray(ids[None, :40]), pool, tables,
        jnp.asarray([40]), jnp.asarray([0]), jnp.asarray([0]))
    logits, pool = decode_step(cfg, served, jnp.asarray(ids[40:]), pool,
                               tables, jnp.asarray([40]), impl="kernel")
    assert pool[G.SSM_KEYS[0]].dtype == jnp.float32
    assert sum(pool[k].nbytes for k in PAGES) == 0
    assert logits.dtype == jnp.float32
    want = np.asarray(ref.logits(MODEL, served, ids))[-1]
    assert np.abs(np.asarray(logits[0]) - want).max() < 2e-3


def test_a_stack_without_a_cache_layer_has_a_pool_of_no_bytes():
    (run,) = G.layer_runs(CFG)
    assert (run.name, run.count, run.mixer, run.ffn, run.attends, run.mixes,
            run.state_first) == ("blocks", 3, "retention", "dense", False,
                                 True, 0)
    assert (G.cache_layers(CFG), G.paged_layers(CFG), G.ssm_layers(CFG)) == (
        0, (0, 0), 3)
    assert G.state_mixer(CFG) is CFG.retention
    assert not G.chunks_to_pages(CFG)
    pool = G.init_paged_cache(CFG, 9, 16, jnp.bfloat16, ring_slots=2)
    assert sorted(pool) == ["k_pages", "ssm_conv", "ssm_state", "v_pages"]
    assert pool["k_pages"].shape == (0, 4, 9, 16, 8)
    assert pool["ssm_state"].nbytes == 2 * G.ssm_bytes_per_slot(CFG) == \
        2 * 3 * CFG.retention.slot_bytes()
    assert pool["ssm_conv"].shape == (3, 2, 0, 0)
    assert G.paged_pages_per_step(CFG, 16, 8, jnp.bfloat16) == 0


def test_a_config_the_program_cannot_run_is_refused_by_name():
    base = dict(vocab_size=64, n_layer=2, n_head=4, d_model=32,
                norm="rmsnorm", linear_bias=False, mlp_gated=True,
                rotary=True, retention=CFG.retention)
    G.GPTConfig(**base)
    from deepspeed_tpu.models import kda, ssm

    for over, named in (
            (dict(ssm=ssm.SsmMixer(heads=2, head_dim=8, state=4, groups=1)),
             "no ssm"),
            (dict(kda=kda.KdaMixer(heads=2, head_dim=8), kda_layers=(1,)),
             "kda"),
            (dict(moe_experts=4, moe_k=1, moe_d_ff=8), "moe_experts"),
            (dict(attn_kind="mla", kv_lora_rank=16, qk_nope_dim=8,
                  qk_rope_dim=4, v_head_dim=8), "latent attention"),
            (dict(ut_steps=2), "loop"),
            (dict(rotary=False), "rotary=True"),
            (dict(norm="layernorm"), "norm='rmsnorm'")):
        with pytest.raises(ValueError, match=named):
            G.GPTConfig(**{**base, **over})
    with pytest.raises(ValueError, match="whole groups"):
        retention.RetentionMixer(heads=5, kv_heads=2, head_dim=8)
    # a chunk of a prompt straight to pages carries no state
    with pytest.raises(ValueError, match="retention="):
        G.paged_prefill_step(
            CFG, G.init_params(CFG, jax.random.PRNGKey(0)),
            jnp.zeros((1, 16), jnp.int32),
            G.init_paged_cache(CFG, 9, 16, jnp.float32, ring_slots=1),
            jnp.zeros((1, 8), jnp.int32), jnp.asarray([40]),
            jnp.asarray([0]), jnp.asarray([0]), chunk=(jnp.int32(16), 16))


@pytest.mark.parametrize("name", ["tiny-falcon-h1-serve",
                                  "tiny-nemotron-h-serve",
                                  "tiny-kimi-linear-serve"])
def test_the_other_mixers_cache_trees_are_what_they_were(name):
    """``ssm`` and ``kda`` keep their two arrays, a window of ``K - 1`` rows
    each; they are told positions and ignore them."""
    from benchmark.lib import manifest

    config = config_file(name)
    cfg = manifest.family_of(config).config(dict(config["model"]))
    mixer = G.state_mixer(cfg)
    assert mixer is (cfg.ssm if cfg.ssm is not None else cfg.kda)
    pool = jax.eval_shape(lambda: G.init_paged_cache(
        cfg, 9, 16, jnp.bfloat16, ring_slots=2))
    lead = (G.ssm_layers(cfg), 2)
    assert pool["ssm_state"].shape == lead + mixer.state_shape()
    assert pool["ssm_conv"].shape == lead + mixer.window_shape()
    assert mixer.window_shape()[0] == mixer.conv - 1 > 0
    assert pool["ssm_state"].dtype == pool["ssm_conv"].dtype == jnp.float32
