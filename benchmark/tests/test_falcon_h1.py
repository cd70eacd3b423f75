"""The Falcon-H1 files: the reference against a second, naive writing of its
layer in numpy float64 (a hand-written recurrence over two and over five
tokens: loops over tokens, heads and taps, every multiplier where the
equations place it), its counts against the issue's arithmetic at the
published widths, the judging of a layer's state through its readings, the
family's step over pages and states (its third output, the states put back as
they were; a program without the block refused by name), and the two new
readers on hand-made traces and on runs with nothing to read. (The served
path against the reference is ``tests/test_falcon_h1.py``'s; the family
through ``run.py`` is ``test_rehearsal.py``'s: it runs
``tiny-falcon-h1-serve.tiny-closed`` as every rehearsal cell.)"""

import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import kernel_cost_gqa, manifest
from benchmark.lib.peaks import device_peaks
from benchmark.reference import falcon_h1_ref as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


TINY = _config("tiny-falcon-h1-serve")["model"]
REAL = _config("falcon-h1-34b-serve")


def _params(model, seed=0):
    """A tree in the reference's names, every leaf random (gains near 1): no
    function of the program."""
    rng = np.random.default_rng(seed)
    d, f, L = model["d_model"], model["d_ff"], model["n_layer"]
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    gn = model["n_groups"] * model["ssm_state_size"]
    inner, K = H * P, model["conv_kernel"]
    heads, g, dh = model["n_head"], model["n_kv_head"], model["head_dim"]

    def normal(*shape, by=0.3):
        return rng.normal(0, by, shape).astype(np.float32)

    blocks = {"ln1_scale": 1 + normal(L, d) / 3, "ln2_scale": 1 + normal(L, d) / 3,
              "ssm_in_w": normal(L, d, 2 * inner + 2 * gn + H, by=1.0),
              "ssm_conv_w": normal(L, K, inner + 2 * gn),
              "ssm_conv_b": normal(L, inner + 2 * gn),
              "ssm_dt_bias": normal(L, H), "ssm_A_log": normal(L, H),
              "ssm_D": 1 + normal(L, H),
              "ssm_norm_scale": 1 + normal(L, inner),
              "ssm_out_w": normal(L, inner, d, by=2.0),
              "q_w": normal(L, d, heads * dh),
              "kv_w": normal(L, d, 2 * g * dh, by=3.0),
              "attn_out_w": normal(L, heads * dh, d, by=3.0),
              "mlp_gate_w": normal(L, d, f, by=1.0),
              "mlp_up_w": normal(L, d, f),
              "mlp_down_w": normal(L, f, d, by=5.0)}
    return {"wte": normal(model["vocab_size"], d),
            "lm_head": normal(model["vocab_size"], d, by=10.0),
            "lnf_scale": 1 + normal(d) / 3,
            "blocks": {k: jnp.asarray(v) for k, v in blocks.items()}}


def _by_hand(model, params, ids):
    """The equations of ``falcon_h1_ref``'s docstring in numpy float64, a
    token, a head and a tap at a time: the logits [T, V] and each layer's
    state after the last token [n_layer, H, P, N]."""
    f64 = lambda a: np.asarray(a, np.float64)      # noqa: E731
    silu = lambda a: a / (1 + np.exp(-a))           # noqa: E731
    eps = model["rms_norm_eps"]
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, G, K = model["ssm_state_size"], model["n_groups"], model["conv_kernel"]
    heads, g, dh = model["n_head"], model["n_kv_head"], model["head_dim"]
    inner, gn = H * P, G * N
    vz, vx, vb, vc, vd = model["ssm_multipliers"]
    gate_m, down_m = model["mlp_multipliers"]
    T = len(ids)

    def norm(x, gain):
        return x / np.sqrt(np.mean(x * x) + eps) * f64(gain)

    x = model["embedding_multiplier"] * f64(params["wte"])[ids]     # [T, d]
    states = []
    for l in range(model["n_layer"]):
        w = {k: f64(v[l]) for k, v in params["blocks"].items()}
        h = np.stack([norm(x[t], w["ln1_scale"]) for t in range(T)])
        # --- the mixer, the recurrence a token at a time
        proj = (model["ssm_in_multiplier"] * h) @ w["ssm_in_w"]
        z = proj[:, :inner] * vz
        xbc = np.concatenate([proj[:, inner:2 * inner] * vx,
                              proj[:, 2 * inner:2 * inner + gn] * vb,
                              proj[:, 2 * inner + gn:2 * inner + 2 * gn] * vc],
                             axis=1)
        dt_raw = proj[:, 2 * inner + 2 * gn:] * vd
        S = np.zeros((H, P, N))
        d_ssm = np.zeros_like(x)
        for t in range(T):
            conv = w["ssm_conv_b"].copy()
            for k in range(K):              # tap k on the row K - 1 - k back
                back = t - (K - 1) + k
                if back >= 0:
                    conv += w["ssm_conv_w"][k] * xbc[back]
            conv = silu(conv)
            xs = conv[:inner].reshape(H, P)
            B = conv[inner:inner + gn].reshape(G, N)
            C = conv[inner + gn:].reshape(G, N)
            y = np.zeros((H, P))
            for i in range(H):
                dt = math.log1p(math.exp(dt_raw[t, i] + w["ssm_dt_bias"][i]))
                a = -math.exp(w["ssm_A_log"][i])
                grp = i // (H // G)
                S[i] = math.exp(dt * a) * S[i] + dt * np.outer(xs[i], B[grp])
                y[i] = S[i] @ C[grp] + w["ssm_D"][i] * xs[i]
            y = y.reshape(inner) * silu(z[t])
            y = np.concatenate([
                part / np.sqrt(np.mean(part * part) + eps)
                for part in y.reshape(G, inner // G)]) * w["ssm_norm_scale"]
            d_ssm[t] = model["ssm_out_multiplier"] * (y @ w["ssm_out_w"])
        states.append(S.copy())
        # --- attention, a query head at a time
        a_in = model["attention_in_multiplier"] * h
        q = (a_in @ w["q_w"]).reshape(T, heads, dh)
        kv = (a_in @ w["kv_w"]).reshape(T, 2, g, dh)
        keys, values = model["key_multiplier"] * kv[:, 0], kv[:, 1]
        half = dh // 2
        freq = float(model["rope_theta"]) ** (-np.arange(half) / half)

        def turn(v, t):
            c, s = np.cos(t * freq), np.sin(t * freq)
            return np.concatenate([v[:half] * c - v[half:] * s,
                                   v[half:] * c + v[:half] * s])
        out = np.zeros((T, heads, dh))
        for t in range(T):
            for i in range(heads):
                grp = i // (heads // g)
                scores = np.array([
                    turn(q[t, i], t) @ turn(keys[s, grp], s) / math.sqrt(dh)
                    for s in range(t + 1)])
                p = np.exp(scores - scores.max())
                out[t, i] = (p / p.sum()) @ values[:t + 1, grp]
        d_att = model["attention_out_multiplier"] * (
            out.reshape(T, heads * dh) @ w["attn_out_w"])
        x = x + d_ssm + d_att
        gl = np.stack([norm(x[t], w["ln2_scale"]) for t in range(T)])
        mid = (gl @ w["mlp_up_w"]) * silu(gate_m * (gl @ w["mlp_gate_w"]))
        x = x + down_m * (mid @ w["mlp_down_w"])
    last = np.stack([norm(x[t], params["lnf_scale"]) for t in range(T)])
    return (model["lm_head_multiplier"] * (last @ f64(params["lm_head"]).T),
            np.stack(states))


@pytest.mark.parametrize("tokens", [2, 5])
def test_the_reference_is_the_hand_written_recurrence(tokens):
    """Two tokens (one step of the recurrence from a filled state, a
    convolution that reaches before the sequence, one rotation) and five
    (every tap inside the sequence), logits and the states' readings."""
    model = dict(TINY, n_layer=2, hybrid_pattern="MM")
    params = _params(model)
    ids = np.random.default_rng(tokens).integers(0, 256, tokens)
    want, states = _by_hand(model, params, ids)
    got = np.asarray(ref.logits(model, params, ids))
    assert np.abs(want).max() > 0.05
    assert np.abs(got - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    _, own, _ = ref.forward(model, params, ids)
    probes = ref.state_probes(model)
    for l in range(2):
        mine = np.einsum("hpn,rh,rp,rn->r", states[l], probes["head"],
                         probes["row"], probes["column"])
        theirs = np.asarray(own[-1, l]).view(np.float32)[:-1]
        assert np.abs(mine - theirs).max() < 1e-4 * np.abs(mine).max()


def test_a_multiplier_moves_what_the_equations_say_it_moves():
    """``key_multiplier`` scales the scores and nothing else: doubling it
    with the keys' columns halved gives the same logits; without the halving
    they differ. ``lm_head_multiplier`` scales the logits."""
    model = dict(TINY, n_layer=1, hybrid_pattern="M")
    params = _params(model, seed=3)
    ids = np.arange(7)
    base = np.asarray(ref.logits(model, params, ids))
    keys = model["n_kv_head"] * model["head_dim"]
    halved = dict(params, blocks=dict(
        params["blocks"], kv_w=params["blocks"]["kv_w"].at[..., :keys].multiply(0.5)))
    twice = dict(model, key_multiplier=2 * model["key_multiplier"])
    assert np.abs(np.asarray(ref.logits(twice, halved, ids)) - base).max() < 1e-5
    assert np.abs(np.asarray(ref.logits(twice, params, ids)) - base).max() > 1e-3
    louder = dict(model, lm_head_multiplier=2 * model["lm_head_multiplier"])
    assert np.abs(np.asarray(ref.logits(louder, params, ids)) - 2 * base
                  ).max() < 1e-5


def test_the_counts_are_the_issues_arithmetic():
    """At the published widths: the layer and the tree by the issue's count,
    and the bytes of a decode step at 96 slots: the weights but the
    embedding, the live keys and values of six layers, the active slots'
    states and windows read and written in six layers."""
    model = REAL["model"]
    assert ref.mixer_params(model) == (
        5120 * 9248 + 5120 * 4 + 5120 + 3 * 32 + 4096 + 4096 * 5120)
    assert ref.attention_params(model) == (
        5120 * 2560 + 2 * 5120 * 512 + 2560 * 5120)
    assert ref.layer_params(model) == 430_120_032
    assert ref.held_params(model) == 5_254_594_112
    assert ref.cache_layers(model) == 6
    assert ref.kv_bytes_per_token(model) == 6 * 2 * 4 * 128 * 2
    assert ref.state_bytes_per_slot(model) == 6 * 4 * (
        32 * 128 * 256 + 3 * 5120) == 25_534_464
    weights = 2 * (5_254_594_112 - 261120 * 5120)
    assert weights == 7_835_319_424          # 5.16 GB of layers + the head
    live = 96 * 700
    step = ref.decode_step_bytes(model, live, state_slots=96, active=96)
    assert step == weights + live * 12_288 + 2 * 96 * 25_534_464
    # the states 4.9 GB, the layers 5.16, the head 2.67, the rows 0.8
    assert round(2 * 96 * 25_534_464 / 1e9, 2) == 4.9
    assert round(2 * 6 * ref.layer_params(model) / 1e9, 2) == 5.16
    assert round(2 * (261120 * 5120 + 5120) / 1e9, 2) == 2.67
    assert 16.0 < 1000 * step / device_peaks(
        "TPU v5 lite").hbm_bytes_per_s < 17.0
    # without states the count is the weights and the rows alone
    assert ref.decode_step_bytes(model, live) == weights + live * 12_288
    # the top-level keys are the source's at the values run
    for key, value in REAL["model"].items():
        published = {"d_model": "hidden_size", "d_ff": "intermediate_size",
                     "n_head": "num_attention_heads",
                     "n_kv_head": "num_key_value_heads",
                     "mamba_num_heads": "mamba_n_heads",
                     "mamba_head_dim": "mamba_d_head",
                     "ssm_state_size": "mamba_d_state",
                     "n_groups": "mamba_n_groups",
                     "conv_kernel": "mamba_d_conv",
                     "chunk_size": "mamba_chunk_size",
                     "max_seq_len": "max_position_embeddings"}.get(key, key)
        if published in REAL and key not in ("n_layer",):
            assert REAL[published] == value, key


def test_a_state_is_judged_through_its_readings():
    """The reference's own readings handed back read 0; another state's
    read over ``STATE_TOL`` and turn into slack over ``CHOICE_SLACK``; bits
    that are no number are refused."""
    model = dict(TINY, n_layer=2, hybrid_pattern="MM")
    params = _params(model, seed=1)
    ids = np.random.default_rng(5).integers(0, 256, 9)
    _, own, _ = ref.forward(model, params, ids)
    own = np.asarray(own)
    want, slack = ref.logits(model, params, ids, positions=[8],
                             choices={8: own[8]})
    assert np.abs(np.asarray(want) - np.asarray(
        ref.logits(model, params, ids, positions=[8]))).max() == 0
    assert slack[8].shape == (2,) and (slack[8] == 0).all()
    off = own[8].view(np.float32).copy()
    off[1, :-1] *= -1.0      # layer 1's state, every reading's sign turned
    _, slack = ref.logits(model, params, ids, positions=[8],
                          choices={8: off.view(np.int32)})
    assert slack[8][0] == 0 and slack[8][1] > ref.CHOICE_SLACK
    apart = np.asarray(ref.forward(model, params, ids,
                                   {8: off.view(np.int32)},
                                   distances=True)[2])
    assert apart[8, 1] > ref.STATE_TOL["later"] and apart[:8].max() == 0
    bad = own[8].copy()
    bad[0, 0] = 0x7FC00000      # a NaN's bits
    with pytest.raises(ValueError, match="not finite"):
        ref.logits(model, params, ids, positions=[8], choices={8: bad})
    with pytest.raises(ValueError, match="shape"):
        ref.logits(model, params, ids, positions=[8], choices={8: own[8][:1]})


def test_the_family_hands_over_readings_and_puts_the_states_back():
    """The comparison's step over pages and states: three values, the third
    the readings of the states the step LEFT (the reference's own after the
    same tokens), the stacks back as they came, rows past the comparison's
    zero."""
    from benchmark.families import falcon_h1 as family
    from deepspeed_tpu.models import gpt as G

    cfg = family.config(TINY)
    assert manifest.family_of(_config("tiny-falcon-h1-serve")) is family
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        family.init_params(cfg, jax.random.PRNGKey(2)))
    ids = np.random.default_rng(3).integers(0, 256, (1, 14)).astype(np.int32)
    slots = 6
    pool = G.init_paged_cache(cfg, 9, 16, jnp.float32, ring_slots=slots)
    tables = np.zeros((slots, 2), np.int32)
    tables[0] = [1, 2]
    _, pool, _ = G.paged_prefill_step(
        cfg, params, jnp.asarray(np.pad(ids[:, :13], ((0, 0), (0, 3)))), pool,
        jnp.asarray(tables[:1]), jnp.asarray([13]), jnp.asarray([0]),
        jnp.asarray([0]))
    tokens = np.zeros(slots, np.int32)
    tokens[0] = ids[0, 13]
    lengths = np.zeros(slots, np.int32)
    lengths[0] = 13
    logits, after, handed = family.paged_decode_step(
        cfg, params, jnp.asarray(tokens), dict(pool), jnp.asarray(tables),
        jnp.asarray(lengths), impl="gather")
    assert logits.shape == (slots, 256) and handed.shape == (
        slots, 3, ref.READINGS)
    assert handed.dtype == jnp.int32 and (np.asarray(handed[4:]) == 0).all()
    for key in G.SSM_KEYS:      # the step ran on a copy
        assert (np.asarray(after[key]) == np.asarray(pool[key])).all()
    want, slack = ref.logits(TINY, params, ids[0], positions=[13],
                             choices={13: np.asarray(handed[0])})
    assert np.abs(np.asarray(logits[0]) - np.asarray(want[0])).max() < 2e-5
    assert (slack[13] == 0).all()
    _, own, _ = ref.forward(TINY, params, ids[0])
    got = np.asarray(handed[0]).view(np.float32)
    mine = np.asarray(own[13]).view(np.float32)
    assert np.abs(got - mine).max() < 1e-4 * np.abs(mine).max()


def test_a_program_without_the_block_is_refused_by_name(monkeypatch):
    """The parent of the PR that brought the family: its ``GPTConfig`` has no
    multipliers, and the cell ends at once with an error that says so."""
    from benchmark.families import falcon_h1 as family
    from deepspeed_tpu.models import gpt as G

    monkeypatch.delattr(G, "Multipliers")
    with pytest.raises(ValueError, match="needs a program whose GPTConfig"):
        family.config(TINY)
    with pytest.raises(ValueError, match="falcon_h1_ref reads"):
        family.config({k: v for k, v in TINY.items() if k != "rope_theta"})


class _Span:
    def __init__(self, **stats):
        self.stats = stats


class _Trace:
    reduced = object()

    def __init__(self, spans):
        self.spans = spans

    def named(self, name):
        return self.spans


class _Ctx:
    device_kind = "TPU v5 lite"
    model = REAL["model"]


def test_the_hybrid_roofline_reader_counts_the_programs_own_rows(monkeypatch):
    """A trace with the kernel and the program's count of the rows its
    dispatches read over all six layers; a parent's spans, without the
    count, give nothing."""
    from benchmark.lib import program_trace
    from benchmark.readers import prog_roofline_gqa_hybrid as reader

    monkeypatch.setattr(reader, "_time_and_calls",
                        lambda pt, pattern: (0.05, 48))
    # two blocks of 4 steps over 96 slots holding 60,000 rows: the program's
    # own sum (scheduler._decode_stats)
    rows = 6 * sum(60_000 + 96 * (j + 1) for j in range(4))
    spans = [_Span(steps=4, active=96, live_kv_tokens=60_000, kv_rows=rows)
             for _ in range(2)]
    monkeypatch.setattr(program_trace, "of", lambda c: _Trace(spans))
    got = reader.read(_Ctx(), {"kernel": "paged_decode_gqa"})
    need = kernel_cost_gqa.paged_decode_gqa(2 * rows, 20, 4, 128)
    assert need.bytes == 2 * rows * 2048        # a row 2 x 4 x 128 x 2 B
    peaks = device_peaks("TPU v5 lite")
    assert abs(got - 100 * need.bytes / peaks.hbm_bytes_per_s / 0.05) < 1e-9
    assert 10 < got < 20
    monkeypatch.setattr(program_trace, "of", lambda c: _Trace(
        [_Span(steps=4, active=96, live_kv_tokens=60_000)]))
    assert reader.read(_Ctx(), {"kernel": "paged_decode_gqa"}) is None


def test_the_share_reader_adds_scopes_and_kernels_once(monkeypatch):
    """Operations under ``ssm`` or ``attn`` and the two kernels' calls by
    name over all operations of the decode programs' whole executions; a
    program with neither reads nothing."""
    from benchmark.lib import program_trace
    from benchmark.readers import prog_scope_share as reader

    class Trace:
        window = (0.0, 10.0)
        modules = {0: [("jit_decode_block_4", 1.0, 2.0),
                       ("jit_prefill_fused_128", 2.0, 3.0),
                       ("jit_decode_block_4", 9.5, 10.5)]}   # cut by the edge
        instr = {0: [("fusion.1", 1.0, 1.2), ("ssm_decode.3", 1.2, 1.5),
                     ("fusion.2", 1.5, 1.6), ("fusion.9", 1.6, 2.0),
                     ("fusion.1", 2.1, 2.9)]}
        enclosing = {}

    scopes = {"fusion.1": "jit(decode_block_4)/blocks/ssm/ssm_in/dot",
              "fusion.2": "jit(decode_block_4)/blocks/attn/attn_full/dot",
              "fusion.9": "jit(decode_block_4)/blocks/mlp/dot"}
    monkeypatch.setattr(program_trace, "of", lambda c: Trace())
    monkeypatch.setattr(program_trace, "scopes_of", lambda pt, name: scopes)
    params = manifest.load_metric("hybrid_mixer_share_pct")["params"]
    got = reader.read(_Ctx(), params)
    assert abs(got - 100 * (0.2 + 0.3 + 0.1) / 1.0) < 1e-9
    monkeypatch.setattr(program_trace, "scopes_of", lambda pt, name: {
        k: "jit(decode_block_4)/blocks/mlp/dot" for k in scopes})
    assert abs(reader.read(_Ctx(), params) - 30.0) < 1e-9    # the kernel
    monkeypatch.setattr(program_trace, "scopes_of", lambda pt, name: None)
    assert reader.read(_Ctx(), params) is None
    assert manifest.load_metric("lm_head_ms")["params"]["scope"] == \
        "head_loss"
