"""``reference/ouro_ref.py`` against its docstring's equations written once
more in numpy, at tiny widths on the CPU: the chain of its segments is its
``logits``, the loop does what a loop does, and its counts at the published
sizes."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import ouro_ref
from benchmark.tools import deep_drift

MODEL = dict(deep_drift.OURO, vocab_size=96, n_layer=5, n_head=2, d_model=32,
             d_ff=40, total_ut_steps=3)
T = 13


@pytest.fixture(scope="module")
def weights():
    """Seeded float32 weights with gains that are not 1, so that a norm left
    out or misplaced shows."""
    params = deep_drift.init_params(MODEL, jax.random.PRNGKey(3),
                                    dtype=jnp.float32, std=0.2)
    gains = iter(jax.random.split(jax.random.PRNGKey(4), 5))
    for name in ("ln1_scale", "post_attn_scale", "ln2_scale",
                 "post_mlp_scale"):
        params["blocks"][name] = 1.0 + 0.3 * jax.random.normal(
            next(gains), params["blocks"][name].shape)
    params["lnf_scale"] = 1.0 + 0.3 * jax.random.normal(
        next(gains), params["lnf_scale"].shape)
    ids = np.random.default_rng(2).integers(0, MODEL["vocab_size"], T,
                                            dtype=np.int32)
    return params, ids


@pytest.fixture()
def stretches_of_two(monkeypatch):
    monkeypatch.setattr(ouro_ref, "SEGMENT_BLOCKS", 2)


# --------------------------------------------- the equations, in numpy
def _rms(x, g, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):            # [T, H, Dh], rotate-half over all of Dh
    t, _, dh = x.shape
    half = dh // 2
    ang = np.arange(t)[:, None] * theta ** (-np.arange(half) / half)[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def numpy_forward(model, params, ids, loops=None):
    """Logits [T, V] and the rows [loops * n_layer, H, T, Dh] x 2."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    b, eps = p["blocks"], model["rms_norm_eps"]
    d, h = model["d_model"], model["n_head"]
    dh, t = d // h, len(ids)
    x = p["wte"][ids]
    keys, values = [], []
    for _ in range(model["total_ut_steps"] if loops is None else loops):
        for layer in range(model["n_layer"]):
            qkv = _rms(x, b["ln1_scale"][layer], eps) @ b["qkv_w"][layer]
            q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(t, h, dh)
                       for i in range(3))
            q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
            s = np.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
            s = np.where(np.tril(np.ones((t, t), bool))[None], s, -np.inf)
            pr = np.exp(s - s.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            a = np.einsum("hts,shd->thd", pr, v).reshape(t, d) \
                @ b["attn_out_w"][layer]
            x = x + _rms(a, b["post_attn_scale"][layer], eps)
            hh = _rms(x, b["ln2_scale"][layer], eps)
            gate = hh @ b["mlp_gate_w"][layer]
            m = (gate / (1 + np.exp(-gate)) * (hh @ b["mlp_up_w"][layer])) \
                @ b["mlp_down_w"][layer]
            x = x + _rms(m, b["post_mlp_scale"][layer], eps)
            keys.append(k.transpose(1, 0, 2))
            values.append(v.transpose(1, 0, 2))
        x = _rms(x, p["lnf_scale"], eps)
    return x @ p["lm_head"].T, np.stack(keys), np.stack(values)


def close(got, want, rel=1e-5):
    return np.abs(np.asarray(got) - want).max() <= rel * np.abs(want).max()


# --------------------------------------------------------------- the tests
def test_logits_are_the_docstrings_equations(weights):
    params, ids = weights
    want, _, _ = numpy_forward(MODEL, params, ids)
    assert close(ouro_ref.logits(MODEL, params, ids), want)
    assert close(ouro_ref.logits(MODEL, params, ids, positions=[3, T - 1]),
                 want[[3, T - 1]])


def test_the_chain_of_segments_is_logits(weights, stretches_of_two):
    """Stretch by stretch from ``embed``, each from the exit of the one
    before: the same logits, and the rows in the forward's order."""
    params, ids = weights
    stretches = ouro_ref.segments(MODEL)
    assert stretches[:3] == [(0, 0, 2), (0, 2, 4), (0, 4, 5)]
    assert len(stretches) == 9
    x = ouro_ref.embed(MODEL, params, ids)
    keys, values = [], []
    for k in range(len(stretches)):
        x, kk, vv = ouro_ref.segment(MODEL, params, k, x)
        assert kk.shape == (stretches[k][2] - stretches[k][1],
                            MODEL["n_head"], T, 16) == vv.shape
        keys.append(kk)
        values.append(vv)
    want, want_k, want_v = numpy_forward(MODEL, params, ids)
    got = ouro_ref.head_logits(MODEL, params, x)
    assert close(got, want)
    assert close(got, np.asarray(ouro_ref.logits(MODEL, params, ids)))
    assert len(want_k) == ouro_ref.cache_layers(MODEL) == 15
    assert close(np.concatenate(keys), want_k)
    assert close(np.concatenate(values), want_v)


def test_a_segment_takes_the_served_type(weights, stretches_of_two):
    params, ids = weights
    x = ouro_ref.embed(MODEL, params, ids)
    out, _, _ = ouro_ref.segment(MODEL, params, 0, x.astype(jnp.bfloat16))
    assert out.dtype == jnp.float32
    want, _, _ = ouro_ref.segment(MODEL, params, 0,
                                  x.astype(jnp.bfloat16).astype(jnp.float32))
    assert np.array_equal(np.asarray(out), np.asarray(want))


def test_loops_over_one_set_of_weights(weights):
    """Three loops are not one, and not a stack of three different sets;
    a loop's rows are its own."""
    params, ids = weights
    looped = np.asarray(ouro_ref.logits(MODEL, params, ids))
    once = np.asarray(ouro_ref.logits(dict(MODEL, total_ut_steps=1), params,
                                      ids))
    assert not close(once, looped, rel=1e-2)
    stack = dict(MODEL, n_layer=15, total_ut_steps=1)
    sets = deep_drift.init_params(stack, jax.random.PRNGKey(3),
                                  dtype=jnp.float32, std=0.2)
    sets.update({k: params[k] for k in ("wte", "lm_head", "lnf_scale")})
    assert not close(ouro_ref.logits(stack, sets, ids), looped, rel=1e-2)
    _, keys, values = numpy_forward(MODEL, params, ids)
    n = MODEL["n_layer"]
    for rows in (keys, values):
        assert not close(rows[n:2 * n], rows[:n], rel=1e-2)
        assert not close(rows[2 * n:], rows[n:2 * n], rel=1e-2)


@pytest.mark.parametrize("change", [
    {"sandwich_norm": False}, {"loop_norm": False}, {"tie_embeddings": True},
    {"early_exit_threshold": 0.5}, {"qk_norm": True}, {"n_kv_head": 1},
    {"attention_bias": True}, {"sliding_window": 4096}])
def test_it_refuses_what_it_does_not_cover(weights, change):
    params, ids = weights
    with pytest.raises(ValueError, match="ouro_ref covers"):
        ouro_ref.logits(dict(MODEL, **change), params, ids)


def test_counts_at_the_published_sizes():
    ouro = deep_drift.OURO
    stretches = ouro_ref.segments(ouro)
    assert len(stretches) == 8 and stretches[2:4] == [(1, 0, 24), (1, 24, 48)]
    assert all(stop - first <= 24 for _, first, stop in stretches)
    assert ouro_ref.cache_layers(ouro) == 192
    assert ouro_ref.kv_bytes_per_token(ouro) == 1_572_864
    assert ouro_ref.kv_bytes_per_token(ouro, 1) == 786_432
    assert round(ouro_ref.total_params(ouro) / 1e9, 3) == 2.668
    blocks = 48 * ouro_ref.block_params(ouro)
    assert round(2 * blocks / 1e9, 2) == 4.93
    head = 49152 * 2048
    assert ouro_ref.decode_step_bytes(ouro, 1000) == \
        2 * (4 * blocks + head) + 1000 * 1_572_864
    assert ouro_ref.train_flops_per_token(ouro, 2048) == \
        6.0 * (4 * blocks + head) + 12.0 * 192 * 2048 * 2048


def test_the_tree_has_every_parameter(weights):
    params, _ = weights
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert n == ouro_ref.total_params(MODEL)


def test_a_train_loss_is_the_mean_next_token_loss(weights):
    params, ids = weights
    lg, _, _ = numpy_forward(MODEL, params, ids)
    lg = lg[:-1]
    logz = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) + lg.max(-1)
    want = float((logz - lg[np.arange(T - 1), ids[1:]]).mean())
    assert abs(ouro_ref.loss(MODEL, params, [ids]) - want) < 1e-4


def test_the_paged_kernels_floor_counts_the_cache_layers(monkeypatch):
    """A step of a looped model walks ``loops x layers`` cache layers: the
    same trace reads ``loops`` times the share it reads for the dense block
    of as many layers, whose reference counts none and falls to ``n_layer``."""
    from benchmark.readers import prog_roofline
    from benchmark.tests.test_program_trace import ctx_for, serve_trace

    model = dict(MODEL, n_layer=2, n_head=4, d_model=512)
    ctx = ctx_for(monkeypatch, serve_trace(), model)
    dense = prog_roofline.read(ctx, {"kernel": "paged_decode"})
    assert ctx.count("cache_layers")(model) == 2
    ctx.cell["config_file"]["reference"] = "ouro_ref"
    assert ctx.count("cache_layers")(model) == 6
    assert ctx.count("kv_bytes_per_token") is ouro_ref.kv_bytes_per_token
    assert prog_roofline.read(ctx, {"kernel": "paged_decode"}) \
        == pytest.approx(3 * dense)
