"""``reference/olmoe_ref.py`` at a tiny size on the CPU: against the block's
equations written out in numpy (float64, a token at a time, its 8 experts by
``argsort``, the unnormalised sum), what a handed-over choice does to the
logits and how its slack reads, what it refuses, and its counts against the
parameter tree. The program has no OLMoE yet, so there is no model to agree
with: the numpy below is the second opinion."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import olmoe_ref as ref
from benchmark.tools import routing_flips

MODEL = {"vocab_size": 256, "n_layer": 2, "n_head": 4, "d_model": 64,
         "d_ff": 32, "max_seq_len": 64, "num_experts": 64, "k": 8,
         "norm_topk_prob": False, "qk_norm": True, "tie_embeddings": False,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-5}


def make_params(model, seed=3):
    """At std 0.02 and width 64 the experts say next to nothing: larger
    weights, and gains off 1, or a wrong expert or a swapped gain would not
    show."""
    params = routing_flips.init_params(model, jax.random.PRNGKey(seed),
                                       dtype=jnp.float32, std=0.15)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape)
        if path[-1].key.endswith("_scale") else x
        for (path, x), k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def tiny():
    params = make_params(MODEL)
    ids = np.random.default_rng(0).integers(0, MODEL["vocab_size"], (2, 40),
                                            dtype=np.int32)
    return MODEL, params, ids


# ------------------------------------------------- the equations in numpy
def np_rms(x, g, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g


def np_forward(model, params, ids, fault=None):
    """Logits [T, V] in float64. ``fault``: ``wrong_expert`` (the 8th gives
    way to the weakest of all), ``seven_experts``, ``renormalised``."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    d, n_head, k = model["d_model"], model["n_head"], model["k"]
    dh, eps, t = d // n_head, model["rms_norm_eps"], len(ids)
    x = p["wte"][ids]
    half = dh // 2
    inv = model["rope_theta"] ** (-np.arange(half) / half)
    ang = np.arange(t)[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]

    def rot(a):
        lo, hi = a[..., :half], a[..., half:]
        return np.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)

    for layer in range(model["n_layer"]):
        w = jax.tree_util.tree_map(lambda a: a[layer], p["blocks"])
        h = np_rms(x, w["ln1_scale"], eps)
        qkv = h @ w["qkv_w"]
        q = rot(np_rms(qkv[:, :d], w["q_norm_scale"], eps).reshape(
            t, n_head, dh))
        kk = rot(np_rms(qkv[:, d:2 * d], w["k_norm_scale"], eps).reshape(
            t, n_head, dh))
        v = qkv[:, 2 * d:].reshape(t, n_head, dh)
        out = np.zeros((t, n_head, dh))
        for i in range(t):
            s = np.einsum("hd,shd->hs", q[i], kk[:i + 1]) / math.sqrt(dh)
            a = np.exp(s - s.max(-1, keepdims=True))
            out[i] = np.einsum("hs,shd->hd", a / a.sum(-1, keepdims=True),
                               v[:i + 1])
        x = x + out.reshape(t, d) @ w["attn_out_w"]
        h = np_rms(x, w["ln2_scale"], eps)
        ex = w["moe"]["experts"]
        y = np.zeros_like(x)
        for i in range(t):
            r = h[i] @ w["moe"]["gate_w"]
            prob = np.exp(r - r.max())
            prob /= prob.sum()
            order = np.argsort(-r)
            mine = list(order[:k])
            if fault == "wrong_expert":
                mine[-1] = order[-1]
            if fault == "seven_experts":
                mine = mine[:-1]
            gates = prob[mine]
            if fault == "renormalised":
                gates = gates / gates.sum()
            for e, g in zip(mine, gates):
                a, b = h[i] @ ex["gate_proj_w"][e], h[i] @ ex["up_w"][e]
                y[i] += g * ((a / (1 + np.exp(-a)) * b) @ ex["down_w"][e])
        x = x + y
    return np_rms(x, p["lnf_scale"], eps) @ p["lm_head"].T


def test_agrees_with_the_equations_in_numpy(tiny):
    model, params, ids = tiny
    one_layer = dict(model, n_layer=1)
    for m in (one_layer, model):
        got = np.asarray(ref.logits(m, params, ids[0]))
        want = np_forward(m, params, ids[0])
        assert np.abs(want).max() > 1.0
        assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


def test_positions_pick_rows(tiny):
    model, params, ids = tiny
    full = np.asarray(ref.logits(model, params, ids[1]))
    some = np.asarray(ref.logits(model, params, ids[1], positions=[5, 39]))
    np.testing.assert_allclose(some, full[[5, 39]], rtol=0, atol=1e-6)


@pytest.mark.parametrize("fault", ["wrong_expert", "seven_experts",
                                   "renormalised"])
def test_a_wrong_expert_layer_shows(tiny, fault):
    """The comparison has teeth: each moves float32 logits by far more than
    float32 rounding."""
    model, params, ids = tiny
    got = np.asarray(ref.logits(model, params, ids[0]))
    wrong = np_forward(model, params, ids[0], fault=fault)
    assert np.abs(got - wrong).max() > 1e-2


@pytest.mark.parametrize("wrong", [
    {"norm_topk_prob": True}, {"qk_norm": False}, {"tie_embeddings": True},
    {"attention_bias": True}, {"clip_qkv": 8.0}, {"n_kv_head": 2},
    {"rope_scaling": {"type": "linear"}}, {"shared_experts": 1}])
def test_refuses_what_it_does_not_cover(tiny, wrong):
    model, params, ids = tiny
    with pytest.raises(ValueError, match="olmoe_ref covers"):
        ref.logits(dict(model, **wrong), params, ids[0])


# --------------------------------------------------- choices handed over
def test_its_own_choices_change_nothing(tiny):
    model, params, ids = tiny
    x, own, slack = ref.forward(model, params, ids[0])
    own = np.asarray(own)
    assert own.shape == (40, 2, 8) and not np.asarray(slack).any()
    plain = np.asarray(ref.logits(model, params, ids[0]))
    for handed in ({7: own[7], 39: own[39]},
                   {7: own[7][:, ::-1]},                # a set has no order
                   {p: own[p] for p in range(40)}):
        same, slack = ref.logits(model, params, ids[0], choices=handed)
        assert np.array_equal(np.asarray(same), plain)  # bit for bit
        assert set(slack) == set(handed)
        assert all(s.shape == (2,) and not s.any() for s in slack.values())


def router_logits(model, params, ids, layer0):
    """Layer 0's router logits [T, E], from the reference's own pieces."""
    eps = model["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["wte"][ids]
        x = x + ref.attention(model, ref.rms_norm(x, layer0["ln1_scale"], eps),
                              layer0)
        h = ref.rms_norm(x, layer0["ln2_scale"], eps)
        return np.asarray(h), np.asarray(h @ layer0["moe"]["gate_w"])


def test_a_near_tie_swapped_reads_its_margin(tiny):
    """The 9th expert's router column is moved until it lies 1e-4 under the
    8th at position 11 of layer 0. Handing over the set with the two swapped
    reads a slack of 1e-4 (over the spread), and logits that differ."""
    model, params, ids = tiny
    layer0 = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    h, r = router_logits(model, params, ids[0], layer0)
    pos = 11
    order = np.argsort(-r[pos])
    a, b = order[7], order[8]
    shift = (r[pos, a] - 1e-4 - r[pos, b]) / float(h[pos] @ h[pos])
    gate_w = params["blocks"]["moe"]["gate_w"]
    moved = dict(params, blocks=dict(params["blocks"], moe=dict(
        params["blocks"]["moe"],
        gate_w=gate_w.at[0, :, b].add(shift * h[pos]))))
    _, r = router_logits(model, moved, ids[0],
                         jax.tree_util.tree_map(lambda x: x[0],
                                                moved["blocks"]))
    assert r[pos, a] - r[pos, b] == pytest.approx(1e-4, rel=0.05)

    own = np.asarray(ref.forward(model, moved, ids[0])[1])
    assert set(own[pos, 0]) == set(order[:8])
    swapped = own[pos].copy()
    swapped[0][list(swapped[0]).index(a)] = b
    plain = np.asarray(ref.logits(model, moved, ids[0], positions=[pos, 12]))
    other, slack = ref.logits(model, moved, ids[0], positions=[pos, 12],
                              choices={pos: swapped})
    other, slack = np.asarray(other), slack[pos]
    assert slack[0] * np.std(r[pos]) == pytest.approx(1e-4, rel=0.05)
    assert slack[1] == 0.0
    assert np.abs(other[0] - plain[0]).max() > 1e-3     # an expert's worth
    assert 0 < np.abs(other[1] - plain[1]).max()        # through attention


def test_the_weakest_expert_reads_the_logits_spread(tiny):
    model, params, ids = tiny
    layer0 = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    _, r = router_logits(model, params, ids[0], layer0)
    own = np.asarray(ref.forward(model, params, ids[0])[1])
    pos = 20
    handed = own[pos].copy()
    handed[0, 3] = np.argmin(r[pos])
    left_out = own[pos][0, 3]
    slack = ref.logits(model, params, ids[0], choices={pos: handed})[1][pos]
    want = (r[pos, left_out] - r[pos].min()) / np.std(r[pos])
    assert slack[0] == pytest.approx(want, rel=1e-4) and 2.0 < slack[0] < 6.0
    assert slack[0] > 10 * ref.CHOICE_SLACK


def test_leaving_out_the_strongest_reads_first_to_ninth(tiny):
    """Ranks 2 to 9 in place of 1 to 8 (an off-by-one in a sorted top-k): the
    weakest taken lies only the 8th-to-9th gap under the reference's own
    k-th, and the slack still reads the distance from the expert left out,
    the strongest, whatever the order the set comes in."""
    model, params, ids = tiny
    layer0 = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    _, r = router_logits(model, params, ids[0], layer0)
    own = np.asarray(ref.forward(model, params, ids[0])[1])
    for pos in (20, 39):
        order = np.argsort(-r[pos])
        handed = own[pos].copy()
        handed[0] = order[1:9][::-1]
        slack = ref.logits(model, params, ids[0],
                           choices={pos: handed})[1][pos]
        want = (r[pos, order[0]] - r[pos, order[8]]) / np.std(r[pos])
        near = (r[pos, order[7]] - r[pos, order[8]]) / np.std(r[pos])
        assert slack[0] == pytest.approx(want, rel=1e-4)
        assert slack[0] > 1.0 > ref.CHOICE_SLACK > near


@pytest.mark.parametrize("bad", [
    np.zeros((2, 7), np.int32),                         # wrong k
    np.zeros((1, 8), np.int32),                         # wrong n_layer
    np.tile(np.arange(8), (2, 1)) * 0,                  # one expert 8 times
    np.tile(np.arange(8), (2, 1)) + 60,                 # past the last expert
])
def test_malformed_choices_are_refused(tiny, bad):
    model, params, ids = tiny
    with pytest.raises(ValueError, match="choices at position"):
        ref.logits(model, params, ids[0], choices={3: bad})
    with pytest.raises(ValueError, match="choices at position"):
        ref.logits(model, params, ids[0],
                   choices={40: np.tile(np.arange(8), (2, 1))})


# ---------------------------------------------------------------- counts
def test_counts_against_the_parameter_tree(tiny):
    model, params, _ = tiny
    size = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa: E731
    blocks = params["blocks"]
    banks = size(blocks["moe"]["experts"])
    assert banks == model["n_layer"] * 64 * ref.expert_params(model)
    shared = (blocks["qkv_w"].size + blocks["attn_out_w"].size
              + blocks["moe"]["gate_w"].size + params["lm_head"].size)
    assert ref.shared_params(model) == shared
    gains = 4 * blocks["ln1_scale"].size + params["lnf_scale"].size
    assert size(params) == shared + banks + gains + params["wte"].size
    assert ref.kv_bytes_per_token(model) == 2 * 2 * 64 * 2
    assert ref.decode_step_bytes(model, 100.0) == (
        2 * (shared + banks) + 100 * ref.kv_bytes_per_token(model))
    assert ref.decode_step_bytes(model, 0.0, weight_dtype_bytes=1) == (
        shared + banks)
    cost = ref.expert_ffn_cost(model, rows=24, experts_touched=20)
    assert cost.flops == 2.0 * 24 * 3 * 64 * 32
    assert cost.bytes == 20 * 3 * 64 * 32 * 2 + 2 * 24 * 64 * 2


def test_counts_at_the_published_widths():
    """PERF.md section 7's sizing of the ``olmoe-1b-7b-serve`` cell: 64
    active tokens with 448 live keys and values each, 8 layers."""
    model = routing_flips.OLMOE
    assert ref.expert_params(model) * 64 == 402_653_184
    banks = 8 * 64 * ref.expert_params(model) * 2
    assert banks == pytest.approx(6.44e9, rel=2e-3)
    assert ref.shared_params(model) * 2 == pytest.approx(0.477e9, rel=2e-3)
    assert ref.kv_bytes_per_token(model) == 64 * 1024
    step = ref.decode_step_bytes(model, 64 * 448)
    assert step == banks + ref.shared_params(model) * 2 + 64 * 448 * 65536
    assert banks / step == pytest.approx(0.73, abs=0.01)
    # a step's expert layer: 512 rows on all 64 experts, bound by bytes
    cost = ref.expert_ffn_cost(model, rows=64 * 8, experts_touched=64)
    assert cost.bytes / cost.flops > 0.1
