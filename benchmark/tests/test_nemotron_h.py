"""The Nemotron-3-Nano files: the reference against a second, naive writing of
its layers in numpy (loops over tokens, heads and experts), the judging of
handed-over experts in the unit of score plus bias, the Mamba kernel's cost,
the readers on a run with nothing to read and on a hand-made one, the
family's step over pages and states (its third output, the states put back
as they were), and the judging of a mixer's state through its readings. (The reference's counts at the published widths and the
configuration's arithmetic are ``tests/test_hybrid_ssm_model.py``'s; the
family through ``run.py`` is ``test_rehearsal.py``'s: it runs
``tiny-nemotron-h-serve.tiny-closed`` as every rehearsal cell.)"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import kernel_cost_ssm, manifest
from benchmark.lib.peaks import device_peaks
from benchmark.reference import nemotron_h_ref as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


TINY = _config("tiny-nemotron-h-serve")["model"]


def _params(model, seed=0):
    """A tree in the reference's names, every leaf random (gains near 1): no
    function of the program."""
    rng = np.random.default_rng(seed)
    d = model["d_model"]
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    gn = model["n_groups"] * model["ssm_state_size"]
    inner, K = H * P, model["conv_kernel"]
    count, f = model["held_experts"][1], model["moe_d_ff"]

    def normal(*shape, by=0.3):
        return rng.normal(0, by, shape).astype(np.float32)

    stacks = {}
    for layer, kind in enumerate(model["hybrid_pattern"]):
        name, _ = ref.place(model, layer)
        if kind == "M":
            w = {"ln1_scale": 1 + normal(d) / 3,
                 "ssm_in_w": normal(d, 2 * inner + 2 * gn + H),
                 "ssm_conv_w": normal(K, inner + 2 * gn),
                 "ssm_conv_b": normal(inner + 2 * gn),
                 "ssm_dt_bias": normal(H) - 2, "ssm_A_log": normal(H),
                 "ssm_D": 1 + normal(H), "ssm_norm_scale": 1 + normal(inner),
                 "ssm_out_w": normal(inner, d)}
        elif kind == "*":
            dh = model["head_dim"]
            w = {"ln1_scale": 1 + normal(d) / 3,
                 "q_w": normal(d, model["n_head"] * dh),
                 "kv_w": normal(d, 2 * model["n_kv_head"] * dh),
                 "attn_out_w": normal(model["n_head"] * dh, d)}
        else:
            w = {"ln2_scale": 1 + normal(d) / 3,
                 "router_w": normal(d, model["n_routed_experts"]),
                 "router_bias": normal(model["n_routed_experts"], by=0.05),
                 "experts_up_w": normal(count, d, f),
                 "experts_down_w": normal(count, f, d, by=0.1),
                 "shared_up_w": normal(d, model["shared_d_ff"]),
                 "shared_down_w": normal(model["shared_d_ff"], d, by=0.1)}
        for k, v in w.items():
            stacks.setdefault(name, {}).setdefault(k, []).append(v)
    tree = {name: {k: np.stack(v) for k, v in stack.items()}
            for name, stack in stacks.items()}
    tree.update(wte=normal(model["vocab_size"], d),
                lm_head=normal(model["vocab_size"], d),
                lnf_scale=1 + normal(d) / 3)
    return tree


def _norm(x, gain, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def _silu(a):
    return a / (1 + np.exp(-a))


def _softplus(a):
    return np.log1p(np.exp(a))


def _naive(model, params, ids):
    """The docstring's equations in float64 numpy, a token, a head and an
    expert at a time. Returns (logits [T, V], experts [T, n_layer, k])."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    eps, T = model["rms_norm_eps"], len(ids)
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, G, K = model["ssm_state_size"], model["n_groups"], model["conv_kernel"]
    inner, gn = H * P, G * N
    x = p["wte"][ids]
    chosen = np.full((T, model["n_layer"], model["k"]), -1)
    for layer, kind in enumerate(model["hybrid_pattern"]):
        name, at = ref.place(model, layer)
        w = {k: v[at] for k, v in p[name].items()}
        out = np.zeros_like(x)
        if kind == "M":
            h = _norm(x, w["ln1_scale"], eps)
            state = np.zeros((H, P, N))
            rows = np.zeros((K - 1 + T, inner + 2 * gn))
            for t in range(T):
                proj = h[t] @ w["ssm_in_w"]
                z = proj[:inner]
                rows[K - 1 + t] = proj[inner:2 * inner + 2 * gn]
                dt = np.log1p(np.exp(proj[2 * inner + 2 * gn:]
                                     + w["ssm_dt_bias"]))
                conv = sum(rows[t + k] * w["ssm_conv_w"][k]
                           for k in range(K)) + w["ssm_conv_b"]
                conv = _silu(conv)
                y = np.zeros((H, P))
                for i in range(H):
                    g = i // (H // G)
                    xi = conv[i * P:(i + 1) * P]
                    b = conv[inner + g * N:inner + (g + 1) * N]
                    c = conv[inner + gn + g * N:inner + gn + (g + 1) * N]
                    a = -np.exp(w["ssm_A_log"][i])
                    state[i] = (np.exp(dt[i] * a) * state[i]
                                + dt[i] * np.outer(xi, b))
                    y[i] = state[i] @ c + w["ssm_D"][i] * xi
                y = y.reshape(-1) * _silu(z)
                for g in range(G):
                    part = slice(g * inner // G, (g + 1) * inner // G)
                    y[part] = y[part] / np.sqrt((y[part] ** 2).mean() + eps)
                out[t] = (y * w["ssm_norm_scale"]) @ w["ssm_out_w"]
        elif kind == "*":
            h = _norm(x, w["ln1_scale"], eps)
            heads, g, dh = model["n_head"], model["n_kv_head"], \
                model["head_dim"]
            q = (h @ w["q_w"]).reshape(T, heads, dh)
            kv = (h @ w["kv_w"]).reshape(T, 2, g, dh)
            o = np.zeros((T, heads, dh))
            for t in range(T):
                for i in range(heads):
                    j = i // (heads // g)
                    s = kv[:t + 1, 0, j] @ q[t, i] / np.sqrt(dh)
                    pr = np.exp(s - s.max())
                    o[t, i] = (pr / pr.sum()) @ kv[:t + 1, 1, j]
            out = o.reshape(T, -1) @ w["attn_out_w"]
        else:
            h = _norm(x, w["ln2_scale"], eps)
            first, count = model["held_experts"]
            for t in range(T):
                s = 1 / (1 + np.exp(-(h[t] @ w["router_w"])))
                top = np.argsort(-(s + w["router_bias"]),
                                 kind="stable")[:model["k"]]
                chosen[t, layer] = top
                for e in top:
                    if first <= e < first + count:
                        gate = (model["routed_scaling_factor"] * s[e]
                                / s[top].sum())
                        mid = np.maximum(h[t] @ w["experts_up_w"][e - first],
                                         0) ** 2
                        out[t] += gate * (mid @ w["experts_down_w"][e - first])
                mid = np.maximum(h[t] @ w["shared_up_w"], 0) ** 2
                out[t] += mid @ w["shared_down_w"]
        x = x + out
    return _norm(x, p["lnf_scale"], eps) @ p["lm_head"].T, chosen


@pytest.mark.parametrize("pattern", ["M", "ME*", "MEM*EME"])
def test_the_reference_is_its_equations_written_naively(pattern):
    model = dict(TINY, hybrid_pattern=pattern, n_layer=len(pattern))
    params = _params(model)
    ids = np.random.default_rng(3).integers(0, model["vocab_size"], 13)
    want, chosen = _naive(model, params, ids)
    got = np.asarray(ref.logits(model, params, ids))
    assert np.abs(got - want).max() < 1e-3 * np.abs(want).max()
    own = np.asarray(ref.forward(model, params, ids)[1])
    routed = [l for l, c in enumerate(pattern) if c == "E"]
    own, chosen = own[:, routed], chosen[:, routed]
    assert [sorted(r) for r in own.reshape(-1, model["k"]).tolist()] == \
        [sorted(r) for r in chosen.reshape(-1, model["k"]).tolist()]


def test_a_handed_choice_is_used_and_judged_in_the_unit_of_the_choice():
    params = _params(TINY, seed=4)
    ids = np.random.default_rng(5).integers(0, TINY["vocab_size"], 12)
    plain = np.asarray(ref.logits(TINY, params, ids))
    own = np.asarray(ref.forward(TINY, params, ids)[1])
    assert (own[:, 3] == -1).all() and (own[:, [1, 4, 6]] >= 0).all()
    assert np.isfinite(own[:, [0, 2, 5]].view(np.float32)).all()
    got, slack = ref.logits(TINY, params, ids, positions=[11],
                            choices={11: own[11]})
    assert np.abs(np.asarray(got[0]) - plain[11]).max() < 1e-5
    assert (slack[11] == 0).all()
    # another expert in layer 4: other logits at that position alone, and a
    # slack there that is the distance in score plus bias
    swapped = own[11].copy()
    left_out = next(e for e in range(16) if e not in own[11, 4])
    swapped[4, 0] = left_out
    got, slack = ref.logits(TINY, params, ids, positions=[10, 11],
                            choices={11: swapped})
    assert np.abs(np.asarray(got[0]) - plain[10]).max() < 1e-5
    assert np.abs(np.asarray(got[1]) - plain[11]).max() > 1e-4
    assert slack[11][4] > 0 and (slack[11][:4] == 0).all()
    assert slack[11][4] <= 1 + 0.5      # sigmoids in [0, 1] and a small bias
    bits = own[11].copy()
    bits[0] = -1                    # a mixer's row: -1's bits are no number
    for bad in (own[11][:, :2], np.where(own[11] < 0, 0, own[11]),
                np.full_like(own[11], 3), bits):
        with pytest.raises(ValueError):
            ref.logits(TINY, params, ids, choices={11: bad})
    assert ref.CHOICE_SLACK == 0.01


def test_the_mamba_kernel_is_bound_by_its_bytes():
    cost = kernel_cost_ssm.ssm_decode(1.0, 64, 64, 128, 8)
    state = 64 * 64 * 128
    assert cost.bytes == 4 * (2 * state + 2 * 4096 + 64 + 2 * 1024)
    assert cost.flops == 5 * state
    peaks = device_peaks("TPU v5 lite")
    assert cost.bound(peaks) == "bytes"
    # with the window the call shifts: K - 1 = 3 rows of 6144 read and
    # written and the step's row taken
    shifted = kernel_cost_ssm.ssm_decode(1.0, 64, 64, 128, 8, 3)
    assert shifted.bytes - cost.bytes == 4 * 7 * 6144
    many = kernel_cost_ssm.ssm_decode(4 * 512, 64, 64, 128, 8, 3)
    assert abs(many.floor_s(peaks) - 2048 * shifted.bytes
               / peaks.hbm_bytes_per_s) < 1e-12
    # four layers at 512 slots: 4.45 GB read and as much written, 11.1 ms
    assert 0.0109 < many.floor_s(peaks) < 0.0113


def _served(seed=0, slots=2, pages=5):
    """The family's programs over the tiny configuration: (family, cfg,
    float32 parameters, a cache of ``slots`` slots whose mixers' states are
    random)."""
    family = manifest.plugin("families", "nemotron_h")
    cfg = family.config(TINY)
    params = family.init_params(cfg, jax.random.PRNGKey(seed))
    assert params["moe_blocks"]["experts_up_w"].dtype == jnp.bfloat16
    from deepspeed_tpu.models import gpt

    cache = gpt.init_paged_cache(cfg, pages, 16, jnp.float32,
                                 ring_slots=slots)
    key = jax.random.PRNGKey(1)
    cache["ssm_state"] = jax.random.normal(key, cache["ssm_state"].shape)
    cache["ssm_conv"] = jax.random.normal(key, cache["ssm_conv"].shape)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return family, cfg, f32, cache


def test_the_familys_step_hands_over_experts_and_readings_and_leaves_the_states():
    from deepspeed_tpu.models import gpt

    family, cfg, f32, cache = _served()
    assert sorted(cache) == ["k_pages", "ssm_conv", "ssm_state", "v_pages"]
    assert cache["k_pages"].shape == (1, 2, 5, 16, 16)
    assert cache["ssm_state"].shape == (3, 2, 8, 8, 16)
    assert cache["ssm_conv"].shape == (3, 2, 3, 128)
    args = (jnp.zeros((2,), jnp.int32), cache, jnp.ones((2, 2), jnp.int32),
            jnp.asarray([3, 0]))
    out = family.paged_decode_step(cfg, f32, *args, impl="kernel")
    assert len(out) == 3 and sorted(out[1]) == sorted(cache)
    chosen = np.asarray(out[2])
    assert chosen.shape == (2, TINY["n_layer"], TINY["k"])
    kinds = {c: [l for l, k in enumerate(TINY["hybrid_pattern"]) if k == c]
             for c in "ME*"}
    assert (chosen[:, kinds["*"]] == -1).all()
    assert (chosen[:, kinds["E"]] >= 0).all()
    # the comparison's step can be made again: the states are as they were,
    # the keys and values written
    for name in ("ssm_state", "ssm_conv"):
        assert (np.asarray(out[1][name]) == np.asarray(cache[name])).all()
    assert np.asarray(out[1]["k_pages"][0, :, 1, 3]).any()
    # it is the program's own step, the kernel writing the states: the same
    # logits, and a mixer layer's row reads the states THAT step left,
    # through the reference's patterns
    logits, advanced = gpt.paged_decode_step(cfg, f32, *args, impl="kernel")
    assert (np.asarray(logits[0]) == np.asarray(out[0][0])).all()
    moved = np.asarray(advanced["ssm_state"]) != np.asarray(cache["ssm_state"])
    assert moved[:, 0].any() and not moved[:, 1].any()
    probes = ref.state_probes(TINY)
    for i, layer in enumerate(kinds["M"]):
        want = np.asarray(ref.read_state(probes, advanced["ssm_state"][i, 0],
                                         advanced["ssm_conv"][i, 0]))
        got = chosen[0, layer].view(np.float32)
        assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_the_familys_step_is_made_over_the_comparisons_slots_alone():
    """Six slots, five with a request: the first ``SEQUENCES`` are stepped
    (their logits those of the program's step over all six), every slot's
    state and window comes back as it went in, the rows past them zero."""
    from benchmark.lib.correct import SEQUENCES
    from deepspeed_tpu.models import gpt

    family, cfg, f32, cache = _served(slots=6, pages=6)
    tables = jnp.zeros((6, 2), jnp.int32).at[:5, 0].set(jnp.arange(1, 6))
    args = (jnp.arange(6, dtype=jnp.int32), cache, tables,
            jnp.asarray([3, 2, 5, 1, 4, 0]))
    logits, after, chosen = family.paged_decode_step(cfg, f32, *args,
                                                     impl="kernel")
    assert logits.shape[0] == 6 and chosen.shape[0] == 6 and SEQUENCES == 4
    for name in ("ssm_state", "ssm_conv"):
        assert (np.asarray(after[name]) == np.asarray(cache[name])).all()
    want, _ = gpt.paged_decode_step(cfg, f32, *args, impl="kernel")
    assert np.abs(np.asarray(logits[:4]) - np.asarray(want[:4])).max() < 1e-5
    assert not np.asarray(logits[4:]).any() and not np.asarray(chosen[4:]).any()


def _handed_states(model, params, ids, at, spoil=None):
    """The reference's own experts at position ``at`` with each mixer
    layer's row the readings of ``spoil(state, window)`` of the state and
    window a second writing of the recurrence (numpy, a token at a time)
    leaves there."""
    own = np.asarray(ref.forward(model, params, ids)[1])[at].copy()
    probes = ref.state_probes(model)
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, G, K = model["ssm_state_size"], model["n_groups"], model["conv_kernel"]
    inner, gn = H * P, G * N
    x = params["wte"][ids].astype(np.float32)
    for layer, kind in enumerate(model["hybrid_pattern"]):
        name, i = ref.place(model, layer)
        w = {k: np.asarray(v[i], np.float32) for k, v in params[name].items()}
        if kind == "M":
            h = _norm(x, w["ln1_scale"], model["rms_norm_eps"])
            proj = h @ w["ssm_in_w"]
            pre = proj[:, inner:2 * inner + 2 * gn]
            padded = np.concatenate([np.zeros((K - 1, pre.shape[1])), pre])
            state = np.zeros((H, P, N))
            for t in range(at + 1):
                xbc = _silu(sum(padded[t + k] * w["ssm_conv_w"][k]
                                for k in range(K)) + w["ssm_conv_b"])
                dt = _softplus(proj[t, 2 * inner + 2 * gn:]
                               + w["ssm_dt_bias"])
                b = np.repeat(xbc[inner:inner + gn].reshape(G, N), H // G, 0)
                state = (np.exp(-dt * np.exp(w["ssm_A_log"]))[:, None, None]
                         * state + (dt[:, None] * xbc[:inner].reshape(H, P))
                         [:, :, None] * b[:, None, :])
            window = padded[at + 1:at + K]
            if spoil:
                state, window = spoil(state, window)
            own[layer] = np.asarray(ref.read_state(
                probes, jnp.asarray(state, jnp.float32),
                jnp.asarray(window, jnp.float32))).view(np.int32)
        # the stream goes on as the reference computes it
        x = np.asarray(ref.forward(
            dict(model, hybrid_pattern=model["hybrid_pattern"][:layer + 1],
                 n_layer=layer + 1), params, ids)[0])
    return own


def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


SPOILED = {
    "as the recurrence leaves them": (None, True),
    "the state in bf16": (lambda s, w: (_bf16(s), w), False),
    "the window in bf16": (lambda s, w: (s, _bf16(w)), False),
    "the state decayed once more": (lambda s, w: (0.9 * s, w), False),
    "another slot's state": (lambda s, w: (s[::-1], w), False),
    "the window a row late": (lambda s, w: (s, np.roll(w, 1, 0)), False),
    "nothing absorbed": (lambda s, w: (0 * s, 0 * w), False),
}


@pytest.mark.parametrize("what", sorted(SPOILED))
def test_a_mixers_state_is_held_through_its_readings(what):
    """The readings a family hands over for a mixer layer are judged against
    the reference's own recurrence, in the slack's place: nothing where they
    are the recurrence's, over ``CHOICE_SLACK`` in the layers whose state or
    window is not."""
    spoil, honest = SPOILED[what]
    params = _params(TINY, seed=6)
    ids = np.random.default_rng(7).integers(0, TINY["vocab_size"], 14)
    mixers = [l for l, c in enumerate(TINY["hybrid_pattern"]) if c == "M"]
    handed = _handed_states(TINY, params, ids, 13, spoil)
    _, slack = ref.logits(TINY, params, ids, positions=[13],
                          choices={13: handed})
    apart = np.asarray(ref.forward(TINY, params, ids, {13: handed},
                                   distances=True)[2])[13]
    if honest:
        assert (slack[13] == 0).all() and apart[mixers].max() < 1e-5
    else:
        # the first mixer, which no routed layer precedes, is held tightly
        assert slack[13][mixers[0]] > ref.CHOICE_SLACK
        assert apart[mixers[0]] > ref.STATE_TOL["exact"]
    assert (apart[[l for l in range(TINY["n_layer"])
                   if l not in mixers]] == 0).all()


class _Nothing:
    """A run with no trace: ``program_trace.of`` finds nothing."""
    cell = {"config_file": _config("tiny-nemotron-h-serve")}
    trace = traced = None
    spans = None


NEW_READERS = {
    "prog_roofline_ssm": {"kernel": "ssm_decode"},
    "prog_scope_per": {"pattern": "^jit_decode_block_(\\d+)$",
                       "steps_group": 1, "scope": "ssm"},
    "decode_state_bw_util": {},
}


@pytest.mark.parametrize("reader", sorted(NEW_READERS))
def test_a_new_reader_reads_nothing_where_nothing_is(reader, monkeypatch):
    from benchmark.lib import program_trace

    monkeypatch.setattr(program_trace, "of", lambda ctx: None)
    module = manifest.plugin("readers", reader)
    assert module.read(_Nothing(), NEW_READERS[reader]) is None


class _Span:
    def __init__(self, **stats):
        self.stats = stats


class _Trace:
    reduced = object()

    def __init__(self, spans):
        self.spans = spans

    def named(self, name):
        return self.spans


def _ctx():
    ctx = _Nothing()
    ctx.cell = {"config_file": _config("nemotron-3-nano-serve")}
    ctx.model = ctx.cell["config_file"]["model"]
    ctx.device_kind = "TPU v5 lite"
    ctx.count = lambda name: getattr(ref, name)
    return ctx


def test_the_roofline_reader_counts_the_live_slots_states(monkeypatch):
    """A trace with the kernel and the program's counts: four Mamba layers,
    each live slot's state read and written once a step; a parent's spans,
    without the counts, give nothing."""
    from benchmark.lib import program_trace
    from benchmark.readers import prog_roofline_ssm

    ctx = _ctx()
    monkeypatch.setattr(prog_roofline_ssm, "_time_and_calls",
                        lambda pt, pattern: (0.1, 32))
    spans = [_Span(steps=4, active=500, state_slots=500, live_kv_tokens=9),
             _Span(steps=4, active=512, state_slots=512, live_kv_tokens=9)]
    monkeypatch.setattr(program_trace, "of", lambda c: _Trace(spans))
    got = prog_roofline_ssm.read(ctx, {"kernel": "ssm_decode"})
    one = kernel_cost_ssm.ssm_decode(1.0, 64, 64, 128, 8, 3).bytes
    peaks = device_peaks("TPU v5 lite")
    want = 100 * 4 * 4 * (500 + 512) * one / peaks.hbm_bytes_per_s / 0.1
    assert abs(got - want) < 1e-6 and 80 < got < 90
    monkeypatch.setattr(program_trace, "of", lambda c: _Trace(
        [_Span(steps=4, active=500, live_kv_tokens=9)]))
    assert prog_roofline_ssm.read(ctx, {"kernel": "ssm_decode"}) is None


def test_the_state_bandwidth_reader_counts_weights_rows_and_states(
        monkeypatch):
    from benchmark.lib import program_trace
    from benchmark.readers import decode_state_bw_util

    class Reduced:
        busy_in_span = {"decode": 0.08}

    ctx = _ctx()
    ctx.trace = Reduced()
    spans = [_Span(steps=2, active=512, state_slots=512,
                   live_kv_tokens=300_000)]
    monkeypatch.setattr(program_trace, "of", lambda c: _Trace(spans))
    got = decode_state_bw_util.read(ctx, {})
    need = sum(ref.decode_step_bytes(ctx.model, 300_000 + 512 * j,
                                     state_slots=512, active=512)
               for j in range(2))
    peaks = device_peaks("TPU v5 lite")
    assert abs(got - 100 * need / peaks.hbm_bytes_per_s / 0.08) < 1e-9
    assert 14.5e9 < need / 2 < 15.5e9 and 40 < got < 50
    # without the states the same step is 8.9 GB lighter
    assert abs(need / 2 - ref.decode_step_bytes(
        ctx.model, 300_256, active=512) - 2 * 512 * 8_683_520) < 1e6
    ctx.trace = None
    assert decode_state_bw_util.read(ctx, {}) is None
