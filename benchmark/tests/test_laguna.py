"""The Laguna-XS.2 files: the reference against a second, naive writing of its
layers in numpy, the judging of handed-over experts, the kernel's cost, the
reference's counts at the published widths, the configuration against the
issue's arithmetic, the family's third output over pages and rings, and the
readers on a run with nothing to read. (The family through ``run.py`` is
``test_rehearsal.py``'s: it runs ``tiny-laguna-serve.tiny-closed`` as every
rehearsal cell.)"""

import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import kernel_cost_gqa, manifest
from benchmark.lib.peaks import device_peaks
from benchmark.reference import laguna_ref as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


TINY = _config("tiny-laguna-serve")["model"]


def _params(model, seed=0):
    """A tree in the reference's names, every leaf N(0, 0.3) (gains near
    1): no function of the program."""
    rng = np.random.default_rng(seed)
    d, dh, g = model["d_model"], model["head_dim"], model["n_kv_head"]
    e, f = model["n_routed_experts"], model["moe_d_ff"]
    fs = model["shared_d_ff"]

    def normal(*shape):
        return rng.normal(0, 0.3, shape).astype(np.float32)

    stacks = {}
    for layer in range(model["n_layer"]):
        name, _ = ref.place(model, layer)
        h = model["num_attention_heads_per_layer"][layer]
        w = {"ln1_scale": 1 + normal(d) / 3, "ln2_scale": 1 + normal(d) / 3,
             "q_w": normal(d, h * dh), "kv_w": normal(d, 2 * g * dh),
             "attn_gate_w": normal(d, h), "attn_out_w": normal(h * dh, d)}
        if model["mlp_layer_types"][layer] == "dense":
            w.update(mlp_gate_w=normal(d, model["d_ff"]),
                     mlp_up_w=normal(d, model["d_ff"]),
                     mlp_down_w=normal(model["d_ff"], d))
        else:
            w.update(router_w=normal(d, e) * 3,
                     experts_gate_w=normal(e, d, f),
                     experts_up_w=normal(e, d, f),
                     experts_down_w=normal(e, f, d),
                     shared_gate_w=normal(d, fs), shared_up_w=normal(d, fs),
                     shared_down_w=normal(fs, d))
        stacks.setdefault(name, []).append(w)
    params = {name: {k: np.stack([w[k] for w in ws]) for k in ws[0]}
              for name, ws in stacks.items()}
    v = model["vocab_size"]
    params.update(wte=normal(v, d), lm_head=normal(v, d),
                  lnf_scale=1 + normal(d) / 3)
    return params


def _naive(model, params, ids):
    """The equations of the module docstring a token and a head at a time,
    float64: logits [T, V] and each layer's experts [T, n_layer, k]."""
    eps, d = model["rms_norm_eps"], model["head_dim"]
    g = model["n_kv_head"]

    def norm(x, gain):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain

    def silu(x):
        return x / (1 + np.exp(-x))

    def mlp(h, gate, up, down):
        return (silu(h @ gate) * (h @ up)) @ down

    def rotate(kind, vec, pos):
        rope = model["rope_parameters"][kind]
        rot = int(d * rope.get("partial_rotary_factor", 1))
        half = rot // 2
        theta = float(rope["rope_theta"])
        out = vec.copy()
        for i in range(half):
            freq = theta ** (-i / half)
            factor = 1.0
            if rope.get("rope_type") == "yarn":
                def dim(turns):
                    return (rot * math.log(
                        rope["original_max_position_embeddings"]
                        / (turns * 2 * math.pi)) / (2 * math.log(theta)))
                low = max(math.floor(dim(rope["beta_fast"])), 0)
                high = min(math.ceil(dim(rope["beta_slow"])), rot - 1)
                ramp = min(max((i - low) / (high - low), 0.0), 1.0)
                freq = freq / rope["factor"] * ramp + freq * (1 - ramp)
                factor = rope["attention_factor"]
            # the reference's frequencies are float32
            c = math.cos(pos * float(np.float32(freq))) * factor
            s = math.sin(pos * float(np.float32(freq))) * factor
            a, b = vec[i], vec[i + half]
            out[i], out[i + half] = a * c - b * s, b * c + a * s
        return out

    p64 = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    x = p64["wte"][np.asarray(ids)]
    t_len = len(ids)
    chosen = np.full((t_len, model["n_layer"], model["k"]), -1)
    for layer in range(model["n_layer"]):
        name, at = ref.place(model, layer)
        w = {k: a[at] for k, a in p64[name].items()}
        kind = model["layer_types"][layer]
        heads = model["num_attention_heads_per_layer"][layer]
        h = norm(x, w["ln1_scale"])
        q = (h @ w["q_w"]).reshape(t_len, heads, d)
        kv = (h @ w["kv_w"]).reshape(t_len, 2, g, d)
        for t in range(t_len):
            for i in range(heads):
                q[t, i] = rotate(kind, q[t, i], t)
            for j in range(g):
                kv[t, 0, j] = rotate(kind, kv[t, 0, j], t)
        gamma = 1 / (1 + np.exp(-(h @ w["attn_gate_w"])))
        out = np.zeros((t_len, heads, d))
        for t in range(t_len):
            lo = (max(0, t - model["sliding_window"] + 1)
                  if kind == "sliding_attention" else 0)
            for i in range(heads):
                j = i // (heads // g)
                s = kv[lo:t + 1, 0, j] @ q[t, i] / math.sqrt(d)
                p = np.exp(s - s.max())
                out[t, i] = gamma[t, i] * ((p / p.sum()) @ kv[lo:t + 1, 1, j])
        x = x + out.reshape(t_len, -1) @ w["attn_out_w"]
        h = norm(x, w["ln2_scale"])
        if model["mlp_layer_types"][layer] == "dense":
            x = x + mlp(h, w["mlp_gate_w"], w["mlp_up_w"], w["mlp_down_w"])
            continue
        y = mlp(h, w["shared_gate_w"], w["shared_up_w"], w["shared_down_w"])
        for t in range(t_len):
            r = h[t] @ w["router_w"]
            p = np.exp(r - r.max())
            p /= p.sum()
            top = np.argsort(-p, kind="stable")[:model["k"]]
            chosen[t, layer] = top
            for e in top:
                y[t] += (model["routed_scaling_factor"] * p[e] / p[top].sum()
                         * mlp(h[t], w["experts_gate_w"][e],
                               w["experts_up_w"][e], w["experts_down_w"][e]))
        x = x + y
    return norm(x, p64["lnf_scale"]) @ p64["lm_head"].T, chosen


@pytest.mark.parametrize("layers", ["a full and a window layer", "all five"])
def test_the_reference_is_its_equations_written_naively(layers):
    """Over prompts longer than the window, so that the mask bites, the
    rotary of both kinds, the groups of 2 and 3 queries a key-value head,
    the gate and the renormalised router."""
    model = dict(TINY)
    if layers != "all five":    # layers 0 (dense) and 1 (routed)
        model.update(n_layer=2, **{k: TINY[k][:2] for k in (
            "layer_types", "mlp_layer_types",
            "num_attention_heads_per_layer")})
    params = _params(model)
    ids = np.random.default_rng(3).integers(0, model["vocab_size"], 21)
    want, chosen = _naive(model, params, ids)
    got = np.asarray(ref.logits(model, params, ids))
    assert np.abs(got - want).max() < 2e-3 * np.abs(want).max()
    own = np.asarray(ref.forward(model, params, ids)[1])
    assert [sorted(r) for r in own.reshape(-1, model["k"]).tolist()] == \
        [sorted(r) for r in chosen.reshape(-1, model["k"]).tolist()]


def test_a_handed_choice_is_used_and_judged():
    params = _params(TINY, seed=4)
    ids = np.random.default_rng(5).integers(0, TINY["vocab_size"], 12)
    plain = np.asarray(ref.logits(TINY, params, ids))
    own = np.asarray(ref.forward(TINY, params, ids)[1])
    got, slack = ref.logits(TINY, params, ids, positions=[11],
                            choices={11: own[11]})
    assert np.abs(np.asarray(got[0]) - plain[11]).max() < 1e-5
    assert (slack[11] == 0).all()
    # another expert in layer 2: other logits at that position alone, and a
    # slack from that layer on (the stream moved under the later layers' sets)
    swapped = own[11].copy()
    swapped[2, 0] = next(e for e in range(16) if e not in own[11, 2])
    got, slack = ref.logits(TINY, params, ids, positions=[10, 11],
                            choices={11: swapped})
    assert np.abs(np.asarray(got[0]) - plain[10]).max() < 1e-5
    assert np.abs(np.asarray(got[1]) - plain[11]).max() > 1e-3
    assert slack[11][2] > 0 and (slack[11][:2] == 0).all()
    for bad in (own[11][:, :3], np.where(own[11] < 0, 0, own[11]),
                np.full_like(own[11], 3)):
        with pytest.raises(ValueError):
            ref.logits(TINY, params, ids, choices={11: bad})
    assert ref.CHOICE_SLACK == 0.12


def test_the_gqa_kernel_is_bound_by_its_bytes():
    cost = kernel_cost_gqa.paged_decode_gqa(1.0, 48, 8, 128)
    assert cost.flops == 24576 and cost.bytes == 4096
    assert kernel_cost_gqa.paged_decode_gqa(1.0, 64, 8, 128).flops == 32768
    peaks = device_peaks("TPU v5 lite")
    assert cost.bound(peaks) == "bytes"
    many = kernel_cost_gqa.paged_decode_gqa(1e5, 64, 8, 128)
    assert abs(many.floor_s(peaks) - 4096e5 / peaks.hbm_bytes_per_s) < 1e-12


def test_the_cut_is_the_issues_arithmetic():
    config = _config("laguna-xs.2-serve")
    model = config["model"]
    assert ref.attention_params(model, 0) == 29_458_432
    assert ref.attention_params(model, 1) == 37_879_808
    assert ref.expert_params(model) == 3_145_728
    assert abs(ref.held_params(model) / 1e9 - 3.870) < 0.001
    assert abs(ref.held_params(model) * 2 / 1e9 - 7.74) < 0.005
    assert ref.cache_layers(model) == 5
    assert ref.kv_row_bytes(model) == 4096
    assert ref.kv_bytes_per_token(model) == 2 * 4096
    assert ref.ring_bytes_per_slot(model) == 3 * 512 * 4096
    # the whole model from the same counts: 33.443 B
    whole = dict(model, n_layer=40,
                 layer_types=(model["layer_types"][:4] * 10),
                 num_attention_heads_per_layer=[48, 64, 64, 64] * 10,
                 mlp_layer_types=["dense"] + ["sparse"] * 39)
    assert abs(ref.held_params(whole) / 1e9 - 33.443) < 0.001
    # a decode step of 48 tokens: 78% of each routed layer's experts, the
    # full layers' live rows and the window layers' rows inside the window
    touched = 1 - (1 - 8 / 256) ** 48
    assert abs(touched - 0.782) < 0.001
    step = ref.decode_step_bytes(model, 48 * 3970, 48 * 512, active=48)
    experts = 4 * 256 * 3_145_728
    rest = ref.held_params(model) - 100352 * 2048 - experts
    assert abs(step - ((rest + touched * experts) * 2
                       + (2 * 48 * 3970 + 3 * 48 * 512) * 4096)) < 1e3
    assert ref.decode_step_bytes(model, 1000.0) == (
        ref.held_params(model) - 100352 * 2048) * 2 + 5 * 1000 * 4096
    # what the file says it reduced is what differs from the source's keys
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "mlp_layer_types",
                                 "num_attention_heads_per_layer"]
    assert config["num_hidden_layers"] == 5
    assert config["published"]["num_hidden_layers"] == 40
    assert config["layer_types"] == model["layer_types"] == [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    for key in ("gate", "router", "norms", "activation", "rotary", "window",
                "initialisation", "layers"):
        assert config["assumed"][key]
    for ours, theirs in (("d_model", "hidden_size"),
                         ("d_ff", "intermediate_size"),
                         ("n_kv_head", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("n_routed_experts", "num_experts"),
                         ("k", "num_experts_per_tok"),
                         ("moe_d_ff", "moe_intermediate_size"),
                         ("shared_d_ff", "shared_expert_intermediate_size"),
                         ("routed_scaling_factor",
                          "moe_routed_scaling_factor"),
                         ("sliding_window", "sliding_window"),
                         ("vocab_size", "vocab_size")):
        assert model[ours] == config[theirs], ours
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Laguna-XS.2")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
            else:
                assert config[key] == value[:5] if isinstance(value, list) \
                    else config[key] == 5
    traffic = json.load(open(os.path.join(ROOT, "traffic",
                                          "mixed-decode.json")))
    assert traffic["pages"] == traffic["slots"] * 9216 // 64 + 1 == 6913
    assert max(traffic["prompt_lens"]) + max(traffic["output_lens"]) == \
        config["engine"]["max_model_len"]


def test_the_familys_step_hands_over_the_experts_it_chose():
    family = manifest.plugin("families", "laguna")
    cfg = family.config(TINY)
    params = family.init_params(cfg, jax.random.PRNGKey(0))
    assert params["moe_blocks_window"]["experts_up_w"].dtype == jnp.bfloat16
    from deepspeed_tpu.models import gpt

    cache = gpt.init_paged_cache(cfg, 5, 16, jnp.float32, ring_slots=2)
    assert sorted(cache) == ["k_pages", "k_ring", "v_pages", "v_ring"]
    assert cache["k_pages"].shape == (2, 2, 5, 16, 16)
    assert cache["k_ring"].shape == (3, 2, 2, 16, 16)
    out = family.paged_decode_step(
        cfg, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params),
        jnp.zeros((2,), jnp.int32), cache, jnp.ones((2, 2), jnp.int32),
        jnp.asarray([3, 0]), impl="gather")
    assert len(out) == 3 and sorted(out[1]) == sorted(cache)
    chosen = np.asarray(out[2])
    assert chosen.shape == (2, TINY["n_layer"], TINY["k"])
    assert (chosen[:, 0] == -1).all() and (chosen[:, 1:] >= 0).all()
    assert all(len(set(row)) == TINY["k"]
               for row in chosen[:, 1:].reshape(-1, TINY["k"]).tolist())
    # the slot without a request left its ring as it was
    assert not np.asarray(out[1]["k_ring"][:, :, 1]).any()
    assert np.asarray(out[1]["k_ring"][:, :, 0, 3]).any()


class _Nothing:
    """A run with no trace: ``program_trace.of`` finds nothing."""
    cell = {"config_file": _config("tiny-laguna-serve")}
    trace = traced = None
    spans = None


def test_the_new_readers_read_nothing_where_nothing_is(monkeypatch):
    from benchmark.lib import program_trace
    from benchmark.readers import prog_roofline_gqa, prog_span_ratio

    monkeypatch.setattr(program_trace, "of", lambda ctx: None)
    assert prog_roofline_gqa.read(_Nothing(), {"kernel": "paged_decode_gqa"}) \
        is None
    assert prog_span_ratio.read(_Nothing(), {
        "span": "serve.decode", "of": "kv_rows_window",
        "over": "live_kv_tokens"}) is None


def test_the_roofline_reader_counts_each_layers_rows(monkeypatch):
    """A trace with the kernel and the program's counts: 2 full layers over
    the live rows (and a block's appended tokens), 3 window layers over the
    rows inside the window; a parent's spans, without the counts, give
    nothing."""
    from benchmark.lib import program_trace
    from benchmark.readers import prog_roofline, prog_roofline_gqa

    class Span:
        def __init__(self, **stats):
            self.stats = stats

    class Trace:
        reduced = object()

        def __init__(self, spans):
            self.spans = spans

        def named(self, name):
            return self.spans

    ctx = _Nothing()
    ctx.cell = {"config_file": _config("laguna-xs.2-serve")}
    ctx.model = ctx.cell["config_file"]["model"]
    ctx.device_kind = "TPU v5 lite"
    monkeypatch.setattr(prog_roofline, "_time_and_calls",
                        lambda pt, pattern: (1e-3, 10))
    monkeypatch.setattr(prog_roofline_gqa, "_time_and_calls",
                        lambda pt, pattern: (1e-3, 10))
    spans = [Span(steps=2, active=4, kv_rows_full=1000, kv_rows_window=700,
                  live_kv_tokens=1000)]
    monkeypatch.setattr(program_trace, "of", lambda c: Trace(spans))
    got = prog_roofline_gqa.read(ctx, {"kernel": "paged_decode_gqa"})
    rows = 2 * (1004 + 1008) + 3 * 2 * 700
    peaks = device_peaks("TPU v5 lite")
    assert abs(got - 100 * rows * 4096 / peaks.hbm_bytes_per_s / 1e-3) < 1e-6
    monkeypatch.setattr(program_trace, "of", lambda c: Trace(
        [Span(steps=2, active=4, live_kv_tokens=1000)]))
    assert prog_roofline_gqa.read(ctx, {"kernel": "paged_decode_gqa"}) is None
