"""The LongCat-Flash files: the reference's counts at the published widths,
the configuration against the catalog's row and the issue's arithmetic, the
family's third output and its draw, the reference's ``choices`` and slack (a
zero-compute expert is a choice like any other), the traffic's pool, the
drift tool's cut to 8 bits, and the new metric files on a run with nothing to
read."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import kernel_cost_mla, manifest
from benchmark.lib.peaks import device_peaks
from benchmark.reference import longcat_flash_ref as ref
from benchmark.tools import longcat_drift

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "longcat-flash-omni-serve.answer-decode"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
ROW = {"attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
       "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
       "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
       "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
       "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
       "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
       "n_routed_experts": 512, "max_position_embeddings": 131072,
       "rms_norm_eps": 1e-05, "rope_theta": 10000000,
       "attention_method": "MLA", "zero_expert_num": 256,
       "zero_expert_type": "identity", "moe_topk": 12}


def _json(kind, name):
    with open(os.path.join(ROOT, kind, f"{name}.json")) as f:
        return json.load(f)


def test_at_64_heads_the_latent_kernel_is_bound_by_bytes():
    cost = kernel_cost_mla.paged_decode_mla(1.0, 64, 512, 64)
    peaks = device_peaks("TPU v5 lite")
    assert cost.bytes == 1152 and cost.bound(peaks) == "bytes"
    assert cost.flops / cost.bytes < peaks.bf16_flops / peaks.hbm_bytes_per_s


def test_the_cut_is_the_issues_arithmetic():
    config = _json("configs", "longcat-flash-omni-serve")
    model = config["model"]
    assert abs(ref.attention_params(model) / 1e6 - 90.57) < 0.01
    assert abs(3 * 6144 * 12288 / 1e6 - 226.5) < 0.01
    assert abs(ref.expert_params(model) / 1e6 - 37.75) < 0.01
    outside = (2 * ref.attention_params(model) + 2 * 3 * 6144 * 12288
               + 6144 * 768)
    assert abs(outside / 1e6 - 638.9) < 0.1
    assert abs(ref.layer_params(model) / 1e6 - 1242.8) < 0.1
    assert abs(ref.held_params(model) / 1e9 - 5.173) < 0.001
    assert abs(2 * ref.held_params(model) / 1e9 - 10.35) < 0.01
    whole = outside + 512 * ref.expert_params(model)
    assert abs(whole / 1e9 - 19.97) < 0.01      # a layer no chip holds
    assert ref.outputs(model) == 768 and ref.cache_layers(model) == 8
    assert ref.kv_bytes_per_token(model) == 8 * 576 * 2
    # a decode step at 256 slots and a mean context of 768: the weights but
    # the embedding table, and the live latent rows
    step = ref.decode_step_bytes(model, 256 * 768)
    assert step == (ref.held_params(model) - 16384 * 6144) * 2 \
        + 256 * 768 * 9216
    assert abs(4 * 16 * ref.expert_params(model) * 2 / 1e9 - 4.83) < 0.01
    assert abs((1 - 12 / 768) ** 256 - 0.0177) < 0.0005
    # every number of the row under its key, but what the file says it cut
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    for key, value in ROW.items():
        if key in config["reduced"]:
            assert config["published"][key] == value != config[key]
        else:
            assert config[key] == value, key
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 16, 16384)
    assert model["n_routed_experts"] == 512 and model["held_experts"] == [0, 16]
    assert "tie_word_embeddings" in config["assumed"]
    assert "10.35 GB" in config["deployment"]
    traffic = _json("traffic", "answer-decode")
    assert traffic["pages"] == traffic["slots"] * 1536 // 64 + 1 == 6145
    assert max(traffic["prompt_lens"]) + max(traffic["output_lens"]) == \
        config["engine"]["max_model_len"] == 1536
    assert traffic["pages"] * 64 * 8 * 640 * 2 == 4_027_187_200
    # an expert meets what it meets in the deployment at 8 slots a chip
    assert traffic["slots"] * model["k"] / ref.outputs(model) == 4.0


def test_the_cell_lists_what_it_reports():
    bench = manifest.listed()
    end_to_end, per_layer = manifest.reported(bench, CELL)
    assert end_to_end == ["out_tok_s", "setup_s"]
    assert {"zero_expert_pct", "routed_branch_ms", "dense_ffn_ms",
            "pattern_attn_ms", "lm_head_ms", "mla_decode_roofline_pct",
            "grouped_kernel_pct", "expert_local_pct"} <= set(per_layer)
    # dead since PR 42, and a reader that divides by the passes of a loop
    assert not {"moe_experts_ms", "mla_attn_ms"} & set(per_layer)
    for name in ("zero_expert_pct", "routed_branch_ms", "dense_ffn_ms"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "out_tok_s"
        metric = manifest.load_metric(name)
        assert (metric["unit"], metric["better"], metric["source"],
                metric["layer"]) == (entry["unit"], entry["better"],
                                     entry["source"], entry["layer"])
        manifest.plugin("readers", metric["reader"])


def test_the_familys_step_hands_over_the_outputs_it_chose():
    family = manifest.plugin("families", "longcat_flash")
    model = _json("configs", "tiny-longcat-flash-serve")["model"]
    cfg = family.config(model)
    params = family.init_params(cfg, jax.random.PRNGKey(0))
    assert params["moe_blocks"]["experts_up_w"].dtype == jnp.bfloat16
    assert params["moe_blocks"]["sub1_mlp_up_w"].dtype == jnp.bfloat16
    from deepspeed_tpu.models import gpt

    assert gpt.cache_layers(cfg) == ref.cache_layers(model) == 4
    cache = gpt.init_paged_cache(cfg, 5, 16, jnp.float32)
    out = family.paged_decode_step(
        cfg, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params),
        jnp.zeros((2,), jnp.int32), cache, jnp.ones((2, 2), jnp.int32),
        jnp.asarray([3, 0]), impl="gather")
    assert len(out) == 3
    chosen = np.asarray(out[2])
    assert chosen.shape == (2, model["n_layer"], model["k"])
    assert (chosen >= 0).all() and (chosen < ref.outputs(model)).all()
    assert all(len(set(row)) == model["k"]
               for row in chosen.reshape(-1, model["k"]).tolist())


def test_a_zero_expert_is_a_choice_like_any_other():
    """``logits(..., choices=)``: the reference's own choice handed back
    reads slack 0 and the same logits; a real expert swapped for the
    strongest output left out (a zero-compute one or not) reads the gap
    between them over the token's spread of ``p + b``; an index past the
    router's width is an error."""
    family = manifest.plugin("families", "longcat_flash")
    model = _json("configs", "tiny-longcat-flash-serve")["model"]
    cfg = family.config(model)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        family.init_params(cfg, jax.random.PRNGKey(3)))
    params["moe_blocks"]["router_w"] = params["moe_blocks"]["router_w"] * 40
    ids = np.random.default_rng(0).integers(0, 256, 70).astype(np.int32)
    _, own, _ = ref.forward(model, params, ids)
    own = np.asarray(own)
    real = model["n_routed_experts"]
    assert (own >= real).any() and (own < real).any()
    # a position whose first layer took a zero-compute expert
    at = next(t for t in range(69, 0, -1) if (own[t, 0] >= real).any())
    out, slack = ref.logits(model, params, ids, positions=[at],
                            choices={at: own[at]})
    assert not slack[at].any() and slack[at].shape == (model["n_layer"],)
    same = ref.logits(model, params, ids, positions=[at])
    assert np.abs(np.asarray(out) - np.asarray(same)).max() < 1e-6
    # swap that zero expert of layer 0 for the last output it left out
    swapped = own[at].copy()
    left_out = next(e for e in range(ref.outputs(model) - 1, -1, -1)
                    if e not in swapped[0])
    swapped[0, int(np.argmax(swapped[0] >= real))] = left_out
    moved, slack = ref.logits(model, params, ids, positions=[at],
                              choices={at: swapped})
    assert slack[at][0] > 0     # (the later layers' own choices may move)
    assert np.abs(np.asarray(moved) - np.asarray(same)).max() > 1e-4
    wide = own[at].copy()
    wide[0, 0] = ref.outputs(model)
    with pytest.raises(ValueError, match="different outputs"):
        ref.logits(model, params, ids, choices={at: wide})
    assert ref.CHOICE_SLACK > 0


class _Nothing:
    """A run with no trace: ``program_trace.of`` finds nothing."""
    cell = {"config_file": _json("configs", "tiny-longcat-flash-serve")}
    trace = traced = None
    spans = None


def test_the_draw_is_the_familys_and_the_assumed_one():
    """Small init at the width and a bias of half a mean probability, both
    said by the family's file and not by the program (``gpt.init_params``
    draws 0.02 unless told)."""
    config = _json("configs", "tiny-longcat-flash-serve")
    family = manifest.family_of(config)
    cfg = family.config(config["model"])
    params = family.init_params(cfg, jax.random.PRNGKey(0))
    layers = params["moe_blocks"]
    outputs = cfg.moe_experts + cfg.moe_zero_experts
    assert family.init_std(6144) == pytest.approx(0.00807, rel=1e-3)
    for name in ("wte", "lm_head"):
        assert float(jnp.std(params[name].astype(jnp.float32))) == \
            pytest.approx(family.init_std(cfg.d_model), rel=0.05)
    assert float(jnp.std(layers["router_w"].astype(jnp.float32))) == \
        pytest.approx(family.init_std(cfg.d_model), rel=0.1)
    assert layers["router_bias"].shape == (cfg.n_layer, outputs)
    assert float(jnp.std(layers["router_bias"])) == pytest.approx(
        0.5 / outputs, rel=0.3)
    assert float(jnp.abs(layers["router_bias"]).min()) > 0


def test_the_control_cuts_a_number_to_three_bits_of_mantissa():
    """``cut_to_8_bits``: float8_e4m3's precision at bf16's range, rounded to
    nearest, so at most 2**-4 from the number it cut."""
    x = np.random.default_rng(0).normal(size=4096).astype(np.float32)
    x = np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)
    cut = np.asarray(longcat_drift.cut_to_8_bits(jnp.asarray(x)), np.float32)
    mantissa, _ = np.frexp(cut)
    assert np.all(mantissa * 16 == np.round(mantissa * 16))
    off = np.abs(cut - x) / np.abs(x)
    assert 2.0 ** -6 < off.max() <= 2.0 ** -4
    assert np.array_equal(longcat_drift.cut_to_8_bits(jnp.asarray(cut)), cut)


def test_the_new_metrics_read_nothing_where_nothing_is(monkeypatch):
    from benchmark.lib import program_trace

    monkeypatch.setattr(program_trace, "of", lambda ctx: None)
    for name in ("zero_expert_pct", "routed_branch_ms", "dense_ffn_ms"):
        metric = manifest.load_metric(name)
        reader = manifest.plugin("readers", metric["reader"])
        assert reader.read(_Nothing(), metric["params"]) is None
