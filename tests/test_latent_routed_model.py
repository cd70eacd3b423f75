"""A model with latent attention and routed layers on the normal path
(``models/gpt.py`` with its attention sublayer, rotary scaling, router and
kinds of layer said as data; ``moe/dropless.py``; the latent page pool and
``paged_decode_mla``) against the benchmark's plain reference of those
equations, ``benchmark/reference/deepseek_v2_ref.py``: ``served_contract.py``
bound to the family, and what is the family's own.

Seeded random weights at the rehearsal configuration's size
(``benchmark/configs/tiny-deepseek-v2-serve.json``: a leading dense layer and
two routed ones, 16 experts in 4 groups of which 2 groups and 3 experts a
token, a shared expert, YaRN on, nope 16 / rope 8 / value 12), in float32 on
the CPU. ``TOL`` = 2e-5 on logits of size 1: both sides are float32 and sum
in another order; what was read is 2e-6 at most. A fault of the kinds planted
at the end moves a logit by 2e-4 or more (a router rounded to bf16, the
least of them; the others 1e-3 to 1).
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import deepseek_v2 as family
from benchmark.reference import deepseek_v2_ref as ref
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.moe import dropless
from served_contract import (ServedFamilyContract, config_file, moved,
                             refuses)

MODEL = config_file("tiny-deepseek-v2-serve")["model"]
WHOLE = dict(MODEL, held_experts=[0, MODEL["n_routed_experts"]])
CFG = family.config(MODEL)          # experts 0-7 of 16 held
CFG_WHOLE = family.config(WHOLE)
PAGE = ServedFamilyContract.ENGINE["page_size"]
TOL = ServedFamilyContract.TOL


@functools.cache
def _whole():
    return moved(G.init_params(CFG_WHOLE, jax.random.PRNGKey(0)))


# ------------------------------------------------------------ planted faults
def _bf16_router(monkeypatch):
    route = dropless.route
    monkeypatch.setattr(dropless, "route", lambda logits, *a, **kw: route(
        logits.astype(jnp.bfloat16).astype(jnp.float32), *a, **kw))
    return CFG


def _unnormed_latent(monkeypatch):
    norm = G.rms_norm
    monkeypatch.setattr(G, "rms_norm", lambda x, scale, eps: (
        x if scale.shape[-1] == CFG.kv_lora_rank else norm(x, scale, eps)))
    return CFG


def _renormalised_gates(monkeypatch):
    def route(logits, k, groups, topk_groups, scale, norm_topk=False):
        probs = jax.nn.softmax(logits, axis=-1)
        chosen = dropless.group_limited_topk(probs, k, groups, topk_groups)
        gates = jnp.take_along_axis(probs, chosen, axis=1)
        return chosen, gates / gates.sum(-1, keepdims=True) * scale
    monkeypatch.setattr(dropless, "route", route)
    return CFG


FAULTS = {
    "a router in bf16": _bf16_router,
    "no scaling factor": lambda mp: dataclasses.replace(CFG, moe_scale=1.0),
    "no shared expert": lambda mp: dataclasses.replace(CFG,
                                                       moe_shared_d_ff=0),
    "an un-normalised latent": _unnormed_latent,
    "a wrong YaRN scale": lambda mp: dataclasses.replace(
        CFG, rope_scaling=dataclasses.replace(CFG.rope_scaling,
                                              mscale_all_dim=0.0)),
    "renormalised gates": _renormalised_gates,
    "a cache in bf16": lambda mp: (CFG, jnp.bfloat16),
}


class TestDeepseekV2(ServedFamilyContract):
    FAMILY, REF, CONFIG = family, ref, "tiny-deepseek-v2-serve"
    FORWARDS = {"a share": (40, 0), "whole": (40, 0)}
    PATHS = {"fused": [20], "batch": [9, 30], "chunked": [70],
             "exactly 2 chunks, and a fused prompt": [64, 20]}
    STEPS = 5
    WIDE = {}           # the family's config is the float32 stream
    FAULTS = FAULTS
    NEW_FIELDS = {"attn_kind": "mla", "rope_scaling": CFG.rope_scaling,
                  "moe_experts": 16, "moe_held": (0, 8)}
    REFUSES = refuses("attn_kind=", but=(
        "gpt_moe", "initialize over pipeline stages"))
    test_a_mixed_run_with_a_preemption_leaves_a_clean_audit = None

    def forward_case(self, forward, params):
        return ((CFG, MODEL, params) if forward == "a share"
                else (CFG_WHOLE, WHOLE, _whole()))

    def the_tree(self, params):
        assert sorted(params) == ["blocks", "lm_head", "lnf_scale",
                                  "moe_blocks", "wte"]
        attention = ["attn_out_w", "kv_a_norm_scale", "kv_a_w", "kv_b_w",
                     "ln1_scale", "ln2_scale", "q_a_norm_scale", "q_a_w",
                     "q_b_w"]
        assert sorted(params["blocks"]) == sorted(
            attention + ["mlp_down_w", "mlp_gate_w", "mlp_up_w"])
        assert sorted(params["moe_blocks"]) == sorted(attention + [
            "router_w", "experts_gate_w", "experts_up_w", "experts_down_w",
            "shared_gate_w", "shared_up_w", "shared_down_w"])
        assert params["blocks"]["mlp_up_w"].shape == (1, 64, 96)
        assert params["moe_blocks"]["router_w"].shape == (2, 64, 16)
        assert params["moe_blocks"]["experts_gate_w"].shape == (2, 8, 64, 20)
        assert params["moe_blocks"]["shared_down_w"].shape == (2, 40, 64)
        assert params["blocks"]["kv_b_w"].shape == (1, 32, 4 * (16 + 12))
        matrices = sum(x.size for x in jax.tree_util.tree_leaves(params)
                       if x.ndim > 1 and x.shape[-1] > 1 and x.ndim >= 2
                       and not (x.ndim == 2 and x.shape[0] in (1, 2)))
        assert matrices == ref.held_params(MODEL)
        assert CFG.stream_float32 and CFG.linear_out_float32

    def the_sizes(self):
        from deepspeed_tpu.runtime import aot

        assert G.cache_row(CFG) == (1, 1, 128) and CFG.latent_width == 128
        assert G.cache_row(G.PRESETS["tiny"]) == (2, 4, 16)
        real = family.config(config_file("deepseek-v2-serve")["model"])
        assert real.latent_width == 640 and G.cache_row(real) == (1, 1, 640)
        assert G.paged_kv_bytes_per_token(real) == 5 * 640 * 2
        assert sorted(G.init_paged_cache(CFG, 9, PAGE)) == ["k_pages"]
        assert G.init_paged_cache(CFG, 9, PAGE)["k_pages"].shape == \
            (3, 1, 9, PAGE, 128)
        spec = aot.speculation_hbm_bytes("tiny", draft_model=CFG, num_slots=2,
                                         max_model_len=32, spec_k=2)
        assert spec["parts"]["draft_cache"] == 3 * 2 * 32 * 128 * 2
        # the algorithm's count is the reference's: the pool pads the row
        assert ref.kv_bytes_per_token(MODEL) == 3 * (32 + 8) * 2

    def test_absorbed_attention_is_the_unabsorbed_function(self, params):
        """``_mla_attention`` both ways over the same cached rows, a chunk
        and a single token, against each other."""
        w = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
        rng = np.random.default_rng(1)
        rows = jnp.asarray(rng.normal(size=(2, 24, CFG.latent_width)),
                           jnp.float32)
        for t in (1, 7):
            q = jnp.asarray(rng.normal(size=(2, t, 4, 24)), jnp.float32)
            positions = jnp.asarray([[23 - t + 1 + i for i in range(t)]] * 2)
            a = G._mla_attention(CFG, q, rows, w["kv_b_w"], positions, True)
            b = G._mla_attention(CFG, q, rows, w["kv_b_w"], positions, False)
            assert a.shape == (2, t, 4, 12)
            assert np.abs(np.asarray(a) - np.asarray(b)).max() < 2e-5
        # taken a group of heads at a time, the same
        old, G._MLA_SCORE_BYTES = G._MLA_SCORE_BYTES, 1
        try:
            c = G._mla_attention(CFG, q, rows, w["kv_b_w"], positions, False)
        finally:
            G._MLA_SCORE_BYTES = old
        assert np.abs(np.asarray(c) - np.asarray(b)).max() < 1e-6

    def test_the_slack_asks_both_choices_and_refuses_what_the_rule_cannot_give(
            self, params):
        r = jnp.asarray(np.random.default_rng(9).normal(size=(1, 16)),
                        jnp.float32)
        member, top = ref.own_choice(MODEL, jax.nn.softmax(r, axis=-1))
        top = np.asarray(top[0])
        groups = sorted({int(e) // 4 for e in top})
        spread = float(jnp.std(r))

        def slack(experts):
            m = jnp.zeros((1, 16), bool).at[0, jnp.asarray(experts)].set(True)
            return float(ref.choice_slack(MODEL, r, m)[0])

        # an expert of a kept group left out for a weaker one of that group
        order = np.argsort(-np.asarray(r[0]))
        inside = [int(e) for e in order if int(e) // 4 in groups]
        swapped = inside[:2] + [inside[3]]
        assert abs(slack(swapped)
                   - float(r[0, inside[2]] - r[0, inside[3]]) / spread) < 1e-5
        # a group left out for a weaker group
        score = np.asarray(r[0]).reshape(4, 4).max(-1)
        by_score = np.argsort(-score)
        weak = int(by_score[2])
        other = [int(e) for e in order if int(e) // 4 == weak][:1]
        kept = [int(e) for e in order if int(e) // 4 == int(by_score[0])][:2]
        assert slack(kept + other) >= float(
            score[by_score[1]] - score[weak]) / spread - 1e-5
        # three groups: no run of the rule gives that
        assert slack([0, 4, 8]) == float("inf")
        ids = self.ids(1, 70)[0]
        own = np.asarray(ref.forward(MODEL, params, ids)[1])[69]
        out, got = ref.logits(MODEL, params, ids, positions=[69],
                              choices={69: own})
        assert not got[69].any() and got[69].shape == (3,)
        same = ref.logits(MODEL, params, ids, positions=[69])
        assert np.abs(np.asarray(out) - np.asarray(same)).max() < 1e-6
        names_dense = own.copy()
        names_dense[0] = [0, 1, 2]
        with pytest.raises(ValueError, match="dense layer"):
            ref.logits(MODEL, params, ids, choices={69: names_dense})


def test_a_large_leaf_is_drawn_in_pieces_in_the_served_type(monkeypatch):
    monkeypatch.setattr(G, "_PIECE", 1 << 10)
    got = family.init_params(CFG, jax.random.PRNGKey(3))
    assert got["moe_blocks"]["experts_up_w"].dtype == jnp.bfloat16
    assert got["wte"].dtype == jnp.bfloat16
    assert got["lnf_scale"].dtype == jnp.float32
    flat = np.asarray(got["moe_blocks"]["experts_up_w"], np.float32)
    assert abs(flat.std() - 0.02) < 2e-3 and abs(flat.mean()) < 1e-3
    # no two pieces alike
    assert not np.array_equal(flat[0, 0], flat[0, 1])


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test: the routed part of one layer's result that each of
    four chips gives from its quarter of the experts, summed, and the shared
    expert counted once, is the uncut reference's layer."""
    w = jax.tree_util.tree_map(lambda a: a[1], _whole()["moe_blocks"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 24, 64)),
                    jnp.float32)
    full, chosen = G._moe_delta(CFG_WHOLE, x, w)
    h = G.rms_norm(x, w["ln2_scale"], CFG.layer_norm_eps)[0]
    logits = h @ w["router_w"]
    picked, gates = dropless.route(logits, 3, 4, 2, CFG.moe_scale)
    assert np.array_equal(np.asarray(picked), np.asarray(chosen[0]))
    parts = []
    for first in range(0, 16, 4):
        held = (first, 4)
        parts.append(dropless.held_experts_ffn(
            h, picked, gates, w["experts_gate_w"][first:first + 4],
            w["experts_up_w"][first:first + 4],
            w["experts_down_w"][first:first + 4], held))
        # a share computes something, and not everything
        assert 0 < float(jnp.abs(parts[-1]).max())
    shared = G._mlp_on(CFG, h, w, "shared")
    total = sum(parts) + shared
    assert np.abs(np.asarray(total) - np.asarray(full[0])).max() < 2e-6
    # against the uncut reference's layer, its attention left out
    with jax.default_matmul_precision("highest"):
        g, _, _ = ref.route(WHOLE, h, w["router_w"],
                            jnp.zeros((24, 3), jnp.int32),
                            jnp.zeros((24,), bool))
        want = ref.held_experts(WHOLE, h, w, g) + ref.gated_mlp(
            h, w["shared_gate_w"], w["shared_up_w"], w["shared_down_w"])
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < TOL
    assert float(jnp.abs(parts[0] - want + shared).max()) > 1e-3


def test_each_kind_field_alone_is_named_and_a_wrong_one_is_refused():
    tiny = G.PRESETS["tiny"]
    routed = dataclasses.replace(
        tiny, norm="rmsnorm", linear_bias=False, mlp_gated=True,
        moe_experts=4, moe_k=1, moe_d_ff=8)
    for cfg, name in [(CFG, "attn_kind"), (routed, "moe_experts"),
                      (dataclasses.replace(routed, moe_held=(0, 2)),
                       "moe_experts")]:
        with pytest.raises(ValueError, match=f"{name}="):
            G.require_default_block(cfg, "here", G.KIND_FIELDS)
    G.require_default_block(tiny, "here", G.KIND_FIELDS)
    # a looped dense model passes the kinds' check and not the block's
    G.require_default_block(dataclasses.replace(tiny, ut_steps=2), "here",
                            G.KIND_FIELDS)
    for wrong in (dict(moe_held=(12, 8)), dict(moe_k=9),
                  dict(moe_topk_groups=5), dict(moe_dense_layers=3),
                  dict(linear_bias=True), dict(qk_rope_dim=7),
                  dict(attn_kind="mqa")):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **wrong)


def test_yarn_frequencies_and_scales_are_the_published_ones():
    published = G.YarnScaling(factor=40, original_max_len=4096, beta_fast=32,
                              beta_slow=1, mscale=0.707, mscale_all_dim=0.707)
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert abs(m - 1.2608) < 1e-4
    assert abs(published.softmax_factor - m * m) < 1e-12
    assert published.cos_sin_factor == 1.0
    freqs = published.inv_freq(32, 10000.0)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    # fast dimensions keep their frequency, slow ones are interpolated
    assert np.allclose(freqs[:10], plain[:10], rtol=1e-6)
    assert np.allclose(freqs[-8:], plain[-8:] / 40.0, rtol=1e-6)
    assert (np.diff(freqs) < 0).all()
    real = dict(MODEL, qk_rope_head_dim=64, rope_scaling=dict(
        MODEL["rope_scaling"], original_max_position_embeddings=4096))
    assert np.allclose(freqs, ref.yarn_inv_freq(real), rtol=1e-6)
    assert abs(ref.softmax_scale(dict(real, qk_nope_head_dim=128))
               - 192 ** -0.5 * m * m) < 1e-9
    assert abs(G._softmax_scale(CFG) - ref.softmax_scale(MODEL)) < 1e-9
