"""A model whose layer is two latent-attention sub-blocks, two dense MLPs and
one routed branch across them, under a router that also scores zero-compute
experts, on the normal path (``models/gpt.py`` with ``moe_shortcut`` and
``moe_zero_experts`` said as data; ``moe/dropless.py``; the latent page pool
with two cache layers a layer and ``paged_decode_mla``) against the
benchmark's plain reference of those equations,
``benchmark/reference/longcat_flash_ref.py``: ``served_contract.py`` bound to
the family, and what is the family's own.

Seeded random weights at the rehearsal configuration's size
(``benchmark/configs/tiny-longcat-flash-serve.json``: 2 layers, 4 cache
layers, 8 real and 4 zero-compute experts of which 3 a token, real experts
0-3 held, a choice bias that is not zero, nope 16 / rope 8 / value 12), in
float32 on the CPU. ``TOL`` = 2e-5 on logits of size 1: both sides are float32
and sum in another order; what was read is 1e-6 at most. A fault of the kinds
planted below moves a logit by 1e-4 or more (a router rounded to bf16 is not
among them: at gates of 6 x 1/12 it reads 1.6e-5, under the tolerance; the
chip's drift tool has that row).
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import longcat_flash as family
from benchmark.reference import longcat_flash_ref as ref
from benchmark.tools import longcat_drift
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.profiling import trace
from served_contract import (ServedFamilyContract, config_file, moved,
                             refuses)

MODEL = config_file("tiny-longcat-flash-serve")["model"]
WHOLE = dict(MODEL, held_experts=[0, MODEL["n_routed_experts"]])
CFG = family.config(MODEL)          # real experts 0-3 of 8 held, 4 zero ones
CFG_WHOLE = family.config(WHOLE)
PAGE = ServedFamilyContract.ENGINE["page_size"]
TOL = ServedFamilyContract.TOL
REAL, OUTPUTS = MODEL["n_routed_experts"], ref.outputs(MODEL)


@functools.cache
def _whole():
    return moved(G.init_params(CFG_WHOLE, jax.random.PRNGKey(0)))


# ------------------------------------------------------------ planted faults
def _planted(name):
    """A fault of the chip's drift tool (``tools/longcat_drift.variants``:
    one table of what can go wrong with this layer, read there at the
    published widths), planted here through ``monkeypatch``."""
    cfg, patch = longcat_drift.variants(CFG)[name]

    def fault(monkeypatch):
        if patch is not None:
            patch(monkeypatch.setattr)
        return cfg
    return fault


FAULTS = {name: _planted(name) for name in (
    "no scaling factor", "no identity term", "the bias in the gates",
    "the branch lands after the first sub-block", "one cache layer a layer",
    "no rescale of the latents")}
FAULTS["a cache in bf16"] = lambda mp: (CFG, jnp.bfloat16)


class TestLongcatFlash(ServedFamilyContract):
    FAMILY, REF, CONFIG = family, ref, "tiny-longcat-flash-serve"
    FORWARDS = {"a share": (40, 0), "whole": (40, 0)}
    PATHS = {"fused": [20], "batch": [9, 30], "chunked": [70],
             "exactly 2 chunks, and a fused prompt": [64, 20]}
    STEPS = 5
    FAULTS = FAULTS
    NEW_FIELDS = {"moe_zero_experts": 4, "moe_shortcut": True}
    # the layer's shape is what every other path refuses, before its attention
    REFUSES = refuses("moe_shortcut=")

    def forward_case(self, forward, params):
        return ((CFG, MODEL, params) if forward == "a share"
                else (CFG_WHOLE, WHOLE, _whole()))

    def the_tree(self, params):
        assert sorted(params) == ["lm_head", "lnf_scale", "moe_blocks", "wte"]
        first = ["attn_out_w", "kv_a_norm_scale", "kv_a_w", "kv_b_w",
                 "ln1_scale", "ln2_scale", "q_a_norm_scale", "q_a_w", "q_b_w",
                 "mlp_down_w", "mlp_gate_w", "mlp_up_w"]
        sub = first + [G.SUB1 + name for name in first]
        own = ["router_w", "router_bias", "experts_gate_w", "experts_up_w",
               "experts_down_w"]
        blocks = params["moe_blocks"]
        assert sorted(blocks) == sorted(sub + own)
        # the second sub-block's leaves are the first's in shape, not in value
        for name in first:
            assert blocks[name].shape == blocks[G.SUB1 + name].shape
        assert not np.array_equal(blocks["q_a_w"], blocks["sub1_q_a_w"])
        assert blocks["sub1_mlp_up_w"].shape == (2, 64, 96)
        assert blocks["kv_b_w"].shape == (2, 32, 4 * (16 + 12))
        assert blocks["router_w"].shape == (2, 64, OUTPUTS)
        assert blocks["router_bias"].shape == (2, OUTPUTS)
        assert blocks["experts_gate_w"].shape == (2, 4, 64, 20)
        assert float(jnp.abs(blocks["router_bias"]).min()) > 0
        matrices = sum(blocks[k].size for k in sub + own
                       if k.endswith("_w")) + 2 * 256 * 64
        assert matrices == ref.held_params(MODEL)
        assert CFG.num_params() == sum(
            x.size for x in jax.tree_util.tree_leaves(params))

    def the_sizes(self):
        assert G.cache_row(CFG) == (1, 1, 128) and CFG.latent_width == 128
        assert G.cache_layers(CFG) == 4 == ref.cache_layers(MODEL)
        assert G.paged_layers(CFG) == (4, 0)
        assert G.init_paged_cache(CFG, 9, PAGE)["k_pages"].shape == \
            (4, 1, 9, PAGE, 128)
        assert G.init_cache(CFG, 2, 32)["k"].shape == (4, 2, 1, 32, 128)
        assert ref.kv_bytes_per_token(MODEL) == 4 * (32 + 8) * 2
        real_model = config_file("longcat-flash-omni-serve")["model"]
        real = family.config(real_model)
        assert real.latent_width == 640 and G.cache_layers(real) == 8
        assert G.paged_kv_bytes_per_token(real) == 8 * 640 * 2 == 10240
        # 5.173 B matrix weights = 10.35 GB in bf16; gains and biases beside
        assert ref.held_params(real_model) == 5_172_625_408
        assert real.num_params() == 5_172_749_312
        assert (real.moe_rows, real.moe_width) == (6144, 2048)

    def check_counts(self, assigned, held):
        assert 0 <= held < assigned     # 4 of 12 outputs have weights here

    def test_a_layer_names_two_cache_layers(self):
        """``layer_runs``: ONE run whose layers each count two cache layers,
        layer ``l`` cache layers ``2l`` and ``2l + 1``; every layer routes."""
        (run,) = G.layer_runs(CFG)
        assert (run.name, run.count, run.mixer, run.ffn, run.subs,
                run.per_pass, run.attends, run.mixes, run.routes) == (
            "moe_blocks", 2, "attn", "shortcut", 2, 4, True, False, True)
        assert [run.cache_layer(l, 0) for l in range(2)] == [0, 2]
        assert dict(G.stack_names(CFG)) == {"moe_blocks": 2}
        assert not G.chunks_to_pages(CFG)   # a latent prompt's scratch path
        real = family.config(config_file("longcat-flash-omni-serve")["model"])
        (run,) = G.layer_runs(real)
        assert [run.cache_layer(l, 0) + j for l in range(4)
                for j in range(run.subs)] == list(range(8))

    def test_the_step_counts_the_picks_of_zero_experts(self, params, engines):
        """``routing_of``'s fifth count, ``trace.ROUTED_ZERO`` on
        ``serve.decode``: the active slots' picks at or past the real
        experts, the reference's own."""
        engine = engines()
        prompts = [row[:n] for row, n in zip(self.ids(2, 80, seed=5),
                                             (20, 33))]
        seqs, _, chosen, counts, _ = self.serve(engine, prompts, [3, 2], 2)
        assert counts.shape == (5,) and engine.decode_routing.shape == (1, 5)
        own = [np.asarray(ref.forward(MODEL, params, np.asarray(
            seqs[slot], np.int32))[1])[-1] for slot in (3, 2)]
        zero = sum(int((o >= REAL).sum()) for o in own)
        assert int(counts[4]) == zero > 0
        assert int(counts[0]) == 2 * 2 * 3
        said = trace.routing_stats(np.asarray(engine.decode_routing))
        assert set(said) == set(trace.ROUTING_STATS) | {trace.ROUTED_ZERO}
        # a model without zero experts keeps its four counts and says four
        four = trace.routing_stats(np.asarray([[6, 2, 2, 1]]))
        assert set(four) == set(trace.ROUTING_STATS)
        assert engine.model.facts.cache_layers == 4

    def test_the_programs_carry_the_layers_scopes(self, engines):
        """``dense_ffn`` and ``routed_branch`` (with ``moe_router``,
        ``moe_experts`` and ``moe_zero`` inside it) are in every program of
        the engine; neither lies under the other or under ``mlp``, which the
        layer does not have."""
        engine = engines()
        sink = np.zeros(engine.serving.pages_per_seq, np.int32)
        engine.prefill(0, np.ones(5, np.int32), sink)
        engine.prefill(0, np.ones(40, np.int32), sink)
        zeros = np.zeros(engine.num_slots, np.int32)
        engine.decode(zeros, np.zeros((engine.num_slots, len(sink)),
                                      np.int32), zeros,
                      np.zeros(engine.num_slots, bool), steps=2)
        new = {"dense_ffn", "routed_branch", "moe_zero"}
        assert new <= set(trace.MODEL_SCOPES)
        for name in ("prefill_fused_32", "prefill_chunk_32",
                     "decode_block_2"):
            paths = set(trace.program_scopes(name).values())
            parts = {part for v in paths for part in v.split("/")}
            assert new | {"attn", "moe_router", "moe_experts"} <= parts, name
            assert "mlp" not in parts, name
            for path in paths:
                at = path.split("/")
                assert not {"dense_ffn", "routed_branch"} <= set(at), path
                for inner in ("moe_router", "moe_experts", "moe_zero"):
                    if inner in at:
                        assert "routed_branch" in at[:at.index(inner)], path


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test, at 8 real and 4 zero experts in 4 shares: the routed
    part that each of four chips gives from its two real experts, summed,
    with the identity term, attention and the dense MLPs counted ONCE, is the
    uncut reference's layer; and the vocabulary slices tile the head."""
    params = _whole()
    w = jax.tree_util.tree_map(lambda a: a[1], params["moe_blocks"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 24, 64)),
                    jnp.float32)
    positions = jnp.arange(24)[None]
    attend = G._every_sub(G._attend_sequence(CFG_WHOLE, positions))
    full, _, chosen = G._shortcut_on(CFG_WHOLE, x, w, positions, attend)
    # the layer with the routed branch left out: attention and the dense
    # MLPs, which every chip computes whole
    without = dataclasses.replace(CFG_WHOLE, moe_scale=0.0)
    rest = G._shortcut_on(without, x, w, positions, attend)[0]
    a0 = x + G._attn_delta(CFG_WHOLE, x, w, positions, attend(0, ()))[0]
    h0 = G.rms_norm(a0, w["ln2_scale"], CFG.layer_norm_eps)[0]
    picked, gates = dropless.route(h0 @ w["router_w"], 3, scale=CFG.moe_scale,
                                   bias=w["router_bias"])
    assert np.array_equal(np.asarray(picked), np.asarray(chosen[0]))
    assert (np.asarray(picked) >= REAL).any()
    parts = []
    for first in range(0, REAL, 2):
        parts.append(dropless.held_experts_ffn(
            h0, picked, gates, w["experts_gate_w"][first:first + 2],
            w["experts_up_w"][first:first + 2],
            w["experts_down_w"][first:first + 2], (first, 2)))
        assert 0 < float(jnp.abs(parts[-1]).max())
    identity = dropless.zero_experts(h0, picked, gates, REAL)
    assert 0 < float(jnp.abs(identity).max())
    total = rest[0] + sum(parts) + identity
    assert np.abs(np.asarray(total) - np.asarray(full[0])).max() < 2e-6
    # the identity term belongs to no share: a share's config computes it too
    share, _, _ = G._shortcut_on(
        dataclasses.replace(CFG_WHOLE, moe_held=(2, 2)), x,
        dict(w, **{k: w[k][2:4] for k in G.EXPERT_STACKS}), positions, attend)
    assert np.abs(np.asarray(share[0])
                  - np.asarray(rest[0] + parts[1] + identity)).max() < 2e-6
    # against the uncut reference's layer
    with jax.default_matmul_precision("highest"):
        want, own, _ = ref.block(WHOLE, x[0], params["moe_blocks"], 1,
                                 jnp.zeros((24, 3), jnp.int32),
                                 jnp.zeros((24,), bool))
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < TOL
    assert sorted(np.asarray(own)[5]) == sorted(np.asarray(picked)[5])
    assert float(jnp.abs(total - identity - want).max()) > 1e-3
    # the vocabulary slices tile the head
    state = jnp.asarray(np.random.default_rng(3).normal(size=(5, 64)),
                        jnp.float32)
    whole = ref.head_logits(WHOLE, params, state)
    slices = [ref.head_logits(WHOLE, dict(
        params, lm_head=params["lm_head"][v:v + 64]), state)
        for v in range(0, 256, 64)]
    assert np.abs(np.concatenate(slices, axis=1) - whole).max() < 1e-6


def test_the_router_chooses_by_the_bias_and_weighs_without_it():
    """``route`` over real and zero experts under softmax with a bias: the
    choice is the largest of ``p + b`` (ties to the lower index), the gates
    are ``scale x p``; a bias large enough changes the set and no gate."""
    logits = jnp.asarray(np.random.default_rng(4).normal(size=(6, OUTPUTS)),
                         jnp.float32)
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    plain, gates = dropless.route(logits, 3, scale=6.0,
                                  bias=jnp.zeros(OUTPUTS))
    assert np.array_equal(np.sort(plain, 1), np.sort(np.argsort(-p, 1)[:, :3],
                                                     1))
    lifted = jnp.zeros(OUTPUTS).at[OUTPUTS - 1].set(1.0)
    chosen, g = dropless.route(logits, 3, scale=6.0, bias=lifted)
    assert (np.asarray(chosen) == OUTPUTS - 1).any(axis=1).all()
    assert np.allclose(np.asarray(g), 6.0 * np.take_along_axis(
        p, np.asarray(chosen), 1), rtol=1e-6)
    # ties to the lower index: equal scores everywhere
    tied, _ = dropless.route(jnp.zeros((1, OUTPUTS)), 3,
                             bias=jnp.zeros(OUTPUTS))
    assert sorted(np.asarray(tied)[0]) == [0, 1, 2]
    h = jnp.asarray(np.random.default_rng(5).normal(size=(6, 8)),
                    jnp.float32)
    got = dropless.zero_experts(h, chosen, g, REAL)
    want = (np.where(np.asarray(chosen) >= REAL, np.asarray(g), 0).sum(1)
            [:, None] * np.asarray(h))
    assert np.abs(np.asarray(got) - want).max() < 1e-6


def test_a_wrong_spelling_is_refused():
    for wrong in (dict(moe_zero_experts=4, moe_groups=2, moe_topk_groups=1),
                  dict(moe_dense_layers=1), dict(moe_shared_d_ff=8),
                  dict(attn_window=8), dict(ut_steps=2),
                  dict(moe_zero_experts=-1)):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **wrong)
    tiny = G.PRESETS["tiny"]
    with pytest.raises(ValueError, match="moe_zero_experts"):
        dataclasses.replace(tiny, moe_zero_experts=4)
    with pytest.raises(ValueError, match="moe_shortcut"):
        dataclasses.replace(tiny, moe_shortcut=True)
    # the layer's shape is named first, whatever else the config says
    with pytest.raises(ValueError, match="here does not support "
                       "moe_shortcut=True"):
        G.require_default_block(CFG, "here", G.KIND_FIELDS)
