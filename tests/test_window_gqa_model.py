"""A model with fewer key-value heads than query heads, window and full
attention layers in a period (a head count, a rotary set and a mask a kind), a
gate a head and routed layers on the normal path (``models/gpt.py`` with those
said as data; the page pool for the full layers and a ring a slot for the
window layers; ``paged_decode_gqa``; ``moe/dropless.py`` with renormalised
gates) against the benchmark's plain reference of those equations,
``benchmark/reference/laguna_ref.py``: ``served_contract.py`` bound to the
family, and what is the family's own.

Seeded random weights at the rehearsal configuration's size
(``benchmark/configs/tiny-laguna-serve.json``: d 64, 2 key-value heads for 4
query heads in a full layer and 6 in a window layer, window 8, a leading
dense layer and four routed ones of 16 experts, 4 a token), in float32 on the
CPU. ``TOL`` = 2e-5 on logits of size 1: both sides are float32 and sum in
another order; what was read is 1e-6 at most. Prompts of 5 (inside the
window), 20 and 30 (past it and past the ring) and 70 (serial chunks); pages
of 8 (a ring of exactly the window) and of 16 (a ring of 16 rows of which a
step reads the 8 inside the window).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import laguna as family
from benchmark.reference import laguna_ref as ref
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.ops.pallas import decode_attention as DA
from served_contract import (ServedFamilyContract, config_file, moved,
                             refuses)

MODEL = config_file("tiny-laguna-serve")["model"]
CFG = family.config(MODEL)
WINDOW = MODEL["sliding_window"]
TOL = ServedFamilyContract.TOL


# ------------------------------------------------------------ planted faults
def _full_rotary_everywhere(monkeypatch):
    period = tuple(dataclasses.replace(k, rotary_pct=1.0)
                   for k in CFG.attn_period)
    return dataclasses.replace(CFG, attn_period=period)


def _no_window(monkeypatch):
    attention = G._gqa_attention
    monkeypatch.setattr(G, "_gqa_attention", lambda cfg, *a, **kw: attention(
        dataclasses.replace(cfg, attn_window=0), *a, **kw))
    return CFG


def _window_off_by_one(monkeypatch):
    seen = DA._ring_seen
    monkeypatch.setattr(DA, "_ring_seen", lambda pos, n, ring: seen(
        pos, n, (ring[0], ring[1] - 1)))
    return CFG


def _ring_row_off_by_one(monkeypatch):
    append = G._ring_append
    monkeypatch.setattr(G, "_ring_append", lambda ring, layer, row, lengths:
                        append(ring, layer, row, jnp.where(
                            lengths > 0, lengths + 1, 0)))
    return CFG


FAULTS = {
    "no gate": lambda mp: dataclasses.replace(CFG, attn_gate=False),
    "gates not renormalised": lambda mp: dataclasses.replace(
        CFG, moe_norm_topk=False),
    "no scaling factor": lambda mp: dataclasses.replace(CFG, moe_scale=1.0),
    "the whole head rotated in a full layer": _full_rotary_everywhere,
    "no window in the prompt": _no_window,
    "a window of one less in the step": _window_off_by_one,
    "a ring row off by one": _ring_row_off_by_one,
}


class TestLaguna(ServedFamilyContract):
    FAMILY, REF, CONFIG = family, ref, "tiny-laguna-serve"
    ENGINE = dict(ServedFamilyContract.ENGINE, page_size=8)
    PATHS = {"fused, inside the window": [5],
             "fused, past the ring": [20],
             "batch": ([6, 30], dict(page_size=16)),
             "chunked": [70],
             "chunked, a ring wider than the window": (
                 [70], dict(page_size=16))}
    STEPS = 2 * WINDOW - 3      # the ring wraps under the decoded tokens
    MIXED = dict(num_slots=3)   # three slots' rings for five requests
    WIDE = dict(ServedFamilyContract.WIDE, rotary_float32=True)
    NARROW = {}
    FAULTS, FAULT_STEPS = FAULTS, 2
    NEW_FIELDS = {"n_kv_head": 2, "head_width": 16, "attn_window": 8,
                  "attn_gate": True, "attn_period": CFG.attn_period,
                  "moe_norm_topk": True}
    REFUSES = refuses("attn_kind=")

    def the_tree(self, params):
        assert sorted(params) == ["blocks_full", "lm_head", "lnf_scale",
                                  "moe_blocks_full", "moe_blocks_window",
                                  "wte"]
        attention = ["attn_gate_w", "attn_out_w", "kv_w", "ln1_scale",
                     "ln2_scale", "q_w"]
        assert sorted(params["blocks_full"]) == sorted(
            attention + ["mlp_down_w", "mlp_gate_w", "mlp_up_w"])
        for name, layers, heads in (("moe_blocks_window", 3, 6),
                                    ("moe_blocks_full", 1, 4)):
            stack = params[name]
            assert sorted(stack) == sorted(attention + [
                "router_w", "experts_gate_w", "experts_up_w",
                "experts_down_w", "shared_gate_w", "shared_up_w",
                "shared_down_w"])
            assert stack["q_w"].shape == (layers, 64, heads * 16)
            assert stack["kv_w"].shape == (layers, 64, 2 * 2 * 16)
            assert stack["attn_gate_w"].shape == (layers, 64, heads)
            assert stack["attn_out_w"].shape == (layers, heads * 16, 64)
            assert stack["experts_gate_w"].shape == (layers, 16, 64, 20)
        assert [(r.name, r.offset, r.count, r.first, r.cache_first, r.ring)
                for r in G.layer_runs(CFG)] == [
            ("blocks_full", 0, 1, 0, 0, False),
            ("moe_blocks_window", 0, 3, 1, 0, True),
            ("moe_blocks_full", 0, 1, 4, 1, False)]
        matrices = sum(v.size for k, v in jax.tree_util.tree_leaves_with_path(
            params) if not str(k[-1]).endswith("_scale']"))
        assert matrices == ref.held_params(MODEL)

    def check_engine(self, engine):
        """A ring a window layer and slot, of the window up to whole pages:
        its shape stays while the requests grow."""
        page = engine.serving.page_size
        assert engine.paged_cache["k_ring"].shape == (
            3, 2, engine.num_slots, -(-WINDOW // page) * page, 16)
        assert engine.paged_cache["k_pages"].shape[:2] == (2, 2)

    def the_sizes(self):
        """A ring costs a slot the same at any length. The published widths:
        a window layer's cache a slot is the same for a pool sized for
        requests of 512 and of 9216 tokens, and the pool holds the full
        layers only."""
        model = config_file("laguna-xs.2-serve")["model"]
        real = family.config(model)
        assert G.cache_row(real) == (2, 8, 128)
        assert G.cache_layers(real) == 5 and G.paged_layers(real) == (2, 3)
        assert G.ring_rows(real, 64) == 512
        assert G.paged_kv_bytes_per_token(real) == 2 * 4096
        assert G.ring_bytes_per_slot(real) == 3 * 512 * 4096 == 6291456
        shapes = {}
        for longest in (512, 9216):
            pages = 48 * (longest // 64) + 1
            cache = jax.eval_shape(lambda pages=pages: G.init_paged_cache(
                real, pages, 64, jnp.bfloat16, ring_slots=48))
            assert cache["k_pages"].shape == (2, 8, pages, 64, 128)
            shapes[longest] = cache["k_ring"].shape
        assert shapes[512] == shapes[9216] == (3, 8, 48, 512, 128)
        with pytest.raises(ValueError, match="ring_slots"):
            G.init_paged_cache(CFG, 9, 8)
        assert ref.kv_bytes_per_token(model) == 8192
        assert ref.ring_bytes_per_slot(model) == 6291456
        # 3.870 B: a full layer's attention 29,458,432, a window layer's
        # 37,879,808, an expert 3,145,728 (256 + the shared one and the
        # router a routed layer), the dense MLP 50,331,648, embedding and head
        assert ref.held_params(model) == (
            2 * 29_458_432 + 3 * 37_879_808 + 50_331_648
            + 4 * (257 * 3_145_728 + 2048 * 256) + 2 * 100352 * 2048
        ) == 3_869_835_264
        assert G.dense_kv_bytes(CFG, 2, 32) == 2 * 5 * 2 * 16 * 2 * 2 * 32

    def test_a_deeper_model_walks_its_stacks_in_runs(self):
        """Nine layers, two periods after the leading layer: a stack holds
        every layer of its kinds and the forward reads it a run at a time
        (the experts' stacks whole, a layer's own inside them)."""
        deep = dict(MODEL, n_layer=9,
                    layer_types=(MODEL["layer_types"][:4] * 3)[:9],
                    num_attention_heads_per_layer=([4, 6, 6, 6] * 3)[:9],
                    mlp_layer_types=["dense"] + ["sparse"] * 8)
        cfg = family.config(deep)
        assert len(cfg.attn_period) == 4
        assert dict(G.stack_names(cfg)) == {
            "blocks_full": 1, "moe_blocks_window": 6, "moe_blocks_full": 2}
        assert [(r.name, r.offset, r.count) for r in G.layer_runs(cfg)] == [
            ("blocks_full", 0, 1), ("moe_blocks_window", 0, 3),
            ("moe_blocks_full", 0, 1), ("moe_blocks_window", 3, 3),
            ("moe_blocks_full", 1, 1)]
        p = jax.jit(lambda key: moved(G.init_params(cfg, key)))(
            jax.random.PRNGKey(1))
        ids = self.ids(1, 24, seed=3)
        want = np.asarray(ref.logits(deep, p, ids[0]))
        got = jax.jit(lambda p, ids: G.forward(cfg, p, ids, train=False))(
            p, jnp.asarray(ids))
        assert np.abs(np.asarray(got[0]) - want).max() < TOL
        stepped, _ = jax.jit(lambda p, ids: G.forward_with_cache(
            cfg, p, ids, G.init_cache(cfg, 1, 32, jnp.float32)))(
                p, jnp.asarray(ids))
        assert np.abs(np.asarray(stepped[0]) - want).max() < TOL

    def test_a_decode_span_counts_the_rows_of_each_kind(self, engines):
        sched = engines().make_scheduler()
        sched.lengths[:] = [3, 0, 20, 8]
        mask = np.asarray([True, False, True, True])
        stats = sched._decode_stats(2, [0, 2, 3], mask)
        assert stats["live_kv_tokens"] == stats["kv_rows_full"] == 31
        assert stats["kv_rows_window"] == 3 + 8 + 8
        # four pages a grid step of the kernel over tables of 16 pages of 8;
        # 1, 3 and 2 pages live with the step's token, a group each
        serving = engines().serving
        g = G.gqa_pages_per_step(self.CFG, serving.page_size,
                                 serving.pages_per_seq, jnp.float32)
        assert (g, stats["gqa_pages_per_step"], stats["live_pages"],
                stats["gqa_group_tiles"]) == (4, 4, 6, 12)
        sched.close()
        from deepspeed_tpu.inference.serving import (ServingConfig,
                                                     ServingEngine)

        tiny = G.PRESETS["tiny"]
        plain = ServingEngine(
            tiny, G.init_params(tiny, jax.random.PRNGKey(0)), ServingConfig(
                num_slots=2, page_size=8, max_model_len=32, prefill_chunk=16,
                dtype="float32")).make_scheduler()
        stats = plain._decode_stats(1, [0], np.asarray([True, False]))
        assert not {"kv_rows_window", "gqa_group_tiles"} & set(stats)
        plain.close()


def test_a_config_the_block_does_not_compute_is_refused():
    for wrong in (dict(n_kv_head=4), dict(head_width=0), dict(alibi=True),
                  dict(rotary_interleaved=True), dict(linear_bias=True),
                  dict(attn_window=4), dict(ut_steps=2),
                  dict(attn_period=CFG.attn_period[:1] + (dataclasses.replace(
                      CFG.attn_period[0], n_head=8),))):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **wrong)
    with pytest.raises(ValueError, match="gqa"):
        dataclasses.replace(G.PRESETS["tiny"], n_kv_head=2)
    with pytest.raises(ValueError, match="dense layers lead"):
        family.config(dict(MODEL, mlp_layer_types=["sparse", "dense"]
                           + ["sparse"] * 3))
    with pytest.raises(ValueError, match="laguna_ref reads"):
        family.config(dict(MODEL, norm_topk_prob=False))


def test_the_rotary_sets_are_the_published_ones():
    """YaRN over half a head at the published numbers: ``attention_factor``
    is 0.1 ln 64 + 1, the reference's frequencies are ``YarnScaling``'s, and
    the window kind rotates the whole head plainly."""
    model = config_file("laguna-xs.2-serve")["model"]
    real = family.config(model)
    full, window = real.attn_period[0], real.attn_period[1]
    assert (full.n_head, full.window, full.rotary_pct) == (48, 0, 0.5)
    assert (window.n_head, window.window, window.rotary_pct) == (64, 512, 1.0)
    assert real.attn_period == (full, window, window, window)
    assert abs(full.rope_scaling.cos_sin_factor - 1.4158883083359672) < 1e-12
    assert full.rope_scaling.softmax_factor == 1.0
    assert np.array_equal(full.rope_scaling.inv_freq(32, 500000.0),
                          ref.inv_freq(model, "full_attention"))
    assert ref.rotated_dims(model, "full_attention") == 64
    assert ref.rotated_dims(model, "sliding_attention") == 128
    plain = ref.inv_freq(model, "sliding_attention")
    assert np.allclose(plain, 10000.0 ** (-np.arange(64) / 64))
