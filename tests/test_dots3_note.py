"""The language model of ``dots3-note-prev`` on the normal path
(``models/gpt.py`` with latent attention of two kinds in one cache: pages
under a learned selection with an indexer's keys in pages beside them, a
latent ring a slot for the window layers; a gate a head, the two rescales, a
sigmoid router with a choice bias; ``paged_decode_mla`` for both kinds) against
the benchmark's plain reference of those equations,
``benchmark/reference/dots3_note_ref.py``: ``served_contract.py`` bound to the
family, and what is the family's own.

Seeded random weights at the rehearsal configuration's size
(``benchmark/configs/tiny-dots3-note-serve.json``: full, full, window x 3; a
top-16 selection by 3 index heads of 16; a window of 13 over a ring of 16; 16
experts of which 4 a token and 8 held), in float32 on the CPU. ``TOL`` = 2e-5
on logits of size 1; what was read is 1e-6 at most, the least of the planted
faults 2e-3.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import dots3_note as family
from benchmark.reference import dots3_note_ref as ref
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.profiling import trace
from served_contract import (ServedFamilyContract, config_file,
                             moved, prefill_step, refuses)

MODEL = config_file("tiny-dots3-note-serve")["model"]
WHOLE = dict(MODEL, held_experts=[0, MODEL["n_routed_experts"]])
CFG = family.config(MODEL)          # experts 0-7 of 16 held
CFG_WHOLE = family.config(WHOLE)
PAGE = ServedFamilyContract.ENGINE["page_size"]
TOL = ServedFamilyContract.TOL
TOPK, K = MODEL["index_topk"], MODEL["k"]


def _kinds(**change):
    """``CFG`` with every kind of its period changed where the kind has the
    field set (a window kind has no indexer to change)."""
    return dataclasses.replace(CFG, attn_period=tuple(
        dataclasses.replace(kind, **{
            name: value for name, value in change.items()
            if getattr(kind, name)}) for kind in CFG.attn_period))


# ------------------------------------------------------------ planted faults
def _unrotated_index_key(monkeypatch):
    parts = G._index_parts

    def faulty(cfg, h, c_q, w, rotate):
        q, _, weights = parts(cfg, h, c_q, w, rotate)
        return q, parts(cfg, h, c_q, w, lambda t: t)[1], weights
    monkeypatch.setattr(G, "_index_parts", faulty)
    return CFG


def _the_other_layers_keys(monkeypatch):
    """A decode step scores (and writes) the index keys of the OTHER full
    layer: its selection comes from keys its own prompt never wrote."""
    attend = G._append_and_attend_kinds

    def faulty(cfg, named, *rest):
        if not cfg.index_topk:
            return attend(cfg, named, *rest)
        key = G.INDEX_KEYS[0]
        attn, pools = attend(cfg, dict(named, **{key: named[key][::-1]}),
                             *rest)
        at = rest[0].index(key)
        return attn, pools[:at] + (pools[at][::-1],) + pools[at + 1:]
    monkeypatch.setattr(G, "_append_and_attend_kinds", faulty)
    return CFG


FAULTS = {
    "a rescale left out": lambda mp: dataclasses.replace(
        CFG, mla_lora_rescale=False),
    "the window one short": lambda mp: _kinds(
        window=MODEL["sliding_window"] - 1),
    "a selection one short": lambda mp: _kinds(index_topk=TOPK - 1),
    "an unrotated index key": _unrotated_index_key,
    "the other layer's index keys": _the_other_layers_keys,
    "no gate": lambda mp: dataclasses.replace(CFG, attn_gate=False),
    "gates not renormalised": lambda mp: dataclasses.replace(
        CFG, moe_norm_topk=False),
    "a cache in bf16": lambda mp: (CFG, jnp.bfloat16),
}


@functools.cache
def _whole():
    return moved(G.init_params(CFG_WHOLE, jax.random.PRNGKey(0)))


class TestDots3Note(ServedFamilyContract):
    FAMILY, REF, CONFIG = family, ref, "tiny-dots3-note-serve"
    FORWARDS = {"a share": (40, 0), "whole": (40, 0)}
    PATHS = {"fused": [24], "batch": [9, 31, 20],
             "chunked to pages": [70],
             "decode blocks": ([40], dict(decode_block=4))}
    FAULTS = FAULTS
    NEW_FIELDS = {"index_heads": 3, "index_dim": 16, "index_topk": 16,
                  "mla_lora_rescale": True, "index_float32": True}
    REFUSES = refuses("attn_kind=", but=(
        "gpt_moe", "initialize over pipeline stages"))
    # no dense cache (the refusal is held below): nothing runs its head there
    test_one_position_a_row_is_that_row_of_every_positions_logits = None

    def forward_case(self, forward, params):
        return ((CFG, MODEL, params) if forward == "a share"
                else (CFG_WHOLE, WHOLE, _whole()))

    def the_tree(self, params):
        assert sorted(params) == ["blocks_full", "lm_head", "lnf_scale",
                                  "moe_blocks_full", "moe_blocks_window",
                                  "wte"]
        attention = ["attn_gate_w", "attn_out_w", "kv_a_norm_scale", "kv_a_w",
                     "kv_b_w", "ln1_scale", "ln2_scale", "q_a_norm_scale",
                     "q_a_w", "q_b_w"]
        indexer = ["index_k_norm_bias", "index_k_norm_scale", "index_k_w",
                   "index_q_w", "index_w_w"]
        routed = ["router_w", "router_bias", "experts_gate_w", "experts_up_w",
                  "experts_down_w", "shared_gate_w", "shared_up_w",
                  "shared_down_w"]
        assert sorted(params["blocks_full"]) == sorted(
            attention + indexer + ["mlp_down_w", "mlp_gate_w", "mlp_up_w"])
        assert sorted(params["moe_blocks_full"]) == sorted(
            attention + indexer + routed)
        assert sorted(params["moe_blocks_window"]) == sorted(attention
                                                             + routed)
        # each kind its own heads, ranks and widths
        assert params["blocks_full"]["kv_b_w"].shape == (1, 32, 4 * (16 + 12))
        assert params["moe_blocks_window"]["kv_b_w"].shape == (
            3, 48, 2 * (24 + 12))
        assert params["moe_blocks_window"]["kv_a_w"].shape == (3, 64, 48 + 8)
        assert params["moe_blocks_full"]["index_q_w"].shape == (1, 24, 3 * 16)
        assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == \
            ref.held_params(MODEL)

    def check_engine(self, engine):
        cache = engine.paged_cache
        assert sorted(cache) == ["index_pages", "k_pages", "k_ring",
                                 "selected"]
        n, pages = engine.num_slots, engine.num_pages
        assert cache["k_pages"].shape == (2, 1, pages, PAGE, 128)
        assert cache["index_pages"].shape == (2, 1, pages, PAGE, 16)
        # a window of 13 over a ring of the next whole page, the window
        # kind's own row (48 + 8 numbers in whole lanes)
        assert cache["k_ring"].shape == (3, 1, n, 16, 128)
        assert cache["selected"].shape == (2, n, TOPK)

    def check_state(self, params, ids, slot, own, left):
        """The step's selections are the reference's own at that position,
        in the full layers: ``TOPK`` positions while the context is longer,
        every position while it is not."""
        super().check_state(params, ids, slot, own, left)
        last = len(ids) - 1
        want = ref.forward(MODEL, params, ids, probe=(last,))[3][last]
        got = np.asarray(left["selected"])[:, slot]
        for l, rows in enumerate(got):      # full layers 0 and 1
            kept = sorted(rows[rows >= 0].tolist())
            assert kept == want[l][want[l] >= 0].tolist(), (slot, l)
            assert len(kept) == min(len(ids), TOPK)
        assert (want[2:] == -1).all()

    def test_one_function_sizes_every_cache(self, engines):
        """A run's row sizes each of its caches: the pages, the index keys
        beside them, the rings; a dense cache of one row shape is refused by
        the fields' names."""
        pool = G.init_paged_cache(CFG, 9, PAGE, jnp.bfloat16, ring_slots=2)
        assert (G.cache_row(CFG), G.cache_row(CFG, ring=True)) == (
            (1, 1, 128), (1, 1, 128))
        assert (G.paged_layers(CFG), G.index_layers(CFG),
                G.ring_rows(CFG, PAGE)) == ((2, 3), 2, 16)
        assert pool["k_pages"].nbytes + pool["index_pages"].nbytes == \
            G.paged_kv_bytes_per_token(CFG, page_size=PAGE) * 9 * PAGE
        assert pool["k_ring"].nbytes == 2 * G.ring_bytes_per_slot(CFG, PAGE)
        with pytest.raises(ValueError, match="attn_period="):
            G.init_cache(CFG, 2, 32, jnp.bfloat16)
        tiny = G.PRESETS["tiny"]
        assert G.cache_row(tiny) == G.cache_row(tiny, ring=True) == (2, 4, 16)
        engine = engines()
        assert (engine.paged_cache["k_pages"].nbytes
                + engine.paged_cache["index_pages"].nbytes) == \
            engine.kv_bytes_per_token() * engine.num_pages * PAGE
        assert engine.slot_bytes() == G.ring_bytes_per_slot(
            CFG, PAGE, jnp.float32)
        assert engine.model.facts.cache_layers == 5
        self.the_sizes()

    def the_sizes(self):
        """The published widths: rows of 640 and 1152, a ring of 576, the
        parameter count of the issue's arithmetic."""
        file = config_file("dots3-note-serve")
        real = family.config(file["model"])
        assert G.cache_row(real) == (1, 1, 640)
        assert G.cache_row(real, ring=True) == (1, 1, 1152)   # 1088 in lanes
        assert (G.paged_layers(real), G.ring_rows(real, 64)) == ((2, 3), 576)
        # bf16 rows of 640 and float32 index keys of 128 (index_float32)
        assert real.index_float32
        assert G.paged_kv_bytes_per_token(real) == 2 * (640 * 2 + 128 * 4)
        assert G.ring_bytes_per_slot(real, 64) == 3 * 576 * 1152 * 2
        assert ref.held_params(file["model"]) == 4_087_154_176
        assert ref.layer_params(file["model"], 0) == 356_396_800
        assert ref.layer_params(file["model"], 1) == 923_938_816
        assert ref.layer_params(file["model"], 2) == 870_723_840
        # the tree's leaves are those, no layout padding (1536 and 5120 are
        # whole lanes): counted from shapes, nothing is drawn
        shapes = jax.eval_shape(lambda: family.init_params(
            real, jax.random.PRNGKey(0)))
        assert sum(int(np.prod(a.shape)) for a in
                   jax.tree_util.tree_leaves(shapes)) == 4_087_154_176
        # the algorithm's counts are the reference's: the pool pads the rows
        assert ref.kv_bytes_per_token(file["model"]) == 2 * (576 + 128) * 2
        assert ref.ring_bytes_per_slot(file["model"]) == 3 * 513 * 1088 * 2

    # ------------------------------------------------------ the family's own
    def test_a_request_under_the_selection_reads_what_unselected_mla_reads(
            self, params):
        """A prompt of 12 and its next step keep every row (12 and 13 of at
        most 16): the logits are those of the same weights without an
        indexer."""
        plain = _kinds(index_topk=0, index_heads=0, index_dim=0)
        ids = self.ids(1, 13, seed=3)[0]
        out = {}
        for name, cfg in (("plain", plain), ("selecting", CFG)):
            pool = G.init_paged_cache(cfg, 4, PAGE, jnp.float32, ring_slots=1)
            tables = jnp.asarray([[1, 2]], jnp.int32)
            _, pool, _ = G.paged_prefill_step(
                cfg, params, jnp.asarray(ids[None, :12]), pool, tables,
                jnp.asarray([12]), jnp.asarray([0]), jnp.asarray([0]))
            out[name], left = G.paged_decode_step(
                cfg, params, jnp.asarray(ids[12:]), pool, tables,
                jnp.asarray([12]), impl="kernel")
            if cfg is CFG:
                kept = np.asarray(left["selected"])[:, 0]
                assert (np.sort(kept, axis=1)[:, 3:] == np.arange(13)).all()
        assert np.abs(out["selecting"] - out["plain"]).max() < 1e-6

    @pytest.mark.parametrize("impl", ["kernel", "gather"])
    def test_a_step_under_a_selection_gives_the_references_step(
            self, params, impl):
        """A context of 41 and then 42 under a selection of 16, the table
        walked under the selection's mask by the kernel and by its XLA
        fallback: the reference's logits."""
        ids = self.ids(1, 42, seed=17)[0]
        _, pool, tables = self.prefilled(CFG, params, ids, jnp.float32)
        pool = G.paged_decode_step(CFG, params, jnp.asarray(ids[40:41]),
                                   pool, tables, jnp.asarray([40]),
                                   impl=impl)[1]
        logits = G.paged_decode_step(CFG, params, jnp.asarray(ids[41:]),
                                     pool, tables, jnp.asarray([41]),
                                     impl=impl)[0]
        want = np.asarray(ref.logits(MODEL, params, ids))[-1]
        assert np.abs(np.asarray(logits[0]) - want).max() < TOL

    def test_a_chunk_through_the_chunk_kernel_gives_the_references_logits(
            self, params):
        """What the chip runs: a chunk's full layers through
        ``masked_chunk_attention`` (``use_flash``; interpreted here), the
        chunk's earlier rows read back from its pages and expanded once."""
        cfg = dataclasses.replace(CFG, use_flash=True)
        ids = self.ids(1, 71, seed=21)[0]
        pool = G.init_paged_cache(cfg, 7, PAGE, jnp.float32, ring_slots=1)
        tables = jnp.arange(1, 6, dtype=jnp.int32)[None]
        step = jax.jit(lambda ids, pool, pos: G.paged_prefill_step(
            cfg, params, ids, pool, tables, jnp.asarray([70]),
            jnp.asarray([0]), jnp.asarray([0]), chunk=(pos, 32)))
        for pos in (0, 32, 64):
            chunk = np.zeros((1, 32), np.int32)
            chunk[0, :min(32, 70 - pos)] = ids[pos:min(pos + 32, 70)]
            _, pool, _ = step(jnp.asarray(chunk), pool, jnp.int32(pos))
        logits = G.paged_decode_step(cfg, params, jnp.asarray(ids[70:]),
                                     pool, tables, jnp.asarray([70]),
                                     impl="kernel")[0]
        want = np.asarray(ref.logits(MODEL, params, ids))[-1]
        assert np.abs(np.asarray(logits[0]) - want).max() < TOL

    @pytest.mark.parametrize("chunked", [False, True],
                             ids=["a whole prompt", "chunks"])
    def test_the_index_kernel_selects_what_the_plain_form_selects(
            self, params, chunked):
        """The served path with ``ops/pallas/index_scores`` (interpreted
        here: a chunk's queries and a decode step's against the index keys
        in their pages) and with the plain form over gathered keys: the same
        positions kept at every step, the same logits."""
        ids = self.ids(1, 74, seed=29)[0]
        # six pages: the last chunk's padded places lie inside the table
        tables = jnp.arange(1, 7, dtype=jnp.int32)[None]
        size = 32 if chunked else 70
        out = {}
        for name, impl in (("plain", "gather"), ("kernel", "kernel")):
            cfg = dataclasses.replace(CFG, use_flash=impl == "kernel")
            pool = G.init_paged_cache(cfg, 8, PAGE, jnp.float32, ring_slots=1)
            fill = jax.jit(lambda chunk, pool, pos, cfg=cfg: (
                G.paged_prefill_step(
                    cfg, params, chunk, pool, tables, jnp.asarray([70]),
                    jnp.asarray([0]), jnp.asarray([0]),
                    chunk=(pos, 32) if chunked else None)[1]))
            step = jax.jit(lambda tok, pool, at, cfg=cfg, impl=impl: (
                G.paged_decode_step(cfg, params, tok, pool, tables, at,
                                    impl=impl)))
            for pos in range(0, 70, size):
                chunk = np.zeros((1, size), np.int32)
                chunk[0, :min(size, 70 - pos)] = ids[pos:min(pos + size, 70)]
                pool = fill(jnp.asarray(chunk), pool, jnp.int32(pos))
            out[name] = []
            for at in range(70, 74):
                logits, pool = step(jnp.asarray(ids[at:at + 1]), pool,
                                    jnp.asarray([at]))
                out[name].append((np.asarray(logits[0]), np.sort(
                    np.asarray(pool["selected"])[:, 0], axis=1)))
        for (plain, kept), (kernel, kept_k) in zip(*out.values()):
            assert (kept == kept_k).all()
            assert (kept >= 0).sum() == 2 * TOPK
            assert np.abs(plain - kernel).max() < TOL
        want = np.asarray(ref.logits(MODEL, params, ids))[-1]
        assert np.abs(out["kernel"][-1][0] - want).max() < TOL
        # four whole-model programs with interpreted kernels in them: not
        # kept for the rest of the worker's files (XLA's CPU compiler has
        # crashed in a later file of a process that held them; PR 56)
        jax.clear_caches()

    def test_float32_index_keys_under_a_bf16_cache(self, params):
        """``index_float32``: the index keys' pages are float32 beside bf16
        rows and rings, a token's bytes say so, and the step still selects
        what the reference selects."""
        cfg = dataclasses.replace(CFG, index_float32=True)
        pool = G.init_paged_cache(cfg, 9, PAGE, jnp.bfloat16, ring_slots=1)
        assert {k: str(a.dtype) for k, a in pool.items()} == {
            "k_pages": "bfloat16", "k_ring": "bfloat16",
            "index_pages": "float32", "selected": "int32"}
        assert G.paged_kv_bytes_per_token(cfg) == 2 * (128 * 2 + 16 * 4)
        ids = self.ids(1, 42, seed=23)[0]
        _, pool, tables = self.prefilled(cfg, params, ids, jnp.float32)
        assert pool["index_pages"].dtype == jnp.float32
        logits, left = G.paged_decode_step(
            cfg, params, jnp.asarray(ids[40:41]), pool, tables,
            jnp.asarray([40]), impl="kernel")
        want = ref.forward(MODEL, params, ids[:41], probe=(40,))[3][40]
        got = np.asarray(left["selected"])[:, 0]
        assert [sorted(g.tolist()) for g in got] == [
            sorted(w.tolist()) for w in want[:2]]

    def test_the_cached_rows_carry_the_two_rescales(self, params):
        """Layer 0's cached latent of a prompt's first token is ``sqrt(d /
        rank) RMSNorm(h W_kva)``: the rescale is a factor after the norm, in
        the row a token caches, for the full kind's rank; and the reference
        without the rescales is another model."""
        ids = self.ids(1, 40, seed=2)[0]
        _, pool, _ = self.prefilled(CFG, params, ids, jnp.float32,
                                    prefill_step)
        w = jax.tree_util.tree_map(lambda a: np.asarray(a[0], np.float64),
                                   params["blocks_full"])
        x = np.asarray(params["wte"], np.float64)[ids[0]]

        def rms(a, gain):
            return a / np.sqrt(np.mean(a * a) + MODEL["rms_norm_eps"]) * gain

        c = rms((rms(x, w["ln1_scale"]) @ w["kv_a_w"])[:32],
                w["kv_a_norm_scale"]) * np.sqrt(64 / 32)
        got = np.asarray(pool["k_pages"])[0, 0, 1, 0]
        assert np.abs(got[:32] - c).max() < 1e-5
        assert (got[40:] == 0).all()        # zeros past rank + rope

    def test_the_slack_asks_what_was_left_out_and_refuses_a_wrong_count(
            self, params):
        """Handed its own selection the reference reads slack 0; with the
        weakest kept position swapped for the strongest left out, that
        distance over the scores' spread; with a position too few, no
        limit."""
        ids = self.ids(1, 41, seed=4)[0]
        last = 40
        x, own, _, sels = ref.forward(MODEL, params, ids, probe=(last,))
        rows = np.concatenate([np.asarray(own[last]), sels[last]], axis=1)
        out, slack = ref.logits(MODEL, params, ids, positions=[last],
                                choices={last: rows})
        assert (slack[last] == 0).all()
        want = np.asarray(ref.logits(MODEL, params, ids))[last]
        assert np.abs(np.asarray(out[0]) - want).max() < 1e-6
        other = rows.copy()
        left_out = sorted(set(range(41)) - set(rows[1, K:].tolist()))
        other[1, K] = left_out[0]           # one position swapped, in the
        moved_out, slack = ref.logits(      # last full layer
            MODEL, params, ids, positions=[last], choices={last: other})
        assert slack[last][1] > 0 and slack[last][0] == 0
        assert np.abs(np.asarray(moved_out[0]) - want).max() > 1e-6
        short = rows.copy()
        short[1, -1] = -1                   # fifteen positions of sixteen
        _, slack = ref.logits(MODEL, params, ids, positions=[last],
                              choices={last: short})
        assert np.isinf(slack[last][1])
        with pytest.raises(ValueError, match="window layer"):
            bad = rows.copy()
            bad[3, K] = 5
            ref.logits(MODEL, params, ids, positions=[last],
                       choices={last: bad})

    def test_the_familys_step_hands_experts_and_selections(self, params):
        ids = self.ids(1, 41, seed=6)[0]
        _, pool, tables = self.prefilled(CFG, params, ids, jnp.float32,
                                         prefill_step)
        logits, _, handed = family.paged_decode_step(
            CFG, params, jnp.asarray(ids[40:]), pool, tables,
            jnp.asarray([40]), impl="gather")
        handed = np.asarray(handed[0])
        assert handed.shape == (5, K + TOPK)
        assert (handed[0, :K] == -1).all() and (handed[2:, K:] == -1).all()
        out, slack = ref.logits(MODEL, params, ids, positions=[40],
                                choices={40: handed})
        assert np.abs(np.asarray(logits[0]) - np.asarray(out[0])).max() < TOL
        assert (slack[40] == 0).all()

    def test_a_decode_span_counts_the_rows_scored_and_kept(self, engines):
        """``trace.SELECT_STATS`` beside the window's rows: two full layers,
        a slot of ``n`` cached tokens scores ``n + 1 + j`` keys in step ``j``
        and keeps at most 16."""
        sched = engines().make_scheduler()
        sched.lengths[:] = [3, 0, 20, 8]
        mask = np.asarray([True, False, True, True])
        stats = sched._decode_stats(2, [0, 2, 3], mask)
        assert trace.SELECT_STATS == ("index_rows", "selected_rows")
        assert stats["index_rows"] == 2 * ((4 + 21 + 9) + (5 + 22 + 10))
        assert stats["selected_rows"] == 2 * ((4 + 16 + 9) + (5 + 16 + 10))
        assert stats["kv_rows_window"] == 3 + 13 + 8
        assert stats["live_kv_tokens"] == 31
        sched.close()


def test_the_indexer_and_the_selection_against_numpy():
    """``I(t, s) = sum_j w_tj relu(qI_tj . kI_s)``, the k-th largest by the
    floats' bits, and the ``k`` best with ties to the lower position."""
    rng = np.random.default_rng(0)
    q = rng.normal(size=(2, 5, 3, 16)).astype(np.float32)
    keys = rng.normal(size=(2, 40, 16)).astype(np.float32)
    weights = rng.normal(size=(2, 5, 3)).astype(np.float32)
    got = np.asarray(G._index_scores(jnp.asarray(q), jnp.asarray(weights),
                                     jnp.asarray(keys)))
    want = np.einsum("bths,bth->bts", np.maximum(
        np.einsum("bthd,bsd->bths", q, keys), 0), weights)
    assert np.abs(got - want).max() < 1e-5
    scores = np.round(want[0], 1)           # ties, negatives and zeros
    scores[0, :7] = 0.0
    scores[1, 3:9] = -np.inf
    for k in (1, 4, 16, 39):
        kth = np.asarray(G._kth_largest(jnp.asarray(scores), k))
        assert (kth == np.sort(scores, axis=1)[:, -k]).all(), k
        seen = np.ones(scores.shape, bool)
        seen[2, 20:] = False
        kept = np.asarray(G._selected(jnp.asarray(scores), jnp.asarray(seen),
                                      k))
        for t in range(5):
            order = np.argsort(-np.where(seen[t], scores[t], -np.inf),
                               kind="stable")[:k]
            mine = np.zeros(40, bool)
            mine[order] = True
            assert (kept[t] == (mine & seen[t])).all(), (k, t)
    # no more positions than the selection keeps: all that are seen
    few = jnp.asarray(scores[:, :8])
    assert np.asarray(G._selected(few, jnp.ones((5, 8), bool), 8)).all()


def test_the_shares_add_up_to_the_uncut_layer_and_tile_the_vocabulary():
    """Two chips' halves of the experts, the shared expert counted once, sum
    to the uncut reference's routed layer; and two halves of the vocabulary
    tile the uncut logits."""
    w = jax.tree_util.tree_map(lambda a: a[0], _whole()["moe_blocks_full"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 24, 64)),
                    jnp.float32)
    view = G.kind_view(CFG_WHOLE, CFG_WHOLE.attn_period[0])
    full, chosen = G._moe_delta(view, x, w)
    h = G.rms_norm(x, w["ln2_scale"], CFG.layer_norm_eps)[0]
    picked, gates = dropless.route(
        h @ w["router_w"], K, 1, 1, CFG.moe_scale, True, score="sigmoid",
        bias=w["router_bias"])
    assert np.array_equal(np.asarray(picked), np.asarray(chosen[0]))
    parts = []
    for first in (0, 8):
        parts.append(dropless.held_experts_ffn(
            h, picked, gates, w["experts_gate_w"][first:first + 8],
            w["experts_up_w"][first:first + 8],
            w["experts_down_w"][first:first + 8], (first, 8),
            functools.partial(G._act, CFG)))
        assert 0 < float(jnp.abs(parts[-1]).max())
    shared = G._mlp_on(CFG, h, w, "shared")
    total = sum(parts) + shared
    assert np.abs(np.asarray(total) - np.asarray(full[0])).max() < 2e-6
    with jax.default_matmul_precision("highest"):
        g, _, _ = ref.route(WHOLE, h, w, jnp.zeros((24, K), jnp.int32),
                            jnp.zeros((24,), bool))
        want = ref.held_experts(WHOLE, h, w, g) + ref.gated_mlp(
            h, w["shared_gate_w"], w["shared_up_w"], w["shared_down_w"])
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < TOL
    assert float(jnp.abs(parts[0] - want + shared).max()) > 1e-3
    # the vocabulary: rows 0-127 and 128-255 of the embedding and the head
    params = _whole()
    ids = np.random.default_rng(5).integers(0, 128, 30).astype(np.int32)
    whole = np.asarray(ref.logits(WHOLE, params, ids))
    for first in (0, 128):
        cut = dict(params, lm_head=params["lm_head"][first:first + 128],
                   wte=params["wte"][:128])
        model = dict(WHOLE, vocab_size=128)
        got = np.asarray(ref.logits(model, cut, ids))
        assert np.abs(got - whole[:, first:first + 128]).max() < 1e-6


def test_a_kind_the_block_does_not_compute_is_refused():
    tiny = G.PRESETS["tiny"]
    with pytest.raises(ValueError, match="attn_kind='gqa' and 'mla'"):
        dataclasses.replace(tiny, attn_gate=True)
    with pytest.raises(ValueError, match="index_topk"):
        dataclasses.replace(G.kind_view(CFG, CFG.attn_period[0]),
                            attn_window=4)
    with pytest.raises(ValueError, match="mla_lora_rescale"):
        dataclasses.replace(tiny, mla_lora_rescale=True)
    # a chunk of a prompt of ONE kind of latent rows keeps the dense path
    from benchmark.families import deepseek_v2

    one = deepseek_v2.config(config_file("tiny-deepseek-v2-serve")["model"])
    assert not G.chunks_to_pages(one) and G.chunks_to_pages(CFG)
    assert G.chunks_to_pages(tiny)
