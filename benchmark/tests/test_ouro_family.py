"""Family ``ouro``: the looped model on the program's own path, held by
``lib/correct.serve_segments`` through the family's adapter over a real
``ServingEngine`` (float32 on the CPU: every reading under 1e-4, where what is
left is the order of the sums), and the two readers the loop brought
(``prog_scope_ms`` on a hand-made piece, ``pool_fill_pct`` on the program's
spans). The rehearsal cell ``tiny-ouro-serve.tiny-closed`` runs end to end in
``test_rehearsal.py``, which finds it by its file."""

import dataclasses
import json

import numpy as np
import pytest

import jax

from benchmark.families import ouro
from benchmark.lib import correct, manifest
from benchmark.lib import program_trace as P
from benchmark.lib.context import Context
from benchmark.lib.window import Window
from benchmark.readers import prog_scope_ms, prog_span_ratio
from benchmark.reference import ouro_ref

MODEL = {"vocab_size": 96, "n_layer": 4, "n_head": 2, "d_model": 32,
         "d_ff": 40, "max_seq_len": 128, "total_ut_steps": 3,
         "rope_theta": 1e6, "rms_norm_eps": 1e-6, **ouro_ref.COVERS}
LENGTHS = [5, 9, 14, 18]           # 14 and 18 are longer than a chunk
SEED = 3100000077
TIGHT = dict.fromkeys(correct.SEGMENT_QUANTITIES, 1e-4)


@pytest.fixture(autouse=True)
def tiny_stretches(monkeypatch):
    """Six stretches of two blocks (a cut in the middle of every pass), held
    as tightly as float32 allows."""
    monkeypatch.setattr(ouro_ref, "SEGMENT_BLOCKS", 2)
    monkeypatch.setattr(ouro_ref, "SEGMENT_TOL", TIGHT)


def engine_for(cfg, params):
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    return ServingEngine(cfg, params, ServingConfig(
        num_slots=4, num_pages=24, page_size=4, max_model_len=32,
        prefill_chunk=8, decode_block=2, dtype="float32",
        dispatch_retries=0))


def check(cfg, params):
    return correct.serve_check(ouro, ouro_ref, MODEL,
                               {"prompt_lens": LENGTHS}, cfg, params,
                               engine_for(cfg, params), SEED)


def test_config_maps_the_references_names_and_refuses_what_it_refuses():
    cfg = ouro.config(dict(MODEL, use_flash=True))
    assert (cfg.n_layer, cfg.ut_steps, cfg.state_layers) == (4, 3, (2, 4))
    assert (cfg.norm, cfg.mlp_gated, cfg.linear_bias, cfg.post_norm,
            cfg.loop_norm, cfg.rope_theta, cfg.use_flash) == (
        "rmsnorm", True, False, True, True, 1e6, True)
    assert ouro.config(MODEL).use_flash is None
    with pytest.raises(ValueError, match="qk_norm"):
        ouro.config(dict(MODEL, qk_norm=True))
    params = ouro.init_params(cfg, jax.random.PRNGKey(0))
    got = ouro_ref.logits(MODEL, params, np.arange(6, dtype=np.int32))
    assert got.shape == (6, 96)          # the reference reads this tree


def test_the_adapter_hands_serve_segments_the_programs_own_states(capsys):
    """Fused, batched and chunked prefill, then nine decode steps: every
    stretch of every check sequence under 1e-4, the embedding rows equal."""
    cfg = ouro.config(MODEL)
    params = ouro.init_params(cfg, jax.random.PRNGKey(11))
    verdict = check(cfg, params)
    assert verdict.ok, verdict.notes
    lines = [n for n in verdict.notes if "stretch" in n]
    assert len(lines) == 4 * 6 and not any("over:" in n for n in lines)
    assert sum("equal to the reference's" in n for n in verdict.notes) == 4


@pytest.mark.parametrize("field,value,says", [
    ("loop_norm", False, "state_rms"),        # no closing norm between loops
    ("rope_theta", 1e4, "first_row_rms"),     # another rotation
])
def test_a_program_that_computes_something_else_fails_its_stretch(field, value,
                                                                  says):
    cfg = dataclasses.replace(ouro.config(MODEL), **{field: value})
    params = ouro.init_params(cfg, jax.random.PRNGKey(11))
    verdict = check(cfg, params)
    assert not verdict.ok
    assert any("over:" in n and says in n.split("over:")[1]
               for n in verdict.notes)


def test_decode_states_holds_the_programs_token_to_its_own_last_state():
    cfg = ouro.config(MODEL)
    engine = engine_for(cfg, ouro.init_params(cfg, jax.random.PRNGKey(11)))
    made, _ = correct.check_sequences(MODEL, {"prompt_lens": LENGTHS}, engine,
                                      SEED)
    prompts, tables = made
    tok, states = ouro.prefill_states(engine, 0, prompts[0], tables[0])
    assert states.shape == (7, 5, 32)
    nxt = np.zeros(4, np.int32)
    nxt[0] = tok
    lengths = np.asarray([5, 0, 0, 0], np.int32)
    active = np.asarray([True, False, False, False])
    logits, out, st = ouro.decode_states(engine, nxt, tables, lengths, active)
    assert logits.shape == (4, 96) and st.shape == (4, 7, 32)
    assert out[0] == np.argmax(logits[0])
    keys, values = ouro.gather_kv(engine, tables[0], 6)
    assert keys.shape == values.shape == (12, 2, 6, 16)
    real = engine.decode
    engine.decode = lambda *a, **kw: (real(*a, **kw) + 1) % 96
    with pytest.raises(RuntimeError, match="its head computed something"):
        ouro.decode_states(engine, nxt, tables, lengths + 1, active)


# ------------------------------------------------------------ the readers
def _ctx():
    cell = {"config_file": {"model": MODEL, "reference": "ouro_ref"}}
    return Context(cell=cell, window=Window(0.0, 10.0, [{}], [10.0]),
                   spans=None, requests=[], facts={},
                   device_kind="TPU v5 lite", chips=1, setup_s=0.0)


def _piece(tmp_path, scopes):
    """Two whole executions of ``jit_decode_block_2`` (2 steps each) and one
    cut by the window's end, on one device: a pass loop ``while.1`` of 3 ms
    around a layer loop and a norm, an embedding before it, a head after."""
    def run(at):
        return [["while.1", "while", False, at + 1.0, at + 4.0],
                ["fusion.7", "fusion", False, at + 1.1, at + 2.9],   # blocks
                ["copy.3", "copy", False, at + 2.9, at + 3.0],  # no op_name
                ["fusion.9", "fusion", False, at + 3.0, at + 3.9],   # norm
                ["fusion.2", "fusion", False, at + 0.2, at + 0.9],   # embed
                ["fusion.11", "fusion", False, at + 4.2, at + 4.8]]  # head

    ops = [[i, k, j, s * 1e-3, e * 1e-3]
           for at in (0.0, 10.0, 26.0) for i, k, j, s, e in run(at)]
    data = {"window": [0.0, 0.030],
            "spans": [["serve.decode", 0.0, 0.005,
                       {"steps": 2, "active": 3, "live_kv_tokens": 90,
                        "cache_layers": 12, "pool_tokens": 200}],
                      ["serve.decode", 0.010, 0.015,
                       {"steps": 2, "active": 3, "live_kv_tokens": 110,
                        "cache_layers": 12, "pool_tokens": 200}]],
            "modules": {"0": [["jit_decode_block_2", 0.0, 0.005],
                              ["jit_decode_block_2", 0.010, 0.015],
                              ["jit_decode_block_2", 0.026, 0.031]]},
            "ops": {"0": ops}, "device_async": {},
            "module_ids": {"jit_decode_block_2": [77]}}
    kept = tmp_path / P.SCOPES_DIR
    kept.mkdir(parents=True)
    (kept / "decode_block_2.77.json").write_text(json.dumps(scopes))
    return P.from_plain(data, str(tmp_path))


LOOPED = {"while.1": "jit(decode_block_2)/while/body/ut_loop/while",
          "fusion.7": "jit(decode_block_2)/while/body/ut_loop/blocks/while/"
                      "body/attn/dot_general",
          "fusion.9": "jit(decode_block_2)/while/body/ut_loop/loop_norm/mul",
          "fusion.2": "jit(decode_block_2)/while/body/embed/take",
          "fusion.11": "jit(decode_block_2)/while/body/head_loss/dot_general"}
PARAMS = {"pattern": "^jit_decode_block_(\\d+)$", "steps_group": 1,
          "scope": "ut_loop"}


def test_prog_scope_ms_reads_the_time_under_a_scope_per_step_and_pass(
        tmp_path, monkeypatch):
    pt = _piece(tmp_path, LOOPED)
    monkeypatch.setattr(P, "of", lambda ctx: pt)
    # 3 ms a whole execution lie under ut_loop (the copy takes the while's
    # name); 2 executions x 2 steps x 3 passes; the cut execution is not read
    assert prog_scope_ms.read(_ctx(), PARAMS) == pytest.approx(
        2 * 3.0 / (2 * 2 * 3))
    spec = manifest.load_metric("decode_loop_pass_ms")
    assert spec["reader"] == "prog_scope_ms" and spec["params"] == PARAMS


def test_prog_scope_ms_finds_nothing_where_no_such_scope_is_compiled_in(
        tmp_path, monkeypatch):
    """The parent's programs, or a model that runs its stack once."""
    unlooped = {k: v.replace("ut_loop/", "").replace("loop_norm", "head_loss")
                for k, v in LOOPED.items()}
    pt = _piece(tmp_path, unlooped)
    monkeypatch.setattr(P, "of", lambda ctx: pt)
    assert prog_scope_ms.read(_ctx(), PARAMS) is None
    monkeypatch.setattr(P, "of", lambda ctx: None)
    assert prog_scope_ms.read(_ctx(), PARAMS) is None


def test_pool_fill_pct_reads_the_decode_spans_counts(tmp_path, monkeypatch):
    pt = _piece(tmp_path, LOOPED)
    monkeypatch.setattr(P, "of", lambda ctx: pt)
    spec = manifest.load_metric("pool_fill_pct")
    assert spec["reader"] == "prog_span_ratio"
    assert prog_span_ratio.read(_ctx(), spec["params"]) == pytest.approx(
        100.0 * (90 + 110) / 400)
    # the parent's spans carry no pool_tokens: nothing to read
    for s in pt.spans:
        del s.stats["pool_tokens"]
    assert prog_span_ratio.read(_ctx(), spec["params"]) is None
