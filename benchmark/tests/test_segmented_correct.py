"""``lib/correct.py`` with a segmented reference: ``serve_check`` on
``tools/deep_drift``'s stand-in for a served path of ``reference/ouro_ref.py``
(not the program) at tiny widths in float32, through its fake engine; each
fault the tool plants fails it; what a segmented reference needs of a family;
and that check pages are numbered densely, for every reference."""

import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import correct, manifest
from benchmark.reference import gpt_ref, olmoe_ref, ouro_ref
from benchmark.tools import deep_drift

MODEL = dict(deep_drift.OURO, vocab_size=96, n_layer=4, n_head=2, d_model=32,
             d_ff=40, total_ut_steps=3)
LENGTHS = (5, 9, 14, 18)
PAGE = 4
SEED = 3100000077
# float32 on both sides: what is left is the order of the sums
TIGHT = dict.fromkeys(correct.SEGMENT_QUANTITIES, 1e-4)


@pytest.fixture(scope="module")
def params():
    return deep_drift.init_params(MODEL, jax.random.PRNGKey(11),
                                  dtype=jnp.float32, std=0.2)


@pytest.fixture(autouse=True)
def tiny_stretches(monkeypatch):
    """Six stretches of two blocks, held as tightly as float32 allows."""
    monkeypatch.setattr(ouro_ref, "SEGMENT_BLOCKS", 2)
    monkeypatch.setattr(ouro_ref, "SEGMENT_TOL", TIGHT)


def engine_for(params, fault=None, pages=None):
    return deep_drift.Engine(
        MODEL, params, pages or deep_drift.pages_for(LENGTHS, PAGE), PAGE,
        deep_drift.pad_for(LENGTHS, PAGE), act=jnp.float32, fault=fault)


def check(params, engine, family=deep_drift, reference=ouro_ref):
    return correct.serve_check(family, reference, MODEL,
                               {"prompt_lens": list(LENGTHS)}, None, params,
                               engine, SEED)


def test_an_honest_path_passes_stretch_by_stretch(params, capsys):
    """In a pool of exactly its prompts' pages and the sink: the engine says
    so where a table names a page beyond it."""
    engine = engine_for(params)
    assert engine.num_pages == 1 + 4 + 5 + 6 + 7
    verdict = check(params, engine)
    assert verdict.ok, verdict.notes
    # a sequence: the embedding rows, six stretches, the two logits
    assert len(verdict.notes) == correct.SEQUENCES * (1 + 6 + 2)
    assert verdict.notes[0] == \
        "prompt 5, embedding rows: equal to the reference's"
    assert verdict.notes[1].startswith(
        "prompt 5, stretch 0 (0, 0, 2): exit states rms diff ")
    assert verdict.notes[6].startswith("prompt 5, stretch 5 (2, 2, 4): ")
    assert verdict.notes[7].startswith("prompt 5, after prefill: rms diff ")
    assert verdict.notes[8].startswith("prompt 5, after 8 decodes: rms diff ")
    assert not any("over:" in note for note in verdict.notes)
    assert capsys.readouterr().out.count("[bench] correct: ") == 36


@pytest.mark.parametrize("fault", deep_drift.FAULTS)
def test_each_planted_fault_fails(params, fault):
    """And a line says which stretch and which quantity said no."""
    ok, notes, readings = deep_drift.checked(
        MODEL, params, SEED, fault, lengths=LENGTHS, page_size=PAGE,
        act=jnp.float32)
    assert not ok and len(readings) == 6 * correct.SEQUENCES
    said = dict(deep_drift.caught_by(notes))
    assert said and any("over: " in note for note in notes)
    last_loop = {f"stretch {k}: {q}" for k in (4, 5) for q in TIGHT}
    if fault in ("last_loop_mask_short", "prompt_skips_last_loop",
                 "prompt_rows_a_loop_late"):
        assert set(said) <= last_loop | {"logits"}
    if fault == "no_loop_norm":        # where a loop closes, and not the last
        assert set(said) == {f"stretch {k}: {q}" for k in (1, 3)
                             for q in ("state_rms", "state_max")}


def test_the_far_logits_alone_do_not_see_a_fault_in_the_pool(params):
    """The two logits are held against the reference's head of the served
    last state: rows written a loop late leave them as they were, and the
    rows say it."""
    _, notes, _ = deep_drift.checked(
        MODEL, params, SEED, "prompt_rows_a_loop_late", lengths=LENGTHS,
        page_size=PAGE, act=jnp.float32)
    said = dict(deep_drift.caught_by(notes))
    assert "logits" not in said and "stretch 4: row_rms" in said


def test_the_old_rule_on_the_stand_in(params):
    """In float32 the stand-in's whole forward is the reference's."""
    ids = np.random.default_rng(5).integers(
        0, MODEL["vocab_size"], 18 + correct.DECODE_STEPS + 1, dtype=np.int32)
    got = deep_drift.whole_forward(MODEL, params, ids, page_size=PAGE,
                                   act=jnp.float32)
    ok, notes, pairs = deep_drift.old_rule(MODEL, params, ids, 18, got)
    assert ok and len(notes) == 2 and max(max(p) for p in pairs) < 1e-4


def test_a_pool_a_page_short_is_said_and_not_run(params):
    verdict = check(params, engine_for(
        params, pages=deep_drift.pages_for(LENGTHS, PAGE) - 1))
    assert not verdict.ok
    assert verdict.notes == ["the check's prompts need 23 pages with the "
                             "sink, the pool has 22"]


def test_check_pages_are_numbered_densely():
    """Whatever the reference: sequence j starts where j - 1 ended, not at
    ``1 + j * pages_per_seq``."""
    engine = types.SimpleNamespace(
        num_slots=6, num_pages=24, serving=types.SimpleNamespace(
            page_size=PAGE, pages_per_seq=32))
    (prompts, tables), why_not = correct.check_sequences(
        MODEL, {"prompt_lens": [18, 5, 9, 14, 9]}, engine, SEED)
    assert why_not is None and [len(p) for p in prompts] == [5, 9, 14, 18]
    assert tables.shape == (6, 32)
    used = [row[row > 0].tolist() for row in tables]
    assert used == [[1, 2, 3, 4], [5, 6, 7, 8, 9],
                    [10, 11, 12, 13, 14, 15],
                    [16, 17, 18, 19, 20, 21, 22], [], []]
    engine.num_slots = 3
    assert correct.check_sequences(MODEL, {"prompt_lens": [5]}, engine,
                                   SEED) == (None, "fewer slots than check "
                                                   "sequences")


def test_a_segmented_reference_needs_the_adapter(params):
    family = types.SimpleNamespace(
        __file__="families/no_states.py",
        prefill_states=deep_drift.prefill_states)
    with pytest.raises(manifest.ManifestError) as e:
        check(params, engine_for(params), family=family)
    assert "families/no_states.py lacks decode_states, gather_kv" \
        in str(e.value)
    assert os.path.join("reference", "ouro_ref.py") in str(e.value)


def test_states_of_another_shape_are_refused(params):
    family = types.SimpleNamespace(
        __file__="families/one_boundary.py",
        prefill_states=lambda *a: (0, np.zeros((2, 5, 32), np.float32)),
        decode_states=deep_drift.decode_states,
        gather_kv=deep_drift.gather_kv)
    with pytest.raises(manifest.ManifestError) as e:
        check(params, engine_for(params), family=family)
    assert "families/one_boundary.py handed states of shape (2, 5, 32)" \
        in str(e.value)
    assert "has 6 stretches" in str(e.value)


def test_segmented_and_routed_is_out_of_scope(params):
    both = types.SimpleNamespace(__file__="reference/both.py",
                                 SEGMENT_TOL=TIGHT, CHOICE_SLACK=0.1)
    with pytest.raises(manifest.ManifestError) as e:
        check(params, engine_for(params), reference=both)
    assert "reference/both.py" in str(e.value)
    assert "deep_drift.py" in str(e.value) and "out of scope" in str(e.value)


def test_the_other_references_are_not_segmented():
    assert correct.segmented(ouro_ref)
    assert not any(map(correct.segmented, (gpt_ref, olmoe_ref)))
    assert correct.routed(olmoe_ref) and not correct.routed(ouro_ref)
