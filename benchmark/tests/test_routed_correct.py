"""``lib/correct.py`` with a routed reference: the judging step on a tiny
bf16 stand-in for a served path (``tools/routing_flips.stand_in``: not the
program) against ``reference/olmoe_ref.py``, and that a reference without
``CHOICE_SLACK`` is compared as it was."""

import os
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import correct, manifest
from benchmark.reference import gpt_ref, olmoe_ref
from benchmark.tools import routing_flips
from benchmark.tests.test_olmoe_ref import MODEL
from benchmark.tests.test_rehearsal import run

LENGTH = 31
POSITIONS = [LENGTH, LENGTH + correct.DECODE_STEPS]
TAGS = ["after prefill", "after decodes"]


@pytest.fixture(scope="module")
def served():
    """Weights as they are served (bf16), one sequence, and what the honest
    stand-in says at the two compared positions."""
    params = routing_flips.init_params(MODEL, jax.random.PRNGKey(5),
                                       dtype=jnp.bfloat16, std=0.1)
    ids = np.random.default_rng(1).integers(
        0, MODEL["vocab_size"], LENGTH + correct.DECODE_STEPS + 1,
        dtype=np.int32)
    return params, ids


def judged(params, ids, **fault):
    got, chosen = map(np.asarray, routing_flips.stand_in(
        MODEL, params, ids, **fault))
    handed = {p: chosen[p] for p in POSITIONS}
    notes = []
    ok = correct.judge(olmoe_ref, MODEL, params, ids, POSITIONS, TAGS,
                       [got[p] for p in POSITIONS], handed, notes)
    slack = [float(re.search(r"largest slack (\S+) of", n).group(1))
             for n in notes]
    return ok, notes, max(slack)


def logit_numbers(notes):
    return [tuple(map(float, re.search(
        r"rms diff (\S+) of the logits' spread, max diff (\S+) of", n).groups()))
        for n in notes]


def test_the_stand_in_has_the_references_equations(served):
    """In float32 the stand-in and the reference agree to rounding and choose
    the same experts: what separates them in bf16 is rounding alone."""
    params, ids = served
    got, chosen = routing_flips.stand_in(MODEL, params, ids, act=jnp.float32)
    want = np.asarray(olmoe_ref.logits(MODEL, params, ids))
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * np.abs(want).max()
    own = np.asarray(olmoe_ref.forward(MODEL, params, ids)[1])
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(own, -1))


def test_an_honest_bf16_path_passes_with_its_choices_handed_over(served):
    params, ids = served
    ok, notes, slack = judged(params, ids)
    assert ok and len(notes) == 2 and slack <= olmoe_ref.CHOICE_SLACK
    for note, tag in zip(notes, TAGS):
        assert note.startswith(f"{tag}: rms diff ")
        assert re.search(r"; served experts differ from the reference's own "
                         r"in \d of 2 layers, largest slack \S+ of the router "
                         r"logits' spread \(at most 0\.\d+\)$", note)
    assert all(rms <= correct.LOGIT_RMS_TOL and worst <= correct.LOGIT_MAX_TOL
               for rms, worst in logit_numbers(notes))


def test_one_replaced_expert_fails_on_the_slack(served):
    """Its logits stay inside the tolerance: one expert of eight in one layer
    moves them by no more than a flip does."""
    params, ids = served
    ok, notes, slack = judged(params, ids, fault="wrong_expert",
                              fault_layer=1, at=POSITIONS)
    assert not ok and slack > 5 * olmoe_ref.CHOICE_SLACK
    assert all(rms <= correct.LOGIT_RMS_TOL and worst <= correct.LOGIT_MAX_TOL
               for rms, worst in logit_numbers(notes))
    assert "in 1 of 2 layers" in notes[0]


def test_a_dropped_strongest_expert_fails_on_the_slack(served):
    """Ranks 2 to 9 in one layer: the weakest taken is a near tie with the
    reference's 8th, the logits agree because the reference computes with the
    handed set, and the slack reads what was left out."""
    params, ids = served
    ok, notes, slack = judged(params, ids, fault="dropped_strongest",
                              fault_layer=1, at=POSITIONS)
    assert not ok and slack > 5 * olmoe_ref.CHOICE_SLACK
    assert all(rms <= correct.LOGIT_RMS_TOL and worst <= correct.LOGIT_MAX_TOL
               for rms, worst in logit_numbers(notes))


@pytest.mark.parametrize("fault", ["renormalised_gates", "dropped_expert"])
def test_wrong_gates_fail_on_the_logits(served, fault):
    """The experts reported are the ones the path chose, so it is the logits
    that say it: every comparison is over the tolerance every cell is held
    to."""
    params, ids = served
    ok, notes, _ = judged(params, ids, fault=fault)
    assert not ok
    assert all(rms > correct.LOGIT_RMS_TOL for rms, _ in logit_numbers(notes))


def test_a_routed_reference_needs_the_steps_choices():
    family = types.SimpleNamespace(__file__="families/two_values.py")
    take = correct.step_outputs(family, olmoe_ref)
    assert take(("logits", "cache", "chosen")) == ("logits", "chosen")
    with pytest.raises(manifest.ManifestError) as e:
        take(("logits", "cache"))
    assert "families/two_values.py" in str(e.value)
    assert os.path.join("reference", "olmoe_ref.py") in str(e.value)


def test_a_reference_without_choices_is_compared_as_it_was(monkeypatch):
    """``gpt_ref`` takes no choices and states no limit: the step's logits alone
    are taken, ``logits`` is called without ``choices``, and a line says what
    it said."""
    assert not correct.routed(gpt_ref)
    family = types.SimpleNamespace(__file__="families/gpt.py")
    assert correct.step_outputs(family, gpt_ref)(("logits", "cache")) \
        == "logits"

    calls = []

    def logits(model, params, ids, positions=None):
        calls.append(positions)
        return np.ones((len(positions), 4), np.float32) * [[1, 2, 3, 5]]

    plain = types.SimpleNamespace(logits=logits)
    notes = []
    got = [np.array([1, 2, 3, 5], np.float32)] * 2
    assert correct.judge(plain, {}, None, np.arange(9), [3, 8], TAGS, got,
                         None, notes)
    assert calls == [[3, 8]]
    assert notes == [f"{tag}: rms diff 0 of the logits' spread, max diff 0 of "
                     "the largest logit" for tag in TAGS]


def test_tiny_serve_prints_the_lines_it_printed():
    """``tiny-serve.tiny-closed`` (``gpt_ref``) makes today's 8 comparisons:
    the lines of the parent of the PR that let a reference take choices,
    recorded on this seed, word for word; a number may move in its last
    digit on another CPU."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tiny-serve.tiny-closed.correct.txt")) as f:
        want = f.read().splitlines()
    done = run("tiny-serve.tiny-closed", 0)
    assert done.returncode == 0, done.stderr[-2000:]
    got = [line[len("[bench] "):] for line in done.stdout.splitlines()
           if line.startswith("[bench] correct:")]
    number = re.compile(r"\d+\.\d+(?:e-?\d+)?")
    assert len(got) == len(want) == 2 * correct.SEQUENCES
    for g, w in zip(got, want):
        assert number.sub("#", g) == number.sub("#", w)
        np.testing.assert_allclose([float(x) for x in number.findall(g)],
                                   [float(x) for x in number.findall(w)],
                                   rtol=0.02)
