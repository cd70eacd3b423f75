"""The comparison that decides ``correct``, made in set-up, outside the
window, against the plain reference the configuration names
(``benchmark/reference/<reference>.py``; ``gpt_ref`` where it names none).

Tolerances, and why. The program computes in bf16 (8 bits of mantissa, rounding
error 2**-9 per operation) from the same bf16 weights the reference upcasts, so
the two differ by accumulated bf16 rounding of activations over 24 blocks and
by nothing else.

- Serving logits: the root-mean-square difference over the vocabulary, as a
  share of the reference logits' own root-mean-square spread, may be at most
  ``LOGIT_RMS_TOL``; the largest single difference at most ``LOGIT_MAX_TOL``
  of the largest logit. On the chip the served bf16 path reads 0.0086-0.0096
  on the first and 0.0086-0.0105 on the second, over 32 comparisons in two
  cells and prompts of 64 to 1984 tokens (my chip runs, PR 23); a float32 path
  reads 1e-6 (the CPU test). The first is an average over 50304 logits and
  hardly moves, so its room is 30%: a second source of rounding as large as
  bf16's own (int8 pages or weights in place of bf16: PERF.md, PR 21 read them
  level) would raise it by a factor of 1.4 and fail. A wrong position, page or
  mask gives differences of the order of the logits themselves.
- Training loss: the step's loss on a batch of a few repeated sequences
  against the reference's mean loss on those sequences, within
  ``LOSS_ABS_TOL``. The loss is a mean over thousands of positions of a
  float32 log-sum-exp, so bf16 rounding averages out: the chip read
  differences of 0.00006 to 0.00014 (my chip runs, PR 23). A loss computed in
  bf16 (resolution 0.06 near 11) or a label shifted by one would not pass.

A routed model, and why its reference is handed the served step's experts.
"By nothing else" is false where a layer picks k experts of E. The choice is
discrete: where a token's k-th and (k+1)-th router logits lie closer than the
bf16 rounding of the activations that feed the router, the served path and the
float32 reference pick different experts, each correctly for its own input,
and the logits then differ by an expert's whole contribution. Measured
(``tools/routing_flips.py``, OLMoE's published widths at 8 layers, a bf16
stand-in against ``reference/olmoe_ref.py``, 42 seeds; my chip runs, PR 27):
30% of positions pick another set in some layer; where the reference routes
for itself those read 0.0070-0.021 on the first number (median 0.010) for the
0.0055-0.016 (median 0.0066) of the others, 4.6% of all positions are over the
tolerances above, and 12 of 42 runs of 8 comparisons come out not correct
though nothing is wrong. Under the experts the stand-in chose, all 336
comparisons read at most 0.0076 and 0.0095.
So a reference that routes takes the served step's choices and states a limit
for them (``benchmark/README.md``, the ``reference`` row), and
``serve_logits`` then makes the same 8 comparisons in another way (``judge``):

- the family's ``paged_decode_step`` returns, third, the experts its step
  chose for each slot's token, int32 ``[slots, n_layer, k]``: the served
  path's own choices, from its own bf16 activations;
- the reference's ``logits(..., choices=)`` computes the compared position with
  those experts in place of its own top-k (its gates stay its own float32
  probabilities of them, every other token routes as the reference would), and
  the pair is held to ``LOGIT_RMS_TOL`` and ``LOGIT_MAX_TOL`` as every cell
  is. A flip then costs nothing, and what the dense tolerance catches is
  caught as before: renormalised gates, an expert reported and not computed
  (another ``k`` is a shape error), wrong expert weights, a wrong position,
  page or mask;
- the same call returns the slack of what was handed over, per layer: how far
  the weakest expert taken lies under the strongest expert left out, by the
  reference's own router logits. That is held to the reference's
  ``CHOICE_SLACK``. An expert the router did not defensibly choose is caught
  here and not by the logits, which is the point: one wrong expert of eight in
  one of eight layers moves the logits by no more than a flip does. It asks
  what was left out as well as what was taken, so a step that omits its
  strongest expert and takes ranks 2 to 9 reads the distance from the 1st to
  the 9th, not the 8th to the 9th.

Both must pass, and each line says in how many layers the served set differed
from the reference's own and the largest slack. What this does not verify, and
what it needs (PERF.md, section 7):

- the router's precision. A router computed in bf16 reads the same slack as
  one in float32 and passes both checks: the rounding of its input, which both
  have, is what moves a choice;
- check prompts of at least 64 tokens. With the context left to the reference,
  positions under 64 read up to 0.0127 on the first number (1 of 1,344
  readings over the tolerance, at position 8); from 64 on at most 0.0087 and
  0.0095 (5,712 readings). A routed cell's traffic has no shorter prompt;
- the limit is calibrated on a stand-in with N(0, 0.02) weights, not on the
  program. The PR that brings a routed family reads the honest and the
  bf16-router rows again with its own step before its cell is accepted.

A reference without ``CHOICE_SLACK`` (``gpt_ref``) is compared as above and
prints what it printed; one with it and a family whose step returns two values
is a ``ManifestError`` that names both files. ``train_loss`` is as it was:
whether a top-8 first-step loss stays inside ``LOSS_ABS_TOL`` is open (PERF.md,
section 7).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

from .device import say
from .manifest import ManifestError, family_of, reference_of

LOGIT_RMS_TOL = 0.0125
LOGIT_MAX_TOL = 0.02
LOSS_ABS_TOL = 0.002
DECODE_STEPS = 8
SEQUENCES = 4


@dataclasses.dataclass
class Verdict:
    ok: bool
    notes: List[str]


def model_of(cell: dict) -> dict:
    return cell["config_file"]["model"]


def logit_differences(got, want):
    """The two numbers compared, for logits [V] or rows of them [.., V]: the
    root-mean-square difference over the reference's root-mean-square spread,
    and the largest difference over the largest reference logit."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    spread = np.sqrt(np.mean((want - want.mean(-1, keepdims=True)) ** 2, -1))
    rms = np.sqrt(np.mean((got - want) ** 2, -1)) / spread
    return rms, np.max(np.abs(got - want), -1) / np.max(np.abs(want), -1)


def compare_logits(tag: str, got, want, notes: List[str]) -> bool:
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        notes.append(f"{tag}: non-finite logits")
        return False
    rms, worst = map(float, logit_differences(got, want))
    notes.append(f"{tag}: rms diff {rms:.3g} of the logits' spread, "
                 f"max diff {worst:.3g} of the largest logit")
    return rms <= LOGIT_RMS_TOL and worst <= LOGIT_MAX_TOL


def compare_choices(slack, limit: float, notes: List[str]) -> bool:
    """``slack`` [n_layer] is the reference's slack at the position whose
    logits ``notes[-1]`` compares; said on the same line."""
    slack = np.asarray(slack, np.float32)
    worst = float(slack.max())
    notes[-1] += (f"; served experts differ from the reference's own in "
                  f"{int((slack > 0).sum())} of {slack.size} layers, largest "
                  f"slack {worst:.3g} of the router logits' spread (at most "
                  f"{limit:g})")
    return bool(np.isfinite(slack).all()) and worst <= limit


def routed(reference) -> bool:
    return hasattr(reference, "CHOICE_SLACK")


def step_outputs(family, reference):
    """What the comparison takes from ``family.paged_decode_step``'s values:
    the logits, and for a routed reference the experts chosen beside them."""
    if not routed(reference):
        return lambda out: out[0]

    def logits_and_choices(out):
        if len(out) < 3:
            raise ManifestError(
                f"{reference.__file__} defines CHOICE_SLACK, so the "
                f"comparison needs the experts the served step chose, and "
                f"paged_decode_step of {family.__file__} returns "
                f"{len(out)} values: the third is int32 [slots, n_layer, k]")
        return out[0], out[2]
    return logits_and_choices


def reference_side(reference, model: dict, params, ids, positions, handed):
    """The reference's logits [len(positions), V] of one sequence from one
    forward and, where ``handed`` maps each position to the experts
    [n_layer, k] the served step chose there, their slack {position:
    [n_layer]} under those experts; None where ``handed`` is."""
    if handed is None:
        return np.asarray(reference.logits(model, params, ids,
                                           positions=positions)), None
    ref, slack = reference.logits(model, params, ids, positions=positions,
                                  choices=handed)
    return np.asarray(ref), slack


def hold(reference, positions, tags, got, ref, slack,
         notes: List[str]) -> bool:
    """The served logits ``got[i]`` at ``positions[i]`` against ``ref[i]``,
    and with a ``slack`` the handed experts against ``CHOICE_SLACK``."""
    ok = True
    for tag, pos, g, r in zip(tags, positions, got, ref):
        ok &= compare_logits(tag, g, r, notes)
        if slack is not None:
            ok &= compare_choices(slack[pos], reference.CHOICE_SLACK, notes)
    return ok


def judge(reference, model: dict, params, ids, positions, tags, got, handed,
          notes: List[str]) -> bool:
    """One sequence's comparisons (``reference_side``, then ``hold``)."""
    ref, slack = reference_side(reference, model, params, ids, positions,
                                handed)
    return hold(reference, positions, tags, got, ref, slack, notes)


def serve_logits(cell: dict, cfg, params, engine, seed: int) -> Verdict:
    """For ``SEQUENCES`` seeded prompts of lengths the traffic uses: prefill
    through the engine's own path into pages, then ``DECODE_STEPS`` decode
    steps through the paged cache. The logits of the step after the prefill
    and of the step after the decodes (from the program's own
    ``paged_decode_step`` over the engine's pools, the function the decode
    program wraps) must agree with the reference's full forward over the same
    tokens. With a routed reference, under the experts that step chose
    for that token (``judge``)."""
    import jax
    import jax.numpy as jnp

    family = family_of(cell["config_file"])
    reference = reference_of(cell["config_file"])
    model, s = model_of(cell), engine.serving
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC0FFEE]))
    grid = sorted(set(cell["traffic_file"]["prompt_lens"]))
    picks = [grid[i * (len(grid) - 1) // (SEQUENCES - 1)]
             for i in range(SEQUENCES)]
    if engine.num_slots < SEQUENCES:
        return Verdict(False, ["fewer slots than check sequences"])
    impl = s.kernel_impl
    taken = step_outputs(family, reference)
    step = jax.jit(lambda p, c, t, tb, ln: taken(family.paged_decode_step(
        cfg, p, t, c, tb, ln, impl=impl)))

    n = engine.num_slots
    tables = np.zeros((n, s.pages_per_seq), np.int32)
    lengths = np.zeros(n, np.int32)
    nxt = np.zeros(n, np.int32)
    active = np.zeros(n, bool)
    seqs = []
    for j, length in enumerate(picks):
        prompt = rng.integers(0, model["vocab_size"], size=length,
                              dtype=np.int32)
        pages = -(-(length + DECODE_STEPS + 2) // s.page_size)
        tables[j, :pages] = 1 + j * s.pages_per_seq + np.arange(pages)
        nxt[j] = engine.prefill(j, prompt, tables[j])
        lengths[j] = length
        active[j] = True
        seqs.append(list(prompt) + [int(nxt[j])])

    def step_now():
        """The logits of the check sequences' next token and, for a routed
        reference, the experts chosen for it."""
        out = jax.device_get(step(
            engine.params, engine.paged_cache, jnp.asarray(nxt),
            jnp.asarray(tables), jnp.asarray(lengths)))
        logits, chosen = out if routed(reference) else (out, None)
        return np.asarray(logits)[:SEQUENCES], chosen

    after_prefill, chose_prefill = step_now()
    for _ in range(DECODE_STEPS):
        out = engine.decode(nxt.copy(), tables.copy(), lengths.copy(), active,
                            steps=1)
        lengths[active] += 1
        nxt[:SEQUENCES] = out[0, :SEQUENCES]
        for j in range(SEQUENCES):
            seqs[j].append(int(out[0, j]))
    after_decode, chose_decode = step_now()

    notes, ok = [], True
    for j, length in enumerate(picks):
        positions = [length, length + DECODE_STEPS]
        handed = {pos: np.asarray(chose[j]) for pos, chose in zip(
            positions, (chose_prefill, chose_decode))} \
            if routed(reference) else None
        ok &= judge(reference, model, params, np.asarray(seqs[j], np.int32),
                    positions,
                    [f"prompt {length}, after prefill",
                     f"prompt {length}, after {DECODE_STEPS} decodes"],
                    [after_prefill[j], after_decode[j]], handed, notes)
    for line in notes:
        say(f"correct: {line}")
    return Verdict(bool(ok), notes)


def reference_loss(cell: dict, params, sample_ids) -> float:
    return reference_of(cell["config_file"]).loss(model_of(cell), params,
                                                  sample_ids)


def train_loss(cell: dict, engine_loss: float, want: float,
               n_sequences: int) -> Verdict:
    note = (f"first step loss {engine_loss:.5f}, reference {want:.5f} on "
            f"{n_sequences} sequences (ln V = "
            f"{math.log(model_of(cell)['vocab_size']):.4f})")
    say(f"correct: {note}")
    ok = math.isfinite(engine_loss) and abs(engine_loss - want) <= LOSS_ABS_TOL
    return Verdict(ok, [note])
