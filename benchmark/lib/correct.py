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

A model whose layers run many times, and why it is held stretch by stretch.
"Over 24 blocks" is the other half of the first paragraph, and the tolerances
are calibrated there. ``tools/deep_drift.py`` reads the same two numbers, by
``judge`` as above, for a bf16 stand-in of ``reference/ouro_ref.py``'s
equations (sandwich norms, gated MLP, hidden 2048, 16 heads of 128, weights
N(0, 0.02) rounded to bf16) at other depths, unshared and looped over one set
of weights (my chip runs, PR 31; 2 seeds x prompts of 64, 128, 256, 512, two
comparisons a prompt, 16 readings a shape: median and largest of each number):

    blocks x loops     first number        second number
    24 x 1             0.0153   0.0165     0.0151   0.0173
    48 x 1             0.0201   0.0209     0.0213   0.0226
    96 x 1             0.0271   0.0286     0.0259   0.0286
    12 x 4 (looped)    0.0390   0.0454     0.0431   0.0502
    24 x 4 (looped)    0.0865   0.1261     0.0858   0.1181
    48 x 2 (looped)    0.0357   0.0415     0.0382   0.0451
    48 x 4 (Ouro's)    0.2395   0.3340     0.2287   0.3387

(This block at 24 x 1 reads half again what the program's GPT-NeoX block does
at 24: four norms and a gated product a block round more often. 192 unshared
blocks of this width are 19.7 GB and fit no chip.) Depth alone grows the
reading slowly, about 1.3 times a doubling; running one set of seeded weights
again multiplies what the last loop left, 2.2 to 2.8 times a doubling of the
loops (a trained looped model's loops contract; seeded weights are what the
benchmark has). At Ouro's shape, 48 blocks four times, an honest bf16 path ends
a quarter of the logits' spread from its float32 reference and fails in every
seed, and no tolerance on the far logits is both passed by that and failed by
pages rounded to 8 bits, which move a stretch's rows by 0.7% of their size. So
the guide's rule (a comparison tight enough that the next lower precision
fails it) cannot be kept by loosening, and is kept by cutting: a reference
that states ``SEGMENT_TOL`` is segmented (``benchmark/README.md``, the
``reference`` and ``family`` rows), and ``serve_check`` then compares in
another way (``serve_segments``):

- the family hands over, from the engine's own programs over the engine's own
  pool, the residual stream of every token of a check sequence at every
  boundary between the reference's stretches (at most 24 block applications
  each, the depth calibrated above), and the rows the pool holds for it.
  States and rows come from one execution, the one that filled the pages: a
  step jitted apart rounds differently and, four loops on, is as far from the
  pool's rows as from the reference;
- each stretch is computed once by the reference, in float32, from the served
  path's own entry states, and held at every position: its exit states
  against the served exit states, its rows against the pool's, both by
  ``worst_rows`` under the reference's limits; the rows of a stretch's first
  block, which come from the served entry states through one norm, one
  product and the rotation, under limits of their own, seven times tighter:
  that is where pages kept in a lower precision show (0.0072 or more for an
  honest 0.0030), the drift of 24 blocks would cover them. Boundary 0 is held
  against the embedding rows exactly, and the two logits of today against the
  reference's head of the served last state, under ``LOGIT_RMS_TOL`` and
  ``LOGIT_MAX_TOL``. Every link is held on the served path's own input, so
  nothing compounds, and nothing a step reads goes unheld: a wrong row, page,
  position or mask shows in the stretch that first reads or writes it;
- the limits are the reference's, set at most 1.3 times the largest honest
  reading of each quantity (``reference/ouro_ref.py`` has them and the
  readings), the room ``LOGIT_RMS_TOL`` has. Of nine planted faults
  (``tools/deep_drift.FAULTS``) each fails in every seed; PERF.md, section 6
  (PR 31) says which stretch and quantity says so.

A reference without ``SEGMENT_TOL`` is compared as it was, two things apart
that hold for every cell: check pages are numbered densely (sequence ``j``
starts where ``j - 1`` ended, so the pool need only hold the four prompts and
the sink: numbered by slot row, Ouro's 96 MiB pages asked 10.7 GB of a pool
whose prompts need 1.9), and the check's own step donates the pool and hands
it back (``check_step``). A reference with both ``SEGMENT_TOL`` and
``CHOICE_SLACK``, and a segmented reference whose family lacks the adapter,
are ``ManifestError``s that name both files.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import List

import numpy as np

from .device import say
from .manifest import ManifestError, family_of, reference_of

LOGIT_RMS_TOL = 0.0125
LOGIT_MAX_TOL = 0.02
LOSS_ABS_TOL = 0.002
DECODE_STEPS = 8
SEQUENCES = 4
# the two numbers of a line that ``compare_logits`` wrote
LOGIT_LINE = re.compile(r"rms diff (\S+) of the logits' spread, max diff "
                        r"(\S+) of the largest logit")


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


def segmented(reference) -> bool:
    return hasattr(reference, "SEGMENT_TOL")


SEGMENT_ADAPTER = ("prefill_states", "decode_states", "gather_kv")


def segment_adapter(family, reference) -> None:
    """A segmented reference needs a family that hands over the served
    path's states and rows (``benchmark/README.md``, the ``family`` row)."""
    if routed(reference):
        raise ManifestError(
            f"{reference.__file__} defines SEGMENT_TOL and CHOICE_SLACK: a "
            f"reference that is segmented and routed is out of scope (the "
            f"handed experts would have to reach every stretch); "
            f"{family.__file__} cannot be compared with it")
    missing = [name for name in SEGMENT_ADAPTER if not hasattr(family, name)]
    if missing:
        raise ManifestError(
            f"{reference.__file__} defines SEGMENT_TOL, so the comparison "
            f"holds each stretch on the served path's own states and rows, "
            f"and {family.__file__} lacks {', '.join(missing)}: "
            f"prefill_states(engine, slot, prompt, table) -> (next token, "
            f"states [n_seg + 1, T, d]), decode_states(engine, tokens, "
            f"tables, lengths, active) -> (logits [slots, V], next tokens "
            f"[slots], states [slots, n_seg + 1, d]), gather_kv(engine, "
            f"table, length) -> (keys, values) [cache layers, H, length, Dh]")


def check_step(family, reference, cfg, impl):
    """The check's own decode step over the engine's pool: the family's
    ``paged_decode_step``, the function the decode program wraps, jitted to
    return what ``step_outputs`` takes and the pool. The pool is donated and
    handed back, so one pool is live (undonated, the program held a second:
    14.94 GB for ``batch-decode`` at 545 pages, compile-only, PR 28). The row
    it writes is the row the next ``engine.decode`` writes again in place."""
    import jax

    taken = step_outputs(family, reference)

    def fn(params, cache, tokens, tables, lengths):
        out = family.paged_decode_step(cfg, params, tokens, cache, tables,
                                       lengths, impl=impl)
        return taken(out), out[1]
    return jax.jit(fn, donate_argnums=(1,))


def check_sequences(model: dict, traffic: dict, engine, seed: int):
    """``SEQUENCES`` seeded prompts of lengths the traffic uses and their
    block tables [slots, pages_per_seq]: pages numbered densely from 1 (0 is
    the sink), each sequence taking what its prompt, the decoded tokens and
    the steps' writes need, so the pool a check needs is its prompts' pages
    and the sink. None where the engine cannot hold them."""
    s = engine.serving
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC0FFEE]))
    grid = sorted(set(traffic["prompt_lens"]))
    picks = [grid[i * (len(grid) - 1) // (SEQUENCES - 1)]
             for i in range(SEQUENCES)]
    if engine.num_slots < SEQUENCES:
        return None, "fewer slots than check sequences"
    tables = np.zeros((engine.num_slots, s.pages_per_seq), np.int32)
    prompts, first = [], 1
    for j, length in enumerate(picks):
        prompts.append(rng.integers(0, model["vocab_size"], size=length,
                                    dtype=np.int32))
        pages = -(-(length + DECODE_STEPS + 2) // s.page_size)
        if pages > s.pages_per_seq:
            return None, (f"prompt {length} and {DECODE_STEPS} decodes need "
                          f"{pages} pages, a table has {s.pages_per_seq}")
        tables[j, :pages] = first + np.arange(pages)
        first += pages
    if first > engine.num_pages:
        return None, (f"the check's prompts need {first} pages with the "
                      f"sink, the pool has {engine.num_pages}")
    return (prompts, tables), None


def serve_logits(cell: dict, cfg, params, engine, seed: int) -> Verdict:
    """The cell's family and reference, then ``serve_check``."""
    return serve_check(family_of(cell["config_file"]),
                       reference_of(cell["config_file"]), model_of(cell),
                       cell["traffic_file"], cfg, params, engine, seed)


def serve_check(family, reference, model: dict, traffic: dict, cfg, params,
                engine, seed: int) -> Verdict:
    """For ``SEQUENCES`` seeded prompts of lengths the traffic uses: prefill
    through the engine's own path into pages, then ``DECODE_STEPS`` decode
    steps through the paged cache. The logits of the step after the prefill
    and of the step after the decodes (from the program's own
    ``paged_decode_step`` over the engine's pool, the function the decode
    program wraps: ``check_step``) must agree with the reference's full
    forward over the same tokens. With a routed reference, under the experts
    that step chose for that token (``judge``). With a segmented reference
    the steps go through the family's adapter and every stretch is held by
    itself (``serve_segments``)."""
    cut = segmented(reference)
    if cut:
        segment_adapter(family, reference)
    made, why_not = check_sequences(model, traffic, engine, seed)
    if made is None:
        return Verdict(False, [why_not])
    if cut:
        notes, ok, _ = serve_segments(family, reference, model, params,
                                      engine, *made)
    else:
        notes, ok = serve_whole(family, reference, model, cfg, params, engine,
                                *made)
    for line in notes:
        say(f"correct: {line}")
    return Verdict(bool(ok), notes)


def serve_whole(family, reference, model: dict, cfg, params, engine, prompts,
                tables):
    """The comparison of a reference that is not segmented: the two compared
    logits of each sequence against the reference's one forward."""
    import jax
    import jax.numpy as jnp

    step = check_step(family, reference, cfg, engine.serving.kernel_impl)
    n = engine.num_slots
    lengths = np.zeros(n, np.int32)
    nxt = np.zeros(n, np.int32)
    active = np.zeros(n, bool)
    seqs = []
    for j, prompt in enumerate(prompts):
        nxt[j] = engine.prefill(j, prompt, tables[j])
        lengths[j] = len(prompt)
        active[j] = True
        seqs.append(list(prompt) + [int(nxt[j])])

    def step_now():
        """The logits of the check sequences' next token and, for a routed
        reference, the experts chosen for it."""
        out, engine.paged_cache = step(
            engine.params, engine.paged_cache, jnp.asarray(nxt),
            jnp.asarray(tables), jnp.asarray(lengths))
        out = jax.device_get(out)
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
    for j, prompt in enumerate(prompts):
        length = len(prompt)
        positions = [length, length + DECODE_STEPS]
        handed = {pos: np.asarray(chose[j]) for pos, chose in zip(
            positions, (chose_prefill, chose_decode))} \
            if routed(reference) else None
        ok &= judge(reference, model, params, np.asarray(seqs[j], np.int32),
                    positions,
                    [f"prompt {length}, after prefill",
                     f"prompt {length}, after {DECODE_STEPS} decodes"],
                    [after_prefill[j], after_decode[j]], handed, notes)
    return notes, ok


def worst_rows(got, want):
    """The two numbers states and rows are held by, for rows ``[..., T, n]``:
    the root-mean-square difference of a row over the reference's root mean
    square of that row, and the largest difference of a row over the
    reference's largest entry of it, each the largest over the T positions:
    arrays ``[...]``. NaN where either side is not finite, which no limit
    admits."""
    import jax.numpy as jnp

    got = jnp.asarray(got).astype(jnp.float32)
    want = jnp.asarray(want).astype(jnp.float32)
    diff = got - want
    rms = jnp.sqrt(jnp.mean(diff * diff, -1) / jnp.mean(want * want, -1))
    big = jnp.max(jnp.abs(diff), -1) / jnp.max(jnp.abs(want), -1)
    return (np.asarray(jnp.max(rms, -1), np.float64),
            np.asarray(jnp.max(big, -1), np.float64))


def _token_rows(rows):
    """Cached rows [blocks, H, T, Dh] as one row a cache layer and token over
    all heads, [blocks, T, H * Dh]."""
    b, h, t, dh = rows.shape
    return rows.transpose(0, 2, 1, 3).reshape(b, t, h * dh)


SEGMENT_QUANTITIES = ("state_rms", "state_max", "first_row_rms",
                      "first_row_max", "row_rms", "row_max")


def segment_readings(reference, model: dict, params, states, keys, values):
    """One sequence, stretch by stretch on the served path's own entry
    states: ``states`` [n_seg + 1, T, d] at every boundary and ``keys``,
    ``values`` [cache layers, H, T, Dh] as the pool holds them, in the order
    the forward applies the blocks. For each stretch the numbers
    ``SEGMENT_TOL`` limits, ``worst_rows`` of each: the exit states; the rows
    (the larger of keys' and values') of the stretch's first block, which
    come from the entry states through one norm, one product and the
    rotation and are held far tighter for it (pages in a lower precision
    show here and nowhere else); the rows of all its blocks. Beside them the
    rows' numbers block by block (``tools/deep_drift.py`` keeps them)."""
    out, at = [], 0
    for k in range(len(reference.segments(model))):
        want, want_k, want_v = reference.segment(model, params, k, states[k])
        n = want_k.shape[0]
        state = worst_rows(states[k + 1], want)
        rows = [worst_rows(_token_rows(got[at:at + n]), _token_rows(ref))
                for got, ref in ((keys, want_k), (values, want_v))]
        # np.maximum hands a NaN on, which max() may not
        by_block = [np.maximum(rows[0][i], rows[1][i]) for i in (0, 1)]
        out.append({"stretch": k,
                    "state_rms": float(state[0]), "state_max": float(state[1]),
                    "first_row_rms": float(by_block[0][0]),
                    "first_row_max": float(by_block[1][0]),
                    "row_rms": float(np.max(by_block[0])),
                    "row_max": float(np.max(by_block[1])),
                    "row_rms_by_block": by_block[0].tolist(),
                    "row_max_by_block": by_block[1].tolist()})
        at += n
    return out


def hold_segments(reference, model: dict, params, ids, tag: str, states, keys,
                  values, notes: List[str]):
    """One sequence's stretches against ``SEGMENT_TOL``, one line each, and
    boundary 0 against the embedding rows exactly: whether all hold, and
    ``segment_readings``' rows."""
    tol = reference.SEGMENT_TOL
    states = np.asarray(states, np.float32)
    same = np.array_equal(states[0], np.asarray(
        reference.embed(model, params, ids), np.float32))
    notes.append(f"{tag}, embedding rows: "
                 f"{'equal' if same else 'NOT equal'} to the reference's")
    ok = bool(same)
    stretches = reference.segments(model)
    rows = segment_readings(reference, model, params, states, keys, values)
    for k, row in enumerate(rows):
        over = [name for name in SEGMENT_QUANTITIES
                if not row[name] <= tol[name]]
        notes.append(
            f"{tag}, stretch {k} {stretches[k]}: exit states rms diff "
            f"{row['state_rms']:.3g} of a state's rms, max diff "
            f"{row['state_max']:.3g} of its largest entry (at most "
            f"{tol['state_rms']:g}, {tol['state_max']:g}); first block's "
            f"rows {row['first_row_rms']:.3g}, {row['first_row_max']:.3g} (at "
            f"most {tol['first_row_rms']:g}, {tol['first_row_max']:g}); all "
            f"rows {row['row_rms']:.3g}, {row['row_max']:.3g} (at most "
            f"{tol['row_rms']:g}, {tol['row_max']:g})"
            + (f"; over: {', '.join(over)}" if over else ""))
        ok &= not over
    return ok, rows


def serve_segments(family, reference, model: dict, params, engine, prompts,
                   tables):
    """The comparison of a segmented reference. The check sequences are
    prefilled and then decoded ``DECODE_STEPS + 1`` times through the
    family's adapter, which runs the engine's own programs over the engine's
    own pool and hands over, from the execution that filled the pages, the
    states of each token at every boundary between stretches; the first
    step's logits are those "after prefill", the last's those after
    ``DECODE_STEPS`` decodes. Every position of a sequence then has served
    states at every boundary and served rows in every cache layer, and each
    stretch is held on the served path's own entry states
    (``hold_segments``): nothing compounds across stretches, and nothing a
    step reads goes unheld. The two logits are held against the reference's
    head of the served last state, under the tolerances of every cell.
    Beside the lines and the verdict, every stretch's numbers with all their
    digits, each row tagged with its ``sequence``."""
    n = engine.num_slots
    n_bound = len(reference.segments(model)) + 1
    lengths = np.zeros(n, np.int32)
    nxt = np.zeros(n, np.int32)
    active = np.zeros(n, bool)
    seqs, states = [], []

    def boundaries(st, shape):
        st = np.asarray(st, np.float32)
        if st.shape[:len(shape)] != shape:
            raise ManifestError(
                f"{family.__file__} handed states of shape {st.shape}, "
                f"{reference.__file__} has {n_bound - 1} stretches: wanted "
                f"{shape + ('d',)}")
        return st

    for j, prompt in enumerate(prompts):
        tok, st = family.prefill_states(engine, j, prompt, tables[j])
        nxt[j], lengths[j], active[j] = tok, len(prompt), True
        seqs.append(list(prompt) + [int(tok)])
        states.append([boundaries(st, (n_bound, len(prompt)))])
    compared = []
    for step in range(DECODE_STEPS + 1):
        logits, out, st = family.decode_states(
            engine, nxt.copy(), tables.copy(), lengths.copy(), active)
        st = boundaries(st, (n, n_bound))
        if step in (0, DECODE_STEPS):
            compared.append(np.asarray(logits, np.float32)[:SEQUENCES])
        lengths[active] += 1
        nxt[:SEQUENCES] = np.asarray(out)[:SEQUENCES]
        for j in range(SEQUENCES):
            states[j].append(st[j][:, None])
            if step < DECODE_STEPS:
                seqs[j].append(int(nxt[j]))

    notes, readings, ok = [], [], True
    for j, prompt in enumerate(prompts):
        length = len(prompt)
        served = np.concatenate(states[j], axis=1)    # [n_bound, T, d]
        keys, values = family.gather_kv(engine, tables[j], served.shape[1])
        held, rows = hold_segments(
            reference, model, params, np.asarray(seqs[j], np.int32),
            f"prompt {length}", served, keys, values, notes)
        ok &= held
        readings += [dict(row, sequence=f"prompt {length}") for row in rows]
        positions = [length, length + DECODE_STEPS]
        want = np.asarray(reference.head_logits(model, params, served[-1],
                                                positions))
        for tag, got, ref in zip(
                (f"prompt {length}, after prefill",
                 f"prompt {length}, after {DECODE_STEPS} decodes"),
                (compared[0][j], compared[1][j]), want):
            ok &= compare_logits(tag, got, ref, notes)
    return notes, ok, readings


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
