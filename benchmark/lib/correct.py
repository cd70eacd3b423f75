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
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

from .device import say
from .manifest import family_of, reference_of

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


def compare_logits(tag: str, got, want, notes: List[str]) -> bool:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        notes.append(f"{tag}: non-finite logits")
        return False
    spread = float(np.sqrt(np.mean((want - want.mean()) ** 2)))
    rms = float(np.sqrt(np.mean((got - want) ** 2))) / spread
    worst = float(np.max(np.abs(got - want))) / float(np.max(np.abs(want)))
    notes.append(f"{tag}: rms diff {rms:.3g} of the logits' spread, "
                 f"max diff {worst:.3g} of the largest logit")
    return rms <= LOGIT_RMS_TOL and worst <= LOGIT_MAX_TOL


def serve_logits(cell: dict, cfg, params, engine, seed: int) -> Verdict:
    """For ``SEQUENCES`` seeded prompts of lengths the traffic uses: prefill
    through the engine's own path into pages, then ``DECODE_STEPS`` decode
    steps through the paged cache. The logits of the step after the prefill
    and of the step after the decodes (from the program's own
    ``paged_decode_step`` over the engine's pools, the function the decode
    program wraps) must agree with the reference's full forward over the same
    tokens."""
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
    step_logits = jax.jit(lambda p, c, t, tb, ln: family.paged_decode_step(
        cfg, p, t, c, tb, ln, impl=impl)[0])

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

    def logits_now():
        return np.asarray(jax.device_get(step_logits(
            engine.params, engine.paged_cache, jnp.asarray(nxt),
            jnp.asarray(tables), jnp.asarray(lengths))))[:SEQUENCES]

    after_prefill = logits_now()
    for _ in range(DECODE_STEPS):
        out = engine.decode(nxt.copy(), tables.copy(), lengths.copy(), active,
                            steps=1)
        lengths[active] += 1
        nxt[:SEQUENCES] = out[0, :SEQUENCES]
        for j in range(SEQUENCES):
            seqs[j].append(int(out[0, j]))
    after_decode = logits_now()

    notes, ok = [], True
    for j, length in enumerate(picks):
        ref = np.asarray(reference.logits(
            model, params, np.asarray(seqs[j], np.int32),
            positions=[length, length + DECODE_STEPS]))
        ok &= compare_logits(f"prompt {length}, after prefill",
                             after_prefill[j], ref[0], notes)
        ok &= compare_logits(f"prompt {length}, after {DECODE_STEPS} decodes",
                             after_decode[j], ref[1], notes)
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
