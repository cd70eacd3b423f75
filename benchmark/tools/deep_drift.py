#!/usr/bin/env python3
"""How far an honest bf16 path of a deep or looped model ends from its float32
reference, and what the comparison that decides ``correct`` can still tell
apart there: the measurement ``reference/ouro_ref.py``'s ``SEGMENT_TOL`` is set
from, and the proof that ``lib/correct.py``'s segmented rule passes an honest
bf16 path of Ouro's shape and fails nine wrong ones.

    chiprun -- python3 benchmark/tools/deep_drift.py [--seeds 6] [--table-seeds 2]

It measures on a TPU and refuses any other platform, as a listed cell does: a
limit is not set from a CPU's rounding. The tests build tiny models through
``init_params`` and ``Engine`` directly.

**The stand-in is not the program, and no evidence about the program.** It is
``ouro_ref``'s equations once more (its docstring has them) as a served path
would run them: activations kept in ``act`` (bf16: matmuls accumulated in
float32, norms and the softmax in float32), a paged pool ``{"k", "v"}``
``[cache layers, H, pages, page size, Dh]`` in the served type, a prefill
program over one padded prompt and a decode program over the slot array, each
of which returns, beside its tokens, the residual stream at every boundary
between ``ouro_ref.segments``: the states are an output of the execution that
filled the pages. ``Engine`` holds the weights and the pool as a
``ServingEngine`` does for ``lib/correct.py``, and this module is its family:
``prefill_states``, ``decode_states`` and ``gather_kv`` are the segmented
adapter (``benchmark/README.md``, the ``family`` row). It shows what bf16
rounding alone does over 48 x 4 blocks, which is all the comparison has to
allow for. ``Engine(fault=...)`` plants one of ``FAULTS``; every fault is a
run-time switch of the same two programs, so the honest path and the wrong
ones are one compilation.

What it prints, and writes with every reading to
``chiprun_out/deep_drift.json``:

(i) the old rule, by ``lib/correct.judge`` itself, on the stand-in's logits
    at the two compared positions against the reference's whole forward, for
    the shapes of ``TABLE``: unshared stacks of 24, 48 and 96 blocks, and
    looped ones up to 48 x 4 (192 unshared blocks of this width are 19.7 GB
    and fit no chip; ``ouro_ref`` covers the sandwich norms only, so there is
    no pre-norm row);
(ii) the segmented rule on the honest stand-in at 48 x 4: every stretch's
    four numbers over seeds x prompts of ``LENGTHS`` + 9 tokens through
    ``lib/correct.serve_segments``, their largest, and that over
    ``SEGMENT_TOL``;
(iii) one row a planted fault: in how many seeds the segmented rule failed
    it, and which stretch and which quantity (state, row or logits) said so.
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import math
import os
import re
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib import correct  # noqa: E402
from benchmark.lib.device import NoChip, require_devices  # noqa: E402
from benchmark.reference import ouro_ref as ref  # noqa: E402

OURO = {"vocab_size": 49152, "n_layer": 48, "n_head": 16, "d_model": 2048,
        "d_ff": 5632, "max_seq_len": 65536, "total_ut_steps": 4,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-6, "sandwich_norm": True,
        "loop_norm": True, "tie_embeddings": False,
        "early_exit_threshold": 1.0}
LENGTHS = (64, 128, 256, 512)
PAGE_SIZE = 64
# (n_layer, total_ut_steps) of table (i); the last is Ouro's
TABLE = ((24, 1), (48, 1), (96, 1), (12, 4), (24, 4), (48, 2), (48, 4))
FAULTS = (
    "int8_pages",              # keys and values rounded to 8 bits in the pool
    "int8_block_outputs",      # every block's output rounded to 8 bits
    "decode_rotary_late",      # decoded tokens rotated as one position on
    "last_loop_mask_short",    # the last loop's mask leaves out the token itself
    "prompt_skips_last_loop",  # prefill runs one loop fewer, decode all
    "no_loop_norm",            # no closing norm between loops
    "one_post_norm_missing",   # one layer adds its attention output unnormed
    "prompt_rows_a_loop_late",  # prefill writes the last loop's cache layers
                                # with the rows of the loop before
    "decode_row_late",         # a decoded row is written one place late
)


# ------------------------------------------------------------- the stand-in
def _norm(x, gain, eps, act):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(act)


def _mm(a, b, act):
    precision = "highest" if act == jnp.float32 else None
    return jnp.matmul(a.astype(act), b.astype(act), precision=precision,
                      preferred_element_type=jnp.float32).astype(act)


def _einsum(spec, a, b, act):
    precision = "highest" if act == jnp.float32 else None
    return jnp.einsum(spec, a, b, precision=precision,
                      preferred_element_type=jnp.float32)


def _rotate(x, positions, theta):
    """x [N, H, Dh] rotated over all of Dh, rotate-half, row n at
    ``positions[n]``."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * (
        theta ** (-jnp.arange(half, dtype=jnp.float32) / half))[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _int8(x):
    """Rounded to 8 bits: symmetric, one scale a row of the last axis."""
    y = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(y), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (jnp.round(y / scale) * scale).astype(x.dtype)


def _is(fault, name):
    """``fault`` is 0 for the honest path, else 1 + its place in FAULTS."""
    return fault == 1 + FAULTS.index(name)


def _block(model, x, w, positions, attend, act, fault, layer):
    """One block on the rows ``x`` [N, d] (a prompt's tokens, or the slots'
    new tokens); ``attend(q, k, v)`` gives the attention output [N, H, Dh]
    and whatever it carries (the pool, or the rows)."""
    n, d = x.shape
    n_head, eps = model["n_head"], model["rms_norm_eps"]
    dh = d // n_head
    qkv = _mm(_norm(x, w["ln1_scale"], eps, act), w["qkv_w"], act)
    q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(n, n_head, dh)
               for i in range(3))
    q = _rotate(q, positions, model["rope_theta"])
    k = _rotate(k, positions, model["rope_theta"])
    out, carried = attend(q, k, v)
    a = _mm(out.reshape(n, d), w["attn_out_w"], act)
    unnormed = _is(fault, "one_post_norm_missing") \
        & (layer == model["n_layer"] // 2)
    x = x + jnp.where(unnormed, a, _norm(a, w["post_attn_scale"], eps, act))
    h = _norm(x, w["ln2_scale"], eps, act)
    mid = (jax.nn.silu(_mm(h, w["mlp_gate_w"], act).astype(jnp.float32))
           * _mm(h, w["mlp_up_w"], act).astype(jnp.float32)).astype(act)
    x = x + _norm(_mm(mid, w["mlp_down_w"], act), w["post_mlp_scale"], eps,
                  act)
    x = jnp.where(_is(fault, "int8_block_outputs"), _int8(x), x)
    return x, carried


def _softmax_v(q, k, v, mask, act):
    """q [N, H, Dh] under ``mask`` [N, S] over keys and values that are each
    row's own, [N, H, S, Dh], or one sequence's, [H, S, Dh]."""
    kv = "nhsd" if k.ndim == 4 else "hsd"
    scores = _einsum(f"nhd,{kv}->nhs", q, k, act) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(mask[:, None, :], scores, -jnp.inf),
                           axis=-1).astype(act)
    return _einsum(f"nhs,{kv}->nhd", probs, v, act).astype(act)


def _layer_weights(blocks, layer):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        blocks)


def _logits(params, x, act):
    return jnp.matmul(x, params["lm_head"].astype(act).T,
                      precision="highest" if act == jnp.float32 else None,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
def _prefill(model_items, act, params, pool, ids, table, length, fault):
    """One prompt ``ids`` [pad] of ``length`` real tokens: its keys and
    values into the pages ``table`` names (padding goes past ``length`` in
    its last page, and to the sink, page 0), the greedy next token, and the
    residual stream of every row at every boundary [n_seg + 1, pad, d]."""
    model = dict(model_items)
    pad = ids.shape[0]
    n_layer, loops = model["n_layer"], model["total_ut_steps"]
    n_head = model["n_head"]
    dh = model["d_model"] // n_head
    ps = pool["k"].shape[3]
    positions = jnp.arange(pad)
    t, s = positions[:, None], positions[None, :]
    x = params["wte"].astype(act)[ids]
    bounds = [x]
    before = None          # the loop before's rows [n_layer, pad, H, Dh] x 2

    def pages_of(rows):    # [pad, H, Dh] -> [pages, 1, H, 1, ps, Dh]
        return rows.reshape(pad // ps, ps, n_head, dh).transpose(
            0, 2, 1, 3)[:, None, :, None]

    def write(pool_side, layer, rows):
        blocks = pages_of(rows)
        return jax.lax.fori_loop(
            0, pad // ps, lambda p, side: jax.lax.dynamic_update_slice(
                side, blocks[p], (layer, 0, table[p], 0, 0)), pool_side)

    for u in range(loops):
        last = u == loops - 1
        short = _is(fault, "last_loop_mask_short") & last
        mask = s <= jnp.where(short, jnp.maximum(t - 1, 0), t)
        late = _is(fault, "prompt_rows_a_loop_late") & last & (u > 0)
        skipped = _is(fault, "prompt_skips_last_loop") & last
        entry = x
        rows = []
        for _, first, stop in [g for g in ref.segments(model) if g[0] == u]:
            old = before if before is not None else (
                jnp.zeros((n_layer, pad, n_head, dh), act),) * 2

            def body(carry, xs):
                x, pk, pv = carry
                layer, old_k, old_v = xs

                def attend(q, k, v):
                    out = _softmax_v(q, k.transpose(1, 0, 2),
                                     v.transpose(1, 0, 2), mask, act)
                    return out, (k, v)
                x, (k, v) = _block(model, x,
                                   _layer_weights(params["blocks"], layer),
                                   positions, attend, act, fault, layer)
                rounded = _is(fault, "int8_pages")
                wk = jnp.where(late, old_k, jnp.where(rounded, _int8(k), k))
                wv = jnp.where(late, old_v, jnp.where(rounded, _int8(v), v))
                # a loop that is skipped writes no rows
                wk, wv = (jnp.where(skipped, 0, r).astype(act)
                          for r in (wk, wv))
                pk = write(pk, u * n_layer + layer, wk)
                pv = write(pv, u * n_layer + layer, wv)
                return (x, pk, pv), (k, v)

            (x, pk, pv), kv = jax.lax.scan(
                body, (x, pool["k"], pool["v"]),
                (jnp.arange(first, stop), old[0][first:stop],
                 old[1][first:stop]))
            pool = {"k": pk, "v": pv}
            rows.append(kv)
            if stop == n_layer:
                normed = _norm(x, params["lnf_scale"], model["rms_norm_eps"],
                               act)
                unclosed = _is(fault, "no_loop_norm") & (not last)
                x = jnp.where(unclosed, x, normed)
            # a prompt that skips the last loop hands its entry on unchanged
            x = jnp.where(skipped, entry, x)
            bounds.append(x)
        before = tuple(jnp.concatenate([r[i] for r in rows]) for i in (0, 1))
    logits = _logits(params, x[length - 1], act)
    return jnp.argmax(logits).astype(jnp.int32), jnp.stack(bounds), pool


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
def _decode(model_items, act, params, pool, tokens, tables, lengths, fault):
    """One step over the slot array: each slot's token at position
    ``lengths[slot]``, its rows appended to the pool, its attention over the
    pages its table names. Logits [slots, V], next tokens, the residual
    stream at every boundary [slots, n_seg + 1, d], the pool."""
    model = dict(model_items)
    n_layer, loops = model["n_layer"], model["total_ut_steps"]
    n = tokens.shape[0]
    ps = pool["k"].shape[3]
    reach = tables.shape[1] * ps
    rotated_at = lengths + _is(fault, "decode_rotary_late")
    written_at = lengths + _is(fault, "decode_row_late")
    x = params["wte"].astype(act)[tokens]
    bounds = [x]

    def append(side, layer, rows):      # rows [slots, H, Dh]
        for slot in range(n):
            at = written_at[slot]
            side = jax.lax.dynamic_update_slice(
                side, rows[slot][None, :, None, None, :],
                (layer, 0, tables[slot, at // ps], at % ps, 0))
        return side

    def gathered(side, layer):          # -> [slots, H, reach, Dh]
        own = jax.lax.dynamic_index_in_dim(side, layer, 0, keepdims=False)
        own = own[:, tables]            # [H, slots, pages, ps, Dh]
        return own.transpose(1, 0, 2, 3, 4).reshape(
            n, own.shape[0], reach, own.shape[-1])

    for u in range(loops):
        last = u == loops - 1
        short = _is(fault, "last_loop_mask_short") & last
        mask = jnp.arange(reach)[None, :] <= (lengths - short)[:, None]
        for _, first, stop in [g for g in ref.segments(model) if g[0] == u]:
            def body(carry, layer):
                x, pk, pv = carry
                at = u * n_layer + layer

                def attend(q, k, v):
                    rounded = _is(fault, "int8_pages")
                    pk2 = append(pk, at, jnp.where(rounded, _int8(k), k))
                    pv2 = append(pv, at, jnp.where(rounded, _int8(v), v))
                    out = _softmax_v(q, gathered(pk2, at), gathered(pv2, at),
                                     mask, act)
                    return out, (pk2, pv2)
                x, (pk, pv) = _block(model, x,
                                     _layer_weights(params["blocks"], layer),
                                     rotated_at, attend, act, fault, layer)
                return (x, pk, pv), None

            (x, pk, pv), _ = jax.lax.scan(
                body, (x, pool["k"], pool["v"]), jnp.arange(first, stop))
            pool = {"k": pk, "v": pv}
            if stop == n_layer:
                normed = _norm(x, params["lnf_scale"], model["rms_norm_eps"],
                               act)
                unclosed = _is(fault, "no_loop_norm") & (not last)
                x = jnp.where(unclosed, x, normed)
            bounds.append(x)
    logits = _logits(params, x, act)
    return (logits, jnp.argmax(logits, -1).astype(jnp.int32),
            jnp.stack(bounds, axis=1), pool)


class Engine:
    """What ``lib/correct.py`` asks of a serving engine, around the stand-in:
    the served weights, one pool, the geometry of its tables."""

    def __init__(self, model: dict, params, pages: int, page_size: int,
                 pad: int, act=jnp.bfloat16, fault: str = None,
                 slots: int = correct.SEQUENCES):
        self.model, self.params, self.act = model, params, act
        self.items = tuple(sorted(model.items()))
        self.fault = jnp.int32(0 if fault is None else 1 + FAULTS.index(fault))
        self.num_slots, self.num_pages, self.pad = slots, pages, pad
        self.serving = types.SimpleNamespace(
            page_size=page_size, pages_per_seq=pad // page_size,
            kernel_impl=None)
        shape = (ref.cache_layers(model), model["n_head"], pages, page_size,
                 model["d_model"] // model["n_head"])
        self.paged_cache = {"k": jnp.zeros(shape, act),
                            "v": jnp.zeros(shape, act)}

    def named(self, tables) -> None:
        """A page a table names and the pool lacks is read clamped and
        written nowhere by XLA, in silence: said here."""
        if np.asarray(tables).max() >= self.num_pages:
            raise ValueError(f"a table names page {np.asarray(tables).max()}, "
                             f"the pool has {self.num_pages}")


# ---- the segmented adapter: this module is the stand-in's family
def prefill_states(engine: Engine, slot: int, prompt, table):
    """The greedy next token and the states [n_seg + 1, T, d] of the prompt's
    tokens, from the program that wrote their rows into ``table``'s pages."""
    del slot      # a slot is a row of the tables, and the stand-in keeps none
    engine.named(table)
    length = len(prompt)
    ids = np.zeros(engine.pad, np.int32)
    ids[:length] = prompt
    tok, bounds, engine.paged_cache = _prefill(
        engine.items, engine.act, engine.params, engine.paged_cache,
        jnp.asarray(ids), jnp.asarray(table, jnp.int32), jnp.int32(length),
        engine.fault)
    return int(tok), bounds[:, :length]


def decode_states(engine: Engine, tokens, tables, lengths, active):
    """Logits [slots, V], next tokens [slots] and the states [slots, n_seg +
    1, d] of each slot's token, from the step that appended its rows."""
    del active    # every slot runs; the check's four are all of them
    engine.named(tables)
    logits, nxt, bounds, engine.paged_cache = _decode(
        engine.items, engine.act, engine.params, engine.paged_cache,
        jnp.asarray(tokens, jnp.int32), jnp.asarray(tables, jnp.int32),
        jnp.asarray(lengths, jnp.int32), engine.fault)
    return logits, np.asarray(nxt), bounds


def gather_kv(engine: Engine, table, length: int):
    """The first ``length`` rows of the pages ``table`` names, keys and
    values [cache layers, H, length, Dh] in ``ouro_ref``'s order (the pool's
    own: cache layer ``n_layer * loop + layer``)."""
    ps = engine.serving.page_size
    pages = jnp.asarray(table[:-(-length // ps)], jnp.int32)

    def rows(side):
        got = side[:, :, pages]          # [layers, H, pages, ps, Dh]
        return got.reshape(got.shape[0], got.shape[1], -1,
                           got.shape[-1])[:, :, :length]
    return rows(engine.paged_cache["k"]), rows(engine.paged_cache["v"])


# ------------------------------------------------------------ the weights
def init_params(model: dict, key, dtype=jnp.bfloat16, std=0.02):
    """``ouro_ref``'s tree from a PRNG key: every matrix N(0, ``std``) and
    rounded to ``dtype``, every norm gain 1; a leaf a dispatch, so that the
    float32 draws of a 10 GB stack are never live together."""
    n, d, f, v = (model["n_layer"], model["d_model"], model["d_ff"],
                  model["vocab_size"])
    shapes = {"wte": (v, d), "lm_head": (v, d), "exit_gate_w": (d, 1),
              "qkv_w": (n, d, 3 * d), "attn_out_w": (n, d, d),
              "mlp_gate_w": (n, d, f), "mlp_up_w": (n, d, f),
              "mlp_down_w": (n, f, d)}
    draw = jax.jit(lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                                     * std).astype(dtype), static_argnums=1)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    w = {name: draw(keys[name], shape) for name, shape in shapes.items()}
    ones = jnp.ones((n, d), dtype)
    blocks = {name: w[name] for name in shapes if len(shapes[name]) == 3}
    blocks.update(ln1_scale=ones, post_attn_scale=ones, ln2_scale=ones,
                  post_mlp_scale=ones)
    return {"wte": w["wte"], "lm_head": w["lm_head"],
            "lnf_scale": jnp.ones((d,), dtype),
            "exit_gate_w": w["exit_gate_w"],
            "exit_gate_b": jnp.zeros((1,), dtype), "blocks": blocks}


# ------------------------------------------------------------- the readings
def pages_for(lengths, page_size: int) -> int:
    """The sink and what ``lib/correct.check_sequences`` gives prompts of
    ``lengths``."""
    return 1 + sum(-(-(n + correct.DECODE_STEPS + 2) // page_size)
                   for n in lengths)


def pad_for(lengths, page_size: int) -> int:
    longest = max(lengths) + correct.DECODE_STEPS + 2
    return -(-longest // page_size) * page_size


def checked(model: dict, params, seed: int, fault=None, lengths=LENGTHS,
            page_size: int = PAGE_SIZE, act=jnp.bfloat16):
    """One run's check of the stand-in by ``lib/correct.py``'s segmented
    rule, in a pool of exactly its prompts' pages and the sink: the verdict,
    its lines, and every stretch's numbers with all their digits."""
    engine = Engine(model, params, pages_for(lengths, page_size), page_size,
                    pad_for(lengths, page_size), act=act, fault=fault)
    made, why_not = correct.check_sequences(
        model, {"prompt_lens": list(lengths)}, engine, seed)
    if made is None:
        raise ValueError(why_not)
    notes, ok, readings = correct.serve_segments(
        sys.modules[__name__], ref, model, params, engine, *made)
    return bool(ok), notes, readings


def whole_forward(model: dict, params, ids, page_size: int = PAGE_SIZE,
                  act=jnp.bfloat16, pad: int = None):
    """The stand-in's logits [T, V] of one sequence from one prefill, padded
    to ``pad`` tokens (one program for every length): what the old rule
    compares."""
    pad = pad or -(-len(ids) // page_size) * page_size
    engine = Engine(model, params, 1 + pad // page_size, page_size, pad,
                    act=act)
    table = 1 + np.arange(pad // page_size, dtype=np.int32)
    _, bounds = prefill_states(engine, 0, ids, table)
    return np.asarray(_logits(params, bounds[-1], act))


def old_rule(model: dict, params, ids, length: int, got):
    """``lib/correct.judge`` as every unsegmented cell has it: the two
    compared logits against the reference's whole forward."""
    positions = [length, length + correct.DECODE_STEPS]
    want = np.asarray(ref.logits(model, params, ids, positions))
    notes = []
    ok = correct.hold(ref, positions,
                      [f"prompt {length}, position {p}" for p in positions],
                      [got[p] for p in positions], want, None, notes)
    rms, worst = correct.logit_differences(got[positions], want)
    return bool(ok), notes, [(float(a), float(b)) for a, b in zip(rms, worst)]


OVER_LINE = re.compile(r", (stretch \d+) .*; over: (.*)$")


def caught_by(notes) -> list:
    """Which comparisons of a run said no, from its lines, and in how many
    of its sequences: ``stretch k: <quantity>``, ``embedding rows`` and
    ``logits``."""
    said = collections.Counter()
    for note in notes:
        m = OVER_LINE.search(note)
        if m:
            said.update(f"{m.group(1)}: {q}" for q in m.group(2).split(", "))
        if "NOT equal" in note:
            said["embedding rows"] += 1
        m = correct.LOGIT_LINE.search(note)
        if m and not (float(m.group(1)) <= correct.LOGIT_RMS_TOL
                      and float(m.group(2)) <= correct.LOGIT_MAX_TOL):
            said["logits"] += 1
    return sorted(said.items())


def spread_of(values):
    v = np.asarray(list(values), np.float64)
    return {"n": int(v.size), "min": float(np.nanmin(v)),
            "median": float(np.nanmedian(v)), "max": float(np.nanmax(v)),
            "not_finite": int((~np.isfinite(v)).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--table-seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=3100000001)
    ap.add_argument("--faults", default=",".join(FAULTS))
    args = ap.parse_args(argv)
    try:
        device = require_devices(1, rehearsal=False)[0]
    except NoChip as e:
        print(f"deep_drift: {e}", file=sys.stderr)
        return 2
    say = functools.partial(print, "[drift]", flush=True)
    say(f"{device.platform} {device.device_kind}; {json.dumps(OURO)}")
    faults = [f for f in args.faults.split(",") if f]
    out = {"device": f"{device.platform} {device.device_kind}", "model": OURO,
           "lengths": list(LENGTHS), "segment_tol": ref.SEGMENT_TOL,
           "table": [], "honest": [], "faults": {}}
    os.makedirs("chiprun_out", exist_ok=True)

    def keep():
        with open(os.path.join("chiprun_out", "deep_drift.json"), "w") as f:
            json.dump(out, f)

    # (ii) and (iii): Ouro's shape, the segmented rule
    seeds = range(args.first_seed, args.first_seed + args.seeds)
    for seed in seeds:
        params = init_params(OURO, jax.random.PRNGKey(seed))
        for fault in [None] + faults:
            ok, notes, readings = checked(OURO, params, seed, fault)
            said = caught_by(notes)
            row = {"seed": seed, "ok": ok, "said": said,
                   "readings": readings,
                   "logits": [list(map(float, m.groups())) for m in map(
                       correct.LOGIT_LINE.search, notes) if m]}
            if fault is None:
                out["honest"].append(row)
                for note in notes:
                    say(f"seed {seed} honest: {note}")
            else:
                out["faults"].setdefault(fault, []).append(row)
            say(f"seed {seed} {fault or 'honest'}: "
                f"{'correct' if ok else 'NOT correct'}; said no: {said}")
            keep()
        del params

    # (i): the old rule over depth and loops
    rng_of = lambda seed: np.random.default_rng(  # noqa: E731
        np.random.SeedSequence([seed, 0xDEE9]))
    pad = pad_for(LENGTHS, PAGE_SIZE)
    for n_layer, loops in TABLE if args.table_seeds else ():
        model = dict(OURO, n_layer=n_layer, total_ut_steps=loops)
        rows, runs_ok = [], 0
        for seed in range(args.first_seed,
                          args.first_seed + args.table_seeds):
            params = init_params(model, jax.random.PRNGKey(seed))
            rng, run_ok = rng_of(seed), True
            for length in LENGTHS:
                ids = rng.integers(0, model["vocab_size"],
                                   size=length + correct.DECODE_STEPS + 1,
                                   dtype=np.int32)
                ok, _, pairs = old_rule(
                    model, params, ids, length,
                    whole_forward(model, params, ids, pad=pad))
                run_ok &= ok
                rows += pairs
            runs_ok += run_ok
            del params
        out["table"].append({
            "blocks": n_layer, "loops": loops, "runs": args.table_seeds,
            "runs_correct_old_rule": runs_ok,
            "rms": spread_of(r[0] for r in rows),
            "max": spread_of(r[1] for r in rows)})
        say(f"table (i) {n_layer} x {loops}: {json.dumps(out['table'][-1])}")
        keep()

    # the summary
    names = list(ref.SEGMENT_TOL)
    honest = [r for run in out["honest"] for r in run["readings"]]
    out["honest_largest"] = {n: spread_of(r[n] for r in honest)
                             for n in names} if honest else {}
    out["honest_by_stretch"] = {
        k: {n: max(r[n] for r in honest if r["stretch"] == k) for n in names}
        for k in sorted({r["stretch"] for r in honest})}
    out["honest_logits"] = {
        "rms": spread_of(p[0] for run in out["honest"] for p in run["logits"]),
        "max": spread_of(p[1] for run in out["honest"] for p in run["logits"])
    } if honest else {}
    say(f"(ii) honest, {len(out['honest'])} seeds: runs correct "
        f"{sum(r['ok'] for r in out['honest'])}; largest "
        f"{json.dumps(out['honest_largest'])}; limits "
        f"{json.dumps(ref.SEGMENT_TOL)}; logits "
        f"{json.dumps(out['honest_logits'])}")
    for k, row in out["honest_by_stretch"].items():
        say(f"(ii) stretch {k}: {json.dumps(row)}")
    for fault, runs in out["faults"].items():
        faulty = [r for run in runs for r in run["readings"]]
        # what said no in every seed: "stretch k: quantity", or "logits"
        every = set.intersection(*({n for n, _ in run["said"]}
                                   for run in runs))
        by = collections.defaultdict(list)
        for name in sorted(every):
            where, _, quantity = name.partition(": ")
            by[quantity or where].append(where.replace("stretch ", ""))
        say(f"(iii) {fault}: failed in {sum(not r['ok'] for r in runs)} of "
            f"{len(runs)} seeds; in every seed said no, quantity and "
            f"stretches: {dict(by)}; largest "
            + json.dumps({n: max(r[n] for r in faulty) for n in names}))
    keep()
    return 0


if __name__ == "__main__":
    sys.exit(main())
