#!/usr/bin/env python3
"""How often a bf16 forward of a routed model picks other experts than its
float32 reference, and what that does to the comparison that decides
``correct``: the measurement ``reference/olmoe_ref.py``'s ``CHOICE_SLACK`` is
set from, and the proof that ``lib/correct.py``'s judging step tells an honest
bf16 path from a wrong one.

    chiprun -- python3 benchmark/tools/routing_flips.py [--seeds 6]

It measures on a TPU and refuses any other platform, as a listed cell does:
a limit is not set from a CPU's rounding. The tests build tiny models through
``init_params`` and ``stand_in`` directly.

``stand_in`` is OLMoE's equations once more (``olmoe_ref``'s docstring has
them), with the type the activations are kept in as a parameter: bf16
matmuls accumulated in float32, norms and both softmaxes in float32, the
router in float32 on the bf16 activations, a gather of each token's experts.
It returns its logits and the experts it chose. **It is a stand-in for a
served path, not the program, and no evidence about the program**: it shows
what bf16 rounding alone does to a choice of 8 in 64, which is all the
comparison has to allow for. Its ``fault`` argument builds the wrong paths the
comparison has to catch.

For each seed (weights N(0, 0.02) rounded to bf16, norm gains 1, at the
published widths and 8 layers) and each prompt length, one sequence
of ``length + DECODE_STEPS + 1`` tokens is judged at the two positions
``lib/correct.serve_logits`` compares, ``length`` and ``length +
DECODE_STEPS``, by ``lib/correct.judge`` itself: under the old rule (the
reference routes for itself) and the new (the stand-in's experts handed over
at those two positions, the context left to the reference). A seed's 8
comparisons are one run's. Two positions a sequence say little about a tail,
so beside that: over every position, the share whose set differs from the
reference's in some layer, and the differences and the slack with every
position handed over (``all_handed``: the context follows the stand-in too);
and the comparison's own situation at many positions (``one_in_32``: four
forwards a sequence, each with one position in 32 handed over and read, the
other 31 left to the reference). The table goes to the standard output and,
with every ``one_in_32`` reading, to ``chiprun_out/routing_flips.json``.
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib import correct  # noqa: E402
from benchmark.lib.device import NoChip, require_devices  # noqa: E402
from benchmark.reference import olmoe_ref as ref  # noqa: E402

OLMOE = {"vocab_size": 50304, "n_layer": 8, "n_head": 16, "d_model": 2048,
         "d_ff": 1024, "max_seq_len": 4096, "num_experts": 64, "k": 8,
         "norm_topk_prob": False, "qk_norm": True, "tie_embeddings": False,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-5}
LENGTHS = (128, 256, 384, 512)
STRIDE = 32
FAULTS = ("wrong_expert", "dropped_strongest", "dropped_expert",
          "renormalised_gates")
ONE_LAYER_FAULTS = ("wrong_expert", "dropped_strongest")


# ------------------------------------------------------------- the stand-in
def _norm(x, gain, eps, act):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(act)


def _mm(a, b, act):
    precision = "highest" if act == jnp.float32 else None
    return jnp.matmul(a.astype(act), b.astype(act), precision=precision,
                      preferred_element_type=jnp.float32).astype(act)


def _rotate(x, theta):
    t, _, dh = x.shape
    half = dh // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * (
        theta ** (-jnp.arange(half, dtype=jnp.float32) / half))[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _layer(model, x, w, act, router, fault, at):
    t, d = x.shape
    n_head, k, eps = model["n_head"], model["k"], model["rms_norm_eps"]
    dh = d // n_head
    h = _norm(x, w["ln1_scale"], eps, act)
    qkv = _mm(h, w["qkv_w"], act)
    q = _norm(qkv[:, :d], w["q_norm_scale"], eps, act).reshape(t, n_head, dh)
    kk = _norm(qkv[:, d:2 * d], w["k_norm_scale"], eps, act).reshape(
        t, n_head, dh)
    v = qkv[:, 2 * d:].reshape(t, n_head, dh)
    q, kk = _rotate(q, model["rope_theta"]), _rotate(kk, model["rope_theta"])
    scores = jnp.einsum("thd,shd->hts", q, kk,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(act)
    out = jnp.einsum("hts,shd->thd", probs, v,
                     preferred_element_type=jnp.float32).astype(act)
    x = x + _mm(out.reshape(t, d), w["attn_out_w"], act)

    h = _norm(x, w["ln2_scale"], eps, act)
    moe = w["moe"]
    r = _mm(h, moe["gate_w"], router).astype(jnp.float32)       # [T, E]
    p = jax.nn.softmax(r.astype(router), axis=-1).astype(jnp.float32)
    order = jnp.argsort(-r, axis=-1)
    chosen = order[:, :k]
    if fault == "wrong_expert":
        # where ``at`` names a rank among the others (k and up, by router
        # logit), the weakest of the k gives way to that expert
        wrong = jnp.take_along_axis(order, at[:, None], axis=1)[:, 0]
        chosen = chosen.at[:, -1].set(jnp.where(at > 0, wrong, chosen[:, -1]))
    if fault == "dropped_strongest":
        # an off-by-one in a sorted top-k: ranks 2 to k+1 where ``at`` is set
        chosen = jnp.where((at > 0)[:, None], order[:, 1:k + 1], chosen)
    gates = jnp.take_along_axis(p, chosen, axis=1)               # [T, k]
    if fault == "renormalised_gates":
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    if fault == "dropped_expert":
        gates = gates.at[:, -1].set(0.0)

    ex = moe["experts"]
    member = jnp.zeros(r.shape, jnp.float32).at[
        jnp.arange(t)[:, None], chosen].add(gates)               # [T, E]

    def one(y, e):
        gate_proj, up, down, g = e
        mid = (jax.nn.silu(_mm(h, gate_proj, act).astype(jnp.float32))
               * _mm(h, up, act).astype(jnp.float32)).astype(act)
        return y + g[:, None] * _mm(mid, down, act).astype(jnp.float32), None

    y, _ = jax.lax.scan(one, jnp.zeros((t, d), jnp.float32),
                        (ex["gate_proj_w"], ex["up_w"], ex["down_w"],
                         member.T))
    return x + y.astype(act), chosen


@functools.partial(jax.jit, static_argnums=(0, 5, 6, 7))
def _layer_at(model_items, x, blocks, layer, at, act, router, fault):
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        blocks)
    return _layer(dict(model_items), x, w, act, router, fault, at)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(model_items, params, x, act):
    x = _norm(x, params["lnf_scale"], dict(model_items)["rms_norm_eps"], act)
    return jnp.matmul(x, params["lm_head"].astype(act).T,
                      precision="highest" if act == jnp.float32 else None,
                      preferred_element_type=jnp.float32)


def stand_in(model, params, ids, act=jnp.bfloat16, router=jnp.float32,
             fault=None, fault_layer=None, at=None):
    """Logits [T, V] float32 and the experts chosen [T, n_layer, k] of one
    sequence, activations kept in ``act``, the router's product and softmax
    in ``router``. ``fault``: ``wrong_expert`` (in layer ``fault_layer``, at
    the positions ``at``, the weakest chosen expert is replaced by one drawn
    at random from the others, the draw seeded by the ids, and the
    replacement is what is computed with and reported);
    ``dropped_strongest`` (in that layer at those positions the strongest
    expert is left out and the (k+1)-th taken: ranks 2 to k+1, computed with
    and reported);
    ``dropped_expert`` (every token's weakest chosen expert is reported and
    not computed); ``renormalised_gates`` (the k gates sum to 1)."""
    items = tuple(sorted(model.items()))
    ids = jnp.asarray(ids, jnp.int32)
    rows = np.zeros(ids.shape[0], np.int32)
    if at is not None:
        rng = np.random.default_rng(int(np.asarray(ids, np.int64).sum()))
        rows[list(at)] = rng.integers(model["k"], model["num_experts"],
                                      len(at))
    x = params["wte"].astype(act)[ids]
    chosen = []
    for layer in range(model["n_layer"]):
        here = fault if fault not in ONE_LAYER_FAULTS \
            or layer == fault_layer else None
        x, c = _layer_at(items, x, params["blocks"], jnp.int32(layer), rows,
                         act, router, here)
        chosen.append(c)
    return _logits(items, params, x, act), jnp.stack(chosen, axis=1)


# ------------------------------------------------------------ the weights
def init_params(model, key, dtype=jnp.bfloat16, std=0.02):
    """``olmoe_ref``'s tree from a PRNG key: every matrix N(0, ``std``) and
    rounded to ``dtype``, every norm gain 1."""
    n, d, f = model["n_layer"], model["d_model"], model["d_ff"]
    e, v = model["num_experts"], model["vocab_size"]
    shapes = {"wte": (v, d), "lm_head": (v, d), "qkv_w": (n, d, 3 * d),
              "attn_out_w": (n, d, d), "gate_w": (n, d, e),
              "gate_proj_w": (n, e, d, f), "up_w": (n, e, d, f),
              "down_w": (n, e, f, d)}
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    w = {name: (jax.random.normal(keys[name], shape, jnp.float32)
                * std).astype(dtype) for name, shape in shapes.items()}
    ones = jnp.ones((n, d), dtype)
    return {
        "wte": w["wte"], "lm_head": w["lm_head"],
        "lnf_scale": jnp.ones((d,), dtype),
        "blocks": {
            "ln1_scale": ones, "ln2_scale": ones, "q_norm_scale": ones,
            "k_norm_scale": ones, "qkv_w": w["qkv_w"],
            "attn_out_w": w["attn_out_w"],
            "moe": {"gate_w": w["gate_w"], "experts": {
                "gate_proj_w": w["gate_proj_w"], "up_w": w["up_w"],
                "down_w": w["down_w"]}}}}


# ------------------------------------------------------------- the readings
def judged(model, params, ids, length, got, chosen):
    """``lib/correct.judge``'s two halves on one sequence at the two compared
    positions (``chosen`` None: the old rule), and the numbers it compared
    with all their digits: (rms, max, layers differing, slack) a position."""
    positions = [length, length + correct.DECODE_STEPS]
    handed = None if chosen is None else {
        p: np.asarray(chosen[p]) for p in positions}
    want, slack = correct.reference_side(ref, model, params, ids, positions,
                                         handed)
    notes = []
    ok = correct.hold(ref, positions,
                      [f"prompt {length}, position {p}" for p in positions],
                      [got[p] for p in positions], want, slack, notes)
    rms, worst = correct.logit_differences(got[positions], want)
    rows = [(float(rms[i]), float(worst[i]),
             None if slack is None else int((slack[p] > 0).sum()),
             None if slack is None else float(slack[p].max()))
            for i, p in enumerate(positions)]
    return bool(ok), notes, rows


def random_pick_slack(model, params, ids, positions, own):
    """What the slack reads where one expert of the reference's ``own``
    set [T, n_layer, k] is replaced by one picked at random among the others:
    the scale an indefensible choice reads on."""
    rng = np.random.default_rng(len(ids))
    handed = {}
    for p in positions:
        sets = own[p].copy()
        for row in sets:
            others = np.setdiff1d(np.arange(model["num_experts"]), row)
            row[rng.integers(model["k"])] = rng.choice(others)
        handed[p] = sets
    slack = np.asarray(ref.forward(model, params, ids, handed)[2])
    return slack[positions].ravel()


def one_sequence(model, params, ids, length, counts, readings):
    t = len(ids)
    positions = [length, length + correct.DECODE_STEPS]
    got, chosen = stand_in(model, params, ids)
    got, chosen = np.asarray(got), np.asarray(chosen)

    # every position, the reference routing for itself and with all handed
    x, own, _ = ref.forward(model, params, ids)
    own = np.asarray(own)
    flipped = (np.sort(chosen, -1) != np.sort(own, -1)).any(-1)  # [T, L]
    alone = np.asarray(ref.head_logits(model, params, x))
    everything = {p: chosen[p] for p in range(t)}
    x, _, slack_all = ref.forward(model, params, ids, everything)
    handed_all = np.asarray(ref.head_logits(model, params, x))
    rms_alone, max_alone = correct.logit_differences(got, alone)
    rms_all, max_all = correct.logit_differences(got, handed_all)
    any_flip = flipped.any(-1)
    counts["positions"] += t
    counts["positions_flipped"] += int(any_flip.sum())
    counts["layer_choices"] += flipped.size
    counts["layer_choices_flipped"] += int(flipped.sum())
    readings["slack_all_handed"].extend(np.asarray(slack_all).max(-1).tolist())
    for name, rms, worst in (("alone", rms_alone, max_alone),
                             ("all_handed", rms_all, max_all)):
        readings[f"rms_{name}_no_flip"].extend(rms[~any_flip].tolist())
        readings[f"rms_{name}_flipped"].extend(rms[any_flip].tolist())
        readings[f"max_{name}"].extend(worst.tolist())
    counts["over_tolerance_alone"] += int(((rms_alone > correct.LOGIT_RMS_TOL) | (
        max_alone > correct.LOGIT_MAX_TOL)).sum())
    counts["over_tolerance_all_handed"] += int(((
        rms_all > correct.LOGIT_RMS_TOL) | (
        max_all > correct.LOGIT_MAX_TOL)).sum())
    readings["random_pick_slack"].extend(
        random_pick_slack(model, params, ids, positions, own).tolist())
    for offset in range(0, STRIDE, STRIDE // 4):
        some = list(range(offset, t, STRIDE))
        x, _, slack = ref.forward(model, params, ids,
                                  {p: chosen[p] for p in some})
        rms, worst = correct.logit_differences(
            got[some], ref.head_logits(model, params, x, some))
        slack = np.asarray(slack)[some]
        readings["one_in_32"].extend(
            [p, float(rms[i]), float(worst[i]), int((slack[i] > 0).sum()),
             float(slack[i].max())] for i, p in enumerate(some))

    # the two compared positions, as lib/correct.judge sees them
    verdicts = {
        "honest_old_rule": judged(model, params, ids, length, got, None),
        "honest": judged(model, params, ids, length, got, chosen)}
    verdicts["bf16_router"] = judged(model, params, ids, length, *map(
        np.asarray, stand_in(model, params, ids, router=jnp.bfloat16)))
    for fault in FAULTS:
        verdicts[fault] = judged(model, params, ids, length, *map(
            np.asarray, stand_in(model, params, ids, fault=fault,
                                 fault_layer=model["n_layer"] // 2,
                                 at=positions)))
    return verdicts


def spread_of(values):
    v = np.asarray(values, np.float64)
    if not v.size:
        return None
    return {"n": int(v.size), "min": float(v.min()),
            "median": float(np.median(v)), "p99": float(np.quantile(v, 0.99)),
            "p999": float(np.quantile(v, 0.999)), "max": float(v.max())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=2700000001)
    args = ap.parse_args(argv)

    try:
        device = require_devices(1, rehearsal=False)[0]
    except NoChip as e:
        print(f"routing_flips: {e}", file=sys.stderr)
        return 2
    model = OLMOE
    print(f"[flips] {device.platform} {device.device_kind}; "
          f"{json.dumps(model)}", flush=True)

    make = jax.jit(lambda key: init_params(model, key))
    counts, readings = collections.Counter(), collections.defaultdict(list)
    runs = {}
    router_spread = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = jax.block_until_ready(make(jax.random.PRNGKey(seed)))
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0xF11B5]))
        for length in LENGTHS:
            ids = rng.integers(0, model["vocab_size"],
                               size=length + correct.DECODE_STEPS + 1,
                               dtype=np.int32)
            verdicts = one_sequence(model, params, ids, length, counts,
                                     readings)
            for name, (ok, notes, rows) in verdicts.items():
                run = runs.setdefault(name, {}).setdefault(
                    seed, {"ok": True, "rows": []})
                run["ok"] &= ok
                run["rows"].extend(rows)
                for note in notes:
                    print(f"[flips] seed {seed} {name}: {note}", flush=True)
        # layer 0's router on the normalised embeddings of the last sequence
        h = _norm(params["wte"][ids], params["lnf_scale"], 1e-5, jnp.float32)
        router_spread.append(float(jnp.std(
            h @ params["blocks"]["moe"]["gate_w"][0].astype(jnp.float32))))
        del params

    table = {
        "device": f"{device.platform} {device.device_kind}",
        "model": model, "seeds": args.seeds, "prompt_lengths": list(LENGTHS),
        "router_logit_std": float(np.mean(router_spread)),
        "positions": counts["positions"],
        "positions_flipped_pct": 100.0 * counts["positions_flipped"]
        / counts["positions"],
        "layer_choices_flipped_pct": 100.0 * counts["layer_choices_flipped"]
        / counts["layer_choices"],
        "over_tolerance_alone_pct": 100.0 * counts["over_tolerance_alone"]
        / counts["positions"],
        "over_tolerance_all_handed_pct": 100.0
        * counts["over_tolerance_all_handed"] / counts["positions"],
    }
    sparse = np.asarray(readings.pop("one_in_32"))
    for name, part in (("", sparse), ("_from_64", sparse[sparse[:, 0] >= 64])):
        table[f"one_in_32{name}"] = {
            "rms": spread_of(part[:, 1]), "max": spread_of(part[:, 2]),
            "over_logit_tolerance": int(((part[:, 1] > correct.LOGIT_RMS_TOL)
                                         | (part[:, 2] > correct.LOGIT_MAX_TOL)
                                         ).sum()),
            "over_0.8_of_logit_tolerance": int(
                ((part[:, 1] > 0.8 * correct.LOGIT_RMS_TOL)
                 | (part[:, 2] > 0.8 * correct.LOGIT_MAX_TOL)).sum()),
            "differing_in_some_layer_pct": 100.0 * float(
                (part[:, 3] > 0).mean()),
            "slack": spread_of(part[:, 4])}
    for k, v in readings.items():
        table[k] = spread_of(v)
    table["random_pick_under_choice_slack_pct"] = 100.0 * float(np.mean(
        np.asarray(readings["random_pick_slack"]) <= ref.CHOICE_SLACK))
    table["judged"] = {}
    for name, by_seed in runs.items():
        rows = [r for run in by_seed.values() for r in run["rows"]]
        slacks = [r[3] for r in rows if r[3] is not None]
        table["judged"][name] = {
            "runs_correct": sum(run["ok"] for run in by_seed.values()),
            "runs": len(by_seed), "comparisons": len(rows),
            "rms": spread_of([r[0] for r in rows]),
            "max": spread_of([r[1] for r in rows]),
            "over_logit_tolerance": sum(
                r[0] > correct.LOGIT_RMS_TOL or r[1] > correct.LOGIT_MAX_TOL
                for r in rows),
            "layers_differing": spread_of(
                [r[2] for r in rows if r[2] is not None]),
            "slack": spread_of(slacks),
            "over_choice_slack": sum(s > ref.CHOICE_SLACK for s in slacks)}
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "routing_flips.json"), "w") as f:
        json.dump(dict(table, one_in_32_rows=sparse.tolist()), f)
    for key, value in table.items():
        if key != "judged":
            print(f"[flips] {key}: {json.dumps(value)}")
    for name, row in table["judged"].items():
        print(f"[flips] judged {name}: {json.dumps(row)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
