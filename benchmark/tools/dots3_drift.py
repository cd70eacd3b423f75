#!/usr/bin/env python3
"""On the chip, outside any cell: how far the served ``dots3-note-serve`` path
lies from ``reference/dots3_note_ref.py``, by the two numbers ``lib/correct.py``
compares, by the slack of the experts the served step chose and by the slack
of the positions its full layers selected (``SELECT_SLACK``), with the
program's own programs (``ServingEngine``: chunks straight to pages, index-key
pages and rings, then teacher-forced decode steps through them) over prompts
of the cell's lengths; and what a decode step and a prompt's chunks cost.

    chiprun -- python3 benchmark/tools/dots3_drift.py '{"seeds": [1, 2]}'
    chiprun -- python3 benchmark/tools/dots3_drift.py '{"time": true}'
    JAX_PLATFORMS=cpu python3 benchmark/tools/dots3_drift.py \\
        '{"cell": "tiny-dots3-note-serve.tiny-closed", "prompts": [40]}'

The honest row, then planted faults, each against the unedited tolerances,
``CHOICE_SLACK`` and ``SELECT_SLACK``: a selection from the other full layer's
index keys, a selection one short (top-2047), an unrotated index key, the
window one short (512), a rescale left out, no gate, a bf16 stream where the
configuration says float32. Every decoded position is handed over for the
slacks; the logits are compared where the cell's check compares them (after
the prefill and after 8 decodes). One JSON line a variant, prompt and seed; a
summary last. ``"time": true`` instead times ``decode_block_4`` of an engine
whose slots all hold ``length`` tokens and the chunk program at ``chunks``
(512 / 1024 / 2048) over a prompt of ``prompt`` tokens: ms a step and prompt
tokens a second. It refuses to
measure the real cell without a TPU.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import correct, manifest  # noqa: E402

STEPS = correct.DECODE_STEPS


def variants(cfg):
    """name -> (config, patch): ``patch(monkey)`` plants what the config
    cannot say; ``monkey(obj, name, value)`` sets and remembers."""
    from deepspeed_tpu.models import gpt

    def kinds(**change):
        return dataclasses.replace(cfg, attn_period=tuple(
            dataclasses.replace(kind, **{
                name: value(getattr(kind, name)) for name, value
                in change.items() if getattr(kind, name)})
            for kind in cfg.attn_period))

    parts, attend = gpt._index_parts, gpt._append_and_attend_kinds

    def unrotated_index_key(monkey):
        def faulty(c, h, c_q, w, rotate):
            q, _, weights = parts(c, h, c_q, w, rotate)
            return q, parts(c, h, c_q, w, lambda t: t)[1], weights
        monkey(gpt, "_index_parts", faulty)

    def other_layers_keys(monkey):
        def faulty(c, named, *rest):
            if not c.index_topk:
                return attend(c, named, *rest)
            key = gpt.INDEX_KEYS[0]
            attn, pools = attend(c, dict(named, **{key: named[key][::-1]}),
                                 *rest)
            at = rest[0].index(key)
            return attn, pools[:at] + (pools[at][::-1],) + pools[at + 1:]
        monkey(gpt, "_append_and_attend_kinds", faulty)

    return {
        "honest": (cfg, None),
        "stream in bf16": (dataclasses.replace(
            cfg, stream_float32=not cfg.stream_float32), None),
        "index keys and scores in the other precision": (
            dataclasses.replace(cfg, index_float32=not cfg.index_float32),
            None),
        "the other layer's index keys": (cfg, other_layers_keys),
        "a selection one short": (kinds(index_topk=lambda k: k - 1), None),
        "an unrotated index key": (cfg, unrotated_index_key),
        "the window one short": (kinds(window=lambda w: w - 1), None),
        "a rescale left out": (dataclasses.replace(
            cfg, mla_lora_rescale=False), None),
        "no gate": (dataclasses.replace(cfg, attn_gate=False), None),
    }


def _engine(cfg, params, eng, slots, pages_each):
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    return ServingEngine(cfg, params, ServingConfig(
        num_slots=slots, num_pages=slots * pages_each + 1, **eng))


def measure(name, family, cfg, params, model, eng, reference, prompts, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    ps = eng["page_size"]
    pages = -(-(max(len(p) for p in prompts) + STEPS + 2) // ps)
    engine = _engine(cfg, params, eng, len(prompts), pages)
    tables = np.zeros((len(prompts), engine.serving.pages_per_seq), np.int32)
    for j, prompt in enumerate(prompts):
        tables[j, :pages] = 1 + j * pages + np.arange(pages)
        engine.prefill(j, prompt, tables[j])
    step = jax.jit(lambda p, c, t, tb, ln: family.paged_decode_step(
        cfg, p, t, c, tb, ln), donate_argnums=(1,))
    rng = np.random.default_rng([seed, 7])
    forced = rng.integers(0, model["vocab_size"],
                          (STEPS + 1, len(prompts))).astype(np.int32)
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    got, chose = [], []
    for k in range(STEPS + 1):     # teacher-forced: every variant, one text
        logits, engine.paged_cache, handed = step(
            engine.params, engine.paged_cache, jnp.asarray(forced[k]),
            jnp.asarray(tables), jnp.asarray(lengths + k))
        got.append(np.asarray(logits, np.float32))
        handed = np.asarray(handed)     # a selection one short: -1 after it
        chose.append(np.pad(handed, ((0, 0), (0, 0), (
            0, model["k"] + model["index_topk"] - handed.shape[2])),
            constant_values=-1))
    rows = []
    k_experts, topk = model["k"], model["index_topk"]
    for j, prompt in enumerate(prompts):
        n = len(prompt)
        ids = np.concatenate([prompt, forced[:, j]])
        handed = {n + k: chose[k][j] for k in range(STEPS + 1)}
        want, slack = reference.logits(model, params, ids,
                                       positions=[n, n + STEPS],
                                       choices=handed)
        # the selections' share of the slack, read again with the experts
        # alone handed: what is left over is the experts'
        _, experts = reference.logits(
            model, params, ids, positions=[n, n + STEPS],
            choices={p: c[:, :k_experts] for p, c in handed.items()}) \
            if name == "honest" else (None, None)
        want = np.asarray(want)
        readings = [tuple(map(float, correct.logit_differences(
            got[k][j], want[i]))) for i, k in enumerate((0, STEPS))]
        all_slack = np.stack([slack[pos] for pos in sorted(slack)])
        own = reference.forward(model, params, ids, probe=sorted(handed))[3]
        differ = [len(set(chose[k][j][l, k_experts:].tolist())
                      - set(own[n + k][l].tolist()) - {-1})
                  for k in range(STEPS + 1) for l in range(model["n_layer"])
                  if reference.kind_of(model, l) == "full"]
        rows.append({
            "variant": name, "seed": seed, "prompt": n,
            "rms": [r[0] for r in readings], "max": [r[1] for r in readings],
            "slack_max": float(all_slack.max()),
            "slack_in_select_units": float(
                all_slack.max() * reference.SELECT_SLACK
                / reference.CHOICE_SLACK),
            "experts_slack_max": None if experts is None else float(
                np.stack([experts[pos] for pos in sorted(experts)]).max()),
            "layers_flipped": int((all_slack > 0).sum()),
            "layers": int(all_slack.size),
            "selected_rows_not_the_references_own": [
                max(differ), float(np.mean(differ))],
            "of": min(topk, n)})
        print(json.dumps(rows[-1]), flush=True)
    del engine
    return rows


def timings(family, cfg, params, model, eng, spec):
    """ms a decode step, and the chunk programs' rates."""
    import jax
    import numpy as np

    length, slots = spec.get("length", 9984), spec.get("slots", 32)
    pages = eng["max_model_len"] // eng["page_size"]
    for chunk in spec.get("chunks", [eng["prefill_chunk"]]):
        engine = _engine(cfg, params, dict(eng, prefill_chunk=chunk), slots,
                         pages)
        tables = 1 + np.arange(slots * pages, dtype=np.int32).reshape(
            slots, pages)
        prompt = np.zeros(spec.get("prompt", 16384), np.int32)
        engine.prefill(0, prompt, tables[0])            # compiles
        t0 = time.perf_counter()
        engine.prefill(0, prompt, tables[0])
        took = time.perf_counter() - t0
        print(json.dumps({"chunk": chunk, "prompt": len(prompt),
                          "prefill_s": took,
                          "prompt_tok_s": len(prompt) / took}), flush=True)
        if chunk != eng["prefill_chunk"]:
            del engine
            continue
        lengths = np.full(slots, length, np.int32)
        zeros = np.zeros(slots, np.int32)
        active = np.ones(slots, bool)
        engine.decode(zeros, tables, lengths, active, steps=4)
        t0 = time.perf_counter()
        for _ in range(spec.get("repeats", 10)):
            jax.block_until_ready(engine.decode(
                zeros, tables, lengths, active, steps=4))
        took = (time.perf_counter() - t0) / spec.get("repeats", 10) / 4
        print(json.dumps({"length": length, "slots": slots,
                          "ms_a_step": 1e3 * took}), flush=True)
        del engine


def main(argv) -> int:
    spec = json.loads(argv[0]) if argv else {}
    import jax
    import numpy as np

    name = spec.get("cell", "dots3-note-serve.long-notes")
    if jax.devices()[0].platform != "tpu" and not name.startswith("tiny-"):
        print("no TPU: this measures a bf16 path on the chip", file=sys.stderr)
        return 2
    cell = manifest.load_cell(name)
    config = cell["config_file"]
    model, eng = config["model"], dict(config["engine"])
    family = manifest.family_of(config)
    reference = manifest.reference_of(config)
    cfg = family.config(model)
    wanted = spec.get("variants")
    out = []
    for seed in spec.get("seeds", [1]):
        t0 = time.perf_counter()
        params = jax.block_until_ready(jax.jit(
            lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(seed)))
        print(f"seed {seed}: weights in {time.perf_counter() - t0:.1f}s",
              flush=True)
        if spec.get("time"):
            timings(family, cfg, params, model, eng, spec)
            continue
        rng = np.random.default_rng([seed, 0xC0FFEE])
        prompts = [rng.integers(0, model["vocab_size"], n).astype(np.int32)
                   for n in spec.get("prompts", [4096, 8192])]
        for vname, (vcfg, patch) in variants(cfg).items():
            if wanted and vname not in wanted:
                continue
            undo = []

            def monkey(obj, attr, value):
                undo.append((obj, attr, getattr(obj, attr)))
                setattr(obj, attr, value)
            if patch:
                patch(monkey)
            try:
                out += measure(vname, family, vcfg, params, model, eng,
                               reference, prompts, seed)
            finally:
                for obj, attr, old in undo:
                    setattr(obj, attr, old)
        del params
    if not out:
        return 0
    print("== summary: variant, readings, largest rms and max diff (limits "
          f"{correct.LOGIT_RMS_TOL}, {correct.LOGIT_MAX_TOL}), largest slack "
          f"(limit {reference.CHOICE_SLACK}; a selection's slack times "
          f"{reference.CHOICE_SLACK / reference.SELECT_SLACK:g}), share of "
          "layer choices flipped")
    for vname in dict.fromkeys(r["variant"] for r in out):
        rows = [r for r in out if r["variant"] == vname]
        rms = [x for r in rows for x in r["rms"]]
        mx = [x for r in rows for x in r["max"]]
        print(json.dumps({
            "variant": vname, "readings": len(rms),
            "rms_median": float(np.median(rms)), "rms_max": max(rms),
            "max_max": max(mx),
            "slack_max": max(r["slack_max"] for r in rows),
            "experts_slack_max": max(
                (r["experts_slack_max"] for r in rows
                 if r["experts_slack_max"] is not None), default=None),
            "flipped_share": sum(r["layers_flipped"] for r in rows)
            / sum(r["layers"] for r in rows),
            "rows_not_own_max": max(
                r["selected_rows_not_the_references_own"][0] for r in rows)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
