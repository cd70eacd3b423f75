#!/usr/bin/env python3
"""On the chip, outside any cell: how far the served Laguna-XS.2 path of
``laguna-xs.2-serve`` lies from ``reference/laguna_ref.py``, by the two numbers
``lib/correct.py`` compares and by the slack of the experts the served step
chose, with the program's own programs (``ServingEngine``: the fused prompt
program or serial prefill chunks and ``jit_scatter`` into pages and rings, then
teacher-forced decode steps through ``paged_decode_gqa``) over prompts of the
cell's lengths: inside the window, past it, and past it sixteen times.

    chiprun -- python3 benchmark/tools/laguna_drift.py '{"seeds": [1]}'

``tools/dsv2_drift.py``'s rows read again with this family's own step at these
widths, as ``benchmark/README.md`` asks of the PR that brings a routed family:
the honest row, the row with the router's logits rounded to bf16, the stream
in bf16, and planted faults, each against the unedited tolerances and against
``CHOICE_SLACK``. Every decoded position is handed over for the slack; the
logits are compared where the cell's check compares them (after the prefill
and after 8 decodes). One JSON line a variant, prompt and seed; a summary
last. It refuses to measure without a TPU.
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
    import jax.numpy as jnp

    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.moe import dropless

    route, append = dropless.route, gpt._ring_append

    def bf16_router(monkey):
        monkey(dropless, "route", lambda logits, *a: route(
            logits.astype(jnp.bfloat16).astype(jnp.float32), *a))

    def ring_row_off_by_one(monkey):
        monkey(gpt, "_ring_append", lambda ring, layer, row, lengths: append(
            ring, layer, row, jnp.where(lengths > 0, lengths + 1, 0)))

    def kinds(**change):
        return dataclasses.replace(cfg, attn_period=tuple(
            dataclasses.replace(k, **{n: f(k) for n, f in change.items()})
            for k in cfg.attn_period))

    return {
        "honest": (cfg, None),
        "router in bf16": (cfg, bf16_router),
        "stream in bf16": (dataclasses.replace(
            cfg, stream_float32=False, linear_out_float32=False,
            rotary_float32=False), None),
        "no gate": (dataclasses.replace(cfg, attn_gate=False), None),
        "gates not renormalised": (dataclasses.replace(
            cfg, moe_norm_topk=False), None),
        "no scaling factor": (dataclasses.replace(cfg, moe_scale=1.0), None),
        "the whole head rotated in a full layer": (
            kinds(rotary_pct=lambda k: 1.0), None),
        "a window of 448": (
            kinds(window=lambda k: 448 if k.window else 0), None),
        "a ring row off by one": (cfg, ring_row_off_by_one),
    }


def measure(name, cfg, params, model, reference, prompts, seed, engine_keys):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models import gpt

    ps = engine_keys["page_size"]
    pages = -(-(max(len(p) for p in prompts) + STEPS + 2) // ps)
    engine = ServingEngine(cfg, params, ServingConfig(
        num_slots=len(prompts), num_pages=len(prompts) * pages + 1,
        **engine_keys))
    tables = np.zeros((len(prompts), engine.serving.pages_per_seq), np.int32)
    for j, prompt in enumerate(prompts):
        tables[j, :pages] = 1 + j * pages + np.arange(pages)
        engine.prefill(j, prompt, tables[j])
    step = jax.jit(lambda p, c, t, tb, ln: gpt.paged_decode_step(
        cfg, p, t, c, tb, ln, return_routing=True), donate_argnums=(1,))
    rng = np.random.default_rng([seed, 7])
    forced = rng.integers(0, model["vocab_size"],
                          (STEPS + 1, len(prompts))).astype(np.int32)
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    got, chose = [], []
    for k in range(STEPS + 1):     # teacher-forced: every variant, one text
        logits, engine.paged_cache, (chosen, _) = step(
            engine.params, engine.paged_cache, jnp.asarray(forced[k]),
            jnp.asarray(tables), jnp.asarray(lengths + k))
        got.append(np.asarray(logits, np.float32))
        chose.append(np.asarray(chosen))
    rows = []
    for j, prompt in enumerate(prompts):
        n = len(prompt)
        ids = np.concatenate([prompt, forced[:, j]])
        handed = {n + k: chose[k][j] for k in range(STEPS + 1)}
        want, slack = reference.logits(model, params, ids,
                                       positions=[n, n + STEPS],
                                       choices=handed)
        want = np.asarray(want)
        readings = [tuple(map(float, correct.logit_differences(
            got[k][j], want[i]))) for i, k in enumerate((0, STEPS))]
        all_slack = np.stack([slack[pos] for pos in sorted(slack)])
        rows.append({
            "variant": name, "seed": seed, "prompt": n,
            "rms": [r[0] for r in readings], "max": [r[1] for r in readings],
            "slack_max": float(all_slack.max()),
            "layers_flipped": int((all_slack > 0).sum()),
            "layers": int(all_slack.size)})
        print(json.dumps(rows[-1]), flush=True)
    del engine
    return rows


def main(argv) -> int:
    spec = json.loads(argv[0]) if argv else {}
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu" and not spec.get("cell"):
        print("no TPU: this measures a bf16 path on the chip", file=sys.stderr)
        return 2
    # {"cell": a rehearsal cell}: the tool's own rehearsal on the CPU
    cell = manifest.load_cell(spec.get("cell",
                                       "laguna-xs.2-serve.mixed-decode"))
    config = cell["config_file"]
    model = config["model"]
    family = manifest.family_of(config)
    reference = manifest.reference_of(config)
    cfg = family.config(model)
    engine_keys = {k: config["engine"][k] for k in (
        "page_size", "max_model_len", "prefill_chunk", "decode_block",
        "dtype")}
    wanted = spec.get("variants")
    out = []
    for seed in spec.get("seeds", [1]):
        t0 = time.perf_counter()
        params = jax.block_until_ready(jax.jit(
            lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(seed)))
        rng = np.random.default_rng([seed, 0xC0FFEE])
        prompts = [rng.integers(0, model["vocab_size"], n).astype(np.int32)
                   for n in spec.get("prompts", [512, 2048, 8192])]
        print(f"seed {seed}: weights in {time.perf_counter() - t0:.1f}s",
              flush=True)
        for name, (vcfg, patch) in variants(cfg).items():
            if wanted and name not in wanted:
                continue
            undo = []

            def monkey(obj, attr, value):
                undo.append((obj, attr, getattr(obj, attr)))
                setattr(obj, attr, value)
            if patch:
                patch(monkey)
            try:
                out += measure(name, vcfg, params, model, reference, prompts,
                               seed, engine_keys)
            finally:
                for obj, attr, old in undo:
                    setattr(obj, attr, old)
        del params
    print("== summary: variant, readings, largest rms and max diff (limits "
          f"{correct.LOGIT_RMS_TOL}, {correct.LOGIT_MAX_TOL}), largest slack "
          f"(limit {reference.CHOICE_SLACK}), layer choices that differ")
    for name in dict.fromkeys(r["variant"] for r in out):
        rows = [r for r in out if r["variant"] == name]
        print(f"{name}: {2 * len(rows)} readings, "
              f"rms {max(max(r['rms']) for r in rows):.4f}, "
              f"max {max(max(r['max']) for r in rows):.4f}, "
              f"slack {max(r['slack_max'] for r in rows):.4f}, "
              f"{sum(r['layers_flipped'] for r in rows)} of "
              f"{sum(r['layers'] for r in rows)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
