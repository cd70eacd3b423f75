#!/usr/bin/env python3
"""On the chip, outside any cell: how far the served DeepSeek-V2 path of
``deepseek-v2-serve`` lies from ``reference/deepseek_v2_ref.py``, by the two
numbers ``lib/correct.py`` compares and by the slack of the experts the served
step chose, with the program's own programs (``ServingEngine``: serial prefill
chunks, ``jit_scatter``, then teacher-forced decode steps through the latent
pages) over prompts of the cell's lengths.

    chiprun -- python3 benchmark/tools/dsv2_drift.py '{"seeds": [1, 2]}'

What ``tools/routing_flips.py`` measured on a stand-in for a served OLMoE, read
again with this family's own step at these widths: the honest row, the row
with the router's logits rounded to bf16, and planted faults, each against the
unedited tolerances and against ``CHOICE_SLACK``. Every decoded position is
handed over for the slack; the logits are compared where the cell's check
compares them (after the prefill and after 8 decodes). One JSON line a
variant, prompt and seed; a summary last. It refuses to measure without a TPU.
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
    from deepspeed_tpu.moe import dropless
    import jax.numpy as jnp

    route = dropless.route

    def bf16_router(monkey):
        monkey(dropless, "route", lambda logits, *a: route(
            logits.astype(jnp.bfloat16).astype(jnp.float32), *a))

    from deepspeed_tpu.ops.pallas import decode_attention
    kernel = decode_attention.paged_decode_mla

    def one_pass_kernel(monkey):
        monkey(decode_attention, "paged_decode_mla", lambda q, *a, **kw:
               kernel(q.astype(jnp.bfloat16), *a, **kw))

    return {
        "honest": (cfg, None),
        "latent kernel in one pass": (cfg, one_pass_kernel),
        "stream in bf16": (dataclasses.replace(
            cfg, stream_float32=False), None),
        "narrow linear outputs": (dataclasses.replace(
            cfg, linear_out_float32=False), None),
        "router in bf16": (cfg, bf16_router),
        # the routed experts' output out of both sides: what is left is not
        # the choices'
        "no routed output, both sides": (dataclasses.replace(
            cfg, moe_scale=0.0), None),
        "no scaling factor": (dataclasses.replace(cfg, moe_scale=1.0), None),
        "no shared expert": (dataclasses.replace(cfg, moe_shared_d_ff=0),
                             None),
        "a wrong YaRN scale": (dataclasses.replace(
            cfg, rope_scaling=dataclasses.replace(cfg.rope_scaling,
                                                  mscale_all_dim=0.0)), None),
    }


def measure(name, cfg, params, model, reference, prompts, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models import gpt

    pages = -(-(max(len(p) for p in prompts) + STEPS + 2) // 64)
    engine = ServingEngine(cfg, params, ServingConfig(
        num_slots=len(prompts), num_pages=len(prompts) * pages + 1,
        page_size=64, max_model_len=3072, prefill_chunk=512, decode_block=4))
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
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("no TPU: this measures a bf16 path on the chip", file=sys.stderr)
        return 2
    cell = manifest.load_cell("deepseek-v2-serve.long-decode")
    config = cell["config_file"]
    model = config["model"]
    family = manifest.family_of(config)
    reference = manifest.reference_of(config)
    cfg = family.config(model)
    wanted = spec.get("variants")
    out = []
    for seed in spec.get("seeds", [1]):
        t0 = time.perf_counter()
        params = jax.block_until_ready(jax.jit(
            lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(seed)))
        rng = np.random.default_rng([seed, 0xC0FFEE])
        prompts = [rng.integers(0, model["vocab_size"], n).astype(np.int32)
                   for n in spec.get("prompts", [1024, 2048])]
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
                out += measure(
                    name, vcfg, params,
                    dict(model, routed_scaling_factor=0.0)
                    if name.endswith("both sides") else model,
                    reference, prompts, seed)
            finally:
                for obj, attr, old in undo:
                    setattr(obj, attr, old)
        del params
    print("== summary: variant, readings, largest rms and max diff (limits "
          f"{correct.LOGIT_RMS_TOL}, {correct.LOGIT_MAX_TOL}), largest slack "
          f"(limit {reference.CHOICE_SLACK}), share of layer choices flipped")
    for name in dict.fromkeys(r["variant"] for r in out):
        rows = [r for r in out if r["variant"] == name]
        rms = [x for r in rows for x in r["rms"]]
        mx = [x for r in rows for x in r["max"]]
        print(json.dumps({
            "variant": name, "readings": len(rms),
            "rms_median": float(np.median(rms)), "rms_max": max(rms),
            "max_max": max(mx),
            "slack_max": max(r["slack_max"] for r in rows),
            "flipped_share": sum(r["layers_flipped"] for r in rows)
            / sum(r["layers"] for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
