#!/usr/bin/env python3
"""On the chip, outside any cell: how far the served LongCat-Flash path of
``longcat-flash-omni-serve`` lies from ``reference/longcat_flash_ref.py``, by
the two numbers ``lib/correct.py`` compares and by the slack of the outputs
the served step chose (real and zero-compute experts alike), with the
program's own programs (``ServingEngine``: a prompt straight into the latent
pages of its eight cache layers, then teacher-forced decode steps through
them) over prompts of the cell's lengths.

    chiprun -- python3 benchmark/tools/longcat_drift.py '{"seeds": [1, 2]}'

The honest row (the configuration as shipped: a bf16 stream over bf16 weights
and pages; ONE seed a process on the chip: a second seed's weights do not fit
beside the first's), the stream in float32, a router whose logits are rounded
to bf16, and planted faults of the layer's own mechanisms (the identity term
left out, the routed branch landing after the first sub-block, the second
sub-block on the first's cache layer, the gates with the bias in them, a
latent left unscaled), each against the unedited tolerances and against
``CHOICE_SLACK``; the next precision below the configuration's in the
program's place (the pool a prompt left, its rotated keys alone, or the stream
between layers, cut to 8 bits a number: ``cut_to_8_bits``), which has to come
out not correct; and what is left of bf16 in the float32 stream's path, a
piece at a time (float32 products at full precision, the pages in float32,
the experts' rows in two passes). Each row also says how far the served
stream lies from the reference's after the embedding and after every layer,
at the first compared position (``stream_rms_by_layer``). Every decoded
position is handed over for the slack; the logits are compared where the
cell's check compares them (after the prefill and after 8 decodes). One JSON
line a variant, prompt and seed; a summary last. It refuses to measure
without a TPU, but for a rehearsal cell: ``'{"cell": "tiny-longcat-flash-serve.tiny-closed",
"prompts": [20, 40]}'`` walks the same code on the CPU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import correct, manifest  # noqa: E402

STEPS = correct.DECODE_STEPS
CELL = "longcat-flash-omni-serve.answer-decode"
# variants the configuration cannot say and no patch plants: how the programs
# are traced, what the pool is made of
FULL = "float32 products at full precision"
PAGES32 = "pages in float32"
# the next precision below the configuration's bf16, put in the program's
# place: what the prompt left in the pool, or only the rotated keys in it,
# cut to 8 bits a number before the compared steps read it
PAGES8 = "pages cut to 8 bits"
KEYS8 = "rotated keys in the pages cut to 8 bits"


def cut_to_8_bits(a):
    """bf16 ``a`` with its 7 stored bits of mantissa rounded to the nearest
    of 3, float8_e4m3's precision at bf16's range: by bit arithmetic, so that
    it asks the device for no float8 type."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(a.astype(jnp.bfloat16), jnp.uint16)
    return jax.lax.bitcast_convert_type(
        (bits + 0x8) & 0xFFF0, jnp.bfloat16).astype(a.dtype)


def layer_states(reference, model, params, ids, choices, at):
    """The reference's residual stream at position ``at`` after the
    embedding and after every layer (after the final norm, the last), [n_layer
    + 1, d], under ``choices``: the boundaries ``GPTConfig.state_layers``
    names."""
    import jax
    import jax.numpy as jnp

    items = reference._frozen(model)
    handed, use = reference._handed(model, len(ids), choices)
    with jax.default_matmul_precision("highest"):
        x = params["wte"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
        out = [x[at]]
        for layer in range(model["n_layer"]):
            x, _, _ = reference._block_at(items, x, params["moe_blocks"],
                                          jnp.int32(layer), handed[layer],
                                          use)
            out.append(x[at])
    # the served program's last boundary is the pass's closing norm
    out[-1] = reference.rms_norm(out[-1], params["lnf_scale"],
                                 model["rms_norm_eps"])
    return jnp.stack(out)


def variants(cfg):
    """name -> (config, patch): ``patch(monkey)`` plants what the config
    cannot say; ``monkey(obj, name, value)`` sets and remembers."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.moe import dropless

    route, shortcut = dropless.route, gpt._shortcut_on

    def bf16_router(monkey):
        monkey(dropless, "route", lambda logits, *a, **kw: route(
            logits.astype(jnp.bfloat16).astype(jnp.float32), *a, **kw))

    def no_identity(monkey):
        monkey(dropless, "zero_experts",
               lambda h, *a: jnp.zeros(h.shape, jnp.float32))

    def biased_gates(monkey):
        def biased(logits, k, *a, bias=None, **kw):
            scores = jax.nn.softmax(logits, axis=-1) + bias
            chosen = jax.lax.top_k(scores, k)[1].astype(jnp.int32)
            return chosen, jnp.take_along_axis(scores, chosen,
                                               1) * cfg.moe_scale
        monkey(dropless, "route", biased)

    def stream_8_bits(monkey):
        monkey(gpt, "_shortcut_on", lambda c, x, w, pos, attend, drop=None:
               shortcut(c, cut_to_8_bits(x), w, pos, attend, drop))

    def one_cache_layer(monkey):
        monkey(gpt, "_shortcut_on", lambda c, x, w, pos, attend, drop=None:
               shortcut(c, x, w, pos, lambda j, carried: attend(0, carried),
                        drop))

    def lands_early(monkey):
        def early(c, x, w, positions, attend, drop=None):
            second = {k[len(gpt.SUB1):]: v for k, v in w.items()
                      if k.startswith(gpt.SUB1)}
            carried = ()
            for j, wj in enumerate((w, second)):
                attn, rows = gpt._attn_delta(c, x, wj, positions,
                                             attend(j, carried))
                carried += (rows,)
                a = (x + attn).astype(x.dtype)
                h = gpt._norm(c, a, wj, "ln2")
                x = a + gpt._mlp_on(c, h, wj)
                if j == 0:
                    held, chosen = gpt._routed_on(c, h, w)
                    x = x + held
                x = x.astype(a.dtype)
            return x, carried, chosen
        monkey(gpt, "_shortcut_on", early)

    wide = dict(stream_float32=True, linear_out_float32=True,
                rotary_float32=True)
    return {
        "honest": (cfg, None),
        # the other cells' arrangement: the serving forwards' stream in
        # float32 over the same bf16 weights and pages; and what is left of
        # bf16 in that path, a piece at a time: the products of two float32
        # operands (one bf16 pass on the chip by default; the latent kernel
        # refuses them at full precision), the pages, the experts' rows
        "stream in float32": (dataclasses.replace(cfg, **wide), None),
        FULL: (dataclasses.replace(cfg, **wide), None),
        PAGES32: (dataclasses.replace(cfg, **wide), None),
        "experts in two passes": (dataclasses.replace(
            cfg, moe_two_pass=True, **wide), None),
        "router in bf16": (cfg, bf16_router),
        PAGES8: (cfg, None),
        KEYS8: (cfg, None),
        "stream cut to 8 bits between layers": (cfg, stream_8_bits),
        "no identity term": (cfg, no_identity),
        "the branch lands after the first sub-block": (cfg, lands_early),
        "one cache layer a layer": (cfg, one_cache_layer),
        "the bias in the gates": (cfg, biased_gates),
        "no scaling factor": (dataclasses.replace(cfg, moe_scale=1.0), None),
        "no rescale of the latents": (dataclasses.replace(
            cfg, mla_lora_rescale=False), None),
    }


def measure(name, cfg, params, config, reference, prompts, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models import gpt

    model, eng = config["model"], config["engine"]
    page = eng["page_size"]
    pages = -(-(max(len(p) for p in prompts) + STEPS + 2) // page)
    # the stream after every layer beside the logits: where the distance grows
    cfg = dataclasses.replace(cfg, state_layers=tuple(
        range(1, cfg.n_layer + 1)))
    engine = ServingEngine(cfg, params, ServingConfig(
        num_slots=len(prompts), num_pages=len(prompts) * pages + 1,
        page_size=page, max_model_len=eng["max_model_len"],
        prefill_chunk=eng["prefill_chunk"], decode_block=eng["decode_block"],
        dtype=eng["dtype"]))
    if name == PAGES32:
        engine.paged_cache = gpt.init_paged_cache(
            cfg, engine.num_pages, page, jnp.float32)
    tables = np.zeros((len(prompts), engine.serving.pages_per_seq), np.int32)
    for j, prompt in enumerate(prompts):
        tables[j, :pages] = 1 + j * pages + np.arange(pages)
        engine.prefill(j, prompt, tables[j])
    if name in (PAGES8, KEYS8):
        first = cfg.kv_lora_rank if name == KEYS8 else 0
        engine.paged_cache = jax.tree_util.tree_map(
            lambda a: a.at[..., first:].set(cut_to_8_bits(a[..., first:]))
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            engine.paged_cache)
    step = jax.jit(lambda p, c, t, tb, ln: gpt.paged_decode_step(
        cfg, p, t, c, tb, ln, return_states=True, return_routing=True),
        donate_argnums=(1,))
    rng = np.random.default_rng([seed, 7])
    forced = rng.integers(0, model["vocab_size"],
                          (STEPS + 1, len(prompts))).astype(np.int32)
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    got, chose, streams = [], [], []
    for k in range(STEPS + 1):     # teacher-forced: every variant, one text
        logits, engine.paged_cache, states, (chosen, _) = step(
            engine.params, engine.paged_cache, jnp.asarray(forced[k]),
            jnp.asarray(tables), jnp.asarray(lengths + k))
        got.append(np.asarray(logits, np.float32))
        chose.append(np.asarray(chosen))
        streams.append(np.asarray(states, np.float32))
    rows = []
    for j, prompt in enumerate(prompts):
        n = len(prompt)
        ids = np.concatenate([prompt, forced[:, j]])
        handed = {n + k: chose[k][j] for k in range(STEPS + 1)}
        want, slack = reference.logits(model, engine.params, ids,
                                       positions=[n, n + STEPS],
                                       choices=handed)
        want = np.asarray(want)
        readings = [tuple(map(float, correct.logit_differences(
            got[k][j], want[i]))) for i, k in enumerate((0, STEPS))]
        all_slack = np.stack([slack[pos] for pos in sorted(slack)])
        picks = np.stack([chose[k][j] for k in range(STEPS + 1)])
        # the served stream at the first compared position, layer by layer
        want_x = np.asarray(layer_states(reference, model, engine.params, ids,
                                         handed, n))
        by_layer = [float(np.sqrt(np.mean((streams[0][j][b] - want_x[b]) ** 2)
                                  / np.mean(want_x[b] ** 2)))
                    for b in range(len(want_x))]
        rows.append({
            "variant": name, "seed": seed, "prompt": n,
            "rms": [r[0] for r in readings], "max": [r[1] for r in readings],
            "slack_max": float(all_slack.max()),
            "layers_flipped": int((all_slack > 0).sum()),
            "layers": int(all_slack.size),
            "stream_rms_by_layer": [round(x, 6) for x in by_layer],
            "zero_pct": float(100 * (picks >= model["n_routed_experts"]
                                     ).mean())})
        print(json.dumps(rows[-1]), flush=True)
    del engine
    return rows


def main(argv) -> int:
    spec = json.loads(argv[0]) if argv else {}
    import jax
    import numpy as np

    cell = manifest.load_cell(spec.get("cell", CELL))
    if jax.devices()[0].platform != "tpu" and not cell.get("rehearsal"):
        print("no TPU: this measures a bf16 path on the chip", file=sys.stderr)
        return 2
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
                   for n in spec.get("prompts", [256, 512])]
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
                with (jax.default_matmul_precision("highest")
                      if name == FULL else contextlib.nullcontext()):
                    out += measure(name, vcfg, params, config, reference,
                                   prompts, seed)
            except Exception as e:  # a variant the chip refuses is a row too
                print(json.dumps({"variant": name, "seed": seed,
                                  "refused": str(e)[:300]}), flush=True)
            finally:
                for obj, attr, old in undo:
                    setattr(obj, attr, old)
        del params
    print("== summary: variant, readings, largest rms and max diff (limits "
          f"{correct.LOGIT_RMS_TOL}, {correct.LOGIT_MAX_TOL}), largest slack "
          f"(limit {reference.CHOICE_SLACK}), share of layer choices flipped, "
          "share of picks that took a zero-compute expert")
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
            / sum(r["layers"] for r in rows),
            "zero_pct": float(np.mean([r["zero_pct"] for r in rows]))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
