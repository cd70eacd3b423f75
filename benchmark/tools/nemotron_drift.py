#!/usr/bin/env python3
"""On the chip, outside any cell: how far the served Nemotron-3-Nano path of
``nemotron-3-nano-serve`` lies from ``reference/nemotron_h_ref.py``, by the two
numbers ``lib/correct.py`` compares and by the slack of the experts the served
step chose, with the program's own programs (``ServingEngine``: the fused
prompt program or serial prefill chunks, the state and the window into the
decode slot, then teacher-forced decode steps through ``ssm_decode`` and
``paged_decode_gqa``) over prompts of the cell's lengths.

    chiprun -- python3 benchmark/tools/nemotron_drift.py '{"seeds": [1]}'

``tools/laguna_drift.py``'s rows read again with this family's own step at
these widths, as ``benchmark/README.md`` asks of the PR that brings a routed
family: the honest row, the router's logits rounded to bf16, the stream in
bf16, the mixers' states and windows rounded to bf16 after every step, and
planted faults, each against the unedited tolerances and against
``CHOICE_SLACK`` (in the unit of ``s + bias``). Every decoded position is
handed over for the slack; the logits are compared where the cell's check
compares them (after the prefill and after 8 decodes). First, ``ssm_decode``
itself against the recurrence on inputs of order 1 (``kernel_row``). One JSON
line a variant, prompt and seed; a summary last. It refuses to measure without a TPU.
``{"conv_taps": "conv1d"}`` reads the same rows with convolution taps as a
``Conv1d`` starts them, under which the state carries a third of a mixer's
output (PERF.md section 6, PR 40).
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
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.pallas import ssm_decode as kernel

    route, decode = dropless.route, kernel.ssm_decode

    def bf16(a):    # not a cast there and back, which the compiler may drop
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def bf16_router(monkey):
        monkey(dropless, "route", lambda logits, *a, **kw: route(
            bf16(logits.astype(jnp.float32)), *a, **kw))

    def choice_without_bias(monkey):
        monkey(dropless, "route", lambda logits, *a, bias=None, **kw: route(
            logits, *a, bias=None, **kw))

    def bf16_state(monkey):
        def rounded(*a, **kw):
            y, states, *rest = decode(*a, **kw)
            return (y, bf16(states), *(bf16(w) for w in rest))
        monkey(kernel, "ssm_decode", rounded)

    def decay_once_more(monkey):
        monkey(kernel, "ssm_decode", lambda s, layer, dtx, decay, *a, **kw:
               decode(s, layer, dtx, decay * decay, *a, **kw))

    return {
        "honest": (cfg, None),
        "router in bf16": (cfg, bf16_router),
        "stream in bf16": (dataclasses.replace(
            cfg, stream_float32=False, linear_out_float32=False), None),
        "states and windows in bf16": (cfg, bf16_state),
        "chosen without the bias": (cfg, choice_without_bias),
        "gates not renormalised": (dataclasses.replace(
            cfg, moe_norm_topk=False), None),
        "no scaling factor": (dataclasses.replace(cfg, moe_scale=1.0), None),
        "relu for relu squared": (dataclasses.replace(
            cfg, activation="relu"), None),
        "a decode step decays twice": (cfg, decay_once_more),
    }


def kernel_row(model, slots=64, live=37):
    """``ssm_decode`` on the chip against the recurrence in ``jax.numpy``, at
    the model's sizes, with inputs of order 1: under the seeding the
    configuration states the state's share of a mixer's output is under a
    thousandth, so no logit shows a fault of the recurrence; this row does.
    An idle slot's state and the other layers' must come back bit-equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas import ssm_decode as kernel

    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, G, K = model["ssm_state_size"], model["n_groups"], model["conv_kernel"]
    C = H * P + 2 * G * N
    key = jax.random.split(jax.random.PRNGKey(0), 8)
    state = jax.random.normal(key[0], (2, slots, H, P, N), jnp.float32)
    windows = jax.random.normal(key[1], (2, slots, K - 1, C), jnp.float32)
    args = (jax.random.normal(key[2], (slots, H, P)),
            jax.random.uniform(key[3], (slots, H)),
            jax.random.normal(key[4], (slots, G, N)),
            jax.random.normal(key[5], (slots, G, N)),
            jnp.asarray(np.random.default_rng(0).permutation(slots) < live))
    row = jax.random.normal(key[6], (slots, C))
    got = jax.jit(lambda s, w: kernel.ssm_decode(
        s, jnp.int32(1), *args, impl="kernel", windows=w, new_row=row))(
            state, windows)
    want = jax.jit(lambda s, w: kernel.ssm_decode(
        s, jnp.int32(1), *args, impl="gather", windows=w, new_row=row))(
            state, windows)
    idle = ~np.asarray(args[-1])
    out = {"variant": "ssm_decode against the recurrence", "slots": slots,
           "live": live,
           "y_err": float(jnp.abs(got[0] - want[0]).max()),
           "state_err": float(jnp.abs(got[1] - want[1]).max()),
           "window_err": float(jnp.abs(got[2] - want[2]).max()),
           "idle_and_other_layer_bit_equal": bool(
               (np.asarray(got[1][0]) == np.asarray(state[0])).all()
               and (np.asarray(got[1][1])[idle]
                    == np.asarray(state[1])[idle]).all()
               and (np.asarray(got[2][0]) == np.asarray(windows[0])).all()
               and (np.asarray(got[2][1])[idle]
                    == np.asarray(windows[1])[idle]).all())}
    print(json.dumps(out), flush=True)
    return out


def measure(name, cfg, params, model, family, reference, prompts, seed,
            engine_keys):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    ps = engine_keys["page_size"]
    pages = -(-(max(len(p) for p in prompts) + STEPS + 2) // ps)
    # a slot more than the comparison steps, idle, as in a cell: the
    # family's step takes the first ``SEQUENCES`` slots out of a larger stack
    n = len(prompts)
    slots = max(n, correct.SEQUENCES) + 1
    engine = ServingEngine(cfg, params, ServingConfig(
        num_slots=slots, num_pages=n * pages + 1, **engine_keys))
    tables = np.zeros((slots, engine.serving.pages_per_seq), np.int32)
    for j, prompt in enumerate(prompts):
        tables[j, :pages] = 1 + j * pages + np.arange(pages)
        engine.prefill(j, prompt, tables[j])
    # the comparison's own step (``correct.check_step``): it leaves the
    # states as they were, so a decode through the engine's own program
    # follows it, as in ``correct.serve_whole``
    step = correct.check_step(family, reference, cfg,
                              engine.serving.kernel_impl)
    rng = np.random.default_rng([seed, 7])
    forced = np.zeros((STEPS + 1, slots), np.int32)
    forced[:, :n] = rng.integers(0, model["vocab_size"], (STEPS + 1, n))
    active = np.arange(slots) < n
    lengths = np.zeros(slots, np.int32)
    lengths[:n] = [len(p) for p in prompts]
    got, chose = [], []
    for k in range(STEPS + 1):     # teacher-forced: every variant, one text
        (logits, chosen), engine.paged_cache = step(
            engine.params, engine.paged_cache, jnp.asarray(forced[k]),
            jnp.asarray(tables), jnp.asarray(lengths + k * active))
        got.append(np.asarray(logits, np.float32))
        chose.append(np.asarray(chosen))
        if k < STEPS:
            engine.decode(forced[k].copy(), tables.copy(),
                          lengths + k * active, active, steps=1)
    rows = []
    for j, prompt in enumerate(prompts):
        n = len(prompt)
        ids = np.concatenate([prompt, forced[:, j]])
        handed = {n + k: chose[k][j] for k in range(STEPS + 1)}
        x, _, apart = reference.forward(model, params, ids, handed,
                                        distances=True)
        want = np.asarray(reference.head_logits(model, params, x,
                                                [n, n + STEPS]))
        readings = [tuple(map(float, correct.logit_differences(
            got[k][j], want[i]))) for i, k in enumerate((0, STEPS))]
        apart = np.asarray(apart)[sorted(handed)]   # [positions, n_layer]
        all_slack = reference.state_slack(model, apart)
        mixers = [l for l, c in enumerate(model["hybrid_pattern"])
                  if c == "M"]
        rows.append({
            "variant": name, "seed": seed, "prompt": n,
            "rms": [r[0] for r in readings], "max": [r[1] for r in readings],
            "slack_max": float(all_slack.max()),
            # the mixers' states: first mixer, later mixers, each the
            # largest over the positions (``STATE_TOL``)
            "state_first": float(apart[:, mixers[0]].max()),
            "state_later": float(apart[:, mixers[1:]].max())
            if mixers[1:] else 0.0,
            "layers_flipped": int((all_slack > 0).sum()),
            "flipped_by_layer": (all_slack > 0).sum(axis=0).tolist(),
            "layers": int(all_slack.size)})
        print(json.dumps(rows[-1]), flush=True)
    del engine
    return rows


def main(argv) -> int:
    spec = json.loads(argv[0]) if argv else {}
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu" and not spec.get("cell"):
        print("no TPU: this measures a bf16 path on the chip", file=sys.stderr)
        return 2
    # {"cell": a rehearsal cell}: the tool's own rehearsal on the CPU
    cell = manifest.load_cell(spec.get("cell",
                                       "nemotron-3-nano-serve.chat-decode"))
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
    if not wanted or "kernel" in wanted:
        kernel_row(model)
    for seed in spec.get("seeds", [1]):
        t0 = time.perf_counter()
        # every leaf in the served type, as ``lib/mode_serve.build`` makes
        # them: the engine casts what it is given, and a reference handed
        # the uncast tree would read another ``A_log``, ``dt_bias`` and
        # router bias than the engine serves (this tool did until PR 40's
        # review: PERF.md section 6)
        dtype = jnp.dtype(engine_keys["dtype"])
        params = jax.block_until_ready(jax.jit(
            lambda k: jax.tree_util.tree_map(
                lambda x: x.astype(dtype), family.init_params(cfg, k)))(
                    jax.random.PRNGKey(seed)))
        if spec.get("conv_taps") == "conv1d":
            # taps of U(-1 / sqrt(K), 1 / sqrt(K)), a depthwise Conv1d's
            # start: the state is then a third of a mixer's output, where
            # the configuration's N(0, 0.02) leave it under a thousandth
            taps = params["ssm_blocks"]["ssm_conv_w"]
            bound = 1.0 / np.sqrt(taps.shape[1])
            params["ssm_blocks"] = dict(
                params["ssm_blocks"], ssm_conv_w=jax.random.uniform(
                    jax.random.PRNGKey(seed + 1), taps.shape, jnp.float32,
                    -bound, bound).astype(taps.dtype))
        rng = np.random.default_rng([seed, 0xC0FFEE])
        prompts = [rng.integers(0, model["vocab_size"], n).astype(np.int32)
                   for n in spec.get("prompts", [128, 256, 512])]
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
                out += measure(name, vcfg, params, model, family, reference,
                               prompts, seed, engine_keys)
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
              f"state {max(r['state_first'] for r in rows):.2e} first "
              f"{max(r['state_later'] for r in rows):.2e} later, "
              f"{sum(r['layers_flipped'] for r in rows)} of "
              f"{sum(r['layers'] for r in rows)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
