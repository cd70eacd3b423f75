#!/usr/bin/env python3
"""On the chip, outside any cell: how far the served Falcon-H1 path of
``falcon-h1-34b-serve`` lies from ``reference/falcon_h1_ref.py``, by the two
numbers ``lib/correct.py`` compares and by the distance of every layer's
state and window from the reference's recurrence, with the program's own
programs (``ServingEngine``: the fused prompt program or serial prefill
chunks, pages and the state into the decode slot, then teacher-forced decode
steps through ``paged_decode_gqa`` AND ``ssm_decode`` in every layer) over
prompts of the cell's lengths.

    chiprun -- python3 benchmark/tools/falcon_h1_drift.py '{"seeds": [1]}'

``tools/nemotron_drift.py``'s rows read with this family's own step at these
widths: the honest row; the stream in bf16; the states and windows rounded to
bf16 after every step; each branch of a layer left out (its out-projection's
multiplier 0 on the served side); a multiplier dropped (set to 1 on the
served side: ``key``, ``ssm B``, ``mlp gate``, or ``{"multipliers": [...]}``
by ``gpt.Multipliers``' field names, ``ssm.<i>`` for a segment); a decode
step that decays twice. Each against the unedited logit tolerances and
``STATE_TOL`` (layer 0's states, and the later layers'). Every decoded
position is handed over for the states; the logits are compared where the
cell's check compares them (after the prefill and after 8 decodes). First,
what the seeded draw is judged by (``draw_row``: the scores' standard
deviation, the shares of ``d_ssm``, ``d_att`` and the MLP's delta in a
layer's delta, the state's part of ``y``), and ``ssm_decode`` itself against
the recurrence at these shapes on inputs of order 1 (``kernel_row``). One
JSON line a variant, prompt and seed; a summary last. It refuses to measure
without a TPU. ``{"cell": "tiny-falcon-h1-serve.tiny-closed", "prompts":
[20]}`` rehearses it on the CPU.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import correct, manifest  # noqa: E402

STEPS = correct.DECODE_STEPS
DROPPED = ("key", "ssm.2", "mlp_gate")


def _without(cfg, name, value):
    """``cfg`` with the multiplier ``name`` (a field of ``gpt.Multipliers``,
    ``ssm.<i>`` for a segment of the projection) set to ``value``."""
    m = cfg.multipliers
    if name.startswith("ssm."):
        at = int(name[4:])
        m = dataclasses.replace(m, ssm=tuple(
            value if i == at else v for i, v in enumerate(m.ssm)))
    else:
        m = dataclasses.replace(m, **{name: value})
    return dataclasses.replace(cfg, multipliers=m)


def variants(cfg, dropped=DROPPED):
    """name -> (config, patch): ``patch(monkey)`` plants what the config
    cannot say; ``monkey(obj, name, value)`` sets and remembers."""
    import jax

    from deepspeed_tpu.ops.pallas import ssm_decode as kernel

    decode = kernel.ssm_decode

    def bf16(a):    # not a cast there and back, which the compiler may drop
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def bf16_state(monkey):
        def rounded(*a, **kw):
            y, states, *rest = decode(*a, **kw)
            return (y, bf16(states), *(bf16(w) for w in rest))
        monkey(kernel, "ssm_decode", rounded)

    def decay_once_more(monkey):
        monkey(kernel, "ssm_decode", lambda s, layer, dtx, decay, *a, **kw:
               decode(s, layer, dtx, decay * decay, *a, **kw))

    out = {
        "honest": (cfg, None),
        "stream in bf16": (dataclasses.replace(
            cfg, stream_float32=False, linear_out_float32=False), None),
        "stream float32, linears' outputs rounded": (dataclasses.replace(
            cfg, linear_out_float32=False), None),
        "states and windows in bf16": (cfg, bf16_state),
        "the ssm branch left out": (_without(cfg, "ssm_out", 0.0), None),
        "the attention branch left out": (_without(cfg, "attn_out", 0.0),
                                          None),
        "a decode step decays twice": (cfg, decay_once_more),
    }
    for name in dropped:
        out[f"multiplier {name} dropped"] = (_without(cfg, name, 1.0), None)
    return out


def kernel_row(model, slots=8, live=5):
    """``ssm_decode`` on the chip against the recurrence in ``jax.numpy``, at
    the model's sizes (a slot's block 4 MB, a head's tile [128, 256], 16
    heads a group), with inputs of order 1. An idle slot's state and the
    other layer's must come back bit-equal."""
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
           "live": live, "tile": [P, N], "heads_a_group": H // G,
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


def draw_row(reference, model, params, prompt, seed):
    """What the seeded draw is judged by, from the reference's own forward
    over one prompt: a layer a row of ``falcon_h1_ref.block``'s readings,
    the largest and the smallest over the layers."""
    import numpy as np

    seen = reference.forward(model, params, prompt, seen=True)[3]
    spread, ssm, att, mlp, delta, sc, dx = np.asarray(seen).T
    out = {"variant": "the seeded draw", "seed": seed, "prompt": len(prompt)}
    for name, a in (("scores_std", spread), ("d_ssm_share", ssm / delta),
                    ("d_att_share", att / delta), ("d_mlp_share", mlp / delta),
                    ("state_part_of_y", sc / np.sqrt(sc ** 2 + dx ** 2))):
        out[name] = [round(float(a.min()), 4), round(float(a.max()), 4)]
    print(json.dumps(out), flush=True)
    return out


def measure(name, cfg, params, model, family, reference, prompts, seed,
            engine_keys):
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
    got, handed_over = [], []
    for k in range(STEPS + 1):     # teacher-forced: every variant, one text
        (logits, readings), engine.paged_cache = step(
            engine.params, engine.paged_cache, jnp.asarray(forced[k]),
            jnp.asarray(tables), jnp.asarray(lengths + k * active))
        got.append(np.asarray(logits, np.float32))
        handed_over.append(np.asarray(readings))
        if k < STEPS:
            engine.decode(forced[k].copy(), tables.copy(),
                          lengths + k * active, active, steps=1)
    rows = []
    for j, prompt in enumerate(prompts):
        n = len(prompt)
        ids = np.concatenate([prompt, forced[:, j]])
        handed = {n + k: handed_over[k][j] for k in range(STEPS + 1)}
        x, _, apart = reference.forward(model, params, ids, handed,
                                        distances=True)
        want = np.asarray(reference.head_logits(model, params, x,
                                                [n, n + STEPS]))
        readings = [tuple(map(float, correct.logit_differences(
            got[k][j], want[i]))) for i, k in enumerate((0, STEPS))]
        apart = np.asarray(apart)[sorted(handed)]   # [positions, n_layer]
        slack = reference.state_slack(model, apart)
        rows.append({
            "variant": name, "seed": seed, "prompt": n,
            "rms": [r[0] for r in readings], "max": [r[1] for r in readings],
            # the layers' states: layer 0's, the later layers', each the
            # largest over the positions (``STATE_TOL``)
            "state_first": float(apart[:, 0].max()),
            "state_later": float(apart[:, 1:].max())
            if apart.shape[1] > 1 else 0.0,
            "slack_max": float(slack.max()),
            "passes": bool(
                max(r[0] for r in readings) <= correct.LOGIT_RMS_TOL
                and max(r[1] for r in readings) <= correct.LOGIT_MAX_TOL
                and slack.max() <= reference.CHOICE_SLACK)})
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
                                       "falcon-h1-34b-serve.long-answer"))
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
        # an engine and its programs name each other: until the cycle is
        # collected the last seed's 10.5 GB of weights stay on the chip
        gc.collect()
        t0 = time.perf_counter()
        # every leaf in the served type, as ``lib/mode_serve.build`` makes
        # them: the engine casts what it is given, and the reference reads
        # the tree the engine serves
        dtype = jnp.dtype(engine_keys["dtype"])
        params = jax.block_until_ready(jax.jit(
            lambda k: jax.tree_util.tree_map(
                lambda x: x.astype(dtype), family.init_params(cfg, k)))(
                    jax.random.PRNGKey(seed)))
        rng = np.random.default_rng([seed, 0xC0FFEE])
        prompts = [rng.integers(0, model["vocab_size"], n).astype(np.int32)
                   for n in spec.get("prompts", [128, 256, 512])]
        print(f"seed {seed}: weights in {time.perf_counter() - t0:.1f}s",
              flush=True)
        if not wanted or "draw" in wanted:
            draw_row(reference, model, params, prompts[0], seed)
        for name, (vcfg, patch) in variants(
                cfg, spec.get("multipliers", DROPPED)).items():
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
          f"{correct.LOGIT_RMS_TOL}, {correct.LOGIT_MAX_TOL}), largest "
          f"distance of layer 0's and of the later layers' states (limits "
          f"{reference.STATE_TOL['first']}, {reference.STATE_TOL['later']}), "
          "whether every reading passes")
    for name in dict.fromkeys(r["variant"] for r in out):
        rows = [r for r in out if r["variant"] == name]
        print(f"{name}: {2 * len(rows)} readings, "
              f"rms {max(max(r['rms']) for r in rows):.5f}, "
              f"max {max(max(r['max']) for r in rows):.5f}, "
              f"state {max(r['state_first'] for r in rows):.2e} first "
              f"{max(r['state_later'] for r in rows):.2e} later, "
              f"{'passes' if all(r['passes'] for r in rows) else 'FAILS'}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
