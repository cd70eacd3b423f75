#!/usr/bin/env python3
"""On the chip, outside any cell: how far the served Brumby path of
``brumby-14b-serve`` lies from ``reference/brumby_ref.py``, by the two numbers
``lib/correct.py`` compares and by the distance of every layer's state from
the reference's own sums, with the program's own programs (``ServingEngine``:
the fused prompt program or serial prefill chunks with the state carried in
the scratch cache, the state into the decode slot, then teacher-forced decode
steps through ``retention_decode`` in every layer) over prompts of the cell's
lengths.

    chiprun -- python3 benchmark/tools/brumby_drift.py '{"seeds": [1], "prompts": [2048]}'

``tools/falcon_h1_drift.py``'s rows read with this family's own step at these
widths: the honest row; the states rounded to bf16 after every decode step;
the prompt's quotient without its normaliser; the gate applied after the
write (every term of the state times its own token's gate: ``k`` times
``sqrt g`` on the served side); QK-norm left out and the rotation left out
(on the reference's side); a query head reading another key-value head's
state (the served ``W_q``'s groups of columns rolled by one). Each against
the unedited logit tolerances and ``STATE_TOL``. Every decoded position is
handed over for the states; the logits are compared where the cell's check
compares them (after the prefill and after 8 decodes). One JSON line a
variant, prompt and seed; a summary last. It refuses to measure without a
TPU. ``{"cell": "tiny-brumby-serve.tiny-closed", "prompts": [20]}`` rehearses
it on the CPU.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import correct, manifest  # noqa: E402

STEPS = correct.DECODE_STEPS


def variants(cfg, reference):
    """name -> (patch, params' edit): ``patch(monkey)`` plants what neither
    config nor weights can say; ``monkey(obj, name, value)`` sets and
    remembers."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import retention
    from deepspeed_tpu.ops.pallas import retention_decode as kernel

    decode, project = kernel.retention_decode, retention._project_in
    m = cfg.retention

    def bf16_state(monkey):
        def rounded(*a, **kw):  # not a cast there and back, which the
            o, states = decode(*a, **kw)        # compiler may drop
            return o, jax.lax.reduce_precision(states, exponent_bits=8,
                                               mantissa_bits=7)
        monkey(kernel, "retention_decode", rounded)

    def gate_after(monkey):
        def scaled(*a, **kw):
            q, k, v, gamma = project(*a, **kw)
            return q, k * jnp.sqrt(jax.nn.sigmoid(gamma))[..., None], v, gamma
        monkey(retention, "_project_in", scaled)

    def on_reference(name, value):
        def patch(monkey):
            monkey(reference, name, value)
            reference._block_at.clear_cache()   # it keeps what it traced
        return patch

    def no_head_norm(x, gain, eps):
        if x.ndim == 3:     # [T, heads, D]: a head's norm
            return x
        return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps) * gain.astype(jnp.float32))

    def other_head(params):
        q = params["blocks"]["retention_q_w"]
        return dict(params, blocks=dict(
            params["blocks"], retention_q_w=jnp.roll(
                q, m.group * m.head_dim, axis=-1)))

    return {
        "honest": (None, None),
        "states in bf16": (bf16_state, None),
        "the prompt's normaliser left out": (
            lambda monkey: monkey(retention, "_quotient",
                                  lambda num, den: num), None),
        "the gate after the write": (gate_after, None),
        "QK-norm left out": (on_reference("rms_norm", no_head_norm), None),
        "the rotation left out": (
            on_reference("rotate", lambda model, x: x), None),
        "a query head reads another state": (None, other_head),
    }


def measure(name, cfg, served, params, model, family, reference, prompts,
            seed, engine_keys):
    """``served``: the tree the engine is given; ``params``: the one the
    reference reads."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    ps = engine_keys["page_size"]
    pages = -(-(max(len(p) for p in prompts) + STEPS + 2) // ps)
    n = len(prompts)
    slots = max(n, correct.SEQUENCES) + 1
    engine = ServingEngine(cfg, served, ServingConfig(
        num_slots=slots, num_pages=n * pages + 1, **engine_keys))
    tables = np.zeros((slots, engine.serving.pages_per_seq), np.int32)
    for j, prompt in enumerate(prompts):
        tables[j, :pages] = 1 + j * pages + np.arange(pages)
        engine.prefill(j, prompt, tables[j])
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
        apart = np.asarray(apart)                   # [positions, n_layer]
        rows.append({
            "variant": name, "seed": seed, "prompt": n,
            "rms": [r[0] for r in readings], "max": [r[1] for r in readings],
            "state_by_layer": [float(a) for a in apart.max(axis=0)],
            "state": float(apart.max()),
            "passes": bool(
                max(r[0] for r in readings) <= correct.LOGIT_RMS_TOL
                and max(r[1] for r in readings) <= correct.LOGIT_MAX_TOL
                and apart.max() <= reference.STATE_TOL)})
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
    cell = manifest.load_cell(spec.get("cell", "brumby-14b-serve.many-shot"))
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
        gc.collect()    # an engine and its programs name each other
        t0 = time.perf_counter()
        dtype = jnp.dtype(engine_keys["dtype"])
        params = jax.block_until_ready(jax.jit(
            lambda k: jax.tree_util.tree_map(
                lambda x: x.astype(dtype), family.init_params(cfg, k)))(
                    jax.random.PRNGKey(seed)))
        rng = np.random.default_rng([seed, 0xC0FFEE])
        prompts = [rng.integers(0, model["vocab_size"], n).astype(np.int32)
                   for n in spec.get("prompts", [2048])]
        print(f"seed {seed}: weights in {time.perf_counter() - t0:.1f}s",
              flush=True)
        for name, (patch, edit) in variants(cfg, reference).items():
            if wanted and name not in wanted:
                continue
            undo = []

            def monkey(obj, attr, value):
                undo.append((obj, attr, getattr(obj, attr)))
                setattr(obj, attr, value)
            if patch:
                patch(monkey)
            try:
                out += measure(name, cfg, edit(params) if edit else params,
                               params, model, family, reference, prompts,
                               seed, engine_keys)
            finally:
                for obj, attr, old in undo:
                    setattr(obj, attr, old)
                reference._block_at.clear_cache()
        del params
    print("== summary: variant, readings, largest rms and max diff (limits "
          f"{correct.LOGIT_RMS_TOL}, {correct.LOGIT_MAX_TOL}), largest "
          f"distance of a layer's state (limit {reference.STATE_TOL}), "
          "whether every reading passes")
    for name in dict.fromkeys(r["variant"] for r in out):
        rows = [r for r in out if r["variant"] == name]
        print(f"{name}: {2 * len(rows)} readings, "
              f"rms {max(max(r['rms']) for r in rows):.5f}, "
              f"max {max(max(r['max']) for r in rows):.5f}, "
              f"state {max(r['state'] for r in rows):.2e} "
              f"(smallest {min(r['state'] for r in rows):.2e}), "
              f"{'passes' if all(r['passes'] for r in rows) else 'FAILS'}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
