"""Times ``ops/pallas/grouped_dot`` beside ``jax.lax.ragged_dot`` at the
grouped products the routed serve cells run (through ``chiprun``; a TPU only).

    chiprun -- python scripts/grouped_dot_bench.py ['{"shapes": ["nemotron"], "ahead": [1, 2]}']

A shape is (rows, K, N, groups of the stack, groups that hold rows, share of
the rows that met a held expert, pieces a row): the (token, expert)
assignments are dealt over one layer's experts by a seeded Dirichlet draw
(uneven, as seeded routers are); the other layers' groups are empty, the
rows past the held ones belong to none. Prints, a shape and
implementation, the median milliseconds of ``reps`` calls by the host's
clock around a ``block_until_ready`` of the last (calls of one program
queue behind each other: the device's time where it is over 0.1 ms), the
GB/s over the touched matrices' bytes, and how far the kernel's rows lie
from ``ragged_dot``'s. ``ahead``: ``grouped_dot._AHEAD`` values to try.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.ops.pallas import grouped_dot as gd  # noqa: E402

# name: (rows, K, N, stack's groups, one layer's groups, held share of the
# rows, pieces a row): decode = slots x experts a token, chunk = its tokens x
# experts a token; Nemotron's rows come in two bf16 pieces (moe_two_pass)
SHAPES = {
    "nemotron.decode.up": (6144, 3072, 2048, 256, 64, 0.53, 2),
    "nemotron.decode.down": (6144, 2048, 3072, 256, 64, 0.53, 2),
    "nemotron.chunk256.up": (3072, 3072, 2048, 256, 64, 0.53, 2),
    # an expert's matrices as published and at whole lanes, without
    # ``GPTConfig.moe_width`` / ``moe_rows``'s padding to 3072 x 2048
    "nemotron.unpadded.2688x1856.up": (6144, 2688, 1856, 256, 64, 0.53, 2),
    "nemotron.unpadded.2688x1920.up": (6144, 2688, 1920, 256, 64, 0.53, 2),
    "nemotron.unpadded.1920x2688.down": (6144, 1920, 2688, 256, 64, 0.53, 2),
    "deepseek.decode.up": (768, 5120, 1536, 160, 40, 0.25, 1),
    "deepseek.decode.down": (768, 1536, 5120, 160, 40, 0.25, 1),
    "deepseek.chunk512.up": (3072, 5120, 1536, 160, 40, 0.25, 1),
    "laguna.decode.up": (384, 2048, 512, 1024, 256, 1.0, 1),
    "laguna.decode.down": (384, 512, 2048, 1024, 256, 1.0, 1),
    "laguna.chunk512.up": (4096, 2048, 512, 1024, 256, 1.0, 1),
    "laguna.chunk512.down": (4096, 512, 2048, 1024, 256, 1.0, 1),
}


def sizes_of(rng, rows, stack, layer_groups, held, pieces):
    p = rng.dirichlet(np.full(layer_groups, 2.0))
    one = rng.multinomial(int(rows * held) // pieces, p) * pieces
    sizes = np.zeros(stack, np.int32)
    sizes[layer_groups:2 * layer_groups] = one              # layer 1 of the stack
    return sizes


def timed(fn, args, reps):
    out = fn(*args)
    out.block_until_ready()
    took = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        out.block_until_ready()
        took.append((time.perf_counter() - t) / reps)
    return out, float(np.median(took)) * 1e3


def main():
    spec = json.loads(sys.argv[1]) if len(sys.argv) > 1 else {}
    if jax.default_backend() != "tpu":
        sys.exit("a TPU only: a CPU's time is not the device's")
    rng = np.random.default_rng(spec.get("seed", 0))
    reps = spec.get("reps", 20)
    for name, (m, k, n, stack, layer_groups, held, pieces) in SHAPES.items():
        if not any(s in name for s in spec.get("shapes", [""])):
            continue
        sizes = sizes_of(rng, m, stack, layer_groups, held, pieces)
        a = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
        w = jax.random.normal(jax.random.key(1), (stack, k, n), jnp.bfloat16)
        touched = int((sizes > 0).sum()) * k * n * 2
        line = {"shape": name, "rows_held": int(sizes.sum()),
                "groups_hit": int((sizes > 0).sum()),
                "load_max": int(sizes.max()), "mb": round(touched / 1e6, 1)}
        want, ms = timed(jax.jit(lambda a, w, s: gd.grouped_dot(
            a, w, s, jnp.float32, impl="ragged")), (a, w, sizes), reps)
        line["ragged_ms"] = round(ms, 4)
        line["ragged_gbs"] = round(touched / ms / 1e6, 1)
        if gd._plan(m, k, n, a.dtype, w.dtype, jnp.float32) is None:
            print(json.dumps(dict(line, kernel="no tiles")), flush=True)
            continue
        for ahead in spec.get("ahead", [gd._AHEAD]):
            gd._AHEAD = ahead
            got, ms = timed(jax.jit(lambda a, w, s: gd.grouped_dot(
                a, w, s, jnp.float32, impl="kernel")), (a, w, sizes), reps)
            rows = int(sizes.sum())
            line[f"kernel_ms.{ahead}"] = round(ms, 4)
            line[f"kernel_gbs.{ahead}"] = round(touched / ms / 1e6, 1)
            line[f"max_diff.{ahead}"] = float(
                jnp.abs(got[:rows] - want[:rows]).max())
        print(json.dumps(line), flush=True)
        del a, w


if __name__ == "__main__":
    main()
