"""Times ``ops/pallas/ssm_decode`` alone at the two state-space cells' shapes
and slot counts, the walk of :func:`ssm_decode._plan` beside the walk a head
at a time (through ``chiprun``; a TPU only).

    chiprun -- python scripts/ssm_decode_bench.py ['{"shapes": ["nemotron"], "still": true}']

A shape is (layers of the stack, slots, heads, head_dim, state, groups), every
slot live, the convolution windows shifted in the same call. Prints, a shape
and walk: ``_plan``'s answer; the kernel's milliseconds a call, read off a
trace of its own (the ``ssm_decode`` events of the device's operation line:
the XLA operations around the kernel are in ``jit_ms``, the host's clock
around ``reps`` calls, and not in it); us a slot; GB/s and the share of the
v5e's 819 GB/s over ``benchmark/lib/kernel_cost_ssm.py``'s bytes; how far
state and ``y`` of four slots lie from ``ssm_decode_reference``, and whether
the two walks' states, windows and ``y`` are equal bit for bit. ``copy``: a
kernel with the same blocks that only hands the state back, the pace of the
block's two copies. ``"still": true`` names slot 0 in every grid step, so
that no block moves after the first: the walk's arithmetic alone.
"""

import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import kernel_cost_ssm  # noqa: E402
from deepspeed_tpu.ops.pallas import ssm_decode as sd  # noqa: E402

# layers, slots, heads, head_dim, state, groups: the mixers' stacks of
# nemotron-3-nano-serve.chat-decode and falcon-h1-34b-serve.long-answer
SHAPES = {"nemotron": (4, 512, 64, 64, 128, 8),
          "falcon": (6, 96, 32, 128, 256, 2)}
K1 = 3          # rows of the convolution window, conv_kernel - 1
HBM_BYTES_S = 819e9


def _copy_kernel(_rows, n_ref, _layer, s_ref, *rest, **_):
    rest[-3][...] = s_ref[...]
    rest[-2][...] = jnp.zeros_like(rest[-2])
    rest[-1][...] = rest[-5][...]


def kernel_ms(trace_dir, kernel="ssm_decode"):
    """Milliseconds a call of ``kernel`` on the first TPU's operation line
    of the newest trace under ``trace_dir``, and the calls counted."""
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    ns = calls = 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if kernel in ev.name.split(" = ")[0]:
                    ns, calls = ns + ev.duration_ns, calls + 1
    return ns / max(calls, 1) * 1e-6, calls


def main():
    spec = json.loads(sys.argv[1]) if len(sys.argv) > 1 else {}
    if jax.default_backend() != "tpu":
        sys.exit("a TPU only: a CPU's time is not the device's")
    reps = spec.get("reps", 20)
    plan, kernel = sd._plan, sd._kernel
    walks = {"a head at a time": (lambda *a: None, kernel),
             "tiles": (plan, kernel), "copy": (plan, _copy_kernel)}
    for name in spec.get("shapes", list(SHAPES)):
        L, S, H, P, N, G = SHAPES[name]
        C = H * P + 2 * G * N
        key = jax.random.split(jax.random.PRNGKey(spec.get("seed", 0)), 7)
        dtx = jax.random.normal(key[1], (S, H, P))
        decay = jax.random.uniform(key[2], (S, H))
        b, c = (jax.random.normal(k, (S, G, N)) for k in key[3:5])
        row = jax.random.normal(key[6], (S, C))
        active = jnp.ones((S,), bool)
        live = sd.live_slots(active)
        if spec.get("still"):
            live = (jnp.zeros((S,), jnp.int32), live[1])
        cost = kernel_cost_ssm.ssm_decode(S, H, P, N, G, K1)
        few = slice(0, 4)
        want = sd.ssm_decode_reference(
            jax.random.normal(key[0], (L, S, H, P, N))[:, few], 1, dtx[few],
            decay[few], b[few], c[few], active[few])
        want = np.asarray(want[0]), np.asarray(want[1][1])
        first = None
        for walk, (walk_plan, walk_kernel) in walks.items():
            sd._plan, sd._kernel = walk_plan, walk_kernel
            fn = jax.jit(lambda s, w: sd.ssm_decode(
                s, jnp.int32(1), dtx, decay, b, c, active, impl="kernel",
                live=live, windows=w, new_row=row), donate_argnums=(0, 1))
            y, state, windows = fn(
                jax.random.normal(key[0], (L, S, H, P, N)),
                jax.random.normal(key[5], (L, S, K1, C)))
            got = tuple(np.asarray(a) for a in (y[few], state[1, few],
                                                windows[1, few]))
            took = []
            for _ in range(3):
                t = time.perf_counter()
                for _ in range(reps):
                    y, state, windows = fn(state, windows)
                y.block_until_ready()
                took.append((time.perf_counter() - t) / reps)
            trace_dir = os.path.join(REPO, "chiprun_out", ".ssm_decode_trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            with jax.profiler.trace(trace_dir):
                for _ in range(5):
                    y, state, windows = fn(state, windows)
                y.block_until_ready()
            ms, calls = kernel_ms(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
            line = dict(
                shape=name, walk=walk, still=bool(spec.get("still")),
                plan=None if walk == "a head at a time" else plan(H, P, N, G),
                kernel_ms=round(ms, 4), calls=calls,
                us_slot=round(ms * 1e3 / S, 3),
                gbs=round(cost.bytes / ms / 1e6, 1),
                roofline_pct=round(cost.bytes / HBM_BYTES_S / ms * 1e5, 2),
                jit_ms=round(float(np.median(took)) * 1e3, 4))
            if walk != "copy" and not spec.get("still"):
                line["y_err"] = float(np.abs(got[0] - want[0]).max())
                line["state_err"] = float(np.abs(got[1] - want[1]).max())
                if first is None:
                    first = got
                else:
                    line["bit_equal_to_a_head_at_a_time"] = dict(zip(
                        ("y", "state", "window"),
                        (bool((a == o).all()) for a, o in zip(got, first))))
            print(json.dumps(line), flush=True)
            del y, state, windows
        sd._plan, sd._kernel = plan, kernel


if __name__ == "__main__":
    main()
