"""Times, on the chip, the pieces a learned top-k selection over a latent page
pool is made of, at ``dots3-note-serve.long-notes``'s shapes: ``lax.top_k``
of a decode step's and of a prompt chunk's index scores, the selected rows
gathered from the pool row by row, and the index keys gathered page by page
through the block table. Prints one line a piece: ms a call.

    chiprun -- python3 scripts/select_bench.py
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from deepspeed_tpu.models import gpt  # noqa: E402


def timed(fn, *args, n=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def main():
    spec = json.loads(sys.argv[1]) if len(sys.argv) > 1 else {}
    slots, pages, ps, k = 32, 272, 64, spec.get("k", 2048)
    P = spec.get("pool", 8705)
    S = pages * ps
    key = jax.random.PRNGKey(0)
    pool = jax.random.normal(key, (2, 1, P, ps, 640), jnp.bfloat16)
    idx = jax.random.normal(key, (2, 1, P, ps, 128), jnp.bfloat16)
    tables = (jnp.arange(slots * pages, dtype=jnp.int32).reshape(slots, pages)
              % (P - 1)) + 1
    out = {}
    for name, rows in (("decode", slots), ("chunk512", 512),
                       ("chunk1024", 1024)):
        scores = jax.random.normal(jax.random.fold_in(key, rows), (rows, S))
        out[f"top_k_{name}_ms"] = timed(
            jax.jit(lambda s: jax.lax.top_k(s, k)), scores)
        out[f"sort_{name}_ms"] = timed(
            jax.jit(lambda s: jnp.sort(s, axis=-1)), scores)

        out[f"bits_{name}_ms"] = timed(    # the k-th largest by bit counting
            jax.jit(lambda s: gpt._kth_largest(s, k)), scores)
    sel = jax.random.randint(key, (slots, k), 0, S)

    def gather_rows(pool, sel):
        page = jnp.take_along_axis(tables, sel // ps, axis=1)
        return pool[0, 0, page, sel % ps]
    out["gather_rows_ms"] = timed(jax.jit(gather_rows), pool, sel)
    out["gather_rows_sorted_ms"] = timed(jax.jit(gather_rows), pool,
                                         jnp.sort(sel, axis=-1))

    def gather_keys(idx):
        return idx[0, 0][tables]
    out["gather_index_pages_ms"] = timed(jax.jit(gather_keys), idx)

    def scores_of(idx, q, w):
        keys = idx[0, 0][tables].reshape(slots, S, 128)
        s = jnp.einsum("bhd,bsd->bhs", q, keys,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("bhs,bh->bs", jax.nn.relu(s), w)
    q = jax.random.normal(key, (slots, 64, 128), jnp.bfloat16)
    w = jax.random.normal(key, (slots, 64), jnp.float32)
    out["index_scores_ms"] = timed(jax.jit(scores_of), idx, q, w)
    for name, ms in out.items():
        print(f"{name} {ms:.3f}", flush=True)


if __name__ == "__main__":
    main()
