"""A guard for the path the benchmark's two models share with every later
architecture: ``PRESETS["tiny"]`` in its GPT-2 and its GPT-NeoX form gives,
through ``forward``, ``forward_with_cache`` and ``paged_decode_step``, bitwise
what it gave before ``GPTConfig`` could say another block (PR 32: RMSNorm, a
gated MLP, bias-free linears, norms after the sublayers, a loop over the
stack). The values in ``tests/data/block_guard_<form>.npz`` were recorded from
the parent of that PR by ``python tests/test_block_guard.py --record``; float32
on the CPU, called eagerly, so the only compiled units are the layer scans.
A field added later keeps these bits or says why it may not.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt as G

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FORMS = {
    "gpt2": G.PRESETS["tiny"],
    "neox": dataclasses.replace(G.PRESETS["tiny"], rotary=True,
                                rotary_pct=0.25, parallel_residual=True,
                                tie_embeddings=False,
                                activation="gelu_exact"),
}
PAGE, PAGES, PROMPT, STEPS = 8, 9, 11, 3


def compute(form: str) -> dict:
    cfg = FORMS[form]
    params = G.init_params(cfg, jax.random.PRNGKey(7))
    # the zero biases and unit gains of a fresh tree would hide a dropped
    # term: move every leaf off its initial value
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(8), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.01 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    rng = np.random.default_rng(3)
    ids = rng.integers(0, cfg.vocab_size, (2, PROMPT + STEPS)).astype(np.int32)
    out = {"forward": G.forward(cfg, params, jnp.asarray(ids), train=False)}
    cache = G.init_cache(cfg, 2, 16, jnp.float32)
    logits, cache = G.forward_with_cache(cfg, params,
                                         jnp.asarray(ids[:, :PROMPT]), cache)
    out["prefill"] = logits
    out["prefill_k"] = cache["k"]
    # pages out of order: row 0 holds pages 5, 2, 7, row 1 pages 8, 1, 4
    tables = jnp.asarray([[5, 2, 7], [8, 1, 4]], jnp.int32)
    paged = G.init_paged_cache(cfg, PAGES, PAGE, jnp.float32)
    paged = G.write_prompt_kv_batch(paged, cache, tables,
                                    jnp.full((2,), PROMPT, jnp.int32))
    lengths = jnp.full((2,), PROMPT, jnp.int32)
    for step in range(STEPS):
        logits, paged = G.paged_decode_step(
            cfg, params, jnp.asarray(ids[:, PROMPT + step]), paged, tables,
            lengths + step, impl="gather")
        out[f"decode_{step}"] = logits
    out["pool_k"] = paged["k_pages"]
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_shared_path_gives_the_bits_it_gave(form):
    want = np.load(os.path.join(DATA, f"block_guard_{form}.npz"))
    got = compute(form)
    assert sorted(got) == sorted(want.files)
    for name in want.files:
        assert got[name].dtype == want[name].dtype, name
        assert np.array_equal(got[name], want[name]), (
            f"{form}/{name}: largest difference "
            f"{np.abs(got[name] - want[name]).max()}")


if __name__ == "__main__":
    if sys.argv[1:] != ["--record"]:
        sys.exit("usage: python tests/test_block_guard.py --record")
    os.makedirs(DATA, exist_ok=True)
    for name in FORMS:
        np.savez_compressed(os.path.join(DATA, f"block_guard_{name}.npz"),
                            **compute(name))
        print("recorded", name)
