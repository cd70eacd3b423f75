"""The plain reference agrees with ``models/gpt.py`` on the ``tiny`` preset on
the CPU, for the GPT-2 variant (learned positions, tied head, tanh gelu) and
the GPT-NeoX variant (rotary on 25% of the head, parallel residual, untied
head, erf gelu). Both sides in float32 at the highest matmul precision, so the
tolerance is float32 rounding over two blocks."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import gpt_ref
from deepspeed_tpu.models import gpt

VARIANTS = {
    "gpt2": {},
    "neox": dict(rotary=True, rotary_pct=0.25, parallel_residual=True,
                 tie_embeddings=False, activation="gelu_exact"),
}


def setup(variant):
    cfg = dataclasses.replace(gpt.PRESETS["tiny"], **VARIANTS[variant])
    params = gpt.init_params(cfg, jax.random.PRNGKey(3))
    # biases and layer-norm gains start at 0 and 1: move them, or a swapped
    # bias would not show
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
             if isinstance(getattr(cfg, f.name), (int, float, bool, str))}
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (3, 48),
                                            dtype=np.int32)
    return cfg, params, model, ids


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_logits_and_loss_agree(variant):
    cfg, params, model, ids = setup(variant)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(gpt.forward(cfg, params, jnp.asarray(ids),
                                      train=False))
        want_loss, _ = gpt.loss_fn(cfg, params, {"input_ids": jnp.asarray(ids)},
                                   train=False)
    got = np.stack([np.asarray(gpt_ref.logits(model, params, s)) for s in ids])
    assert np.abs(got - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    assert gpt_ref.loss(model, params, ids) == pytest.approx(
        float(want_loss), abs=2e-5)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_positions_pick_rows(variant):
    _, params, model, ids = setup(variant)
    full = np.asarray(gpt_ref.logits(model, params, ids[0]))
    some = np.asarray(gpt_ref.logits(model, params, ids[0], positions=[5, 47]))
    np.testing.assert_allclose(some, full[[5, 47]], rtol=0, atol=1e-6)


def test_a_wrong_block_shows():
    """The comparison has teeth: the NeoX weights through the GPT-2 block
    equations (sequential residual) disagree by far more than the tolerance."""
    cfg, params, model, ids = setup("neox")
    wrong = dict(model, parallel_residual=False)
    a = np.asarray(gpt_ref.logits(model, params, ids[0]))
    b = np.asarray(gpt_ref.logits(wrong, params, ids[0]))
    assert np.abs(a - b).max() > 1e-2
