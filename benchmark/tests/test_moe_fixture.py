"""The fixture that shows an architecture enters the harness as files:
``families/gpt_moe.py``, ``reference/gshard_moe_ref.py``,
``configs/tiny-moe-train.json`` and its rehearsal cell
(``tests/test_rehearsal.py`` runs that through ``run.py``). Here: the
reference agrees with ``models/gpt_moe.py`` in float32 and has teeth, the
family builds the program's configuration from the flat ``model`` group, and
the readers take the reference's counts, which count the one expert a token
meets."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import flops, manifest
from benchmark.lib.context import Context
from benchmark.reference import gshard_moe_ref as ref
from deepspeed_tpu.models import gpt_moe

CELL = "tiny-moe-train.tiny-steady"


@pytest.fixture(scope="module")
def fixture():
    cell = manifest.load_cell(CELL)
    config = cell["config_file"]
    family = manifest.family_of(config)
    assert family.__name__ == "benchmark.families.gpt_moe"
    assert manifest.reference_of(config) is ref
    cfg = family.config(config["model"])
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    # biases start at 0 and gains at 1, and at std 0.02 every expert says
    # nearly the same: move them all, or a wrong expert would not show
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    ids = np.random.default_rng(0).integers(
        0, cfg.base.vocab_size, (3, 48), dtype=np.int32)
    return cell, cfg, params, ids


def test_family_splits_the_flat_model_group(fixture):
    cell, cfg, _, _ = fixture
    model = cell["config_file"]["model"]
    assert isinstance(cfg, gpt_moe.GPTMoEConfig)
    assert (cfg.num_experts, cfg.k, cfg.moe_freq) == (4, 1, 2)
    assert cfg.drop_tokens is False and cfg.use_rts is False
    assert cfg.aux_loss_coef == 0.0
    assert cfg.base.d_model == model["d_model"] and cfg.base.n_layer == 2
    assert set(cell["config_file"]["assumed"]) == {
        "num_experts", "moe_freq", "k", "drop_tokens", "use_rts",
        "aux_loss_coef"}


def test_reference_agrees_with_the_program(fixture):
    cell, cfg, params, ids = fixture
    model = cell["config_file"]["model"]
    with jax.default_matmul_precision("highest"):
        want, _ = gpt_moe.forward(cfg, params, jnp.asarray(ids), train=False)
        want_loss, _ = gpt_moe.loss_fn(
            cfg, params, {"input_ids": jnp.asarray(ids)}, train=False)
    want = np.asarray(want)
    got = np.stack([np.asarray(ref.logits(model, params, s)) for s in ids])
    assert np.abs(got - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    assert ref.loss(model, params, ids) == pytest.approx(float(want_loss),
                                                         abs=2e-5)
    some = np.asarray(ref.logits(model, params, ids[0], positions=[5, 47]))
    np.testing.assert_allclose(some, got[0][[5, 47]], rtol=0, atol=1e-6)


def test_a_wrong_expert_shows(fixture):
    """Every token sent to its neighbour's expert, and the dense MLP's
    equations on the expert layer, both disagree by far more than the
    tolerance."""
    cell, _, params, ids = fixture
    model = cell["config_file"]["model"]
    right = np.asarray(ref.logits(model, params, ids[0]))
    moe = params["moe_blocks"]["moe"]
    rolled = dict(params, moe_blocks=dict(params["moe_blocks"], moe=dict(
        moe, experts=jax.tree_util.tree_map(
            lambda a: jnp.roll(a, 1, axis=1), moe["experts"]))))
    assert np.abs(np.asarray(ref.logits(model, rolled, ids[0]))
                  - right).max() > 1e-2
    ungated = dict(params, moe_blocks=dict(params["moe_blocks"], moe=dict(
        moe, gate_w=jnp.zeros_like(moe["gate_w"]))))
    assert np.abs(np.asarray(ref.logits(model, ungated, ids[0]))
                  - right).max() > 1e-2


def test_reference_refuses_what_it_does_not_cover(fixture):
    cell, _, params, ids = fixture
    for wrong in ({"k": 2}, {"drop_tokens": True}, {"aux_loss_coef": 0.01},
                  {"use_residual": True}):
        with pytest.raises(ValueError, match="gshard_moe_ref covers"):
            ref.logits(dict(cell["config_file"]["model"], **wrong), params,
                       ids[0])


def test_counts_are_the_references_own(fixture):
    """``train_mfu_pct`` reads ``ctx.count``: the reference's function where
    it has one (a token meets one expert of four), ``lib/flops``'s else."""
    cell, cfg, _, _ = fixture
    model = cell["config_file"]["model"]

    def ctx_of(c):
        return Context(cell=c, window=None, spans=None, requests=[], facts={},
                       device_kind="cpu", chips=4, setup_s=0.0)

    assert ctx_of(cell).count("train_flops_per_token") \
        is ref.train_flops_per_token
    assert ctx_of(cell).count("decode_step_bytes") is flops.decode_step_bytes
    dense = ctx_of(manifest.load_cell("tiny-train.tiny-steady"))
    assert dense.count("train_flops_per_token") is flops.train_flops_per_token

    shapes = jax.eval_shape(lambda k: gpt_moe.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    size = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa: E731
    experts = size(shapes["moe_blocks"]["moe"]["experts"])
    assert experts == cfg.n_super * 4 * ref.expert_params(model)
    assert ref.matmul_params(model) == (
        size(shapes["blocks"]) + size(shapes["moe_blocks"])
        - experts * 3 // 4 + shapes["wte"].size)
    one = ref.train_flops_per_token(model, 64)
    assert one == 6.0 * ref.matmul_params(model) + 12.0 * 2 * 64 * 64
    all_four = one + 6.0 * 3 * cfg.n_super * ref.expert_params(model)
    assert one < all_four
    # the dense block's count with the gate added: the expert is one MLP
    assert one == flops.train_flops_per_token(model, 64) + 6.0 * 64 * 4
