"""Activation checkpointing: remat correctness, partitioning, RNG tracker.

Mirrors the reference's test_activation_checkpointing.py intent: checkpointed
forward/backward must match the unchckpointed one bit-for-bit (same RNG), and the
config plumbing must set the module globals.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.runtime.activation_checkpointing import (
    CheckpointConfig,
    checkpoint,
    checkpoint_wrapper,
    configure,
    get_rng_tracker,
    is_configured,
    reset,
)


@pytest.fixture(autouse=True)
def _clean():
    reset()
    yield
    reset()


def _mlp(params, x):
    h = jnp.tanh(x @ params["w1"])
    return (h @ params["w2"]).sum()


def _params(rng):
    return {
        "w1": jnp.asarray(rng.normal(size=(16, 32)), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32),
    }


def test_checkpoint_matches_plain(rng):
    # Both grads are compiled: remat determinism is an intra-program XLA
    # guarantee, and the engine only ever remats inside jit. Eager op-by-op
    # dispatch compiles the recomputed forward as separate tiny programs whose
    # fusion/layout choices differ at the last ulp from the plain backward —
    # that divergence is a dispatch artifact, not a remat correctness property
    # (this exact comparison, unjitted, failed from the seed onward).
    params = _params(rng)
    x = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)

    def loss_plain(p):
        return _mlp(p, x)

    def loss_ckpt(p):
        return checkpoint(lambda q: _mlp(q, x), p)

    g1 = jax.jit(jax.grad(loss_plain))(params)
    g2 = jax.jit(jax.grad(loss_ckpt))(params)
    for k in g1:
        np.testing.assert_array_equal(np.asarray(g1[k]), np.asarray(g2[k]))


def test_checkpoint_wrapper_inside_jit_and_scan(rng):
    params = _params(rng)
    xs = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    f = checkpoint_wrapper(lambda p, x: _mlp(p, x))

    @jax.jit
    def loss(p):
        def body(c, x):
            return c + f(p, x), None

        tot, _ = jax.lax.scan(body, 0.0, xs)
        return tot

    g = jax.grad(loss)(params)
    assert np.isfinite(np.asarray(g["w1"])).all()


def test_configure_from_ds_config():
    cfg = deepspeed_tpu.DeepSpeedConfig.load({
        "train_micro_batch_size_per_gpu": 1,
        "activation_checkpointing": {
            "partition_activations": True,
            "cpu_checkpointing": False,
            "number_checkpoints": 4,
        },
    }, world_size=8)
    configure(deepspeed_config=cfg)
    assert is_configured()
    from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as m

    assert m._config.partition_activations is True
    assert m._config.number_checkpoints == 4


def test_configure_explicit_overrides():
    configure(partition_activations=False, num_checkpoints=2, profile=True)
    from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as m

    assert m._config.profile is True
    assert m._config.number_checkpoints == 2


def test_partition_activations_constraint_runs(rng):
    # on the 8-dev CPU mesh with tp>1 the saved residuals get sharded; verify the
    # checkpointed function still produces identical grads
    from deepspeed_tpu.runtime.topology import MeshTopology, mesh_context

    topo = MeshTopology.create(dp=4, tp=2)
    params = _params(rng)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    cfg = CheckpointConfig(partition_activations=True)
    f = checkpoint_wrapper(lambda p: _mlp(p, x), cfg)
    with mesh_context(topo.mesh):
        g1 = jax.jit(jax.grad(f))(params)
        g2 = jax.jit(jax.grad(lambda p: _mlp(p, x)))(params)
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]), rtol=1e-6)


def test_partition_saved_shards_over_the_bound_mesh(devices):
    from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
        _partition_saved)
    from deepspeed_tpu.runtime.topology import (MeshTopology, bound_mesh,
                                                mesh_context)

    topo = MeshTopology.create(dp=4, tp=2)
    x = jnp.ones((8, 16), jnp.float32)
    assert bound_mesh() is None
    with mesh_context(topo.mesh):
        am = bound_mesh()
        assert am is not None and am.shape["tp"] == 2 and am.shape["dp"] == 4
        y = jax.jit(lambda a: _partition_saved(a * 2, ("tp",)))(x)
    assert tuple(y.sharding.spec) == (None, "tp")
    # no mesh bound: nothing to shard over, the activation passes through
    z = jax.jit(lambda a: _partition_saved(a * 2, ("tp",)))(x)
    assert z.sharding.is_fully_replicated
    # and through the wrapper the constraint is IN the program (under a
    # binding that left the abstract mesh empty it silently was not)
    f = checkpoint_wrapper(lambda a: jnp.tanh(a),
                           CheckpointConfig(partition_activations=True))
    with mesh_context(topo.mesh):
        jaxpr = str(jax.make_jaxpr(jax.grad(lambda a: f(a).sum()))(x))
    assert "sharding_constraint" in jaxpr and "'tp'" in jaxpr


def test_rng_tracker_fork_determinism():
    tr = get_rng_tracker()
    tr.reset()
    tr.add("model-parallel-rng", 42)
    k1 = tr.fork()
    k2 = tr.fork()
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    tr.reset()
    tr.add("model-parallel-rng", 42)
    k1b = tr.fork()
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k1b))
    with pytest.raises(Exception):
        tr.add("model-parallel-rng", 1)


def test_engine_configures_activation_checkpointing(rng):
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models.gpt import GPTConfig

    model, _ = build_gpt(GPTConfig(
        vocab_size=64, d_model=32, n_layer=1, n_head=2, max_seq_len=16))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "activation_checkpointing": {"partition_activations": True},
            "steps_per_print": 0,
        })
    assert is_configured()
    del engine
