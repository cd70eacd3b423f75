"""1-bit optimizers + compressed collectives.

Mirrors the reference's tests/onebit/ intent: the compressed allreduce must be an
unbiased-ish error-compensated approximation (error feedback keeps the cumulative
drift bounded), and 1-bit Adam must track dense Adam's loss trajectory through the
warmup→compressed switch.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.runtime.comm.compressed import (
    compressed_allreduce,
    compression_error_shapes,
    pack_signs,
    unpack_signs,
)
from deepspeed_tpu.runtime.topology import MeshTopology


def test_pack_unpack_roundtrip(rng):
    x = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    packed = pack_signs(x)
    assert packed.shape == (8,) and packed.dtype == jnp.uint8
    signs = unpack_signs(packed, 64)
    np.testing.assert_array_equal(np.asarray(signs), np.sign(np.asarray(x)) + (np.asarray(x) == 0))


def _run_compressed(xs, werr, serr, mesh, world):
    """xs: [W, n] per-rank vectors."""
    def _body(x, w, s):
        r, w2, s2 = compressed_allreduce(x[0], w[0], s[0], "dp")
        return r, w2[None, :], s2[None, :]

    f = shard_map(
        _body,
        mesh=mesh,
        in_specs=(P("dp", None), P("dp", None), P("dp", None)),
        out_specs=(P(), P("dp", None), P("dp", None)),
        check_vma=False)

    # adapt out shapes: result replicated, errors per-rank
    def g(x, w, s):
        r, w2, s2 = f(x, w, s)
        return r, w2, s2

    return jax.jit(g)(xs, werr, serr)


@pytest.mark.slow
def test_compressed_allreduce_error_feedback_bounded(rng):
    world, n = 4, 256
    topo = MeshTopology.create(dp=world, devices=jax.devices()[:world])
    wn, sn = compression_error_shapes(n, world)
    xs = jnp.asarray(rng.normal(size=(world, n)), jnp.float32)
    werr = jnp.zeros((world, wn))
    serr = jnp.zeros((world, sn // 1))[:, : sn]
    serr = jnp.zeros((world, sn))
    true_mean = np.asarray(xs).mean(axis=0)

    # repeated allreduce of the SAME vectors: error feedback must make the
    # time-average of outputs converge to the true mean (the defining property
    # of error-compensated compression)
    acc = np.zeros(n)
    steps = 60
    for i in range(steps):
        out, w2, s2 = _run_compressed(xs, werr, serr, topo.mesh, world)
        r = np.asarray(out)
        # shard_map out P() gives result from averaging chunks of all server ranks
        acc += r
        werr, serr = w2, s2
    avg = acc / steps
    err0 = np.linalg.norm(np.asarray(_run_compressed(
        xs, jnp.zeros_like(werr), jnp.zeros_like(serr), topo.mesh, world)[0]) - true_mean)
    err_avg = np.linalg.norm(avg - true_mean)
    # time-averaged output is much closer to the truth than any single compressed step
    assert err_avg < err0 * 0.2, (err_avg, err0)


def test_compressed_allreduce_identical_inputs_sign_exact(rng):
    # all ranks hold c * ones: sign compression is EXACT for constant vectors
    world, n = 4, 64
    topo = MeshTopology.create(dp=world, devices=jax.devices()[:world])
    xs = jnp.ones((world, n), jnp.float32) * 0.5
    werr = jnp.zeros((world, n))
    serr = jnp.zeros((world, n // world))
    out, _, _ = _run_compressed(xs, werr, serr, topo.mesh, world)
    np.testing.assert_allclose(np.asarray(out), 0.5 * np.ones(n), rtol=1e-6)


def _tiny_engine(opt_type, opt_params, gas=1):
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models.gpt import GPTConfig

    model, cfg = build_gpt(GPTConfig(
        vocab_size=128, d_model=32, n_layer=2, n_head=2, max_seq_len=32))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": opt_type, "params": opt_params},
            "steps_per_print": 0,
        })
    return engine, cfg


def _batches(cfg, n, bs, seq=16, gas=1, seed=0):
    r = np.random.default_rng(seed)
    shape = (bs, seq) if gas == 1 else (gas, bs, seq)
    return [{"input_ids": r.integers(0, cfg.vocab_size, size=shape, dtype=np.int32)}
            for _ in range(n)]


@pytest.mark.parametrize("opt_type", ["OneBitAdam", "ZeroOneAdam", "OneBitLamb"])
@pytest.mark.slow
def test_onebit_trains_through_switch(opt_type):
    engine, cfg = _tiny_engine(opt_type, {
        "lr": 1e-3, "freeze_step": 3, "var_freeze_step": 5})
    # batch = micro_bs * dp(8) = 16; train on ONE repeated batch so the loss
    # must fall if the compressed stage is actually optimizing
    (batch,) = _batches(cfg, 1, 16)
    losses = []
    for _ in range(10):
        m = engine.train_batch(batch)
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
    # crossed freeze_step=3 into the compressed stage and kept training
    assert engine.global_steps == 10
    assert engine._onebit._compressed_jit is not None
    assert losses[-1] < losses[2], losses  # improving after the switch


@pytest.mark.slow
def test_onebit_matches_dense_during_warmup():
    engine_1b, cfg = _tiny_engine("OneBitAdam", {"lr": 1e-3, "freeze_step": 100})
    engine_d, _ = _tiny_engine("Adam", {"lr": 1e-3})
    for b in _batches(cfg, 3, 16):
        m1 = engine_1b.train_batch(b)
        m2 = engine_d.train_batch(b)
        # warmup phase IS dense adam (adam_w_mode differences aside: wd=0)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)


def test_onebit_rejects_zero2_and_fp16():
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models.gpt import GPTConfig

    model, _ = build_gpt(GPTConfig(
        vocab_size=64, d_model=32, n_layer=1, n_head=2, max_seq_len=16))
    with pytest.raises(ValueError, match="ZeRO"):
        deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "OneBitAdam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
        })
    with pytest.raises(RuntimeError, match="train_batch"):
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "OneBitAdam", "params": {"lr": 1e-3}},
        })
        engine.forward({"input_ids": np.zeros((8, 16), np.int32)})


@pytest.mark.slow
def test_onebit_bf16_updates_master():
    """Compressed stage must step the fp32 master, not the bf16 params."""
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models.gpt import GPTConfig

    model, cfg = build_gpt(GPTConfig(
        vocab_size=128, d_model=32, n_layer=2, n_head=2, max_seq_len=32))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "bf16": {"enabled": True},
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": 1e-3, "freeze_step": 1}},
            "steps_per_print": 0,
        })
    (batch,) = _batches(cfg, 1, 16)
    engine.train_batch(batch)  # warmup step
    master_before = np.asarray(engine.state["master"]["wte"], np.float32).copy()
    engine.train_batch(batch)  # compressed step
    master_after = np.asarray(engine.state["master"]["wte"], np.float32)
    assert not np.array_equal(master_before, master_after)
    # params follow the master (bf16 rounding of it)
    np.testing.assert_allclose(
        np.asarray(engine.state["params"]["wte"], np.float32), master_after,
        rtol=1e-2)


@pytest.mark.slow
def test_onebit_with_grad_accumulation():
    engine, cfg = _tiny_engine("OneBitAdam", {"lr": 1e-3, "freeze_step": 2}, gas=2)
    for b in _batches(cfg, 4, 16, gas=2):
        m = engine.train_batch(b)
        assert np.isfinite(float(m["loss"]))
    assert engine.global_steps == 4
