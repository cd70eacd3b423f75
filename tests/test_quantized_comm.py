"""Quantized ZeRO collectives (comm/quantized.py).

Discipline mirrors test_onebit.py: (a) the wire format round-trips within its
analytic error bound, (b) each quantized collective matches its full-precision
counterpart within the bound on a real CPU mesh, (c) error feedback keeps the
cumulative drift bounded over repeated steps, and (d) the engine-level knobs
(zero_quantized_weights / zero_quantized_gradients) produce working training
with the advertised >= 3.5x wire-byte reduction in the accounting ledger.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm.quantized import (
    dequantize_blockwise,
    effective_block,
    qall_gather,
    qall_to_all,
    qreduce_scatter,
    quantization_shrinks,
    quantize_blockwise,
    quantized_reshard,
    wire_bytes_per_element,
)
from deepspeed_tpu.comm.runtime_accounting import wire_ledger
from jax import shard_map

W = 8  # conftest forces an 8-device CPU mesh


@pytest.fixture()
def mesh(devices):
    return Mesh(np.asarray(devices), ("dp",))


# --------------------------------------------------------------------- primitives
@pytest.mark.parametrize("bits", [8, 4])
def test_roundtrip_error_bound(rng, bits):
    """Per-block affine round-trip error is at most half a quantization step:
    (max - min) / (2^bits - 1) / 2 per block."""
    x = jnp.asarray(rng.normal(size=(3, 512)), jnp.float32)
    q, s, z = quantize_blockwise(x, bits=bits, block_size=128)
    xh = dequantize_blockwise(q, s, z, bits=bits, block_size=128, orig_size=512)
    err = np.abs(np.asarray(xh) - np.asarray(x))
    # bound per block, broadcast back over block elements
    step = np.asarray(s)  # scale == (max-min)/levels
    bound = np.repeat(step * 0.5 + 1e-7, 128, axis=-1).reshape(err.shape)
    assert (err <= bound).all()


def test_int4_packs_two_per_byte(rng):
    x = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    q8, _, _ = quantize_blockwise(x, bits=8, block_size=64)
    q4, _, _ = quantize_blockwise(x, bits=4, block_size=64)
    assert q8.shape == (256,) and q4.shape == (128,)
    assert q8.dtype == jnp.uint8 and q4.dtype == jnp.uint8


def test_stochastic_rounding_unbiased(rng):
    x = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    outs = []
    for i in range(100):
        q, s, z = quantize_blockwise(x, bits=8, block_size=64, stochastic=True,
                                     rng=jax.random.PRNGKey(i))
        outs.append(np.asarray(dequantize_blockwise(
            q, s, z, bits=8, block_size=64, orig_size=256)))
    bias = np.abs(np.mean(outs, axis=0) - np.asarray(x)).max()
    step = float(np.asarray(s).max())
    assert bias < step  # |E[x_hat] - x| << one quantization step


def test_effective_block_adapts_to_short_rows(rng):
    """A [.., 32] leaf must not pad to 256-blocks (that would INFLATE the
    wire 8x); the effective block shrinks to the row and the shrink predicate
    reports when quantization stops paying."""
    assert effective_block(32, 256) == 32
    assert effective_block(1024, 256) == 256
    assert effective_block(7, 256) == 8
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    q, s, z = quantize_blockwise(x, bits=8, block_size=256)
    assert q.shape == (16, 32) and s.shape == (16, 1)  # one block per row
    assert quantization_shrinks(32, 8, 256, 4)       # fp32: 4 -> 1.25 B/elt
    assert not quantization_shrinks(2, 8, 256, 2)    # bf16 pairs: 2 -> 5 B/elt
    # ratio helper consistency: fp32/int8 at block 256 is the advertised 3.88x
    assert 4 / wire_bytes_per_element(8, 256) == pytest.approx(3.879, abs=1e-2)


# --------------------------------------------------------------------- collectives
def test_qall_gather_matches_all_gather(rng, mesh):
    xs = jnp.asarray(rng.normal(size=(W, 1024)), jnp.float32)

    def body(x):
        return qall_gather(x[0], "dp", axis=0, tiled=True)[None]

    out = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp", None),
                            out_specs=P("dp", None)))(xs)
    ref = np.asarray(xs).reshape(-1)
    got = np.asarray(out)[0]
    # int8 per-block error: half a step of the worst block
    assert np.abs(got - ref).max() < 0.05
    # every rank sees the same gathered vector
    full = jax.jit(shard_map(lambda x: qall_gather(x[0], "dp")[None],
                             mesh=mesh, in_specs=P("dp", None),
                             out_specs=P("dp", None)))(xs)
    assert np.asarray(full).shape == (W, W * 1024)  # each rank: full vector


@pytest.mark.parametrize("mean", [False, True])
def test_qreduce_scatter_matches_reduce_scatter(rng, mesh, mean):
    xs = jnp.asarray(rng.normal(size=(W, 1024)), jnp.float32)
    ref = np.asarray(xs).sum(0)
    if mean:
        ref = ref / W
    ref = ref.reshape(W, -1)  # rank i holds chunk i

    def body(x):
        return qreduce_scatter(x[0], "dp", axis=0, mean=mean)[None]

    out = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp", None),
                            out_specs=P("dp", None)))(xs)
    got = np.asarray(out).reshape(W, -1)
    rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 0.02, rel  # documented int8 tolerance (COMM_COMPRESSION.md)


def test_qreduce_scatter_error_feedback_converges(rng, mesh):
    """Repeated quantized reduction of the SAME vector with the residual
    carried: the time-average converges to the true reduction (error feedback
    keeps the drift bounded instead of letting bias accumulate). int4 to make
    the single-shot error visibly large."""
    xs = jnp.asarray(rng.normal(size=(W, 1024)), jnp.float32)
    ref = np.asarray(xs).sum(0).reshape(W, -1)

    def body(x, r):
        o, nr = qreduce_scatter(x[0], "dp", axis=0, residual=r[0],
                                bits=4, block_size=64)
        return o[None], nr[None]

    f = jax.jit(shard_map(body, mesh=mesh,
                          in_specs=(P("dp", None), P("dp", None)),
                          out_specs=(P("dp", None), P("dp", None))))
    resid = jnp.zeros((W, 1024), jnp.float32)
    acc = np.zeros_like(ref)
    errs = []
    for t in range(1, 16):
        o, resid = f(xs, resid)
        acc += np.asarray(o).reshape(W, -1)
        errs.append(np.abs(acc / t - ref).max())
    assert errs[-1] < errs[0] / 3, errs  # time-average error shrinks
    # residual stays bounded (no blow-up)
    assert np.abs(np.asarray(resid)).max() < 10 * float(np.abs(xs).max())


def test_qall_to_all_matches_all_to_all(rng, mesh):
    xs = jnp.asarray(rng.normal(size=(64, 16, 256)), jnp.float32)

    def bodyq(x):
        return qall_to_all(x, "dp", split_axis=0, concat_axis=1)

    def bodyr(x):
        return jax.lax.all_to_all(x, "dp", split_axis=0, concat_axis=1,
                                  tiled=True)

    spec = P("dp", None, None)
    got = jax.jit(shard_map(bodyq, mesh=mesh, in_specs=spec, out_specs=spec))(xs)
    ref = jax.jit(shard_map(bodyr, mesh=mesh, in_specs=spec, out_specs=spec))(xs)
    assert got.shape == ref.shape
    assert np.abs(np.asarray(got) - np.asarray(ref)).max() < 0.05


def test_quantized_reshard_value_and_straight_through_grad(rng, mesh):
    y = jnp.asarray(rng.normal(size=(64, 512)), jnp.float32)
    with mesh:
        val = jax.jit(lambda v: quantized_reshard(v, P(None, None)))(y)
        g = jax.jit(jax.grad(
            lambda v: quantized_reshard(v, P(None, None)).sum()))(y)
    assert np.abs(np.asarray(val) - np.asarray(y)).max() < 0.05
    np.testing.assert_array_equal(np.asarray(g), np.ones_like(y))  # STE
    # dp-sharded input -> replicated output: the actual ZeRO-3 gather shape
    y_sh = jax.device_put(y, NamedSharding(mesh, P("dp", None)))
    with mesh:
        gathered = jax.jit(lambda v: quantized_reshard(v, P(None, None)))(y_sh)
    assert np.abs(np.asarray(gathered) - np.asarray(y)).max() < 0.05


# --------------------------------------------------------------------- config knobs
def test_zero_config_knobs_parse_and_validate():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig

    z = DeepSpeedZeroConfig(stage=3, zero_quantized_weights=True,
                            zero_quantize_bits=4,
                            zero_quantize_block_size=128)
    assert z.quantized_comm_enabled and z.zero_quantize_bits == 4
    with pytest.raises(Exception):
        DeepSpeedZeroConfig(zero_quantize_bits=5)
    with pytest.raises(Exception):
        DeepSpeedZeroConfig(zero_quantize_block_size=33)
    # prescale_gradients fights block quantization: refused
    with pytest.raises(ValueError):
        DeepSpeedConfig.load({
            "train_micro_batch_size_per_gpu": 1,
            "prescale_gradients": True,
            "zero_optimization": {"stage": 2,
                                  "zero_quantized_gradients": True},
        }, world_size=8)
    # a DeepSpeed-style JSON block parses unchanged
    cfg = DeepSpeedConfig.load({
        "train_micro_batch_size_per_gpu": 1,
        "zero_optimization": {"stage": 3, "zero_quantized_weights": True,
                              "zero_quantized_gradients": True},
    }, world_size=8)
    assert cfg.zero_optimization.zero_quantized_weights


# --------------------------------------------------------------------- engine paths
def _tiny_engine(zero_cfg, gas=1, d_model=256):
    from deepspeed_tpu.models import build_gpt, gpt

    model, _ = build_gpt(gpt.GPTConfig(
        vocab_size=64, n_layer=4, n_head=2, d_model=d_model, max_seq_len=32))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": zero_cfg,
            "steps_per_print": 0,
        })
    return engine


def _batch(rng, gas=1):
    shape = (16, 32) if gas == 1 else (gas, 16, 32)
    return {"input_ids": rng.integers(0, 64, size=shape, dtype=np.int32)}


@pytest.mark.slow
def test_zero3_quantized_weights_trains_with_ratio(rng, devices):
    """The acceptance row: ZeRO-3 with zero_quantized_weights matches the
    full-precision step loss within int8 tolerance and the accounting ledger
    reports >= 3.5x wire-byte reduction on the parameter gathers."""
    dense = _tiny_engine({"stage": 3})
    b = _batch(rng)
    l_dense = float(dense.train_batch(b)["loss"])

    wire_ledger.reset()
    q = _tiny_engine({"stage": 3, "zero_quantized_weights": True})
    l_q = float(q.train_batch(b)["loss"])
    assert np.isfinite(l_q)
    assert abs(l_q - l_dense) / abs(l_dense) < 1e-2  # int8 weight-gather noise
    ratio = wire_ledger.ratio("qgather[zero3]")
    assert ratio >= 3.5, wire_ledger.summary_dict()
    # a few more steps actually train
    for _ in range(3):
        m = q.train_batch(_batch(rng))
    assert np.isfinite(float(m["loss"]))


@pytest.mark.slow
def test_quantized_gradients_match_dense_first_step(rng, devices):
    """zero_quantized_gradients replaces the fp psum with the int8 RS+AG
    exchange; the forward is untouched, so the first step's loss must match
    the dense engine's exactly-ish, and the exchange must show in the ledger."""
    dense = _tiny_engine({"stage": 2})
    b = _batch(rng)
    l_dense = float(dense.train_batch(b)["loss"])

    wire_ledger.reset()
    q = _tiny_engine({"stage": 2, "zero_quantized_gradients": True})
    l_q = float(q.train_batch(b)["loss"])
    assert abs(l_q - l_dense) < 1e-4, (l_q, l_dense)
    assert wire_ledger.ratio("qgrad_reduce_scatter") >= 3.5
    assert wire_ledger.ratio("qgrad_all_gather") >= 3.5
    # grad norms stay in the same ballpark (quantized exchange, not garbage)
    gn_d = dense.get_global_grad_norm()
    gn_q = q.get_global_grad_norm()
    assert abs(gn_q - gn_d) / (gn_d + 1e-9) < 0.1, (gn_q, gn_d)


@pytest.mark.slow
def test_quantized_gradients_error_feedback_residual(rng, devices):
    """Error feedback: the persistent residual exists, is updated, and loss
    keeps decreasing over repeated steps (the EF convergence property at the
    engine level, with gas=2 exercising the residual through the scan)."""
    e = _tiny_engine({"stage": 2, "zero_quantized_gradients": True,
                      "zero_quantize_error_feedback": True,
                      "zero_quantize_stochastic": True}, gas=2)
    assert "qgrad_residual" in e.state
    losses = []
    for _ in range(6):
        losses.append(float(e.train_batch(_batch(rng, gas=2))["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # training converges through the int wire
    resid = np.asarray(e.state["qgrad_residual"])
    assert np.abs(resid).max() > 0  # residual is live, not dead state


def test_qall_gather_untiled_respects_axis(rng, mesh):
    """tiled=False must place the new world dim at ``axis`` exactly like
    lax.all_gather (drop-in parity), not always at the front."""
    xs = jnp.asarray(rng.normal(size=(W, 4, 256)), jnp.float32)

    def bodyq(x):
        return qall_gather(x[0], "dp", axis=1, tiled=False)[None]

    def bodyr(x):
        return jax.lax.all_gather(x[0], "dp", axis=1, tiled=False)[None]

    spec = P("dp", None, None)
    ospec = P("dp", None, None, None)
    got = jax.jit(shard_map(bodyq, mesh=mesh, in_specs=spec,
                            out_specs=ospec))(xs)
    ref = jax.jit(shard_map(bodyr, mesh=mesh, in_specs=spec,
                            out_specs=ospec))(xs)
    assert got.shape == ref.shape == (W, 4, W, 256)
    assert np.abs(np.asarray(got) - np.asarray(ref)).max() < 0.05


def test_overflow_resets_error_feedback_residual(rng, devices):
    """A non-finite residual (the state an fp16 overflow leaves behind) must
    be dropped at the skipped boundary, not carried forward — one bad step
    must not poison the rest of training."""
    from deepspeed_tpu.models import build_gpt, gpt

    model, _ = build_gpt(gpt.GPTConfig(
        vocab_size=64, n_layer=2, n_head=2, d_model=64, max_seq_len=32))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "fp16": {"enabled": True, "loss_scale": 0.0},
            "zero_optimization": {"stage": 2,
                                  "zero_quantized_gradients": True,
                                  "zero_quantize_error_feedback": True},
            "steps_per_print": 0,
        })
    # poison the residual the way an overflow micro-step would
    bad = jnp.full_like(engine.state["qgrad_residual"], jnp.nan)
    engine.state["qgrad_residual"] = jax.device_put(
        bad, engine.state["qgrad_residual"].sharding)
    m1 = engine.train_batch(_batch(rng))
    assert bool(m1["overflow"])  # NaN grads detected, update skipped
    resid = np.asarray(engine.state["qgrad_residual"])
    assert np.isfinite(resid).all()  # residual dropped with the step
    m2 = engine.train_batch(_batch(rng))  # next step recovers
    assert not bool(m2["overflow"]) and np.isfinite(float(m2["loss"]))


def test_gathered_parameters_quantized_host_fetch(rng, devices):
    e = _tiny_engine({"stage": 3, "zero_quantized_weights": True})
    from deepspeed_tpu.runtime.zero.partitioned_params import GatheredParameters

    wire_ledger.reset()
    with GatheredParameters(e, paths=["blocks"], quantized=True) as full:
        key = next(k for k in full if k.endswith("qkv_w") or "w" in k)
        fetched = full[key]
    assert wire_ledger.ratio("qgather[host]") >= 3.5
    ref = np.array(jax.device_get(e.state["params"]["blocks"][key.split(".")[-1]]))
    rel = np.abs(fetched - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 0.02
    with pytest.raises(ValueError):
        GatheredParameters(e, modify=True, quantized=True)


def test_comms_logger_reports_wire_ratio():
    from deepspeed_tpu.comm import comm as c

    logger = c.CommsLogger(enabled=True)
    logger.record("qall_gather[dp]", 4096, wire_bytes=1056)
    logger.record("all_reduce[dp]", 4096)
    out = logger.log_summary()
    assert "wire=1056" in out and "3.88x" in out
    assert "all_reduce" in out
