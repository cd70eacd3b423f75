"""Flash attention kernel vs XLA reference (runs in interpret mode on CPU)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.ops.pallas import flash_attention as fa_mod
from deepspeed_tpu.ops.pallas.flash_attention import _plan, flash_attention


def make_qkv(B=2, T=256, H=2, D=64, dtype=jnp.float32, seed=0, S=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S or T, H, D), dtype)
    v = jax.random.normal(ks[2], (B, S or T, H, D), dtype)
    return q, k, v


def out_and_grads(attn, q, k, v):
    """The output and the three gradients of sum(out ** 2)."""
    return (attn(q, k, v),) + jax.grad(
        lambda a, b, c: jnp.sum(attn(a, b, c) ** 2), argnums=(0, 1, 2))(q, k, v)


def assert_parity(q, k, v, causal, **blocks):
    """Forward and all three gradients against the plain reference."""
    ref = out_and_grads(functools.partial(
        dot_product_attention, causal=causal), q, k, v)
    got = out_and_grads(functools.partial(
        flash_attention, causal=causal, **blocks), q, k, v)
    for a, b, name, tol in zip(ref, got, ("o", "dq", "dk", "dv"),
                               (2e-3, 2e-2, 2e-2, 2e-2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol, err_msg=name)


# [B, T, H, D] of a chip's micro-batch in the benchmark's two train cells
CELLS = {"gpt2": (16, 1024, 16, 64), "pythia": (8, 2048, 16, 128)}


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = make_qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3)


def test_backward_matches_reference():
    q, k, v = make_qkv(T=128)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=64, block_k=64) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ref, g_fl, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2, err_msg=name)


def test_uneven_blocks_rejected():
    q, k, v = make_qkv(T=100)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_k=64)


@pytest.mark.slow
def test_cross_length_causal_offset():
    """kv_len != q_len: causal mask must use absolute positions (review finding)."""
    q, k, v = make_qkv(T=128)
    q_short = q[:, -64:]  # last 64 queries attending over all 128 keys
    ref = dot_product_attention(q_short, k, v, causal=True)
    out = flash_attention(q_short, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3)

    # gradients too
    def loss_ref(q_, k_, v_):
        return jnp.sum(dot_product_attention(q_, k_, v_, causal=True) ** 2)

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True,
                                       block_q=64, block_k=64) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q_short, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q_short, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.slow
def test_stochastic_mode_close_to_exact(dtype):
    """stochastic_mode (parity: ds_transformer_cuda.cpp:63): bf16 MXU operands
    with fp32 accumulation — close to, but not necessarily bitwise equal to,
    the exact fp32-operand kernel; gradients flow through the same flag."""
    q, k, v = make_qkv(T=256, dtype=dtype)
    exact = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    fast = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                           stochastic_mode=True)
    np.testing.assert_allclose(
        np.asarray(exact, np.float32), np.asarray(fast, np.float32),
        rtol=2e-2, atol=2e-2)

    def loss(fn_kwargs):
        def f(q_, k_, v_):
            out = flash_attention(q_, k_, v_, causal=True, block_q=128,
                                  block_k=128, **fn_kwargs)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return f

    g_exact = jax.grad(loss({}), argnums=(0, 1, 2))(q, k, v)
    g_fast = jax.grad(loss({"stochastic_mode": True}),
                      argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_exact, g_fast):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-2)


def test_flash_shard_mapped_on_mesh():
    """Mosaic kernels cannot be GSPMD-auto-partitioned: under a bound mesh the
    dispatcher must shard_map over batch (dp) and heads (tp) — found by the
    pipeline AOT compile row, where the bare call crashes XLA."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops.attention import multihead_attention
    from deepspeed_tpu.runtime.topology import mesh_context

    devs = np.array(jax.devices()).reshape(1, 4, 1, 1, 2)
    mesh = Mesh(devs, ("pp", "dp", "ep", "sp", "tp"))
    q, k, v = make_qkv(B=4, T=128, H=2, D=64)
    ref = dot_product_attention(q, k, v, causal=True)

    with mesh_context(mesh):
        spec = NamedSharding(mesh, P(("dp", "ep"), None, "tp", None))
        qs, ks, vs = (jax.device_put(t, spec) for t in (q, k, v))
        out = jax.jit(lambda a, b, c: multihead_attention(
            a, b, c, causal=True, use_flash=True))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_streamed_multiblock_parity(causal):
    """Many k tiles per q tile (the loop inside a grid step, eight tiles
    resident): fwd and grads must match the XLA reference across them."""
    q, k, v = make_qkv(B=1, T=1024, H=1, D=64)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-3, atol=2e-3)
    g_ref = jax.grad(lambda a, b, c: jnp.sum(
        dot_product_attention(a, b, c, causal=causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, causal=causal, block_q=128,
                        block_k=128) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)


# ------------------------------------------------ resident ranges and the plan
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_head_shapes_parity(cell, causal):
    """The two train cells' head shapes at the tiles they run (each
    kernel's own), two heads: the whole sequence resident, the loop bounds
    and the mask of the diagonal tiles against the reference, forward and
    gradients."""
    _, T, _, D = CELLS[cell]
    q, k, v = make_qkv(B=1, T=T, H=2, D=D)
    plan = _plan("fwd", 2, T, T, D, q.dtype.itemsize)
    assert (plan.resident, plan.rows) == (T, T)
    assert_parity(q, k, v, causal)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_cell_head_shape_at_small_tiles(causal):
    """gpt2's head shape at the 256 x 256 tile: four owned tiles a head, one
    to four key tiles each, the first ones unmasked."""
    q, k, v = make_qkv(B=1, T=1024, H=2, D=64)
    assert_parity(q, k, v, causal, block_q=256, block_k=256)


def test_cross_length_resident():
    """T < S through the resident path: the causal offset moves the loop
    bounds and the mask (rows of a step start at position S - T)."""
    q, k, v = make_qkv(B=1, T=128, S=384, H=2)
    assert _plan("dkv", 2, 128, 384, 64, 4, 64, 64).resident == 128
    assert_parity(q, k, v, True, block_q=64, block_k=64)


@pytest.mark.parametrize("T,S", [(512, 512), (256, 512)],
                         ids=["square", "cross"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_streamed_equals_resident(monkeypatch, causal, T, S):
    """A budget too small for the sequence: the last grid axis runs over
    resident ranges, the state persists across it, hidden ranges are not
    fetched, and the result is the resident one."""
    q, k, v = make_qkv(B=1, T=T, S=S, H=2)
    attn = functools.partial(flash_attention, causal=causal,
                             block_q=64, block_k=64)
    resident = out_and_grads(attn, q, k, v)
    # room for one owned tile and 128 to 256 walked rows of a head
    monkeypatch.setattr(fa_mod, "_VMEM_BLOCK_BYTES", 1 << 20)
    for kernel in ("fwd", "dq", "dkv"):
        plan = _plan(kernel, 2, T, S, 64, 4, 64, 64)
        assert plan.grid[2] > 1 and (plan.rows, plan.heads) == (64, 1), plan
    streamed = out_and_grads(attn, q, k, v)
    for a, b, name in zip(resident, streamed, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    assert_parity(q, k, v, causal, block_q=64, block_k=64)


def test_several_heads_a_step():
    """Small heads: the plan takes several a grid step, and several owned
    tiles of each."""
    q, k, v = make_qkv(B=2, T=256, H=4, D=64)
    for kernel in ("fwd", "dq", "dkv"):
        plan = _plan(kernel, 8, 256, 256, 64, 4, 64, 64)
        assert plan.heads == 8 and plan.rows == 256 and plan.grid == (1, 1, 1)
    assert_parity(q, k, v, True, block_q=64, block_k=64)


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_plan_keeps_the_cells_resident(cell, kernel):
    """At both cells' shapes (bf16) the whole sequence is resident, a step
    owns whole heads and the grid has a few hundred steps at most: the
    streamed one-tile-a-step grid had 4,096 (gpt2) and 8,192 (pythia)."""
    B, T, H, D = CELLS[cell]
    plan = _plan(kernel, B * H, T, T, D, 2)
    tile = 1024 if kernel == "fwd" else 512
    assert (plan.resident, plan.rows, plan.block_q, plan.block_k) == (
        T, T, tile, tile)
    steps = {("gpt2", "fwd"): 64, ("gpt2", "dq"): 128, ("gpt2", "dkv"): 128,
             ("pythia", "fwd"): 128, ("pythia", "dq"): 128,
             ("pythia", "dkv"): 128}[cell, kernel]
    assert plan.grid == (steps, 1, 1)
    assert plan.heads == B * H // steps
    assert plan.vmem_bytes < plan.vmem_limit <= 64 << 20


def test_plan_streams_beyond_the_budget():
    """(2, 8192, 16, 64): the lane-padded lse and delta rows make the walked
    queries of dkv 24 MB a head, so dkv streams them in two ranges, one key
    tile of one head a step; fwd and dq keep the 8 MB of keys resident. At
    32k everything streams."""
    dkv = _plan("dkv", 32, 8192, 8192, 64, 2)
    assert (dkv.resident, dkv.rows, dkv.heads) == (4096, 512, 1)
    assert dkv.grid == (32, 16, 2)
    for kernel in ("fwd", "dq"):
        plan = _plan(kernel, 32, 8192, 8192, 64, 2)
        assert plan.resident == 8192 and plan.grid[2] == 1
    for kernel in ("fwd", "dq", "dkv"):
        plan = _plan(kernel, 8, 32768, 32768, 128, 2)
        assert plan.resident < 32768 and plan.grid[2] == 32768 // plan.resident
        assert (plan.rows, plan.heads) == (
            plan.block_k if kernel == "dkv" else plan.block_q, 1)


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_plan_vmem_under_its_limit(kernel):
    """Whatever the shape, what the plan reckons stays under what it asks
    for, the blocks stay in the budget, and the grid tiles the operands."""
    for bh, t, s, d, itemsize, bq, bk in [
            (256, 1024, 1024, 64, 2, 256, 256), (128, 2048, 2048, 128, 2, 256, 256),
            (32, 8192, 8192, 64, 2, 256, 256), (8, 32768, 32768, 128, 4, 512, 512),
            (4, 128, 384, 64, 4, 64, 64), (6, 1024, 1024, 96, 2, 1024, 512),
            (1, 128, 128, 256, 4, 128, 128)]:
        plan = _plan(kernel, bh, t, s, d, itemsize, bq, bk)
        assert plan.vmem_bytes < plan.vmem_limit
        own, walk = (s, t) if kernel == "dkv" else (t, s)
        assert plan.grid == (bh // plan.heads, own // plan.rows,
                             walk // plan.resident)
        assert bh % plan.heads == own % plan.rows == walk % plan.resident == 0
        assert plan.grid[2] == 1 or (plan.heads == 1 and plan.rows in (bq, bk))
