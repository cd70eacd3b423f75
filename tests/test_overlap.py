"""Compute/communication overlap for quantized ZeRO collectives.

The overlap schedules must be *free* numerically: the pipelined gather scan
issues the same gathers feeding the same body in the same order (bitwise
equality is asserted engine-level on the 8-device CPU mesh), and the bucketed
gradient exchange is the same ZeRO++ RS+AG math per layer bucket. These tests
pin: the scan restructuring (trip counts), bitwise loss equality pipelined vs
inline at prefetch depth 1 and 2 (per-layer and k=2 windows), per-bucket
error-feedback convergence, the grad-bucket tap against the dense pmean, the
dequant-fused matmul kernel, the exposed-vs-overlapped ledger arithmetic, and
the dslint gate that the hot path stays overlapped.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_gpt, gpt
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.runtime.zero.gather import (
    gather_window,
    overlap_depth,
    zero3_layer_scan,
)


def _scan_lengths(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
            out.extend(_scan_lengths(eqn.params["jaxpr"].jaxpr))
        elif "jaxpr" in eqn.params:
            inner = eqn.params["jaxpr"]
            out.extend(_scan_lengths(getattr(inner, "jaxpr", inner)))
    return out


# --------------------------------------------------------------------- config
def test_overlap_knob_resolution():
    assert DeepSpeedZeroConfig(stage=3).overlap_comm_effective is True
    assert DeepSpeedZeroConfig(
        stage=3, overlap_comm=False).overlap_comm_effective is False
    assert DeepSpeedZeroConfig(
        stage=3, overlap_comm=True).overlap_comm_effective is True
    with gather_window(DeepSpeedZeroConfig(stage=3)):
        assert overlap_depth() == 1
    with gather_window(DeepSpeedZeroConfig(stage=3, overlap_comm=False)):
        assert overlap_depth() == 0
    with gather_window(DeepSpeedZeroConfig(stage=3, overlap_prefetch_depth=3)):
        assert overlap_depth() == 3
    with gather_window(DeepSpeedZeroConfig(stage=2)):
        assert overlap_depth() == 0  # below stage 3: nothing to prefetch
    assert overlap_depth() == 0  # no bound config


# ------------------------------------------------------------- scan structure
def test_pipelined_scan_structure_and_numerics():
    """Depth d turns the length-L layer loop into a length-(L-d) pipelined
    scan plus d drained windows; values and grads match the plain scan."""
    blocks = {"w": jnp.asarray(
        np.random.default_rng(0).normal(size=(8, 4, 4)), jnp.float32)}
    x0 = jnp.ones((4,), jnp.float32)
    spec = {"w": P()}

    def body(c, w):
        return jnp.tanh(w["w"] @ c), None

    def run(cfg):
        def f(blocks):
            with gather_window(cfg):
                return jnp.sum(zero3_layer_scan(body, x0, blocks,
                                                gathered_spec=spec))
        return f

    plain = run(DeepSpeedZeroConfig(stage=3, overlap_comm=False))
    lens_plain = _scan_lengths(jax.make_jaxpr(plain)(blocks))
    assert 8 in lens_plain

    for depth, want in ((1, 7), (2, 6)):
        pf = run(DeepSpeedZeroConfig(stage=3, overlap_prefetch_depth=depth))
        lens = _scan_lengths(jax.make_jaxpr(pf)(blocks))
        assert want in lens and 8 not in lens, (depth, lens)
        v1, g1 = jax.value_and_grad(plain)(blocks)
        v2, g2 = jax.value_and_grad(pf)(blocks)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]),
                                   rtol=1e-5)


def test_max_live_clamps_prefetch_depth():
    """A stage3_max_live_parameters cap that only fits one window must clamp
    the pipeline back to the inline schedule (no silent OOM-by-default)."""
    blocks = {"w": jnp.ones((4, 8, 8), jnp.float32)}  # 64 params/layer
    spec = {"w": P()}

    def body(c, w):
        return c + jnp.sum(w["w"]), None

    def trace(cfg):
        def f(blocks):
            with gather_window(cfg):
                return zero3_layer_scan(body, jnp.float32(0), blocks,
                                        gathered_spec=spec)
        return _scan_lengths(jax.make_jaxpr(f)(blocks))

    # cap = exactly one layer live -> inline length-4 scan, no pipeline
    lens = trace(DeepSpeedZeroConfig(stage=3, stage3_max_live_parameters=64))
    assert 4 in lens and 3 not in lens
    # two layers live -> depth-1 pipeline engages
    lens = trace(DeepSpeedZeroConfig(stage=3, stage3_max_live_parameters=128))
    assert 3 in lens


# --------------------------------------------------------- engine-level bitwise
def _make_engine(zero_cfg, n_layer=4):
    model, _ = build_gpt(gpt.GPTConfig(
        vocab_size=64, n_layer=n_layer, n_head=2, d_model=32, max_seq_len=32))
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": zero_cfg,
        "mesh": {"dp": 8},
        "bf16": {"enabled": False},
        "steps_per_print": 0,
    })
    return engine


def _losses(engine, steps=2):
    ids = np.random.default_rng(0).integers(0, 64, size=(8, 16), dtype=np.int32)
    out = []
    for _ in range(steps):
        m = engine.train_batch({"input_ids": ids})
        out.append((float(m["loss"]), float(m["grad_norm"])))
    return out


@pytest.mark.slow
def test_pipelined_quantized_gathers_bitwise():
    """The acceptance bar: the pipelined quantized-gather FORWARD is bitwise
    identical to the inline schedule (same gathers, same quantize/dequantize,
    same consumption order — only the issue point moves), at prefetch depth 1
    and 2. The backward restructures the loop (scan-carried windows + drained
    epilogue), and XLA fuses the per-layer cotangent matmuls differently
    there, so gradients — and with them the multi-step trajectory — agree to
    float32 resolution rather than bitwise: the same divergence class as
    remat-vs-plain backward (see test_activation_checkpointing's note), not a
    schedule bug. Step-1 loss on identical state is the bitwise invariant."""
    base = {"stage": 3, "zero_quantized_weights": True,
            "stage3_param_persistence_threshold": 0}
    inline = _losses(_make_engine({**base, "overlap_comm": False}), steps=3)
    for depth in (1, 2):
        pf = _losses(_make_engine({**base, "overlap_prefetch_depth": depth}),
                     steps=3)
        assert pf[0][0] == inline[0][0], (depth, pf[0], inline[0])  # bitwise
        # ulp-level backward differences compound through Adam over steps;
        # a real schedule bug would sit orders of magnitude above these
        for (pl, pg), (il, ig) in zip(pf, inline):
            np.testing.assert_allclose(pl, il, rtol=1e-5)
            np.testing.assert_allclose(pg, ig, rtol=1e-3)


@pytest.mark.slow
def test_pipelined_windowed_gathers_bitwise():
    """Same bar with k=2 layer windows (stage3_prefetch_bucket_size):
    pipelining composes with gather windowing."""
    model, _ = build_gpt(gpt.GPTConfig(
        vocab_size=64, n_layer=4, n_head=2, d_model=32, max_seq_len=32))
    params = gpt.init_params(model.gpt_config, jax.random.PRNGKey(0))
    per_layer = sum(int(np.prod(x.shape))
                    for x in jax.tree_util.tree_leaves(params["blocks"])) // 4
    base = {"stage": 3, "zero_quantized_weights": True,
            "stage3_param_persistence_threshold": 0,
            "stage3_prefetch_bucket_size": 2 * per_layer,
            "stage3_max_live_parameters": 10**9}
    inline = _losses(_make_engine({**base, "overlap_comm": False}))
    pf = _losses(_make_engine(base))
    assert pf[0][0] == inline[0][0], (pf[0], inline[0])  # bitwise fwd
    for (pl, pg), (il, ig) in zip(pf, inline):
        np.testing.assert_allclose(pl, il, rtol=1e-5)
        np.testing.assert_allclose(pg, ig, rtol=1e-3)


def test_pipelined_gathers_record_pf_marker():
    from deepspeed_tpu.comm.runtime_accounting import wire_ledger

    before = wire_ledger.snapshot()
    _losses(_make_engine({"stage": 3, "zero_quantized_weights": True,
                          "stage3_param_persistence_threshold": 0}), steps=1)
    delta = wire_ledger.delta(before)
    assert any(k.startswith("qgather[zero3/pf]") for k in delta), delta
    assert not any(k.startswith("qgather[zero3]") for k in delta), delta


# ------------------------------------------------------------- grad buckets
def test_grad_bucket_reduce_matches_pmean():
    """The tap's backward = per-bucket quantized RS+AG mean-reduce: grads
    come out reduced across dp, within int8 block-quantization tolerance of
    the dense pmean."""
    from deepspeed_tpu.comm.quantized import grad_bucket_reduce
    from deepspeed_tpu.runtime.topology import MeshTopology
    from jax import shard_map

    topo = MeshTopology.create(dp=8)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)   # per-rank data
    w = {"a": jnp.asarray(rng.normal(size=(64,)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)}

    def loss(w, xr):
        return jnp.sum(jnp.tanh(xr @ w["a"])) + jnp.sum(w["b"] ** 2)

    def body(w, xs):
        def tapped_loss(q):
            q = grad_bucket_reduce(q, None, None)
            return loss(q, xs)
        return jax.grad(tapped_loss)(w)

    g = shard_map(body, mesh=topo.mesh, in_specs=(P(), P("dp", None)),
                  out_specs=P(), check_vma=False)(w, x)
    g_dense = jax.grad(
        lambda q: float(0) + jnp.mean(
            jax.vmap(lambda xr: loss(q, xr[None]))(x)))(w)
    for k in g:
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(g_dense[k]),
                                   rtol=0.05, atol=0.05)


def test_bucketed_grad_engine_matches_dense():
    """Engine-level: bucketed overlapped qgrads track the dense fp engine's
    loss trajectory (same tolerance class as the monolithic exchange), and
    the per-bucket collectives land in the wire ledger."""
    from deepspeed_tpu.comm.runtime_accounting import wire_ledger

    before = wire_ledger.snapshot()
    dense = _losses(_make_engine({"stage": 2}), steps=4)
    buck = _losses(_make_engine({"stage": 2, "zero_quantized_gradients": True}),
                   steps=4)
    delta = wire_ledger.delta(before)
    assert any(k.startswith("qgrad_bucket_rs") for k in delta), delta
    assert any(k.startswith("qgrad_bucket_ag") for k in delta), delta
    for (dl, _), (bl, _) in zip(dense, buck):
        np.testing.assert_allclose(bl, dl, rtol=0.02)
    assert buck[-1][0] < buck[0][0]  # it trains


def test_bucketed_error_feedback_converges():
    """Per-bucket EF: residual state exists per layer bucket, is finite, and
    the EF run stays at least as close to the dense trajectory as plain
    stochastic-free quantization at the final step."""
    e = _make_engine({"stage": 2, "zero_quantized_gradients": True,
                      "zero_quantize_error_feedback": True})
    assert "qgrad_bucket_residual" in e.state
    losses = _losses(e, steps=5)
    resid = np.asarray(e.state["qgrad_bucket_residual"])
    assert resid.shape[0] == 4  # one bucket per layer
    assert np.isfinite(resid).all()
    assert np.abs(resid).sum() > 0  # EF actually captured quantization error
    assert losses[-1][0] < losses[0][0]


def test_bucket_mode_falls_back_monolithic_when_disabled():
    e = _make_engine({"stage": 2, "zero_quantized_gradients": True,
                      "overlap_comm": False})
    assert e._qgrad_bucket_key is None
    e2 = _make_engine({"stage": 2, "zero_quantized_gradients": True,
                       "zero_quantize_stochastic": True})
    assert e2._qgrad_bucket_key is None  # stochastic has no per-bucket rng


# ------------------------------------------------------------- fused dequant
def test_dequant_matmul_fallback_and_kernel():
    from deepspeed_tpu.comm.quantized import (
        dequantize_blockwise,
        quantize_blockwise,
    )
    from deepspeed_tpu.ops.pallas.dequant_matmul import dequant_matmul

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    q, s, z = quantize_blockwise(w, bits=8, block_size=256)
    ref = x @ dequantize_blockwise(q, s, z, bits=8, orig_size=512)

    out = dequant_matmul(x, q, s, z, orig_size=512)  # CPU: XLA fallback
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)

    old = os.environ.get("DS_TPU_PALLAS_INTERPRET")
    os.environ["DS_TPU_PALLAS_INTERPRET"] = "1"  # Pallas path, interpreted
    try:
        out_k = dequant_matmul(x, q, s, z, orig_size=512)
    finally:
        if old is None:
            os.environ.pop("DS_TPU_PALLAS_INTERPRET", None)
        else:
            os.environ["DS_TPU_PALLAS_INTERPRET"] = old
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_quantized_matmul_reshard_values_and_straight_through():
    from deepspeed_tpu.comm.quantized import (
        dequantize_blockwise,
        quantize_blockwise,
        quantized_matmul_reshard,
    )

    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(4, 6, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128, 384)), jnp.float32)
    q, s, z = quantize_blockwise(w, bits=8, block_size=128)
    w_hat = dequantize_blockwise(q, s, z, bits=8, orig_size=384)
    ref = jnp.einsum("btd,df->btf", h, w_hat)

    out = quantized_matmul_reshard(h, w, P(), bits=8, block_size=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)

    # straight-through: d_w == h^T g exactly (no dequant/quant jacobian),
    # d_h comes from the dequantized weight
    g_h, g_w = jax.grad(
        lambda hh, ww: jnp.sum(
            quantized_matmul_reshard(hh, ww, P(), 8, 128)),
        argnums=(0, 1))(h, w)
    h2 = np.asarray(h).reshape(-1, 128)
    np.testing.assert_allclose(np.asarray(g_w), h2.T @ np.ones((24, 384)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(g_h).reshape(-1, 128), np.ones((24, 384)) @ np.asarray(w_hat).T,
        rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_quantized_head_engine():
    """zero_quantized_head: the LM-head gather goes through the dequant-fused
    matmul — ledger records the qmatmul op, loss stays in the quantized-weight
    tolerance class of the unquantized-head engine, and it trains."""
    from deepspeed_tpu.comm.runtime_accounting import wire_ledger

    base = {"stage": 3, "zero_quantized_weights": True,
            "stage3_param_persistence_threshold": 0}
    plain = _losses(_make_engine(base), steps=3)
    before = wire_ledger.snapshot()
    qhead = _losses(_make_engine({**base, "zero_quantized_head": True}),
                    steps=3)
    delta = wire_ledger.delta(before)
    assert any(k.startswith("qmatmul[lm_head]") for k in delta), delta
    np.testing.assert_allclose(qhead[0][0], plain[0][0], rtol=2e-2)
    assert qhead[-1][0] < qhead[0][0]


# ------------------------------------------------------------ overlap ledger
def test_overlap_accounting_sums_to_step_time():
    """The ledger invariants, on a synthetic device timeline:
    exposed + overlapped == collective, and busy == compute + exposed —
    the accounting always explains where the step time went."""
    from deepspeed_tpu.comm.runtime_accounting import overlap_from_events

    events = [
        # lane 0: 100us compute, an async gather 50-110 (50 hidden, 10 exposed)
        {"ph": "X", "pid": 0, "name": "fusion.1", "ts": 0.0, "dur": 100.0},
        {"ph": "X", "pid": 0, "name": "all-gather-start.1", "ts": 50.0,
         "dur": 60.0},
        {"ph": "X", "pid": 0, "name": "all-gather-done.1", "ts": 110.0,
         "dur": 5.0},  # skipped: the -start carries the transfer
        # lane 1: a bare sync all-reduce, fully exposed
        {"ph": "X", "pid": 1, "name": "all-reduce.2", "ts": 0.0, "dur": 40.0},
        # non-X metadata must be ignored
        {"ph": "M", "pid": 0, "name": "process_name"},
    ]
    st = overlap_from_events(events, n_devices=2)
    assert st.collective_us == pytest.approx(100.0)
    assert st.overlapped_us == pytest.approx(50.0)
    assert st.exposed_us == pytest.approx(50.0)
    assert st.compute_us == pytest.approx(100.0)
    assert st.busy_us == pytest.approx(150.0)
    # the two identities the bench column relies on
    assert st.exposed_us + st.overlapped_us == pytest.approx(st.collective_us)
    assert st.compute_us + st.exposed_us == pytest.approx(st.busy_us)
    assert st.hidden_frac == pytest.approx(0.5)
    d = st.to_dict()
    assert d["hidden_frac"] == pytest.approx(0.5)


def test_wire_ledger_overlap_column_renders():
    from deepspeed_tpu.comm.runtime_accounting import WireLedger

    led = WireLedger()
    led.record("qgather[zero3/pf]", 1000, 250)
    led.set_overlap({"collective_us": 100.0, "exposed_us": 25.0,
                     "overlapped_us": 75.0, "hidden_frac": 0.75})
    out = led.summary()
    assert "overlap (measured)" in out and "75" in out


@pytest.mark.slow
def test_engine_measure_overlap_end_to_end():
    e = _make_engine({"stage": 3, "zero_quantized_weights": True,
                      "stage3_param_persistence_threshold": 0})
    ids = np.random.default_rng(0).integers(0, 64, size=(8, 16), dtype=np.int32)
    e.train_batch({"input_ids": ids})  # compile outside the profile
    st = e.measure_overlap({"input_ids": ids})
    assert st.collective_us > 0
    assert st.exposed_us + st.overlapped_us == pytest.approx(
        st.collective_us, rel=1e-6)
    from deepspeed_tpu.comm.runtime_accounting import wire_ledger

    assert wire_ledger.overlap is not None


# ------------------------------------------------------------------- dslint
def test_dslint_unoverlapped_rule():
    """ERROR on the inline schedules, silent on the overlapped defaults."""
    def rules_fired(zc):
        e = _make_engine(zc)
        ids = np.random.default_rng(0).integers(0, 64, size=(8, 16),
                                                dtype=np.int32)
        rep = e.analyze(batch={"input_ids": ids})
        return [f for f in rep.findings
                if f.rule_id == "collective/unoverlapped-quantized-collective"]

    assert rules_fired({"stage": 3, "zero_quantized_weights": True,
                        "stage3_param_persistence_threshold": 0,
                        "overlap_comm": False})
    assert not rules_fired({"stage": 3, "zero_quantized_weights": True,
                            "stage3_param_persistence_threshold": 0})
    assert rules_fired({"stage": 2, "zero_quantized_gradients": True,
                        "overlap_comm": False})
    assert not rules_fired({"stage": 2, "zero_quantized_gradients": True})
