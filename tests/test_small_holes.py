"""Round-3 'small holes' (VERDICT r2 'next' #9): comm benchmarks + ds_bench,
sparse embedding gradients, the WandB monitor backend, and the diffusers
(Stable-Diffusion) inference skeleton."""

import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------- comm bench
def test_comm_bench_all_ops_produce_sane_records(devices):
    from deepspeed_tpu.benchmarks.communication import OPS, run_collective_bench

    for op in OPS:
        recs = run_collective_bench(op, [1 << 12], dtype=jnp.float32,
                                    trials=2, warmups=1)
        (r,) = recs
        assert r["op"] == op and r["world"] == 8
        assert r["latency_us"] > 0
        assert r["busbw_GBps"] > 0
        if op == "all_reduce":
            # records are rounded to 3 decimals; ratio is approximate
            np.testing.assert_allclose(r["busbw_GBps"] / r["algbw_GBps"],
                                       2 * 7 / 8, rtol=0.1)


def test_comm_bench_collectives_are_correct(devices):
    """The timed programs must compute the real collective, not a no-op."""
    from deepspeed_tpu.benchmarks.communication import _collective_fn
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("bench",))
    x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    xs = jax.device_put(x, NamedSharding(mesh, P("bench")))
    ar = np.asarray(_collective_fn("all_reduce", mesh)(xs))
    want = x.sum(axis=0)
    for row in ar.reshape(8, 128):
        np.testing.assert_allclose(row, want, rtol=1e-6)
    ag = np.asarray(_collective_fn("all_gather", mesh)(xs))
    np.testing.assert_allclose(ag, x.reshape(-1), rtol=1e-6)


def test_ds_bench_cli_json(devices, capsys):
    from deepspeed_tpu.benchmarks.communication import main

    rc = main(["--ops", "all_reduce", "--minsize", "4096", "--maxsize", "4096",
               "--trials", "2", "--json"])
    assert rc == 0
    import json

    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["world"] == 8 and out["results"][0]["op"] == "all_reduce"


# ----------------------------------------------------------------- sparse grads
def test_sparse_tensor_dense_equivalence(rng):
    from deepspeed_tpu.runtime.sparse_tensor import SparseTensor

    V, D = 16, 8
    ids = jnp.asarray(rng.integers(0, V, size=(2, 5)), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(2, 5, D)), jnp.float32)
    st = SparseTensor.from_embedding_grad(ids, rows, V)
    dense = np.zeros((V, D), np.float32)
    for i, r in zip(np.asarray(ids).reshape(-1), np.asarray(rows).reshape(-1, D)):
        dense[i] += r
    np.testing.assert_allclose(np.asarray(st.to_dense()), dense, rtol=1e-6)
    # sparse add == dense add
    st2 = st.add(st)
    np.testing.assert_allclose(np.asarray(st2.to_dense()), 2 * dense, rtol=1e-6)
    assert st.nbytes < V * D * 4  # smaller than the dense gradient


def test_sparse_all_reduce_matches_dense_psum(devices, rng):
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.runtime.sparse_tensor import SparseTensor, sparse_all_reduce

    V, D, n = 16, 4, 8
    mesh = Mesh(np.asarray(devices), ("dp",))
    ids = jnp.asarray(rng.integers(0, V, size=(n, 6)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(n, 6, D)), jnp.float32)

    def body(ids, vals):
        st = SparseTensor(ids.reshape(-1), vals.reshape(-1, D), (V, D))
        return sparse_all_reduce(st, "dp").to_dense()

    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("dp"), P("dp")), out_specs=P(None),
        check_vma=False))
    got = np.asarray(fn(ids, vals))

    dense = np.zeros((V, D), np.float32)
    for r in range(n):
        for i, v in zip(np.asarray(ids[r]), np.asarray(vals[r])):
            dense[i] += v / n
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------- wandb
def test_wandb_monitor_backend(monkeypatch):
    from deepspeed_tpu.monitor.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import MonitorConfig

    calls = {"init": [], "log": []}
    fake = types.ModuleType("wandb")
    fake.init = lambda **kw: calls["init"].append(kw)
    fake.log = lambda d, step=None: calls["log"].append((d, step))
    monkeypatch.setitem(sys.modules, "wandb", fake)

    cfg = MonitorConfig(wandb={"enabled": True, "project": "p", "group": "g"})
    assert cfg.enabled
    mm = MonitorMaster(cfg)
    mm.write_events([("Train/loss", 1.5, 3)])
    assert calls["init"] == [{"entity": None, "group": "g", "project": "p"}]
    assert calls["log"] == [({"Train/loss": 1.5}, 3)]


def test_wandb_missing_package_degrades_gracefully(monkeypatch):
    from deepspeed_tpu.monitor.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import MonitorConfig

    monkeypatch.setitem(sys.modules, "wandb", None)  # import -> ImportError
    mm = MonitorMaster(MonitorConfig(wandb={"enabled": True}))
    mm.write_events([("Train/loss", 1.0, 1)])  # must not raise
    assert mm.backends == []


# ----------------------------------------------------------------- diffusion
@pytest.mark.slow
def test_unet_shapes_and_determinism(rng):
    from deepspeed_tpu.models.diffusion import UNetConfig, apply_unet, init_unet

    cfg = UNetConfig(base_channels=16, channel_mults=(1, 2), text_dim=12,
                     n_head=2, time_dim=32)
    params = init_unet(cfg, jax.random.PRNGKey(0))
    lat = jnp.asarray(rng.normal(size=(2, 8, 8, 4)), jnp.float32)
    t = jnp.asarray([10, 500], jnp.int32)
    txt = jnp.asarray(rng.normal(size=(2, 5, 12)), jnp.float32)
    out = apply_unet(cfg, params, lat, t, txt)
    assert out.shape == (2, 8, 8, 4)
    out2 = apply_unet(cfg, params, lat, t, txt)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # timestep conditioning is live
    out3 = apply_unet(cfg, params, lat, jnp.asarray([11, 501], jnp.int32), txt)
    assert np.abs(np.asarray(out) - np.asarray(out3)).max() > 0
    # text conditioning is live (cross-attention)
    out4 = apply_unet(cfg, params, lat, t, txt + 1.0)
    assert np.abs(np.asarray(out) - np.asarray(out4)).max() > 0


@pytest.mark.slow
def test_stable_diffusion_pipeline_end_to_end(rng):
    from deepspeed_tpu.models.diffusion import (
        StableDiffusionPipeline,
        UNetConfig,
        VAEDecoderConfig,
    )

    pipe = StableDiffusionPipeline.init_random(
        jax.random.PRNGKey(0),
        unet_cfg=UNetConfig(base_channels=16, channel_mults=(1, 2),
                            text_dim=12, n_head=2, time_dim=32),
        vae_cfg=VAEDecoderConfig(base_channels=16, upsamples=2),
        latent_size=8)
    txt = jnp.asarray(rng.normal(size=(1, 5, 12)), jnp.float32)
    un = jnp.zeros_like(txt)
    img = pipe(txt, un, num_steps=4, guidance_scale=3.0)
    assert img.shape == (1, 32, 32, 3)
    assert np.all(np.isfinite(img)) and np.abs(img).max() <= 1.0
    # guidance scale changes the output (classifier-free guidance is live)
    img2 = pipe(txt, un, num_steps=4, guidance_scale=1.0)
    assert np.abs(img - img2).max() > 0


@pytest.mark.slow
def test_engine_emits_full_event_set():
    """The gas-boundary monitor events must include loss/lr/grad_norm (and
    loss_scale under fp16) — the reference's engine.py:2183-2206 set."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models.gpt import GPTConfig

    from deepspeed_tpu.monitor.monitor import CallbackMonitor, MonitorMaster
    from deepspeed_tpu.runtime.config import MonitorConfig

    events = []
    model, _ = build_gpt(GPTConfig(vocab_size=64, d_model=32, n_layer=1,
                                   n_head=2, max_seq_len=16))
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "fp16": {"enabled": True},
        "mesh": {"dp": 8},
        "steps_per_print": 0,
    })
    engine._monitor = MonitorMaster(
        MonitorConfig(), extra_backends=[CallbackMonitor(events.extend)])
    engine.train_batch({"input_ids": np.zeros((8, 16), np.int32)})
    keys = {name for name, _, _ in events}
    assert {"Train/loss", "Train/lr", "Train/grad_norm",
            "Train/loss_scale"} <= keys


def test_wall_clock_breakdown_logs_fused_timers(caplog, monkeypatch):
    import logging

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models.gpt import GPTConfig
    from deepspeed_tpu.utils.logging import logger as ds_logger

    model, _ = build_gpt(GPTConfig(vocab_size=64, d_model=32, n_layer=1,
                                   n_head=2, max_seq_len=16))
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "mesh": {"dp": 8},
        "wall_clock_breakdown": True,
        "steps_per_print": 1,
    })
    monkeypatch.setattr(ds_logger, "propagate", True)
    with caplog.at_level(logging.INFO, logger=ds_logger.name):
        engine.train_batch({"input_ids": np.zeros((8, 16), np.int32)})
    joined = "\n".join(r.message for r in caplog.records)
    assert "train_batch" in joined and "batch_input" in joined
