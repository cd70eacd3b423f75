"""runtime.aot: host-side TPU-topology compile reports (the bin/ds_aot core).

These run the REAL XLA TPU compiler on the host (jax.experimental.topologies)
— no accelerator needed — which is exactly the product claim being tested.
"""

import json
import subprocess
import sys

import pytest


@pytest.fixture(scope="module")
def tiny_report():
    from deepspeed_tpu.runtime.aot import train_program_report

    return train_program_report("gpt2-125m", micro_bs=2, seq=256, stage=1)


@pytest.mark.slow
def test_report_fields_and_fit(tiny_report):
    r = tiny_report
    assert r["fits_v5e_hbm"] is True
    pd = r["per_device_bytes"]
    assert pd["peak"] > 0 and pd["arguments"] > 0
    # 125M params: bf16 params + fp32 master + 2x fp32 moments ~ 1.8 GB args
    assert 0.5 * 2**30 < pd["arguments"] < 4 * 2**30
    # analytic (trustworthy) flops: ~6*N*tokens; the raw XLA count is
    # scan-body-once and much lower
    assert r["analytic_flops_per_program"] > 1e11
    assert r["xla_cost_analysis_flops"] > 0
    assert r["topology"] == "v5e:2x2"
    json.dumps(r)


@pytest.mark.slow
def test_k_steps_peak_matches_single_step(tiny_report):
    """train_batches' scan must not grow peak HBM (no cross-step accumulator)
    — the property that made k_steps the dispatch-amortization choice."""
    from deepspeed_tpu.runtime.aot import train_program_report

    r8 = train_program_report("gpt2-125m", micro_bs=2, seq=256, stage=1,
                              k_steps=4)
    assert r8["fits_v5e_hbm"]
    # within 5%: scan bookkeeping only, no extra full-size buffer
    assert r8["per_device_bytes"]["peak"] < \
        tiny_report["per_device_bytes"]["peak"] * 1.05


@pytest.mark.slow
def test_gas_adds_accumulator(tiny_report):
    """gas DOES add a full fp32 grad accumulator across the scan — the
    documented reason bench rows use k_steps instead."""
    from deepspeed_tpu.runtime.aot import train_program_report

    rg = train_program_report("gpt2-125m", micro_bs=2, seq=256, stage=1,
                              gas=4)
    n_param_bytes = 125e6 * 4
    grown = (rg["per_device_bytes"]["peak"]
             - tiny_report["per_device_bytes"]["peak"])
    assert grown > 0.5 * n_param_bytes


@pytest.mark.slow
def test_cli_ds_aot():
    p = subprocess.run(
        [sys.executable, "/root/repo/bin/ds_aot", "--model", "gpt2-125m",
         "--micro-bs", "2", "--seq", "256"],
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-300:]
    rep = json.loads(p.stdout.strip().splitlines()[-1])
    assert rep["fits_v5e_hbm"] is True


@pytest.mark.slow
def test_decode_report():
    from deepspeed_tpu.runtime.aot import decode_program_report

    r = decode_program_report("gpt2-125m", batch=2, prompt=32, gen=8)
    assert r["fits_v5e_hbm"] is True
    # ~2*(non-embedding params) per decode token: 125M total - ~39M embedding
    # tables -> ~172M; require the right order of magnitude
    assert 1e8 < r["flops_per_token"] < 5e8  # from xla count (unrolled-ish here)
    # KV bytes: 2 tensors * L * B * H * S * Dh * 2B
    assert r["kv_cache_bytes"] == 2 * 12 * 2 * 12 * (32 + 8 + 8) * 64 * 2
    json.dumps(r)


@pytest.mark.slow
def test_decode_report_paged_kv8():
    """The serving-shaped paged probe: the quantized pool's bytes are the
    payload actually allocated (int8 + fp32 per-page scales), roughly half
    the dense bf16 pool — what lets the kv-aware ladder admit ~2x."""
    from deepspeed_tpu.runtime.aot import decode_program_report

    rd = decode_program_report("tiny", batch=4, prompt=32, gen=8,
                               page_size=16, paged=True)
    r8 = decode_program_report("tiny", batch=4, prompt=32, gen=8,
                               page_size=16, kv_bits=8)
    assert rd["paged"] and r8["paged"] and r8["kv_bits"] == 8
    assert rd["fits_v5e_hbm"] and r8["fits_v5e_hbm"]
    pages = 4 * (-(-(32 + 8 + 8) // 16)) + 1
    per_tok = 2 * 2 * 4 * 16  # 2 tensors * L * H * Dh (tiny: 2/4/16)
    assert rd["kv_cache_bytes"] == per_tok * pages * 16 * 2  # bf16
    assert r8["kv_cache_bytes"] == (per_tok * pages * 16
                                    + 2 * 2 * 4 * 4 * pages)  # int8+scales
    assert r8["kv_cache_bytes"] < 0.6 * rd["kv_cache_bytes"]
    json.dumps(rd), json.dumps(r8)


@pytest.mark.slow
def test_find_max_batch_ladder():
    from deepspeed_tpu.runtime.aot import find_max_batch

    r = find_max_batch("gpt2-125m", lo=1, hi=4, seq=256, stage=1)
    # tiny model at short seq: everything in [1,4] fits -> ladder tops out
    assert r["max_micro_bs"] == 4
    assert r["report"]["fits_v5e_hbm"] is True
    assert r["trace"][0] == {"micro_bs": 1, "fits": True}


@pytest.mark.slow
def test_sd_report_tiny():
    from deepspeed_tpu.runtime.aot import sd_program_report

    r = sd_program_report(batch=1, latent=16, ddim_steps=2,
                          channels=(32, 64), text_dim=64)
    assert r["fits_v5e_hbm"] is True
    assert r["flops_per_image"] > 0
    json.dumps(r)


@pytest.mark.slow
def test_decode_report_int8_shrinks_arguments():
    from deepspeed_tpu.runtime.aot import decode_program_report

    bf = decode_program_report("gpt2-125m", batch=1, prompt=32, gen=4)
    q8 = decode_program_report("gpt2-125m", batch=1, prompt=32, gen=4,
                               quantize_bits=8)
    assert q8["fits_v5e_hbm"]
    # int8 weight stack (+ scales) must be well under the bf16 arguments
    assert q8["per_device_bytes"]["arguments"] < \
        0.75 * bf["per_device_bytes"]["arguments"]


@pytest.mark.slow
def test_cli_batch_mode(tmp_path):
    specs = tmp_path / "specs.jsonl"
    specs.write_text(
        '{"kind":"train","name":"t","model":"gpt2-125m","micro_bs":2,'
        '"seq":256}\n')
    out = tmp_path / "out.jsonl"
    p = subprocess.run(
        [sys.executable, "/root/repo/bin/ds_aot", "--batch", str(specs),
         "--out", str(out)],
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-300:]
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert rows and rows[0]["name"] == "t" and rows[0]["fits_v5e_hbm"]


def test_fit_verdict_margins():
    """VERDICT r4 next #4: no 'fits' within the fragmentation margin of the
    ceiling without an explicit marginal label."""
    from deepspeed_tpu.runtime.aot import fit_verdict

    v = fit_verdict(10e9, hbm_bytes=15.75e9, margin_bytes=1e9)
    assert v["confidence"] == "fits" and "note" not in v
    v = fit_verdict(15.2e9, hbm_bytes=15.75e9, margin_bytes=1e9)
    assert v["confidence"] == "marginal"
    assert "prediction" in v["note"]
    assert v["headroom_bytes"] == int(15.75e9 - 15.2e9)
    v = fit_verdict(16.5e9, hbm_bytes=15.75e9, margin_bytes=1e9)
    assert v["confidence"] == "oom"


@pytest.mark.slow
def test_infinity_program_report_whole_moments():
    """The streaming schedule's peak is compiler-accounted (residents are
    program ARGUMENTS of the compiled moment), not an arithmetic sum."""
    from deepspeed_tpu.runtime.aot import infinity_program_report

    r = infinity_program_report("gpt2-125m", micro_bs=1, seq=128,
                                keep_layers=2)
    assert set(r["moments"]) == {"head_moment", "layer_bwd_moment"}
    assert all(m["ok"] for m in r["moments"].values())
    assert all(p["ok"] for p in r["programs"].values())
    # the whole-moment peak must dominate every single-program peak, and its
    # arguments must cover the resident activation stack + unit window
    assert r["whole_run_peak_bytes"] >= max(
        p["peak"] for p in r["programs"].values())
    lm = r["moments"]["layer_bwd_moment"]
    assert lm["arguments"] > 4 * r["layer_unit_bytes"]  # keep+2 window + acts
    assert r["fit"]["confidence"] in ("fits", "marginal")
    assert r["per_device_bytes"]["peak"] == r["whole_run_peak_bytes"]


@pytest.mark.slow
def test_find_max_decode_batch_ladder(monkeypatch):
    """Binary search over decode batch with compile-time verdicts (the
    serving-capacity analog of find_max_batch); probes are mocked so the
    search logic is tested exactly."""
    from deepspeed_tpu.runtime import aot

    calls = []

    def fake_report(model, *, batch, **kw):
        calls.append(batch)
        return {"fits_v5e_hbm": batch <= 11, "batch": batch}

    monkeypatch.setattr(aot, "decode_program_report", fake_report)
    r = aot.find_max_decode_batch("gpt2-125m", lo=1, hi=32)
    assert r["max_batch"] == 11
    assert r["report"]["batch"] == 11
    assert all(t["fits"] == (t["batch"] <= 11) for t in r["trace"])

    def never_fits(model, *, batch, **kw):
        return {"fits_v5e_hbm": False}

    monkeypatch.setattr(aot, "decode_program_report", never_fits)
    r = aot.find_max_decode_batch("gpt2-125m", lo=1, hi=8)
    assert r["max_batch"] == 0 and r["report"] is None


@pytest.mark.slow
def test_fused_train_step_matches_engine_semantics():
    """Every AOT report compiles runtime/aot.fused_train_step and presents
    its memory/flops as THE engine program's. Pin the semantics: one step of
    the fused function from the engine's own initial state must produce the
    same loss and the same updated master as engine.train_batch."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models.gpt import GPTConfig
    from deepspeed_tpu.ops.optimizers import get_optimizer
    from deepspeed_tpu.runtime.aot import fused_train_step

    from deepspeed_tpu.runtime.topology import MeshTopology

    model, _ = build_gpt(GPTConfig(vocab_size=128, n_layer=2, n_head=2,
                                   d_model=32, max_seq_len=32))
    # dp=1: an 8-way grad psum reorders float sums, and first-step Adam
    # amplifies that noise to full +/-lr on near-zero-grad leaves — the
    # semantic pin needs bitwise-comparable reductions
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        topology=MeshTopology.create(dp=1, devices=jax.devices()[:1]),
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW",
                              "params": {"lr": 3e-4, "weight_decay": 0.1}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 0},
                "gradient_clipping": 1.0,
                "steps_per_print": 0})
    tmap = jax.tree_util.tree_map
    state0 = {k: tmap(jnp.copy, engine.state[k])
              for k in ("params", "master", "opt")}
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 128, (8, 32), dtype=np.int32)}

    m = engine.train_batch(batch)
    eng_loss = float(m["loss"])

    step = fused_train_step(model, get_optimizer(
        "AdamW", {"lr": 3e-4, "weight_decay": 0.1}))
    _, new_master, _, loss, _ = jax.jit(step)(
        state0["params"], state0["master"], state0["opt"],
        {"input_ids": jnp.asarray(batch["input_ids"])},
        jax.random.PRNGKey(0))
    assert abs(float(loss) - eng_loss) < 1e-3, (float(loss), eng_loss)
    assert (jax.tree_util.tree_structure(new_master)
            == jax.tree_util.tree_structure(engine.state["master"]))
    for a, b in zip(jax.tree_util.tree_leaves(new_master),
                    jax.tree_util.tree_leaves(engine.state["master"]),
                    strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("shape", [(16, 1024, 16, 64), (8, 2048, 16, 128),
                                   (2, 8192, 16, 64)],
                         ids=["gpt2-cell", "pythia-cell", "8k-streams"])
def test_flash_kernels_compile_for_v5e(shape, monkeypatch):
    """The four flash kernels at both train cells' micro-batches, and at the
    8k shape whose dkv streams, through the real Mosaic compiler: resident
    blocks, the scoped VMEM the plan asks for and the tiles' alignment are
    things interpret mode cannot refuse."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "0")
    try:
        td = topologies.get_topology_desc(platform="tpu",
                                          topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(td.devices[0]))
    grads = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    text = grads.lower(x, x, x).compile().as_text()
    for kernel in ("flash_fwd", "flash_bwd_delta", "flash_bwd_dq",
                   "flash_bwd_dkv"):
        assert kernel in text, kernel


@pytest.mark.parametrize("shape", [
    (96, 16, 128, 32, 24, 481, None), (10, 16, 128, 12, 192, 81, None),
    (8, 16, 64, 16, 24, 129, None), (8, 16, 128, 16, 4, 129, 8),
    (8, 16, 64, 16, 4, 129, 4)],
    ids=["batch-decode-cell", "reason-decode-cell", "heads-of-64", "int8",
         "int4-heads-of-64"])
def test_paged_decode_compiles_for_v5e(shape, monkeypatch):
    """``paged_decode`` / ``paged_decode_q`` over a layer of the whole stack,
    at the serve cells' shapes (rows, heads, head size, table width, cache
    layers, pages, pool bits; pages of 64), through the real Mosaic
    compiler: the grid's last axis is a traced bound, the live pages of the
    batch, and the page ids come from the work list in SMEM, which interpret
    mode cannot refuse."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention, paged_held_list, paged_pages_per_step)

    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "0")
    try:
        td = topologies.get_topology_desc(platform="tpu",
                                          topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    B, H, Dh, table, L, P, bits = shape
    ps = 64

    def spec(dims, dtype):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(td.devices[0]))

    pool = spec((L, H, P, ps, Dh // 2 if bits == 4 else Dh),
                jnp.int8 if bits else jnp.bfloat16)
    scales = (spec((L, H, P), jnp.float32),) * 2 if bits else ()

    def layer_of_a_step(q, k, v, lens, tables, layer, *scales):
        ks, vs = scales or (None, None)
        return paged_decode_attention(
            q, k, v, lens, tables, impl="kernel", layer=layer, k_scales=ks,
            v_scales=vs, work=paged_held_list(
                lens, tables, ps, paged_pages_per_step(
                    H, ps, k.shape[-1], k.dtype, table, bool(bits))))

    text = jax.jit(layer_of_a_step).lower(
        spec((B, 1, H, Dh), jnp.bfloat16), pool, pool,
        spec((B,), jnp.int32), spec((B, table), jnp.int32),
        spec((), jnp.int32), *scales).compile().as_text()
    assert ("paged_decode_q" if bits else "paged_decode") in text


# case -> (slots, query heads, key-value heads, table width, cache layers,
# pages, the query's and the pool's type, ring)
GQA_CELL_SHAPES = {
    "full-48": (48, 48, 8, 144, 2, 6913, "bfloat16", "bfloat16", None),
    "window-64": (48, 64, 8, 8, 3, 384, "bfloat16", "bfloat16", (512, 512)),
    "full-48-float32": (48, 48, 8, 144, 2, 6913, "float32", "bfloat16", None),
    "long-answer": (96, 20, 4, 24, 6, 2305, "float32", "bfloat16", None),
    "chat-decode": (512, 32, 2, 16, 1, 8193, "float32", "float32", None),
}


@pytest.mark.parametrize("case", list(GQA_CELL_SHAPES))
def test_paged_decode_gqa_compiles_for_v5e(case, monkeypatch):
    """``paged_decode_gqa`` over a layer of the whole stack at the three
    cells' shapes (heads of 128, pages of 64), through the real Mosaic
    compiler: ``laguna-xs.2-serve.mixed-decode``'s full layer of 48 queries
    over the 6913-page pool, its window layer of 64 over the slots' rings of
    512 rows read as pages, and a float32 query in two passes;
    ``falcon-h1-34b-serve.long-answer``'s 4 key-value heads for 20 over
    tables of 24; ``nemotron-3-nano-serve.chat-decode``'s 2 for 32 over
    float32 pages and 512 slots. Each takes four pages a grid step, the page
    ids from the grouped work list in SMEM. A group of 6 or 10 queries is not
    a whole sublane tile, and the batched products are the MXU's: interpret
    mode refuses neither."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.ops.pallas.decode_attention import (
        gqa_pages_per_step, paged_decode_gqa, paged_work_list)

    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "0")
    try:
        td = topologies.get_topology_desc(platform="tpu",
                                          topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    B, H, G, table, L, P, q_dt, pool_dt, ring = GQA_CELL_SHAPES[case]
    Dh, ps = 128, 64
    group = gqa_pages_per_step(G, ps, Dh, pool_dt, table, ring is not None)
    assert group == 4

    def spec(dims, dtype):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(td.devices[0]))

    def layer_of_a_step(q, k, v, lens, tables, layer):
        cap = lens if ring is None else jnp.minimum(lens, ring[0])
        work = paged_work_list(cap, tables, ps, group)._replace(lens=lens)
        return paged_decode_gqa(q, k, v, lens, tables, impl="kernel",
                                layer=layer, work=work, ring=ring)

    pool = spec((L, G, P, ps, Dh), pool_dt)
    text = jax.jit(layer_of_a_step).lower(
        spec((B, 1, H, Dh), q_dt), pool, pool, spec((B,), jnp.int32),
        spec((B, table), jnp.int32), spec((), jnp.int32)).compile().as_text()
    assert "paged_decode_gqa" in text


# case -> (slots, heads, row width, rank, table width, cache layers, pages,
# ring, a selection's mask, the pages a grid step takes, the kernel's name)
MLA_CELL_SHAPES = {
    "long-decode": (128, 128, 640, 512, 48, 5, 6145, None, False, 8,
                    "paged_decode_mla"),
    "long-notes-select": (32, 128, 640, 512, 272, 2, 8705, None, True, 8,
                          "paged_decode_mla_select"),
    "long-notes-ring": (32, 64, 1152, 1024, 9, 3, 288, (576, 513), False, 3,
                        "paged_decode_mla_ring"),
}


@pytest.mark.parametrize("case", list(MLA_CELL_SHAPES))
def test_paged_decode_mla_compiles_for_v5e(case, monkeypatch):
    """``paged_decode_mla`` over a layer of the whole stack at the two
    latent cells' three shapes (pages of 64, a float32 query over bf16 rows
    in two passes), through the real Mosaic compiler:
    ``deepseek-v2-serve.long-decode``'s 128 heads over rows of 640 and
    tables of 48; ``dots3-note-serve.long-notes``' full layers under a
    selection's mask over tables of 272 and its window layers' 64 heads over
    rows of 1152 in rings of 9 pages. The page ids come from the grouped
    work list in SMEM under a traced grid bound, eight pages a step (three
    over the ring), the chain in sub-tiles of 128 rows."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.ops.pallas.decode_attention import (
        mla_pages_per_step, paged_decode_mla, paged_work_list)

    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "0")
    try:
        td = topologies.get_topology_desc(platform="tpu",
                                          topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    B, H, C, rank, table, L, P, ring, select, group, name = \
        MLA_CELL_SHAPES[case]
    ps = 64
    assert mla_pages_per_step(ps, C, jnp.bfloat16, table,
                              ring is not None) == group

    def spec(dims, dtype):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(td.devices[0]))

    def layer_of_a_step(q, pool, lens, tables, layer, *allowed):
        cap = lens if ring is None else jnp.minimum(lens, ring[0])
        work = paged_work_list(cap, tables, ps, group)._replace(lens=lens)
        return paged_decode_mla(q, pool, lens, tables, rank, C ** -0.5,
                                impl="kernel", layer=layer, work=work,
                                ring=ring, allowed=(allowed or (None,))[0])

    text = jax.jit(layer_of_a_step).lower(
        spec((B, 1, H, C), jnp.float32),
        spec((L, 1, P, ps, C), jnp.bfloat16), spec((B,), jnp.int32),
        spec((B, table), jnp.int32), spec((), jnp.int32),
        *([spec((B, table * ps), jnp.int32)] if select else [])
    ).compile().as_text()
    assert f'"{name}"' in text or name in text


@pytest.mark.parametrize("shape", [
    (6144, 3072, 2048, 256), (6144, 2048, 3072, 256), (768, 5120, 1536, 160),
    (768, 1536, 5120, 160), (384, 2048, 512, 1024), (4096, 512, 2048, 1024),
    (48, 3072, 2048, 256)],
    ids=["chat-decode-up", "chat-decode-down", "long-decode-up",
         "long-decode-down", "mixed-decode-up", "mixed-decode-chunk-down",
         "chat-decode-check-step"])
def test_grouped_dot_compiles_for_v5e(shape, monkeypatch):
    """``grouped_dot`` at the routed cells' products (rows, K, N, the groups
    of the whole stack) through the real Mosaic compiler: a whole matrix a
    copy into VMEM, three of them resident (47 MB of DeepSeek-V2's), the
    limit ``_plan`` asks for, a traced grid bound and copies started by one
    grid step and waited for by a later one: interpret mode refuses none of
    these."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.ops.pallas.grouped_dot import grouped_dot

    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "0")
    try:
        td = topologies.get_topology_desc(platform="tpu",
                                          topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    m, k, n, groups = shape

    def spec(dims, dtype):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(td.devices[0]))

    compiled = jax.jit(lambda a, w, sizes: grouped_dot(
        a, w, sizes, jnp.float32, impl="kernel")).lower(
            spec((m, k), jnp.bfloat16), spec((groups, k, n), jnp.bfloat16),
            spec((groups,), jnp.int32)).compile()
    assert "grouped_dot" in compiled.as_text()
    # the stack is read where it lies: nothing of its size beside it
    assert compiled.memory_analysis().temp_size_in_bytes < k * n * 2


@pytest.mark.parametrize("shape", [(4, 512, 64, 64, 128, 8),
                                   (6, 96, 32, 128, 256, 2)],
                         ids=["chat-decode", "long-answer"])
def test_ssm_decode_compiles_for_v5e(shape, monkeypatch):
    """``ssm_decode`` at the two state-space cells' stacks (layers, slots,
    heads, head_dim, state, groups), the windows shifted in the same call,
    through the real Mosaic compiler: the walk in whole tiles contracts the
    pieces of ``dt x`` over their rows (a transposed left operand), takes a
    pass's heads as ``[rows, 128]`` and stores ``y`` in lane ranges, none of
    which interpret mode refuses; and no copy of the stack beside it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.ops.pallas import ssm_decode as sd

    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "0")
    try:
        td = topologies.get_topology_desc(platform="tpu",
                                          topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    L, S, H, P, N, G = shape
    assert sd._plan(H, P, N, G) is not None
    C = H * P + 2 * G * N

    def spec(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(td.devices[0]))

    compiled = jax.jit(
        lambda s, x, a, b, c, live, w, row: sd.ssm_decode(
            s, jnp.int32(1), x, a, b, c, live, impl="kernel", windows=w,
            new_row=row), donate_argnums=(0, 6)).lower(
                spec((L, S, H, P, N)), spec((S, H, P)), spec((S, H)),
                spec((S, G, N)), spec((S, G, N)), spec((S,), jnp.bool_),
                spec((L, S, 3, C)), spec((S, C))).compile()
    assert "ssm_decode" in compiled.as_text()
    # the states are updated where they lie: nothing of the stack's size,
    # nor of one layer's, beside it
    assert compiled.memory_analysis().temp_size_in_bytes < S * H * P * N * 4


@pytest.mark.parametrize("tokens", [128, 32])
def test_a_chunk_over_pages_compiles_for_v5e(tokens):
    """The chunk program of ``pythia-1.4b-serve`` (``gpt.paged_prefill_step``
    with ``chunk=``) at the cell's widths and pool, two layers deep, through
    the TPU's compiler: the donated pool is updated where it lies, and what
    the program holds beside its arguments is a block of the table's rows,
    not the table's width and not a cache of every layer."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.models import gpt as G

    try:
        td = topologies.get_topology_desc(platform="tpu",
                                          topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(td.devices[0])
    cfg = G.GPTConfig(vocab_size=50304, n_layer=2, n_head=16, d_model=2048,
                      max_seq_len=2048, rotary=True, rotary_pct=0.25,
                      parallel_residual=True, tie_embeddings=False)

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, dtype or a.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: G.init_params(cfg, k), jax.random.PRNGKey(0)), jnp.bfloat16)
    pool = on_chip(jax.eval_shape(
        lambda: G.init_paged_cache(cfg, 481, 64, jnp.bfloat16)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                              sharding=chip)

    def chunk(params, ids, pool, table, length, start, pos):
        return G.paged_prefill_step(cfg, params, ids, pool, table[None],
                                    length[None], start[None],
                                    chunk=(pos, 128))

    compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
        params, i32(1, tokens), pool, i32(32), i32(), i32(), i32()).compile()
    mem = compiled.memory_analysis()
    pool_bytes = 2 * 2 * 16 * 481 * 64 * 128 * 2
    assert mem.alias_size_in_bytes >= pool_bytes        # updated in place
    # the table's whole width of one layer would be 8.4 MB a side, a dense
    # cache of these two layers 33.6 MB
    assert mem.temp_size_in_bytes < 8 << 20, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert "conditional" in text and "while" in text
