#!/usr/bin/env python
"""Benchmark harness: prints ONE JSON line {"metric","value","unit","vs_baseline",...}.

An ORCHESTRATOR that never touches a JAX backend itself (a chip belongs to
one process; a parent that held it would starve its workers):

1. probe for the TPU once, in a subprocess with a hard timeout. No TPU means
   exit non-zero — a device benchmark never falls back to the CPU;
2. run each benchmark config in its own worker subprocess (``--worker``)
   under a timeout, retrying once. A failed row or a failed kernel smoke is
   recorded, the sweep goes on, and the exit code is non-zero;
3. ``--cpu`` runs the CPU-host correctness rows instead (counts and outcomes
   only: no row of that sweep carries ``tokens_per_sec_chip`` or ``mfu``).

Sweep (BASELINE.json matrix): ZeRO-1/2/3 training MFU on the flagship GPT,
plus decode latency and serving rows. The headline metric is the best training
config's tokens/sec/chip; ``vs_baseline`` is its MFU / 0.45 (the reference
stack's at-scale MFU bar — BASELINE.md north star).
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

PROBE_TIMEOUT = 240
WORKER_TIMEOUT = int(os.environ.get("BENCH_WORKER_TIMEOUT", "1200"))
# append-only log of every completed row, written the moment it finishes (a
# sweep killed mid-run leaves what it measured). Output only: nothing here
# ever reads it back.
PARTIAL_PATH = os.environ.get("BENCH_PARTIAL_PATH",
                              os.path.join(REPO, "bench_partial.jsonl"))
# global sweep budget: when set, each row's worker timeout is clamped to the
# remaining budget and rows that no longer fit are SKIPPED with a recorded
# reason instead of letting an external `timeout` kill the whole artifact; a
# SIGTERM mid-row still flushes a final summary of everything measured so far.
TOTAL_BUDGET = int(os.environ.get("BENCH_TOTAL_BUDGET", "0"))  # seconds, 0=off
ROW_RESERVE = int(os.environ.get("BENCH_ROW_RESERVE", "45"))


def _persist_row(row: dict) -> None:
    try:
        with open(PARTIAL_PATH, "a") as f:
            f.write(json.dumps({"ts": time.time(), **row}) + "\n")
    except OSError as e:
        print(f"[bench] partial persist failed: {e}", file=sys.stderr)


# ZeRO-Infinity rows: host masters streamed unit-by-unit through HBM —
# multi-billion-param training on the single chip (the reference trains 13B
# on one V100 the same way, docs/_pages/training.md:301)
INFINITY_CONFIGS = [
    # micro_bs 16: the streaming schedule's HBM estimate is 10.6 GB at 6.7B
    # (infinity_aot row) — doubling the batch doubles the tokens amortizing
    # the fixed host-Adam + transfer cost per step
    {"kind": "train", "name": "gpt2-1.3b-infinity", "model": "gpt2-1.3b",
     "micro_bs": 16, "seq": 1024, "steps": 3, "offload": "param_stream",
     "keep_layers": 2, "timeout": 3600},
    {"kind": "train", "name": "gpt-neox-6.7b-infinity",
     "model": "gpt-neox-6.7b", "micro_bs": 16, "seq": 1024, "steps": 2,
     "offload": "param_stream", "keep_layers": 2, "timeout": 5400},
    # the ROADMAP item 3 deliverable: a real measured train step for a >=7B
    # model on ONE v5e host, host masters streamed through the depth-2
    # prefetch pipeline with quantized (block-int8) host fetches — the
    # infinity_aot fit rows say bloom-7b1 fits; this row is the chip-session
    # flagship that turns the AOT verdict into a measured step (reports the
    # host-DMA column: exposed_wait_s, overlapped_frac, qpush ratio)
    {"kind": "train", "name": "bloom-7b1-infinity-streamed",
     "model": "bloom-7b1", "micro_bs": 4, "seq": 1024, "steps": 2,
     "offload": "param_stream", "keep_layers": 2,
     "offload_prefetch_depth": 2, "offload_quantized_fetch": True,
     "timeout": 7200},
    # ZeRO-Offload (optimizer-only) at billion scale: bf16 params resident
    # (2.6 GB), fp32 grads (5.2 GB) + chunked loss ≈ 10 GB device; fp32
    # master+moments (15.6 GB) live in host RAM, stepped by the C++ SIMD Adam
    {"kind": "train", "name": "gpt2-1.3b-offload-opt", "model": "gpt2-1.3b",
     "micro_bs": 8, "seq": 1024, "steps": 3, "offload": "optimizer",
     "stage": 1, "loss_chunk": 128, "timeout": 3600},
]

# Quantized ZeRO collectives (ZeRO++-style, comm/quantized.py): two
# apples-to-apples pairs at identical geometry — stage-3 fp vs quantized
# param gathers (the weight-wire lever), and stage-2 fp vs quantized grad
# reduction (the gradient-wire lever; stage 2 because the quantized grad
# program replicates params per device, which would negate the stage-3 row's
# memory story). fp32 compute on purpose — the wire ratio is measured against
# the logical dtype, and bf16 would halve the 4x-class reduction the knob is
# sold on. Rows report the wire_ledger per-op dict next to step time.
QUANTIZED_ZERO_CONFIGS = [
    {"kind": "train", "name": "gpt2-125m-zero3-fp", "model": "gpt2-125m",
     "micro_bs": 4, "seq": 512, "stage": 3, "steps": 3, "precision": "fp32",
     "timeout": 1800},
    {"kind": "train", "name": "gpt2-125m-zero3-qw8", "model": "gpt2-125m",
     "micro_bs": 4, "seq": 512, "stage": 3, "steps": 3, "precision": "fp32",
     "quantized_weights": True, "timeout": 1800},
    {"kind": "train", "name": "gpt2-125m-zero2-fp", "model": "gpt2-125m",
     "micro_bs": 4, "seq": 512, "stage": 2, "steps": 3, "precision": "fp32",
     "timeout": 1800},
    {"kind": "train", "name": "gpt2-125m-zero2-qg8", "model": "gpt2-125m",
     "micro_bs": 4, "seq": 512, "stage": 2, "steps": 3, "precision": "fp32",
     "quantized_gradients": True, "timeout": 1800},
    # overlap A/B at identical geometry: pipelined (default) vs inline
    # quantized gathers, each with a profiled step reporting the
    # exposed-vs-overlapped collective-time column (wire_overlap)
    {"kind": "train", "name": "gpt2-125m-zero3-qw8-overlap",
     "model": "gpt2-125m", "micro_bs": 4, "seq": 512, "stage": 3, "steps": 3,
     "precision": "fp32", "quantized_weights": True, "measure_overlap": True,
     "timeout": 1800},
    {"kind": "train", "name": "gpt2-125m-zero3-qw8-inline",
     "model": "gpt2-125m", "micro_bs": 4, "seq": 512, "stage": 3, "steps": 3,
     "precision": "fp32", "quantized_weights": True, "overlap_comm": False,
     "measure_overlap": True, "timeout": 1800},
]

# Compile-only evidence rows: the XLA TPU compiler runs on the host, so these
# produce v5e HBM/FLOPs numbers for the flagship train configs without a chip.
AOT_TRAIN_CONFIGS = [
    {"kind": "sd_aot", "name": "aot-sd-ddim20", "latent": 32,
     "ddim_steps": 20, "force_cpu": True},
    {"kind": "infer_aot", "name": "aot-350m-decode-b1", "model": "gpt2-350m",
     "batch": 1, "prompt": 128, "gen": 64, "force_cpu": True},
    {"kind": "infer_aot", "name": "aot-350m-decode-b8", "model": "gpt2-350m",
     "batch": 8, "prompt": 128, "gen": 64, "force_cpu": True},
    {"kind": "infer_aot", "name": "aot-350m-decode-b8-int8",
     "model": "gpt2-350m", "batch": 8, "prompt": 128, "gen": 64,
     "quantize_bits": 8, "force_cpu": True},
    # 13B weights chip-RESIDENT via the int8 Pallas matmul (the reference
    # needs host offload at this size — ZeRO-Inference regime)
    {"kind": "infer_aot", "name": "aot-opt13b-decode-b1-int8",
     "model": "opt-13b", "batch": 1, "prompt": 128, "gen": 64,
     "quantize_bits": 8, "force_cpu": True},
    # 20B chip-RESIDENT via the packed int4 Pallas matmul (13.8 GB peak,
    # 1.9 GB headroom — outside the fragmentation margin)
    {"kind": "infer_aot", "name": "aot-neox20b-decode-b1-int4",
     "model": "gpt-neox-20b", "batch": 1, "prompt": 128, "gen": 64,
     "quantize_bits": 4, "force_cpu": True, "timeout": 2700},
    {"kind": "kernels_aot", "name": "pallas-kernels-v5e-aot",
     "force_cpu": True, "timeout": 1500},
    {"kind": "train_aot", "name": "gpt2-760m-selrm16-chunk-aot",
     "model": "gpt2-760m", "micro_bs": 16, "seq": 1024,
     "remat_policy": "save_attn_mlp_out", "loss_chunk": 128,
     "force_cpu": True, "timeout": 1500},
    {"kind": "train_aot", "name": "gpt2-760m-bs24-chunk-aot",
     "model": "gpt2-760m", "micro_bs": 24, "seq": 1024, "loss_chunk": 128,
     "force_cpu": True, "timeout": 1500},
    {"kind": "infinity_aot", "name": "bloom-7b1-infinity-aot",
     "model": "bloom-7b1", "micro_bs": 4, "seq": 1024, "keep_layers": 2,
     "force_cpu": True},
    {"kind": "infinity_aot", "name": "gpt-neox-20b-infinity-aot",
     "model": "gpt-neox-20b", "micro_bs": 8, "seq": 1024, "keep_layers": 2,
     "force_cpu": True},
    {"kind": "infinity_aot", "name": "gpt-neox-6.7b-infinity-aot",
     "model": "gpt-neox-6.7b", "micro_bs": 8, "seq": 1024, "keep_layers": 2,
     "force_cpu": True, "timeout": 1500},
    # long context: ring-attention sequence parallelism over 4 chips at
    # seq 8192, and SINGLE-chip 8k via the streamed flash kernels (the k/v
    # stream rides the grid, so there is no whole-sequence VMEM residency)
    {"kind": "train_aot", "name": "gpt2-350m-seq8k-ring-sp4",
     "model": "gpt2-350m", "micro_bs": 2, "seq": 8192, "sp": 4,
     "seq_parallel_impl": "ring", "loss_chunk": 512,
     "force_cpu": True, "timeout": 1500},
    {"kind": "train_aot", "name": "gpt2-350m-seq8k-1chip",
     "model": "gpt2-350m", "micro_bs": 2, "seq": 8192, "loss_chunk": 512,
     "force_cpu": True, "timeout": 1500},
    {"kind": "train_aot", "name": "gpt2-350m-seq8k-ulysses-sp4",
     "model": "gpt2-350m", "micro_bs": 2, "seq": 8192, "sp": 4,
     "seq_parallel_impl": "ulysses", "loss_chunk": 512,
     "force_cpu": True, "timeout": 1500},
    # tensor parallelism: Megatron specs + the shard_mapped flash kernel
    # over tp=2 x dp=2 (the multi-chip config the GSPMD/Mosaic bug would
    # have crashed before this round's fix)
    {"kind": "train_aot", "name": "gpt2-350m-tp2-dp2",
     "model": "gpt2-350m", "micro_bs": 8, "dp": 2, "tp": 2, "seq": 1024,
     "loss_chunk": 128, "force_cpu": True, "timeout": 1500},
    # expert parallelism (BASELINE config #4 shape): expert bank over ep=4,
    # gating all-to-alls over ICI, ZeRO-1 over the (dp, ep) world
    {"kind": "moe_aot", "name": "moe-125m-8e-ep4-aot",
     "model": "moe-125m-8e", "ep": 4, "micro_bs": 4, "seq": 1024,
     "force_cpu": True, "timeout": 1500},
]

# Pipeline rows. The AOT row needs no chips at all — the XLA TPU compiler
# runs on the host against a v5e:2x2 topology.
PIPELINE_CONFIGS = [
    {"kind": "pipeline_aot", "name": "gpt2-350m-pp2-aot",
     "model": "gpt2-350m", "pp": 2, "dp": 2, "micro_bs": 4, "seq": 1024,
     "num_micro": 4, "force_cpu": True, "timeout": 1500},
    {"kind": "pipeline_mpmd", "name": "mpmd-dispatch-overhead",
     "d_model": 1024, "n_blocks": 24, "stages": 2, "num_micro": 4,
     "micro_bs": 4, "seq": 1024, "steps": 5, "timeout": 1500},
    # static schedule-prover comparison (ISSUE 18): 1F1B vs interleaved vs
    # zero-bubble bubble % at equal microbatches on the 8-device mesh shape
    # (MULTICHIP_r05.json dry-run world) — pure host math, proofs included
    {"kind": "pipeline_schedule", "name": "schedule-bubble-pp8",
     "stages": 8, "num_micro": 16, "vstages": 2, "micro_bs": 4, "seq": 1024,
     "d_model": 1024, "force_cpu": True, "n_devices": 8, "timeout": 600},
]


def _throughput_fields(tok_per_sec_device: float,
                       flops_per_token: float) -> dict:
    """Worker-side. Device metrics exist only on a device: tokens/sec/chip and
    MFU against the chip's published bf16 peak (one table, unknown chip
    raises). A CPU-host run reports its rate under a host name, never as
    ``tokens_per_sec_chip`` or ``mfu``."""
    import jax

    from deepspeed_tpu.accelerator.peaks import device_peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return {"host_tokens_per_sec": round(tok_per_sec_device, 1)}
    peak = device_peaks(dev.device_kind).bf16_flops
    return {"tokens_per_sec_chip": round(tok_per_sec_device, 1),
            "mfu": round(tok_per_sec_device * flops_per_token / peak, 4),
            "device_kind": dev.device_kind}


def _cpu_env(env: dict, n_devices: int = 1) -> dict:
    """Force a virtual n-device CPU mesh — single source of truth lives in
    __graft_entry__."""
    from __graft_entry__ import _force_cpu_env

    return _force_cpu_env(n_devices, env)


# a real matmul, so 'initialized' means 'usable'
_PROBE_CODE = (
    "import jax, jax.numpy as jnp; d = jax.devices(); "
    "x = jnp.ones((256,256), jnp.bfloat16); (x@x).block_until_ready(); "
    "print('PLATFORM=%s' % d[0].platform)")


def probe_backend() -> tuple:
    """``(platform, error)`` from ONE subprocess probe — the parent never
    initializes a backend (it would hold the chip its workers need)."""
    try:
        p = subprocess.run([sys.executable, "-c", _PROBE_CODE],
                           timeout=PROBE_TIMEOUT, capture_output=True,
                           text=True, cwd=REPO)
    except subprocess.TimeoutExpired:
        return "none", f"backend probe hung >{PROBE_TIMEOUT}s (killed)"
    if p.returncode != 0 or "PLATFORM=" not in p.stdout:
        return "none", f"probe rc={p.returncode}: {p.stderr.strip()[-400:]}"
    return p.stdout.split("PLATFORM=")[1].split()[0], None


def run_worker(cfg: dict, platform: str, retries: int = 1):
    """Run one benchmark config in a subprocess; returns parsed JSON or error dict."""
    if cfg.get("force_cpu"):
        # e.g. the AOT pipeline row: the XLA TPU compiler runs on the host,
        # and a worker on the CPU platform leaves the chip to the measured
        # rows. Rows that model a multi-chip world (the schedule-prover
        # row's 8-stage mesh) set n_devices for a virtual CPU mesh of that
        # size.
        env = _cpu_env(os.environ, n_devices=int(cfg.get("n_devices", 1)))
    else:
        env = dict(os.environ) if platform == "tpu" else _cpu_env(os.environ)
    timeout = int(cfg.get("timeout", WORKER_TIMEOUT))
    last_err = None
    for attempt in range(retries + 1):
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--worker", json.dumps(cfg)],
                timeout=timeout, capture_output=True, text=True, env=env, cwd=REPO)
            for line in reversed(p.stdout.strip().splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    return json.loads(line)
            last_err = f"rc={p.returncode}: {p.stderr.strip()[-500:]}"
        except subprocess.TimeoutExpired:
            last_err = f"worker hung >{timeout}s (killed)"
        if attempt < retries:
            time.sleep(5)
    return {"config": cfg.get("name"), "error": last_err}


# ---------------------------------------------------------------- worker side

def _worker(cfg: dict) -> None:
    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()  # every worker of every sweep shares one fixed dir
    fn = {"train": _worker_train, "inference": _worker_infer,
          "serving": _worker_serving,
          "serving_overload": _worker_serving_overload,
          "serving_tiered": _worker_serving_tiered,
          "serving_lever": _worker_serving_lever,
          "serving_fleet": _worker_serving_fleet,
          "serving_disagg": _worker_serving_disagg,
          "moe_train": _worker_moe_train,
          "kernels": _worker_kernels, "diffusion": _worker_diffusion,
          "pipeline_aot": _worker_pipeline_aot,
          "pipeline_mpmd": _worker_pipeline_mpmd,
          "pipeline_schedule": _worker_pipeline_schedule,
          "train_aot": _worker_train_aot,
          "infer_aot": _worker_infer_aot,
          "sd_aot": _worker_sd_aot,
          "kernels_aot": _worker_kernels_aot,
          "infinity_aot": _worker_infinity_aot,
          "chaos_mttr": _worker_chaos_mttr,
          "chaos_sdc": _worker_chaos_sdc,
          "moe_aot": _worker_moe_aot}[cfg["kind"]]
    print(json.dumps(fn(cfg)))


def _worker_kernels(cfg: dict) -> dict:
    """Mosaic-compile every Pallas kernel on the chip at bench-realistic shapes
    BEFORE the sweep, so a BlockSpec regression costs one config, not the
    round's inference evidence (VERDICT r2 'next' #1)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(0)
    results, failed = {}, []

    def check(name, fn):
        try:
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            results[name] = {"ok": True,
                             "compile_s": round(time.perf_counter() - t0, 1)}
        except Exception as e:  # record, keep probing the others
            results[name] = {"ok": False, "error": str(e)[-300:]}
            failed.append(name)

    B, H, S, Dh = 4, 16, 1024, 64
    q4 = jnp.asarray(rng.standard_normal((B, S, H, Dh)), jnp.bfloat16)

    def flash():
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        return f(q4, q4, q4)

    def flash_bwd():
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        f = jax.jit(jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal=True)
            .astype(jnp.float32).sum()))
        return f(q4, q4, q4)

    def decode():
        from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

        qd = jnp.asarray(rng.standard_normal((B, 1, H, Dh)), jnp.bfloat16)
        kc = jnp.asarray(rng.standard_normal((B, H, S, Dh)), jnp.bfloat16)
        f = jax.jit(lambda q, k, v, n: decode_attention(q, k, v, n))
        return f(qd, kc, kc, jnp.int32(S // 2))

    def decode_b16():
        # the BENCH_r02 regression shape: wide batch grid + per-row lengths
        from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

        qd = jnp.asarray(rng.standard_normal((16, 1, H, Dh)), jnp.bfloat16)
        kc = jnp.asarray(rng.standard_normal((16, H, S, Dh)), jnp.bfloat16)
        lens = jnp.asarray(rng.integers(1, S + 1, (16,)), jnp.int32)
        f = jax.jit(lambda q, k, v, n: decode_attention(q, k, v, n))
        return f(qd, kc, kc, lens)

    def paged_decode():
        # block-table gather through the scalar-prefetched index_map
        from deepspeed_tpu.ops.pallas.decode_attention import \
            paged_decode_attention

        ps, MP, P = 128, 8, 256
        qd = jnp.asarray(rng.standard_normal((16, 1, H, Dh)), jnp.bfloat16)
        kp = jnp.asarray(rng.standard_normal((H, P, ps, Dh)), jnp.bfloat16)
        tbl = jnp.asarray(rng.integers(1, P, (16, MP)), jnp.int32)
        lens = jnp.asarray(rng.integers(1, MP * ps + 1, (16,)), jnp.int32)
        f = jax.jit(lambda q, k, v, n, t: paged_decode_attention(
            q, k, v, n, t, impl="kernel"))
        return f(qd, kp, kp, lens, tbl)

    def blocksparse():
        from deepspeed_tpu.ops.pallas.blocksparse_attention import (
            blocksparse_attention)
        from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig

        sc = FixedSparsityConfig(num_heads=H, block=128)
        layout = np.asarray(sc.make_layout(S))
        f = jax.jit(lambda q, k, v: blocksparse_attention(
            q, k, v, layout=layout, block=128))
        return f(q4, q4, q4)

    def blocksparse_bwd():
        from deepspeed_tpu.ops.pallas.blocksparse_attention import (
            blocksparse_attention)
        from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig

        sc = FixedSparsityConfig(num_heads=H, block=128)
        layout = np.asarray(sc.make_layout(S))
        f = jax.jit(jax.grad(lambda q, k, v: blocksparse_attention(
            q, k, v, layout=layout, block=128).astype(jnp.float32).sum()))
        return f(q4, q4, q4)

    def int8mm():
        from deepspeed_tpu.ops.pallas.int8_matmul import int8_matmul

        x8 = jnp.asarray(rng.standard_normal((8, 512)), jnp.bfloat16)
        q8 = jnp.asarray(rng.integers(-127, 128, (512, 1536)), jnp.int8)
        s8 = jnp.asarray(rng.uniform(0.01, 0.1, (512 * 1536 // 128,)),
                         jnp.float32)
        f = jax.jit(lambda x, q, s: int8_matmul(x, q, s, group_size=128))
        return f(x8, q8, s8)

    def int4mm():
        from deepspeed_tpu.ops.pallas.int8_matmul import int4_matmul

        x4 = jnp.asarray(rng.standard_normal((8, 512)), jnp.bfloat16)
        q4 = jnp.asarray(rng.integers(-128, 128, (512, 1536)), jnp.int8)
        s4 = jnp.asarray(rng.uniform(0.01, 0.1, (512 * 3072 // 128,)),
                         jnp.float32)
        f = jax.jit(lambda x, q, s: int4_matmul(x, q, s, group_size=128))
        return f(x4, q4, s4)

    check("flash_attention", flash)
    check("flash_attention_bwd", flash_bwd)
    check("decode_attention", decode)
    check("decode_attention_b16", decode_b16)
    check("paged_decode_attention", paged_decode)
    check("blocksparse_attention", blocksparse)
    check("blocksparse_attention_bwd", blocksparse_bwd)
    check("int8_matmul", int8mm)
    check("int4_matmul", int4mm)
    out = {"config": cfg["name"], "kind": "kernels", "platform": platform,
           "kernels": results}
    if failed:
        out["error"] = "Mosaic compile failed: " + ", ".join(
            f"{k} ({results[k]['error'][-120:]})" for k in failed)
    return out


def _worker_train(cfg: dict) -> dict:
    import dataclasses

    import numpy as np

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models import gpt as gpt_mod

    platform = jax.devices()[0].platform
    mcfg = gpt_mod.PRESETS[cfg["model"]]
    if cfg.get("remat", True):
        mcfg = dataclasses.replace(
            mcfg, remat=True,
            remat_policy=cfg.get("remat_policy", "nothing_saveable"))
    if cfg.get("loss_chunk"):
        mcfg = dataclasses.replace(mcfg, loss_chunk=int(cfg["loss_chunk"]))
    model, mcfg = build_gpt(mcfg)
    n_chips = len(jax.devices())
    micro_bs, seq, steps = cfg["micro_bs"], cfg["seq"], cfg["steps"]
    zero_cfg = {"stage": cfg.get("stage", 0)}
    # quantized collectives (QUANTIZED_ZERO_CONFIGS): block-int8 wire for the
    # ZeRO-3 param gathers and/or the dp gradient reduction
    if cfg.get("quantized_weights"):
        zero_cfg["zero_quantized_weights"] = True
    if cfg.get("quantized_gradients"):
        zero_cfg["zero_quantized_gradients"] = True
    if cfg.get("quantize_bits"):
        zero_cfg["zero_quantize_bits"] = int(cfg["quantize_bits"])
    # overlap knobs (docs/COMM_COMPRESSION.md "Overlap & fusion"): default is
    # the pipelined/bucketed schedules; overlap_comm=False benches the inline
    # baseline the overlap rows are compared against
    if cfg.get("overlap_comm") is not None:
        zero_cfg["overlap_comm"] = bool(cfg["overlap_comm"])
    if cfg.get("prefetch_depth"):
        zero_cfg["overlap_prefetch_depth"] = int(cfg["prefetch_depth"])
    if cfg.get("offload") == "param_stream":
        # ZeRO-Infinity: host masters streamed unit-by-unit through HBM —
        # the bigger-than-HBM single-chip regime (reference: 13B on one V100,
        # docs/_pages/training.md:301). Streaming knobs (docs/OFFLOAD.md):
        # offload_stream=False benches the fetch-on-demand baseline the
        # streamed rows are A/B'd against; offload_quantized_fetch pushes
        # units over the block-int8 host wire
        op_cfg = {"device": "cpu", "buffer_count": cfg.get("keep_layers", 2)}
        if cfg.get("offload_stream") is not None:
            op_cfg["stream"] = bool(cfg["offload_stream"])
        if cfg.get("offload_prefetch_depth") is not None:
            op_cfg["prefetch_depth"] = int(cfg["offload_prefetch_depth"])
        if cfg.get("offload_quantized_fetch"):
            op_cfg["quantized_fetch"] = True
        zero_cfg["offload_param"] = op_cfg
    elif cfg.get("offload") == "optimizer":
        zero_cfg["offload_optimizer"] = {"device": "cpu"}
    # gas>1 folds all micro-steps into one compiled program (engine's fused
    # accumulation scan), exactly the way real accumulated training does
    gas = int(cfg.get("gas", 1))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 3e-4, "weight_decay": 0.1}},
            # precision=fp32 (the quantized-zero rows): logical wire dtype is
            # fp32 so the ledger ratio reflects the full int8 reduction
            "bf16": {"enabled": cfg.get("precision", "bf16") != "fp32"},
            "zero_optimization": zero_cfg,
            "gradient_clipping": 1.0,
            "steps_per_print": 0,
        })

    rng = np.random.default_rng(0)
    # k_steps>1: K complete optimizer steps per dispatch (engine.train_batches
    # scan — no cross-step accumulator, peak HBM equals the k=1 program; the
    # gas=8 variants AOT-OOM at the lead geometries)
    k_steps = int(cfg.get("k_steps", 1))
    shape = ((gas, micro_bs * n_chips, seq) if gas > 1
             else (micro_bs * n_chips, seq))
    if k_steps > 1:
        shape = (k_steps,) + shape

    def make_batch():
        return {"input_ids": rng.integers(
            0, mcfg.vocab_size, size=shape, dtype=np.int32)}

    step_fn = engine.train_batches if k_steps > 1 else engine.train_batch
    m = step_fn(make_batch())  # warmup/compile
    float(m["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        m = step_fn(make_batch())
    # host transfer: device_get can't return until the whole chain executed
    float(m["loss"])
    _ = np.asarray(jax.device_get(m["grad_norm"]))
    dt = time.perf_counter() - t0

    tokens = steps * k_steps * gas * micro_bs * n_chips * (seq - 1)
    tok_per_sec_chip = tokens / dt / n_chips
    n_params = mcfg.num_params()
    # 6*N FLOPs/token (fwd+bwd) + attention term 12*L*d*T per token
    flops_per_token = 6 * n_params + 12 * mcfg.n_layer * mcfg.d_model * seq
    out = {
        "config": cfg["name"], "kind": "train", "platform": platform,
        **_throughput_fields(tok_per_sec_chip, flops_per_token),
        "chips": n_chips, "micro_bs": micro_bs,
        "gas": gas, "k_steps": k_steps, "seq": seq,
        "stage": cfg.get("stage", 0),
        "loss": round(float(m["loss"]), 4),
        "step_ms": round(dt / (steps * k_steps) * 1e3, 1),
    }
    if cfg.get("measure_overlap"):
        # one extra profiled step: the exposed-vs-overlapped collective-time
        # column — where the step time actually went (docs/COMM_COMPRESSION.md
        # "Overlap & fusion"). A profiling failure must not cost the row's
        # measured numbers.
        try:
            single = {"input_ids": rng.integers(
                0, mcfg.vocab_size,
                size=((gas, micro_bs * n_chips, seq) if gas > 1
                      else (micro_bs * n_chips, seq)), dtype=np.int32)}
            out["wire_overlap"] = engine.measure_overlap(single).to_dict()
        except Exception as e:
            out["wire_overlap"] = {"error": str(e)[-200:]}
    if cfg.get("quantized_weights") or cfg.get("quantized_gradients"):
        # logical-vs-wire bytes per quantized op (trace-time ledger): the
        # compression evidence the QUANTIZED_ZERO_CONFIGS rows exist for
        from deepspeed_tpu.comm.runtime_accounting import wire_ledger

        out["wire"] = wire_ledger.summary_dict()
        out["wire_ratio"] = round(wire_ledger.ratio(), 3)
    if cfg.get("offload"):
        out["offload"] = cfg["offload"]
        runner = getattr(engine, "_param_stream", None)
        if runner is not None and runner.last_stats:
            # HBM/host breakdown: the whole point of the >HBM-sized row
            out["memory"] = {k: runner.last_stats[k]
                             for k in ("hbm_peak_bytes", "host_rss_bytes",
                                       "n_params", "wire_bytes_per_step",
                                       "prefetch_depth",
                                       "stream_buffer_bytes")
                             if k in runner.last_stats}
            # the streamed-vs-inline A/B observable (docs/OFFLOAD.md): how
            # much of the host<->HBM DMA sat exposed at a consume point,
            # and the fraction of waits the prefetch schedule hid entirely
            if "host_dma" in runner.last_stats:
                out["host_dma"] = runner.last_stats["host_dma"]
    return out


def _worker_chaos_mttr(cfg: dict) -> dict:
    """MTTR row (docs/RESILIENCE.md "In-run health"): inject a NaN at a known
    data cursor and measure the self-heal — detection + rollback latency,
    steps to rejoin a pre-divergence loss level, and the poisoned cursors
    provably excluded. Runs the REAL engine health loop (sentinel config +
    chaos injector), not a simulation."""
    import math
    import tempfile
    import time as _time

    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models import gpt as gpt_mod
    from deepspeed_tpu.resilience import FaultPlan, install_plan

    mcfg = gpt_mod.PRESETS[cfg["model"]]
    model, mcfg = build_gpt(mcfg)
    micro_bs, seq = cfg["micro_bs"], cfg["seq"]
    steps, nan_at = int(cfg["steps"]), int(cfg["nan_at"])
    with tempfile.TemporaryDirectory() as td:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model,
            config={
                "train_micro_batch_size_per_gpu": micro_bs,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
                "bf16": {"enabled": False},
                "steps_per_print": 0,
                "resilience": {
                    "enabled": True, "save_dir": td,
                    "install_signal_handlers": False,
                    "sentinel": {"enabled": True, "warmup_steps": 1,
                                 "checkpoint_interval": 1,
                                 "cursor_checkpointable": True}},
            })
        install_plan(FaultPlan.from_dict({"nan_at_step": nan_at}))

        def make_batch(cursor):
            r = np.random.default_rng(cursor)
            return {"input_ids": r.integers(
                0, mcfg.vocab_size, size=(micro_bs, seq), dtype=np.int32)}

        losses, rollback = [], None
        detect_wall = heal_wall = None
        t0 = _time.monotonic()
        while engine.global_steps < steps:
            m = engine.train_batch(make_batch(engine.data_cursor))
            if m.get("skipped_batch"):
                continue
            h = m.get("health", {}).get("rolled_back")
            if h:
                rollback = h
                detect_wall = _time.monotonic() - t0
            elif math.isfinite(float(m["loss"])):
                losses.append(float(m["loss"]))
                if rollback is not None and heal_wall is None:
                    heal_wall = _time.monotonic() - t0
        install_plan(None)
        health = engine._health
        return {
            "config": cfg["name"],
            "healed": rollback is not None and math.isfinite(losses[-1]),
            "rollbacks": health.rollbacks,
            "rollback_latency_s": (round(rollback["latency_s"], 4)
                                   if rollback else None),
            # wall-clock from divergence detection to the first healthy
            # post-heal step — the row's MTTR
            "mttr_s": (round(heal_wall - detect_wall, 3)
                       if heal_wall is not None else None),
            "skipped_cursors": health.skipped_cursors,
            "final_loss": round(losses[-1], 4) if losses else None,
            "steps": int(engine.global_steps),
            "data_cursor": int(engine.data_cursor),
        }


def _worker_chaos_sdc(cfg: dict) -> dict:
    """SDC row (docs/RESILIENCE.md "Data integrity"): one REAL bit flip in
    each of two state domains — a cpu-offloaded optimizer shard mid-training
    and a prefix-shared KV page mid-serving — measuring detection latency,
    heal (rollback replay must be step-exact; serving re-prefill must be
    generate-identical), and the integrity scan's overhead at the DEFAULT
    budget (scan_interval=16 x 4 blocks), which the row asserts ≤5%."""
    import math
    import tempfile
    import time as _time

    import numpy as np

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.inference.serving.scheduler import Request
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models import gpt as gpt_mod
    from deepspeed_tpu.resilience import FaultPlan, install_plan

    mcfg = gpt_mod.PRESETS[cfg["model"]]
    micro_bs, seq = cfg["micro_bs"], cfg["seq"]
    steps, flip_at = int(cfg["steps"]), int(cfg["flip_at"])

    # ---- training domain: host-offloaded optimizer shard -----------------
    def train_run(td: str, flip: bool) -> dict:
        install_plan(FaultPlan.from_dict(
            {"flip_bit_at": flip_at, "flip_bit_domain": "host_shards"})
            if flip else None)
        model, _ = build_gpt(mcfg)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model,
            config={
                "train_micro_batch_size_per_gpu": micro_bs,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
                "steps_per_print": 0,
                "zero_optimization": {
                    "stage": 2, "offload_optimizer": {"device": "cpu"}},
                "resilience": {
                    "enabled": True, "save_dir": td,
                    "install_signal_handlers": False,
                    "sentinel": {"enabled": True, "warmup_steps": 1,
                                 "checkpoint_interval": 4,
                                 "cursor_checkpointable": True},
                    # DEFAULT scan budget — the overhead number the row
                    # reports is the one production would pay
                    "integrity": {"enabled": True}},
            })

        def make_batch(cursor):
            r = np.random.default_rng(cursor)
            return {"input_ids": r.integers(
                0, mcfg.vocab_size, size=(micro_bs, seq), dtype=np.int32)}

        rollback = None
        detect_step = detect_wall = heal_wall = None
        t0 = _time.monotonic()
        loss = float("nan")
        while engine.global_steps < steps:
            m = engine.train_batch(make_batch(engine.data_cursor))
            h = m.get("health", {}).get("rolled_back")
            if h is not None and "sdc" in m:
                rollback = h
                # the cursor already rewound with the rollback — the
                # detection boundary is where the rollback started from
                detect_step = int(h.get("from_step", engine.data_cursor))
                detect_wall = _time.monotonic() - t0
                continue
            loss = float(m["loss"])
            if rollback is not None and heal_wall is None \
                    and math.isfinite(loss):
                heal_wall = _time.monotonic() - t0
        report = engine._integrity.report()
        counters = dict(engine._recovery_log.counters)
        install_plan(None)
        return {"loss": loss, "rollback": rollback,
                "detect_step": detect_step,
                "mttr_s": (round(heal_wall - detect_wall, 3)
                           if heal_wall is not None else None),
                "report": report, "counters": counters}

    with tempfile.TemporaryDirectory() as td:
        ref = train_run(os.path.join(td, "ref"), flip=False)
        hit = train_run(os.path.join(td, "flip"), flip=True)
    training = {
        "detected": hit["rollback"] is not None,
        # boundaries from injection to detection: the flip lands at the
        # pre-step verify of the SAME boundary, so this is scan latency
        "detect_latency_steps": (hit["detect_step"] - flip_at
                                 if hit["detect_step"] is not None else None),
        "rollback_latency_s": (round(hit["rollback"]["latency_s"], 4)
                               if hit["rollback"] else None),
        "mttr_s": hit["mttr_s"],
        # the heal contract: replayed batches land on the SAME final loss
        "step_exact": hit["loss"] == ref["loss"],
        "final_loss": round(hit["loss"], 4),
        "clean_run_sdc_events": ref["counters"].get("sdc_detected", 0),
        "scan_overhead_frac": round(ref["report"]["overhead_frac"], 5),
        "blocks_verified": ref["report"]["blocks_verified"],
    }

    # ---- serving domain: prefix-shared KV page ---------------------------
    params = gpt_mod.init_params(mcfg, jax.random.PRNGKey(0))
    eng = ServingEngine(mcfg, params, ServingConfig(
        num_slots=4, page_size=16, max_model_len=128, prefill_chunk=32,
        dtype="float32", decode_block=1, max_queue=64,
        enable_prefix_cache=True, page_fingerprints=True))
    prompt = (np.arange(40, dtype=np.int32) % (mcfg.vocab_size - 1)) + 1

    def serve_run(flip: bool) -> dict:
        install_plan(FaultPlan.from_dict(
            {"flip_bit_at": 2, "flip_bit_domain": "kv_page"})
            if flip else None)
        sched = eng.make_scheduler()
        reqs = [Request(prompt=prompt.copy(), max_new_tokens=8)
                for _ in range(2)]
        sched.submit(reqs[0])
        for _ in range(3):
            sched.step()
        sched.submit(reqs[1])
        detect_step = flip_step = None
        audit_mid = None
        for _ in range(120):
            sched.step()
            if flip_step is None and sched.counters.get("chaos_injected"):
                flip_step = sched.steps
            if detect_step is None and sched.counters.get("sdc_detected"):
                detect_step = sched.steps
            if audit_mid is None and sched.page_stats["shared"]:
                audit_mid = sched.audit()
            if all(r.state.value == "finished" for r in reqs):
                break
        out = {"tokens": [list(r.tokens) for r in reqs],
               "counters": dict(sched.counters),
               "flip_step": flip_step, "detect_step": detect_step,
               "audit_mid": audit_mid, "audit": sched.audit()}
        sched.close()
        install_plan(None)
        return out

    sref = serve_run(flip=False)
    sflip = serve_run(flip=True)
    serving = {
        "detected": bool(sflip["counters"].get("sdc_detected")),
        "healed": bool(sflip["counters"].get("sdc_healed")),
        "detect_latency_steps": (sflip["detect_step"] - sflip["flip_step"]
                                 if sflip["detect_step"] is not None
                                 and sflip["flip_step"] is not None else None),
        "borrower_preemptions": sflip["counters"].get("preemption", 0),
        "greedy_identical": sflip["tokens"] == sref["tokens"],
        "audit_ok": bool(sflip["audit"]["ok"]),
        "pages_fingerprint_swept": (sref["audit_mid"] or {}).get(
            "fingerprinted", 0),
        "clean_run_sdc_events": sref["counters"].get("sdc_detected", 0),
    }

    domains = int(training["detected"]) + int(serving["detected"])
    return {
        "config": cfg["name"],
        "training": training,
        "serving": serving,
        "domains_detected": domains,
        "healed": (domains == 2 and training["step_exact"]
                   and serving["greedy_identical"] and serving["audit_ok"]),
        "overhead_ok": training["scan_overhead_frac"] <= 0.05,
    }


def _worker_moe_train(cfg: dict) -> dict:
    """Measured MoE training step (VERDICT r4 'next' #5): GShard top-k gating +
    expert bank through the full engine step on the real device. Single-chip
    ep=1 keeps the whole expert bank resident; the gating/dispatch einsums are
    identical to the ep>1 program (moe/sharded_moe.py), so step time here is
    the per-chip compute term of BASELINE config #4 (the reference measures
    this path in ``DeepSpeed-MoE``, deepspeed/moe/sharded_moe.py)."""
    import numpy as np

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import build_gpt_moe

    platform = jax.devices()[0].platform
    model, mcfg = build_gpt_moe(cfg.get("model", "moe-125m-8e"))
    micro_bs, seq = int(cfg["micro_bs"]), int(cfg["seq"])
    steps = int(cfg.get("steps", 5))
    n_chips = len(jax.devices())
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 3e-4, "weight_decay": 0.1}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": cfg.get("stage", 1)},
            "gradient_clipping": 1.0,
            "steps_per_print": 0,
        })
    b = mcfg.base
    rng = np.random.default_rng(0)

    def make_batch():
        # global batch rides the dp mesh axis, micro_bs per chip (as
        # _worker_train does) so tokens/sec/chip stays per-chip truth
        return {"input_ids": rng.integers(
            0, b.vocab_size, size=(micro_bs * n_chips, seq), dtype=np.int32)}

    m = engine.train_batch(make_batch())  # warmup/compile
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.train_batch(make_batch())
    float(m["loss"])
    dt = time.perf_counter() - t0

    # MFU over ACTIVE FLOPs/token: attention + dense MLPs + gate + the k
    # routed expert FFNs (a dropped-token step does fewer — this is the upper
    # bound the capacity factor allows, the standard MoE-MFU convention)
    d, L, ff = b.d_model, b.n_layer, b.ffn_dim
    n_super = mcfg.n_super
    active = (L * 4 * d * d + (L - n_super) * 2 * d * ff
              + n_super * (mcfg.k * 2 * d * ff + d * mcfg.num_experts)
              + d * b.vocab_size)
    flops_per_token = 6 * active + 12 * L * d * seq
    tok = steps * micro_bs * n_chips * (seq - 1) / dt / n_chips
    return {
        "config": cfg["name"], "kind": "moe_train", "platform": platform,
        "model": cfg.get("model", "moe-125m-8e"),
        "num_experts": mcfg.num_experts, "k": mcfg.k,
        "micro_bs": micro_bs, "seq": seq, "chips": n_chips,
        **_throughput_fields(tok, flops_per_token),
        "step_ms": round(dt / steps * 1e3, 1),
        "loss": round(float(m["loss"]), 4),
    }


def _worker_infer(cfg: dict) -> dict:
    import numpy as np

    import jax

    from deepspeed_tpu.inference import DeepSpeedInferenceConfig, InferenceEngine
    from deepspeed_tpu.inference.engine import for_gpt
    from deepspeed_tpu.models import gpt as gpt_mod

    platform = jax.devices()[0].platform
    mcfg = gpt_mod.PRESETS[cfg["model"]]
    # quantize_bits: weight-only int8/int4 decode (Pallas dequant-per-tile
    # matmuls) — measures the weight-bandwidth lever on the real chip
    qbits = int(cfg.get("quantize_bits", 0))
    if cfg.get("stream_init"):
        # big models (13B/20B): host-streamed quantized init — the fp32 tree
        # never exists anywhere, the device gets only the narrow stacks
        params = gpt_mod.init_quantized_decode_params(
            mcfg, bits=qbits or 4, group_size=128)
        quant = {"enabled": False}  # params arrive pre-quantized
    else:
        params = gpt_mod.init_params(mcfg, jax.random.PRNGKey(0))
        quant = {"enabled": bool(qbits), "bits": qbits or 8,
                 "group_size": 128}
    engine = InferenceEngine(
        for_gpt(mcfg, params),
        DeepSpeedInferenceConfig(
            dtype="bfloat16",
            max_out_tokens=cfg["prompt"] + cfg["gen"] + 8,
            quant=quant))
    ids = np.asarray(np.random.default_rng(0).integers(
        0, mcfg.vocab_size, (cfg["batch"], cfg["prompt"])), np.int32)

    short, long_ = max(cfg["gen"] // 4, 1), cfg["gen"]
    # warmup/compile both shapes
    np.asarray(engine.generate(ids, max_new_tokens=short))
    np.asarray(engine.generate(ids, max_new_tokens=long_))
    lat = []
    for _ in range(cfg.get("reps", 5)):
        t0 = time.perf_counter()
        np.asarray(engine.generate(ids, max_new_tokens=short))
        t1 = time.perf_counter()
        np.asarray(engine.generate(ids, max_new_tokens=long_))
        t2 = time.perf_counter()
        # subtract prefill+dispatch overhead: marginal per-token decode latency
        lat.append(((t2 - t1) - (t1 - t0)) / (long_ - short) * 1e3)
    lat.sort()
    p50 = lat[len(lat) // 2]
    p90 = lat[min(len(lat) - 1, int(len(lat) * 0.9))]
    out = {
        "config": cfg["name"], "kind": "inference", "platform": platform,
        "decode_p50_ms": round(p50, 3), "decode_p90_ms": round(p90, 3),
        "tokens_per_sec": round(1e3 / max(p50, 1e-9) * cfg["batch"], 1),
        "batch": cfg["batch"], "prompt": cfg["prompt"],
    }
    if qbits:
        out["quantize_bits"] = qbits
    return out


def _worker_serving(cfg: dict) -> dict:
    """Request-level serving bench: open-loop arrivals through the
    continuous-batching paged stack vs the static-batch ``generate``
    baseline on the SAME seeded workload (equal useful-token accounting,
    comparable HBM budget). Reports p50/p99 TTFT, per-token latency, and
    aggregate tokens/s for both, plus the speedup the serving row's
    acceptance bar is judged on."""
    import numpy as np

    import jax

    from deepspeed_tpu.inference import (DeepSpeedInferenceConfig,
                                         InferenceEngine)
    from deepspeed_tpu.inference.engine import for_gpt
    from deepspeed_tpu.inference.serving import (ServingConfig, ServingEngine,
                                                 make_open_loop_workload,
                                                 run_continuous,
                                                 run_static_baseline)
    from deepspeed_tpu.models import gpt as gpt_mod

    platform = jax.devices()[0].platform
    mcfg = gpt_mod.PRESETS[cfg["model"]]
    params = gpt_mod.init_params(mcfg, jax.random.PRNGKey(0))
    dtype = cfg.get("dtype", "bfloat16")
    slots = int(cfg.get("slots", 8))
    max_len = int(cfg.get("max_model_len", 512))
    page_size = int(cfg.get("page_size", 64))
    prompt_rng = tuple(cfg.get("prompt_range", (32, 128)))
    gen_rng = tuple(cfg.get("gen_range", (16, 96)))
    n_req = int(cfg.get("requests", 24))
    rate = float(cfg.get("rate_rps", 8.0))

    def workload(seed=0):
        return make_open_loop_workload(
            n_req, rate, prompt_rng, gen_rng, mcfg.vocab_size, seed=seed)

    # equal-HBM framing: both sides get the same KV token budget. Static
    # batching must reserve the workload's padded worst case per row; the
    # paged pool shares the same tokens across MORE slots (mixed lengths
    # mean average residency << worst case; preemption covers the tail).
    wl_probe = workload()
    warm_t = max(len(r.prompt) for r in wl_probe)
    warm_g = max(r.max_new_tokens for r in wl_probe)
    static_row_tokens = -(-(warm_t + warm_g) // 128) * 128  # generate's pad
    hbm_tokens = int(cfg.get("hbm_tokens", slots * static_row_tokens // 2))
    static_batch = max(1, hbm_tokens // static_row_tokens)

    eng = ServingEngine(mcfg, params, ServingConfig(
        num_slots=slots, page_size=page_size, max_model_len=max_len,
        num_pages=hbm_tokens // page_size + 1,
        prefill_chunk=int(cfg.get("prefill_chunk", 128)), dtype=dtype,
        tp=int(cfg.get("tp", 1))))

    # compile every serving program shape outside the timed window
    eng.warmup()
    cont = run_continuous(eng, workload())

    ie = InferenceEngine(for_gpt(mcfg, params), DeepSpeedInferenceConfig(
        dtype=dtype, max_out_tokens=max_len))
    # warm the exact batch shape the measured baseline will run (the
    # baseline pads globally to the workload's max prompt/gen)
    from deepspeed_tpu.inference.serving import Request
    warm = [Request(prompt=np.zeros(warm_t, np.int32), max_new_tokens=warm_g)
            for _ in range(static_batch)]
    run_static_baseline(ie, warm, batch_size=static_batch)
    static = run_static_baseline(ie, workload(), batch_size=static_batch)

    speedup = (cont["tokens_per_sec"] / static["tokens_per_sec"]
               if static["tokens_per_sec"] else float("nan"))
    out = {
        "config": cfg["name"], "kind": "serving", "platform": platform,
        "model": cfg["model"], "num_slots": slots,
        "hbm_tokens": hbm_tokens, "static_batch": static_batch,
        "static_row_tokens": static_row_tokens,
        "requests": n_req, "rate_rps": rate,
        "tokens_per_sec": cont["tokens_per_sec"],
        "ttft_p50_ms": cont["ttft_p50_ms"], "ttft_p99_ms": cont["ttft_p99_ms"],
        "per_token_p50_ms": cont["per_token_p50_ms"],
        "per_token_p99_ms": cont["per_token_p99_ms"],
        "preemptions": cont["preemptions"],
        "compiled_programs": cont["compiled_programs"],
        "hbm_token_slots": cont["hbm_token_slots"],
        "static_tokens_per_sec": static["tokens_per_sec"],
        "static_ttft_p50_ms": static["ttft_p50_ms"],
        "static_ttft_p99_ms": static["ttft_p99_ms"],
        "speedup_vs_static": round(speedup, 3),
        "continuous": cont, "static": static,
    }
    return out


def _worker_serving_overload(cfg: dict) -> dict:
    """Overload A/B at 2x saturation (docs/SERVING.md "Overload & failure"):
    calibrate the server's closed-loop saturation rate, then drive the SAME
    2x-rate Poisson workload through (a) an overload-CONTROLLED scheduler
    (bounded queue, token backpressure, deadlines = the SLO) and (b) an
    uncontrolled one (the unsafe default). Both score against the same
    evaluation SLO, so the row shows what admission control buys: bounded
    p99 TTFT of *accepted* requests and higher goodput, versus a baseline
    whose queue — and tail — grows for as long as the load lasts."""
    import jax

    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                 ServingConfig, ServingEngine,
                                                 estimate_saturation_rps,
                                                 make_open_loop_workload,
                                                 run_continuous)
    from deepspeed_tpu.models import gpt as gpt_mod

    platform = jax.devices()[0].platform
    mcfg = gpt_mod.PRESETS[cfg["model"]]
    params = gpt_mod.init_params(mcfg, jax.random.PRNGKey(0))
    slots = int(cfg.get("slots", 8))
    page_size = int(cfg.get("page_size", 16))
    max_len = int(cfg.get("max_model_len", 128))
    prompt_rng = tuple(cfg.get("prompt_range", (8, 32)))
    gen_rng = tuple(cfg.get("gen_range", (8, 32)))
    n_req = int(cfg.get("requests", 24))
    slo_s = float(cfg.get("slo_s", 2.0))

    eng = ServingEngine(mcfg, params, ServingConfig(
        num_slots=slots, page_size=page_size, max_model_len=max_len,
        prefill_chunk=int(cfg.get("prefill_chunk", 32)),
        dtype=cfg.get("dtype", "float32")))
    eng.warmup()
    sat_rps = estimate_saturation_rps(eng, prompt_rng, gen_rng,
                                      mcfg.vocab_size)
    rate = float(cfg.get("overload_factor", 2.0)) * sat_rps

    def workload():
        return make_open_loop_workload(n_req, rate, prompt_rng, gen_rng,
                                       mcfg.vocab_size,
                                       seed=int(cfg.get("seed", 5)))

    def sched(controlled: bool) -> ContinuousBatchingScheduler:
        kw = {}
        if controlled:
            kw = dict(max_queue=slots,
                      max_queued_tokens=eng.hbm_token_slots(),
                      ttft_deadline_s=slo_s / 2, deadline_s=slo_s)
        return ContinuousBatchingScheduler(
            executor=eng, num_slots=eng.num_slots, num_pages=eng.num_pages,
            page_size=page_size, pages_per_seq=eng.serving.pages_per_seq,
            decode_block=eng.serving.decode_block, max_context=max_len, **kw)

    wall = float(cfg.get("max_wall_s", 120.0))
    on = run_continuous(eng, workload(), max_wall_s=wall, slo_s=slo_s,
                        scheduler=sched(True))
    off = run_continuous(eng, workload(), max_wall_s=wall, slo_s=slo_s,
                         scheduler=sched(False))
    return {
        "config": cfg["name"], "kind": "serving_overload",
        "platform": platform, "model": cfg["model"], "num_slots": slots,
        "saturation_rps": round(sat_rps, 3), "rate_rps": round(rate, 3),
        "slo_s": slo_s, "requests": n_req,
        "goodput_tokens_per_sec": on["goodput_tokens_per_sec"],
        "shed_rate": on["shed_rate"],
        "deadline_miss_rate": on["deadline_miss_rate"],
        "accepted_ttft_p99_ms": on["ttft_p99_ms"],
        "pool_audit_ok": on["pool_audit_ok"] and off["pool_audit_ok"],
        "uncontrolled_goodput_tokens_per_sec":
            off["goodput_tokens_per_sec"],
        "uncontrolled_ttft_p99_ms": off["ttft_p99_ms"],
        "uncontrolled_deadline_miss_rate": off["deadline_miss_rate"],
        "controlled": on, "uncontrolled": off,
    }


def _worker_serving_tiered(cfg: dict) -> dict:
    """Multi-tenant SLO-tier A/B at 2x saturation (docs/SERVING.md
    "Multi-tenancy & SLO tiers"): a 3-tier mixed-tenant Poisson stream
    (one tenant per tier) driven through (a) a TIERED scheduler — WFQ
    virtual-time ordering, per-tier admission partitions, the brownout
    degradation ladder, tier-aware preemption — and (b) the same
    scheduler untiered (FIFO, tier-blind shed). The overload stream is
    batch-heavy (default shares 15/25/60) — the noisy-neighbor shape:
    a tenant whose OWN demand saturates the box is not a neighbor
    problem, so the protected tier must be light relative to capacity
    for "protect interactive" to be a scheduling claim rather than a
    physics violation. A light-load (0.5x saturation, even shares)
    tiered run calibrates the unloaded interactive TTFT floor the
    overloaded run is judged against. The row shows what the tier
    table buys: interactive p99 TTFT pinned near its light-load value
    (WFQ ordering + latency preemption of batch slots) while the batch
    tier absorbs the shed, versus an untiered baseline that sheds and
    queues tier-blind. Greedy agreement between
    the tiered and untiered runs is compared over the COMMON generated
    prefix (the ladder's clamp_batch stage may shorten a batch
    request's budget; prioritization must never change the tokens
    themselves). Batch bounded-wait is asserted structurally: every
    batch request reaches a terminal state — finished, typed shed, or
    typed expiry — never a silent starve."""
    import jax

    from deepspeed_tpu.inference.serving import (BrownoutConfig,
                                                 ContinuousBatchingScheduler,
                                                 RequestState, ServingConfig,
                                                 ServingEngine,
                                                 estimate_saturation_rps,
                                                 make_tiered_workload,
                                                 resolve_tiers,
                                                 run_continuous)
    from deepspeed_tpu.models import gpt as gpt_mod

    platform = jax.devices()[0].platform
    mcfg = gpt_mod.PRESETS[cfg["model"]]
    params = gpt_mod.init_params(mcfg, jax.random.PRNGKey(0))
    slots = int(cfg.get("slots", 4))
    page_size = int(cfg.get("page_size", 16))
    max_len = int(cfg.get("max_model_len", 96))
    prompt_rng = tuple(cfg.get("prompt_range", (8, 24)))
    gen_rng = tuple(cfg.get("gen_range", (8, 24)))
    n_per_tier = int(cfg.get("requests_per_tier", 8))
    slo_s = float(cfg.get("slo_s", 3.0))
    seed = int(cfg.get("seed", 5))
    wall = float(cfg.get("max_wall_s", 120.0))

    eng = ServingEngine(mcfg, params, ServingConfig(
        num_slots=slots, page_size=page_size, max_model_len=max_len,
        prefill_chunk=int(cfg.get("prefill_chunk", 32)),
        dtype=cfg.get("dtype", "float32"),
        decode_block=int(cfg.get("decode_block", 4))))
    eng.warmup()
    sat = estimate_saturation_rps(eng, prompt_rng, gen_rng, mcfg.vocab_size)
    rate = float(cfg.get("overload_factor", 2.0)) * sat

    # tier policy: deadlines track the evaluation SLO (interactive must
    # answer inside it, standard gets slack, batch has none and rides the
    # backlog); the batch admission partition is shallow so overflow is
    # absorbed there — by policy, not by arrival luck; reserved interactive
    # slots make the protected tier's TTFT load-independent (dispatch
    # shapes are padded, so service time is constant — slot wait was the
    # only load-dependent term)
    tiers = resolve_tiers(cfg.get("tiers") or {
        "interactive": {"ttft_deadline_s": slo_s / 2,
                        "deadline_s": 4 * slo_s,
                        "reserved_slots": max(1, slots // 8)},
        "standard": {"ttft_deadline_s": 2 * slo_s,
                     "deadline_s": 8 * slo_s},
        "batch": {"max_queue": max(2, slots // 2)},
    })

    def sched(tiered: bool) -> ContinuousBatchingScheduler:
        kw = dict(max_queue=4 * slots,
                  max_queued_tokens=eng.hbm_token_slots())
        if tiered:
            kw.update(tiers=tiers,
                      brownout=BrownoutConfig(
                          window_s=float(cfg.get("brownout_window_s", 5.0)),
                          min_dwell_s=float(cfg.get("brownout_dwell_s",
                                                    0.5))))
        return ContinuousBatchingScheduler(
            executor=eng, num_slots=eng.num_slots, num_pages=eng.num_pages,
            page_size=page_size, pages_per_seq=eng.serving.pages_per_seq,
            decode_block=eng.serving.decode_block, max_context=max_len, **kw)

    shares = cfg.get("tier_shares") or {"interactive": 0.15,
                                        "standard": 0.25, "batch": 0.6}

    def workload(rps: float, shaped: bool = True):
        return make_tiered_workload(n_per_tier, rps, prompt_rng, gen_rng,
                                    mcfg.vocab_size, seed=seed,
                                    shares=shares if shaped else None)

    # the unloaded interactive-TTFT floor: the SAME tier policy at half
    # saturation, even shares (nothing sheds, nothing queues long)
    light = run_continuous(eng, workload(0.5 * sat, shaped=False),
                           max_wall_s=wall,
                           slo_s=slo_s, scheduler=sched(True))
    wl_on, wl_off = workload(rate), workload(rate)
    on_sched = sched(True)
    on = run_continuous(eng, wl_on, max_wall_s=wall, slo_s=slo_s,
                        scheduler=on_sched)
    off = run_continuous(eng, wl_off, max_wall_s=wall, slo_s=slo_s,
                         scheduler=sched(False))

    # bounded wait: every batch request terminal (finished / typed shed /
    # typed expiry) — the ladder may delay or shed batch, never strand it
    batch_on = [r for r in wl_on if r.tier == "batch"]
    stranded = [r.rid for r in batch_on
                if r.t_done is None
                and r.state not in (RequestState.REJECTED,
                                    RequestState.EXPIRED)]
    assert not stranded, f"batch requests stranded: {stranded}"

    # greedy agreement over the common prefix, tiered vs untiered (same
    # seeded workload; pairs where both sides produced tokens)
    pairs = [(a, b) for a, b in zip(wl_on, wl_off)
             if a.t_done is not None and b.t_done is not None]
    match = 0
    for a, b in pairs:
        ta, tb = a.tokens[:a.max_new_tokens], b.tokens[:b.max_new_tokens]
        n = min(len(ta), len(tb))
        match += ta[:n] == tb[:n]

    on_int = (on.get("by_tier") or {}).get("interactive") or {}
    light_int = (light.get("by_tier") or {}).get("interactive") or {}
    on_batch = (on.get("by_tier") or {}).get("batch") or {}
    off_int = (off.get("by_tier") or {}).get("interactive") or {}
    light_p99 = light_int.get("ttft_p99_ms") or float("nan")
    on_p99 = on_int.get("ttft_p99_ms") or float("nan")
    batch_shed_share = (on_batch.get("shed", 0) / on["shed"]
                        if on.get("shed") else None)
    return {
        "config": cfg["name"], "kind": "serving_tiered",
        "platform": platform, "model": cfg["model"], "num_slots": slots,
        "saturation_rps": round(sat, 3), "rate_rps": round(rate, 3),
        "slo_s": slo_s, "requests": 3 * n_per_tier,
        "tiers": sorted(tiers), "tier_shares": shares,
        "interactive_reserved_slots": tiers["interactive"].reserved_slots,
        # the headline: interactive under 2x overload vs its unloaded self
        "interactive_ttft_p99_ms": on_p99,
        "light_load_interactive_ttft_p99_ms": light_p99,
        "interactive_ttft_inflation": (round(on_p99 / light_p99, 3)
                                       if light_p99 == light_p99
                                       and light_p99 else None),
        "interactive_ttft_within_15pct": bool(on_p99 <= 1.15 * light_p99)
        if on_p99 == on_p99 and light_p99 == light_p99 else None,
        "interactive_miss_rate": on_int.get("deadline_miss_rate"),
        # who absorbed the overload
        "shed": on["shed"], "batch_shed": on_batch.get("shed"),
        "batch_shed_share": (round(batch_shed_share, 4)
                             if batch_shed_share is not None else None),
        "batch_finished": on_batch.get("finished"),
        "batch_preemptions": on_batch.get("preemptions"),
        "batch_stranded": 0,
        "brownout_transitions": on_sched.counters.get("tier_brownout", 0),
        "goodput_tokens_per_sec": on["goodput_tokens_per_sec"],
        "pool_audit_ok": on["pool_audit_ok"] and off["pool_audit_ok"]
        and light["pool_audit_ok"],
        # the tier-blind baseline on the same stream
        "untiered_interactive_ttft_p99_ms": off_int.get("ttft_p99_ms"),
        "untiered_interactive_miss_rate": off_int.get("deadline_miss_rate"),
        "untiered_shed": off["shed"],
        "untiered_goodput_tokens_per_sec": off["goodput_tokens_per_sec"],
        "greedy_match_rate": round(match / max(len(pairs), 1), 4),
        "greedy_pairs_compared": len(pairs),
        "tiered": on, "untiered": off, "light_load": light,
    }


def _worker_serving_lever(cfg: dict) -> dict:
    """A/B one serving-capacity lever on the SAME 2x-saturation Poisson
    workload (docs/SERVING.md "KV quantization & prefix caching"):

    - ``lever="kv8"`` — dense vs int8 KV pools at EQUAL HBM BYTES: the
      quantized pool re-divides the same byte budget into ~2x (fp32: 4x)
      the pages AND the decode slot count scales with it — the same
      KV-bytes-bound sizing the AOT fit ladder applies on a real chip
      (``serving_admission_limit(kv_bits=8)``), emulated here because CPU
      slots are not genuinely HBM-bound. More resident tokens + more slots
      = less queueing at saturation = higher goodput. Greedy agreement
      with the dense run is reported (the documented quantization
      tolerance: per-page int8 can flip rare near-tie argmaxes).
    - ``lever="prefix"`` — copy-on-write shared-prefix caching OFF vs ON on
      a chat-style workload (every request opens with the same
      ``prefix_len``-token system prompt): physical pages < logical pages,
      byte-identical outputs.
    - ``lever="spec"`` — speculative decoding OFF vs ON (n-gram
      self-drafting, adaptive k) at equal slots/pages. The row runs both
      sides at ``decode_block=1``: on CPU both the scan block and
      speculation amortize the same per-dispatch overhead, so the A/B
      isolates the speculation lever itself — the regime that stands in
      for the TPU's weight-bound decode, where a k+1-token verify reads
      the weights once and the block scan k+1 times (that orthogonal win
      is the TPU flagship row's). Reports ``accept_rate`` and
      ``tokens_per_dispatch`` next to the goodput/TTFT deltas, with
      greedy_match_rate as the equivalence gate (the verify fallback is
      bit-identical per position to sequential decode on dense pools, so
      the gate is expected at exactly 1.0).

    All variants report max-slots/pool pages, tokens/s + goodput, TTFT
    p50/p99, and the physical-vs-logical page ratio."""
    import numpy as np

    import jax

    from deepspeed_tpu.inference.serving import (Request, ServingConfig,
                                                 ServingEngine,
                                                 estimate_saturation_rps,
                                                 make_open_loop_workload,
                                                 run_continuous)
    from deepspeed_tpu.models import gpt as gpt_mod

    platform = jax.devices()[0].platform
    lever = cfg.get("lever", "kv8")
    mcfg = gpt_mod.PRESETS[cfg["model"]]
    params = gpt_mod.init_params(mcfg, jax.random.PRNGKey(0))
    slots = int(cfg.get("slots", 4))
    page_size = int(cfg.get("page_size", 16))
    max_len = int(cfg.get("max_model_len", 96))
    prompt_rng = tuple(cfg.get("prompt_range", (8, 24)))
    gen_rng = tuple(cfg.get("gen_range", (8, 24)))
    n_req = int(cfg.get("requests", 16))
    slo_s = float(cfg.get("slo_s", 3.0))
    dtype = cfg.get("dtype", "float32")
    prefix_len = int(cfg.get("prefix_len", 2 * page_size))
    # pool overcommitted (half of every-slot-maxes-out) so capacity actually
    # binds at 2x saturation — the regime the levers exist for
    base_kw = dict(page_size=page_size, max_model_len=max_len,
                   prefill_chunk=int(cfg.get("prefill_chunk", 32)),
                   dtype=dtype, max_queue=8 * slots,
                   request_deadline_s=slo_s,
                   decode_block=int(cfg.get("decode_block", 4)))
    pages_per_seq = -(-max_len // page_size)
    dense_pages = int(cfg.get("pool_pages",
                              max(pages_per_seq + 1,
                                  slots * pages_per_seq // 2)))

    def build(kv_bits=None, prefix=False, pages=dense_pages,
              num_slots=slots, spec=False):
        eng = ServingEngine(mcfg, params, ServingConfig(
            num_slots=num_slots, num_pages=pages + 1, kv_bits=kv_bits,
            enable_prefix_cache=prefix,
            spec_drafter=("ngram" if spec else None),
            spec_k=int(cfg.get("spec_k", 4)),
            spec_equivalence_harness=spec,  # this row IS the harness: it
            # reports greedy_match_rate against the spec-off side
            **base_kw))
        eng.warmup()
        return eng

    base_eng = build()
    sat = estimate_saturation_rps(base_eng, prompt_rng, gen_rng,
                                  mcfg.vocab_size)
    rate = float(cfg.get("overload_factor", 2.0)) * sat
    seed = int(cfg.get("seed", 5))

    def workload():
        wl = make_open_loop_workload(n_req, rate, prompt_rng, gen_rng,
                                     mcfg.vocab_size, seed=seed)
        if lever == "prefix":
            sysp = (np.arange(prefix_len, dtype=np.int32) * 7 + 3) \
                % mcfg.vocab_size
            wl = [Request(prompt=np.concatenate([sysp, r.prompt]),
                          max_new_tokens=r.max_new_tokens,
                          arrival_time=r.arrival_time) for r in wl]
        return wl

    wall = float(cfg.get("max_wall_s", 120.0))
    if lever == "kv8":
        # equal HBM BYTES: the int8 pool holds budget // bytes-per-page
        # pages (int8 payload + fp32 per-page scales), and the decode slot
        # count scales with the pool — the KV-bytes-bound sizing the AOT
        # fit ladder (serving_admission_limit(kv_bits=8)) applies on chip
        budget = dense_pages * page_size * base_eng.kv_bytes_per_token()
        q_per_tok = gpt_mod.paged_kv_bytes_per_token(mcfg, 8, page_size)
        q_pages = max(pages_per_seq + 1, int(budget
                                             // (page_size * q_per_tok)))
        q_slots = max(slots + 1, q_pages * slots // dense_pages)
        lever_eng = build(kv_bits=8, pages=q_pages, num_slots=q_slots)
    elif lever == "spec":
        # equal slots, equal pages: the ONLY difference is the drafter
        lever_eng = build(spec=True)
    else:
        lever_eng = build(prefix=True)
    wl_base, wl_lever = workload(), workload()
    base = run_continuous(base_eng, wl_base, max_wall_s=wall, slo_s=slo_s)
    lever_rep = run_continuous(lever_eng, wl_lever, max_wall_s=wall,
                               slo_s=slo_s)

    # greedy agreement request-by-request (both runs replay the same seeded
    # workload; requests unfinished on either side are skipped). Exact
    # per-request match is the strict bar; the mean common-prefix fraction
    # separates "rare near-tie argmax flip, then a diverged tail" from
    # genuinely different behavior (one early flip cascades the sequence)
    pairs = [(a, b) for a, b in zip(wl_base, wl_lever)
             if a.t_done is not None and b.t_done is not None]
    match = sum(a.tokens[:a.max_new_tokens] == b.tokens[:b.max_new_tokens]
                for a, b in pairs)
    prefix_agree = []
    for a, b in pairs:
        ta, tb = a.tokens[:a.max_new_tokens], b.tokens[:b.max_new_tokens]
        n = min(len(ta), len(tb))
        same = next((i for i in range(n) if ta[i] != tb[i]), n)
        prefix_agree.append(same / max(n, 1))

    spec_rep = lever_rep.get("spec") or {}
    return {
        "config": cfg["name"], "kind": "serving_lever", "lever": lever,
        "accept_rate": spec_rep.get("accept_rate"),
        "tokens_per_dispatch": spec_rep.get("tokens_per_dispatch"),
        "drafter": spec_rep.get("drafter"),
        "platform": platform, "model": cfg["model"],
        "num_slots": slots, "lever_num_slots": lever_eng.num_slots,
        "saturation_rps": round(sat, 3),
        "rate_rps": round(rate, 3), "slo_s": slo_s, "requests": n_req,
        "dense_pool_pages": dense_pages,
        "lever_pool_pages": lever_eng.num_pages - 1,
        "hbm_bytes_per_token_dense": round(base_eng.kv_bytes_per_token()),
        "hbm_bytes_per_token_lever": round(lever_eng.kv_bytes_per_token()),
        "tokens_per_sec": lever_rep["tokens_per_sec"],
        "goodput_tokens_per_sec": lever_rep["goodput_tokens_per_sec"],
        "ttft_p50_ms": lever_rep["ttft_p50_ms"],
        "ttft_p99_ms": lever_rep["ttft_p99_ms"],
        "physical_logical_page_ratio":
            lever_rep["physical_logical_page_ratio"],
        "preemptions": lever_rep["preemptions"],
        "baseline_tokens_per_sec": base["tokens_per_sec"],
        "baseline_goodput_tokens_per_sec": base["goodput_tokens_per_sec"],
        "baseline_ttft_p50_ms": base["ttft_p50_ms"],
        "baseline_ttft_p99_ms": base["ttft_p99_ms"],
        "baseline_preemptions": base["preemptions"],
        "pool_audit_ok": base["pool_audit_ok"] and lever_rep["pool_audit_ok"],
        "greedy_match_rate": round(match / max(len(pairs), 1), 4),
        "greedy_token_prefix_agreement": round(
            float(np.mean(prefix_agree)) if prefix_agree else 1.0, 4),
        "greedy_pairs_compared": len(pairs),
        "lever_run": lever_rep, "baseline_run": base,
    }


def _worker_serving_fleet(cfg: dict) -> dict:
    """Fleet overload A/B at 2x saturation (docs/SERVING.md "Fleet"):
    ``replicas`` router-fronted replica WORKER PROCESSES of ``slots``
    slots each versus ONE engine with the same total slots, pool pages,
    and admission bounds, on the same 2x-calibrated-saturation Poisson
    workload scored against one SLO. Each replica owns its compute (a
    process here, a chip allocation in production), and the router's
    two-phase pump runs their steps concurrently — so one replica's
    prefill never stalls another's decode, where the single engine
    serializes every prefill against all of its running slots. The chaos
    variant replays the same workload and SIGKILLs one replica
    mid-stream: the row reports survivor page audits, re-route counts,
    and the greedy match rate of surviving requests against the
    fault-free fleet run. ``replica_env`` ({name: value-with-{i}}) pins
    per-replica devices on multi-chip hosts."""
    import dataclasses as _dc
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from deepspeed_tpu.inference.fleet import (FleetConfig, ReplicaRouter,
                                               SubprocessReplica, run_fleet)
    from deepspeed_tpu.inference.serving import (ServingConfig, ServingEngine,
                                                 estimate_saturation_rps,
                                                 make_open_loop_workload,
                                                 run_continuous)
    from deepspeed_tpu.models import gpt as gpt_mod

    platform = jax.devices()[0].platform
    mcfg = gpt_mod.PRESETS[cfg["model"]]
    params = gpt_mod.init_params(mcfg, jax.random.PRNGKey(0))
    n_rep = int(cfg.get("replicas", 2))
    slots = int(cfg.get("slots", 2))          # per replica
    page_size = int(cfg.get("page_size", 16))
    max_len = int(cfg.get("max_model_len", 96))
    prompt_rng = tuple(cfg.get("prompt_range", (8, 32)))
    gen_rng = tuple(cfg.get("gen_range", (8, 24)))
    n_req = int(cfg.get("requests", 24))
    slo_s = float(cfg.get("slo_s", 3.0))
    dtype = cfg.get("dtype", "float32")
    pages_per_seq = -(-max_len // page_size)
    # per-replica pool, overcommitted so capacity binds at 2x saturation
    pool = int(cfg.get("pool_pages",
                       max(pages_per_seq + 1, slots * pages_per_seq // 2)))

    def serving_kw(num_slots, pages):
        # queues deep enough that the TTFT deadline — not the depth cap —
        # is the binding overload control: the A/B compares deadline
        # behavior, and a shallow cap would shed everything first
        return dict(
            num_slots=num_slots, num_pages=pages + 1, page_size=page_size,
            max_model_len=max_len,
            prefill_chunk=int(cfg.get("prefill_chunk", 32)), dtype=dtype,
            max_queue=int(cfg.get("queue_per_slot", 4)) * num_slots,
            ttft_deadline_s=slo_s / 2, request_deadline_s=slo_s)

    def build_engine(num_slots, pages):
        eng = ServingEngine(mcfg, params,
                            ServingConfig(**serving_kw(num_slots, pages)))
        eng.warmup()
        return eng

    model_dict = _dc.asdict(mcfg)

    def spawn(i):
        env = {k: str(v).format(i=i)
               for k, v in (cfg.get("replica_env") or {}).items()}
        return SubprocessReplica(f"r{i}", model_dict,
                                 serving_kw(slots, pool), seed=0,
                                 env=env or None)

    def build_fleet():
        # spawn concurrently: each ctor blocks on its worker's warmup
        with ThreadPoolExecutor(n_rep) as ex:
            reps = list(ex.map(spawn, range(n_rep)))
        return ReplicaRouter(reps, FleetConfig(
            reroute_budget=2, heartbeat_deadline_s=120.0))

    # equal-resources baseline: one scheduler over ALL the slots and pages
    single_eng = build_engine(n_rep * slots, n_rep * pool)
    sat = estimate_saturation_rps(single_eng, prompt_rng, gen_rng,
                                  mcfg.vocab_size)
    rate = float(cfg.get("overload_factor", 2.0)) * sat
    seed = int(cfg.get("seed", 5))

    def workload():
        return make_open_loop_workload(n_req, rate, prompt_rng, gen_rng,
                                       mcfg.vocab_size, seed=seed)

    wall = float(cfg.get("max_wall_s", 120.0))
    wl_single = workload()
    single = run_continuous(single_eng, wl_single, max_wall_s=wall,
                            slo_s=slo_s)

    router = build_fleet()
    wl_fleet = workload()
    fleet = run_fleet(router, wl_fleet, max_wall_s=wall, slo_s=slo_s)
    router.close()

    # chaos variant: identical workload, one replica killed mid-stream
    chaos_router = build_fleet()
    wl_chaos = workload()
    killed = {"done": False}
    kill_after = int(cfg.get("kill_after_tokens", 40))

    def on_step(rt, produced_total):
        if not killed["done"] and produced_total >= kill_after:
            victim = rt.replica("r0")
            if victim is not None and victim.alive:
                victim.kill()
                killed["done"] = True

    chaos = run_fleet(chaos_router, wl_chaos, max_wall_s=wall, slo_s=slo_s,
                      on_step=on_step)
    chaos_audit = chaos_router.audit_survivors()
    chaos_drained = all(r["allocated"] == 0
                        for r in chaos_audit["replicas"].values())
    chaos_router.close()
    # surviving (finished in both the fault-free fleet run and the
    # killed-replica run) requests must be greedy-IDENTICAL: failover is
    # recompute, not approximation
    pairs = [(a, b) for a, b in zip(wl_fleet, wl_chaos)
             if a.t_done is not None and b.t_done is not None]
    match = sum(a.tokens[:a.max_new_tokens] == b.tokens[:b.max_new_tokens]
                for a, b in pairs)

    return {
        "config": cfg["name"], "kind": "serving_fleet",
        "platform": platform, "model": cfg["model"],
        "replicas": n_rep, "slots_per_replica": slots,
        "total_slots": n_rep * slots, "pool_pages_per_replica": pool,
        "saturation_rps": round(sat, 3), "rate_rps": round(rate, 3),
        "slo_s": slo_s, "requests": n_req,
        "goodput_tokens_per_sec": fleet["goodput_tokens_per_sec"],
        "deadline_miss_rate": fleet["deadline_miss_rate"],
        "ttft_p50_ms": fleet["ttft_p50_ms"],
        "ttft_p99_ms": fleet["ttft_p99_ms"],
        "shed_rate": fleet["shed_rate"],
        "single_goodput_tokens_per_sec": single["goodput_tokens_per_sec"],
        "single_deadline_miss_rate": single["deadline_miss_rate"],
        "single_ttft_p50_ms": single["ttft_p50_ms"],
        "single_ttft_p99_ms": single["ttft_p99_ms"],
        "single_shed_rate": single["shed_rate"],
        "fleet_beats_single_goodput":
            fleet["goodput_tokens_per_sec"]
            > single["goodput_tokens_per_sec"],
        "fleet_beats_single_miss_rate":
            fleet["deadline_miss_rate"] < single["deadline_miss_rate"],
        "fleet_audit_ok": fleet["fleet_audit_ok"],
        # chaos: replica r0 killed mid-stream
        "chaos_killed": killed["done"],
        "chaos_reroutes": chaos["reroutes"],
        "chaos_survivor_audit_ok": bool(chaos_audit["ok"]),
        "chaos_survivor_pools_drained": bool(chaos_drained),
        "chaos_goodput_tokens_per_sec": chaos["goodput_tokens_per_sec"],
        "greedy_match_rate": round(match / max(len(pairs), 1), 4),
        "greedy_pairs_compared": len(pairs),
        "fleet_run": fleet, "single_run": single, "chaos_run": chaos,
    }


def _worker_serving_disagg(cfg: dict) -> dict:
    """Disaggregated prefill/decode A/B at 2x saturation (docs/SERVING.md
    "Tensor parallel & disaggregation"): a prefill-specialist replica
    fills KV pages and hands each request off to a decode-specialist
    over the subprocess wire, versus a COLOCATED fleet (same replica
    count, role="both") at equal TOTAL slots and pool pages on the same
    2x-calibrated-saturation prefill-heavy workload. Handoff is
    ownership transfer — the prefill worker exports the request's pages
    (quantized pages + per-page scales when kv_bits is set, so the wire
    payload shrinks with the pool) and frees them only after the decode
    side imports. Disaggregation also unlocks PER-ROLE sizing inside the
    fixed budget: the prefill specialist runs few slots and a small pool
    (pages live there only until handoff), the decode specialist takes
    the rest. The chaos variant replays the workload and SIGKILLs the
    prefill replica mid-stream: in-flight handoffs are orphaned, victims
    re-route through the role-fallback path (the decode survivor
    re-prefills them), and the row reports survivor audits + drained
    pools — zero page leaks. ``replica_env`` ({name: value-with-{i}})
    pins per-replica devices; ``tp`` shards each replica over chips."""
    import dataclasses as _dc
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from deepspeed_tpu.inference.fleet import (FleetConfig, ReplicaRouter,
                                               SubprocessReplica, run_fleet)
    from deepspeed_tpu.inference.serving import (ServingConfig, ServingEngine,
                                                 estimate_saturation_rps,
                                                 make_open_loop_workload)
    from deepspeed_tpu.models import gpt as gpt_mod

    platform = jax.devices()[0].platform
    mcfg = gpt_mod.PRESETS[cfg["model"]]
    params = gpt_mod.init_params(mcfg, jax.random.PRNGKey(0))
    slots = int(cfg.get("slots", 2))          # per colocated replica
    page_size = int(cfg.get("page_size", 16))
    max_len = int(cfg.get("max_model_len", 96))
    prompt_rng = tuple(cfg.get("prompt_range", (64, 112)))
    gen_rng = tuple(cfg.get("gen_range", (4, 8)))
    n_req = int(cfg.get("requests", 24))
    slo_s = float(cfg.get("slo_s", 4.0))
    dtype = cfg.get("dtype", "float32")
    kv_bits = cfg.get("kv_bits")
    tp = int(cfg.get("tp", 1))
    pages_per_seq = -(-max_len // page_size)
    pool = int(cfg.get("pool_pages",
                       max(pages_per_seq + 1, slots * pages_per_seq // 2)))
    # per-role split of the SAME total budget (2*slots, 2*pool). Equal by
    # default: the prefill side holds each request only until handoff,
    # but a staged handoff keeps BOTH its slot and its pages parked until
    # the router forwards it (export-before-free), so starving the
    # prefill replica of either serializes admissions. The knobs let a
    # row skew the split where the roles' residencies actually differ.
    p_slots = int(cfg.get("prefill_slots", slots))
    d_slots = 2 * slots - p_slots
    p_pool = int(cfg.get("prefill_pool", pool))
    d_pool = 2 * pool - p_pool

    def serving_kw(num_slots, pages, role="both"):
        # queue depth = admission control, the binding overload lever at
        # 2x saturation: per-replica front doors on both sides so the
        # excess sheds early and accepted requests stay inside the SLO.
        # The one exception is the decode specialist: its queue is NOT an
        # admission door — the router only forwards staged handoffs
        # there, and a refusal costs a re-prefill fallback on the
        # bottleneck prefill replica — so it gets system depth and must
        # never refuse.
        qps = int(cfg.get("queue_per_slot", 4))
        kw = dict(
            num_slots=num_slots, num_pages=pages + 1, page_size=page_size,
            max_model_len=max_len,
            max_queue=qps * (2 * slots if role == "decode" else num_slots),
            prefill_chunk=int(cfg.get("prefill_chunk", 32)), dtype=dtype,
            ttft_deadline_s=slo_s / 2, request_deadline_s=slo_s, role=role)
        if kv_bits:
            kw["kv_bits"] = int(kv_bits)
        if tp > 1:
            kw["tp"] = tp
        return kw

    model_dict = _dc.asdict(mcfg)

    def spawn(i, role, num_slots, pages):
        env = {k: str(v).format(i=i)
               for k, v in (cfg.get("replica_env") or {}).items()}
        return SubprocessReplica(f"{role[0]}{i}", model_dict,
                                 serving_kw(num_slots, pages, role), seed=0,
                                 env=env or None)

    def build_fleet(specs):
        with ThreadPoolExecutor(len(specs)) as ex:
            reps = list(ex.map(lambda s: spawn(*s), specs))
        return ReplicaRouter(reps, FleetConfig(
            reroute_budget=2, heartbeat_deadline_s=120.0))

    coloc_specs = [(0, "both", slots, pool), (1, "both", slots, pool)]
    disagg_specs = [(0, "prefill", p_slots, p_pool),
                    (1, "decode", d_slots, d_pool)]

    # calibrate saturation once on an equal-total-resources local engine
    cal = ServingEngine(mcfg, params,
                        ServingConfig(**serving_kw(2 * slots, 2 * pool)))
    cal.warmup()
    sat = estimate_saturation_rps(cal, prompt_rng, gen_rng, mcfg.vocab_size)
    del cal
    rate = float(cfg.get("overload_factor", 2.0)) * sat
    seed = int(cfg.get("seed", 5))

    def workload():
        return make_open_loop_workload(n_req, rate, prompt_rng, gen_rng,
                                       mcfg.vocab_size, seed=seed)

    wall = float(cfg.get("max_wall_s", 120.0))

    coloc_router = build_fleet(coloc_specs)
    wl_coloc = workload()
    coloc = run_fleet(coloc_router, wl_coloc, max_wall_s=wall, slo_s=slo_s)
    coloc_router.close()

    disagg_router = build_fleet(disagg_specs)
    wl_disagg = workload()
    disagg = run_fleet(disagg_router, wl_disagg, max_wall_s=wall, slo_s=slo_s)
    disagg_router.close()

    # chaos variant: identical workload, prefill specialist SIGKILLed
    # mid-stream — orphaned handoffs and queued victims must re-route to
    # the decode survivor through role fallback, with no leaked pages
    chaos_router = build_fleet(disagg_specs)
    wl_chaos = workload()
    killed = {"done": False}
    kill_after = int(cfg.get("kill_after_tokens", 8))

    def on_step(rt, produced_total):
        if not killed["done"] and produced_total >= kill_after:
            victim = rt.replica("p0")
            if victim is not None and victim.alive:
                victim.kill()
                killed["done"] = True

    chaos = run_fleet(chaos_router, wl_chaos, max_wall_s=wall, slo_s=slo_s,
                      on_step=on_step)
    chaos_audit = chaos_router.audit_survivors()
    chaos_drained = all(r["allocated"] == 0
                        for r in chaos_audit["replicas"].values())
    chaos_router.close()
    # surviving requests (finished in both the fault-free disagg run and
    # the killed-prefill run) must be greedy-IDENTICAL: failover is
    # re-prefill of the kept tokens, not approximation
    pairs = [(a, b) for a, b in zip(wl_disagg, wl_chaos)
             if a.t_done is not None and b.t_done is not None]
    match = sum(a.tokens[:a.max_new_tokens] == b.tokens[:b.max_new_tokens]
                for a, b in pairs)

    return {
        "config": cfg["name"], "kind": "serving_disagg",
        "platform": platform, "model": cfg["model"],
        "tp": tp, "kv_bits": kv_bits,
        "total_slots": 2 * slots, "total_pool_pages": 2 * pool,
        "prefill_slots": p_slots, "decode_slots": d_slots,
        "prefill_pool_pages": p_pool, "decode_pool_pages": d_pool,
        "saturation_rps": round(sat, 3), "rate_rps": round(rate, 3),
        "slo_s": slo_s, "requests": n_req,
        "handoffs_forwarded":
            disagg["fleet_counters"].get("handoff_forwarded", 0),
        "handoff_fallbacks":
            disagg["fleet_counters"].get("handoff_fallback", 0),
        "goodput_tokens_per_sec": disagg["goodput_tokens_per_sec"],
        "deadline_miss_rate": disagg["deadline_miss_rate"],
        "ttft_p50_ms": disagg["ttft_p50_ms"],
        "ttft_p99_ms": disagg["ttft_p99_ms"],
        "shed_rate": disagg["shed_rate"],
        "colocated_goodput_tokens_per_sec": coloc["goodput_tokens_per_sec"],
        "colocated_deadline_miss_rate": coloc["deadline_miss_rate"],
        "colocated_ttft_p50_ms": coloc["ttft_p50_ms"],
        "colocated_ttft_p99_ms": coloc["ttft_p99_ms"],
        "colocated_shed_rate": coloc["shed_rate"],
        "disagg_beats_colocated_goodput":
            disagg["goodput_tokens_per_sec"]
            >= coloc["goodput_tokens_per_sec"],
        "disagg_beats_colocated_ttft_p99":
            disagg["ttft_p99_ms"] < coloc["ttft_p99_ms"],
        "disagg_audit_ok": disagg["fleet_audit_ok"],
        "colocated_audit_ok": coloc["fleet_audit_ok"],
        # chaos: prefill specialist p0 killed mid-stream
        "chaos_killed": killed["done"],
        "chaos_reroutes": chaos["reroutes"],
        "chaos_orphaned_handoffs":
            chaos["fleet_counters"].get("handoff_fallback", 0),
        "chaos_survivor_audit_ok": bool(chaos_audit["ok"]),
        "chaos_survivor_pools_drained": bool(chaos_drained),
        "chaos_goodput_tokens_per_sec": chaos["goodput_tokens_per_sec"],
        "greedy_match_rate": round(match / max(len(pairs), 1), 4),
        "greedy_pairs_compared": len(pairs),
        "disagg_run": disagg, "colocated_run": coloc, "chaos_run": chaos,
    }


def _worker_diffusion(cfg: dict) -> dict:
    """Stable-Diffusion latent inference (BASELINE.json config #5) on the
    FAITHFUL SD-1.x architecture (CrossAttn UNet + AutoencoderKL decoder):
    full DDIM scan + CFG + VAE decode as one compiled program; reports
    per-image latency. ``arch: "skeleton"`` selects the lightweight model."""
    import numpy as np

    import jax

    platform = jax.devices()[0].platform
    if cfg.get("arch", "sd15") == "skeleton":
        from deepspeed_tpu.models.diffusion import (
            StableDiffusionPipeline, UNetConfig, VAEDecoderConfig)

        pipe = StableDiffusionPipeline.init_random(
            jax.random.PRNGKey(0),
            unet_cfg=UNetConfig(base_channels=cfg.get("base_channels", 128),
                                channel_mults=(1, 2, 4),
                                text_dim=cfg.get("text_dim", 256), n_head=8),
            vae_cfg=VAEDecoderConfig(base_channels=64, upsamples=3),
            latent_size=cfg.get("latent", 32))
        text_dim = pipe.unet_cfg.text_dim
    else:
        from deepspeed_tpu.models.sd_unet import (
            SDPipeline, SDUNetConfig, SDVAEDecoderConfig, init_sd_unet,
            init_sd_vae_decoder)

        chans = tuple(cfg.get("channels", (128, 256, 512)))
        groups = min(32, min(chans))
        ucfg = SDUNetConfig(
            block_out_channels=chans,
            cross_attn=tuple(i < len(chans) - 1 for i in range(len(chans))),
            cross_attention_dim=cfg.get("text_dim", 512), n_head=8,
            norm_groups=groups)
        vcfg = SDVAEDecoderConfig(
            block_out_channels=tuple(max(c // 2, groups) for c in chans),
            norm_groups=groups)
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        pipe = SDPipeline(ucfg, vcfg, init_sd_unet(ucfg, k1),
                          init_sd_vae_decoder(vcfg, k2),
                          latent_size=cfg.get("latent", 32))
        text_dim = ucfg.cross_attention_dim
    rng = np.random.default_rng(0)
    B, S = cfg.get("batch", 1), 77
    text = np.asarray(rng.normal(size=(B, S, text_dim)), np.float32)
    uncond = np.asarray(rng.normal(size=(B, S, text_dim)), np.float32)
    steps = cfg.get("ddim_steps", 20)
    img = pipe(text, uncond, num_steps=steps)  # warmup/compile
    lat = []
    for i in range(cfg.get("reps", 3)):
        t0 = time.perf_counter()
        img = pipe(text, uncond, num_steps=steps, seed=i)
        lat.append((time.perf_counter() - t0) / B * 1e3)
    lat.sort()
    return {
        "config": cfg["name"], "kind": "diffusion", "platform": platform,
        "image_ms_p50": round(lat[len(lat) // 2], 1),
        "ddim_steps": steps, "batch": B,
        "image_px": int(img.shape[1]),
    }


def _worker_kernels_aot(cfg: dict) -> dict:
    """Mosaic-compile every Pallas kernel against the v5e TPU compiler on the
    host — the kernel smoke without a chip. A kernel that fails HERE would
    fail on hardware (same compiler)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.runtime.topology import MeshTopology, mesh_context

    os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"
    td = topologies.get_topology_desc(
        platform="tpu", topology_name=cfg.get("topology", "v5e:2x2"))
    topo = MeshTopology.create(dp=1, devices=list(td.devices)[:1])
    rep = NamedSharding(topo.mesh, P())

    def a(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    B, H, S, Dh = 4, 16, 1024, 64
    q4 = a((B, S, H, Dh))
    results, failed = {}, []

    def check(name, fn, *args):
        try:
            t0 = time.perf_counter()
            with mesh_context(topo.mesh):
                jax.jit(fn).lower(*args).compile()
            results[name] = {"ok": True,
                             "compile_s": round(time.perf_counter() - t0, 1)}
        except Exception as e:
            results[name] = {"ok": False, "error": str(e)[-300:]}
            failed.append(name)

    from deepspeed_tpu.ops.pallas.blocksparse_attention import (
        blocksparse_attention)
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig

    check("flash_attention",
          lambda q, k, v: flash_attention(q, k, v, causal=True), q4, q4, q4)
    check("flash_attention_bwd",
          jax.grad(lambda q, k, v: flash_attention(q, k, v, causal=True)
                   .astype(jnp.float32).sum()), q4, q4, q4)
    check("flash_attention_stochastic",
          lambda q, k, v: flash_attention(q, k, v, causal=True,
                                          stochastic_mode=True), q4, q4, q4)
    check("decode_attention",
          lambda q, k, v, n: decode_attention(q, k, v, n),
          a((B, 1, H, Dh)), a((B, H, S, Dh)), a((B, H, S, Dh)),
          a((), jnp.int32))
    layout = np.asarray(
        FixedSparsityConfig(num_heads=H, block=128).make_layout(S))
    check("blocksparse_attention",
          lambda q, k, v: blocksparse_attention(q, k, v, layout=layout,
                                                block=128), q4, q4, q4)
    check("blocksparse_attention_bwd",
          jax.grad(lambda q, k, v: blocksparse_attention(
              q, k, v, layout=layout, block=128)
              .astype(jnp.float32).sum()), q4, q4, q4)
    from deepspeed_tpu.ops.pallas.int8_matmul import int4_matmul, int8_matmul

    check("int8_matmul",
          lambda x, qq, s: int8_matmul(x, qq, s, group_size=128),
          a((8, 512)), a((512, 1536), jnp.int8),
          a((512 * 1536 // 128,), jnp.float32))
    check("int4_matmul",
          lambda x, qq, s: int4_matmul(x, qq, s, group_size=128),
          a((8, 512)), a((512, 1536), jnp.int8),
          a((512 * 3072 // 128,), jnp.float32))
    out = {"config": cfg["name"], "kind": "kernels_aot",
           "platform": "tpu-compile-only", "kernels": results}
    if failed:
        out["error"] = "Mosaic v5e compile failed: " + ", ".join(failed)
    return out


def _worker_infinity_aot(cfg: dict) -> dict:
    """AOT evidence for the ZeRO-Infinity streaming schedule: the five
    stream programs plus the schedule's two peak MOMENTS compiled whole
    (all resident buffers as program arguments), so peak_bytes is the XLA
    compiler's own accounting, with a fragmentation-margin verdict (core:
    deepspeed_tpu.runtime.aot.infinity_program_report — closes the r4
    'peak_bytes: null / est' gap, VERDICT r4 next #4)."""
    from deepspeed_tpu.runtime.aot import infinity_program_report

    rep = infinity_program_report(
        cfg.get("model", "gpt-neox-6.7b"),
        topology=cfg.get("topology", "v5e:2x2"),
        micro_bs=int(cfg.get("micro_bs", 8)), seq=int(cfg.get("seq", 1024)),
        keep_layers=int(cfg.get("keep_layers", 2)),
        # streamed-schedule accounting (docs/OFFLOAD.md): the fit verdict
        # includes the d in-flight prefetch buffers, itemized under "stream"
        prefetch_depth=int(cfg.get("prefetch_depth", 2)),
        quantized_fetch=bool(cfg.get("quantized_fetch", False)))
    return {"config": cfg["name"], "kind": "infinity_aot",
            "platform": "tpu-compile-only", **rep}


def _aot_fused_step(model, optimizer, gas: int = 1, k_steps: int = 1):
    """Engine-shaped fused step; single definition lives in the package
    (deepspeed_tpu.runtime.aot.fused_train_step) so every AOT producer —
    these bench rows, bin/ds_aot, tests — compiles identical semantics."""
    from deepspeed_tpu.runtime.aot import fused_train_step

    return fused_train_step(model, optimizer, gas=gas, k_steps=k_steps)


def _aot_report(compiled, compile_s: float) -> dict:
    from deepspeed_tpu.runtime.aot import report_from_compiled

    return report_from_compiled(compiled, compile_s)


def _worker_pipeline_aot(cfg: dict) -> dict:
    """AOT-compile the pp=2 SPMD pipeline training step against a REAL TPU
    (v5e) topology — the XLA TPU compiler runs on the host, no chips needed —
    and report the compiler's per-device memory analysis + program
    FLOPs (VERDICT r3 next #4). The program is the engine-shaped fused step:
    pipelined loss (collective-permute schedule), grads, global-norm clip,
    AdamW on the fp32 master, bf16 copy-back, ZeRO-1 sharded optimizer state.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models import gpt as gpt_mod
    from deepspeed_tpu.ops.optimizers import get_optimizer
    from deepspeed_tpu.runtime.topology import MeshTopology, mesh_context
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
    from deepspeed_tpu.runtime.zero.policy import ZeroShardingPolicy

    import dataclasses

    topo_name = cfg.get("topology", "v5e:2x2")
    pp, dp = int(cfg.get("pp", 2)), int(cfg.get("dp", 2))
    td = topologies.get_topology_desc(platform="tpu", topology_name=topo_name)
    topo = MeshTopology.create(dp=dp, pp=pp, devices=list(td.devices))

    # compile the REAL chip program: Mosaic flash kernels, not the CPU-process
    # interpret fallback (which would misrepresent memory AND OOM the compiler
    # on [T,T] dense-attention scores)
    os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"
    mcfg = gpt_mod.PRESETS[cfg.get("model", "gpt2-350m")]
    mcfg = dataclasses.replace(mcfg, remat=True, use_flash=True)
    base_model, _ = build_gpt(mcfg)
    M = int(cfg.get("num_micro", 2 * pp))
    model = base_model.to_pipeline(pp, M)
    micro_bs, seq = int(cfg.get("micro_bs", 8)), int(cfg.get("seq", 1024))
    B = micro_bs * M * dp

    rng = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(model.init, rng)
    base_specs = model.specs(shapes)
    policy = ZeroShardingPolicy(topo, DeepSpeedZeroConfig(stage=1))
    tmap = jax.tree_util.tree_map
    pspec = tmap(lambda s, b: policy.param_spec(s.shape, b), shapes, base_specs)
    ospec = tmap(lambda s, b: policy.opt_spec(s.shape, b), shapes, base_specs)
    sh = lambda spec: NamedSharding(topo.mesh, spec)  # noqa: E731
    optimizer = get_optimizer("AdamW", {"lr": 3e-4, "weight_decay": 0.1})
    opt_shapes = jax.eval_shape(optimizer.init, shapes)
    step = _aot_fused_step(model, optimizer)

    def abstract(tree_shapes, spec_tree, dtype=None):
        return tmap(
            lambda s, p: jax.ShapeDtypeStruct(
                s.shape, dtype or s.dtype, sharding=sh(p)),
            tree_shapes, spec_tree)

    a_params = abstract(shapes, pspec, jnp.bfloat16)
    a_master = abstract(shapes, ospec, jnp.float32)
    # optimizer-state placement EXACTLY as the engine does it
    # (engine.py state_spec call): per-param leaves carry the opt specs
    # (incl. the pp placement of block moments), scalars replicate
    opt_spec_tree = optimizer.state_spec(
        tmap(lambda p: sh(p), ospec), sh(P()))
    a_opt = tmap(
        lambda s, shd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shd),
        opt_shapes, opt_spec_tree)
    a_batch = {"input_ids": jax.ShapeDtypeStruct(
        (B, seq), jnp.int32, sharding=sh(topo.batch_spec(1)))}
    a_rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sh(P()))

    with mesh_context(topo.mesh):
        t0 = time.perf_counter()
        try:
            # donation mirrors the engine's fused step (state buffers aliased)
            compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
                a_params, a_master, a_opt, a_batch, a_rng).compile()
        except Exception as e:
            return {"config": cfg["name"], "kind": "pipeline_aot",
                    "platform": "tpu-compile-only", "topology": topo_name,
                    "pp": pp, "dp": dp, "num_micro": M, "micro_bs": micro_bs,
                    "seq": seq, "model": cfg.get("model", "gpt2-350m"),
                    **_aot_oom_row(e)}
        compile_s = time.perf_counter() - t0
    # note: the pipeline bubble M/(M+pp-1) is already in the program's schedule
    return {
        "config": cfg["name"], "kind": "pipeline_aot",
        "platform": "tpu-compile-only", "topology": topo_name,
        "pp": pp, "dp": dp, "num_micro": M, "micro_bs": micro_bs, "seq": seq,
        "model": cfg.get("model", "gpt2-350m"),
        **_aot_report(compiled, compile_s),
    }


def _worker_train_aot(cfg: dict) -> dict:
    """AOT-compile a dense training config against the v5e topology (no
    chips needed): per-device HBM breakdown + program FLOPs, or a
    structured compile-time OOM verdict. Core lives in
    deepspeed_tpu.runtime.aot.train_program_report (also behind bin/ds_aot)."""
    from deepspeed_tpu.runtime.aot import train_program_report

    rep = train_program_report(
        cfg["model"],
        topology=cfg.get("topology", "v5e:2x2"),
        dp=int(cfg.get("dp", 1)), tp=int(cfg.get("tp", 1)),
        sp=int(cfg.get("sp", 1)), stage=int(cfg.get("stage", 1)),
        micro_bs=int(cfg.get("micro_bs", 16)), seq=int(cfg.get("seq", 1024)),
        gas=int(cfg.get("gas", 1)), k_steps=int(cfg.get("k_steps", 1)),
        remat_policy=cfg.get("remat_policy"),
        loss_chunk=int(cfg.get("loss_chunk", 0)),
        seq_parallel_impl=cfg.get("seq_parallel_impl"))
    return {"config": cfg["name"], "kind": "train_aot",
            "platform": "tpu-compile-only", **rep}


def _worker_infer_aot(cfg: dict) -> dict:
    """AOT-compile the generate-shaped decode program against the v5e
    topology: KV-cache-dominated HBM fit + per-token FLOPs evidence with no
    chips (core: deepspeed_tpu.runtime.aot.decode_program_report)."""
    from deepspeed_tpu.runtime.aot import decode_program_report

    rep = decode_program_report(
        cfg.get("model", "gpt2-350m"),
        topology=cfg.get("topology", "v5e:2x2"),
        batch=int(cfg.get("batch", 1)), prompt=int(cfg.get("prompt", 128)),
        gen=int(cfg.get("gen", 64)),
        cache_dtype=cfg.get("cache_dtype", "bfloat16"),
        quantize_bits=int(cfg.get("quantize_bits", 0)))
    return {"config": cfg["name"], "kind": "infer_aot",
            "platform": "tpu-compile-only", **rep}


def _worker_sd_aot(cfg: dict) -> dict:
    """AOT-compile the full SD inference program (DDIM scan + CFG UNet + VAE
    decode) against the v5e topology (core: runtime.aot.sd_program_report)."""
    from deepspeed_tpu.runtime.aot import sd_program_report

    rep = sd_program_report(
        topology=cfg.get("topology", "v5e:2x2"),
        batch=int(cfg.get("batch", 1)), latent=int(cfg.get("latent", 32)),
        ddim_steps=int(cfg.get("ddim_steps", 20)),
        channels=tuple(cfg.get("channels", (128, 256, 512))),
        text_dim=int(cfg.get("text_dim", 512)))
    return {"config": cfg["name"], "kind": "sd_aot",
            "platform": "tpu-compile-only", **rep}


def _aot_oom_row(e: Exception) -> dict:
    from deepspeed_tpu.runtime.aot import oom_row

    return oom_row(e)


def _worker_moe_aot(cfg: dict) -> dict:
    """AOT-compile the MoE expert-parallel training step (ep over the v5e
    mesh: expert bank sharded, gating all-to-alls over ICI) against the v5e
    compiler — BASELINE config #4's program shape, no chips needed."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.models import build_gpt_moe
    from deepspeed_tpu.ops.optimizers import get_optimizer
    from deepspeed_tpu.runtime.topology import MeshTopology, mesh_context
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
    from deepspeed_tpu.runtime.zero.policy import ZeroShardingPolicy

    os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"
    td = topologies.get_topology_desc(
        platform="tpu", topology_name=cfg.get("topology", "v5e:2x2"))
    ep, dp = int(cfg.get("ep", 4)), int(cfg.get("dp", 1))
    topo = MeshTopology.create(dp=dp, ep=ep, devices=list(td.devices)[:dp * ep])
    model, mcfg = build_gpt_moe(cfg.get("model", "moe-125m-8e"))
    micro_bs = int(cfg.get("micro_bs", 4))
    seq = int(cfg.get("seq", 1024))
    B = micro_bs * dp * ep  # batch rides the (dp, ep) axes

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    base_specs = model.specs(shapes)
    policy = ZeroShardingPolicy(topo, DeepSpeedZeroConfig(
        stage=int(cfg.get("stage", 1))))
    tmap = jax.tree_util.tree_map
    sh = lambda spec: NamedSharding(topo.mesh, spec)  # noqa: E731
    pspec = tmap(lambda s, b: policy.param_spec(s.shape, b), shapes, base_specs)
    ospec = tmap(lambda s, b: policy.opt_spec(s.shape, b), shapes, base_specs)
    optimizer = get_optimizer("AdamW", {"lr": 3e-4, "weight_decay": 0.1})
    opt_shapes = jax.eval_shape(optimizer.init, shapes)
    step = _aot_fused_step(model, optimizer)

    def abstract(tree_shapes, spec_tree, dtype=None):
        return tmap(lambda s, p: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=sh(p)), tree_shapes, spec_tree)

    opt_spec_tree = optimizer.state_spec(tmap(lambda p: sh(p), ospec), sh(P()))
    a_opt = tmap(lambda s, shd: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=shd), opt_shapes, opt_spec_tree)
    a_batch = {"input_ids": jax.ShapeDtypeStruct(
        (B, seq), jnp.int32, sharding=sh(topo.batch_spec(1)))}
    a_rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sh(P()))
    out = {"config": cfg["name"], "kind": "moe_aot",
           "platform": "tpu-compile-only",
           "model": cfg.get("model", "moe-125m-8e"),
           "ep": ep, "dp": dp, "micro_bs": micro_bs, "seq": seq}
    with mesh_context(topo.mesh):
        t0 = time.perf_counter()
        try:
            compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
                abstract(shapes, pspec, jnp.bfloat16),
                abstract(shapes, ospec, jnp.float32),
                a_opt, a_batch, a_rng).compile()
        except Exception as e:
            out.update(_aot_oom_row(e))
            return out
        compile_s = time.perf_counter() - t0
    out.update(_aot_report(compiled, compile_s))
    return out


def _worker_pipeline_schedule(cfg: dict) -> dict:
    """Static schedule comparison (ISSUE 18): generate 1F1B, interleaved,
    and zero-bubble IRs at equal microbatches on the 8-device mesh shape,
    prove each with the pipeline-schedule prover, and report the static
    bubble %% + priced peak residency side by side. Pure host math — the
    whole point is that this verdict is available before any compile or
    dispatch."""
    import jax

    from deepspeed_tpu.analysis.schedule import prove_schedule
    from deepspeed_tpu.runtime.aot import pipeline_schedule_report
    from deepspeed_tpu.runtime.pipe.mpmd import (
        generate_1f1b_ir, generate_interleaved_ir, generate_zero_bubble_ir)

    platform = jax.devices()[0].platform
    S = int(cfg.get("stages", 8))
    M = int(cfg.get("num_micro", 16))
    V = int(cfg.get("vstages", 2))
    mb = int(cfg.get("micro_bs", 4))
    seq = int(cfg.get("seq", 1024))
    d_model = int(cfg.get("d_model", 1024))
    act_bytes = mb * seq * d_model * 2  # one bf16 stage-input activation

    rows = {}
    for ir in (generate_1f1b_ir(M, S),
               generate_interleaved_ir(M, S, num_vstages=V),
               generate_zero_bubble_ir(M, S)):
        rep = pipeline_schedule_report(ir, activation_bytes=act_bytes)
        kind = ir.name.split("[")[0]
        rows[kind] = {
            "schedule": ir.name,
            "proof_ok": rep["proof_ok"],
            "n_findings": len(rep["findings"]),
            "bubble_frac": rep["bubble_frac"],
            "peak_activation_buffers": rep["peak_activation_buffers"],
            "peak_schedule_bytes": rep["peak_schedule_bytes"],
            "confidence": rep.get("confidence"),
        }
    zb, il, f1 = (rows["zero-bubble"]["bubble_frac"],
                  rows["interleaved"]["bubble_frac"],
                  rows["1f1b"]["bubble_frac"])
    return {
        "config": cfg["name"], "kind": "pipeline_schedule",
        "platform": platform, "n_devices": len(jax.devices()),
        "num_stages": S, "num_micro": M, "vstages": V,
        "activation_bytes": act_bytes,
        "schedules": rows,
        "all_proven": all(r["proof_ok"] for r in rows.values()),
        "zero_bubble_beats_1f1b": bool(zb < f1),
        "interleaved_beats_1f1b": bool(il < f1),
        "bubble_reduction_vs_1f1b": {
            "interleaved": round(1.0 - il / f1, 4) if f1 else None,
            "zero-bubble": round(1.0 - zb / f1, 4) if f1 else None,
        },
    }


def _worker_pipeline_mpmd(cfg: dict) -> dict:
    """MPMD 1F1B interpreter dispatch microbench (VERDICT r3 weak #5): run a
    2-stage PipelineModule's slot loop on the available device(s) and compare
    its steady-state step time against ONE fused jit doing the identical
    compute — the gap is the per-slot host-dispatch + buffer-rotation cost the
    Python interpreter adds. Stages share a device when only one chip exists
    (correctness-preserving; the overhead measurement is what matters here)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.runtime.pipe.module import LayerSpec, PipelineModule
    from deepspeed_tpu.runtime.pipe.mpmd import MPMDPipelineEngine

    platform = jax.devices()[0].platform
    d = int(cfg.get("d_model", 1024))
    n_blocks = int(cfg.get("n_blocks", 24))
    S, M = int(cfg.get("stages", 2)), int(cfg.get("num_micro", 4))
    mb, T = int(cfg.get("micro_bs", 4)), int(cfg.get("seq", 512))
    steps = int(cfg.get("steps", 8))

    def mlp_init(rng):
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (d, 4 * d), jnp.bfloat16) * 0.02,
                "w2": jax.random.normal(k2, (4 * d, d), jnp.bfloat16) * 0.02}

    def mlp_apply(w, x):
        return x + jnp.tanh(x @ w["w1"]) @ w["w2"]

    def loss_fn(y, mb_):
        return jnp.mean(y.astype(jnp.float32) ** 2)

    specs = [LayerSpec(mlp_init, mlp_apply, name=f"blk{i}",
                       param_count=8 * d * d) for i in range(n_blocks)]
    module = PipelineModule(specs, num_stages=S, partition_method="uniform",
                            loss_fn=loss_fn)
    devs = [jax.devices()[i % len(jax.devices())] for i in range(S)]
    eng = MPMDPipelineEngine(
        module, num_micro=M, devices=devs,
        optimizer=(lambda p: (), lambda g, s, p=None: (g, s)))
    params = eng.init(jax.random.PRNGKey(0))
    opt_state = eng.init_optimizer(params)
    # batch leaves are [M, mb, ...]; a bare array feeds stage 0 directly
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (M, mb, T, d)), jnp.bfloat16)

    _, _, metrics = eng.train_batch(params, opt_state, x, apply_update=False)
    jax.block_until_ready(metrics["loss"])  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(steps):
        _, _, metrics = eng.train_batch(params, opt_state, x,
                                        apply_update=False)
    jax.block_until_ready(
        (metrics["loss"], jax.tree_util.tree_leaves(metrics["grads"])[0]))
    mpmd_ms = (time.perf_counter() - t0) / steps * 1e3

    # identical compute as ONE fused program: all blocks, all micro-batches
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[mlp_init(k) for k in jax.random.split(
            jax.random.PRNGKey(0), n_blocks)])

    def fused(w, xs):
        def body(h, lw):
            return mlp_apply(lw, h), None

        def one(mb_x):
            h, _ = jax.lax.scan(body, mb_x, w)
            return jnp.mean(h.astype(jnp.float32) ** 2)

        return jnp.mean(jax.vmap(one)(xs))

    fused_vg = jax.jit(jax.value_and_grad(fused))
    l2, g2 = fused_vg(stacked, x)
    jax.block_until_ready(l2)
    t0 = time.perf_counter()
    for _ in range(steps):
        l2, g2 = fused_vg(stacked, x)
    jax.block_until_ready((l2, jax.tree_util.tree_leaves(g2)[0]))
    fused_ms = (time.perf_counter() - t0) / steps * 1e3

    return {
        "config": cfg["name"], "kind": "pipeline_mpmd", "platform": platform,
        "stages": S, "num_micro": M, "micro_bs": mb, "seq": T, "d_model": d,
        "n_blocks": n_blocks, "devices": len(set(devs)),
        "mpmd_step_ms": round(mpmd_ms, 1),
        "fused_step_ms": round(fused_ms, 1),
        "dispatch_overhead_ms": round(mpmd_ms - fused_ms, 1),
        "overhead_pct": round((mpmd_ms - fused_ms) / fused_ms * 100, 1),
    }


# ---------------------------------------------------------------- parent side

def tpu_core_configs() -> list:
    """The measured TPU sweep (order = evidence priority) + AOT fit rows."""
    model = os.environ.get("BENCH_MODEL", "gpt2-350m")
    bs = int(os.environ.get("BENCH_BS", "16"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    # k_steps=8 + fewer outer dispatches: same measured optimizer steps,
    # 1/8th the dispatches. k_steps (full steps scanned in-program) not gas:
    # the gas-8 fp32 accumulator AOT-OOMs the lead 760M geometries.
    steps = int(os.environ.get("BENCH_STEPS", "5"))
    kst = int(os.environ.get("BENCH_K_STEPS", "8"))
    big = os.environ.get("BENCH_BIG_MODEL", "gpt2-760m")
    big_bs = int(os.environ.get("BENCH_BIG_BS", "16"))
    # The DEFAULT sweep is the high-value core; BENCH_FULL=1 restores the
    # wide grid. Row order = evidence priority.
    full = os.environ.get("BENCH_FULL", "0") == "1"
    return [
        {"kind": "kernels", "name": "pallas-kernel-smoke"},
        # the two strongest measured train rows (r4 chip grid), k8-fused
        {"kind": "train", "name": f"{big}-zero1-selrm12", "model": big,
         "micro_bs": 12, "seq": seq, "stage": 1, "steps": steps,
         "k_steps": kst, "timeout": 2700,
         "remat_policy": "save_attn_mlp_out"},
        {"kind": "train", "name": f"{model}-zero1", "model": model,
         "micro_bs": bs, "seq": seq, "stage": 1,
         "steps": steps, "k_steps": kst, "timeout": 2700,
         "remat_policy": "save_attn_mlp_out"},
        {"kind": "inference", "name": f"{model}-decode", "model": model,
         "batch": 1, "prompt": 128, "gen": 64, "timeout": 2700},
        # batched decode: amortized per-token throughput
        {"kind": "inference", "name": f"{model}-decode-b8", "model": model,
         "batch": 8, "prompt": 128, "gen": 64, "timeout": 2700},
        # the weight-bandwidth lever, measured: packed int4 quarters the
        # bytes per decoded token
        {"kind": "inference", "name": f"{model}-decode-b8-int4",
         "model": model, "batch": 8, "prompt": 128, "gen": 64,
         "quantize_bits": 4, "timeout": 2700},
        # continuous-batching serving row (ROADMAP item 1): open-loop
        # arrivals through the paged decode stack, A/B'd against static
        # generate batches on the same seeded workload — reports p50/p99
        # TTFT + aggregate tokens/s and the speedup_vs_static bar
        {"kind": "serving", "name": f"{model}-serving-cb", "model": model,
         "slots": 16, "page_size": 128, "max_model_len": 512,
         "prefill_chunk": 128, "requests": 32, "rate_rps": 8.0,
         "prompt_range": (32, 160), "gen_range": (8, 128),
         "timeout": 2700},
        # serving-era flagship lever row: int8 KV pages vs dense at equal
        # HBM bytes, 2x saturation — the capacity-vs-SLO axis measured on
        # the chip (the next chip run's first serving-era bench point)
        {"kind": "serving_lever", "name": f"{model}-serving-cb-kv8",
         "lever": "kv8", "model": model, "slots": 16, "page_size": 128,
         "max_model_len": 512, "prefill_chunk": 128, "requests": 32,
         "slo_s": 6.0, "prompt_range": (32, 160), "gen_range": (8, 128),
         "dtype": "bfloat16", "timeout": 2700},
        # speculative-decoding flagship: n-gram self-drafting + adaptive k
        # vs spec-off at equal slots/pages on the chip, where decode is
        # weight-bound — the k+1-token verify reads each weight matrix
        # once, so accepted tokens per dispatch is the direct multiplier
        # the Gemma serving paper frames capacity around. decode_block=1
        # on both sides isolates the lever (the scan block's win is
        # host-round-trip amortization, already measured by -serving-cb)
        {"kind": "serving_lever", "name": f"{model}-serving-cb-spec",
         "lever": "spec", "model": model, "slots": 16, "page_size": 128,
         "max_model_len": 512, "prefill_chunk": 128, "requests": 32,
         "slo_s": 6.0, "spec_k": 4, "decode_block": 1,
         "prompt_range": (32, 160), "gen_range": (8, 128),
         "dtype": "bfloat16", "timeout": 2700},
        # multi-tenancy flagship: the 3-tier SLO contract at 2x saturation
        # on the chip — WFQ + brownout ladder holding interactive p99 TTFT
        # at its light-load floor while batch absorbs the shed, vs the
        # tier-blind scheduler on the same stream
        {"kind": "serving_tiered", "name": f"{model}-serving-tiers",
         "model": model, "slots": 16, "page_size": 128,
         "max_model_len": 512, "prefill_chunk": 128,
         "requests_per_tier": 12, "slo_s": 6.0,
         "prompt_range": (32, 160), "gen_range": (8, 128),
         "dtype": "bfloat16", "timeout": 2700},
        # fleet flagship: 2 router-fronted replica processes vs one engine
        # at equal total slots at 2x saturation + the replica-kill chaos
        # variant — graceful degradation a single replica cannot produce.
        # Prefill-heavy (TTFT-bound) shape; replica_env pins one chip per
        # worker so replicas own their compute (two processes cannot share
        # one TPU runtime)
        {"kind": "serving_fleet", "name": f"{model}-serving-fleet",
         "model": model, "replicas": 2, "slots": 8, "page_size": 128,
         "max_model_len": 512, "prefill_chunk": 128, "requests": 32,
         "slo_s": 6.0, "prompt_range": (128, 384), "gen_range": (8, 32),
         "replica_env": {"TPU_VISIBLE_DEVICES": "{i}"},
         "dtype": "bfloat16", "timeout": 2700},
        # tensor-parallel serving flagship: the SAME continuous-batching
        # row sharded over 2 chips (tp=2 weight stacks + paged pools,
        # one psum per block) — greedy-identical outputs, ~2x the
        # weight bandwidth per decoded token where decode is weight-bound
        {"kind": "serving", "name": f"{model}-serving-tp2", "model": model,
         "tp": 2, "slots": 16, "page_size": 128, "max_model_len": 512,
         "prefill_chunk": 128, "requests": 32, "rate_rps": 8.0,
         "prompt_range": (32, 160), "gen_range": (8, 128),
         "timeout": 2700},
        # disaggregated prefill/decode flagship: prefill + decode
        # specialist worker processes (one chip each via replica_env) vs
        # the colocated fleet at equal total slots/pages — page-handoff
        # ownership transfer over the wire, int8 pages to shrink the
        # payload, plus the prefill-kill chaos phase (zero survivor
        # page leaks, greedy-identical failover)
        {"kind": "serving_disagg", "name": f"{model}-serving-disagg",
         "model": model, "slots": 8, "page_size": 128,
         "max_model_len": 512, "prefill_chunk": 128, "kv_bits": 8,
         "requests": 32, "slo_s": 6.0, "prompt_range": (128, 384),
         "gen_range": (8, 32),
         "replica_env": {"TPU_VISIBLE_DEVICES": "{i}"},
         "dtype": "bfloat16", "timeout": 2700},
        {"kind": "diffusion", "name": "sd-ddim20", "latent": 32,
         "ddim_steps": 20, "timeout": 2700},
        # measured MoE row (VERDICT r4 next #5): single-chip expert bank,
        # same gating/dispatch program as ep>1
        {"kind": "moe_train", "name": "moe-125m-8e-train",
         "model": "moe-125m-8e", "micro_bs": 8, "seq": seq, "steps": steps,
         "timeout": 2700},
        # the overlap target row (ROADMAP item 2): quantized ZeRO-3 gathers
        # pipelined under compute on the flagship geometry, with a profiled
        # step reporting the exposed-vs-overlapped collective-time column —
        # the ≥0.45 MFU bar is judged here
        {"kind": "train", "name": f"{big}-zero3-qw8-overlap", "model": big,
         "micro_bs": 12, "seq": seq, "stage": 3, "steps": steps,
         "k_steps": kst, "quantized_weights": True, "measure_overlap": True,
         "remat_policy": "save_attn_mlp_out", "timeout": 2700},
        # chunked loss drops the fp32 logits buffer — AOT-verified to fit
        # where unchunked OOMs; longest compile, so last of the core rows
        {"kind": "train", "name": f"{big}-zero1-selrm16-chunk",
         "model": big, "micro_bs": 16, "seq": seq, "stage": 1,
         "steps": steps, "k_steps": kst, "timeout": 2700,
         "remat_policy": "save_attn_mlp_out", "loss_chunk": 128},
    ] + (([
        {"kind": "train", "name": f"{model}-zero{s}", "model": model,
         "micro_bs": bs, "seq": seq, "stage": s, "steps": steps,
         "k_steps": kst, "timeout": 2700}
        for s in (2, 3)
    ] + [
        {"kind": "train", "name": f"{big}-zero{s}", "model": big,
         "micro_bs": big_bs, "seq": seq, "stage": s, "steps": steps,
         "k_steps": kst, "timeout": 2700}
        for s in (1, 3)
    ] + [
        {"kind": "train", "name": f"{big}-zero1-bs24-chunk", "model": big,
         "micro_bs": 24, "seq": seq, "stage": 1, "steps": steps,
         "k_steps": kst, "loss_chunk": 128, "timeout": 2700},
    ]) if full else []) + (
        # pipeline_aot + AOT rows are force_cpu (host-side v5e compiler):
        # cheap chip-independent fit evidence; pipeline_mpmd is a short
        # on-chip dispatch microbench. Infinity rows (long, host-streamed)
        # only under BENCH_FULL.
        PIPELINE_CONFIGS + AOT_TRAIN_CONFIGS
        + QUANTIZED_ZERO_CONFIGS
        + (INFINITY_CONFIGS if full else []))


def cpu_configs() -> list:
    """The ``--cpu`` sweep: tiny shapes on the CPU host (correctness outcomes
    and counts — never device metrics) + chip-independent AOT rows. Every row
    carries force_cpu: these are CPU-host rows BY DESIGN."""
    return [
        {"kind": "train", "name": f"cpu-zero{s}", "model": "gpt2-125m",
         "micro_bs": 2, "seq": 128, "stage": s, "steps": 3, "force_cpu": True}
        for s in (1, 2)
    ] + [
        # quantized ZeRO-3 wire evidence is chip-independent (the ledger
        # records at trace time), so the --cpu sweep measures it too
        {"kind": "train", "name": "cpu-zero3-qw8",
         "model": "gpt2-125m", "micro_bs": 2, "seq": 128, "stage": 3,
         "steps": 3, "precision": "fp32", "quantized_weights": True,
         "force_cpu": True},
    ] + [
        # streamed ZeRO-Infinity A/B (docs/OFFLOAD.md): the same host-
        # streamed step with the depth-2 prefetch pipeline vs fetch-on-
        # demand. Numerics are bitwise-identical by construction (same
        # units, same order — asserted in tests/test_infinity_stream.py);
        # the rows report the host-DMA column (exposed_wait_s,
        # overlapped_frac) so the schedule's latency hiding is a measured
        # number, and step_ms must be no worse than inline
        {"kind": "train", "name": "cpu-infinity-streamed",
         "model": "gpt2-125m", "micro_bs": 1, "seq": 64, "steps": 2,
         "offload": "param_stream", "keep_layers": 2,
         "offload_prefetch_depth": 2, "force_cpu": True, "timeout": 900},
        {"kind": "train", "name": "cpu-infinity-inline",
         "model": "gpt2-125m", "micro_bs": 1, "seq": 64, "steps": 2,
         "offload": "param_stream", "keep_layers": 2,
         "offload_stream": False, "force_cpu": True, "timeout": 900},
    ] + [
        # MTTR evidence: NaN at a known cursor -> sentinel rollback ->
        # poisoned-batch skip -> rejoin; the heal mechanics are
        # chip-independent (host-side detection + checkpoint restore)
        {"kind": "chaos_mttr", "name": "cpu-chaos-nan-mttr",
         "model": "gpt2-125m", "micro_bs": 2, "seq": 128, "steps": 5,
         "nan_at": 3, "force_cpu": True},
    ] + [
        # SDC evidence (docs/RESILIENCE.md "Data integrity"): a real bit
        # flip in a cpu-offloaded optimizer shard AND in a prefix-shared
        # KV page, both detected and healed (training replay step-exact,
        # serving re-prefill generate-identical) with the scan overhead
        # measured at the default budget (must be ≤5% of step time). The
        # flip lands at step 17: the default scan_interval=16 budget has
        # stamped its first blocks at the step-16 boundary, so detection
        # rides the production cadence, not a cranked-up test one
        {"kind": "chaos_sdc", "name": "cpu-chaos-sdc",
         "model": "gpt2-125m", "micro_bs": 2, "seq": 128, "steps": 20,
         "flip_at": 17, "force_cpu": True, "timeout": 900},
    ] + [
        # continuous-batching A/B is measurable on CPU once the model is
        # compute-bound (125m): slot recycling + exact-length decode beat
        # the padded static scan ~1.7x on tokens/s at equal HBM tokens,
        # with ~7x better TTFT p50 (measured while building the row)
        {"kind": "serving", "name": "cpu-serving-cb", "model": "gpt2-125m",
         "slots": 8, "page_size": 16, "max_model_len": 128,
         "prefill_chunk": 64, "requests": 12, "rate_rps": 50.0,
         "hbm_tokens": 640, "prompt_range": (8, 48), "gen_range": (2, 48),
         "dtype": "float32", "force_cpu": True, "timeout": 900},
    ] + [
        # overload A/B at 2x saturation: with admission control ON, p99
        # TTFT of accepted requests stays bounded and goodput holds; the
        # uncontrolled baseline's queue (and tail) grows with the load
        {"kind": "serving_overload", "name": "cpu-serving-overload",
         "model": "gpt2-125m", "slots": 4, "page_size": 16,
         "max_model_len": 96, "prefill_chunk": 32, "requests": 16,
         "slo_s": 3.0, "prompt_range": (8, 24), "gen_range": (8, 24),
         "dtype": "float32", "force_cpu": True, "timeout": 900},
    ] + [
        # multi-tenant SLO-tier A/B at 2x saturation (docs/SERVING.md
        # "Multi-tenancy & SLO tiers"): 3-tier mixed-tenant stream, tiered
        # (WFQ + per-tier partitions + brownout ladder) vs untiered on the
        # same workload — interactive p99 TTFT held near its light-load
        # floor while the batch tier absorbs the shed; batch bounded-wait
        # asserted; greedy_match_rate 1.0 (prioritization must never
        # change the tokens). 125m because the within-15% TTFT bar is only
        # meaningful where TTFT is service-dominated (a dispatch-bound
        # tiny model turns 2x overload into a sub-second burst and the
        # comparison into scheduler-jitter noise); the SLO and wall are
        # sized for a 1-core CI host serving 125m at ~0.4 rps saturation
        {"kind": "serving_tiered", "name": "cpu-serving-tiers",
         "model": "gpt2-125m", "slots": 4, "page_size": 16,
         "max_model_len": 96, "prefill_chunk": 32, "decode_block": 2,
         "requests_per_tier": 10, "slo_s": 30.0, "max_wall_s": 240.0,
         "prompt_range": (8, 24), "gen_range": (8, 24),
         "dtype": "float32", "force_cpu": True, "timeout": 900},
    ] + [
        # serving-lever A/B rows at 2x saturation (docs/SERVING.md "KV
        # quantization & prefix caching"): int8 KV pages at equal HBM bytes
        # (4x the fp32 pool pages -> fewer preemptions, higher goodput),
        # and copy-on-write prefix caching on a shared-system-prompt
        # workload (physical pages < logical, outputs byte-identical)
        {"kind": "serving_lever", "name": "cpu-serving-cb-kv8",
         "lever": "kv8", "model": "gpt2-125m", "slots": 4, "page_size": 16,
         "max_model_len": 96, "prefill_chunk": 32, "requests": 16,
         "slo_s": 3.0, "prompt_range": (8, 24), "gen_range": (8, 24),
         "dtype": "float32", "force_cpu": True, "timeout": 900},
        {"kind": "serving_lever", "name": "cpu-serving-cb-prefix",
         "lever": "prefix", "model": "gpt2-125m", "slots": 4,
         "page_size": 16, "max_model_len": 96, "prefill_chunk": 64,
         "requests": 16, "slo_s": 3.0, "prefix_len": 32,
         "prompt_range": (4, 16), "gen_range": (8, 24),
         "dtype": "float32", "force_cpu": True, "timeout": 900},
        # speculative decoding A/B at 2x saturation: n-gram self-drafting +
        # adaptive k against the spec-off scheduler at EQUAL slots/pages,
        # decode_block=1 on both sides (on CPU the scan block and the
        # verify window amortize the same dispatch overhead; block=1
        # isolates the lever — the dispatch-bound "tiny" model is the
        # honest CPU stand-in for the TPU's weight-bound regime, where
        # verify reads the weights once per k+1 tokens). Gate:
        # greedy_match_rate == 1.0 — speculation must be invisible in the
        # outputs, visible only in goodput/TTFT/tokens_per_dispatch
        # (measured while building: goodput 4056-4616 vs 3251-3423 tok/s,
        # TTFT p50 33 vs 43-49ms / p99 66-78 vs 120-124ms across seeds,
        # accept_rate ~0.90, tokens_per_dispatch ~12.3, greedy_match_rate
        # 1.0 — longer generations give the drafter loops to lock onto)
        {"kind": "serving_lever", "name": "cpu-serving-cb-spec",
         "lever": "spec", "model": "tiny", "slots": 4, "page_size": 16,
         "max_model_len": 96, "prefill_chunk": 32, "requests": 24,
         "slo_s": 3.0, "spec_k": 4, "decode_block": 1, "gen_range": (16, 48),
         "prompt_range": (8, 24), "dtype": "float32", "force_cpu": True,
         "timeout": 900},
    ] + [
        # fleet overload A/B at 2x saturation (docs/SERVING.md "Fleet"):
        # 2 router-fronted replica PROCESSES vs one engine at equal total
        # slots — the fleet must beat the single scheduler on goodput AND
        # deadline-miss rate, and the replica-kill chaos variant must show
        # zero survivor page leaks with greedy_match_rate 1.0. The
        # workload is prefill-heavy (long prompts, short gens — the
        # TTFT-bound chat shape): that is where per-replica compute bites,
        # because a single engine serializes every prefill against all of
        # its running slots while replicas prefill concurrently
        {"kind": "serving_fleet", "name": "cpu-serving-fleet",
         "model": "gpt2-125m", "replicas": 2, "slots": 2, "page_size": 16,
         "max_model_len": 128, "prefill_chunk": 64, "pool_pages": 16,
         "requests": 48, "slo_s": 4.0, "prompt_range": (64, 112),
         "gen_range": (4, 8), "dtype": "float32", "force_cpu": True,
         "timeout": 1200},
    ] + [
        # disaggregated prefill/decode A/B at 2x saturation (docs/
        # SERVING.md "Tensor parallel & disaggregation"): 1 prefill + 1
        # decode specialist vs 2 colocated replicas at equal TOTAL
        # slots/pages on the fleet row's prefill-heavy (TTFT-bound)
        # shape, int8 KV pages keeping the handoff wire payload small
        # (pages + per-page scales). Measured while building the row
        # (single-core CI host): TTFT p99 strictly better in 6/8 runs
        # (e.g. 12.7s vs 13.8s, 13.2s vs 19.7s — the prefill
        # specialist's first tokens never queue behind decode slot
        # commitments), chaos phase (prefill specialist SIGKILLed
        # mid-stream) always zero survivor page leaks with
        # greedy_match_rate 1.0. The goodput >= colocated bar is judged
        # on the CHIP row: on a one-core host every replica process
        # timeshares the same CPU, so disagg pays the handoff wire cost
        # without collecting its win (prefill and decode no longer
        # stealing each other's compute) — that win needs replicas that
        # own their chips (replica_env)
        {"kind": "serving_disagg", "name": "cpu-serving-disagg",
         "model": "gpt2-125m", "slots": 2, "page_size": 16,
         "max_model_len": 128, "prefill_chunk": 64, "pool_pages": 16,
         "kv_bits": 8, "requests": 24, "slo_s": 12.0,
         "prompt_range": (64, 112), "gen_range": (4, 8),
         "max_wall_s": 300.0,
         "dtype": "float32", "force_cpu": True, "timeout": 1800},
    ] + [{"kind": "inference", "name": "cpu-decode", "model": "gpt2-125m",
          "batch": 1, "prompt": 32, "gen": 16, "reps": 3, "force_cpu": True},
         PIPELINE_CONFIGS[0]] + AOT_TRAIN_CONFIGS


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true",
                    help="run the CPU-host correctness rows (no device "
                         "metrics) instead of the TPU sweep")
    args = ap.parse_args(argv)
    if args.cpu:
        platform, configs = "cpu", cpu_configs()
    else:
        platform, err = probe_backend()
        if platform != "tpu":
            print(f"bench.py: no TPU ({err or f'found platform {platform!r}'}"
                  "); a device benchmark does not fall back. Pass --cpu for "
                  "the CPU-host correctness rows.", file=sys.stderr)
            return 1
        configs = tpu_core_configs()
    # run delimiter so a reader of the append-only log can attribute rows to
    # the sweep that produced them
    _persist_row({"run_start": True, "platform": platform,
                  "argv": sys.argv[1:]})

    sweep, errors = [], []

    def _flush_on_term(signum, frame):
        # an external `timeout`/driver kill mid-row must still leave a final
        # summary on stdout
        errors.append(f"killed by signal {signum} mid-sweep")
        _persist_row({"killed_by_signal": signum, "rows_completed": len(sweep)})
        print(json.dumps(_summarize(platform, sweep, errors)), flush=True)
        sys.exit(124)

    signal.signal(signal.SIGTERM, _flush_on_term)

    deadline = time.time() + TOTAL_BUDGET if TOTAL_BUDGET else None
    for cfg in configs:
        if deadline is not None:
            remaining = deadline - time.time()
            if remaining < ROW_RESERVE:
                # banking a skip beats an rc=124 with the row half-run
                r = {"config": cfg.get("name"),
                     "skipped": "global_budget_exhausted",
                     "remaining_s": round(max(0.0, remaining), 1)}
                sweep.append(r)
                _persist_row(r)
                print(f"[bench] {json.dumps(r)}", file=sys.stderr)
                continue
            cfg = dict(cfg)
            cfg["timeout"] = int(min(cfg.get("timeout", WORKER_TIMEOUT),
                                     max(ROW_RESERVE, remaining - ROW_RESERVE)))
        r = run_worker(cfg, platform)
        sweep.append(r)
        _persist_row(r)
        if "error" in r:
            errors.append(f"{cfg['name']}: {r['error']}")
        print(f"[bench] {json.dumps(r)}", file=sys.stderr)
        # refresh the stdout artifact after EVERY row: if the sweep is killed
        # mid-run, the last complete line is still a valid summary of
        # everything measured so far
        print(json.dumps(_summarize(platform, sweep, errors)), flush=True)

    print(json.dumps(_summarize(platform, sweep, errors)))
    return 1 if errors else 0


def _summarize(platform: str, sweep: list, errors: list) -> dict:
    """Built from THIS run's rows only. Device headline metrics
    (tokens/sec/chip, MFU, decode latency) exist only for a TPU sweep; a
    ``--cpu`` sweep reports how many rows completed."""
    ok = [r for r in sweep if "error" not in r and not r.get("skipped")]
    result = {"platform": platform, "sweep": sweep}
    if errors:
        result["errors"] = errors[-4:]
    # compile-only evidence digest: v5e-compiler fit verdicts
    aot_rows = [r for r in ok
                if str(r.get("kind", "")).endswith("_aot") and "config" in r]
    if aot_rows:
        result["aot_evidence"] = [
            {"config": r["config"], "kind": r["kind"],
             "fits_v5e_hbm": r.get("fits_v5e_hbm"),
             "peak_bytes": (r.get("per_device_bytes") or {}).get("peak"),
             # margin-aware: "marginal" = compiles but inside the
             # fragmentation margin — a prediction needing runtime confirm
             "fit_confidence": (r.get("fit") or {}).get("confidence"),
             "kernels_ok": (all(k.get("ok") for k in r["kernels"].values())
                            if "kernels" in r else None)}
            for r in aot_rows]
    if platform != "tpu":
        result.update({
            "metric": "rows completed on the CPU host (correctness and "
                      "counts only; device metrics not measured)",
            "value": len(ok), "unit": "rows", "vs_baseline": None})
        return result
    train_ok = [r for r in ok if r.get("kind") in ("train", "moe_train")
                and "mfu" in r]
    if train_ok:
        best = max(train_ok, key=lambda r: r["mfu"])
        result.update({
            "metric": f"{best['config']} bf16 training tokens/sec/chip",
            "value": best["tokens_per_sec_chip"],
            "unit": "tokens/sec/chip",
            "vs_baseline": round(best["mfu"] / 0.45, 3),
            "mfu": best["mfu"],
            "device_kind": best.get("device_kind"),
        })
    else:
        result.update({
            "metric": "training throughput (all configs failed)",
            "value": 0.0, "unit": "tokens/sec/chip", "vs_baseline": 0.0,
        })
    infer_ok = [r for r in ok if r.get("kind") == "inference"
                and r.get("platform") == "tpu"]
    if infer_ok:
        result["decode_p50_ms"] = infer_ok[0]["decode_p50_ms"]
        result["decode_tokens_per_sec"] = infer_ok[0].get("tokens_per_sec")
        # the reference's published decode bar, embedded so the artifact is
        # self-describing even when nobody writes the comparison up by hand
        result["decode_reference_bar"] = {
            "zero_inference_opt30b_tok_s": 43,
            "hardware": "1x V100-32GB, full CPU offload",
            "source": "docs/_posts/2022-09-10-zero-inference.md:52",
            "note": ("this row decodes a chip-RESIDENT model on one "
                     "v5e; the reference bar is the host-offload "
                     "regime — compare decode_tokens_per_sec directly")}
    # a measured chip-RESIDENT big-model decode (13B int8 / 20B int4) is its
    # own headline: the reference's answer at this size is host offload
    big = [r for r in infer_ok if r.get("quantize_bits")
           and any(m in str(r.get("config", "")) for m in ("13b", "20b"))]
    if big:
        result["resident_big_decode"] = big[0]
    diff_ok = [r for r in ok if r.get("kind") == "diffusion"
               and r.get("platform") == "tpu"]
    if diff_ok:
        result["sd_image_ms_p50"] = diff_ok[0]["image_ms_p50"]
    return result


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        _worker(json.loads(sys.argv[2]))
    else:
        sys.exit(main())
