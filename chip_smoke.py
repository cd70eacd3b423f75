#!/usr/bin/env python3
"""On-chip smoke: the quickest proof that the system still starts on the TPU.

Drives the two main paths once, through the entry points a user calls, at the
full width of gpt2-350m (24L, d1024, 16 heads, vocab 50304; random weights
from a seed):

- train: ``build_gpt`` -> ``deepspeed_tpu.initialize`` (bf16, ZeRO-3, AdamW,
  clip 1.0, micro-batch 16 x seq 1024) -> ``train_batch`` x5 on one fixed
  batch, then one ``train_batches`` (k=4);
- serve: ``ServingEngine`` (default page/chunk geometry, 8 slots) + its
  scheduler over 8 requests, bf16 pools and ``kv_bits=8``, then
  ``num_slots="auto"``;
- with >= 4 devices also dp2 x tp2 training, dp4-vs-one-device loss on the same
  batch, and ``ServingConfig(tp=2)``.

Everything runs in THIS process, one phase after another (a chip belongs to
one process; no child is started). The first failed check raises, so the exit
code is non-zero and no result line is printed. It refuses to run without a
TPU. The last stdout line is the result JSON.

    python chip_smoke.py
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import sys
import time

MODEL = "gpt2-350m"
MICRO_BS, SEQ = 16, 1024
TRAIN_STEPS, TRAIN_K = 5, 4
SLOTS = 8
PROMPT_LENS = (32, 64, 100, 160, 230, 300, 410, 512)
NEW_TOKENS = (32, 40, 48, 56, 64, 36, 44, 60)
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT, _CACHE_MISS = ("/jax/compilation_cache/cache_hits",
                           "/jax/compilation_cache/cache_misses")


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileLog:
    """Per-program backend compile seconds and persistent-cache hits/misses,
    from jax.monitoring (a cache hit still reports its retrieval time)."""

    def __init__(self):
        import jax

        self.programs, self.hits, self.misses = [], 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == _COMPILE_EVENT:
            self.programs.append((str(kw.get("fun_name", "?")), float(secs)))

    def _event(self, event, **kw):
        self.hits += event == _CACHE_HIT
        self.misses += event == _CACHE_MISS

    def mark(self):
        return len(self.programs), self.hits, self.misses

    def report(self, tag: str, since) -> float:
        n0, h0, m0 = since
        progs = self.programs[n0:]
        total = sum(s for _, s in progs)
        for name, s in progs:
            if s >= 1.0:
                say(f"{tag}: compile {name} {s:.1f}s")
        say(f"{tag}: {len(progs)} programs, compile total {total:.1f}s, "
            f"cache hits {self.hits - h0} misses {self.misses - m0}")
        return total


def memory(devices) -> dict:
    out = {}
    for d in devices:
        ms = d.memory_stats()
        out[d.id] = {k: int(ms[k]) for k in
                     ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
    return out


def fmt_mem(mem: dict) -> str:
    return " ".join(
        f"dev{i}: in_use {m['bytes_in_use'] / 1e9:.2f} "
        f"peak {m['peak_bytes_in_use'] / 1e9:.2f} "
        f"limit {m['bytes_limit'] / 1e9:.2f} GB" for i, m in mem.items())


def release(tree) -> None:
    """Free device buffers now: the serving pools must not share HBM with a
    train state some jit closure still references."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
    gc.collect()


def require_mosaic(tag: str, jitted, *args, mesh=None) -> int:
    """The Pallas kernels must be IN the compiled program: count Mosaic custom
    calls in the optimized HLO (an interpret/reference route has none).
    Returns the program's per-device peak bytes."""
    import contextlib

    from deepspeed_tpu.analysis.ir import capture
    from deepspeed_tpu.runtime.topology import mesh_context

    with mesh_context(mesh) if mesh is not None else contextlib.nullcontext():
        prog = capture(jitted, *args, name=tag, compile=True)
    n = prog.hlo.count(MOSAIC_CALL)
    # memory_stats() peaks do not see a program's temporaries on this runtime
    # (first chip run: peak == resident state), so the per-device peak comes
    # from the executable itself
    peak = int(prog.compiled.memory_analysis().peak_memory_in_bytes)
    say(f"{tag}: {n} Mosaic custom call(s) in the compiled HLO; program peak "
        f"{peak / 1e9:.2f} GB per device")
    check(n > 0, f"{tag}: no Mosaic custom call in the compiled program — "
                 "the kernel path fell back to interpret/reference")
    return peak


# --------------------------------------------------------------------- train
def shard_report(state, devices) -> dict:
    """Where the parameter/optimizer shards actually sit: bytes per device
    from the arrays' own addressable shards, and the replicated share."""
    import jax

    per_dev = {d: 0 for d in devices}  # the mesh's (TPU) devices
    total = 0
    for leaf in jax.tree_util.tree_leaves(state):
        check(isinstance(leaf, jax.Array), "state leaf is not a jax.Array")
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            check(sh.device in per_dev,
                  f"state shard on {sh.device}, outside the mesh")
            per_dev[sh.device] += sh.data.nbytes
    return {"total_bytes": total,
            "per_device_bytes": {d.id: b for d, b in per_dev.items()}}


def train_phase(tag, mesh, clog, *, micro_bs=MICRO_BS, steps=TRAIN_STEPS,
                k=TRAIN_K) -> dict:
    """``mesh`` over the first prod(mesh) devices. The batch is seeded, so two
    phases with the same global batch size train on the same tokens."""
    import numpy as np

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models.gpt import PRESETS
    from deepspeed_tpu.runtime.topology import MeshTopology

    t_phase = time.perf_counter()
    since = clog.mark()
    n_dev = math.prod(mesh.values())
    check(n_dev <= len(jax.devices()), f"{tag}: mesh {mesh} needs {n_dev} devices")
    devices = jax.devices()[:n_dev]
    topo = (MeshTopology.create(**mesh, devices=devices)  # device-subset mesh
            if n_dev < len(jax.devices()) else None)
    model_mod, mcfg = build_gpt(dataclasses.replace(PRESETS[MODEL], remat=True))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_mod, topology=topo, config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-4, "weight_decay": 0.1}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3},
            "gradient_clipping": 1.0,
            "steps_per_print": 0,
            "mesh": mesh,
        })
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, mcfg.vocab_size, dtype=np.int32,
        size=(micro_bs * engine.topo.data_parallel_size, SEQ))}

    losses, gnorms, step_s = [], [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        losses.append(float(m["loss"]))
        gnorms.append(float(m["grad_norm"]))
        step_s.append(time.perf_counter() - t0)
    say(f"{tag}: train_batch losses {[round(x, 4) for x in losses]} "
        f"grad_norm {[round(x, 3) for x in gnorms]}")
    say(f"{tag}: step wall s {[round(x, 3) for x in step_s]} "
        "(first includes compile)")
    check(all(map(math.isfinite, losses + gnorms)),
          f"{tag}: non-finite loss or grad_norm")
    ln_v = math.log(mcfg.vocab_size)
    check(abs(losses[0] - ln_v) < 0.5,
          f"{tag}: first loss {losses[0]:.3f} not near ln(vocab)={ln_v:.3f}")
    if steps > 1:
        # no warmup: the first Adam steps overshoot and the loss alternates on
        # its way down (both chip runs), hence the better of the last two
        check(min(losses[-2:]) < losses[0] - 0.05,
              f"{tag}: loss did not fall on the repeated batch: {losses}")
    out = {"mesh": mesh, "micro_bs": micro_bs, "losses": losses,
           "step_s": step_s}
    if k:
        stacked = {"input_ids": np.stack([batch["input_ids"]] * k)}
        t0 = time.perf_counter()
        mk = engine.train_batches(stacked)
        dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        mk2 = engine.train_batches(stacked)
        dt2 = time.perf_counter() - t0
        say(f"{tag}: train_batches(k={k}) loss {float(mk['loss']):.4f} "
            f"({dt:.2f}s incl. compile), again {float(mk2['loss']):.4f} "
            f"({dt2:.3f}s = {dt2 / k:.3f}s/step)")
        check(math.isfinite(float(mk["loss"]))
              and float(mk2["loss"]) < losses[0] - 0.1,
              f"{tag}: train_batches loss not finite and below the first")
        out["train_batches_s_per_step"] = dt2 / k
        out["train_batches_loss"] = float(mk2["loss"])

    shards = shard_report(engine.state, devices)
    fair = shards["total_bytes"] / n_dev
    say(f"{tag}: state {shards['total_bytes'] / 1e9:.2f} GB total, per device "
        f"{ {i: round(b / 1e9, 2) for i, b in shards['per_device_bytes'].items()} } GB")
    for i, b in shards["per_device_bytes"].items():
        # ZeRO-3 leaves only sub-threshold leaves replicated
        check(0 < b <= 1.2 * fair + 64e6,
              f"{tag}: device {i} holds {b / 1e9:.2f} GB of state, fair share "
              f"{fair / 1e9:.2f} GB — shards are replicated or misplaced")
    mem = memory(devices)
    say(f"{tag}: {fmt_mem(mem)}")
    out["memory"] = mem
    out["state_bytes_per_device"] = shards["per_device_bytes"]
    out["program_peak_bytes"] = require_mosaic(
        f"{tag}/train_batch", engine._train_batch_jit, engine.state,
        engine._place_batch(batch, leading_gas=True), engine._next_rng(),
        mesh=engine.mesh)
    out["compile_s"] = clog.report(tag, since)
    release(engine.state)
    del engine
    gc.collect()
    out["wall_s"] = time.perf_counter() - t_phase
    say(f"{tag}: phase wall {out['wall_s']:.1f}s")
    return out


# --------------------------------------------------------------------- serve
def make_requests(vocab: int):
    import numpy as np

    from deepspeed_tpu.inference.serving.scheduler import Request

    rng = np.random.default_rng(1)
    return [Request(prompt=rng.integers(0, vocab, size=t, dtype=np.int32),
                    max_new_tokens=n)
            for t, n in zip(PROMPT_LENS, NEW_TOKENS)]


def decode_logits(se, toks, tables, lengths, impl):
    """One decode step's logits from the engine's own decode function over
    its live pools (not donated: the pools stay usable)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda p, c, t, tb, ln: se.model.decode_step(
        p, t, c, tb, ln, impl)[0])
    return jax.device_get(fn(
        se.params, se.paged_cache, jnp.asarray(toks, jnp.int32),
        jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32)))


def logits_close(tag, a, b, rows) -> float:
    """bf16 tolerance: the two routes reduce in different orders. Random-init
    logits are nearly flat, so compare values, never argmax."""
    import numpy as np

    a = np.asarray(a, np.float32)[rows]
    b = np.asarray(b, np.float32)[rows]
    check(np.isfinite(a).all() and np.isfinite(b).all(),
          f"{tag}: non-finite logits")
    scale = float(np.max(np.abs(b)))
    err = float(np.max(np.abs(a - b)))
    say(f"{tag}: max |diff| {err:.4g} at logit scale {scale:.4g} "
        f"over {len(rows)} rows")
    check(err <= 0.03 * max(scale, 1.0), f"{tag}: logits disagree "
          f"(max diff {err:.4g}, scale {scale:.4g})")
    return err


def serve_phase(tag, cfg, params, clog, *, kv_bits=None, tp=1):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.inference.serving.scheduler import RequestState

    t_phase = time.perf_counter()
    since = clog.mark()
    se = ServingEngine(cfg, params, ServingConfig(
        num_slots=SLOTS, kv_bits=kv_bits, tp=tp))
    n_prog = se.warmup()
    say(f"{tag}: warmup compiled {n_prog} programs in "
        f"{time.perf_counter() - t_phase:.1f}s")
    sched = se.make_scheduler()
    reqs = make_requests(cfg.vocab_size)
    try:
        for r in reqs:
            check(bool(sched.submit(r)), f"{tag}: request {r.rid} rejected")
        t0 = time.perf_counter()
        err = None
        for n_steps in range(1, 100_000):
            if sched.idle:
                break
            sched.step()
            active = sched.active_slots
            if err is None and len(active) >= min(4, len(reqs)):
                # mid-flight: the kernel route against the gather reference
                snap = (sched.next_input.copy(), sched.tables.copy(),
                        sched.lengths.copy())
                rows = [s for s in active if snap[2][s] > 0]
                err = logits_close(
                    f"{tag}: kernel vs gather",
                    decode_logits(se, *snap, "kernel"),
                    decode_logits(se, *snap, "gather"), rows)
        serve_s = time.perf_counter() - t0
        check(sched.idle, f"{tag}: scheduler did not drain")
        check(err is not None, f"{tag}: never saw 4 slots decoding at once")
        for r in reqs:
            check(r.state is RequestState.FINISHED,
                  f"{tag}: request {r.rid} ended {r.state}")
            check(len(r.tokens) == r.max_new_tokens,
                  f"{tag}: request {r.rid} produced {len(r.tokens)} of "
                  f"{r.max_new_tokens} tokens")
            check(all(0 <= t < cfg.vocab_size for t in r.tokens),
                  f"{tag}: request {r.rid} token out of vocab")
        audit = sched.audit()
        check(audit["ok"], f"{tag}: page audit {audit['errors']}")
        check(sched.allocator.allocated_pages == 0
              and sched.allocator.free_pages == se.num_pages - 1,
              f"{tag}: page leak — {sched.allocator.allocated_pages} pages "
              "still allocated after the drain")
        n_tok = sum(len(r.tokens) for r in reqs)
        say(f"{tag}: {len(reqs)} requests, {n_tok} tokens, {n_steps} scheduler "
            f"steps in {serve_s:.2f}s (includes the logits probe); audit "
            f"clean, {sched.allocator.free_pages} pages free")
    finally:
        sched.close()
    zeros = jnp.zeros(se.num_slots, jnp.int32)
    require_mosaic(
        f"{tag}/decode", se._get_decode(1), se.params, se.paged_cache, zeros,
        jnp.zeros((se.num_slots, se.serving.pages_per_seq), jnp.int32), zeros)
    out = {"requests": len(reqs), "tokens": n_tok, "serve_s": serve_s,
           "kernel_vs_gather_max_diff": err,
           "compile_s": clog.report(tag, since)}
    out["wall_s"] = time.perf_counter() - t_phase
    say(f"{tag}: phase wall {out['wall_s']:.1f}s")
    return se, out


def prefill_then_logits(se, cfg):
    """Prefill four fixed prompts into hand-numbered pages through the
    engine's executor API, then one decode step's logits — the same inputs on
    any engine, so two engines' logits are comparable."""
    import numpy as np

    rng = np.random.default_rng(2)
    s = se.serving
    lens = (40, 100, 200, 330)
    tables = np.zeros((se.num_slots, s.pages_per_seq), np.int32)
    lengths = np.zeros(se.num_slots, np.int32)
    nxt = 1
    for slot, t in enumerate(lens):
        need = -(-(t + 1) // s.page_size)
        tables[slot, :need] = np.arange(nxt, nxt + need)
        nxt += need
        se.prefill(slot, rng.integers(0, cfg.vocab_size, size=t,
                                      dtype=np.int32), tables[slot])
        lengths[slot] = t
    toks = rng.integers(0, cfg.vocab_size, size=se.num_slots, dtype=np.int32)
    return decode_logits(se, toks, tables, lengths, None), list(range(len(lens)))


def auto_slots_phase(tag, cfg, params, clog) -> dict:
    """``num_slots="auto"`` compiles against a device-less TPU topology inside
    the process that holds the chip."""
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    t0 = time.perf_counter()
    since = clog.mark()
    se = ServingEngine(cfg, params, ServingConfig(
        num_slots="auto", model_name=MODEL))
    say(f"{tag}: num_slots='auto' resolved {se.num_slots} slots "
        f"({se.num_pages} pages) in {time.perf_counter() - t0:.1f}s")
    check(se.num_slots >= 1, f"{tag}: auto resolved no slots")
    out = {"slots": se.num_slots, "compile_s": clog.report(tag, since)}
    release((se.params, se.paged_cache))
    return out


# ---------------------------------------------------------------------- main
def refuse(why: str) -> int:
    print(f"chip_smoke: refusing to run: {why}", file=sys.stderr)
    return 1


def main() -> int:
    t_start = time.perf_counter()
    plat = os.environ.get("JAX_PLATFORMS", "").lower()
    if plat and "tpu" not in plat.split(","):
        return refuse(f"JAX_PLATFORMS={plat!r} pins a non-TPU platform")
    if os.environ.get("DS_TPU_ACCELERATOR", "").lower() == "cpu":
        return refuse("DS_TPU_ACCELERATOR=cpu pins the CPU accelerator")

    import jax
    import jaxlib

    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return refuse(f"JAX found no accelerator (platform "
                      f"{devs[0].platform!r}, {len(devs)} device(s))")
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device {device}; jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}; compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries at start)")
    from deepspeed_tpu.runtime.aot import HBM_BYTES

    limit = devs[0].memory_stats()["bytes_limit"]
    say(f"bytes_limit {limit / 1e9:.3f} GB vs aot.HBM_BYTES "
        f"{HBM_BYTES / 1e9:.3f} GB assumed by fit_verdict")
    clog = CompileLog()
    summary = {"device": device, "bytes_limit": limit}
    multi = len(devs) >= 4

    if multi:
        summary["train_dp2_tp2"] = train_phase(
            "train dp2xtp2", {"dp": 2, "tp": 2}, clog, k=0)
    summary["train"] = train_phase(f"train dp{len(devs)}", {"dp": len(devs)},
                                   clog)
    if multi:
        # both see the same seeded 16 x 1024 batch
        l4 = train_phase("train dp4 mb4", {"dp": 4}, clog,
                         micro_bs=MICRO_BS // 4, steps=1, k=0)["losses"][0]
        l1 = train_phase("train one-device", {"dp": 1}, clog, steps=1,
                         k=0)["losses"][0]
        say(f"first-step loss on the same 16x{SEQ} batch: dp4 {l4:.4f} vs "
            f"one device {l1:.4f}")
        check(abs(l4 - l1) < 0.02, f"dp4 loss {l4} != one-device loss {l1}")
        summary["loss_dp4_vs_one"] = [l4, l1]

    from deepspeed_tpu.models import gpt as gpt_mod

    cfg = gpt_mod.PRESETS[MODEL]
    params = gpt_mod.init_params(cfg, jax.random.PRNGKey(0))
    se, summary["serve_bf16"] = serve_phase("serve bf16", cfg, params, clog)
    if not multi:
        release((se.params, se.paged_cache))
    se8, summary["serve_kv8"] = serve_phase("serve kv8", cfg, params, clog,
                                            kv_bits=8)
    release((se8.params, se8.paged_cache))
    if multi:
        se2, summary["serve_tp2"] = serve_phase("serve tp2", cfg, params,
                                                clog, tp=2)
        shard_devs = {sh.device.id for leaf in jax.tree_util.tree_leaves(
            (se2.params, se2.paged_cache)) for sh in leaf.addressable_shards}
        check(len(shard_devs) == 2,
              f"serve tp2: weights/pools sit on devices {shard_devs}")
        l_tp2, rows = prefill_then_logits(se2, cfg)
        l_tp1, _ = prefill_then_logits(se, cfg)
        summary["tp2_vs_tp1_max_diff"] = logits_close(
            "serve tp2 vs tp1 decode logits", l_tp2, l_tp1, rows)
        release((se.params, se.paged_cache, se2.params, se2.paged_cache))
    else:
        say(f"multi-chip phase skipped: {len(devs)} device(s) visible, the "
            "dp4 / dp2xtp2 / tp2 checks need 4")
    summary["serve_auto"] = auto_slots_phase("serve auto", cfg, params, clog)
    release(params)

    summary["compile_total_s"] = sum(s for _, s in clog.programs)
    summary["cache_hits"], summary["cache_misses"] = clog.hits, clog.misses
    summary["wall_s"] = time.perf_counter() - t_start
    say(f"compile total {summary['compile_total_s']:.1f}s over "
        f"{len(clog.programs)} programs, cache hits {clog.hits} misses "
        f"{clog.misses}; wall {summary['wall_s']:.1f}s")
    say("summary " + json.dumps(summary))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
