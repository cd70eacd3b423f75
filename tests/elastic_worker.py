"""Elastic-agent worker fixture: trains a tiny GPT on a forced-CPU mesh of
``--elastic-world`` devices, checkpointing every step, resuming from the latest
checkpoint on start. Used by test_elastic_agent.py (kill-and-resume),
test_reshard.py and scripts/elastic_smoke.py (chaos-tested device-loss
recovery, docs/RESILIENCE.md "Elastic membership").

Elastic-resume extensions (all optional; defaults keep the original
behavior):

- ``--resilience``: arm the ``resilience`` block (commit-protocol saves,
  auto-resume from the newest committed tag, recovery-event log — the
  ``reshard_applied`` event lands in ``<ckpt>/recovery_events.jsonl``).
- ``--cursor-data``: drive batches from ``engine.data_cursor`` (the
  checkpointable-cursor contract the reshard path keeps sample-exact).
- ``--qgrad``: arm the quantized gradient exchange with error feedback —
  the run carries the world-size-coupled ``qgrad_residual`` state the
  reshard-on-load path must reset by policy.
- ``--lose-at N``: install a ``lose_worker_at_step`` fault plan (SIGKILL at
  data cursor N — a dp worker dying with its lost device).
- ``--pid-file``: write our pid at start (the smoke's device probe treats
  this process's existence as one device's health).
- ``--out-state``: npz dump of the final engine state for bitwise compares.
- ``--elastic-config JSON``: include this ``elasticity`` block in the ds
  config — exercises the runtime-side validation + the scheduler
  fingerprint check against ``DS_TPU_ELASTICITY_CONFIG``.
"""

import argparse
import json
import os
import sys


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--log", required=True)
    p.add_argument("--steps", type=int, required=True)
    p.add_argument("--crash-at", type=int, default=-1)
    p.add_argument("--on-crash-write", default=None,
                   help="'path:text' written just before the simulated crash "
                        "(models the membership change that caused it)")
    p.add_argument("--elastic-world", type=int, required=True)
    p.add_argument("--elastic-micro", type=int, required=True)
    p.add_argument("--elastic-gas", type=int, required=True)
    p.add_argument("--resilience", action="store_true")
    p.add_argument("--cursor-data", action="store_true")
    p.add_argument("--qgrad", action="store_true")
    p.add_argument("--lose-at", type=int, default=-1)
    p.add_argument("--pid-file", default=None)
    p.add_argument("--out-state", default=None)
    p.add_argument("--elastic-config", default=None)
    args = p.parse_args()

    if args.pid_file:
        with open(args.pid_file, "w") as f:
            f.write(str(os.getpid()))

    # strip any inherited device-count flag so ours wins (XLA_FLAGS is read at
    # backend init; jax is imported below, after the environment is set)
    flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={args.elastic_world}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["DS_TPU_ACCELERATOR"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_gpt, gpt
    from deepspeed_tpu.runtime.topology import MeshTopology

    world, micro, gas = args.elastic_world, args.elastic_micro, args.elastic_gas
    model, cfg = build_gpt(gpt.GPTConfig(
        vocab_size=64, n_layer=2, n_head=2, d_model=32, max_seq_len=32))
    topo = MeshTopology.create(dp=world, devices=jax.devices()[:world])
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1},
        "mesh": {"dp": world},
        "bf16": {"enabled": False},
        "steps_per_print": 0,
    }
    if args.qgrad:
        config["zero_optimization"].update({
            "zero_quantized_gradients": True,
            "zero_quantize_error_feedback": True,
        })
    if args.elastic_config:
        config["elasticity"] = json.loads(args.elastic_config)
    if args.resilience:
        res = {"enabled": True, "save_dir": args.ckpt_dir}
        if args.lose_at >= 0:
            res["chaos"] = {"lose_worker_at_step": args.lose_at}
        config["resilience"] = res
    engine, _, _, _ = ds.initialize(model=model, topology=topo, config=config)
    if not args.resilience:
        engine.load_checkpoint(args.ckpt_dir)  # no-op on the first launch
    # resilience mode auto-resumed from the newest COMMITTED tag at init

    effective = micro * gas * world

    def batch_for(step: int):
        # deterministic per-step data, independent of the decomposition: the
        # same `effective`-sized batch regardless of world/micro/gas. A small
        # repeating set (2 distinct batches) so the loss measurably descends
        # and a resumed run is distinguishable from a cold restart.
        r = np.random.default_rng(1000 + step % 2)
        ids = r.integers(0, 64, size=(effective, 16), dtype=np.int32)
        if gas > 1:
            ids = ids.reshape(gas, micro * world, 16)
        return {"input_ids": ids}

    while engine.global_steps < args.steps:
        index = engine.data_cursor if args.cursor_data else engine.global_steps
        m = engine.train_batch(batch_for(index))
        with open(args.log, "a") as f:
            f.write(json.dumps({
                "step": engine.global_steps, "loss": float(m["loss"]),
                "cursor": engine.data_cursor, "index": index,
                "world": world, "micro": micro, "gas": gas,
                "effective": effective}) + "\n")
        engine.save_checkpoint(args.ckpt_dir)
        if args.crash_at >= 0 and engine.global_steps >= args.crash_at:
            if args.on_crash_write:
                path, text = args.on_crash_write.rsplit(":", 1)
                with open(path, "w") as f:
                    f.write(text)
            os._exit(17)  # simulated worker failure

    if args.out_state:
        from deepspeed_tpu.checkpoint.serialization import (
            _UINT_FOR_SIZE,
            _fetch_full,
            _flatten_with_paths,
        )

        out = {}
        for key, leaf in _flatten_with_paths(engine.state)[0]:
            arr = _fetch_full(leaf)
            if arr.dtype.kind not in "biufc":
                arr = arr.view(_UINT_FOR_SIZE[arr.dtype.itemsize])
            out[key] = arr
        np.savez(args.out_state, **out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
