"""One scheduled tuning experiment: ``python -m deepspeed_tpu.autotuning.run_exp exp.json``.

The job side of the scheduler's file contract (parity: the reference's
per-experiment ``ds_config`` + ``AUTOTUNING_METRIC_PATH`` metric file,
``autotuning/scheduler.py``): read the experiment config, build the model
from its ``"model_spec"`` block, run a few measured ``train_batch`` steps,
write ``metrics.json`` next to the config.

``model_spec``: ``{"preset": "gpt2-125m", "overrides": {...GPTConfig
fields...}, "seq": 512, "steps": 5}`` — presets come from
``models.gpt.PRESETS``; overrides reach ``dataclasses.replace`` so model
knobs (remat policy, flash tiles) participate in tuning.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) != 1:
        print("usage: python -m deepspeed_tpu.autotuning.run_exp exp.json",
              file=sys.stderr)
        return 2
    exp_path = argv[0]
    with open(exp_path) as f:
        cfg = json.load(f)

    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import build_gpt
    from deepspeed_tpu.models import gpt as gpt_mod

    spec = dict(cfg.pop("model_spec", {}))
    preset = spec.get("preset", "gpt2-125m")
    mcfg = gpt_mod.PRESETS[preset]
    if spec.get("overrides"):
        mcfg = dataclasses.replace(mcfg, **spec["overrides"])
    seq = int(spec.get("seq", min(512, mcfg.max_seq_len)))
    steps = int(spec.get("steps", 5))
    model, mcfg = build_gpt(mcfg)

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config={**cfg, "steps_per_print": 0})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, mcfg.vocab_size, size=(engine.train_batch_size, seq),
        dtype=np.int32)}
    m = engine.train_batch(batch)  # compile
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.train_batch(batch)
    float(m["loss"])
    dt = time.perf_counter() - t0
    tokens_per_sec = steps * engine.train_batch_size * seq / dt

    with open(os.path.join(os.path.dirname(exp_path), "metrics.json"),
              "w") as f:
        json.dump({"metric_value": tokens_per_sec,
                   "tokens_per_sec": tokens_per_sec,
                   "loss": float(m["loss"]), "steps": steps}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
