"""The bench config lists must be executable as-is: a malformed spec
discovered on the chip would burn the chip-time budget."""

import json

import pytest


def _bench():
    import importlib
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    return importlib.reload(bench)


def test_all_config_lists_have_registered_kinds_and_serialize():
    bench = _bench()
    kinds = {"train", "inference", "kernels", "diffusion", "pipeline_aot",
             "pipeline_mpmd", "pipeline_schedule", "train_aot", "kernels_aot",
             "infinity_aot", "moe_aot", "infer_aot", "sd_aot"}
    for lst in (bench.INFINITY_CONFIGS, bench.PIPELINE_CONFIGS,
                bench.AOT_TRAIN_CONFIGS, bench.QUANTIZED_ZERO_CONFIGS):
        assert lst, "config list emptied"
        for cfg in lst:
            assert cfg["kind"] in kinds, cfg
            assert cfg["name"]
            json.dumps(cfg)  # the worker boundary is a JSON argv


def test_train_configs_reference_real_presets():
    bench = _bench()
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.models.gpt_moe import PRESETS as MOE

    for lst in (bench.INFINITY_CONFIGS, bench.PIPELINE_CONFIGS,
                bench.AOT_TRAIN_CONFIGS, bench.QUANTIZED_ZERO_CONFIGS):
        for cfg in lst:
            model = cfg.get("model")
            if model:
                assert model in gpt.PRESETS or model in MOE, cfg
            if cfg.get("remat_policy") and cfg["remat_policy"] != \
                    "save_attn_mlp_out":
                assert hasattr(__import__("jax").checkpoint_policies,
                               cfg["remat_policy"]), cfg


def test_tpu_core_sweep_includes_measured_moe_row():
    """The driver sweep itself must carry a measured MoE row, not just the
    moe_aot compile."""
    bench = _bench()
    cfgs = bench.tpu_core_configs()
    moe = [c for c in cfgs if c["kind"] == "moe_train"]
    assert moe and moe[0]["model"] == "moe-125m-8e"
    names = [c["name"] for c in cfgs]
    assert len(names) == len(set(names)), "duplicate config names"
    json.dumps(cfgs)


def test_moe_train_row_counts_toward_headline():
    """The measured MoE row competes for the headline like any train row."""
    bench = _bench()
    s = bench._summarize("tpu", [
        {"kind": "moe_train", "config": "moe-row", "platform": "tpu",
         "tokens_per_sec_chip": 9000.0, "mfu": 0.30},
    ], [])
    assert s["metric"].startswith("moe-row")
    assert s["vs_baseline"] == round(0.30 / 0.45, 3)


@pytest.mark.slow
def test_moe_train_worker_end_to_end():
    """The measured-MoE row must be executable as-is: run the actual bench
    worker subprocess on the tiny preset (a spec typo or engine regression
    here would burn chip time)."""
    import os
    import subprocess
    import sys

    bench = _bench()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, bench.__file__, "--worker",
         json.dumps({"kind": "moe_train", "name": "tiny-moe-worker",
                     "model": "tiny-moe", "micro_bs": 2, "seq": 32,
                     "steps": 2})],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=os.path.dirname(bench.__file__))
    assert p.returncode == 0, p.stderr[-800:]
    line = next(ln for ln in reversed(p.stdout.strip().splitlines())
                if ln.startswith("{"))
    r = json.loads(line)
    assert r["kind"] == "moe_train" and r["num_experts"] == 4
    # a CPU-host run: a rate under a host name, never a device metric
    assert r["host_tokens_per_sec"] > 0
    assert "tokens_per_sec_chip" not in r and "mfu" not in r
    import numpy as np

    assert np.isfinite(r["loss"])
