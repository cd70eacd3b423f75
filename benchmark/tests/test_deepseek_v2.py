"""The DeepSeek-V2 files: the kernel's cost, the reference's counts at the
published widths, the configuration against the catalog's arithmetic, the
family's third output, and the two readers on a run with nothing to read."""

import json
import os

import numpy as np

import jax
import jax.numpy as jnp

from benchmark.lib import kernel_cost_mla, manifest
from benchmark.lib.peaks import device_peaks
from benchmark.reference import deepseek_v2_ref as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_the_latent_kernel_sits_on_the_ridge():
    cost = kernel_cost_mla.paged_decode_mla(1.0, 128, 512, 64)
    assert cost.flops == 278528 and cost.bytes == 1152
    peaks = device_peaks("TPU v5 lite")
    ridge = peaks.bf16_flops / peaks.hbm_bytes_per_s
    assert abs(cost.flops / cost.bytes - 241.8) < 0.05
    assert abs(ridge - 240.5) < 0.1 and cost.bound(peaks) == "flops"
    # ten thousand live rows: the floor is their operations over the peak
    many = kernel_cost_mla.paged_decode_mla(1e4, 128, 512, 64)
    assert abs(many.floor_s(peaks) - 278528e4 / peaks.bf16_flops) < 1e-12


def test_the_cut_is_the_issues_arithmetic():
    config = _config("deepseek-v2-serve")
    model = config["model"]
    assert abs(ref.attention_params(model) / 1e6 - 149.23) < 0.01
    assert abs(ref.expert_params(model) / 1e6 - 23.59) < 0.01
    assert abs(ref.held_params(model) / 1e9 - 5.164) < 0.001
    assert ref.kv_bytes_per_token(model) == 5 * 576 * 2 == 5760
    assert ref.cache_layers(model) == 5
    # a decode step at 128 slots and a mean context of 1,920: the weights
    # but the embedding table, and the live latent rows
    step = ref.decode_step_bytes(model, 128 * 1920)
    weights = (ref.held_params(model) - 25600 * 5120) * 2
    assert step == weights + 128 * 1920 * 5760
    assert abs(4 * 40 * ref.expert_params(model) * 2 / 1e9 - 7.55) < 0.01
    assert abs(0.9625 ** 128 - 0.0075) < 0.0002
    # what the file says it reduced is what differs from the source's keys
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    for key, value in config["published"].items():
        assert config[key] != value
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 40, 25600)
    assert model["n_routed_experts"] == 160 and model["held_experts"] == [0, 40]
    traffic = json.load(open(os.path.join(ROOT, "traffic",
                                          "long-decode.json")))
    assert traffic["pages"] == traffic["slots"] * 3072 // 64 + 1 == 6145


def test_the_familys_step_hands_over_the_experts_it_chose():
    family = manifest.plugin("families", "deepseek_v2")
    model = _config("tiny-deepseek-v2-serve")["model"]
    cfg = family.config(model)
    params = family.init_params(cfg, jax.random.PRNGKey(0))
    assert params["moe_blocks"]["experts_up_w"].dtype == jnp.bfloat16
    pool = jax.eval_shape(lambda: family.init_cache(cfg, 1, 8, jnp.float32))
    assert sorted(pool) == ["k", "pos"]
    from deepspeed_tpu.models import gpt

    cache = gpt.init_paged_cache(cfg, 5, 16, jnp.float32)
    out = family.paged_decode_step(
        cfg, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params),
        jnp.zeros((2,), jnp.int32), cache, jnp.ones((2, 2), jnp.int32),
        jnp.asarray([3, 0]), impl="gather")
    assert len(out) == 3
    chosen = np.asarray(out[2])
    assert chosen.shape == (2, model["n_layer"], model["k"])
    assert (chosen[:, 0] == -1).all() and (chosen[:, 1:] >= 0).all()
    # inside topk_group groups, as the reference demands of a handed set
    per = model["n_routed_experts"] // model["n_group"]
    assert all(len({e // per for e in row}) <= model["topk_group"]
               for row in chosen[:, 1:].reshape(-1, model["k"]).tolist())


class _Nothing:
    """A run with no trace: ``program_trace.of`` finds nothing."""
    cell = {"config_file": _config("tiny-deepseek-v2-serve")}
    trace = traced = None
    spans = None


def test_the_new_readers_read_nothing_where_nothing_is(monkeypatch):
    from benchmark.lib import program_trace
    from benchmark.readers import prog_roofline_mla, prog_span_stat

    monkeypatch.setattr(program_trace, "of", lambda ctx: None)
    assert prog_roofline_mla.read(_Nothing(), {"kernel": "paged_decode_mla"}) \
        is None
    assert prog_span_stat.read(_Nothing(), {"span": "serve.decode",
                                            "stat": "expert_load_max"}) is None
