"""The FLOP and byte functions count what the parameter tree holds."""

import json
import os

import pytest

import jax

from benchmark.lib import flops
from benchmark.lib.manifest import ROOT
from deepspeed_tpu.models import gpt

CONFIGS = ["gpt2-medium-train", "pythia-1.4b-train-dp4", "tiny-serve",
           "tiny-train"]


def model_of(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("name", CONFIGS)
def test_params_match_the_tree(name):
    model = model_of(name)
    cfg = gpt.GPTConfig(**model)
    shapes = jax.eval_shape(lambda k: gpt.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    size = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa: E731
    assert flops.total_params(model) == size(shapes)
    assert flops.block_params(model) * cfg.n_layer == size(shapes["blocks"])
    head = shapes["wte"] if cfg.tie_embeddings else shapes["lm_head"]
    assert flops.matmul_params(model) == size(shapes["blocks"]) + head.size


def test_known_counts():
    gpt2 = model_of("gpt2-medium-train")
    assert flops.matmul_params(gpt2) == 24 * 12_596_224 + 50304 * 1024
    assert flops.train_flops_per_token(gpt2, 1024) == pytest.approx(
        6 * flops.matmul_params(gpt2) + 12 * 24 * 1024 * 1024)
    pythia = model_of("pythia-1.4b-train-dp4")
    assert flops.kv_bytes_per_token(pythia) == 2 * 24 * 2048 * 2
    # a page of 64 tokens over 24 layers: the 12.6 MB the issue reckons with
    assert 64 * flops.kv_bytes_per_token(pythia) == 12_582_912
    assert flops.decode_step_bytes(pythia, 1000) == (
        2 * flops.matmul_params(pythia) + 1000 * 196_608)
