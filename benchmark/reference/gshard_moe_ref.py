"""Plain reference forward and loss for the GPT block with GShard top-1
experts (Lepikhin et al. 2020, "GShard", section 2.2 and algorithm 1; Fedus et
al. 2021, "Switch Transformers", section 2.1 for one expert a token).

``gpt_ref``'s model with the MLP of every ``moe_freq``-th layer replaced by

    p = softmax(h W_g)            float32, over the ``num_experts`` experts
    e = argmax_e p_e
    y = p_e * FFN_e(h),           FFN_e(h) = gelu_tanh(h W1_e + b1_e) W2_e + b2_e

with ``h`` the layer's second layer norm of the residual stream. An expert
layer is sequential whatever ``parallel_residual`` says of the dense ones
(``x' = x + Attn(LN1(x))``, then ``x' + y(LN2(x'))``), as in the Megatron GPT
that DeepSpeed-MoE trains. Every token reaches its expert: no capacity, no
dropped token, no random tie-breaking and no auxiliary loss in the loss
returned here, so a configuration has to say ``k`` 1, ``drop_tokens`` false
and ``aux_loss_coef`` 0 (anything else is refused: top-2's renormalised
weights and capacity queues are other equations).

Float32 under ``jax.default_matmul_precision("highest")``, one sequence at a
time, every expert computed for every token and the chosen one selected: no
sort, no gather, no capacity buffer, nothing shared with
``deepspeed_tpu/models/gpt_moe.py`` or ``deepspeed_tpu/moe``. It reads the
program's parameter tree: dense layers stacked under ``blocks`` in their own
order, expert layers under ``moe_blocks`` with ``moe.gate_w`` [d, E] and
``moe.experts.{up_w [E, d, f], up_b, down_w [E, f, d], down_b}``. Layer ``l``
(from 0) is an expert layer where ``(l + 1) % moe_freq == 0``.

Its counts (``lib/context.Context.count`` prefers them to ``lib/flops``'s):
a token multiplies with the one expert that runs, not with all of them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..lib import flops
from . import gpt_ref as G


COVERS = {"k": 1, "drop_tokens": False, "aux_loss_coef": 0.0}
NOT_COVERED = ("use_residual", "noisy_gate_policy")


def _check(model: dict) -> None:
    have = {key: model.get(key) for key in COVERS}
    have.update({key: model[key] for key in NOT_COVERED if model.get(key)})
    if have != COVERS:
        raise ValueError(f"gshard_moe_ref covers {COVERS} and neither of "
                         f"{NOT_COVERED}; the configuration says {have}")


def moe_mlp(model: dict, h, w):
    """``p_e(h) * FFN_e(h)`` for ``h`` [T, d], ``e`` each token's argmax."""
    probs = jax.nn.softmax(h @ G._f32(w["gate_w"]), axis=-1)        # [T, E]
    chosen = jnp.argmax(probs, axis=-1)
    ex = w["experts"]
    y = jnp.zeros_like(h)
    for e in range(model["num_experts"]):
        up = G.gelu_tanh(h @ G._f32(ex["up_w"][e]) + G._f32(ex["up_b"][e]))
        out = up @ G._f32(ex["down_w"][e]) + G._f32(ex["down_b"][e])
        y = y + jnp.where((chosen == e)[:, None], probs[:, e:e + 1] * out, 0.0)
    return y


def moe_block(model: dict, x, w):
    eps = model.get("layer_norm_eps", 1e-5)
    x = x + G.attention(model, G.layer_norm(x, w["ln1_scale"], w["ln1_bias"],
                                            eps), w)
    h = G.layer_norm(x, w["ln2_scale"], w["ln2_bias"], eps)
    return x + moe_mlp(model, h, w["moe"])


@functools.partial(jax.jit, static_argnums=(0,))
def _moe_block_at(model_items, x, moe_blocks, index):
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False),
        moe_blocks)
    return moe_block(dict(model_items), x, w)


def hidden(model: dict, params, ids):
    """Residual stream after the last block for one sequence ``ids`` [T]."""
    _check(model)
    items, freq = G._frozen(model), model["moe_freq"]
    with jax.default_matmul_precision("highest"):
        x = G._embed(items, params, jnp.asarray(ids, jnp.int32))
        for layer in range(model["n_layer"]):
            if (layer + 1) % freq == 0:
                x = _moe_block_at(items, x, params["moe_blocks"],
                                  jnp.int32(layer // freq))
            else:
                x = G._block_at(items, x, params["blocks"],
                                jnp.int32(layer - layer // freq))
    return x


def logits(model: dict, params, ids, positions=None):
    """Logits [len(positions), V] of one sequence; all positions if None."""
    return G.head_logits(model, params, hidden(model, params, ids), positions)


def loss(model: dict, params, batch_ids) -> float:
    """Mean next-token cross entropy over ``batch_ids`` [B, T], as
    ``gpt_ref.loss``: the expert layers add nothing to it."""
    return G.mean_nll(lambda ids: logits(model, params, ids), batch_ids)


# ------------------------------------------------------------------ counts
def expert_params(model: dict) -> int:
    """Weights and biases of one expert's two matrices."""
    d, f = model["d_model"], flops._ffn(model)
    return 2 * d * f + f + d


def matmul_params(model: dict) -> int:
    """Parameters a token multiplies with: a dense layer's block, an expert
    layer's block with the gate and ``k`` experts in place of the MLP, and
    the head."""
    dense = flops.block_params(model)
    expert_layer = (dense - expert_params(model)
                    + model["d_model"] * model["num_experts"]
                    + model["k"] * expert_params(model))
    n_expert = model["n_layer"] // model["moe_freq"]
    return ((model["n_layer"] - n_expert) * dense + n_expert * expert_layer
            + model["vocab_size"] * model["d_model"])


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """``lib/flops.train_flops_per_token``'s convention (6N + 12*L*d*T, no
    recomputation) with N the parameters a token meets."""
    return (6.0 * matmul_params(model)
            + 12.0 * model["n_layer"] * model["d_model"] * seq_len)
