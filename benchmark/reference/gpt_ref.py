"""Plain reference forward and loss for the GPT-2 and GPT-NeoX blocks.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one sequence at a time, no kernel,
no cache, no batching, no sharding rule. Written from the published block
equations (Radford et al. 2019, "Language Models are Unsupervised Multitask
Learners"; Black et al. 2022, "GPT-NeoX-20B", section 2; Su et al. 2021 for
the rotary embedding) and independent of ``deepspeed_tpu/models/gpt.py``: it
shares no function with it. It reads the same parameter tree, whose names and
layout are the program's:

- ``blocks[name][l]`` is layer ``l`` of a stacked leaf;
- ``qkv_w`` is [d, 3d] with columns ordered q | k | v, each split into heads
  of ``d / n_head`` columns. The GPT-NeoX checkpoint orders the same columns
  head by head; with weights drawn from a seed that is a permutation and no
  departure in the mathematics.

The weights arrive in the type they are served or trained in (bf16) and are
upcast one block at a time, so the reference needs no second copy of the
model.

``model`` is the ``model`` group of a configuration file.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _f32(x):
    return x.astype(jnp.float32)


def layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(gain) + _f32(bias)


def gelu_tanh(x):
    """GPT-2's ``gelu_new``."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def gelu_erf(x):
    """GPT-NeoX's ``gelu``."""
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


ACTIVATIONS = {"gelu": gelu_tanh, "gelu_exact": gelu_erf}


def rotary(x, rot_dims: int, base: float = 10000.0):
    """Rotate the first ``rot_dims`` of each head by position (GPT-NeoX pairs
    dimension i with i + rot_dims/2). x: [T, H, Dh]."""
    t = x.shape[0]
    half = rot_dims // 2
    inv_freq = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:rot_dims], x[..., rot_dims:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def attention(model: dict, h, w):
    """Causal multi-head self-attention of the normalised input ``h`` [T, d]."""
    t, d = h.shape
    n_head = model["n_head"]
    dh = d // n_head
    qkv = h @ _f32(w["qkv_w"]) + _f32(w["qkv_b"])
    q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(t, n_head, dh)
               for i in range(3))
    if model.get("rotary"):
        rot = int(model.get("rotary_pct", 1.0) * dh)
        rot -= rot % 2
        q, k = rotary(q, rot), rotary(k, rot)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(t, d)
    return out @ _f32(w["attn_out_w"]) + _f32(w["attn_out_b"])


def mlp(model: dict, h, w):
    act = ACTIVATIONS[model.get("activation", "gelu")]
    up = act(h @ _f32(w["mlp_up_w"]) + _f32(w["mlp_up_b"]))
    return up @ _f32(w["mlp_down_w"]) + _f32(w["mlp_down_b"])


def block(model: dict, x, w):
    eps = model.get("layer_norm_eps", 1e-5)
    h1 = layer_norm(x, w["ln1_scale"], w["ln1_bias"], eps)
    if model.get("parallel_residual"):
        # GPT-NeoX: x + Attn(LN1(x)) + MLP(LN2(x))
        h2 = layer_norm(x, w["ln2_scale"], w["ln2_bias"], eps)
        return x + attention(model, h1, w) + mlp(model, h2, w)
    # GPT-2: x' = x + Attn(LN1(x)); x' + MLP(LN2(x'))
    x = x + attention(model, h1, w)
    h2 = layer_norm(x, w["ln2_scale"], w["ln2_bias"], eps)
    return x + mlp(model, h2, w)


def _frozen(model: dict):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, bool, str))))


@functools.partial(jax.jit, static_argnums=(0,))
def _block_at(model_items, x, blocks, layer):
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        blocks)
    return block(dict(model_items), x, w)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(model_items, params, ids):
    model = dict(model_items)
    x = _f32(params["wte"])[ids]
    if not model.get("rotary"):
        x = x + _f32(params["wpe"])[jnp.arange(ids.shape[0])]
    return x


@functools.partial(jax.jit, static_argnums=(0,))
def _head(model_items, params, x):
    model = dict(model_items)
    x = layer_norm(x, params["lnf_scale"], params["lnf_bias"],
                   model.get("layer_norm_eps", 1e-5))
    head = params["wte"] if model.get("tie_embeddings", True) \
        else params["lm_head"]
    return x @ _f32(head).T


def hidden(model: dict, params, ids):
    """Residual stream after the last block for one sequence ``ids`` [T]."""
    items = _frozen(model)
    with jax.default_matmul_precision("highest"):
        x = _embed(items, params, jnp.asarray(ids, jnp.int32))
        for layer in range(model["n_layer"]):
            x = _block_at(items, x, params["blocks"], jnp.int32(layer))
    return x


def head_logits(model: dict, params, x, positions=None):
    """Final layer norm and head over the rows ``positions`` of the residual
    stream ``x`` [T, d]; all rows if None."""
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    with jax.default_matmul_precision("highest"):
        return _head(_frozen(model), params, x)


def logits(model: dict, params, ids, positions=None):
    """Logits [len(positions), V] of one sequence; all positions if None."""
    return head_logits(model, params, hidden(model, params, ids), positions)


@jax.jit
def _nll(lg, targets):
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def mean_nll(logits_of, batch_ids) -> float:
    """Mean next-token cross entropy over ``batch_ids`` [B, T] of the model
    whose logits for one sequence ``logits_of(ids)`` gives: position t
    predicts token t+1, the last position predicts nothing."""
    total, count = 0.0, 0
    for ids in batch_ids:
        lg = logits_of(ids)[:-1]
        total += float(_nll(lg, jnp.asarray(ids[1:], jnp.int32)))
        count += len(ids) - 1
    return total / count


def loss(model: dict, params, batch_ids) -> float:
    return mean_nll(lambda ids: logits(model, params, ids), batch_ids)
