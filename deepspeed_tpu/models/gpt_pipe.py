"""Pipeline-parallel GPT: the stacked-layer GPT executed over the ``pp`` mesh axis.

Capability parity with the reference's pipeline training path (``PipelineModule`` +
``PipelineEngine.train_batch``, ``runtime/pipe/module.py:86`` /
``runtime/pipe/engine.py:295``) for its flagship workload (decoder LM). The generic
layer-list machinery lives in :mod:`deepspeed_tpu.runtime.pipe.module`; this module
is the homogeneous-transformer fast path that actually pipelines on TPU:

- block params ``[L, ...]`` are reshaped to ``[S, L/S, ...]`` with the stage axis
  sharded ``P("pp", ...)``;
- micro-batches stream through :func:`~deepspeed_tpu.runtime.pipe.spmd.pipelined_apply`
  (collective-permute pipelining, autodiff backward pipeline);
- embedding and LM head stay outside the pipelined scan, replicated over ``pp``;
  tied-embedding gradients combine automatically (the reference's explicit
  tied-weight allreduce at ``runtime/pipe/module.py:421`` is autodiff here).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..runtime.pipe.spmd import (
    pipelined_apply,
    split_microbatches,
    stack_stage_params,
)
from .api import Module, maybe_shard
from . import gpt as G

BATCH = G.BATCH


def init_params(cfg: G.GPTConfig, num_stages: int, rng: jax.Array) -> Dict[str, Any]:
    params = G.init_params(cfg, rng)
    params["blocks"] = stack_stage_params(params["blocks"], num_stages)
    return params


def partition_specs(cfg: G.GPTConfig, num_stages: int, param_shapes) -> Dict[str, Any]:
    """Stage axis over pp; per-layer axis free; tp specs shifted right by one."""
    base = G.partition_specs(cfg, param_shapes)
    base["blocks"] = jax.tree_util.tree_map(
        lambda spec: P("pp", None, *tuple(spec)[1:]), base["blocks"],
        is_leaf=lambda x: isinstance(x, P))
    return base


def forward(cfg: G.GPTConfig, num_stages: int, num_micro: int, params,
            input_ids: jnp.ndarray, rngs=None, train: bool = True,
            return_hidden: bool = False) -> jnp.ndarray:
    """Logits [B, T, V] via pipelined blocks (or the post-LN hidden states
    with ``return_hidden``). B must divide by num_micro."""
    B, T = input_ids.shape
    if T > cfg.max_seq_len:
        raise ValueError(
            f"sequence length {T} exceeds max_seq_len {cfg.max_seq_len} "
            f"(out-of-range position lookups would return NaN)")
    x = jnp.take(params["wte"], input_ids, axis=0)
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    if not cfg.rotary:
        x = x + jnp.take(params["wpe"], positions + cfg.pos_offset, axis=0)
    x = x.astype(params["blocks"]["qkv_w"].dtype)

    drng = (rngs or {}).get("dropout")
    # positions per micro-batch are identical slices; recompute inside the stage
    mb = B // num_micro
    stream = split_microbatches(x, num_micro)  # [M, mb, T, D]

    layers_per_stage = cfg.n_layer // num_stages

    def stage_fn(w, x, micro_id, stage_id):
        # w: blocks dict with leading [L/S]; one micro-batch x: [mb, T, D]
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (x.shape[0], T))

        def body(carry, layer_w):
            x, i = carry  # i = GLOBAL layer index (matches dense rng folding)
            lrng = (jax.random.fold_in(jax.random.fold_in(drng, micro_id), i)
                    if drng is not None else None)
            x = G._block(cfg, x, layer_w, pos, lrng, train, layer_idx=i)
            return (x, i + 1), None

        (x, _), _ = jax.lax.scan(
            body, (x, stage_id * layers_per_stage), w)
        return x

    stream_spec = P(BATCH, None, None)  # [mb, T, D] per micro-batch
    out = pipelined_apply(
        stage_fn, params["blocks"], stream, num_stages,
        stream_spec=stream_spec, remat=True)
    x = out.reshape(B, T, -1)
    x = maybe_shard(x, P(BATCH, None, None))
    x = G.layer_norm(x, params["lnf_scale"], params["lnf_bias"], cfg.layer_norm_eps)
    if return_hidden:
        return x
    head = params["wte"] if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("btd,vd->btv", x, head.astype(x.dtype))


def loss_fn(cfg: G.GPTConfig, num_stages: int, num_micro: int, params, batch,
            rngs=None, train: bool = True):
    if cfg.loss_chunk:
        # same chunked head as the dense model — the fp32 [B,T,V] logits
        # never materialize (G.chunked_head_loss)
        ids_in, targets, mask, n_tok = G._chunk_targets(cfg, batch)
        hidden = forward(cfg, num_stages, num_micro, params, ids_in,
                         rngs=rngs, train=train, return_hidden=True)
        return G.chunked_head_loss(cfg, params, hidden, targets, mask,
                                   num_tokens=n_tok)
    return G.next_token_loss(
        lambda ids: forward(cfg, num_stages, num_micro, params, ids,
                            rngs=rngs, train=train),
        cfg.max_seq_len, batch)


def build(cfg_or_name, num_stages: int, num_micro: int) -> Tuple[Module, G.GPTConfig]:
    """Pipeline-parallel GPT :class:`Module`. ``num_stages`` must equal the mesh's
    ``pp`` extent; ``cfg.n_layer`` must divide by it; the per-step batch must
    divide by ``num_micro``."""
    cfg = G.PRESETS[cfg_or_name] if isinstance(cfg_or_name, str) else cfg_or_name
    G.require_default_block(cfg, "the pipelined GPT (models/gpt_pipe.py)")
    if cfg.n_layer % num_stages != 0:
        raise ValueError(f"n_layer {cfg.n_layer} % stages {num_stages} != 0")
    return Module(
        init=functools.partial(init_params, cfg, num_stages),
        apply=lambda params, batch, rngs=None, train=True: loss_fn(
            cfg, num_stages, num_micro, params, batch, rngs=rngs, train=train),
        partition_specs=functools.partial(partition_specs, cfg, num_stages),
        pipelined=True,
    ), cfg
