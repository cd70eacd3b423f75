"""GPT with Mixture-of-Experts MLPs (DeepSpeed-MoE capability).

Parity target: the reference's MoE training path — ``deepspeed/moe/layer.py`` wired
into a Megatron-style GPT where every ``moe_freq``-th MLP is a gated expert bank
(BASELINE.json config #4: "DeepSpeed-MoE GShard 350M x 64-expert"). PR-MoE's
residual experts (``moe/layer.py:34``) are available via ``use_residual``.

TPU-first structure: like :mod:`.gpt`, per-layer weights are stacked and scanned —
here over *super-blocks* of (``moe_freq - 1`` dense blocks, 1 MoE block), so one
compiled body serves any depth. The MoE load-balance aux loss is accumulated in the
scan carry and surfaced through the loss.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..moe.layer import MoEConfig, apply_moe, init_moe, moe_specs
from .api import Module, maybe_shard
from .gpt import (GPTConfig, _block, _dropout, attention_sublayer, layer_norm,
                  next_token_loss, require_default_block)
from .gpt import init_params as gpt_init_params
from .gpt import partition_specs as gpt_partition_specs

BATCH = ("dp", "ep")


@dataclasses.dataclass(frozen=True)
class GPTMoEConfig:
    base: GPTConfig = dataclasses.field(default_factory=GPTConfig)
    num_experts: int = 8
    moe_freq: int = 2           # every moe_freq-th layer is MoE (1 = all layers)
    k: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True
    use_residual: bool = False  # PR-MoE
    aux_loss_coef: float = 0.01
    num_groups: int = 1         # gating groups; set ~ dp*ep for rank-local gating

    def __post_init__(self):
        assert self.base.n_layer % self.moe_freq == 0, (
            f"n_layer {self.base.n_layer} must divide by moe_freq {self.moe_freq}")

    @property
    def n_super(self) -> int:
        return self.base.n_layer // self.moe_freq

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            d_model=self.base.d_model, d_ff=self.base.ffn_dim,
            num_experts=self.num_experts, k=self.k,
            capacity_factor=self.capacity_factor,
            eval_capacity_factor=self.eval_capacity_factor,
            min_capacity=self.min_capacity,
            noisy_gate_policy=self.noisy_gate_policy,
            drop_tokens=self.drop_tokens, use_rts=self.use_rts,
            use_residual=self.use_residual, num_groups=self.num_groups)


PRESETS: Dict[str, GPTMoEConfig] = {
    # BASELINE.json config #4 flagship
    "moe-350m-64e": GPTMoEConfig(
        base=GPTConfig(n_layer=24, n_head=16, d_model=1024), num_experts=64),
    "moe-125m-8e": GPTMoEConfig(
        base=GPTConfig(n_layer=12, n_head=12, d_model=768), num_experts=8),
    "tiny-moe": GPTMoEConfig(
        base=GPTConfig(vocab_size=256, n_layer=2, n_head=4, d_model=64,
                       max_seq_len=128),
        num_experts=4, moe_freq=2, capacity_factor=2.0),
}


def _stack_init(rng: jax.Array, n: int, init_one):
    """Stack n independently-initialized param trees on a leading axis."""
    keys = jax.random.split(rng, n)
    trees = [init_one(k) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg: GPTMoEConfig, rng: jax.Array) -> Dict[str, Any]:
    b = cfg.base
    k_base, k_moe = jax.random.split(rng)
    # dense skeleton: embeddings/lns from gpt init at the DENSE layer count
    dense_layers = b.n_layer - cfg.n_super  # layers keeping a dense MLP
    base_cfg = dataclasses.replace(b, n_layer=max(dense_layers, 1))
    # total_depth: residual-out init scales with the FULL depth, not the dense count
    params = gpt_init_params(base_cfg, k_base, total_depth=b.n_layer)
    if dense_layers == 0:
        # all layers MoE: the dense block stack is empty but attention weights are
        # still needed per layer — keep one stacked block set of attention-only use
        params_blocks = params["blocks"]
        params["blocks"] = jax.tree_util.tree_map(
            lambda x: x[:0], params_blocks)
    res_std = 0.02 / np.sqrt(2.0 * b.n_layer)
    # MoE blocks: attention weights + moe mlp, stacked over n_super
    moe_cfg = cfg.moe_config()

    def init_moe_block(key):
        ka, km = jax.random.split(key)
        kq, ko = jax.random.split(ka)
        d = b.d_model
        blk = {
            "ln1_scale": jnp.ones((d,)), "ln1_bias": jnp.zeros((d,)),
            "qkv_w": jax.random.normal(kq, (d, 3 * d), jnp.float32) * 0.02,
            "qkv_b": jnp.zeros((3 * d,)),
            "attn_out_w": jax.random.normal(ko, (d, d), jnp.float32) * res_std,
            "attn_out_b": jnp.zeros((d,)),
            "ln2_scale": jnp.ones((d,)), "ln2_bias": jnp.zeros((d,)),
            "moe": init_moe(km, moe_cfg, std=0.02, res_std=res_std),
        }
        return blk

    params["moe_blocks"] = _stack_init(k_moe, cfg.n_super, init_moe_block)
    return params


def partition_specs(cfg: GPTMoEConfig, param_shapes) -> Dict[str, Any]:
    b = cfg.base
    dense_layers = b.n_layer - cfg.n_super
    base_cfg = dataclasses.replace(b, n_layer=max(dense_layers, 1))
    specs = gpt_partition_specs(base_cfg, None)
    mspecs = moe_specs(cfg.moe_config())

    def prepend(spec: P) -> P:
        return P(None, *tuple(spec))

    specs["moe_blocks"] = {
        "ln1_scale": P(None, None), "ln1_bias": P(None, None),
        "qkv_w": P(None, None, "tp"), "qkv_b": P(None, "tp"),
        "attn_out_w": P(None, "tp", None), "attn_out_b": P(None, None),
        "ln2_scale": P(None, None), "ln2_bias": P(None, None),
        "moe": jax.tree_util.tree_map(
            prepend, mspecs, is_leaf=lambda x: isinstance(x, P)),
    }
    return specs


def _moe_block(cfg: GPTMoEConfig, x, w, positions, rng, train, layer_idx=None):
    b = cfg.base
    x = attention_sublayer(b, x, w, positions, rng, train, layer_idx=layer_idx)
    h = layer_norm(x, w["ln2_scale"], w["ln2_bias"], b.layer_norm_eps)
    # decorrelate gating noise/RTS draws from the dropout mask (both fold small
    # constants into their key; give the gate its own subtree of the key space)
    moe_rng = jax.random.fold_in(rng, 0x6A7E) if rng is not None else None
    y, aux, _counts = apply_moe(cfg.moe_config(), w["moe"], h, rng=moe_rng, train=train)
    x = x + _dropout(y, b.dropout, rng, train, salt=1)
    return x, aux


def forward(cfg: GPTMoEConfig, params, input_ids: jnp.ndarray,
            rngs=None, train: bool = True, return_hidden: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits [B,T,V], aux_loss) — or (post-LN hidden, aux_loss)
    with ``return_hidden`` (the chunked-loss path)."""
    b = cfg.base
    B, T = input_ids.shape
    if T > b.max_seq_len:
        raise ValueError(
            f"sequence length {T} exceeds max_seq_len {b.max_seq_len} "
            f"(out-of-range position lookups would return NaN)")
    x = jnp.take(params["wte"], input_ids, axis=0)
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    if not b.rotary:
        x = x + jnp.take(params["wpe"], positions + b.pos_offset, axis=0)
    x = x.astype(params["moe_blocks"]["qkv_w"].dtype)
    x = maybe_shard(x, P(BATCH, "sp", None))
    drng = (rngs or {}).get("dropout")

    n_dense_per_super = cfg.moe_freq - 1

    def super_block(x, dense_ws, moe_w, idx):
        # dense blocks of this super-block
        if n_dense_per_super > 0:
            def dense_body(carry, layer_w):
                xx, i = carry
                lrng = jax.random.fold_in(drng, i) if drng is not None else None
                xx = _block(b, xx, layer_w, positions, lrng, train, layer_idx=i)
                return (xx, i + 1), None

            (x, idx), _ = jax.lax.scan(dense_body, (x, idx), dense_ws)
        lrng = jax.random.fold_in(drng, idx) if drng is not None else None
        x, aux = _moe_block(cfg, x, moe_w, positions, lrng, train,
                            layer_idx=idx)
        return x, idx + 1, aux

    if cfg.base.remat:
        policy = getattr(jax.checkpoint_policies, cfg.base.remat_policy)
        super_block = jax.checkpoint(super_block, policy=policy, static_argnums=())

    # reshape stacked dense blocks [L_dense, ...] -> [n_super, n_dense_per_super, ...]
    dense_stack = params["blocks"]
    if n_dense_per_super > 0:
        dense_stack = jax.tree_util.tree_map(
            lambda a: a.reshape(cfg.n_super, n_dense_per_super, *a.shape[1:]),
            dense_stack)

    # super-block loop through the ZeRO-3 gather window (stage3 knobs apply to
    # MoE stacks too; plain scan when unconfigured — runtime/zero/gather.py)
    from ..runtime.zero.gather import zero3_layer_scan

    specs_all = partition_specs(cfg, None)
    moe_specs_t = jax.tree_util.tree_map(
        lambda s: P(*tuple(s)[1:]), specs_all["moe_blocks"],
        is_leaf=lambda s: isinstance(s, P))
    if n_dense_per_super > 0:
        def body(carry, layer_in):
            x, idx, aux_sum = carry
            dense_ws, moe_w = layer_in
            x, idx, aux = super_block(x, dense_ws, moe_w, idx)
            return (x, idx, aux_sum + aux), None

        xs = (dense_stack, params["moe_blocks"])
        dense_specs_t = jax.tree_util.tree_map(
            lambda s: P(None, *tuple(s)[1:]), specs_all["blocks"],
            is_leaf=lambda s: isinstance(s, P))
        gathered = (dense_specs_t, moe_specs_t)
    else:
        def body(carry, moe_w):
            x, idx, aux_sum = carry
            x, idx, aux = super_block(x, None, moe_w, idx)
            return (x, idx, aux_sum + aux), None

        xs = params["moe_blocks"]
        gathered = moe_specs_t

    (x, _, aux_sum) = zero3_layer_scan(
        body, (x, jnp.int32(0), jnp.float32(0.0)), xs, gathered_spec=gathered)

    x = layer_norm(x, params["lnf_scale"], params["lnf_bias"], b.layer_norm_eps)
    if return_hidden:
        return x, aux_sum / cfg.n_super
    head = params["wte"] if b.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,vd->btv", x, head.astype(x.dtype))
    return logits, aux_sum / cfg.n_super


def loss_fn(cfg: GPTMoEConfig, params, batch, rngs=None, train: bool = True):
    if cfg.base.loss_chunk:
        # same chunked head as the dense model (the fp32 [B,T,V] logits never
        # materialize) — silently dropping the knob would re-create the exact
        # OOM it exists to avoid
        from .gpt import _chunk_targets, chunked_head_loss

        ids_in, targets, mask, n_tok = _chunk_targets(cfg.base, batch)
        hidden, aux = forward(cfg, params, ids_in, rngs=rngs, train=train,
                              return_hidden=True)
        lm_loss, _ = chunked_head_loss(cfg.base, params, hidden, targets,
                                       mask, num_tokens=n_tok)
        return (lm_loss + cfg.aux_loss_coef * aux,
                {"lm_loss": lm_loss, "moe_aux_loss": aux})
    aux_box = []

    def fwd(ids):
        logits, aux = forward(cfg, params, ids, rngs=rngs, train=train)
        aux_box.append(aux)
        return logits

    lm_loss, _ = next_token_loss(fwd, cfg.base.max_seq_len, batch)
    aux = aux_box[0]
    loss = lm_loss + cfg.aux_loss_coef * aux
    return loss, {"lm_loss": lm_loss, "moe_aux_loss": aux}


def build(cfg_or_name) -> Tuple[Module, GPTMoEConfig]:
    cfg = PRESETS[cfg_or_name] if isinstance(cfg_or_name, str) else cfg_or_name
    require_default_block(cfg.base, "the expert model (models/gpt_moe.py)")
    return Module(
        init=functools.partial(init_params, cfg),
        apply=lambda params, batch, rngs=None, train=True: loss_fn(
            cfg, params, batch, rngs=rngs, train=train),
        partition_specs=functools.partial(partition_specs, cfg),
    ), cfg


# ------------------------------------------------------------- KV-cache decode
def init_cache(cfg: GPTMoEConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16):
    """Dense-block and MoE-block cache stacks (layouts as ``gpt.init_cache``).
    Parity: the reference's MoE inference workspace
    (``ops/transformer/inference/moe_inference.py`` + ``inference_context.h``)."""
    b = cfg.base
    dense_layers = b.n_layer - cfg.n_super
    shape_d = (dense_layers, batch_size, b.n_head, max_len, b.head_dim)
    shape_m = (cfg.n_super, batch_size, b.n_head, max_len, b.head_dim)
    return {"k_dense": jnp.zeros(shape_d, dtype), "v_dense": jnp.zeros(shape_d, dtype),
            "k_moe": jnp.zeros(shape_m, dtype), "v_moe": jnp.zeros(shape_m, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def _moe_block_with_cache(cfg: GPTMoEConfig, x, w, k_c, v_c, pos,
                          layer_idx=None):
    """Cached MoE block: cached attention + expert-parallel MLP (eval gating:
    no jitter/RTS, eval capacity factor). Parity: the reference's
    ``DeepSpeedMoEInference`` layer (``ops/transformer/inference/moe_inference.py``)."""
    b = cfg.base
    from .gpt import attn_with_cache

    x, k_c, v_c = attn_with_cache(b, x, w, k_c, v_c, pos, layer_idx=layer_idx)
    h = layer_norm(x, w["ln2_scale"], w["ln2_bias"], b.layer_norm_eps)
    y, _aux, _counts = apply_moe(cfg.moe_config(), w["moe"], h, rng=None,
                                 train=False)
    return x + y, k_c, v_c


def forward_with_cache(cfg: GPTMoEConfig, params, input_ids: jnp.ndarray, cache):
    """Prefill or decode through the dense/MoE super-block structure; returns
    (logits [B, T, V], new_cache)."""
    from .gpt import _block_with_cache

    b = cfg.base
    B, T = input_ids.shape
    pos = cache["pos"]
    x = jnp.take(params["wte"], input_ids, axis=0)
    positions = pos + jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    if not b.rotary:
        x = x + jnp.take(params["wpe"], positions + b.pos_offset, axis=0)
    x = x.astype(params["moe_blocks"]["qkv_w"].dtype)
    x = maybe_shard(x, P(BATCH, None, None))

    n_dense = cfg.moe_freq - 1

    def super_body(carry, layer_in):
        x, idx = carry  # idx = global layer index (local-attention schedule)
        if n_dense > 0:
            dense_ws, kd, vd, moe_w, km, vm = layer_in

            def dense_body(c, lin):
                xx, i = c
                layer_w, k_c, v_c = lin
                xx, k_c, v_c = _block_with_cache(b, xx, layer_w, k_c, v_c, pos,
                                                 layer_idx=i)
                return (xx, i + 1), (k_c, v_c)

            (x, idx), (kd, vd) = jax.lax.scan(
                dense_body, (x, idx), (dense_ws, kd, vd))
        else:
            moe_w, km, vm = layer_in
            kd = vd = None
        x, km, vm = _moe_block_with_cache(cfg, x, moe_w, km, vm, pos,
                                          layer_idx=idx)
        out = (kd, vd, km, vm) if n_dense > 0 else (km, vm)
        return (x, idx + 1), out

    if n_dense > 0:
        dense_stack = jax.tree_util.tree_map(
            lambda a: a.reshape(cfg.n_super, n_dense, *a.shape[1:]),
            params["blocks"])
        kd = cache["k_dense"].reshape(cfg.n_super, n_dense, *cache["k_dense"].shape[1:])
        vd = cache["v_dense"].reshape(cfg.n_super, n_dense, *cache["v_dense"].shape[1:])
        xs = (dense_stack, kd, vd, params["moe_blocks"], cache["k_moe"], cache["v_moe"])
    else:
        xs = (params["moe_blocks"], cache["k_moe"], cache["v_moe"])

    (x, _), outs = jax.lax.scan(super_body, (x, jnp.int32(0)), xs)
    if n_dense > 0:
        new_kd, new_vd, new_km, new_vm = outs
        new_kd = new_kd.reshape(cache["k_dense"].shape)
        new_vd = new_vd.reshape(cache["v_dense"].shape)
    else:
        new_km, new_vm = outs
        new_kd, new_vd = cache["k_dense"], cache["v_dense"]

    x = layer_norm(x, params["lnf_scale"], params["lnf_bias"], b.layer_norm_eps)
    head = params["wte"] if b.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,vd->btv", x, head.astype(x.dtype))
    new_cache = {"k_dense": new_kd, "v_dense": new_vd, "k_moe": new_km,
                 "v_moe": new_vm, "pos": pos + T}
    return logits, new_cache
