"""Family ``deepseek_v2``: DeepSeek-V2's layers on the program's normal path,
``deepspeed_tpu/models/gpt.py`` with its attention sublayer, rotary scaling,
router and kinds of layer said as data (latent attention over a latent page
pool, YaRN, a leading dense layer and routed layers with a shared expert,
``moe/dropless.py`` over the experts this chip holds).
``reference/deepseek_v2_ref.py`` has the equations and the parameter tree;
``init_params`` here makes that tree.

``config(model)`` takes the ``model`` group of a configuration file in the
names ``deepseek_v2_ref`` reads and refuses what the reference refuses. It
rotates in float32, keeps a linear's output in float32 up to the next
rounding point and the serving forwards' stream in float32
(``rotary_float32``, ``linear_out_float32``, ``stream_float32``): five layers
whose outputs each dwarf the stream they are added to carry every rounding
on, and the bf16 path read 0.011-0.026 on the first compared number for a
tolerance of 0.0125 (PERF.md, section 6, PR 34).

The reference routes, so ``paged_decode_step`` returns the experts its step
chose third, int32 ``[slots, n_layer, k]`` (``benchmark/README.md``, the
``model family`` row): a dense layer's row is -1, as the reference takes it.

``init_params`` rounds every matrix to bf16 as it is drawn, a piece no larger
than an expert at a time (``gpt._normal_in_pieces``): ``lib/mode_serve.build``
casts to the served type only after the whole tree is made, and a float32
tree of 5.16 B parameters is 20.7 GB, which no chip holds. The values are
N(0, 0.02) (0.02 / sqrt(2 n_layer) for the projections into the stream)
rounded to bf16, what the reference upcasts either way; the norm gains stay
float32 ones.
"""

from __future__ import annotations

import jax.numpy as jnp

from deepspeed_tpu.models import gpt as _gpt
from deepspeed_tpu.models.gpt import init_cache  # noqa: F401

from ..reference import deepseek_v2_ref


def config(model: dict):
    deepseek_v2_ref._check(model)
    rs = model["rope_scaling"]
    return _gpt.GPTConfig(
        vocab_size=model["vocab_size"], n_layer=model["n_layer"],
        n_head=model["n_head"], d_model=model["d_model"], d_ff=model["d_ff"],
        max_seq_len=model["max_seq_len"], rotary=True, tie_embeddings=False,
        activation="silu", layer_norm_eps=model["rms_norm_eps"],
        norm="rmsnorm", mlp_gated=True, linear_bias=False,
        rope_theta=float(model["rope_theta"]), rotary_float32=True,
        linear_out_float32=True, stream_float32=True,
        attn_kind="mla", q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        rope_scaling=_gpt.YarnScaling(
            factor=float(rs["factor"]),
            original_max_len=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs.get("mscale", 1.0)),
            mscale_all_dim=float(rs.get("mscale_all_dim", 0.0))),
        moe_experts=model["n_routed_experts"],
        moe_held=tuple(model["held_experts"]), moe_k=model["k"],
        moe_groups=model["n_group"], moe_topk_groups=model["topk_group"],
        moe_d_ff=model["moe_d_ff"],
        moe_shared_d_ff=model["n_shared_experts"] * model["moe_d_ff"],
        moe_scale=float(model["routed_scaling_factor"]),
        moe_dense_layers=model["n_dense_layers"],
        # tools/compile_only.py says which attention to lower
        use_flash=model.get("use_flash"))


def module(cfg):
    return _gpt.build(cfg)[0]


def init_params(cfg, key):
    return _gpt.init_params(cfg, key, dtype=jnp.bfloat16)


def paged_decode_step(cfg, params, tokens, cache, tables, lengths, impl=None):
    """(logits [slots, V], the pool, the experts chosen [slots, n_layer, k])
    of the program's own step."""
    logits, cache, (chosen, _) = _gpt.paged_decode_step(
        cfg, params, tokens, cache, tables, lengths, impl=impl,
        return_routing=True)
    return logits, cache, chosen
