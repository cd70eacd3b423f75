"""Family ``longcat_flash``: the language model of LongCat-Flash-Omni on the
program's normal path, ``deepspeed_tpu/models/gpt.py`` with a layer of two
latent-attention sub-blocks, two dense MLPs and one routed branch across them
(``moe_shortcut``), a router over real and zero-compute experts
(``moe_zero_experts``: an index at or past the real ones is the identity) with
a choice bias beside a softmax's scores, and the two rescales of the normed
latents, said as data; ``paged_decode_mla`` over a latent page pool of two
cache layers a layer; ``moe/dropless.py`` over the real experts this chip
holds. ``reference/longcat_flash_ref.py`` has the equations and the parameter
tree; ``init_params`` here makes that tree.

``config(model)`` takes the ``model`` group of a configuration file in the
names ``longcat_flash_ref`` reads and refuses what the reference refuses. The
group's ``rotary_float32``, ``linear_out_float32`` and ``stream_float32``
(absent: false) are ``GPTConfig``'s.

The reference routes, so ``paged_decode_step`` returns the outputs its step
chose third, int32 ``[slots, n_layer, k]`` (``benchmark/README.md``, the
``model family`` row): every layer routes, and an index at or past
``n_routed_experts`` names a zero-compute expert, a choice like any other.

``init_params`` rounds every matrix to bf16 as it is drawn, a piece no larger
than an expert at a time (``gpt._normal_in_pieces``): a float32 tree of 5.17 B
parameters is 20.7 GB, which no chip holds. The draw is this file's, not the
program's: ``gpt.init_params(..., std=)`` with ``init_std(d_model)`` = ``sqrt(2
/ (5 d_model))`` (0.00807 at 6144: "small init", GPT-NeoX's default; ``std /
sqrt(2 n_layer)`` for the projections into the stream), and the choice bias
scaled from the program's 0.02 to ``bias_std`` = 0.5 / the router's width, half
a mean probability of a softmax over that many. Not GPT-2's 0.02 of the other
families, and chosen AFTER 0.02 had failed the comparison on the chip (at this
width under the two rescales it spreads the attention scores by 2.5 before the
softmax, and eight sub-blocks put even a float32 stream 1.3-1.9% from the
reference). What stands for the choice, and what it costs the comparison, is
PERF.md section 6, PR 63: under this draw the scores spread by 0.40 (a query
reads 219 of 256 rows alike; ``deepseek-v2-serve``'s 0.71 under 0.02 reads
156), and ``tools/longcat_drift.py`` has the rows in the next precision below
(the pool, its rotated keys alone, the stream, cut to 8 bits) against the
harness's unedited limits.
"""

from __future__ import annotations

import jax.numpy as jnp

from deepspeed_tpu.models import gpt as _gpt
from deepspeed_tpu.models.gpt import init_cache  # noqa: F401

from ..reference import longcat_flash_ref


def config(model: dict):
    longcat_flash_ref._check(model)
    if "moe_shortcut" not in _gpt.GPTConfig.__dataclass_fields__:
        raise ValueError(     # a program from before PR 63
            "family longcat_flash needs a program whose layer can be two "
            "attention sub-blocks with a routed branch across them "
            "(models/gpt.py: GPTConfig.moe_shortcut, moe_zero_experts); "
            "this one's cannot")
    return _gpt.GPTConfig(
        vocab_size=model["vocab_size"], n_layer=model["n_layer"],
        n_head=model["n_head"], d_model=model["d_model"], d_ff=model["d_ff"],
        max_seq_len=model["max_seq_len"], rotary=True, tie_embeddings=False,
        activation="silu", layer_norm_eps=model["rms_norm_eps"],
        norm="rmsnorm", mlp_gated=True, linear_bias=False,
        rope_theta=float(model["rope_theta"]),
        rotary_float32=bool(model.get("rotary_float32")),
        linear_out_float32=bool(model.get("linear_out_float32")),
        stream_float32=bool(model.get("stream_float32")),
        attn_kind="mla", q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        mla_lora_rescale=True,
        moe_experts=model["n_routed_experts"],
        moe_zero_experts=model["zero_expert_num"],
        moe_held=tuple(model["held_experts"]), moe_k=model["k"],
        moe_d_ff=model["moe_d_ff"],
        moe_scale=float(model["routed_scaling_factor"]),
        moe_score_bias=True, moe_shortcut=True,
        # tools/compile_only.py says which attention to lower
        use_flash=model.get("use_flash"))


def module(cfg):
    return _gpt.build(cfg)[0]


def init_std(d_model: int) -> float:
    return (2.0 / (5 * d_model)) ** 0.5


def bias_std(outputs: int) -> float:
    return 0.5 / outputs


def init_params(cfg, key):
    params = _gpt.init_params(cfg, key, dtype=jnp.bfloat16,
                              std=init_std(cfg.d_model))
    layers = params["moe_blocks"]
    outputs = layers["router_bias"].shape[-1]
    layers["router_bias"] = layers["router_bias"] * (bias_std(outputs) / 0.02)
    return params


def paged_decode_step(cfg, params, tokens, cache, tables, lengths, impl=None):
    """(logits [slots, V], the pool, the outputs chosen [slots, n_layer, k])
    of the program's own step."""
    logits, cache, (chosen, _) = _gpt.paged_decode_step(
        cfg, params, tokens, cache, tables, lengths, impl=impl,
        return_routing=True)
    return logits, cache, chosen
