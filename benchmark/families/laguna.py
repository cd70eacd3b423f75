"""Family ``laguna``: Laguna-XS.2's layers on the program's normal path,
``deepspeed_tpu/models/gpt.py`` with fewer key-value heads than query heads,
kinds of attention in a period (a head count, a window and a rotary set a
kind), a gate a head and a router that renormalises what it took, said as
data; the page pool for the full layers and a ring a slot for the window
layers; ``paged_decode_gqa``; ``moe/dropless.py`` over all 256 experts.
``reference/laguna_ref.py`` has the equations and the parameter tree;
``init_params`` here makes that tree.

``config(model)`` takes the ``model`` group of a configuration file in the
names ``laguna_ref`` reads and refuses what the reference refuses, and a
layer list that is no period of kinds after leading dense layers. The
group's ``rotary_float32``, ``linear_out_float32`` and ``stream_float32``
(absent: false) are ``GPTConfig``'s.

The reference routes, so ``paged_decode_step`` returns the experts its step
chose third, int32 ``[slots, n_layer, k]`` (``benchmark/README.md``, the
``model family`` row): a dense layer's row is -1, as the reference takes it.

``init_params`` rounds every matrix to bf16 as it is drawn, a piece no larger
than an expert at a time (``gpt._normal_in_pieces``): a float32 tree of 3.87 B
parameters is 15.5 GB, which no chip holds beside anything. The values are
N(0, 0.02) (0.02 / sqrt(2 n_layer) for the projections into the stream)
rounded to bf16, what the reference upcasts either way; the norm gains stay
float32 ones.
"""

from __future__ import annotations

import jax.numpy as jnp

from deepspeed_tpu.models import gpt as _gpt
from deepspeed_tpu.models.gpt import init_cache  # noqa: F401

from ..reference import laguna_ref


def _kind(model: dict, name: str, heads: int) -> _gpt.AttnKind:
    rope = model["rope_parameters"][name]
    scaling = None
    if rope.get("rope_type", "default") == "yarn":
        scaling = _gpt.YarnScaling(
            factor=float(rope["factor"]),
            original_max_len=int(rope["original_max_position_embeddings"]),
            beta_fast=float(rope["beta_fast"]),
            beta_slow=float(rope["beta_slow"]))
        if abs(scaling.cos_sin_factor - rope["attention_factor"]) > 1e-9:
            raise ValueError(
                f"attention_factor {rope['attention_factor']} is not YaRN's "
                f"own 0.1 ln(factor) + 1 = {scaling.cos_sin_factor}")
    return _gpt.AttnKind(
        n_head=heads,
        window=(model["sliding_window"] if name == "sliding_attention"
                else 0),
        rotary_pct=float(rope.get("partial_rotary_factor", 1)),
        rope_theta=float(rope["rope_theta"]), rope_scaling=scaling)


def config(model: dict):
    laguna_ref._check(model)
    if not hasattr(_gpt, "AttnKind"):   # a program from before PR 38
        raise ValueError(
            "family laguna needs a program whose GPTConfig says key-value "
            "heads and kinds of attention layer (models/gpt.py: AttnKind, "
            "attn_period, n_kv_head); this one has neither")
    n = model["n_layer"]
    kinds = [_kind(model, name, heads) for name, heads in zip(
        model["layer_types"], model["num_attention_heads_per_layer"])]
    period = next(p for p in range(1, n + 1)
                  if all(kinds[l] == kinds[l % p] for l in range(n)))
    dense = model["mlp_layer_types"].count("dense")
    if model["mlp_layer_types"] != (["dense"] * dense
                                    + ["sparse"] * (n - dense)):
        raise ValueError("laguna: dense layers lead the routed ones, got "
                         f"{model['mlp_layer_types']}")
    return _gpt.GPTConfig(
        vocab_size=model["vocab_size"], n_layer=n,
        n_head=max(k.n_head for k in kinds), d_model=model["d_model"],
        d_ff=model["d_ff"], max_seq_len=model["max_seq_len"], rotary=True,
        tie_embeddings=False, activation="silu",
        layer_norm_eps=model["rms_norm_eps"], norm="rmsnorm",
        mlp_gated=True, linear_bias=False,
        rotary_float32=bool(model.get("rotary_float32")),
        linear_out_float32=bool(model.get("linear_out_float32")),
        stream_float32=bool(model.get("stream_float32")),
        attn_kind="gqa", n_kv_head=model["n_kv_head"],
        head_width=model["head_dim"], attn_gate=True,
        attn_period=tuple(kinds[:period]),
        moe_experts=model["n_routed_experts"], moe_k=model["k"],
        moe_d_ff=model["moe_d_ff"], moe_shared_d_ff=model["shared_d_ff"],
        moe_scale=float(model["routed_scaling_factor"]),
        moe_dense_layers=dense, moe_norm_topk=True,
        # tools/compile_only.py says which attention to lower
        use_flash=model.get("use_flash"))


def module(cfg):
    return _gpt.build(cfg)[0]


def init_params(cfg, key):
    return _gpt.init_params(cfg, key, dtype=jnp.bfloat16)


def paged_decode_step(cfg, params, tokens, cache, tables, lengths, impl=None):
    """(logits [slots, V], the cache, the experts chosen [slots, n_layer,
    k]) of the program's own step."""
    logits, cache, (chosen, _) = _gpt.paged_decode_step(
        cfg, params, tokens, cache, tables, lengths, impl=impl,
        return_routing=True)
    return logits, cache, chosen
