"""Family ``dots3_note``: the language model of ``dots3-note-prev`` on the
program's normal path, ``deepspeed_tpu/models/gpt.py`` with latent attention
of two kinds in a period (``AttnKind``: each its own heads, latent ranks, head
widths, rotary base; the window kind a latent ring a slot, the full kind
pages under a learned selection with an indexer's keys in pages beside them),
a gate a head, the two rescales of the normed latents and a sigmoid router
with a choice bias, said as data; ``paged_decode_mla`` for both kinds;
``moe/dropless.py`` over the experts this chip holds.
``reference/dots3_note_ref.py`` has the equations and the parameter tree;
``init_params`` here makes that tree.

``config(model)`` takes the ``model`` group of a configuration file in the
names ``dots3_note_ref`` reads and refuses what the reference refuses, and a
layer list that is no period of kinds after leading dense layers. The
group's ``rotary_float32``, ``linear_out_float32``, ``stream_float32``,
``index_float32`` and ``experts_two_pass`` (absent: false) are
``GPTConfig``'s.

The reference routes AND selects, so ``paged_decode_step`` returns third,
int32 ``[slots, n_layer, k + index_topk]``, what its step chose for each
slot's token: a layer's experts in the first ``k`` columns (-1 in a dense
layer) and, in a full layer, the positions its selection kept in the others
(-1 after them, and throughout in a window layer), which the program's step
leaves in the cache it returns (``selected``, ``gpt.init_paged_cache``).
``lib/correct.py`` passes the rows through untouched, as it passes a routed
family's ``[n_layer, k]`` (``benchmark/README.md``, the ``model family`` row);
``reference/dots3_note_ref.py``, "What is handed over", says what the wider
row means.

``init_params`` rounds every matrix to bf16 as it is drawn, a piece no larger
than an expert at a time (``gpt._normal_in_pieces``): a float32 tree of 4.09 B
parameters is 16.3 GB, which no chip holds. The values are N(0, 0.02) (0.02 /
sqrt(2 n_layer) for the projections into the stream) rounded to bf16, what the
reference upcasts either way; the choice bias N(0, 0.02); the norm gains stay
float32 ones.
"""

from __future__ import annotations

import jax.numpy as jnp

from deepspeed_tpu.models import gpt as _gpt
from deepspeed_tpu.models.gpt import init_cache  # noqa: F401

from ..reference import dots3_note_ref


def _kind(model: dict, name: str) -> "_gpt.AttnKind":
    g = model[dots3_note_ref.LAYER_KINDS[name]]
    full = name == "full_attention"
    return _gpt.AttnKind(
        n_head=g["n_head"], window=0 if full else model["sliding_window"],
        rope_theta=float(g["rope_theta"]), q_lora_rank=g["q_lora_rank"],
        kv_lora_rank=g["kv_lora_rank"], qk_nope_dim=g["qk_nope_head_dim"],
        qk_rope_dim=g["qk_rope_head_dim"], v_head_dim=g["v_head_dim"],
        index_heads=model["index_n_heads"] if full else 0,
        index_dim=model["index_head_dim"] if full else 0,
        index_topk=model["index_topk"] if full else 0)


def config(model: dict):
    dots3_note_ref._check(model)
    if "index_topk" not in getattr(_gpt.AttnKind, "__dataclass_fields__", {}):
        raise ValueError(     # a program from before PR 51
            "family dots3_note needs a program whose kinds of attention "
            "layer say a latent geometry and an indexer (models/gpt.py: "
            "AttnKind.kv_lora_rank, AttnKind.index_topk); this one's do not")
    n = model["n_layer"]
    kinds = [_kind(model, name) for name in model["layer_types"]]
    period = next(p for p in range(1, n + 1)
                  if all(kinds[l] == kinds[l % p] for l in range(n)))
    full = model["full"]
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
        attn_kind="mla", q_lora_rank=full["q_lora_rank"],
        kv_lora_rank=full["kv_lora_rank"],
        qk_nope_dim=full["qk_nope_head_dim"],
        qk_rope_dim=full["qk_rope_head_dim"], v_head_dim=full["v_head_dim"],
        attn_gate=True, mla_lora_rescale=True,
        index_float32=bool(model.get("index_float32")),
        attn_period=tuple(kinds[:period]),
        moe_experts=model["n_routed_experts"],
        moe_held=tuple(model["held_experts"]), moe_k=model["k"],
        moe_d_ff=model["moe_d_ff"],
        moe_shared_d_ff=model["n_shared_experts"] * model["moe_d_ff"],
        moe_scale=float(model["routed_scaling_factor"]),
        moe_dense_layers=model["n_dense_layers"], moe_norm_topk=True,
        moe_score="sigmoid", moe_score_bias=True,
        moe_two_pass=bool(model.get("experts_two_pass")),
        # tools/compile_only.py says which attention to lower
        use_flash=model.get("use_flash"))


def module(cfg):
    return _gpt.build(cfg)[0]


def init_params(cfg, key):
    return _gpt.init_params(cfg, key, dtype=jnp.bfloat16)


def chosen_and_selected(cfg, chosen, cache):
    """The experts ``chosen`` [slots, n_layer, k] with, beside them, the
    positions each full layer's selection kept, from the cache the step
    returned: [slots, n_layer, k + index_topk]."""
    selected = cache[_gpt.INDEX_KEYS[1]]            # [full layers, slots, k']
    rows = jnp.full((chosen.shape[0], cfg.n_layer, selected.shape[2]), -1,
                    jnp.int32)
    at = 0
    for run in _gpt.layer_runs(cfg):
        if _gpt.kind_view(cfg, run.kind).index_topk:
            layers = jnp.arange(run.first, run.first + run.count)
            rows = rows.at[:, layers].set(
                selected[at:at + run.count].transpose(1, 0, 2))
            at += run.count
    return jnp.concatenate([chosen, rows], axis=-1)


def paged_decode_step(cfg, params, tokens, cache, tables, lengths, impl=None):
    """(logits [slots, V], the cache, what the step chose [slots, n_layer,
    k + index_topk]) of the program's own step."""
    logits, cache, (chosen, _) = _gpt.paged_decode_step(
        cfg, params, tokens, cache, tables, lengths, impl=impl,
        return_routing=True)
    return logits, cache, chosen_and_selected(cfg, chosen, cache)
