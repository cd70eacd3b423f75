"""Family ``ouro``: a looped language model of Ouro's shape on the program's
normal path, ``deepspeed_tpu/models/gpt.py`` with its block said as data
(RMSNorm, a SiLU-gated MLP, bias-free linears, a norm on each sublayer's
output, rotary over the whole head, the stack run ``total_ut_steps`` times
with the final norm closing every pass). ``reference/ouro_ref.py`` has the
equations and the parameter tree; ``init_params`` here makes that tree.

``config(model)`` takes the ``model`` group of a configuration file in the
names ``ouro_ref`` reads, refuses what the reference refuses, and sets the
boundaries the programs report (``state_layers``) where
``ouro_ref.segments(model)`` cuts.

The reference is segmented, so this family also hands over the served path's
own states and rows (``benchmark/README.md``, the ``model family`` row):
``prefill_states``, ``decode_states`` and ``gather_kv`` read what the engine's
own programs returned beside their tokens (``ServingEngine.prefill_states``,
``.decode_states``: outputs of the executions that filled the pages, left on
the device) and the engine's own pool. The decode program samples in-program
and returns no logits, so ``decode_states`` applies the program's own head
function once more to the last state that program returned, and holds the
program's sampled token to those logits: a program whose head computed
something else raises here instead of passing unseen.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import gpt as _gpt
from deepspeed_tpu.models.gpt import (init_cache, init_params,  # noqa: F401
                                      paged_decode_step)

from ..reference import ouro_ref

# how far under the largest logit the program's sampled token may lie, as a
# share of the largest: bf16 logits tie within two units of their last place
SAMPLED_TOKEN_TOL = 2.0 ** -6


def config(model: dict):
    ouro_ref._check(model)
    cuts = tuple(sorted({stop for _, _, stop in ouro_ref.segments(model)}))
    return _gpt.GPTConfig(
        vocab_size=model["vocab_size"], n_layer=model["n_layer"],
        n_head=model["n_head"], d_model=model["d_model"], d_ff=model["d_ff"],
        max_seq_len=model["max_seq_len"], rotary=True, rotary_pct=1.0,
        tie_embeddings=model["tie_embeddings"], activation="silu",
        layer_norm_eps=model["rms_norm_eps"], norm="rmsnorm", mlp_gated=True,
        linear_bias=False, post_norm=model["sandwich_norm"],
        rope_theta=float(model["rope_theta"]), rotary_float32=True,
        ut_steps=model["total_ut_steps"], loop_norm=model["loop_norm"],
        state_layers=cuts,
        early_exit_threshold=model["early_exit_threshold"],
        # tools/compile_only.py says which attention to lower
        use_flash=model.get("use_flash"))


def module(cfg):
    return _gpt.build(cfg)[0]


# ------------------------------------------------- the segmented adapter
def prefill_states(engine, slot: int, prompt, table):
    """The engine's own prefill of one prompt: the greedy next token and the
    residual stream of every prompt position at every boundary,
    [boundaries, T, d]. One array a dispatch: the serial chunks of a long
    prompt in order, padding after the last."""
    token = engine.prefill(slot, np.asarray(prompt, np.int32), table)
    states = jnp.concatenate([s[0] for s in engine.prefill_states], axis=1)
    return token, np.asarray(states[:, :len(prompt)].astype(jnp.float32))


@functools.lru_cache(maxsize=2)
def _head(cfg):
    return jax.jit(lambda params, x: _gpt._head(cfg, params,
                                                x[:, None])[:, 0])


def decode_states(engine, tokens, tables, lengths, active):
    """One step of the engine's own decode program over the slot array:
    logits [slots, V], the program's next tokens [slots] and each slot's
    token at every boundary [slots, boundaries, d]."""
    nxt = np.asarray(engine.decode(tokens, tables, lengths, active,
                                   steps=1))[0]
    states = engine.decode_states[0]
    logits = np.asarray(_head(engine.cfg)(engine.params, states[:, -1])
                        .astype(jnp.float32))
    rows = np.flatnonzero(active)
    top = logits[rows].max(-1)
    short = top - logits[rows, nxt[rows]]
    if (short > SAMPLED_TOKEN_TOL * np.abs(logits[rows]).max(-1)).any():
        raise RuntimeError(
            f"the decode program's sampled tokens {nxt[rows]} lie {short} "
            f"under the largest logit of its own last state ({top}): its "
            "head computed something else than models/gpt._head")
    return logits, nxt, np.asarray(states.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(2,))
def _rows(pool, pages, length):
    got = pool[:, :, pages]                   # [L, H, pages, ps, Dh]
    return got.reshape(got.shape[:2] + (-1, got.shape[-1]))[:, :, :length]


def gather_kv(engine, table, length: int):
    """The first ``length`` key and value rows of the pages ``table`` names,
    [cache layers, H, length, Dh] each, as the pool holds them."""
    ps = engine.serving.page_size
    pages = jnp.asarray(np.asarray(table)[:-(-length // ps)], jnp.int32)
    return tuple(_rows(engine.paged_cache[side], pages, length)
                 for side in ("k_pages", "v_pages"))
