"""Plain reference forward for the OLMoE block (Muennighoff et al. 2024,
"OLMoE: Open Mixture-of-Experts Language Models", section 2 and table 1;
``https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json``;
the public ``modeling_olmoe.py`` of ``transformers`` for what the config does
not say). For a residual stream ``x`` [T, d], every layer:

    h  = RMSNorm_1(x)                          x * rsqrt(mean(x^2) + eps) * g
    q  = RMSNorm_q(h W_q), k = RMSNorm_k(h W_k), v = h W_v      no bias; the two
         norms run over the whole d-wide row before the split into heads (in
         the modelling code, not in config.json: a configuration lists
         ``qk_norm`` under ``assumed``)
    q, k rotated over all of each head's dimensions, rotate-half form
         (dimension i pairs with i + dh/2), base ``rope_theta``
    x  = x + softmax(q k^T / sqrt(dh), causal) v W_o
    h  = RMSNorm_2(x)
    r  = h W_g                                 router logits, float32, [T, E]
    p  = softmax(r)                            over all E experts
    S  = the k experts with the largest r      (an unordered set a token)
    y  = sum over e in S of p_e W_down,e (silu(W_gate,e h) * W_up,e h)
         the k probabilities are NOT renormalised (``norm_topk_prob`` false)
    x  = x + y

then ``RMSNorm_f(x) W_head^T`` with an untied head. Float32 under
``jax.default_matmul_precision("highest")``, one sequence at a time, every
expert computed for every token and weighted by its gate, which is 0 outside
``S``: no sort, no gather, no grouped matmul, no cache, and no function of the
program (``gpt_ref.rotary`` is this directory's). Every token reaches its
experts: there is no capacity. It refuses what it does not cover (``_check``).

**A choice can be handed over.** Top-k of E is a discrete choice. Where the
k-th and (k+1)-th router logits of a token lie closer than the rounding of the
bf16 activations that feed the served router, the served path and this one
pick different experts, each correctly for its own input, and their logits
then differ by an expert's whole contribution (``lib/correct.py`` says what
that does to a dense tolerance). So ``logits(..., choices={position: [n_layer,
k]})`` computes those positions with the experts named in place of ``S``; the
gates stay this forward's own float32 ``p_e`` of those experts, and every other
token routes as above. The same call judges what was handed over and returns
it beside the logits, the slack: for each handed position and layer, how far
the weakest handed expert's router logit lies under the strongest expert that
was left out, in units of the standard deviation of that token's E router
logits (0 where the sets agree; computed along the forward that uses the
choices). It asks which expert was left out, not only which was taken: where
the k-th and (k+1)-th are swapped it is their distance, and a set that omits
its strongest expert and takes ranks 2 to k+1 reads the distance from the 1st
to the (k+1)-th, about 2. The unit is the logits' own spread because the
noise that moves a choice scales with it: bf16 rounding of ``h`` moves ``r`` in
proportion to ``|W_g|``, as the spread is, so the number does not depend on
how the router was initialised.

``CHOICE_SLACK`` is the most a defensible choice may show. Measured by
``tools/routing_flips.py`` on a TPU v5e (my chip runs, PR 27; PERF.md section 6
has the table): published widths, 8 layers, weights N(0, 0.02) rounded to
bf16, the router logits' spread 0.89; 42 seeds x prompts of 128, 256, 384,
512 and 9 further tokens, a bf16 stand-in for a served path with its router
in float32. 29.8% of 55,272 positions picked another set than this reference
in some layer (5.0% of the choices a layer). The largest slack of any choice
the stand-in made: 0.033 with every position handed over; with one position
in 32 handed over (7,056 readings) 0.026 from position 64 on and 0.050 under
it (position 24, two layers flipped); 0.019 at the two positions
``lib/correct.py`` compares (336 comparisons). 0.12 is 2.4 times the largest
of all, 3.7 times the largest with every position handed over and 4.6 times
the largest from position 64 on, where a routed cell's check prompts end
(``lib/correct.py``). Ranks 2 to 9 handed in place of 1 to 8 in one layer read
0.38 to 2.6 (median 1.26). An expert drawn at random in place of one of the
eight reads 1.8 at the median and under 0.12 in 0.4% of 2,688 draws (the 9th
ranked for the 8th is a flip in all but name). A router computed in bf16 reads
the same slack as one in float32 (0.019 at most): the rounding of its inputs,
which both have, is what moves a choice, and the comparison does not tell the
two apart.

It reads the parameter tree below; a family's ``init_params`` makes it.

- ``wte`` [V, d], ``lm_head`` [V, d], ``lnf_scale`` [d];
- ``blocks``: leaves stacked over layers, ``blocks[name][l]`` is layer ``l``:
  ``ln1_scale``, ``ln2_scale``, ``q_norm_scale``, ``k_norm_scale`` [d];
  ``qkv_w`` [d, 3d], columns q | k | v, each split into heads of
  ``d / n_head`` columns; ``attn_out_w`` [d, d]; ``moe.gate_w`` [d, E] (the
  router); ``moe.experts.gate_proj_w``, ``.up_w`` [E, d, f], ``.down_w``
  [E, f, d].

``model`` is the ``model`` group of a configuration file: ``vocab_size``,
``n_layer``, ``n_head``, ``d_model``, ``d_ff`` (one expert's width),
``num_experts``, ``k``, ``rope_theta``, ``rms_norm_eps``, and the three of
``COVERS``. The weights arrive in the served type and are upcast one expert
and one block at a time.

Its counts (``lib/context.Context.count`` prefers them to ``lib/flops``'s):
``kv_bytes_per_token``, ``decode_step_bytes`` and, for the roofline of an
expert kernel, ``expert_ffn_cost``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib.kernel_cost import Cost
from . import gpt_ref as G

CHOICE_SLACK = 0.12

COVERS = {"norm_topk_prob": False, "qk_norm": True, "tie_embeddings": False}
NOT_COVERED = ("attention_bias", "clip_qkv", "rope_scaling", "shared_experts")


def _check(model: dict) -> None:
    have = {key: model.get(key) for key in COVERS}
    have.update({key: model[key] for key in NOT_COVERED if model.get(key)})
    if model.get("n_kv_head", model["n_head"]) != model["n_head"]:
        have["n_kv_head"] = model["n_kv_head"]
    if have != COVERS:
        raise ValueError(f"olmoe_ref covers {COVERS}, as many key and value "
                         f"heads as query heads and none of {NOT_COVERED}; "
                         f"the configuration says {have}")


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * G._f32(gain)


def attention(model: dict, h, w):
    """Causal multi-head self-attention of the normalised input ``h`` [T, d]."""
    t, d = h.shape
    n_head, eps = model["n_head"], model["rms_norm_eps"]
    dh = d // n_head
    qkv = h @ G._f32(w["qkv_w"])
    q = rms_norm(qkv[:, :d], w["q_norm_scale"], eps)
    k = rms_norm(qkv[:, d:2 * d], w["k_norm_scale"], eps)
    q, k, v = (a.reshape(t, n_head, dh) for a in (q, k, qkv[:, 2 * d:]))
    q = G.rotary(q, dh, float(model["rope_theta"]))
    k = G.rotary(k, dh, float(model["rope_theta"]))
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(t, d)
    return out @ G._f32(w["attn_out_w"])


def route(model: dict, h, w, handed, use):
    """Gates [T, E] (``p_e`` on each token's experts, 0 elsewhere), this
    forward's own k largest [T, k], and the slack [T] of ``handed`` [T, k],
    which takes the place of the own set in the rows where ``use`` [T] says
    so: the strongest router logit left out less the weakest taken."""
    r = h @ G._f32(w["gate_w"])                                    # [T, E]
    _, own = jax.lax.top_k(r, model["k"])
    rows = jnp.arange(r.shape[0])[:, None]
    chosen = jnp.where(use[:, None], handed, own)
    member = jnp.zeros(r.shape, bool).at[rows, chosen].set(True)
    weakest_in = jnp.min(jnp.where(member, r, jnp.inf), axis=1)
    strongest_out = jnp.max(jnp.where(member, -jnp.inf, r), axis=1)
    slack = jnp.maximum(strongest_out - weakest_in, 0.0) / jnp.std(r, axis=1)
    return jnp.where(member, jax.nn.softmax(r, axis=-1), 0.0), own, slack


def experts(h, w, gates):
    """``sum_e gates[:, e] * FFN_e(h)``, an expert at a time."""
    def one(y, e):
        gate_proj, up, down, g = e
        out = (jax.nn.silu(h @ G._f32(gate_proj)) * (h @ G._f32(up))) \
            @ G._f32(down)
        return y + g[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w["gate_proj_w"], w["up_w"], w["down_w"], gates.T))
    return y


def block(model: dict, x, w, handed, use):
    eps = model["rms_norm_eps"]
    x = x + attention(model, rms_norm(x, w["ln1_scale"], eps), w)
    h = rms_norm(x, w["ln2_scale"], eps)
    gates, own, slack = route(model, h, w["moe"], handed, use)
    return x + experts(h, w["moe"]["experts"], gates), own, slack


@functools.partial(jax.jit, static_argnums=(0,))
def _block_at(model_items, x, blocks, layer, handed, use):
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        blocks)
    return block(dict(model_items), x, w, handed, use)


@functools.partial(jax.jit, static_argnums=(0,))
def _head(model_items, params, x):
    x = rms_norm(x, params["lnf_scale"], dict(model_items)["rms_norm_eps"])
    return x @ G._f32(params["lm_head"]).T


def _handed(model: dict, t: int, choices):
    """``choices`` ({position: [n_layer, k] experts} or None) as the arrays
    the layers take: experts [n_layer, T, k] and which rows use them [T]."""
    n_layer, k = model["n_layer"], model["k"]
    handed = np.zeros((n_layer, t, k), np.int32)
    use = np.zeros(t, bool)
    for pos, sets in (choices or {}).items():
        sets = np.asarray(sets)
        if not 0 <= pos < t or sets.shape != (n_layer, k):
            raise ValueError(f"choices at position {pos} of {t}: shape "
                             f"{sets.shape}, wanted {(n_layer, k)}")
        distinct = all(len(set(row)) == k for row in sets.tolist())
        if not distinct or sets.min() < 0 or sets.max() >= model["num_experts"]:
            raise ValueError(f"choices at position {pos}: every layer names "
                             f"{k} different experts of "
                             f"{model['num_experts']}, got {sets.tolist()}")
        handed[:, pos], use[pos] = sets, True
    return handed, use


def forward(model: dict, params, ids, choices=None):
    """One sequence ``ids`` [T] through the blocks: the residual stream [T, d]
    after the last, this forward's own k largest [T, n_layer, k] and the slack
    of ``choices`` [T, n_layer] (0 in rows that were handed nothing)."""
    _check(model)
    items = G._frozen(model)
    ids = jnp.asarray(ids, jnp.int32)
    handed, use = _handed(model, ids.shape[0], choices)
    own, slack = [], []
    with jax.default_matmul_precision("highest"):
        x = G._f32(params["wte"])[ids]
        for layer in range(model["n_layer"]):
            x, o, s = _block_at(items, x, params["blocks"], jnp.int32(layer),
                                handed[layer], use)
            own.append(o)
            slack.append(s)
    return x, jnp.stack(own, axis=1), jnp.stack(slack, axis=1)


def head_logits(model: dict, params, x, positions=None):
    """Final norm and head over the rows ``positions`` of the residual stream
    ``x`` [T, d]; all rows if None."""
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    with jax.default_matmul_precision("highest"):
        return _head(G._frozen(model), params, x)


def logits(model: dict, params, ids, positions=None, choices=None):
    """Logits [len(positions), V] of one sequence; all positions if None.
    ``choices`` maps a position to the experts [n_layer, k] to use there, and
    the one forward that uses them then also judges them: the value is
    (logits, {position: slack [n_layer]}). A position's slack is the largest,
    held to ``CHOICE_SLACK``, and the layers above 0 are those where the
    handed set is not this forward's own."""
    x, _, slack = forward(model, params, ids, choices)
    out = head_logits(model, params, x, positions)
    if choices is None:
        return out
    slack = np.asarray(slack)
    return out, {pos: slack[pos] for pos in choices}


# ------------------------------------------------------------------ counts
def expert_params(model: dict) -> int:
    """Weights of one expert's three matrices."""
    return 3 * model["d_model"] * model["d_ff"]


def shared_params(model: dict) -> int:
    """Weights every token of a step multiplies with whatever it chose: each
    layer's attention and router, and the head."""
    d = model["d_model"]
    return (model["n_layer"] * (4 * d * d + d * model["num_experts"])
            + model["vocab_size"] * d)


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """Keys and values of one cached token over all layers (as many key and
    value heads as query heads)."""
    return 2 * model["n_layer"] * model["d_model"] * kv_dtype_bytes


def decode_step_bytes(model: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int = 2,
                      kv_dtype_bytes: int = 2) -> float:
    """What one decode step over the slot array has to read from HBM: every
    attention, router and head weight once, every expert bank once, and the
    live keys and values. Every expert, because a step over a few dozen
    active tokens at ``k`` of ``num_experts`` each touches them all: an expert
    is spared by one token with 1 - k/E (0.875 for 8 of 64) and by n tokens
    with that to the n-th, 0.0002 at 64. Norm gains, activations and the
    tokens' embedding rows are thousands of times smaller and left out."""
    banks = model["n_layer"] * model["num_experts"] * expert_params(model)
    return ((shared_params(model) + banks) * weight_dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(model, kv_dtype_bytes))


def expert_ffn_cost(model: dict, rows: float, experts_touched: float,
                    itemsize: int = 2) -> Cost:
    """One layer's expert sublayer over ``rows`` (token, expert) pairs, ``k``
    a token, that fall on ``experts_touched`` of the experts: three matrix
    products a row; each touched expert's three matrices read once, a row's
    input read and its output written."""
    d = model["d_model"]
    return Cost(2.0 * rows * expert_params(model),
                float(experts_touched * expert_params(model) * itemsize
                      + 2 * rows * d * itemsize))
