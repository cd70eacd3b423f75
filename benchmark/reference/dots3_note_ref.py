"""Plain reference forward for the language model of ``dots3-note-prev``
(``https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json``,
``model_type`` ``dots3_note``; row 19 of the catalog beside the
``model-configs`` guide, whose ``described_as`` says what the config's keys
do not: "MLA + DSA indexer (full layers); SWA(513) with its own low-rank
latent attention + headwise gate"). Text ids only: the vision tower, the audio
encoder and the multi-token-prediction module are not in the catalog's
``config``. For a residual stream ``x`` [T, d], every layer (RMSNorm eps
``rms_norm_eps``, no bias anywhere but the indexer's LayerNorm):

    x = x + Attn_kind(RMSNorm(x));  x = x + FFN(RMSNorm(x))

then ``RMSNorm_f(x) W_head^T``, head untied. ``layer_types`` says each
layer's kind.

**Full layer** (latent attention under a learned selection; the group
``full``: ``H`` heads, ``q_lora_rank``, ``kv_lora_rank``, nope / rope / value
widths, ``rope_theta``):
    c_q = s_q RMSNorm(h W_qa);  q = c_q W_qb       a head [q_nope | q_rope]
    [c | k_r] = h W_kva;  c = s_kv RMSNorm(c);  k_r rotated, one for all heads
    [k_nope | v] a head = c W_kvb
    indexer: qI = c_q W_qI (``index_n_heads`` heads of ``index_head_dim``),
      kI = LayerNorm(h W_kI) (one for all heads), the first ``rope``
      dimensions of both rotated as k_r is, w = (h W_w) / sqrt(heads x dim),
      I(t, s) = sum_j w_tj relu(qI_tj . kI_s) for s <= t;
      S_t = the ``index_topk`` positions of largest I(t, .), ties to the lower
      position (all of s <= t while t < index_topk)
    scores = (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope), softmax
      over S_t only, out = sum p v
    a head's out times sigmoid(h W_g)_head, then concat(out) W_o
**Window layer** (the group ``sliding``: its own heads, ranks, widths and
``rope_theta``): the same without an indexer; position ``t`` sees ``t -
sliding_window + 1 .. t`` (``sliding_window`` 513 counts the query's own
position).
Rotary: rotate-half over the ``rope`` rotated dimensions (dimension i pairs
with i + rope / 2), frequencies ``theta^(-i / (rope / 2))``, no scaling
(``rope_scaling`` null).

**Routed feed-forward** (layers from ``n_dense_layers`` on; before them a
SiLU-gated MLP of ``d_ff``): scores ``s = sigmoid(h W_r)`` over all
``n_routed_experts``; the ``k`` largest of ``s + bias`` are the token's
experts (``noaux_tc`` with one group: ties to the lower index); gates ``s_e /
sum over the set`` times ``routed_scaling_factor``; SiLU-gated experts of
``moe_d_ff``; one shared expert of ``n_shared_experts x moe_d_ff`` on every
token. ``held_experts`` = [first, count]: the experts whose weights the tree
holds; every held expert is computed for every token and weighted by its
gate, 0 outside the set; what the absent experts would add is left out
(``deepseek_v2_ref`` says why).

**Assumed**, each where the config has a switch and no formula (the
configuration file lists them):
 1. ``apply_mla_qkv_lora_rescale``: ``s_q = sqrt(d / q_lora_rank)``, ``s_kv =
    sqrt(d / kv_lora_rank)`` on the normed latents, the form LongCat-Flash
    publishes as ``mla_scale_q_lora`` / ``mla_scale_kv_lora``; the window
    kind with its own ranks.
 2. ``attention_gate_type`` headwise: a sigmoid a head from the sublayer's
    normed input, on the head's output before ``W_o`` (as ``laguna_ref``).
 3. the indexer as DeepSeek-V3.2-Exp's published inference code has it (qI
    from the query latent, the weights from the normed input, a LayerNorm on
    kI, rotation of the leading ``rope`` dimensions), in the served type and
    without its Hadamard rotation (an orthogonal rotation of qI and kI
    changes no score) and without its 8-bit keys; the LayerNorm's eps is
    ``rms_norm_eps``.

Float32 under ``jax.default_matmul_precision("highest")``, one sequence at a
time, ``QUERY_BLOCK`` queries and one head at a time so that 16k positions fit
beside a served engine: no cache, no absorbed product, no sort but the
selection's own, no function of the program.

**What is handed over** (``benchmark/README.md``, the ``reference`` row).
``logits(..., choices={position: [n_layer, k + index_topk]})`` computes those
positions with, in a routed layer, the experts named in the row's first ``k``
columns (-1 in a dense layer) in place of its own, and, in a full layer, the
positions named in the row's last ``index_topk`` columns (-1 after the
selected ones, and throughout in a window layer) in place of its own ``S_t``.
A row of ``k`` columns hands experts only. It returns the slack a layer:
the larger of the experts' (how far the weakest expert taken lies under the
strongest left out, in the unit of ``s + bias``, as ``nemotron_h_ref``) and
the selection's: how far the weakest position taken lies under the strongest
left out by this forward's own float32 ``I(t, .)``, over the standard
deviation of ``I(t, .)`` on the positions ``t`` sees, times ``CHOICE_SLACK /
SELECT_SLACK`` (so that one limit holds both; infinite where the row names
another count than ``min(t + 1, index_topk)``). A top-2048 of 16k scores is
as discrete as a top-8 of 256: the served path scores bf16 keys from a bf16
query, and where the 2048th and 2049th lie closer than that rounding it keeps
another row, each side rightly by its own numbers.

``CHOICE_SLACK`` and ``SELECT_SLACK``, from ``tools/dots3_drift.py`` on a TPU
v5e with this family's own programs at the published widths (5 layers, experts
0-31 of 256 held, bf16 weights, pages and rings, float32 stream and index
keys; my chip runs, PR 51; PERF.md section 6 has every row). The honest path
over seeds 1-7, prompts of 4096, 8192 and 16384, every decoded position handed
over (189 position readings): the experts' slack at most 0.0091 of ``s +
bias``, the selection's at most 0.185 of the index scores' spread (a heavy
tail: 0.04-0.07 is usual, 0.115-0.185 in 6 of 21 prompts), 3-9 selected rows
of 2,048 not this forward's own (at most 45). Faults: a selection from the
other full layer's keys 7.6-7.9 (and no logit over its tolerance: the slack
alone catches it), an unrotated index key 5.7-7.0, no gate 9.8-11.1, a
rescale left out 14.5-15.5, a selection one short infinite; an indexer or a
stream in bf16 0.46-1.00 and 1.04-1.73, which the logits catch too
(0.020-0.037 for the tolerance of 0.0125). So ``SELECT_SLACK`` 0.6: 3.2 times
the largest honest reading, a twelfth of the least fault that nothing else
catches. ``CHOICE_SLACK`` 0.03: 3.3 times the experts' largest honest reading
(``nemotron_h_ref``'s 0.01 was passed by 0.0091 and, with ``SELECT_SLACK`` at
0.15, missed by a selection at 0.19 in 2 runs of 6: the limits were set again
from the readings above, not the traffic narrowed). **What no limit catches at
these widths: the window one row short** (512 for 513 reads 0.0084-0.0111 on
the first number for the honest 0.0075-0.0103: one row of 513 under nearly
flat attention); ``tests/test_dots3_note.py`` holds it at float32's noise.

It reads the parameter tree below; the family's ``init_params`` makes it.
``place(model, layer)`` says where a layer lies.

- ``wte`` [V, d], ``lm_head`` [V, d], ``lnf_scale`` [d];
- a stack a kind of layer, leaves stacked over its layers:
  ``blocks_full`` / ``blocks_window`` (dense feed-forward: ``mlp_gate_w``,
  ``mlp_up_w`` [d, d_ff], ``mlp_down_w``) and ``moe_blocks_full`` /
  ``moe_blocks_window`` (``router_w`` [d, E], ``router_bias`` [E],
  ``experts_*`` [count, d, f] / [count, f, d], ``shared_*``). All:
  ``ln1_scale``, ``ln2_scale``; ``q_a_w`` [d, q_lora], ``q_a_norm_scale``;
  ``q_b_w`` [q_lora, H (nope + rope)]; ``kv_a_w`` [d, kv_lora + rope],
  ``kv_a_norm_scale``; ``kv_b_w`` [kv_lora, H (nope + v)]; ``attn_out_w``
  [H v, d]; ``attn_gate_w`` [d, H]. Full: ``index_q_w`` [q_lora, Hi Di],
  ``index_k_w`` [d, Di], ``index_k_norm_scale``, ``index_k_norm_bias`` [Di],
  ``index_w_w`` [d, Hi].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHOICE_SLACK = 0.03
# the most a defensible selection may show, in standard deviations of the
# token's index scores (the module docstring has the readings)
SELECT_SLACK = 0.6
# queries a block of the attention and of the indexer takes at once
QUERY_BLOCK = 1024


KIND_KEYS = ("n_head", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "rope_theta")
KEYS = ("vocab_size", "n_layer", "n_dense_layers", "d_model", "d_ff",
        "layer_types", "full", "sliding", "sliding_window", "index_n_heads",
        "index_head_dim", "index_topk", "rms_norm_eps", "n_routed_experts",
        "held_experts", "k", "moe_d_ff", "n_shared_experts",
        "routed_scaling_factor")
COVERS = {"norm_topk_prob": True, "tie_embeddings": False,
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "attention_gate_type": "headwise",
          "apply_mla_qkv_lora_rescale": True, "rope_scaling": None}
LAYER_KINDS = {"full_attention": "full", "sliding_attention": "sliding"}


def _check(model: dict) -> None:
    missing = [key for key in KEYS if key not in model]
    have = {key: model.get(key) for key in COVERS}
    if missing or have != COVERS:
        raise ValueError(f"dots3_note_ref reads {KEYS} and covers {COVERS}; "
                         f"the configuration lacks {missing} and says {have}")
    for group in ("full", "sliding"):
        lacks = [key for key in KIND_KEYS if key not in model[group]]
        if lacks:
            raise ValueError(f"dots3_note_ref: the group {group!r} lacks "
                             f"{lacks}")
    if (len(model["layer_types"]) != model["n_layer"]
            or set(model["layer_types"]) - set(LAYER_KINDS)):
        raise ValueError(f"layer_types {model['layer_types']}: one of "
                         f"{tuple(LAYER_KINDS)} for each of "
                         f"{model['n_layer']} layers")
    first, count = model["held_experts"]
    if not (0 <= first and count >= 1
            and first + count <= model["n_routed_experts"]):
        raise ValueError(f"held_experts {model['held_experts']} of "
                         f"{model['n_routed_experts']}")


def _frozen(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _frozen(x)) for k, x in v.items()))
    return tuple(v) if isinstance(v, list) else v


def _thawed(items) -> dict:
    model = dict(items)
    for group in ("full", "sliding"):
        model[group] = dict(model[group])
    return model


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(gain)


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(gain) + _f32(bias)


def kind_of(model: dict, layer: int) -> str:
    return LAYER_KINDS[model["layer_types"][layer]]


def place(model: dict, layer: int):
    """(stack, index in it) of layer ``layer``: a stack a kind of layer,
    ``blocks`` the dense and ``moe_blocks`` the routed ones, ``_full`` and
    ``_window`` by the attention's kind."""
    def stack(l):
        return (("blocks" if l < model["n_dense_layers"] else "moe_blocks")
                + ("_full" if kind_of(model, l) == "full" else "_window"))
    return stack(layer), sum(stack(l) == stack(layer) for l in range(layer))


def rotary(x, theta: float):
    """``x`` [T, heads, rope] rotated by position, rotate-half pairing."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# --------------------------------------------------------------- attention
def selection_slack(scores, seen, member, topk: int):
    """The slack of the sets ``member`` [P, T] among the positions ``seen``
    [P, T] under this forward's index scores ``scores`` [P, T], in their
    standard deviation over the seen positions; infinite where a set is not
    ``min(seen, topk)`` seen positions."""
    n_seen = seen.sum(axis=1)
    weakest = jnp.min(jnp.where(member, scores, jnp.inf), axis=1)
    strongest = jnp.max(jnp.where(seen & ~member, scores, -jnp.inf), axis=1)
    mean = jnp.sum(jnp.where(seen, scores, 0.0), axis=1) / n_seen
    spread = jnp.sqrt(jnp.sum(jnp.where(
        seen, (scores - mean[:, None]) ** 2, 0.0), axis=1) / n_seen)
    worst = jnp.maximum(strongest - weakest, 0.0)
    slack = jnp.where(worst > 0, worst / spread, 0.0)
    fits = ((member & ~seen).sum(axis=1) == 0) & (
        member.sum(axis=1) == jnp.minimum(n_seen, topk))
    return jnp.where(fits, slack, jnp.inf)


def attention(model: dict, kind: str, h, w, at, named):
    """The attention of the normalised input ``h`` [T, d] for a layer of
    ``kind``. ``at`` [P] are the positions that are handed a selection and
    ``named`` [P, index_topk] the positions it names (-1 after them; a row
    of -1 throughout hands nothing and the position selects for itself).
    Returns the sublayer's output [T, d], this forward's own selections at
    ``at`` [P, index_topk] (ascending, -1 after them) and the slack [P] of
    what was handed (both only of a full layer: else -1 and 0)."""
    g = model[kind]
    t, eps, d = h.shape[0], model["rms_norm_eps"], model["d_model"]
    heads, rank = g["n_head"], g["kv_lora_rank"]
    nope, rope, vd = g["qk_nope_head_dim"], g["qk_rope_head_dim"], \
        g["v_head_dim"]
    theta = float(g["rope_theta"])
    full = kind == "full"
    topk = model["index_topk"]
    c_q = rms_norm(h @ _f32(w["q_a_w"]), w["q_a_norm_scale"], eps) \
        * math.sqrt(d / g["q_lora_rank"])
    kv_a = h @ _f32(w["kv_a_w"])
    c = rms_norm(kv_a[:, :rank], w["kv_a_norm_scale"], eps) \
        * math.sqrt(d / rank)
    k_rope = rotary(kv_a[:, None, rank:], theta)[:, 0]          # [T, rope]
    gate = jax.nn.sigmoid(h @ _f32(w["attn_gate_w"]))           # [T, H]
    scale = (nope + rope) ** -0.5
    if full:
        hi, di = model["index_n_heads"], model["index_head_dim"]
        q_i = (c_q @ _f32(w["index_q_w"])).reshape(t, hi, di)
        q_i = jnp.concatenate([rotary(q_i[..., :rope], theta),
                               q_i[..., rope:]], -1)
        k_i = layer_norm(h @ _f32(w["index_k_w"]), w["index_k_norm_scale"],
                         w["index_k_norm_bias"], eps)
        k_i = jnp.concatenate([rotary(k_i[:, None, :rope], theta)[:, 0],
                               k_i[:, rope:]], -1)              # [T, Di]
        w_i = (h @ _f32(w["index_w_w"])) * (hi * di) ** -0.5    # [T, Hi]
    block = min(QUERY_BLOCK, t)
    n_blocks = -(-t // block)
    padded = n_blocks * block
    key_pos = jnp.arange(t)
    q_b = _f32(w["q_b_w"]).reshape(-1, heads, nope + rope)
    kv_b = _f32(w["kv_b_w"]).reshape(rank, heads, nope + vd)

    def rows_of(a):         # [T, ...] -> [blocks, block, ...]
        a = jnp.pad(a, ((0, padded - t),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((n_blocks, block) + a.shape[1:])

    def one_block(xs):
        first, cq_b, gate_b = xs[:3]
        q_pos = first + jnp.arange(block)
        seen = key_pos[None, :] <= q_pos[:, None]               # [block, T]
        own = jnp.full((at.shape[0], topk), -1, jnp.int32)
        slack = jnp.zeros((at.shape[0],), jnp.float32)
        if not full:
            seen = seen & (key_pos[None, :]
                           > q_pos[:, None] - model["sliding_window"])
        else:
            qi_b, wi_b = xs[3:]

            def index_head(acc, j):
                qj = jax.lax.dynamic_index_in_dim(qi_b, j, 1, False)
                wj = jax.lax.dynamic_index_in_dim(wi_b, j, 1, False)
                return acc + wj[:, None] * jax.nn.relu(qj @ k_i.T), None

            scores, _ = jax.lax.scan(
                index_head, jnp.zeros((block, t), jnp.float32),
                jnp.arange(hi))
            masked = jnp.where(seen, scores, -jnp.inf)
            if t > topk:
                order = jnp.argsort(-masked, axis=1, stable=True)[:, :topk]
                chosen = jnp.zeros((block, t), bool).at[
                    jnp.arange(block)[:, None], order].set(True) & seen
            else:
                chosen = seen
            # the handed positions' rows: their own sets out, the named in
            inside = (at >= first) & (at < first + block)
            local = jnp.clip(at - first, 0, block - 1)
            given = jnp.zeros((at.shape[0], t + 1), bool).at[
                jnp.arange(at.shape[0])[:, None],
                jnp.where(named >= 0, named, t)].set(True)[:, :t]
            uses = inside & (named >= 0).any(axis=1)
            mine = chosen[local]                                # [P, T]
            ranked = jnp.sort(jnp.where(mine, key_pos[None, :], t), axis=1)
            ranked = jnp.pad(ranked, ((0, 0), (0, max(0, topk - t))),
                             constant_values=t)[:, :topk]
            own = jnp.where(inside[:, None],
                            jnp.where(ranked < t, ranked, -1), own)
            slack = jnp.where(uses, selection_slack(
                scores[local], seen[local], given, topk), slack)
            chosen = chosen.at[jnp.where(uses, local, block)].set(
                given, mode="drop")
            seen = chosen

        def head_out(xs_h):     # a head: [q_lora, n + r], [rank, n + v]
            qb_h, kvb_h, g_h = xs_h
            q = cq_b @ qb_h
            # rotate the block's rope part at its own positions
            half = rope // 2
            freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
            ang = q_pos.astype(jnp.float32)[:, None] * freq[None, :]
            a, b = q[:, nope:nope + half], q[:, nope + half:]
            q_rope = jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                                     -1)
            kv = c @ kvb_h
            s = (q[:, :nope] @ kv[:, :nope].T + q_rope @ k_rope.T) * scale
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return (p @ kv[:, nope:]) * g_h[:, None]            # [block, v]

        out = jax.lax.map(head_out, (jnp.moveaxis(q_b, 1, 0),
                                     jnp.moveaxis(kv_b, 1, 0),
                                     jnp.moveaxis(gate_b, 1, 0)))
        return jnp.moveaxis(out, 0, 1).reshape(block, heads * vd), own, slack

    xs = (jnp.arange(n_blocks) * block, rows_of(c_q), rows_of(gate))
    if full:
        xs += (rows_of(q_i), rows_of(w_i))
    out, own, slack = jax.lax.map(one_block, xs)
    out = out.reshape(padded, -1)[:t] @ _f32(w["attn_out_w"])
    # a handed position lies in one block: the others say -1 and 0
    return out, own.max(axis=0), slack.max(axis=0)


# ------------------------------------------------------------ feed-forward
def gated_mlp(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def choice_slack(biased, member):
    """The slack [T] of the sets ``member`` [T, E] under ``biased`` = ``s +
    bias``: how far the weakest expert taken lies under the strongest left
    out, in their own unit (``nemotron_h_ref``)."""
    weakest = jnp.min(jnp.where(member, biased, jnp.inf), axis=1)
    strongest = jnp.max(jnp.where(member, -jnp.inf, biased), axis=1)
    return jnp.maximum(strongest - weakest, 0.0)


def route(model: dict, h, w, handed, use):
    """Gates [T, E] (``routed_scaling_factor * s_e / sum over the set`` on
    each token's experts, 0 elsewhere), this forward's own experts [T, k],
    and the slack [T] of ``handed`` [T, k], which takes the place of the own
    set in the rows where ``use`` [T] says so (0 in the other rows)."""
    s = jax.nn.sigmoid(h @ _f32(w["router_w"]))                     # [T, E]
    biased = s + _f32(w["router_bias"])
    top = jnp.argsort(-biased, axis=1, stable=True)[:, :model["k"]]
    rows = jnp.arange(s.shape[0])[:, None]
    own = jnp.zeros(s.shape, bool).at[rows, top].set(True)
    given = jnp.zeros(s.shape, bool).at[rows, jnp.maximum(handed, 0)].set(
        True)
    member = jnp.where(use[:, None], given, own)
    slack = jnp.where(use, choice_slack(biased, member), 0.0)
    taken = jnp.where(member, s, 0.0)
    gates = (taken / taken.sum(axis=1, keepdims=True)
             * model["routed_scaling_factor"])
    return gates, top, slack


def held_experts(model: dict, h, w, gates):
    """``sum_e gates[:, e] * FFN_e(h)`` over the held experts, one at a time."""
    first, count = model["held_experts"]

    def one(y, e):
        gate, up, down, g = e
        return y + g[:, None] * gated_mlp(h, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (w["experts_gate_w"], w["experts_up_w"], w["experts_down_w"],
         gates[:, first:first + count].T))
    return y


def block(model: dict, kind: str, dense: bool, x, w, handed, use, at, named):
    """A layer of ``kind`` with a ``dense`` or a routed feed-forward: the
    stream, the layer's own experts [T, k] (-1 from a dense layer), the
    experts' slack [T], its own selections at ``at`` and the selections'
    slack [P]."""
    eps, t = model["rms_norm_eps"], x.shape[0]
    delta, own_sel, sel_slack = attention(
        model, kind, rms_norm(x, w["ln1_scale"], eps), w, at, named)
    x = x + delta
    h = rms_norm(x, w["ln2_scale"], eps)
    if dense:
        y = gated_mlp(h, w["mlp_gate_w"], w["mlp_up_w"], w["mlp_down_w"])
        own = jnp.full((t, model["k"]), -1, jnp.int32)
        slack = jnp.zeros((t,), jnp.float32)
    else:
        gates, own, slack = route(model, h, w, handed, use)
        y = held_experts(model, h, w, gates)
        if model["n_shared_experts"]:
            y = y + gated_mlp(h, w["shared_gate_w"], w["shared_up_w"],
                              w["shared_down_w"])
    return x + y, own.astype(jnp.int32), slack, own_sel, sel_slack


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _block_at(model_items, kind, dense, x, stack, index, handed, use, at,
              named):
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False),
        stack)
    return block(_thawed(model_items), kind, dense, x, w, handed, use, at,
                 named)


@functools.partial(jax.jit, static_argnums=(0,))
def _head(eps, params, x):
    return rms_norm(x, params["lnf_scale"], eps) @ _f32(params["lm_head"]).T


def _handed(model: dict, t: int, choices):
    """``choices`` ({position: [n_layer, k] or [n_layer, k + index_topk]} or
    None) as the arrays the layers take: experts [n_layer, T, k] and which
    rows use them [T]; the handed positions [P] and the selections they name
    [n_layer, P, index_topk] (-1 throughout: none)."""
    n_layer, dense, k = model["n_layer"], model["n_dense_layers"], model["k"]
    topk = model["index_topk"]
    handed = np.zeros((n_layer, t, k), np.int32)
    use = np.zeros(t, bool)
    at = np.asarray(sorted(choices or {}), np.int32)
    named = np.full((n_layer, len(at), topk), -1, np.int32)
    for p, pos in enumerate(at.tolist()):
        sets = np.asarray(choices[pos])
        if not 0 <= pos < t or sets.shape not in ((n_layer, k),
                                                  (n_layer, k + topk)):
            raise ValueError(
                f"choices at position {pos} of {t}: shape {sets.shape}, "
                f"wanted {(n_layer, k)} or {(n_layer, k + topk)}")
        experts = sets[:, :k]
        if (experts[:dense] != -1).any():
            raise ValueError(f"choices at position {pos} name experts in a "
                             f"dense layer: {experts[:dense].tolist()}")
        routed = experts[dense:]
        if (not all(len(set(row)) == k for row in routed.tolist())
                or routed.min() < 0
                or routed.max() >= model["n_routed_experts"]):
            raise ValueError(f"choices at position {pos}: every routed layer "
                             f"names {k} different experts of "
                             f"{model['n_routed_experts']}, got "
                             f"{routed.tolist()}")
        handed[dense:, pos], use[pos] = routed, True
        if sets.shape[1] > k:
            rows = sets[:, k:]
            for l in range(n_layer):
                if kind_of(model, l) != "full" and (rows[l] != -1).any():
                    raise ValueError(f"choices at position {pos} name "
                                     f"positions in window layer {l}")
            if rows.max() > pos:
                raise ValueError(f"choices at position {pos} name a later "
                                 f"position: {int(rows.max())}")
            named[:, p] = rows
    return handed, use, at, named


def forward(model: dict, params, ids, choices=None, probe=()):
    """One sequence ``ids`` [T] through the layers: the residual stream
    [T, d] after the last, this forward's own experts [T, n_layer, k] (-1 in
    a dense layer), the slack of ``choices`` [T, n_layer] (0 in rows that
    were handed nothing: the larger of the experts' and the selection's in
    ``CHOICE_SLACK``'s unit) and, fourth, this forward's own selections at
    the positions ``probe`` (or the handed ones), {position: [n_layer,
    index_topk]}, ascending, -1 after them and in a window layer."""
    _check(model)
    items = _frozen({k: model[k] for k in KEYS + tuple(COVERS)})
    ids = jnp.asarray(ids, jnp.int32)
    t = ids.shape[0]
    handed, use, at, named = _handed(model, t, choices)
    if not choices and len(probe):  # positions that are only asked about
        at = np.asarray(sorted(probe), np.int32)
        named = np.full((model["n_layer"], len(at), model["index_topk"]), -1,
                        np.int32)
    own, slack, sels = [], [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"][ids])
        for layer in range(model["n_layer"]):
            name, index = place(model, layer)
            x, o, s, own_sel, sel_slack = _block_at(
                items, kind_of(model, layer),
                layer < model["n_dense_layers"], x, params[name],
                jnp.int32(index), handed[layer], use, at, named[layer])
            if len(at):     # the selection's slack, in the experts' unit
                s = s.at[at].max(sel_slack * (CHOICE_SLACK / SELECT_SLACK))
            own.append(o)
            slack.append(s)
            sels.append(own_sel)
    sels = np.asarray(jnp.stack(sels, axis=1))      # [P, n_layer, topk]
    return (x, jnp.stack(own, axis=1), jnp.stack(slack, axis=1),
            {int(pos): sels[p] for p, pos in enumerate(at.tolist())})


def head_logits(model: dict, params, x, positions=None):
    """Final norm and head over the rows ``positions`` of the residual stream
    ``x`` [T, d]; all rows if None."""
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    with jax.default_matmul_precision("highest"):
        return _head(model["rms_norm_eps"], params, x)


def logits(model: dict, params, ids, positions=None, choices=None):
    """Logits [len(positions), V] of one sequence; all positions if None.
    ``choices`` maps a position to what is handed over there (the module
    docstring), and the one forward that uses it then also judges it: the
    value is (logits, {position: slack [n_layer]})."""
    x, _, slack, _ = forward(model, params, ids, choices)
    out = head_logits(model, params, x, positions)
    if choices is None:
        return out
    slack = np.asarray(slack)
    return out, {pos: slack[pos] for pos in choices}


# ------------------------------------------------------------------ counts
def attention_params(model: dict, kind: str) -> int:
    """One layer's attention of ``kind``: the latent matrices, the gate, the
    two latent norms and, in a full layer, the indexer with its LayerNorm."""
    d, g = model["d_model"], model[kind]
    h, qr, r = g["n_head"], g["q_lora_rank"], g["kv_lora_rank"]
    nope, rope, v = (g["qk_nope_head_dim"], g["qk_rope_head_dim"],
                     g["v_head_dim"])
    n = (d * qr + qr * h * (nope + rope) + d * (r + rope)
         + r * h * (nope + v) + h * v * d + d * h + qr + r)
    if kind == "full":
        hi, di = model["index_n_heads"], model["index_head_dim"]
        n += qr * hi * di + d * di + 2 * di + d * hi
    return n


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["moe_d_ff"]


def layer_params(model: dict, layer: int) -> int:
    """Layer ``layer`` as held: its attention, its two norms, the dense MLP
    or the router with its bias, the shared expert and the held experts."""
    d = model["d_model"]
    n = attention_params(model, kind_of(model, layer)) + 2 * d
    if layer < model["n_dense_layers"]:
        return n + 3 * d * model["d_ff"]
    e = model["n_routed_experts"]
    return n + d * e + e + (model["n_shared_experts"]
                            + model["held_experts"][1]) * expert_params(model)


def held_params(model: dict) -> int:
    """Every weight the tree holds: the layers, the embedding, the head and
    the final norm."""
    return (sum(layer_params(model, l) for l in range(model["n_layer"]))
            + 2 * model["vocab_size"] * model["d_model"] + model["d_model"])


def layers_of(model: dict, kind: str) -> int:
    return sum(kind_of(model, l) == kind for l in range(model["n_layer"]))


def cache_layers(model: dict) -> int:
    """Cache layers a decode step walks: one a layer, pages or a ring."""
    return model["n_layer"]


def row_bytes(model: dict, kind: str, kv_dtype_bytes: int = 2) -> int:
    """One cached row of ``kind``: ``[c | k_r]``, no head axis."""
    g = model[kind]
    return (g["kv_lora_rank"] + g["qk_rope_head_dim"]) * kv_dtype_bytes


def index_key_bytes(model: dict, kv_dtype_bytes: int = 2) -> int:
    return model["index_head_dim"] * kv_dtype_bytes


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """What one more cached token costs: a latent row and an index key in
    every full layer. A window layer's ring is its slot's
    (:func:`ring_bytes_per_slot`). (A pool may pad a row to whole lanes;
    that is the pool's, not the algorithm's.)"""
    return layers_of(model, "full") * (
        row_bytes(model, "full", kv_dtype_bytes)
        + index_key_bytes(model, kv_dtype_bytes))


def ring_bytes_per_slot(model: dict, kv_dtype_bytes: int = 2) -> int:
    """The window layers' rows of one sequence, whatever its length."""
    return (layers_of(model, "sliding") * model["sliding_window"]
            * row_bytes(model, "sliding", kv_dtype_bytes))


def decode_rows(model: dict, length: float) -> dict:
    """Rows one decode step of a request of ``length`` cached tokens (the
    new one among them) reads, a layer of each kind: the index keys it
    scores, the rows its selection keeps, the rows its window sees."""
    return {"index": length, "selected": min(length, model["index_topk"]),
            "window": min(length, model["sliding_window"])}


def decode_step_bytes(model: dict, live_kv_tokens: float,
                      selected_rows: float = None, window_rows: float = None,
                      weight_dtype_bytes: int = 2,
                      kv_dtype_bytes: int = 2) -> float:
    """What one decode step over the slot array has to read from HBM: every
    held matrix but the embedding table once (``deepseek_v2_ref`` says why
    every held expert), and of the cache what the mathematics asks: in each
    full layer an index key of every live token (``live_kv_tokens``, summed
    over the active slots) and a latent row of every SELECTED one
    (``selected_rows``, summed likewise: ``min(length, index_topk)`` a
    slot), in each window layer a row of every position the window sees
    (``window_rows``: ``min(length, sliding_window)`` a slot). A caller that
    knows the live tokens alone (``readers/decode_bw_util``) gets the least
    either can be, one slot's: a floor stays a floor."""
    weights = held_params(model) - model["vocab_size"] * model["d_model"]
    if selected_rows is None:
        selected_rows = min(live_kv_tokens, model["index_topk"])
    if window_rows is None:
        window_rows = min(live_kv_tokens, model["sliding_window"])
    full, window = layers_of(model, "full"), layers_of(model, "sliding")
    return (weights * weight_dtype_bytes
            + full * (live_kv_tokens * index_key_bytes(model, kv_dtype_bytes)
                      + selected_rows * row_bytes(model, "full",
                                                  kv_dtype_bytes))
            + window * window_rows * row_bytes(model, "sliding",
                                               kv_dtype_bytes))
