"""Plain reference forward for DeepSeek-V2's layers (DeepSeek-AI 2024,
"DeepSeek-V2: A Strong, Economical, and Efficient Mixture-of-Experts Language
Model", sections 2.1 and 2.2;
``https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json``, row
11 of the catalog beside the ``model-configs`` guide; the public
``modeling_deepseek.py`` for what the config does not say): latent attention
(MLA), a leading dense layer, then routed layers with a shared expert. For a
residual stream ``x`` [T, d], every layer (RMSNorm eps 1e-6, no bias anywhere):

    x = x + MLA(RMSNorm(x));  x = x + FFN(RMSNorm(x))

then ``RMSNorm_f(x) W_head^T``, head untied.

MLA, ``H`` heads:
    c_q = RMSNorm(h W_qa)                     q_lora_rank wide
    q   = c_q W_qb                            a head [q_nope | q_rope]
    [c_kv | k_rope] = h W_kva;  c_kv = RMSNorm(c_kv)       kv_lora_rank | rope
    k_rope, one for all heads, and q_rope rotated (YaRN, below)
    [k_nope | v] a head = c_kv W_kvb
    scores = (q_nope . k_nope + q_rope . k_rope) * s, causal softmax,
    out = sum p v, concat(out) W_o
Rotary is YaRN from ``rope_scaling``: the ``rope / 2`` frequencies blended
between extrapolated ``theta^(-i / half)`` and interpolated (that ``/ factor``)
by the linear ramp over the correction range ``[floor(dim(beta_fast)),
ceil(dim(beta_slow))]``, ``dim(n) = rope * ln(original / (2 pi n)) / (2 ln
theta)``; cosines and sines times ``m(mscale) / m(mscale_all_dim)``, ``m(c) =
0.1 c ln(factor) + 1``; ``s = (nope + rope)^-0.5 * m(mscale_all_dim)^2``.
Rotate-half pairing (dimension i with i + rope/2): a permutation of the
published interleaved form that seeded weights do not tell apart (a
configuration lists it under ``assumed``). What a served path caches for a
token and layer is ``[c_kv | k_rope]``, no head axis; it may absorb ``W_kvb``
into the query and the output. This forward never does: it expands keys and
values a head, and keeps no cache.

FFN, layers under ``n_dense_layers``: a SiLU-gated MLP of ``d_ff``. From
there on:
    p = softmax(h W_g)                        over all ``n_routed_experts``
    a group's score is its largest p (``n_group`` groups of equal size); the
    ``topk_group`` best groups are kept, the ``k`` largest p inside them are
    the token's experts S (``group_limited_greedy``; ties to the lower index)
    y = sum over e in S of routed_scaling_factor * p_e *
        W_down,e (silu(W_gate,e h) * W_up,e h)          p_e not renormalised
      + the shared expert, one gated MLP of n_shared_experts * moe_d_ff
No capacity: every token reaches its experts.

**A share of the layer.** ``held_experts`` = [first, count] says which routed
experts' weights the parameter tree holds (a chip that is one of several
sharing each layer holds a range of them, and a share of the vocabulary's
rows). The router keeps its full width; every held expert is computed for
every token and weighted by its gate, which is 0 outside ``S``; what the
absent experts would add is left out, as the served chip leaves it out (the
exchange that would add it is not here). With all experts held this is the
whole layer.

Float32 under ``jax.default_matmul_precision("highest")``, one sequence at a
time, no sort, no gather, no grouped product, no cache and no function of the
program. The weights arrive in the served type and are upcast an expert at a
time, never a layer: a layer's 40 held experts in float32 are 3.8 GB beside
12.6 GB resident.

**A choice can be handed over** (``benchmark/README.md``, the ``reference``
row; ``olmoe_ref`` says why). ``logits(..., choices={position: [n_layer,
k]})`` computes those positions with the experts named in place of ``S``, the
gates staying this forward's own ``p_e``; a dense layer's row names nothing
(-1 throughout), and a row that names experts there is an error. The same
call returns the slack of what was handed, for each position and layer, in
``olmoe_ref``'s unit (the standard deviation of that token's router logits).
The rule makes two choices and the slack asks both:
    groups: the handed experts must lie inside ``topk_group`` groups (else the
      slack is infinite: no run of the rule gives such a set); the groups
      taken are those they lie in and, where they are fewer, the strongest
      others; how far the weakest group the handed experts lie in is under
      the strongest group left out, by the groups' largest router logit;
    experts: inside the groups taken, how far the weakest handed expert lies
      under the strongest expert left out.
The larger of the two, 0 where the set is this forward's own.

``CHOICE_SLACK`` is the most a defensible choice may show. Measured by
``tools/dsv2_drift.py`` on a TPU v5e with this family's own programs (the
engine's serial prefill chunks and ``jit_scatter``, then nine teacher-forced
decode steps through the latent pages; bf16 weights and pages, the router in
float32 from the served activations) at the published widths, 1 dense and 4
routed layers, experts 0-39 of 160 held, weights N(0, 0.02) rounded to bf16,
the router logits' spread 1.43 (my chip runs, PR 34; PERF.md section 6 has the
rows): prompts of 1024 and 2048, every decoded position handed over, 3 seeds,
216 routed-layer choices. With the decode token's stream in float32 2.2% of
the choices differ from this forward's own, largest slack 0.013 (the path as
served keeps the prompts' stream in float32 too: 2.8%, 0.008); with the
stream in bf16 10-12% differ, largest 0.023; at the
two positions ``lib/correct.py`` compares, over two runs of the cell, 0.0033.
With the router's logits rounded to bf16 the same 0.013-0.022: as
``olmoe_ref`` found, the rounding of the router's input moves a choice, not
the router's own precision, and the comparison does not tell the two apart.
A missing scaling factor, a missing shared expert and a wrong YaRN scale move
the stream so far that half the choices flip, with slacks of 1.0 to 3.0. 0.12
(``olmoe_ref``'s) is 5 times the largest honest reading and an eighth of the
smallest faulty one.

It reads the parameter tree below; a family's ``init_params`` makes it.

- ``wte`` [V, d], ``lm_head`` [V, d], ``lnf_scale`` [d];
- ``blocks``, the dense layers, and ``moe_blocks``, the routed ones, leaves
  stacked over their layers. Both: ``ln1_scale``, ``ln2_scale`` [d]; ``q_a_w``
  [d, q_lora_rank], ``q_a_norm_scale``; ``q_b_w`` [q_lora_rank, H (nope +
  rope)]; ``kv_a_w`` [d, kv_lora_rank + rope], ``kv_a_norm_scale``
  [kv_lora_rank]; ``kv_b_w`` [kv_lora_rank, H (nope + v)], a head's columns
  [k_nope | v]; ``attn_out_w`` [H v, d]. ``blocks``: ``mlp_gate_w``,
  ``mlp_up_w`` [d, d_ff], ``mlp_down_w`` [d_ff, d]. ``moe_blocks``:
  ``router_w`` [d, n_routed_experts]; ``experts_gate_w``, ``experts_up_w``
  [count, d, f], ``experts_down_w`` [count, f, d], the held experts in order;
  ``shared_gate_w``, ``shared_up_w`` [d, fs], ``shared_down_w`` [fs, d].

``model`` is the ``model`` group of a configuration file, in the names of
``KEYS``. Its counts (``lib/context.Context.count`` prefers them to
``lib/flops``'s): ``cache_layers``, ``kv_bytes_per_token``,
``decode_step_bytes``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHOICE_SLACK = 0.12

KEYS = ("vocab_size", "n_layer", "n_dense_layers", "n_head", "d_model", "d_ff",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_theta", "rope_scaling", "rms_norm_eps",
        "n_routed_experts", "held_experts", "n_group", "topk_group", "k",
        "moe_d_ff", "n_shared_experts", "routed_scaling_factor")
COVERS = {"norm_topk_prob": False, "tie_embeddings": False,
          "scoring_func": "softmax", "topk_method": "group_limited_greedy"}


def _check(model: dict) -> None:
    missing = [key for key in KEYS if key not in model]
    have = {key: model.get(key) for key in COVERS}
    if missing or have != COVERS:
        raise ValueError(f"deepseek_v2_ref reads {KEYS} and covers {COVERS}; "
                         f"the configuration lacks {missing} and says {have}")
    if model["rope_scaling"].get("type") != "yarn":
        raise ValueError("deepseek_v2_ref rotates by YaRN: rope_scaling's "
                         f"type is {model['rope_scaling'].get('type')!r}")
    first, count = model["held_experts"]
    if not (0 <= first and count >= 1
            and first + count <= model["n_routed_experts"]
            and model["n_routed_experts"] % model["n_group"] == 0):
        raise ValueError(f"held_experts {model['held_experts']} of "
                         f"{model['n_routed_experts']} in {model['n_group']} "
                         "groups")


def _frozen(model: dict):
    def freeze(v):
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()))
        return tuple(v) if isinstance(v, list) else v
    return tuple(sorted((k, freeze(model[k])) for k in KEYS + tuple(COVERS)))


def _thawed(items) -> dict:
    model = dict(items)
    model["rope_scaling"] = dict(model["rope_scaling"])
    return model


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(gain)


# ------------------------------------------------------------------ rotary
def yarn_m(factor: float, c: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * c * math.log(factor) + 1.0


def yarn_inv_freq(model: dict) -> np.ndarray:
    """The ``rope / 2`` frequencies, float64 until the end."""
    rs, rope = model["rope_scaling"], model["qk_rope_head_dim"]
    theta, half = float(model["rope_theta"]), rope // 2
    extrapolated = theta ** (-np.arange(half, dtype=np.float64) / half)
    interpolated = extrapolated / rs["factor"]

    def dim(turns):
        return (rope * math.log(rs["original_max_position_embeddings"]
                                / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(rs["beta_fast"])), 0)
    high = min(math.ceil(dim(rs["beta_slow"])), rope - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return (interpolated * ramp + extrapolated * (1 - ramp)).astype(
        np.float32)


def softmax_scale(model: dict) -> float:
    rs = model["rope_scaling"]
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= yarn_m(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rotary(model: dict, x):
    """``x`` [T, heads, rope] rotated by position, rotate-half pairing."""
    rs = model["rope_scaling"]
    half = x.shape[-1] // 2
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(yarn_inv_freq(model))[None, :])
    factor = (yarn_m(rs["factor"], rs.get("mscale", 1.0))
              / yarn_m(rs["factor"], rs.get("mscale_all_dim", 0.0)))
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# ------------------------------------------------------------------ layers
def mla(model: dict, h, w):
    """Latent attention of the normalised input ``h`` [T, d], un-absorbed."""
    t = h.shape[0]
    heads, eps = model["n_head"], model["rms_norm_eps"]
    rank, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    c_q = rms_norm(h @ _f32(w["q_a_w"]), w["q_a_norm_scale"], eps)
    q = (c_q @ _f32(w["q_b_w"])).reshape(t, heads, -1)
    q_nope, q_rope = q[..., :nope], rotary(model, q[..., nope:])
    kv_a = h @ _f32(w["kv_a_w"])
    c_kv = rms_norm(kv_a[:, :rank], w["kv_a_norm_scale"], eps)
    k_rope = rotary(model, kv_a[:, None, rank:])[:, 0]            # [T, rope]
    kv = (c_kv @ _f32(w["kv_b_w"])).reshape(t, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(a):        # a head at a time: the scores of all are gigabytes
        qn, qr, kn, vh = a
        scores = (qn @ kn.T + qr @ k_rope.T) * softmax_scale(model)
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                              axis=-1) @ vh

    out = jax.lax.map(head, tuple(jnp.moveaxis(a, 1, 0) for a in
                                  (q_nope, q_rope, k_nope, v)))
    return jnp.moveaxis(out, 0, 1).reshape(t, -1) @ _f32(w["attn_out_w"])


def gated_mlp(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def own_choice(model: dict, p):
    """The rule's own experts of each row of ``p`` [T, E], as a membership
    mask [T, E]: best groups by their largest member, then the largest
    members inside them; ties to the lower index (a stable descending
    sort)."""
    t, e = p.shape
    groups, per = model["n_group"], e // model["n_group"]
    score = p.reshape(t, groups, per).max(axis=-1)
    kept = jnp.argsort(-score, axis=1, stable=True)[:, :model["topk_group"]]
    rows = jnp.arange(t)[:, None]
    keep = jnp.zeros((t, groups), bool).at[rows, kept].set(True)
    inside = jnp.where(jnp.repeat(keep, per, axis=1), p, -1.0)
    top = jnp.argsort(-inside, axis=1, stable=True)[:, :model["k"]]
    return jnp.zeros((t, e), bool).at[rows, top].set(True), top


def choice_slack(model: dict, r, member):
    """The slack [T] of the sets ``member`` [T, E] under router logits ``r``:
    the module docstring's two questions, the larger answer."""
    t, e = r.shape
    groups, per = model["n_group"], e // model["n_group"]
    spread = jnp.std(r, axis=1)
    by_group = r.reshape(t, groups, per)
    score = by_group.max(axis=-1)                                   # [T, G]
    touched = member.reshape(t, groups, per).any(axis=-1)
    n_touched = touched.sum(axis=1)
    # the groups taken: those touched, then the strongest others
    order = jnp.argsort(-jnp.where(touched, jnp.inf, score), axis=1,
                        stable=True)[:, :model["topk_group"]]
    taken = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], order].set(True)
    weakest_in = jnp.min(jnp.where(touched, score, jnp.inf), axis=1)
    strongest_out = jnp.max(jnp.where(taken, -jnp.inf, score), axis=1)
    by_groups = jnp.maximum(strongest_out - weakest_in, 0.0)
    open_to = jnp.repeat(taken, per, axis=1)
    weakest = jnp.min(jnp.where(member, r, jnp.inf), axis=1)
    strongest = jnp.max(jnp.where(open_to & ~member, r, -jnp.inf), axis=1)
    by_experts = jnp.maximum(strongest - weakest, 0.0)
    worst = jnp.maximum(by_groups, by_experts)
    slack = jnp.where(worst > 0, worst / spread, 0.0)   # all equal: 0 / 0
    return jnp.where(n_touched > model["topk_group"], jnp.inf, slack)


def route(model: dict, h, router_w, handed, use):
    """Gates [T, E] (``routed_scaling_factor * p_e`` on each token's experts,
    0 elsewhere), this forward's own experts [T, k], and the slack [T] of
    ``handed`` [T, k], which takes the place of the own set in the rows
    where ``use`` [T] says so (0 in the other rows)."""
    r = h @ _f32(router_w)                                          # [T, E]
    p = jax.nn.softmax(r, axis=-1)
    own, top = own_choice(model, p)
    rows = jnp.arange(r.shape[0])[:, None]
    given = jnp.zeros(r.shape, bool).at[rows, jnp.maximum(handed, 0)].set(
        True)
    member = jnp.where(use[:, None], given, own)
    slack = jnp.where(use, choice_slack(model, r, member), 0.0)
    gates = jnp.where(member, p, 0.0) * model["routed_scaling_factor"]
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


def dense_block(model: dict, x, w):
    eps = model["rms_norm_eps"]
    x = x + mla(model, rms_norm(x, w["ln1_scale"], eps), w)
    h = rms_norm(x, w["ln2_scale"], eps)
    return x + gated_mlp(h, w["mlp_gate_w"], w["mlp_up_w"], w["mlp_down_w"])


def routed_block(model: dict, x, w, handed, use):
    eps = model["rms_norm_eps"]
    x = x + mla(model, rms_norm(x, w["ln1_scale"], eps), w)
    h = rms_norm(x, w["ln2_scale"], eps)
    gates, own, slack = route(model, h, w["router_w"], handed, use)
    y = held_experts(model, h, w, gates)
    if model["n_shared_experts"]:
        y = y + gated_mlp(h, w["shared_gate_w"], w["shared_up_w"],
                          w["shared_down_w"])
    return x + y, own, slack


def _layer(blocks, layer):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        blocks)


@functools.partial(jax.jit, static_argnums=(0,))
def _dense_at(model_items, x, blocks, layer):
    return dense_block(_thawed(model_items), x, _layer(blocks, layer))


@functools.partial(jax.jit, static_argnums=(0,))
def _routed_at(model_items, x, blocks, layer, handed, use):
    return routed_block(_thawed(model_items), x, _layer(blocks, layer),
                        handed, use)


@functools.partial(jax.jit, static_argnums=(0,))
def _head(model_items, params, x):
    x = rms_norm(x, params["lnf_scale"], dict(model_items)["rms_norm_eps"])
    return x @ _f32(params["lm_head"]).T


def _handed(model: dict, t: int, choices):
    """``choices`` ({position: [n_layer, k] experts} or None) as the arrays
    the routed layers take: experts [routed layers, T, k] and which rows use
    them [T]. A dense layer's row names nothing (-1)."""
    n_layer, dense, k = model["n_layer"], model["n_dense_layers"], model["k"]
    handed = np.zeros((n_layer - dense, t, k), np.int32)
    use = np.zeros(t, bool)
    for pos, sets in (choices or {}).items():
        sets = np.asarray(sets)
        if not 0 <= pos < t or sets.shape != (n_layer, k):
            raise ValueError(f"choices at position {pos} of {t}: shape "
                             f"{sets.shape}, wanted {(n_layer, k)}")
        if (sets[:dense] != -1).any():
            raise ValueError(f"choices at position {pos} name experts in a "
                             f"dense layer: {sets[:dense].tolist()} (a dense "
                             "layer's row is -1 throughout)")
        routed = sets[dense:]
        distinct = all(len(set(row)) == k for row in routed.tolist())
        if (not distinct or routed.min() < 0
                or routed.max() >= model["n_routed_experts"]):
            raise ValueError(f"choices at position {pos}: every routed layer "
                             f"names {k} different experts of "
                             f"{model['n_routed_experts']}, got "
                             f"{routed.tolist()}")
        handed[:, pos], use[pos] = routed, True
    return handed, use


def forward(model: dict, params, ids, choices=None):
    """One sequence ``ids`` [T] through the layers: the residual stream
    [T, d] after the last, this forward's own experts [T, n_layer, k] (-1 in
    a dense layer) and the slack of ``choices`` [T, n_layer] (0 in rows that
    were handed nothing, and in a dense layer)."""
    _check(model)
    items = _frozen(model)
    ids = jnp.asarray(ids, jnp.int32)
    t, dense = ids.shape[0], model["n_dense_layers"]
    handed, use = _handed(model, t, choices)
    own = [jnp.full((t, model["k"]), -1, jnp.int32)] * dense
    slack = [jnp.zeros((t,), jnp.float32)] * dense
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"][ids])
        for layer in range(dense):
            x = _dense_at(items, x, params["blocks"], jnp.int32(layer))
        for layer in range(model["n_layer"] - dense):
            x, o, s = _routed_at(items, x, params["moe_blocks"],
                                 jnp.int32(layer), handed[layer], use)
            own.append(o.astype(jnp.int32))
            slack.append(s)
    return x, jnp.stack(own, axis=1), jnp.stack(slack, axis=1)


def head_logits(model: dict, params, x, positions=None):
    """Final norm and head over the rows ``positions`` of the residual stream
    ``x`` [T, d]; all rows if None."""
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    with jax.default_matmul_precision("highest"):
        return _head(_frozen(model), params, x)


def logits(model: dict, params, ids, positions=None, choices=None):
    """Logits [len(positions), V] of one sequence; all positions if None.
    ``choices`` maps a position to the experts [n_layer, k] to use there (a
    dense layer's row -1), and the one forward that uses them then also
    judges them: the value is (logits, {position: slack [n_layer]})."""
    x, _, slack = forward(model, params, ids, choices)
    out = head_logits(model, params, x, positions)
    if choices is None:
        return out
    slack = np.asarray(slack)
    return out, {pos: slack[pos] for pos in choices}


# ------------------------------------------------------------------ counts
def attention_params(model: dict) -> int:
    """One layer's latent-attention matrices."""
    d, h = model["d_model"], model["n_head"]
    qr, r = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    return (d * qr + qr * h * (nope + rope) + d * (r + rope)
            + r * h * (nope + v) + h * v * d)


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["moe_d_ff"]


def held_params(model: dict) -> int:
    """Matrix weights the tree holds: every layer's attention, the dense
    layers' MLP, each routed layer's router, shared expert and held experts,
    the embedding and the head."""
    d, dense = model["d_model"], model["n_dense_layers"]
    routed = model["n_layer"] - dense
    per_routed = (d * model["n_routed_experts"]
                  + model["n_shared_experts"] * expert_params(model)
                  + model["held_experts"][1] * expert_params(model))
    return (model["n_layer"] * attention_params(model)
            + dense * 3 * d * model["d_ff"] + routed * per_routed
            + 2 * model["vocab_size"] * d)


def cache_layers(model: dict) -> int:
    """Latent layers a decode step walks: one a layer."""
    return model["n_layer"]


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """What one cached token needs over all layers: ``[c_kv | k_rope]`` a
    layer, no head axis. (A pool may pad the row to whole lanes; that is the
    pool's, not the algorithm's.)"""
    return (model["n_layer"]
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            * kv_dtype_bytes)


def decode_step_bytes(model: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int = 2,
                      kv_dtype_bytes: int = 2) -> float:
    """What one decode step over the slot array has to read from HBM: every
    held matrix but the embedding table once (a step reads the embedding's
    rows of its tokens, not the table: they, the norm gains and the
    activations are thousands of times smaller and left out), and the live
    latent rows. Every held expert, because a step over the slot array
    touches nearly all: a token spares an expert with 1 - k/E, n tokens with
    that to the n-th, so at 128 tokens and 6 of 160 an expert goes untouched
    in 0.75% of the steps (0.9625^128) and the count is 0.75% of the
    experts' bytes high."""
    weights = held_params(model) - model["vocab_size"] * model["d_model"]
    return (weights * weight_dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(model, kv_dtype_bytes))
