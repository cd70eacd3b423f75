"""Plain reference forward for Laguna-XS.2's layers (poolside;
``https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json``, the
row ``Laguna-XS.2`` of the catalog beside the ``model-configs`` guide,
``model_type`` ``laguna``): fewer key-value heads than query heads, window
and full attention layers mixed with a head count, a rotary set and a mask a
kind, a sigmoid gate a head on attention's output, a leading dense layer,
then routed layers with a shared expert. For a residual stream ``x`` [T, d],
every layer (RMSNorm eps ``rms_norm_eps``, no bias anywhere):

    x = x + Attn_kind(RMSNorm(x));  x = x + FFN(RMSNorm(x))

then ``RMSNorm_f(x) W_head^T``, head untied. ``h`` is the normed input, ``D``
= ``head_dim``, ``G`` = ``n_kv_head``.

Attn, layer ``l`` of kind ``layer_types[l]`` with ``H =
num_attention_heads_per_layer[l]`` query heads:
    q = h W_q (H heads of D);  [k | v] = h W_kv (G heads of D each)
    q and k rotated by the kind's set of ``rope_parameters``, rotate-half
      (dimension i of the rotated part pairs with i + half of it), the first
      ``partial_rotary_factor * D`` dimensions of a head:
      ``full_attention``: YaRN. The ``rot / 2`` frequencies are blended
        between extrapolated ``theta^(-i / half)`` and interpolated (that
        ``/ factor``) by the linear ramp over ``[floor(dim(beta_fast)),
        ceil(dim(beta_slow))]``, ``dim(n) = rot * ln(original / (2 pi n)) /
        (2 ln theta)``; cosines and sines times ``attention_factor``.
      ``sliding_attention``: plain rotary at its ``rope_theta``.
    query head i reads key-value head i // (H / G)
    scores = q . k / sqrt(D), causal; a sliding layer's query at t sees key j
      only where t - sliding_window < j <= t
    o_i = softmax(scores) v;  gamma = sigmoid(h W_gate), W_gate [d, H];
    o_i = gamma_i o_i;  Attn = concat(o) W_o

FFN, layer ``l`` with ``mlp_layer_types[l]`` ``dense``: ``(silu(h W_g) * h
W_u) W_d`` of ``d_ff``. ``sparse``:
    p = softmax(h W_r)                       over all ``n_routed_experts``
    S = the ``k`` largest p (ties to the lower index)
    g_e = routed_scaling_factor * p_e / sum over S of p     (norm_topk_prob)
    y = sum over S of g_e FFN_e(h) + FFN_shared(h)
experts and the shared expert gated MLPs of ``moe_d_ff`` and ``shared_d_ff``.
No capacity: every token reaches its experts. All experts are held.

**Assumed** (the catalog's ``config`` holds no key for them; the
configuration file lists each with these grounds under ``assumed``):
    - the gate is a sigmoid, one a head, from the normed input (``gating``
      true; the sibling row ``Laguna-S-2.1`` says ``"gating": "per-head"``,
      and the published 33.4 B fits a gate a head: a gate an element would
      add 0.62 B);
    - the router scores by softmax and renormalises the ``k`` it took (the
      sibling has ``norm_topk_prob: true`` and the Qwen2-MoE lineage's keys
      ``decoder_sparse_step``, ``mlp_only_layers``,
      ``shared_expert_intermediate_size``, whose router is a softmax);
    - no norm on q or k, no gate on the shared expert, SiLU, rotate-half
      pairing, ``sliding_window`` counting the query's own position.
No file in this machine says otherwise; nothing else is built.

Float32 under ``jax.default_matmul_precision("highest")``, one sequence at a
time, no cache, no kernel, no sort, no grouped product and no function of
the program. The weights arrive in the served type and are upcast an expert
at a time (a routed layer's 256 experts in float32 are 3.2 GB beside 11.7 GB
resident); attention goes a query head at a time (64 heads x 8.2k x 8.2k
float32 scores are 17 GB whole, one head's are 269 MB).

**A choice can be handed over** (``benchmark/README.md``, the ``reference``
row; ``olmoe_ref`` says why). ``logits(..., choices={position: [n_layer,
k]})`` computes those positions with the experts named in place of ``S``; the
gates are this forward's own ``p_e`` of them over their sum, times the
scaling factor (the rule renormalises what was taken, so the handed set is
what it renormalises over); a dense layer's row names nothing (-1
throughout). The same call returns the slack of what was handed, for each
position and layer, in ``olmoe_ref``'s unit (the standard deviation of that
token's router logits): how far the weakest handed expert lies under the
strongest expert left out, 0 where the set is this forward's own.

``CHOICE_SLACK`` is the most a defensible choice may show: 0.12,
``olmoe_ref``'s and ``deepseek_v2_ref``'s. Read again by
``tools/laguna_drift.py`` on a TPU v5e with this family's own programs (the
engine's fused prompt program or prefill chunks and ``jit_scatter`` into pages
and rings, then nine teacher-forced decode steps through ``paged_decode_gqa``;
bf16 weights, pages and rings, the router in float32 from the served
activations) at the published widths, 1 dense and 4 routed layers of 256
experts, 8 a token, prompts of 512, 2048 and 8192, every decoded position
handed over, 135 routed-layer choices (my chip runs, PR 38; PERF.md section 6
has the rows). As served (the stream in float32): 1 of 135 choices differs from
this forward's own, slack 0.0065, logits 0.0061 and 0.0067 at most (limits
0.0125 and 0.02); over the cell's own check, 64 compared positions in 8 runs,
7 differ in one layer each, largest slack 0.0068. With the router's logits
rounded to bf16: 6 of 135, 0.0076, logits 0.0069 and 0.0074: as ``olmoe_ref``
found, the rounding of the router's input moves a choice, not the router's own
precision, and the comparison does not tell the two apart. With the stream in
bf16: 10 of 135, 0.0285, and the logits' rms 0.0152, over its limit. A fault
that moves the stream flips half the choices: no gate 108 of 135 with slack
3.8, gates not renormalised 77 and 1.1, no scaling factor 67 and 1.0, the
whole head rotated in a full layer 108 and 5.2, a window of 448 for 512 36 and
0.19 (logits 0.082). 0.12 is 18 times the largest honest reading and under
every one of those. What neither number catches on the chip: a ring row off
by one (logits 0.0091: with a ring of exactly the window a misplaced row
costs the oldest of 512 keys, and order does not matter to a softmax); the
CPU tests hold that at 1e-6.

It reads the parameter tree below; a family's ``init_params`` makes it. A
stack holds the layers of one feed-forward kind and one attention kind, in
the order the forward reaches them: ``blocks_full``, ``blocks_window`` (dense
feed-forward) and ``moe_blocks_full``, ``moe_blocks_window`` (routed), those
the model has. Layer ``l`` is entry ``i`` of its stack, ``i`` the earlier
layers of the same two kinds.

- ``wte`` [V, d], ``lm_head`` [V, d], ``lnf_scale`` [d];
- every stack: ``ln1_scale``, ``ln2_scale`` [d]; ``q_w`` [d, H D]; ``kv_w``
  [d, 2 G D], the keys' ``G D`` columns then the values'; ``attn_gate_w``
  [d, H]; ``attn_out_w`` [H D, d];
- a dense stack: ``mlp_gate_w``, ``mlp_up_w`` [d, d_ff], ``mlp_down_w``
  [d_ff, d]; a routed stack: ``router_w`` [d, E]; ``experts_gate_w``,
  ``experts_up_w`` [E, d, f], ``experts_down_w`` [E, f, d]; ``shared_gate_w``,
  ``shared_up_w`` [d, fs], ``shared_down_w`` [fs, d].

``model`` is the ``model`` group of a configuration file, in the names of
``KEYS``. Its counts (``lib/context.Context.count`` prefers them to
``lib/flops``'s): ``cache_layers``, ``kv_bytes_per_token``,
``decode_step_bytes``; and ``attention_params``, ``expert_params``,
``held_params``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHOICE_SLACK = 0.12

KEYS = ("vocab_size", "n_layer", "d_model", "d_ff", "n_kv_head", "head_dim",
        "layer_types", "num_attention_heads_per_layer", "mlp_layer_types",
        "sliding_window", "rope_parameters", "rms_norm_eps",
        "n_routed_experts", "k", "moe_d_ff", "shared_d_ff",
        "routed_scaling_factor")
COVERS = {"norm_topk_prob": True, "tie_embeddings": False,
          "scoring_func": "softmax", "gating": "per-head"}
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def _check(model: dict) -> None:
    missing = [key for key in KEYS if key not in model]
    have = {key: model.get(key) for key in COVERS}
    if missing or have != COVERS:
        raise ValueError(f"laguna_ref reads {KEYS} and covers {COVERS}; the "
                         f"configuration lacks {missing} and says {have}")
    n = model["n_layer"]
    lists = [model[k] for k in ("layer_types", "mlp_layer_types",
                                "num_attention_heads_per_layer")]
    if (any(len(a) != n for a in lists)
            or set(lists[0]) - set(KINDS)
            or set(lists[1]) - {"dense", "sparse"}
            or any(h % model["n_kv_head"] for h in lists[2])
            or set(model["rope_parameters"]) < set(lists[0])):
        raise ValueError(
            f"laguna_ref: {n} layers need layer_types of {sorted(KINDS)} "
            "with a rope_parameters group each, mlp_layer_types of dense / "
            "sparse and head counts n_kv_head divides, one a layer: got "
            f"{lists}")


def _frozen(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _frozen(x)) for k, x in v.items()))
    return tuple(_frozen(x) for x in v) if isinstance(v, list) else v


def _thawed(v):
    if isinstance(v, tuple) and v and all(
            isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str)
            for x in v):
        return {k: _thawed(x) for k, x in v}
    return [_thawed(x) for x in v] if isinstance(v, tuple) else v


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(gain)


def place(model: dict, layer: int):
    """(stack name, index inside it) of layer ``layer`` in the tree."""
    def name(l):
        return (("moe_blocks_" if model["mlp_layer_types"][l] == "sparse"
                 else "blocks_") + KINDS[model["layer_types"][l]])
    return name(layer), sum(name(l) == name(layer) for l in range(layer))


# ------------------------------------------------------------------ rotary
def rotated_dims(model: dict, kind: str) -> int:
    rope = model["rope_parameters"][kind]
    return int(model["head_dim"] * rope.get("partial_rotary_factor", 1))


def inv_freq(model: dict, kind: str) -> np.ndarray:
    """The kind's ``rot / 2`` frequencies, float64 until the end."""
    rope = model["rope_parameters"][kind]
    rot = rotated_dims(model, kind)
    theta, half = float(rope["rope_theta"]), rot // 2
    extrapolated = theta ** (-np.arange(half, dtype=np.float64) / half)
    if rope.get("rope_type", "default") == "default":
        return extrapolated.astype(np.float32)
    if rope["rope_type"] != "yarn":
        raise ValueError(f"laguna_ref rotates plainly or by YaRN, not by "
                         f"{rope['rope_type']!r}")
    interpolated = extrapolated / rope["factor"]

    def dim(turns):
        return (rot * math.log(rope["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return (interpolated * ramp + extrapolated * (1 - ramp)).astype(
        np.float32)


def rotary(model: dict, kind: str, x):
    """``x`` [T, heads, D]: the first ``rotated_dims`` of each head rotated
    by position, rotate-half pairing; the rest as they are."""
    rope = model["rope_parameters"][kind]
    rot = rotated_dims(model, kind)
    half = rot // 2
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq(model, kind))[None, :])
    factor = (float(rope.get("attention_factor", 1.0))
              if rope.get("rope_type") == "yarn" else 1.0)
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], -1)


# ------------------------------------------------------------------ layers
def attention(model: dict, layer: int, h, w):
    """Attention of the normalised input ``h`` [T, d] in layer ``layer``."""
    t = h.shape[0]
    kind = model["layer_types"][layer]
    heads = model["num_attention_heads_per_layer"][layer]
    g, d = model["n_kv_head"], model["head_dim"]
    q = rotary(model, kind, (h @ _f32(w["q_w"])).reshape(t, heads, d))
    kv = (h @ _f32(w["kv_w"])).reshape(t, 2, g, d)
    k, v = rotary(model, kind, kv[:, 0]), kv[:, 1]
    at = jnp.arange(t)
    seen = at[None, :] <= at[:, None]
    if kind == "sliding_attention":
        seen = seen & (at[None, :] > at[:, None] - model["sliding_window"])

    def head(a):        # a query head at a time: the scores of all are GBs
        qh, i = a
        group = i // (heads // g)
        kh = jax.lax.dynamic_index_in_dim(k, group, 1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, group, 1, keepdims=False)
        scores = (qh @ kh.T) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                              axis=-1) @ vh

    out = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.arange(heads)))
    gamma = jax.nn.sigmoid(h @ _f32(w["attn_gate_w"]))            # [T, H]
    out = jnp.moveaxis(out, 0, 1) * gamma[:, :, None]
    return out.reshape(t, -1) @ _f32(w["attn_out_w"])


def gated_mlp(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def choice_slack(r, member):
    """The slack [T] of the sets ``member`` [T, E] under router logits ``r``:
    how far the weakest expert taken lies under the strongest left out, over
    the standard deviation of the token's logits."""
    weakest = jnp.min(jnp.where(member, r, jnp.inf), axis=1)
    strongest = jnp.max(jnp.where(member, -jnp.inf, r), axis=1)
    worst = jnp.maximum(strongest - weakest, 0.0)
    return jnp.where(worst > 0, worst / jnp.std(r, axis=1), 0.0)


def route(model: dict, h, router_w, handed, use):
    """Gates [T, E] (``routed_scaling_factor * p_e / sum over the set`` on
    each token's experts, 0 elsewhere), this forward's own experts [T, k],
    and the slack [T] of ``handed`` [T, k], which takes the place of the own
    set in the rows where ``use`` [T] says so (0 in the other rows)."""
    r = h @ _f32(router_w)                                          # [T, E]
    p = jax.nn.softmax(r, axis=-1)
    top = jnp.argsort(-p, axis=1, stable=True)[:, :model["k"]]
    rows = jnp.arange(r.shape[0])[:, None]
    own = jnp.zeros(r.shape, bool).at[rows, top].set(True)
    given = jnp.zeros(r.shape, bool).at[rows, jnp.maximum(handed, 0)].set(
        True)
    member = jnp.where(use[:, None], given, own)
    slack = jnp.where(use, choice_slack(r, member), 0.0)
    taken = jnp.where(member, p, 0.0)
    gates = (taken / taken.sum(axis=1, keepdims=True)
             * model["routed_scaling_factor"])
    return gates, top, slack


def experts(h, w, gates):
    """``sum_e gates[:, e] * FFN_e(h)`` over all experts, one at a time."""
    def one(y, e):
        gate, up, down, g = e
        return y + g[:, None] * gated_mlp(h, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (w["experts_gate_w"], w["experts_up_w"], w["experts_down_w"],
         gates.T))
    return y


def block(model: dict, layer: int, x, w, handed, use):
    """Layer ``layer``: the stream, the layer's own experts [T, k] (-1 from
    a dense layer) and the slack [T] of what was handed."""
    eps = model["rms_norm_eps"]
    x = x + attention(model, layer, rms_norm(x, w["ln1_scale"], eps), w)
    h = rms_norm(x, w["ln2_scale"], eps)
    if model["mlp_layer_types"][layer] == "dense":
        y = gated_mlp(h, w["mlp_gate_w"], w["mlp_up_w"], w["mlp_down_w"])
        return (x + y, jnp.full((x.shape[0], model["k"]), -1, jnp.int32),
                jnp.zeros((x.shape[0],), jnp.float32))
    gates, own, slack = route(model, h, w["router_w"], handed, use)
    y = experts(h, w, gates) + gated_mlp(
        h, w["shared_gate_w"], w["shared_up_w"], w["shared_down_w"])
    return x + y, own.astype(jnp.int32), slack


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block_at(model_items, layer, x, stack, at, handed, use):
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False),
        stack)
    return block(_thawed(model_items), layer, x, w, handed, use)


@functools.partial(jax.jit, static_argnums=(0,))
def _head(eps, params, x):
    return rms_norm(x, params["lnf_scale"], eps) @ _f32(params["lm_head"]).T


def _handed(model: dict, t: int, choices):
    """``choices`` ({position: [n_layer, k] experts} or None) as the arrays
    the layers take: experts [n_layer, T, k] and which rows use them [T]. A
    dense layer's row names nothing (-1)."""
    n_layer, k = model["n_layer"], model["k"]
    handed = np.zeros((n_layer, t, k), np.int32)
    use = np.zeros(t, bool)
    for pos, sets in (choices or {}).items():
        sets = np.asarray(sets)
        if not 0 <= pos < t or sets.shape != (n_layer, k):
            raise ValueError(f"choices at position {pos} of {t}: shape "
                             f"{sets.shape}, wanted {(n_layer, k)}")
        for l, row in enumerate(sets.tolist()):
            if model["mlp_layer_types"][l] == "dense":
                if set(row) != {-1}:
                    raise ValueError(
                        f"choices at position {pos} name experts in dense "
                        f"layer {l}: {row} (its row is -1 throughout)")
            elif (len(set(row)) != k or min(row) < 0
                  or max(row) >= model["n_routed_experts"]):
                raise ValueError(
                    f"choices at position {pos}, layer {l}: {k} different "
                    f"experts of {model['n_routed_experts']}, got {row}")
        handed[:, pos], use[pos] = sets, True
    return handed, use


def forward(model: dict, params, ids, choices=None):
    """One sequence ``ids`` [T] through the layers: the residual stream
    [T, d] after the last, this forward's own experts [T, n_layer, k] (-1 in
    a dense layer) and the slack of ``choices`` [T, n_layer] (0 in rows that
    were handed nothing, and in a dense layer)."""
    _check(model)
    items = _frozen({k: model[k] for k in KEYS + tuple(COVERS)})
    ids = jnp.asarray(ids, jnp.int32)
    handed, use = _handed(model, ids.shape[0], choices)
    own, slack = [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"][ids])
        for layer in range(model["n_layer"]):
            name, at = place(model, layer)
            x, o, s = _block_at(items, layer, x, params[name], jnp.int32(at),
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
        return _head(model["rms_norm_eps"], params, x)


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
def attention_params(model: dict, layer: int) -> int:
    """Layer ``layer``'s attention matrices: q, k and v, the gate, o."""
    d, dh = model["d_model"], model["head_dim"]
    h, g = model["num_attention_heads_per_layer"][layer], model["n_kv_head"]
    return d * h * dh + 2 * d * g * dh + d * h + h * dh * d


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["moe_d_ff"]


def held_params(model: dict) -> int:
    """Matrix weights the tree holds: every layer's attention, a dense
    layer's MLP, a routed layer's router, shared expert and experts, the
    embedding and the head."""
    d = model["d_model"]
    routed = (d * model["n_routed_experts"] + 3 * d * model["shared_d_ff"]
              + model["n_routed_experts"] * expert_params(model))
    return (sum(attention_params(model, l) for l in range(model["n_layer"]))
            + sum(3 * d * model["d_ff"] if kind == "dense" else routed
                  for kind in model["mlp_layer_types"])
            + 2 * model["vocab_size"] * d)


def cache_layers(model: dict) -> int:
    """Key and value layers a decode step walks: one a layer, of either
    kind."""
    return model["n_layer"]


def layers_of(model: dict, kind: str) -> int:
    return sum(t == kind for t in model["layer_types"])


def kv_row_bytes(model: dict, kv_dtype_bytes: int = 2) -> int:
    """One token's keys and values in one cache layer: ``G`` heads of ``D``
    each."""
    return 2 * model["n_kv_head"] * model["head_dim"] * kv_dtype_bytes


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """What one more cached token costs: a row in every full layer. A window
    layer's rows are its slot's, ``sliding_window`` of them whatever the
    length (``ring_bytes_per_slot``)."""
    return (layers_of(model, "full_attention")
            * kv_row_bytes(model, kv_dtype_bytes))


def ring_bytes_per_slot(model: dict, kv_dtype_bytes: int = 2) -> int:
    return (layers_of(model, "sliding_attention") * model["sliding_window"]
            * kv_row_bytes(model, kv_dtype_bytes))


def decode_step_bytes(model: dict, kv_rows_full: float,
                      kv_rows_window: float = None, active: int = None,
                      weight_dtype_bytes: int = 2,
                      kv_dtype_bytes: int = 2) -> float:
    """What one decode step over the slot array has to read from HBM: every
    held matrix but the embedding table and the experts once, of the experts
    the share ``active`` tokens touch (``1 - (1 - k/E)^active`` of each
    layer's, all of them where ``active`` is None), and the rows of keys and
    values: ``kv_rows_full`` (the live lengths' sum) in every full layer,
    ``kv_rows_window`` (the sum of ``min(length, sliding_window)``; None:
    the full rows, an upper bound) in every window layer."""
    d = model["d_model"]
    n_routed = sum(t == "sparse" for t in model["mlp_layer_types"])
    all_experts = n_routed * model["n_routed_experts"] * expert_params(model)
    touched = (1.0 if active is None else 1.0 - (
        1.0 - model["k"] / model["n_routed_experts"]) ** active)
    weights = (held_params(model) - model["vocab_size"] * d
               - (1.0 - touched) * all_experts)
    if kv_rows_window is None:
        kv_rows_window = kv_rows_full
    rows = (layers_of(model, "full_attention") * kv_rows_full
            + layers_of(model, "sliding_attention") * kv_rows_window)
    return (weights * weight_dtype_bytes
            + rows * kv_row_bytes(model, kv_dtype_bytes))
