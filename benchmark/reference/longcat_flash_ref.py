"""Plain reference forward for the language model of LongCat-Flash-Omni
(Meituan LongCat Team 2025, "LongCat-Flash Technical Report", section 2:
shortcut-connected MoE, zero-computation experts, MLA with scale correction;
``https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json``,
a row of the catalog beside the ``model-configs`` guide; the public
``modeling_longcat_flash.py`` for what the config does not say). All norms
are RMSNorm (eps 1e-5), no linear has a bias. One layer, stream ``x`` [T, d]:

    a0 = x  + MLA_0(norm_in0(x))
    h0 = norm_post0(a0)
    s  = MoE(h0)                       the shortcut: held, not yet added
    b0 = a0 + MLP_0(h0)
    a1 = b0 + MLA_1(norm_in1(b0))
    h1 = norm_post1(a1)
    y  = a1 + MLP_1(h1) + s            the routed branch lands here

then ``RMSNorm_f(x) W_head^T``, head untied. Two attention sub-blocks and two
dense MLPs a layer, each with its own weights; ONE routed branch, computed on
the first sub-block's normed state and added only after the second's MLP (a
deployment sends the tokens to their experts' chips while the first MLP, the
second attention and the second MLP run).

``MLP_j(h) = (silu(h Wg) * (h Wu)) Wd``, width ``d_ff``.

``MLA_j``, ``H`` heads (the two of a layer attend over their own keys):
    c_q = sqrt(d / q_lora_rank) RMSNorm(h W_qa)
    q   = c_q W_qb                            a head [q_nope | q_rope]
    [c_kv | k_rope] = h W_kva;  c = sqrt(d / kv_lora_rank) RMSNorm(c_kv)
    k_rope, one for all heads, and q_rope rotated at ``rope_theta`` (no
    ``rope_scaling`` in the row: no YaRN, no mscale)
    [k_nope | v] a head = c W_kvb
    scores = (q_nope . k_nope + q_rope . k_rope) (nope + rope)^-0.5, causal
    softmax, out = sum p v, concat(out) W_o
The two factors are the published ``mla_scale_q_lora`` / ``mla_scale_kv_lora``.
Rotate-half pairing (dimension i with i + rope/2): a permutation of the
published interleaved form that seeded weights do not tell apart (a
configuration lists it under ``assumed``). What a served path caches for a
token and attention sub-block is ``[c | rotated k_rope]``; it may absorb
``W_kvb`` into the query and the output. This forward never does: it expands
keys and values a head, and keeps no cache.

``MoE(h)``: the router scores ``n_routed_experts + zero_expert_num`` outputs,
the real experts first:
    p = softmax(h W_r)                        float32, over ALL outputs
    S = the ``k`` largest of p + b            (``e_score_correction_bias``;
                                              ties to the lower index)
    MoE(h) = sum over e in S, e <  n_routed_experts of f p_e FFN_e(h)
           + (sum over e in S, e >= n_routed_experts of f p_e) h
``f`` = ``routed_scaling_factor``; the gates are the probabilities WITHOUT
the bias, not renormalised; ``FFN_e`` a SiLU-gated MLP of ``moe_d_ff``; an
index at or past ``n_routed_experts`` is a zero-computation expert, the
identity (``zero_expert_type``). No shared expert, no groups, no capacity.

**A share of the layer.** ``held_experts`` = [first, count] says which REAL
experts' weights the parameter tree holds (a chip that is one of several
sharing each layer holds a range of them, and a share of the vocabulary's
rows). The router keeps its full width; every held expert is computed for
every token and weighted by its gate, which is 0 outside ``S``; what the
absent real experts would add is left out, as the served chip leaves it out.
The identity term needs no weights and no exchange: every chip applies it to
its own tokens, so it is computed whole here whatever the share, and a sum
over shares counts it ONCE. With all experts held this is the whole layer.

Float32 under ``jax.default_matmul_precision("highest")``, one sequence at a
time, no sort, no gather, no grouped product, no cache and no function of the
program. The weights arrive in the served type and are upcast a block at a
time: an expert, an attention matrix, ``BLOCK_COLS`` columns of a dense MLP
(whose three matrices in float32 would be 906 MB beside 14.4 GB resident).

**A choice can be handed over** (``benchmark/README.md``, the ``reference``
row; ``olmoe_ref`` says why). ``logits(..., choices={position: [n_layer,
k]})`` computes those positions with the outputs named in place of ``S``, the
gates staying this forward's own ``f p_e``; a zero-computation expert is a
choice like any other (an index up to ``n_routed_experts + zero_expert_num -
1``). The same call returns the slack of what was handed, for each position
and layer: how far the weakest output taken lies under the strongest left
out, by this forward's own ``p + b`` (the quantity the choice is made by),
**in the unit of the standard deviation of that token's** ``p + b`` over the
router's outputs; 0 where the set is this forward's own.

``CHOICE_SLACK`` is the most a defensible choice may show. Measured by
``tools/longcat_drift.py`` on a TPU v5e with this family's own programs (the
engine's prefill into latent pages, then teacher-forced decode steps through
them; bf16 weights, pages and stream, the router in float32 from the served
activations) at the published widths, 4 layers, experts 0-15 of 512 held,
weights N(0, sqrt(2 / (5 x 6144))) rounded to bf16, the bias N(0, 0.5 / 768);
``MEASURED`` has the rows (my chip runs, PR 63). The honest path picks
another set than this forward's own in 4-17% of the layer choices (12 of 768
lie close), by at most 0.046 of the unit over six seeds (and 0.045 over the
cell's own check); a router in bf16 reads the same, as ``olmoe_ref`` found;
the gates with the bias in them read 0.55, every other fault 6 or more. 0.12
(``olmoe_ref``'s and ``deepseek_v2_ref``'s) is 2.6 times the largest honest
reading and 4.6 times under the smallest faulty one.

It reads the parameter tree below; a family's ``init_params`` makes it.

- ``wte`` [V, d], ``lm_head`` [V, d], ``lnf_scale`` [d];
- ``moe_blocks``, leaves stacked over the layers. The first sub-block's:
  ``ln1_scale`` (norm_in), ``ln2_scale`` (norm_post) [L, d]; ``q_a_w`` [L, d,
  q_lora_rank], ``q_a_norm_scale``; ``q_b_w`` [L, q_lora_rank, H (nope +
  rope)]; ``kv_a_w`` [L, d, kv_lora_rank + rope], ``kv_a_norm_scale`` [L,
  kv_lora_rank]; ``kv_b_w`` [L, kv_lora_rank, H (nope + v)], a head's columns
  [k_nope | v]; ``attn_out_w`` [L, H v, d]; ``mlp_gate_w``, ``mlp_up_w`` [L,
  d, d_ff], ``mlp_down_w`` [L, d_ff, d]. The second sub-block's: the same
  under ``sub1_`` and the name (``sub1_ln1_scale``, ``sub1_q_a_w``, ...). The
  layer's own: ``router_w`` [L, d, E + Z], ``router_bias`` [L, E + Z];
  ``experts_gate_w``, ``experts_up_w`` [L, count, d, f], ``experts_down_w``
  [L, count, f, d], the held experts in order.

``model`` is the ``model`` group of a configuration file, in the names of
``KEYS``. Its counts (``lib/context.Context.count`` prefers them to
``lib/flops``'s): ``cache_layers``, ``kv_bytes_per_token``,
``decode_step_bytes``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHOICE_SLACK = 0.12

# tools/longcat_drift.py on a TPU v5e (my chip runs, PR 63), prompts of 256
# and 512, positions prompt .. prompt + 8 handed over, 4 layers each (72 layer
# choices a seed): the two logit numbers (largest over the four compared
# positions a seed; limits 0.0125 and 0.02), the largest slack, how many of
# the layer choices differ from this forward's own
MEASURED = """
variant                                    seeds  first    second   slack   differ
honest (bf16 stream, weights, pages)       6      0.0091   0.0106   0.046   4-17%
the stream in float32                      1      0.0035   0.0034   0.0036  3%
a router in bf16                           1      0.0089   0.0087   0.026   11%
the pool's rotated keys cut to 8 bits      1      0.0096   0.0089   0.047   14%
the pool a prompt left cut to 8 bits       2      0.0198   0.0218   0.180   32%
the stream cut to 8 bits between layers    2      0.0603   0.0644   0.360   53%
the bias in the gates                      1      0.0806   0.0792   0.555   49%
the branch lands after the first sub-block 1      0.689    0.700    6.0     78%
no scaling factor (float32 stream)         1      0.730    0.783    8.8     75%
no identity term (float32 stream)          1      0.883    0.925    11.2    75%
no rescale of the latents (float32 stream) 1      1.169    1.112    10.0    100%
one cache layer a layer (float32 stream)   1      1.192    1.217    10.9    100%
The three rows "cut to 8 bits" are the next precision below the configuration's
bf16 put in the program's place (3 bits of mantissa, float8_e4m3's): the pool
and the stream come out not correct by the first limit in every reading (the
least 0.0175 and 0.0540, for an honest most of 0.0091), the keys alone pass.
Under GPT-2's N(0, 0.02) instead, at this width: honest under a float32 stream
0.0150 / 0.0165 / 0.163, under a bf16 one 0.0339 / 0.0383 / 0.55 (one seed):
not correct, by eight sharp softmaxes in a row; the draw was chosen after that
reading, which is why the rows above stand beside it (PERF.md section 6, PR 63).
"""

KEYS = ("vocab_size", "n_layer", "n_head", "d_model", "d_ff", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rope_theta", "rms_norm_eps", "n_routed_experts", "zero_expert_num",
        "held_experts", "k", "moe_d_ff", "routed_scaling_factor")
COVERS = {"tie_embeddings": False, "zero_expert_type": "identity",
          "scoring_func": "softmax", "mla_scale_q_lora": True,
          "mla_scale_kv_lora": True, "router_bias": False,
          "attention_bias": False}
BLOCK_COLS = 2048   # columns of a dense MLP upcast at once


def _check(model: dict) -> None:
    missing = [key for key in KEYS if key not in model]
    have = {key: model.get(key) for key in COVERS}
    if missing or have != COVERS:
        raise ValueError(f"longcat_flash_ref reads {KEYS} and covers "
                         f"{COVERS}; the configuration lacks {missing} and "
                         f"says {have}")
    if "rope_scaling" in model and model["rope_scaling"] is not None:
        raise ValueError("longcat_flash_ref rotates at rope_theta alone: "
                         f"rope_scaling is {model['rope_scaling']!r}")
    first, count = model["held_experts"]
    if not (0 <= first and count >= 1
            and first + count <= model["n_routed_experts"]
            and model["zero_expert_num"] >= 0
            and 1 <= model["k"] <= outputs(model)):
        raise ValueError(f"held_experts {model['held_experts']} of "
                         f"{model['n_routed_experts']} real experts, "
                         f"{model['zero_expert_num']} zero-computation ones, "
                         f"{model['k']} a token")


def outputs(model: dict) -> int:
    """The router's width: the real experts, then the zero-computation ones."""
    return model["n_routed_experts"] + model["zero_expert_num"]


def _frozen(model: dict):
    return tuple(sorted((k, tuple(model[k]) if isinstance(model[k], list)
                         else model[k]) for k in KEYS + tuple(COVERS)))


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(gain)


# ------------------------------------------------------------------ rotary
def rotary(model: dict, x):
    """``x`` [T, heads, rope] rotated by position at ``rope_theta``,
    rotate-half pairing, no scaling."""
    half = x.shape[-1] // 2
    inv_freq = float(model["rope_theta"]) ** (
        -np.arange(half, dtype=np.float64) / half)
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# ------------------------------------------------------------------ layers
def mla(model: dict, h, w):
    """Latent attention of the normalised input ``h`` [T, d], un-absorbed;
    ``w`` one sub-block's leaves, by name."""
    t, d = h.shape
    heads, eps = model["n_head"], model["rms_norm_eps"]
    rank, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rope = model["qk_rope_head_dim"]
    c_q = rms_norm(h @ _f32(w["q_a_w"]), w["q_a_norm_scale"], eps) * (
        d / model["q_lora_rank"]) ** 0.5
    q = (c_q @ _f32(w["q_b_w"])).reshape(t, heads, -1)
    q_nope, q_rope = q[..., :nope], rotary(model, q[..., nope:])
    kv_a = h @ _f32(w["kv_a_w"])
    c_kv = rms_norm(kv_a[:, :rank], w["kv_a_norm_scale"], eps) * (
        d / rank) ** 0.5
    k_rope = rotary(model, kv_a[:, None, rank:])[:, 0]            # [T, rope]
    kv = (c_kv @ _f32(w["kv_b_w"])).reshape(t, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = (nope + rope) ** -0.5

    def head(a):        # a head at a time
        qn, qr, kn, vh = a
        scores = (qn @ kn.T + qr @ k_rope.T) * scale
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                              axis=-1) @ vh

    out = jax.lax.map(head, tuple(jnp.moveaxis(a, 1, 0) for a in
                                  (q_nope, q_rope, k_nope, v)))
    return jnp.moveaxis(out, 0, 1).reshape(t, -1) @ _f32(w["attn_out_w"])


def gated_mlp(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _at(a, *index):
    """``a[index]`` over its leading axes, an index traced or not: read where
    it lies, so that no layer's leaves are copied out of their stack."""
    for i in index:
        a = jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    return a


SUB = ("", "sub1_")     # what a sub-block's leaves' names begin with


def dense_mlp(h, blocks, layer, sub):
    """:func:`gated_mlp` of sub-block ``sub`` of layer ``layer``, the middle
    taken ``BLOCK_COLS`` columns at a time (the same sum in blocks: the
    matrices are sliced out of their stacks and upcast a block at once)."""
    f = blocks["mlp_gate_w"].shape[-1]
    cols = BLOCK_COLS if f % BLOCK_COLS == 0 else f

    def cut(name, j, axis):
        a = blocks[SUB[sub] + name]
        start = [layer, 0, 0]
        size = list((1,) + a.shape[1:])
        start[axis], size[axis] = j * cols, cols
        return jax.lax.dynamic_slice(a, start, size)[0]

    def one(y, j):
        return y + gated_mlp(h, cut("mlp_gate_w", j, 2), cut("mlp_up_w", j, 2),
                             cut("mlp_down_w", j, 1)), None

    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(f // cols))[0]


def choice_slack(biased, member):
    """The slack [T] of the sets ``member`` [T, E + Z] under the scores the
    choice is made by, ``biased`` = ``p + b``: how far the weakest output
    taken lies under the strongest left out, over the standard deviation of
    the token's ``biased``; 0 where it does not."""
    weakest = jnp.min(jnp.where(member, biased, jnp.inf), axis=1)
    strongest = jnp.max(jnp.where(member, -jnp.inf, biased), axis=1)
    gap = jnp.maximum(strongest - weakest, 0.0)
    return jnp.where(gap > 0, gap / jnp.std(biased, axis=1), 0.0)


def route(model: dict, h, router_w, router_bias, handed, use):
    """Gates [T, E + Z] (``routed_scaling_factor * p_e`` on each token's
    outputs, 0 elsewhere), this forward's own choice [T, k], and the slack
    [T] of ``handed`` [T, k], which takes the place of the own set in the
    rows where ``use`` [T] says so (0 in the other rows)."""
    p = jax.nn.softmax(h @ _f32(router_w), axis=-1)
    biased = p + _f32(router_bias)
    top = jnp.argsort(-biased, axis=1, stable=True)[:, :model["k"]]
    rows = jnp.arange(p.shape[0])[:, None]
    own = jnp.zeros(p.shape, bool).at[rows, top].set(True)
    given = jnp.zeros(p.shape, bool).at[rows, jnp.maximum(handed, 0)].set(
        True)
    member = jnp.where(use[:, None], given, own)
    slack = jnp.where(use, choice_slack(biased, member), 0.0)
    gates = jnp.where(member, p, 0.0) * model["routed_scaling_factor"]
    return gates, top, slack


def held_experts(model: dict, h, blocks, layer, gates):
    """``sum_e gates[:, e] * FFN_e(h)`` over the held REAL experts of layer
    ``layer``, one at a time; what the other real experts would add is left
    out."""
    first, count = model["held_experts"]

    def one(y, e):
        gate, up, down = (_at(blocks[f"experts_{name}_w"], layer, e)
                          for name in ("gate", "up", "down"))
        g = jax.lax.dynamic_index_in_dim(gates, first + e, 1, keepdims=False)
        return y + g[:, None] * gated_mlp(h, gate, up, down), None

    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(count))[0]


def zero_experts(model: dict, h, gates):
    """What the zero-computation experts give: ``h`` times the sum of their
    gates. Every token's, whatever share of the real experts is held."""
    return gates[:, model["n_routed_experts"]:].sum(axis=1)[:, None] * h


def moe(model: dict, h, blocks, layer, handed, use):
    """``MoE(h)`` of layer ``layer`` as held, this forward's own choice
    [T, k] and the slack [T] of what was handed."""
    gates, own, slack = route(model, h, _at(blocks["router_w"], layer),
                              _at(blocks["router_bias"], layer), handed, use)
    return (held_experts(model, h, blocks, layer, gates)
            + zero_experts(model, h, gates)), own, slack


class _Sub:
    """Sub-block ``sub`` of layer ``layer``: its leaves by name, each read
    where it lies in ``blocks``."""

    def __init__(self, blocks, layer, sub):
        self.blocks, self.layer, self.prefix = blocks, layer, SUB[sub]

    def __getitem__(self, name):
        return _at(self.blocks[self.prefix + name], self.layer)


def block(model: dict, x, blocks, layer, handed, use):
    """Layer ``layer`` of the stacks ``blocks``: the stream, the layer's own
    choice [T, k] and the slack [T] of what was handed."""
    eps = model["rms_norm_eps"]
    w0, w1 = _Sub(blocks, layer, 0), _Sub(blocks, layer, 1)
    a0 = x + mla(model, rms_norm(x, w0["ln1_scale"], eps), w0)
    h0 = rms_norm(a0, w0["ln2_scale"], eps)
    s, own, slack = moe(model, h0, blocks, layer, handed, use)
    b0 = a0 + dense_mlp(h0, blocks, layer, 0)
    a1 = b0 + mla(model, rms_norm(b0, w1["ln1_scale"], eps), w1)
    h1 = rms_norm(a1, w1["ln2_scale"], eps)
    y = a1 + dense_mlp(h1, blocks, layer, 1) + s
    return y, own, slack


@functools.partial(jax.jit, static_argnums=(0,))
def _block_at(model_items, x, blocks, layer, handed, use):
    return block(dict(model_items), x, blocks, layer, handed, use)


@functools.partial(jax.jit, static_argnums=(0,))
def _head(model_items, params, x):
    x = rms_norm(x, params["lnf_scale"], dict(model_items)["rms_norm_eps"])
    return x @ _f32(params["lm_head"]).T


def _handed(model: dict, t: int, choices):
    """``choices`` ({position: [n_layer, k] outputs} or None) as the arrays
    the layers take: outputs [n_layer, T, k] and which rows use them [T]."""
    n_layer, k = model["n_layer"], model["k"]
    handed = np.zeros((n_layer, t, k), np.int32)
    use = np.zeros(t, bool)
    for pos, sets in (choices or {}).items():
        sets = np.asarray(sets)
        if not 0 <= pos < t or sets.shape != (n_layer, k):
            raise ValueError(f"choices at position {pos} of {t}: shape "
                             f"{sets.shape}, wanted {(n_layer, k)}")
        distinct = all(len(set(row)) == k for row in sets.tolist())
        if not distinct or sets.min() < 0 or sets.max() >= outputs(model):
            raise ValueError(f"choices at position {pos}: every layer names "
                             f"{k} different outputs of {outputs(model)}, "
                             f"got {sets.tolist()}")
        handed[:, pos], use[pos] = sets, True
    return handed, use


def forward(model: dict, params, ids, choices=None):
    """One sequence ``ids`` [T] through the layers: the residual stream
    [T, d] after the last, this forward's own choices [T, n_layer, k] and the
    slack of ``choices`` [T, n_layer] (0 in rows that were handed nothing)."""
    _check(model)
    items = _frozen(model)
    ids = jnp.asarray(ids, jnp.int32)
    handed, use = _handed(model, ids.shape[0], choices)
    own, slack = [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"][ids])
        for layer in range(model["n_layer"]):
            x, o, s = _block_at(items, x, params["moe_blocks"],
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
    ``choices`` maps a position to the outputs [n_layer, k] to use there, and
    the one forward that uses them then also judges them: the value is
    (logits, {position: slack [n_layer]})."""
    x, _, slack = forward(model, params, ids, choices)
    out = head_logits(model, params, x, positions)
    if choices is None:
        return out
    slack = np.asarray(slack)
    return out, {pos: slack[pos] for pos in choices}


# ------------------------------------------------------------------ counts
def attention_params(model: dict) -> int:
    """One sub-block's latent-attention matrices."""
    d, h = model["d_model"], model["n_head"]
    qr, r = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    return (d * qr + qr * h * (nope + rope) + d * (r + rope)
            + r * h * (nope + v) + h * v * d)


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["moe_d_ff"]


def layer_params(model: dict) -> int:
    """One layer's matrices as held: two attention sub-blocks, two dense
    MLPs, the router over all its outputs, the held experts."""
    d = model["d_model"]
    return (2 * attention_params(model) + 2 * 3 * d * model["d_ff"]
            + d * outputs(model)
            + model["held_experts"][1] * expert_params(model))


def held_params(model: dict) -> int:
    """Matrix weights the tree holds: the layers, the embedding, the head."""
    return (model["n_layer"] * layer_params(model)
            + 2 * model["vocab_size"] * model["d_model"])


def cache_layers(model: dict) -> int:
    """Latent layers a decode step walks: two a layer, one an attention
    sub-block."""
    return 2 * model["n_layer"]


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """What one cached token needs over all cache layers: ``[c | k_rope]``
    each, no head axis. (A pool may pad the row to whole lanes; that is the
    pool's, not the algorithm's.)"""
    return (cache_layers(model)
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            * kv_dtype_bytes)


def decode_step_bytes(model: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int = 2,
                      kv_dtype_bytes: int = 2) -> float:
    """What one decode step over the slot array has to read from HBM: every
    held matrix but the embedding table once (a step reads the embedding's
    rows of its tokens, not the table; they, the norm gains, the bias and the
    activations are thousands of times smaller and left out), and the live
    latent rows. Every held expert: a token spares a real expert with 1 - k /
    (E + Z), n tokens with that to the n-th, so at 256 tokens and 12 of 768
    an expert goes untouched in 1.8% of the steps (0.984^256) and the count
    is 1.8% of the experts' bytes high."""
    weights = held_params(model) - model["vocab_size"] * model["d_model"]
    return (weights * weight_dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(model, kv_dtype_bytes))
