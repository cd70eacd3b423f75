"""Plain reference forward for Brumby-14B-Base's layers (Manifest AI;
``https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json``,
the row ``Brumby-14B-Base`` of the catalog beside the ``model-configs`` guide,
``model_type`` ``brumby``): the Qwen3 dense block with **power retention**
(Buckman, Gelada, Zhang, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239; the ``retention`` package's ``power_retention``) in place of
softmax attention in EVERY layer. For a residual stream ``x`` [T, d], no bias
anywhere:

    h = RMSNorm_in(x)                            (eps ``rms_norm_eps``)
    q = h W_q (H heads of D);  k = h W_k;  v = h W_v (G heads of D each)
    q, k <- RMSNorm_D(q), RMSNorm_D(k) a head (gains ``w_q``, ``w_k`` shared
            by the heads), then rotated (rotate-half over the whole head,
            base ``rope_theta``)
    log g_t = logsigmoid(h_t W_g + gate_offset) a key-value head;
    A_t = sum_{s <= t} log g_s
    query head i reads key-value head j = i // (H / G); for s <= t
        w[t, s] = (q_t . k_s)^2 exp(A_t - A_s)
    o_t = sum_s w[t, s] v_s / sum_s w[t, s]
    x <- x + concat(o) W_o
    f = RMSNorm_ff(x);  x <- x + (up(f) * silu(gate(f))) W_down
    logits = RMSNorm_f(x) W_head^T                          head untied

run here as exactly that **quadratic form**, a sequence at a time, a query
head at a time, in blocks of rows: no state, no feature map, no chunk. The
program keeps, a key-value head, the state ``S_t = sum_s exp(A_t - A_s)
phi(k_s) v_s^T`` and the normaliser ``z_t = sum_s exp(A_t - A_s) phi(k_s)``
through a map ``phi`` with ``phi(a) . phi(b) = (a . b)^2``; this file never
builds ``phi``, so it shares no function and no feature map with the program.

**Assumed** (the catalog's ``config`` is Qwen3's keys under ``model_type:
brumby`` and has no key for the retention's degree, gate or normaliser; the
configuration file lists each with these grounds under ``assumed``):
    - degree 2, the paper's and the package's default;
    - a gate a key-value head through a bias-free projection and
      ``logsigmoid``, so that one state serves a key-value head's ``H / G``
      queries (the state is a function of ``k``, ``v`` and the gate alone);
      the token's own gate is applied before its own write (``w[t, t] = (q_t .
      k_t)^2``);
    - the normaliser ``z`` and no epsilon: every ``w`` is non-negative, and
      ``w[t, t]`` is positive wherever ``q_t . k_t`` is not 0;
    - QK-norm and the rotation as Qwen3 has them (the config keeps
      ``rope_theta`` 1e6 and ``head_dim`` 128, no key removes them); no scale
      on ``q . k`` (any factor cancels in the quotient);
    - state and normaliser float32 from token to token; the package's switch
      from a key-value cache to the state at a length of its choosing is an
      inference detail with the same mathematics and is not built;
    - the weights are seeded, not the published ones: ``gate_offset`` is a
      constant of the seeded draw (0 for trained weights), set so that ``g``
      lies in about 0.97 to 0.999 and the state remembers hundreds of tokens.

Float32 under ``jax.default_matmul_precision("highest")``, one sequence at a
time, no cache and no kernel. The weights arrive in the served type and are
upcast a piece at a time (the MLP's matrices in column blocks, the head in
blocks of the vocabulary), the scores a query head and ``ROW_BLOCK`` rows at a
time: beside a served tree and states that fill the chip the reference keeps
about a gigabyte of its own at a prompt of 8,192.

**How the state is held: a layer's row is its state's readings.** The
comparison (``lib/correct.py``) holds two logits a sequence. No logit shows
the PRECISION of a state, and a gate applied after the write instead of
before it changes every term of the state by its own ``g_s`` and cancels in
the quotient. So the states are held directly, through the one channel the
comparison has beside the logits, as ``falcon_h1_ref``'s and
``kimi_linear_ref``'s are: this file defines ``CHOICE_SLACK`` and the family's
``paged_decode_step`` returns, third, for every layer ``READINGS`` float32
readings (their bits, int32 ``[slots, n_layer, k]``) of the state the served
step LEFT in the slot: for seeded probe vectors ``a_j`` [D], signs ``e_j`` a
key-value head and ``u_j`` [D + 1],

    r_j = sum_g e_jg  phi(a_j)^T [S_g | z_g] u_j

which this file computes from its own ``k``, ``v`` and ``A`` as ``sum_g e_jg
sum_s exp(A_T - A_s) (a_j . k_s)^2 ([v_s | 1] . u_j)``, again without
``phi``. The distance (``state_distance``: the largest difference over the
root sum of squares of the reading's terms) is held to ``STATE_TOL`` and
reported in the slack's place: 0 within the limit, ``CHOICE_SLACK`` times
distance over limit beyond it. The model has no router: the word "experts" in
the comparison's line stands for these readings. ``MEASURED`` has the readings
the limits are set from.

It reads the parameter tree below; a family's ``init_params`` makes it.
- ``wte`` [V, d], ``lm_head`` [V, d], ``lnf_scale`` [d];
- ``blocks``, every leaf stacked over the layers: ``ln1_scale``,
  ``ln2_scale`` [d]; ``retention_q_w`` [d, H D]; ``retention_kv_w`` [d, 2 G D],
  the keys' columns then the values'; ``retention_gate_w`` [d, G];
  ``retention_q_norm_scale``, ``retention_k_norm_scale`` [D];
  ``retention_out_w`` [H D, d]; ``mlp_gate_w``, ``mlp_up_w`` [d, f];
  ``mlp_down_w`` [f, d].

``model`` is the ``model`` group of a configuration file, in the names of
``KEYS``. Its counts (``lib/context.Context.count`` prefers them to
``lib/flops``'s): ``cache_layers``, ``kv_bytes_per_token``,
``state_bytes_per_slot``, ``decode_step_bytes``; and ``layer_params``,
``held_params``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the slack a state's distance is reported as (module docstring): the model
# chooses no expert, the limit is only the unit ``STATE_TOL`` is turned into
CHOICE_SLACK = 0.01
# readings a layer hands over, a probe vector each
READINGS = 6
# query rows the scores are taken over at once
ROW_BLOCK = 1024
# the most a slot's state may lie from this file's own sums
# (``state_distance``), in every layer: the honest path reads at most 9.7e-5
# over the seeds of ``MEASURED`` (a float32 stream, float32 states and products
# at full precision: nothing between the embedding row and the state rounds to
# bf16 but the weights, which both sides share, so every layer is held as
# tightly as the first), the smallest planted fault 8.2e-3 in its best layer
# (states rounded to bf16 after every decode step): the limit lies ten times
# over the one and eight times under the other
STATE_TOL = 1e-3

# what tools/brumby_drift.py read on the chip (my chip runs, PR 58): the two
# compared logits a prompt (after the prefill, after 8 decodes) and the largest
# distance of a layer's state over the nine decoded positions, a layer at a
# time. PERF.md section 6, PR 58, has the table.
MEASURED = """
variant (seed 1, prompt 2048)          rms       max       state, by layer (1-5)
honest                                 1.8e-5    1.9e-5    7.0e-6  8.0e-5  3.9e-5  5.7e-5  4.6e-5
states in bf16 after every step        9.9e-3    1.2e-2    8.2e-3  1.4e-2  1.1e-2  1.7e-2  1.4e-2
the prompt's normaliser left out       0.654     0.691     7.0e-6  5.2     17.6    37.6    46.8
the gate after the write               0.103     0.100     4.0e-2  0.30    0.27    0.40    0.48
QK-norm left out                       0.298     0.339     0.90    1.52    1.96    1.49    1.21
the rotation left out                  1.222     1.243     1.55    3.14    2.35    1.04    1.39
a query head reads another state       1.377     1.452     7.0e-6  2.29    2.93    3.52    3.52
(limits 0.0125, 0.02; STATE_TOL 1e-3. A state in bf16 fails by the states
alone, in every layer, and by no logit; the gate after the write by the states
and, eight times over, by the logits; everything else by both, 30 times over
and more. Layer 1's state is a function of k, v and the gate alone: a lost
normaliser or a query's wrong head leave it as it was. The honest row over
seeds 2300000053 and 2 to 6, prompts of 2,048 and 8,192, 24 readings: rms and
max at most 3.7e-5, a layer's state 1.3e-5 to 9.7e-5, the largest in layer 4
at 8,192.)
"""

KEYS = ("vocab_size", "n_layer", "d_model", "d_ff", "n_head", "n_kv_head",
        "head_dim", "rope_theta", "rms_norm_eps", "gate_offset")
COVERS = {"tie_embeddings": False, "hidden_act": "silu",
          "retention_degree": 2}


def _check(model: dict) -> None:
    missing = [key for key in KEYS if key not in model]
    have = {key: model.get(key) for key in COVERS}
    if missing or have != COVERS:
        raise ValueError(f"brumby_ref reads {KEYS} and covers {COVERS}; the "
                         f"configuration lacks {missing} and says {have}")
    if model["n_head"] % model["n_kv_head"] or model["head_dim"] % 2:
        raise ValueError("brumby_ref: query heads in whole groups a "
                         "key-value head, an even head_dim")


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(gain)


def place(model: dict, layer: int):
    """(stack name, index inside it) of layer ``layer`` in the tree."""
    return "blocks", layer


# ------------------------------------------------------------------ layers
def state_probes(model: dict):
    """What a slot's state is read through (``STATE_TOL``), seeded, the same
    for every layer: ``READINGS`` probe vectors ``a`` [k, D] of unit length,
    a sign a key-value head ``head`` [k, G] and signs over ``[v | 1]``,
    ``value`` [k, D + 1]."""
    G, D = model["n_kv_head"], model["head_dim"]
    rng = np.random.default_rng(0xB2B1)

    def signs(*shape):
        return (2.0 * rng.integers(0, 2, shape) - 1.0).astype(np.float32)

    a = rng.standard_normal((READINGS, D)).astype(np.float32)
    return {"a": a / np.linalg.norm(a, axis=1, keepdims=True),
            "head": signs(READINGS, G), "value": signs(READINGS, D + 1)}


def rotate(model: dict, x):
    """``x`` [T, heads, D] rotated by position, rotate-half over the whole
    head: dimension ``i`` pairs with ``i + D / 2``."""
    half = model["head_dim"] // 2
    freq = 1.0 / (float(model["rope_theta"])
                  ** (jnp.arange(half, dtype=jnp.float32) / half))
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mixer(model: dict, h, w, probes, read_at):
    """Power retention of the normalised input ``h`` [T, d] as the quadratic
    form: the sublayer's output [T, d]; at each position of ``read_at`` [R]
    the readings [R, k] of the state that position leaves behind (module
    docstring) and the root sums of squares [R, k] they are measured by."""
    t = h.shape[0]
    H, G, D = model["n_head"], model["n_kv_head"], model["head_dim"]
    eps = model["rms_norm_eps"]
    q = rms_norm((h @ _f32(w["retention_q_w"])).reshape(t, H, D),
                 w["retention_q_norm_scale"], eps)
    kv = (h @ _f32(w["retention_kv_w"])).reshape(t, 2, G, D)
    k = rotate(model, rms_norm(kv[:, 0], w["retention_k_norm_scale"], eps))
    q, v = rotate(model, q), kv[:, 1]
    log_g = jax.nn.log_sigmoid(h @ _f32(w["retention_gate_w"])
                               + model["gate_offset"])
    age = jnp.cumsum(log_g, axis=0)                             # A [T, G]

    rows = min(t, ROW_BLOCK)
    n_blocks = -(-t // rows)
    at = jnp.arange(t)

    def block(arg):     # a query head's rows of one block
        i, b = arg
        j = i // (H // G)
        kj, vj, aj = k[:, j], v[:, j], age[:, j]
        first = jnp.minimum(b * rows, t - rows)     # the last block overlaps
        qb = jax.lax.dynamic_slice(q, (first, i, 0), (rows, 1, D))[:, 0]
        ab = jax.lax.dynamic_slice(aj, (first,), (rows,))
        seen = at[None, :] <= (first + jnp.arange(rows))[:, None]
        weight = (qb @ kj.T) ** 2 * jnp.exp(
            jnp.where(seen, ab[:, None] - aj[None, :], -jnp.inf))
        return (weight @ vj) / weight.sum(axis=1, keepdims=True), first

    heads, blocks = jnp.meshgrid(jnp.arange(H), jnp.arange(n_blocks),
                                 indexing="ij")
    out, firsts = jax.lax.map(block, (heads.reshape(-1), blocks.reshape(-1)))
    out = out.reshape(H, n_blocks, rows, D)
    o = jnp.zeros((H, t, D), jnp.float32)
    for b in range(n_blocks):       # in order: an overlapping last block
        first = min(b * rows, t - rows)     # writes the same rows again
        o = o.at[:, first:first + rows].set(out[:, b])
    delta = jnp.moveaxis(o, 0, 1).reshape(t, H * D) @ _f32(
        w["retention_out_w"])

    # the readings: the probes as queries that are neither normed nor
    # rotated, the values [v | 1] under their signs, no quotient
    a_r = age[read_at]                                          # [R, G]
    seen = at[None, :] <= read_at[:, None]                      # [R, T]
    decay = jnp.exp(jnp.where(seen[:, :, None],
                              a_r[:, None, :] - age[None, :, :], -jnp.inf))
    hit = jnp.einsum("jd,sgd->jsg", probes["a"], k) ** 2       # [k, T, G]
    value = (jnp.einsum("sgd,jd->jsg", v, probes["value"][:, :D])
             + probes["value"][:, None, D:])
    terms = (decay[:, None] * hit[None] * value[None]
             * probes["head"][None, :, None, :])                # [R, k, T, G]
    return (delta, terms.sum(axis=(2, 3)),
            jnp.sqrt((terms * terms).sum(axis=(2, 3))))


def state_distance(handed, readings, sizes):
    """How far the readings ``handed`` [R, k] (float32 in int32's bits) lie
    from this forward's own [R, k]: the largest difference over the root sum
    of squares of that reading's terms, [R]."""
    got = jax.lax.bitcast_convert_type(handed, jnp.float32)
    tiny = jnp.finfo(jnp.float32).tiny
    return (jnp.abs(got - readings) / (sizes + tiny)).max(axis=1)


def _pieces(n: int, most: int = 8) -> int:
    """Pieces a side of ``n`` is taken in: a matrix of the published sizes is
    upcast a block of columns at a time."""
    return next(p for p in range(most, 0, -1) if n % p == 0) if n >= 1024 \
        else 1


def mlp(model: dict, f, stack, at):
    """The gated MLP of the normalised input ``f`` [T, d] with layer ``at``'s
    matrices of the stacked leaves ``stack``, a block of the middle's
    columns at a time."""
    d, width = model["d_model"], model["d_ff"]
    n = _pieces(width)
    wide = width // n

    def piece(j, y):
        gate, up = (_f32(jax.lax.dynamic_slice(
            stack[name], (at, 0, j * wide), (1, d, wide))[0])
            for name in ("mlp_gate_w", "mlp_up_w"))
        down = _f32(jax.lax.dynamic_slice(
            stack["mlp_down_w"], (at, j * wide, 0), (1, wide, d))[0])
        return y + ((f @ up) * jax.nn.silu(f @ gate)) @ down

    return jax.lax.fori_loop(0, n, piece, jnp.zeros_like(f))


def block(model: dict, x, stack, at, handed, read_at, probes):
    """One layer: the stream; its own state readings at ``read_at`` [R, k],
    float32 in int32's bits; and the distance [R] of the readings
    ``handed`` [R, k] there (:func:`state_distance`)."""
    eps = model["rms_norm_eps"]
    small = {k: v for k, v in stack.items() if not k.startswith("mlp_")}
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False),
        small)
    delta, readings, sizes = mixer(model, rms_norm(x, w["ln1_scale"], eps),
                                   w, probes, read_at)
    x = x + delta
    x = x + mlp(model, rms_norm(x, w["ln2_scale"], eps), stack, at)
    return (x, jax.lax.bitcast_convert_type(readings, jnp.int32),
            state_distance(handed, readings, sizes))


@functools.partial(jax.jit, static_argnums=(0,))
def _block_at(model_items, x, stack, at, handed, read_at, probes):
    return block(dict(model_items), x, stack, at, handed, read_at, probes)


@functools.partial(jax.jit, static_argnums=(0,))
def _head(eps, params, x):
    """The head in blocks of the vocabulary: beside a tree that fills the
    chip the whole matrix in float32 would not fit."""
    head = params["lm_head"]
    v, d = head.shape
    n = next(p for p in (16, 8, 4, 2, 1) if v % p == 0)
    normed = rms_norm(x, params["lnf_scale"], eps)
    out = jax.lax.map(lambda rows: normed @ _f32(rows).T,
                      head.reshape(n, v // n, d))            # [n, T, V / n]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


def _handed(model: dict, t: int, choices):
    """``choices`` ({position: [n_layer, k] readings} or None) as the arrays
    the layers take: the positions read [R] (the last one where nothing is
    handed), the readings there [n_layer, R, k], float32 in int32's bits, and
    which of those positions were handed any [R]."""
    n_layer = model["n_layer"]
    read_at = sorted(choices) if choices else [t - 1]
    handed = np.zeros((n_layer, len(read_at), READINGS), np.int32)
    for r, pos in enumerate(read_at if choices else ()):
        rows = np.asarray(choices[pos])
        if not 0 <= pos < t or rows.shape != (n_layer, READINGS):
            raise ValueError(f"choices at position {pos} of {t}: shape "
                             f"{rows.shape}, wanted {(n_layer, READINGS)}")
        if not np.isfinite(rows.astype(np.int32).view(np.float32)).all():
            raise ValueError(
                f"choices at position {pos}: a layer's row holds the "
                f"{READINGS} readings of the slot's state, float32 in "
                "int32's bits; these bits are not finite")
        handed[:, r] = rows
    return np.asarray(read_at, np.int32), handed, bool(choices)


def state_slack(distances):
    """``distances`` in the slack's terms: 0 where a layer's distance is
    within ``STATE_TOL``, else ``CHOICE_SLACK`` times the distance over the
    limit, which is over ``CHOICE_SLACK``."""
    out = np.array(distances, np.float32)
    return np.where(out <= STATE_TOL, 0.0, CHOICE_SLACK * out / STATE_TOL)


def embed(model: dict, params, ids):
    return _f32(params["wte"][jnp.asarray(ids, jnp.int32)])


def forward(model: dict, params, ids, choices=None, distances=False):
    """One sequence ``ids`` [T] through the layers: the residual stream
    [T, d] after the last; every layer's own state readings [R, n_layer, k]
    (what a served step would hand over) at the positions ``choices`` names,
    in rising order (the last position where it names none); and the slack of
    ``choices`` [R, n_layer] by :func:`state_slack` (0 where nothing was
    handed) or, with ``distances``, the distance itself."""
    _check(model)
    items = tuple(sorted((k, model[k]) for k in KEYS + tuple(COVERS)))
    read_at, handed, any_handed = _handed(model, len(ids), choices)
    probes = state_probes(model)
    own, apart = [], []
    with jax.default_matmul_precision("highest"):
        x = embed(model, params, ids)
        for layer in range(model["n_layer"]):
            x, o, s = _block_at(items, x, params["blocks"], jnp.int32(layer),
                                handed[layer], read_at, probes)
            own.append(o)
            apart.append(np.asarray(s) if any_handed
                         else np.zeros(len(read_at), np.float32))
    apart = np.stack(apart, axis=1)
    return (x, jnp.stack(own, axis=1),
            apart if distances else state_slack(apart))


def head_logits(model: dict, params, x, positions=None):
    """Final norm and head over the rows ``positions`` of the residual stream
    ``x`` [T, d]; all rows if None."""
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    with jax.default_matmul_precision("highest"):
        return _head(model["rms_norm_eps"], params, x)


def logits(model: dict, params, ids, positions=None, choices=None):
    """Logits [len(positions), V] of one sequence; all positions if None.
    ``choices`` maps a position to the readings [n_layer, k] of the states
    the served step left there, and the one forward then also judges them:
    the value is (logits, {position: slack [n_layer]})."""
    x, _, slack = forward(model, params, ids, choices)
    out = head_logits(model, params, x, positions)
    if choices is None:
        return out
    return out, dict(zip(sorted(choices), np.asarray(slack)))


# ------------------------------------------------------------------ counts
def features(model: dict) -> int:
    """Width of the symmetric second power of a key: the least any form of
    the algorithm keeps a key-value head (8,256 at a head of 128)."""
    return model["head_dim"] * (model["head_dim"] + 1) // 2


def mixer_params(model: dict) -> int:
    """A layer's mixer: q and o, k and v, the gate's projection, two head
    norms (62,955,776 at the published sizes)."""
    d, dh = model["d_model"], model["head_dim"]
    return (2 * d * model["n_head"] * dh + 2 * d * model["n_kv_head"] * dh
            + d * model["n_kv_head"] + 2 * dh)


def layer_params(model: dict) -> int:
    """One layer: mixer, the gated MLP, two norms."""
    d = model["d_model"]
    return mixer_params(model) + 3 * d * model["d_ff"] + 2 * d


def held_params(model: dict) -> int:
    """Weights the tree holds: the layers, embedding, head, final norm."""
    d = model["d_model"]
    return (model["n_layer"] * layer_params(model)
            + 2 * model["vocab_size"] * d + d)


def cache_layers(model: dict) -> int:
    """Key and value layers a decode step walks: no layer attends."""
    return 0


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """What one more cached token costs: nothing. A mixer's state is its
    slot's (:func:`state_bytes_per_slot`)."""
    return 0


def state_bytes_per_slot(model: dict) -> int:
    """The layers' states and normalisers of one sequence, float32, whatever
    its length: ``[features, head_dim + 1]`` a key-value head."""
    return (model["n_layer"] * model["n_kv_head"] * features(model)
            * (model["head_dim"] + 1) * 4)


def decode_step_bytes(model: dict, live_kv_tokens: float,
                      state_slots: float = 0, active: int = None,
                      weight_dtype_bytes: int = 2,
                      kv_dtype_bytes: int = 2) -> float:
    """What one decode step over the slot array has to move through HBM:
    every matrix but the embedding table once (the head with them) and the
    states of ``state_slots`` slots, read AND written. ``live_kv_tokens`` and
    ``active`` are taken and not used: no token caches a row, and no matrix
    is touched by a share of the tokens only."""
    weights = held_params(model) - model["vocab_size"] * model["d_model"]
    return (weights * weight_dtype_bytes
            + 2.0 * state_slots * state_bytes_per_slot(model))
