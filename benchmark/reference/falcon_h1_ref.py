"""Plain reference forward for Falcon-H1-34B-Instruct's layers (TII;
``https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json``,
the row ``Falcon-H1-34B-Instruct`` of the catalog beside the ``model-configs``
guide, ``model_type`` ``falcon_h1``): every layer runs attention AND a Mamba-2
mixer on the same normed input, side by side, sums their outputs into one
delta, then a dense gated MLP; a muP parametrisation multiplies twelve
activations by fixed scalars ``m_*``. For a residual stream ``x`` [T, d], no
bias but the convolution's:

    x_0   = m_emb E[id]
    h     = RMSNorm_in(x)                    (eps ``rms_norm_eps``)
    SSM:  u = m_ssm_in h ;  [z | xBC | dt] = (u W_in) * v
            (v: ``ssm_multipliers``, one a segment z, x, B, C, dt;
             H P | H P + 2 G N | H columns)
          xBC_t <- silu(sum_k w_k xBC_{t - (K - 1) + k} + b)   causal, a
            channel at a time, rows before the sequence zero
          [x | B | C] = xBC                  (H P | G N | G N)
          dt = softplus(dt + dt_bias) ;  A = -exp(A_log)        a head
          S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   [P, N] a head, head i
            reading group i // (H / G), S before the sequence zero
          y_t = S_t C_t + D x_t
          y <- y * silu(z) ;  y <- y * rsqrt(mean over each of the G groups
            of H P / G channels of y^2 + eps) * scale
          d_ssm = m_ssm_out (y W_out)
    ATT:  a = m_attn_in h ;  q = a W_q ;  k = m_key (a W_k) ;  v = a W_v
          q, k rotated (rotate-half over the whole head, base ``rope_theta``)
          ``n_head`` query heads over ``n_kv_head`` key-value heads of
          ``head_dim``, query head i reading i // (n_head / n_kv_head);
          scores q . k / sqrt(head_dim), causal
          d_att = m_attn_out (concat(softmax(scores) v) W_o)
    x <- x + d_ssm + d_att
    g = RMSNorm_ff(x) ;  x <- x + m_down ((up(g) * silu(m_gate gate(g))) W_down)
    logits = m_head (RMSNorm_f(x) W_head^T)              head untied

run here as that recurrence, a ``lax.scan`` over time, never in chunks.
``H = mamba_num_heads`` heads of ``P = mamba_head_dim`` (their product the
published ``mamba_d_ssm``, which is NOT ``mamba_expand`` x ``d_model``), a
state of ``N = ssm_state_size`` a head, ``G = n_groups`` groups of heads, a
convolution of ``K = conv_kernel`` taps.

**Assumed** (the catalog's ``config`` keys alone do not fix them; each place
is the family's published modelling code, ``modeling_falcon_h1.py``; the
configuration file lists each with these grounds under ``assumed``):
    - ``ssm_in_multiplier`` on the mixer's input BEFORE the in-projection,
      ``ssm_multipliers`` on the projection's OUTPUT segments (its
      ``mup_vector``), ``ssm_out_multiplier`` on the mixer's output;
    - ``attention_in_multiplier`` on the input of q, k and v,
      ``key_multiplier`` on the keys after their projection and BEFORE the
      rotation, ``attention_out_multiplier`` after the out-projection;
    - ``mlp_multipliers`` [0] on the gate's pre-activation, [1] after the
      down-projection; ``embedding_multiplier`` on the embedding rows,
      ``lm_head_multiplier`` on the logits;
    - the gate before the grouped norm, ``norm(y * silu(z))``
      (``mamba_rms_norm`` true, ``mamba_norm_before_gate`` false);
    - ``dt`` not clamped above (no ``time_step_limit`` key: (0, inf));
    - attention's scale 1 / sqrt(head_dim), the rotation over the whole head
      (no ``partial_rotary_factor`` key);
    - the state and the convolution window are kept in float32 from token to
      token;
    - the weights are seeded, not the published ones (the family's
      ``init_params`` and the configuration's ``assumed`` say how).
No file in this machine says otherwise; nothing else is built. This file
folds no multiplier into another or into a weight.

Float32 under ``jax.default_matmul_precision("highest")``, one sequence at a
time, no cache, no kernel, no chunked scan and no function of the program.
The weights arrive in the served type and are upcast a piece at a time (the
MLP's matrices in column blocks, the head in blocks of the vocabulary):
beside a served tree that fills the chip the reference keeps under half a
gigabyte of its own. Attention goes a query head at a time.

**How the recurrence is held: a layer's row is its state's readings.** The
comparison (``lib/correct.py``) holds two logits a sequence, after the
prefill and after eight decode steps. Under the seeded draw those logits show
both branches, every multiplier and the MLP (``tools/falcon_h1_drift.py``:
each left out fails by a logit tolerance), but no logit shows the PRECISION
of a state: a state rounded to bf16 after the prefill and after each of
eight steps carries 2**-9 an entry, nine times, and moves the logits by a
few parts in ten thousand whatever share of the output the state has (PR 40
found the same for Nemotron's mixers, ``reference/nemotron_h_ref.py``). So
the states are held directly, through the one channel the comparison has
beside the logits, as that reference's are: this file defines
``CHOICE_SLACK`` and the family's ``paged_decode_step`` returns, third, for
every layer ``k`` float32 readings (their bits, int32 ``[slots, n_layer,
k]``) of the state and the window the served step LEFT in the slot: ``k -
1`` sums of the state [H, P, N] and one of the window [K - 1, C], each under
a seeded pattern of signs (``state_probes``, ``read_state``; such a sum is
as large as the array's root sum of squares, so an error an entry of
relative size e moves it by e of that). This forward reads its own
recurrence the same way at the same position; the distance
(``state_distance``: the largest difference over the state's, or the
window's, root sum of squares) is held to ``STATE_TOL`` and reported in the
slack's place: 0 within the limit, ``CHOICE_SLACK`` times distance over
limit beyond it. The model has no router: no expert is chosen, the word
"experts" in the comparison's line stands for these readings, and nothing
flips. ``STATE_TOL["first"]`` holds layer 0, whose input is the embedding
row times ``m_emb``, the served path's to the bit: its state is this file's
to float32 rounding, and one kept in bf16 is not. ``"later"`` holds the
layers after it, whose input carries what bf16 pages and attention's bf16
products left in the stream (an honest state there lies 1 to 2% from this
file's, more than a bf16 state differs): it tells a slot's state from
another slot's, from none and from one that decayed twice or lost a chunk,
not bf16 from float32. All layers run one program and one kernel, the layer
an index into one stack. ``MEASURED`` has the readings the limits are set
from.

It reads the parameter tree below; a family's ``init_params`` makes it.
- ``wte`` [V, d], ``lm_head`` [V, d], ``lnf_scale`` [d];
- ``blocks``, every leaf stacked over the layers: ``ln1_scale``,
  ``ln2_scale`` [d]; ``ssm_in_w`` [d, H P + (H P + 2 G N) + H], columns in
  the order ``z | x | B | C | dt``; ``ssm_conv_w`` [K, H P + 2 G N], tap
  ``k`` on the row ``K - 1 - k`` before the current one; ``ssm_conv_b``;
  ``ssm_dt_bias``, ``ssm_A_log``, ``ssm_D`` [H]; ``ssm_norm_scale`` [H P];
  ``ssm_out_w`` [H P, d]; ``q_w`` [d, n_head D]; ``kv_w`` [d, 2 n_kv_head D],
  the keys' columns then the values'; ``attn_out_w`` [n_head D, d];
  ``mlp_gate_w``, ``mlp_up_w`` [d, f]; ``mlp_down_w`` [f, d].

``model`` is the ``model`` group of a configuration file, in the names of
``KEYS`` (the multipliers under the published config's own names). Its counts
(``lib/context.Context.count`` prefers them to ``lib/flops``'s):
``cache_layers``, ``kv_bytes_per_token``, ``state_bytes_per_slot``,
``decode_step_bytes``; and ``layer_params``, ``held_params``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# the slack a state's distance is reported as (module docstring): the model
# chooses no expert, the limit is only the unit ``STATE_TOL`` is turned into
CHOICE_SLACK = 0.01
# readings a layer hands over: three sums of the state, one of the window
READINGS = 4
# the most a slot's state and window may lie from this file's recurrence
# (``state_distance``): in layer 0, whose input is the served path's to the
# bit (the honest path reads 8.7e-6 to 5.5e-5 there, states and windows in
# bf16 5.8e-3 to 7.8e-3: ``MEASURED``; the limit lies a factor of seven from
# the one and fourteen from the other), and in the layers after it, whose
# input carries what bf16 pages and a prompt's one-pass scores left in the
# stream (the honest path 1.2e-2 to 3.6e-2, a bf16 state no more; a step that
# decays twice 1.5 to 2.6, another slot's state or none 1 and more)
STATE_TOL = {"first": 4e-4, "later": 0.2}

# what tools/falcon_h1_drift.py read on the chip (my chip runs, PR 47): the
# largest of each number over the readings of a variant (2 compared logits a
# prompt, prompts of 128, 256 and 512, the seeds of the run), and the largest
# distance of layer 0's and of the later layers' states and windows over the
# nine decoded positions. PERF.md section 6, PR 47, has the table.
MEASURED = """
variant                        readings  rms            max     state, layer 0      later layers
honest (seeds 1, 2, 3)               18  .0025-.0045    .0047   8.7e-6 to 5.5e-5    1.2e-2 to 3.6e-2
stream in bf16 (1, 2, 3)             18  .0205-.0222    .0248   5.5e-3 to 1.7e-2    7.0e-2 to 1.1e-1
linears' outputs rounded (1)          6  as the honest row to every digit: under a float32 stream the field is idle
states and windows in bf16 (1-3)     18  .0027-.0070    .0071   5.8e-3 to 7.8e-3    2.4e-2 to 3.7e-2
the ssm branch left out (1)           6  1.38-1.42      1.65    as honest           3.6 to 4.5
the attention branch left out (1)     6  0.65-0.94      0.97    as honest           2.4 to 7.1
a decode step decays twice (1)        6  0.15-0.58      0.58    0.72 to 1.6         1.5 to 2.6
multiplier key dropped (1)            6  1.15-1.23      1.45    as honest           4.1 to 6.2
multiplier ssm B dropped (1)          6  0.88-1.11      1.16    16 to 30            42 to 47
multiplier mlp gate dropped (1)       6  0.81-0.84      0.85    as honest           2.9 to 6.6
(limits 0.0125, 0.02; STATE_TOL 4e-4 and 0.2. A state in bf16 fails by layer
0's state alone, 14 times over, and by no logit; a bf16 stream by the logits
and by layer 0's state; everything else by the logits, 50 times over and more.
``ssm_decode`` at these shapes against the recurrence on inputs of order 1:
y 2.0e-5, state and window 0, idle slots and the other layer bit-equal.)
"""

KEYS = ("vocab_size", "n_layer", "d_model", "d_ff", "n_head", "n_kv_head",
        "head_dim", "rope_theta", "mamba_num_heads", "mamba_head_dim",
        "ssm_state_size", "n_groups", "conv_kernel", "rms_norm_eps",
        "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
        "mlp_multipliers")
COVERS = {"tie_embeddings": False, "hidden_act": "silu",
          "mamba_norm_before_gate": False, "mamba_rms_norm": True}


def _check(model: dict) -> None:
    missing = [key for key in KEYS if key not in model]
    have = {key: model.get(key) for key in COVERS}
    if missing or have != COVERS:
        raise ValueError(f"falcon_h1_ref reads {KEYS} and covers {COVERS}; "
                         f"the configuration lacks {missing} and says {have}")
    if (model["mamba_num_heads"] % model["n_groups"]
            or model["n_head"] % model["n_kv_head"] or model["head_dim"] % 2
            or len(model["ssm_multipliers"]) != 5
            or len(model["mlp_multipliers"]) != 2
            or model.get("hybrid_pattern",
                         "M" * model["n_layer"]) != "M" * model["n_layer"]):
        raise ValueError(
            "falcon_h1_ref: heads in whole groups, an even head_dim, five "
            "ssm_multipliers (z, x, B, C, dt), two mlp_multipliers (gate, "
            "down), a mixer in every layer "
            f"(hybrid_pattern {'M' * model['n_layer']!r})")


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(gain)


def place(model: dict, layer: int):
    """(stack name, index inside it) of layer ``layer`` in the tree."""
    return "blocks", layer


def conv_width(model: dict) -> int:
    return (model["mamba_num_heads"] * model["mamba_head_dim"]
            + 2 * model["n_groups"] * model["ssm_state_size"])


# ------------------------------------------------------------------ layers
def state_probes(model: dict):
    """The patterns of signs a slot's state and window are read through
    (``STATE_TOL``): ``READINGS - 1`` for the state, each the outer product of a
    sign a head, a sign a row and a sign a column, and one for the window, a
    sign a row times a sign a channel; seeded, the same for every layer. The
    sum of an array under such a pattern has the array's own root sum of
    squares as its expected size, whatever the array."""
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, K = model["ssm_state_size"], model["conv_kernel"]
    rng = np.random.default_rng(0xFA1C0)

    def signs(*shape):
        return (2.0 * rng.integers(0, 2, shape) - 1.0).astype(np.float32)

    n = READINGS - 1
    return {"head": signs(n, H), "row": signs(n, P), "column": signs(n, N),
            "age": signs(K - 1), "channel": signs(conv_width(model))}


def read_state(probes, state, window):
    """The ``k`` readings of one slot's ``state`` [H, P, N] and ``window``
    [K - 1, C] (oldest row first) under ``probes``, float32 [k]: what the
    family's step hands over for a layer, and what :func:`mixer` reads of
    its own recurrence."""
    exact = jax.lax.Precision.HIGHEST   # a served step's default is bf16
    of_state = jnp.einsum("hpn,rh,rp,rn->r", state, probes["head"],
                          probes["row"], probes["column"], precision=exact)
    of_window = jnp.einsum("kc,k,c->", window, probes["age"],
                           probes["channel"], precision=exact)
    return jnp.concatenate([of_state, of_window[None]])


def mixer(model: dict, h, w, probes):
    """The Mamba-2 mixer of the normalised input ``h`` [T, d], a token at a
    time: ``d_ssm`` [T, d]; of the state and the window each token leaves
    behind the readings [T, k] (:func:`read_state`) and the root sums of
    squares [T, 2] they are measured by; and the root mean square [T] of the
    state's part ``S C`` of ``y`` and of ``D x``."""
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, G, K = model["ssm_state_size"], model["n_groups"], model["conv_kernel"]
    inner, gn = H * P, G * N
    t = h.shape[0]
    by = np.repeat(np.asarray(model["ssm_multipliers"], np.float32),
                   (inner, inner, gn, gn, H))
    proj = ((model["ssm_in_multiplier"] * h) @ _f32(w["ssm_in_w"])) * by
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * gn],
                  proj[:, 2 * inner + 2 * gn:])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    # the window token t leaves: its own row last, K - 1 rows in all
    windows = jnp.stack([padded[1 + k:1 + k + t] for k in range(K - 1)],
                        axis=1)                                 # [T, K-1, C]
    taps = _f32(w["ssm_conv_w"])
    xbc = jax.nn.silu(
        sum(padded[k:k + t] * taps[k] for k in range(K))
        + _f32(w["ssm_conv_b"]))
    x = xbc[:, :inner].reshape(t, H, P)
    b = jnp.repeat(xbc[:, inner:inner + gn].reshape(t, G, N), H // G, axis=1)
    c = jnp.repeat(xbc[:, inner + gn:].reshape(t, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + _f32(w["ssm_dt_bias"]))              # [T, H]
    a = -jnp.exp(_f32(w["ssm_A_log"]))

    def token(s, now):
        x_t, b_t, c_t, dt_t, window = now
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        size = jnp.stack([jnp.sqrt(jnp.sum(s * s)),
                          jnp.sqrt(jnp.sum(window * window))])
        return s, (jnp.einsum("hpn,hn->hp", s, c_t),
                   read_state(probes, s, window), size)

    _, (sc, readings, sizes) = jax.lax.scan(
        token, jnp.zeros((H, P, N)), (x, b, c, dt, windows))
    dx = _f32(w["ssm_D"])[None, :, None] * x
    parts = jnp.stack([jnp.sqrt(jnp.mean(sc * sc, axis=(1, 2))),
                       jnp.sqrt(jnp.mean(dx * dx, axis=(1, 2)))], axis=1)
    y = (sc + dx).reshape(t, inner) * jax.nn.silu(z)
    grouped = y.reshape(t, G, inner // G)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + model["rms_norm_eps"])
    out = (grouped.reshape(t, inner) * _f32(w["ssm_norm_scale"])) @ _f32(
        w["ssm_out_w"])
    return model["ssm_out_multiplier"] * out, readings, sizes, parts


def state_distance(handed, readings, sizes):
    """How far the readings ``handed`` [T, k] (float32 in int32's bits) lie
    from this forward's own [T, k]: the largest difference of a state's
    reading over the state's root sum of squares, or the window's over the
    window's, whichever is larger, [T]."""
    got = jax.lax.bitcast_convert_type(handed, jnp.float32)
    apart = jnp.abs(got - readings)
    tiny = jnp.finfo(jnp.float32).tiny
    return jnp.maximum(apart[:, :-1].max(axis=1) / (sizes[:, 0] + tiny),
                       apart[:, -1] / (sizes[:, 1] + tiny))


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


def attention(model: dict, h, w):
    """Attention of the normalised input ``h`` [T, d]: ``d_att`` [T, d] and
    the standard deviation of the scores a query sees (over its causal
    row, the mean over heads and queries)."""
    t = h.shape[0]
    heads, g, d = model["n_head"], model["n_kv_head"], model["head_dim"]
    a = model["attention_in_multiplier"] * h
    q = rotate(model, (a @ _f32(w["q_w"])).reshape(t, heads, d))
    kv = (a @ _f32(w["kv_w"])).reshape(t, 2, g, d)
    k = rotate(model, model["key_multiplier"] * kv[:, 0])
    v = kv[:, 1]
    at = jnp.arange(t)
    seen = at[None, :] <= at[:, None]
    many = jnp.maximum(seen.sum(axis=1), 2)

    def head(arg):      # a query head at a time
        qh, i = arg
        group = i // (heads // g)
        kh = jax.lax.dynamic_index_in_dim(k, group, 1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, group, 1, keepdims=False)
        scores = (qh @ kh.T) / math.sqrt(d)
        mean = jnp.where(seen, scores, 0.0).sum(axis=1) / many
        spread = jnp.sqrt(jnp.where(seen, (scores - mean[:, None]) ** 2,
                                    0.0).sum(axis=1) / many)
        return (jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
                @ vh, spread[t // 2:].mean())

    out, spread = jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                                     jnp.arange(heads)))
    out = jnp.moveaxis(out, 0, 1).reshape(t, -1) @ _f32(w["attn_out_w"])
    return model["attention_out_multiplier"] * out, spread.mean()


def _pieces(n: int, most: int = 8) -> int:
    """Pieces a side of ``n`` is taken in: a matrix of the published sizes is
    upcast a block of columns at a time."""
    return next(p for p in range(most, 0, -1) if n % p == 0) if n >= 1024 \
        else 1


def mlp(model: dict, g, stack, at):
    """The gated MLP of the normalised input ``g`` [T, d] with layer ``at``'s
    matrices of the stacked leaves ``stack``, a block of the middle's
    columns at a time."""
    gate_m, down_m = model["mlp_multipliers"]
    d, f = model["d_model"], model["d_ff"]
    n = _pieces(f)
    wide = f // n

    def piece(j, y):
        cols = [_f32(jax.lax.dynamic_slice(
            stack[name], (at, 0, j * wide), (1, d, wide))[0])
            for name in ("mlp_gate_w", "mlp_up_w")]
        down = _f32(jax.lax.dynamic_slice(
            stack["mlp_down_w"], (at, j * wide, 0), (1, wide, d))[0])
        return y + ((g @ cols[1]) * jax.nn.silu(gate_m * (g @ cols[0]))
                    ) @ down

    return down_m * jax.lax.fori_loop(0, n, piece, jnp.zeros_like(g))


def block(model: dict, x, stack, at, handed, use, probes):
    """One layer: the stream; its own state readings [T, k], float32 in
    int32's bits; the distance [T] of the readings handed
    (:func:`state_distance`; 0 in rows handed nothing); and what the seeded
    draw is judged by, [7]: the scores' spread, the root mean squares of
    ``d_ssm``, ``d_att``, the MLP's delta and the layer's whole delta, and
    those of the state's part ``S C`` of ``y`` and of ``D x``
    (``tools/falcon_h1_drift.py`` prints the shares)."""
    eps = model["rms_norm_eps"]
    small = {k: v for k, v in stack.items() if not k.startswith("mlp_")}
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False),
        small)
    h = rms_norm(x, w["ln1_scale"], eps)
    d_ssm, readings, sizes, parts = mixer(model, h, w, probes)
    d_att, spread = attention(model, h, w)
    x = x + d_ssm + d_att
    d_mlp = mlp(model, rms_norm(x, w["ln2_scale"], eps), stack, at)

    def rms(a):
        return jnp.sqrt(jnp.mean(a * a))

    seen = jnp.stack([spread, rms(d_ssm), rms(d_att), rms(d_mlp),
                      rms(d_ssm + d_att + d_mlp),
                      parts[:, 0].mean(), parts[:, 1].mean()])
    return (x + d_mlp, jax.lax.bitcast_convert_type(readings, jnp.int32),
            jnp.where(use, state_distance(handed, readings, sizes), 0.0),
            seen)


def _frozen(v):
    return tuple(_frozen(x) for x in v) if isinstance(v, list) else v


@functools.partial(jax.jit, static_argnums=(0,))
def _block_at(model_items, x, stack, at, handed, use, probes):
    return block(dict(model_items), x, stack, at, handed, use, probes)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(eps, by, params, x):
    """The head in blocks of the vocabulary: beside a tree that fills the
    chip the whole matrix in float32 would not fit."""
    head = params["lm_head"]
    v, d = head.shape
    n = next(p for p in (16, 8, 4, 2, 1) if v % p == 0)
    normed = rms_norm(x, params["lnf_scale"], eps)
    out = jax.lax.map(lambda rows: normed @ _f32(rows).T,
                      head.reshape(n, v // n, d))            # [n, T, V / n]
    return by * jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


def _handed(model: dict, t: int, choices):
    """``choices`` ({position: [n_layer, k] readings} or None) as the arrays
    the layers take: readings [n_layer, T, k], float32 in int32's bits, and
    which rows were handed any [T]."""
    n_layer, k = model["n_layer"], READINGS
    handed = np.zeros((n_layer, t, k), np.int32)
    use = np.zeros(t, bool)
    for pos, rows in (choices or {}).items():
        rows = np.asarray(rows)
        if not 0 <= pos < t or rows.shape != (n_layer, k):
            raise ValueError(f"choices at position {pos} of {t}: shape "
                             f"{rows.shape}, wanted {(n_layer, k)}")
        if not np.isfinite(rows.astype(np.int32).view(np.float32)).all():
            raise ValueError(
                f"choices at position {pos}: a layer's row holds the {k} "
                f"readings of the slot's state and window (read_state), "
                f"float32 in int32's bits; these bits are not finite")
        handed[:, pos], use[pos] = rows, True
    return handed, use


def state_slack(model: dict, distances):
    """``distances`` [T, n_layer] in the slack's terms: 0 where a layer's
    distance is within its limit (``STATE_TOL``), else ``CHOICE_SLACK`` times
    the distance over the limit, which is over ``CHOICE_SLACK``."""
    out = np.array(distances, np.float32)
    for l in range(out.shape[1]):
        limit = STATE_TOL["later" if l else "first"]
        out[:, l] = np.where(out[:, l] <= limit, 0.0,
                             CHOICE_SLACK * out[:, l] / limit)
    return out


def embed(model: dict, params, ids):
    return model["embedding_multiplier"] * _f32(
        params["wte"][jnp.asarray(ids, jnp.int32)])


def forward(model: dict, params, ids, choices=None, distances=False,
            seen=False):
    """One sequence ``ids`` [T] through the layers: the residual stream
    [T, d] after the last, every layer's own state readings [T, n_layer, k]
    (what a served step would hand over) and the slack of ``choices`` [T,
    n_layer] by :func:`state_slack` (0 in rows that were handed nothing) or,
    with ``distances``, the distance itself. With ``seen``, fourth, what
    :func:`block` says of the seeded draw, [n_layer, 7]."""
    _check(model)
    items = tuple(sorted((k, _frozen(model[k]))
                         for k in KEYS + tuple(COVERS)))
    handed, use = _handed(model, len(ids), choices)
    probes = state_probes(model)
    own, apart, draw = [], [], []
    with jax.default_matmul_precision("highest"):
        x = embed(model, params, ids)
        for layer in range(model["n_layer"]):
            x, o, s, r = _block_at(items, x, params["blocks"],
                                   jnp.int32(layer), handed[layer], use,
                                   probes)
            own.append(o)
            apart.append(s)
            draw.append(r)
    apart = np.stack([np.asarray(s) for s in apart], axis=1)
    out = (x, jnp.stack(own, axis=1),
           apart if distances else state_slack(model, apart))
    return out + (np.stack([np.asarray(r) for r in draw]),) if seen else out


def head_logits(model: dict, params, x, positions=None):
    """Final norm and head over the rows ``positions`` of the residual stream
    ``x`` [T, d]; all rows if None."""
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    with jax.default_matmul_precision("highest"):
        return _head(model["rms_norm_eps"], model["lm_head_multiplier"],
                     params, x)


def logits(model: dict, params, ids, positions=None, choices=None):
    """Logits [len(positions), V] of one sequence; all positions if None.
    ``choices`` maps a position to the readings [n_layer, k] of the states
    the served step left there, and the one forward then also judges them:
    the value is (logits, {position: slack [n_layer]})."""
    x, _, slack = forward(model, params, ids, choices)
    out = head_logits(model, params, x, positions)
    if choices is None:
        return out
    slack = np.asarray(slack)
    return out, {pos: slack[pos] for pos in choices}


# ------------------------------------------------------------------ counts
def mixer_params(model: dict) -> int:
    """A layer's mixer: the in-projection, the convolution and its bias,
    ``dt_bias``, ``A_log``, ``D``, the gated norm, the out-projection."""
    d, H = model["d_model"], model["mamba_num_heads"]
    inner, cw = H * model["mamba_head_dim"], conv_width(model)
    return (d * (inner + cw + H) + cw * (model["conv_kernel"] + 1) + 3 * H
            + inner + inner * d)


def attention_params(model: dict) -> int:
    d, dh = model["d_model"], model["head_dim"]
    return (d * model["n_head"] * dh + 2 * d * model["n_kv_head"] * dh
            + model["n_head"] * dh * d)


def layer_params(model: dict) -> int:
    """One layer: mixer, attention, the gated MLP, two norms."""
    d = model["d_model"]
    return (mixer_params(model) + attention_params(model)
            + 3 * d * model["d_ff"] + 2 * d)


def held_params(model: dict) -> int:
    """Weights the tree holds: the layers, embedding, head, final norm."""
    d = model["d_model"]
    return (model["n_layer"] * layer_params(model)
            + 2 * model["vocab_size"] * d + d)


def cache_layers(model: dict) -> int:
    """Key and value layers a decode step walks: every layer attends."""
    return model["n_layer"]


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """What one more cached token costs: a key and a value row in every
    layer. A mixer's state is its slot's (:func:`state_bytes_per_slot`)."""
    return (cache_layers(model) * 2 * model["n_kv_head"] * model["head_dim"]
            * kv_dtype_bytes)


def state_bytes_per_slot(model: dict) -> int:
    """The layers' states and convolution windows of one sequence, float32,
    whatever its length."""
    state = (model["mamba_num_heads"] * model["mamba_head_dim"]
             * model["ssm_state_size"])
    window = (model["conv_kernel"] - 1) * conv_width(model)
    return model["n_layer"] * 4 * (state + window)


def decode_step_bytes(model: dict, live_kv_tokens: float,
                      state_slots: float = 0, active: int = None,
                      weight_dtype_bytes: int = 2,
                      kv_dtype_bytes: int = 2) -> float:
    """What one decode step over the slot array has to move through HBM:
    every matrix but the embedding table once (the head with them), the live
    rows of keys and values in every layer, and the states and windows of
    ``state_slots`` slots, read AND written. ``active`` is taken and not
    used: no matrix is touched by a share of the tokens only."""
    weights = held_params(model) - model["vocab_size"] * model["d_model"]
    return (weights * weight_dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(model, kv_dtype_bytes)
            + 2.0 * state_slots * state_bytes_per_slot(model))
