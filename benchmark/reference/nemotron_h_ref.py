"""Plain reference forward for Nemotron-3-Nano-30B-A3B's layers (NVIDIA;
``https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json``,
the row ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` of the catalog beside the
``model-configs`` guide, ``model_type`` ``nemotron_h``): a layer is ONE
sublayer, a Mamba-2 mixer, a routed feed-forward or attention, by the
character of ``hybrid_pattern`` (the published ``hybrid_override_pattern``).
For a residual stream ``x`` [T, d], every layer

    x = x + f(RMSNorm(x))                    (eps ``rms_norm_eps``)

then ``RMSNorm_f(x) W_head^T``, head untied; no bias but the convolution's.
``h`` is the normed input.

``M``, the mixer, ``H = mamba_num_heads`` heads of ``P = mamba_head_dim``, a
state of ``N = ssm_state_size`` a head, ``G = n_groups`` groups of heads, a
convolution of ``K = conv_kernel`` taps:
    [z | xBC | dt] = h W_in                  (H P | H P + 2 G N | H)
    xBC_t <- silu(sum_k w_k xBC_{t - (K - 1) + k} + b)   causal, a channel at a
      time, rows before the sequence zero
    [x | B | C] = xBC                        (H P | G N | G N)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)         a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T            [P, N] a head, head
      i reading group i // (H / G), S before the sequence zero
    y_t = S_t C_t + D x_t
    y <- y * silu(z);  y <- y * rsqrt(mean over each of the G groups of
      H P / G channels of y^2 + eps) * scale
    f = y W_out
run here as that recurrence, a ``lax.scan`` over time, never in chunks.

``*``, attention: ``n_head`` query heads and ``n_kv_head`` key-value heads of
``head_dim``; ``q = h W_q``, ``[k | v] = h W_kv``; query head i reads key-value
head i // (n_head / n_kv_head); scores ``q . k / sqrt(head_dim)``, causal;
``f = concat(softmax(scores) v) W_o``. Nothing is rotated.

``E``, the routed feed-forward:
    s = sigmoid(h W_r)                       over all ``n_routed_experts``
    S = the ``k`` largest of s + bias        (``e_score_correction_bias``;
      ties to the lower index; ``n_group`` 1: no group limit)
    g_e = routed_scaling_factor * s_e / sum over S of s   (norm_topk_prob)
    f = sum over S of g_e relu(h U_e)^2 D_e + relu(h U_s)^2 D_s
experts of ``moe_d_ff`` and a shared expert of ``shared_d_ff``, no gate
matrix. No capacity: every token reaches its experts. **A share of the
experts is held** (``held_experts`` = [first, count], the deployment's chip):
the router keeps its full width, the sum runs over the held members of ``S``
only, and nothing stands in for what the other chip would add. The tree's
``experts_*`` leaves hold those ``count`` experts; ``vocab_size`` is the
chip's slice of the vocabulary, embedding rows and logits over it.

**Assumed** (the catalog's ``config`` holds no key that decides them; the
configuration file lists each with these grounds under ``assumed``):
    - no rotary in the attention layers: the config carries ``rope_theta``
      and ``partial_rotary_factor``, but ``nemotron_h``'s attention applies
      no position embedding; position comes from the mixers;
    - ``dt`` is not clamped above (``time_step_limit`` absent: (0, inf));
    - the gate before the norm, ``norm(y * silu(z))`` (Mamba-2's
      ``norm_before_gate`` false, what ``nemotron_h``'s gated norm does);
    - attention's scale is 1 / sqrt(head_dim) = 1 / sqrt(128);
    - the state and the convolution window are kept in float32 from token to
      token (the published ``residual_in_fp32`` false speaks of the stream);
    - the weights are seeded, not the published ones (the family's
      ``init_params`` and the configuration's ``assumed`` say how).
No file in this machine says otherwise; nothing else is built.

Float32 under ``jax.default_matmul_precision("highest")``, one sequence at a
time, no cache, no kernel, no chunked scan, no sort, no grouped product and
no function of the program. The weights arrive in the served type and are
upcast an expert at a time; attention goes a query head at a time.

**A choice can be handed over** (``benchmark/README.md``, the ``reference``
row; ``olmoe_ref`` says why). ``logits(..., choices={position: [n_layer,
k]})`` computes those positions with the experts named in place of ``S``;
the gates are this forward's own ``s_e`` of them over their sum, times the
scaling factor; an attention layer names nothing (-1 throughout); a mixer
layer's row holds no experts but the state its step left (below).
The same call returns the slack of what was handed, for each position and
layer: how far the weakest handed expert lies under the strongest expert
left out, **in the unit of ``s + bias``** (the quantity the choice is made
by: sigmoids in [0, 1] plus a bias of a few hundredths; not divided by a
spread, a sigmoid's scale is absolute), 0 where the set is this forward's
own.

``CHOICE_SLACK`` is the most a defensible choice may show: 0.01 of ``s +
bias``. ``tools/nemotron_drift.py`` reads it on a TPU v5e with this family's
own programs at the published widths (nine layers ``MEMEM*EME``, 64 of 128
experts held, bf16 weights, float32 stream, states, keys and values, the
router in float32 from the served activations): ``MEASURED`` below has the
rows, PERF.md section 6, PR 40, what they mean.

**What the comparison cannot hold by itself, and what the configuration does
about it.** The comparison hands over the experts of the COMPARED position
only. Here a routed layer's output is 0.45 to 1.45 times the stream it is
added to, and the mixer after it runs a convolution over the last four
positions: ONE other expert at position T - 1, T - 2 or T - 3 moves the
logits at T by 1.4 to 22% of their spread (this file's own forward, one
expert swapped: the tolerance is 1.25%), at T - 8 by 0.3%. So the served path
must not flip an expert NEAR the compared position, and a path that rounds
to bf16 anywhere before a router does, in 1 of 15 readings. The
configuration therefore keeps the served path at float32's own noise: a
float32 stream, every product's rows in two bf16 halves that keep 16 bits
of them (the experts' too: ``experts_two_pass``), float32 keys and values
(``attention_float32``); the served logits then lie 1e-5 of their spread from
this file's where no expert differs anywhere in the prompt, and 2e-4 to 6e-3
where one does far from the end (in two prompts of three BY THE TOOL AS IT
WAS, whose two sides read a router bias a bf16 rounding apart, ``MEASURED``;
the cell, whose sides read one tree, has shown none: this file's own
products, six bf16 passes on the chip, carry 4e-6 themselves, and the sixth
and seventh of 128 scores lie closer than that in one choice of two
thousand; a third bf16 piece, 24 bits, took the clean readings to 8e-7 and
the prompts with a flip from 20 of 30 to 15 of 30 at a sixth of the decode
step, and was not kept).
What is left is that one choice falling inside the convolution's reach of a
compared position: by the rates above about one reading in two hundred.

**How the recurrence is held: a mixer layer's row is its state's readings.**
With the taps of the convolution N(0, 0.02) like every matrix, ``x``, ``B``
and ``C`` are near 0.02 and ``S C`` is under a thousandth of ``D x``: no
logit shows a state kept in bf16 or decayed twice a step (``MEASURED``:
both read as the honest path does). Taps as a ``Conv1d`` starts them
(U(-1/2, 1/2)) make the state a third of ``y`` and "decays twice" reads
0.035 to 0.098; but then every served prompt carries its first flipped
expert on in the state for good and the honest path reads 0.021 in 1
reading of 60: what an honest flip does and what a wrong recurrence does
scale alike with the state's share, so no seeding between the two parts
them, and a state in bf16 (2**-9 an entry) cannot move a logit by 1.25% at
any share. So the taps stay as the configuration states them, and the
states are compared directly, through the one channel the comparison has
beside the logits: for a mixer layer the family's step hands over, in the
layer's row of ``k`` int32, ``k`` float32 readings (their bits) of the state
and the window the served step LEFT in the slot: ``k - 1`` sums of the state
[H, P, N] and one of the window [K - 1, C], each under a seeded pattern of
signs (``state_probes``, ``read_state``; such a sum is as large as the
array's root sum of squares, so an error an entry of relative size e moves
it by e of that). This forward reads its own recurrence the same way at the
same position, and the distance (``state_distance``: the largest
difference over the state's, or the window's, root sum of squares) is held
to ``STATE_TOL`` and reported in the slack's place: 0 within the limit,
``CHOICE_SLACK`` times distance over limit beyond it, so that the
comparison's line reads as it does where nothing differs and fails on
``CHOICE_SLACK`` where a state does. The family's step is the timed path
(``ssm_decode`` writing where the states lie), after the engine's own
prefill and, for the second compared position, eight of the engine's own
decode steps: what is read is what those programs left.
``STATE_TOL["exact"]`` holds a mixer no routed layer precedes (layer 0):
its input is the embedding row, the served path's to the bit, so its state
is this file's to float32 rounding whatever the experts do. A mixer after a
routed layer takes in every expert the two sides chose apart at ANY earlier
position (only the compared position's are handed over), so its limit
(``"after routed"``) is loose: it tells a slot's state from another slot's,
from none and from one that lost a chunk, not bf16 from float32. All mixer
layers run one program and one kernel, the layer an index into one stack.
``MEASURED`` has both distances for every variant.

It reads the parameter tree below; a family's ``init_params`` makes it. A
stack holds the layers of one kind in the order the forward reaches them;
layer ``l`` is entry ``i`` of its stack, ``i`` the earlier layers of its
kind.

- ``wte`` [V, d], ``lm_head`` [V, d], ``lnf_scale`` [d];
- ``ssm_blocks``: ``ln1_scale`` [d]; ``ssm_in_w`` [d, H P + (H P + 2 G N) +
  H], columns in the order ``z | x | B | C | dt``; ``ssm_conv_w`` [K, H P +
  2 G N], tap ``k`` on the row ``K - 1 - k`` before the current one;
  ``ssm_conv_b``; ``ssm_dt_bias``, ``ssm_A_log``, ``ssm_D`` [H];
  ``ssm_norm_scale`` [H P]; ``ssm_out_w`` [H P, d];
- ``attn_blocks``: ``ln1_scale``; ``q_w`` [d, n_head D]; ``kv_w`` [d, 2
  n_kv_head D], the keys' columns then the values'; ``attn_out_w``;
- ``moe_blocks``: ``ln2_scale``; ``router_w`` [d, E]; ``router_bias`` [E];
  ``experts_up_w`` [held, d, f], ``experts_down_w`` [held, f, d], each as
  the chip lays it out: zeros may follow past ``d`` and past ``f``
  (``moe_d_ff``), and only the first ``d`` rows and columns are read here;
  ``shared_up_w`` [d, fs], ``shared_down_w`` [fs, d].

``model`` is the ``model`` group of a configuration file, in the names of
``KEYS``. Its counts (``lib/context.Context.count`` prefers them to
``lib/flops``'s): ``cache_layers``, ``kv_bytes_per_token``,
``state_bytes_per_slot``, ``decode_step_bytes``; and ``mixer_params``,
``attention_params``, ``expert_params``, ``held_params``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHOICE_SLACK = 0.01
# the most a slot's state and window may lie from this file's recurrence
# (``state_distance``): in a mixer no routed layer precedes, whose input is
# the served path's to the bit (the honest path reads 3.8e-5 there, states
# and windows in bf16 5.4e-3 and more: ``MEASURED``; the limit lies a factor
# of ten from the one and thirteen from the other), and in one that a routed
# layer precedes, whose input carries every expert the two sides chose apart
# (1.2e-4 where none did, up to 0.36 where one did three positions back;
# another slot's state or none reads 1 and more)
STATE_TOL = {"exact": 4e-4, "after routed": 0.75}

# what tools/nemotron_drift.py read on the chip (my chip runs, PR 40): the
# largest of each number over the readings, the largest slack of the experts,
# the choices of the decoded positions that differ from this file's own. The
# first block is the tool as it is: both sides on one tree cast to the served
# type, the comparison's own step, the engine's own decode program between,
# the distances of the first mixer's and of the later mixers' states and
# windows beside (STATE_TOL; every decoded position handed, 27 a seed). The
# second block was read before the review, when the tool handed this file
# the tree as drawn while the engine serves it cast to bf16: router bias,
# A_log and dt_bias then differed by a bf16 rounding between the sides, so
# its rows show more flipped choices than the cell meets (15 runs of the
# cell before the review, 120 comparisons, read at most 1.1e-5 with no
# expert apart at a compared position, as the runs since do); its faults
# fail by margins that do not hang on that.
MEASURED = """
variant                      readings  rms     max     flipped    state, first mixer  later mixers
honest                              6  1.1e-5  1.1e-5  0 of 243   3.8e-5              1.2e-4
states and windows in bf16          6  0.0025  0.0026  (states)   5.4e-3 to 9.2e-3    1.1e-2 to 1.5e-2
a decode step decays twice          6  0.0003  0.0003  (states)   0.36 to 0.56        0.72 to 0.86
(limits 0.0125, 0.02; STATE_TOL 4e-4 and 0.75: the two faults fail by the
first mixer's state alone, 13 and 900 times over, and by no logit)

before the review: the tree handed uncast, the states in no reading
variant                      readings  rms     max     slack   flipped
honest, ten seeds                  60  0.0056  0.0056  0.0010  6 of 2430
router in bf16                     12  0.0331  0.0328  0.0010  3 of 486
stream in bf16                     12  0.1166  0.1145  0.0280  11 of 486
chosen without the bias            12  0.2006  0.2119  0.1120  151 of 486
gates not renormalised             12  1.0852  1.1748  0.7649  162 of 486
no scaling factor                  12  0.4538  0.5203  0.2825  155 of 486
relu for relu squared              12  0.7156  0.7813  0.4558  161 of 486
(limits 0.0125, 0.02, CHOICE_SLACK 0.01)
"""

KEYS = ("vocab_size", "n_layer", "d_model", "hybrid_pattern", "n_head",
        "n_kv_head", "head_dim", "mamba_num_heads", "mamba_head_dim",
        "ssm_state_size", "n_groups", "conv_kernel", "rms_norm_eps",
        "n_routed_experts", "held_experts", "k", "moe_d_ff", "shared_d_ff",
        "routed_scaling_factor")
COVERS = {"norm_topk_prob": True, "tie_embeddings": False,
          "scoring_func": "sigmoid", "mlp_hidden_act": "relu2",
          "n_group": 1, "rotary": False}
STACKS = {"M": "ssm_blocks", "E": "moe_blocks", "*": "attn_blocks"}


def _check(model: dict) -> None:
    missing = [key for key in KEYS if key not in model]
    have = {key: model.get(key) for key in COVERS}
    if missing or have != COVERS:
        raise ValueError(f"nemotron_h_ref reads {KEYS} and covers {COVERS}; "
                         f"the configuration lacks {missing} and says {have}")
    first, count = model["held_experts"]
    if (len(model["hybrid_pattern"]) != model["n_layer"]
            or set(model["hybrid_pattern"]) - set(STACKS)
            or model["mamba_num_heads"] % model["n_groups"]
            or model["n_head"] % model["n_kv_head"]
            or first < 0 or count < 1
            or first + count > model["n_routed_experts"]):
        raise ValueError(
            "nemotron_h_ref: hybrid_pattern a character of M, E, * a layer, "
            "heads in whole groups, held_experts inside the router's width: "
            f"got {model['hybrid_pattern']!r} for {model['n_layer']} layers, "
            f"held {model['held_experts']}")


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(gain)


def place(model: dict, layer: int):
    """(stack name, index inside it) of layer ``layer`` in the tree."""
    kind = model["hybrid_pattern"][layer]
    return STACKS[kind], model["hybrid_pattern"][:layer].count(kind)


# ------------------------------------------------------------------ layers
def state_probes(model: dict):
    """The patterns of signs a slot's state and window are read through
    (``STATE_TOL``): ``k - 1`` for the state, each the outer product of a
    sign a head, a sign a row and a sign a column, and one for the window, a
    sign a row times a sign a channel; seeded, the same for every layer. The
    sum of an array under such a pattern has the array's own root sum of
    squares as its expected size, whatever the array."""
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, K = model["ssm_state_size"], model["conv_kernel"]
    rng = np.random.default_rng(0x57A7E)

    def signs(*shape):
        return (2.0 * rng.integers(0, 2, shape) - 1.0).astype(np.float32)

    n = model["k"] - 1
    return {"head": signs(n, H), "row": signs(n, P), "column": signs(n, N),
            "age": signs(K - 1), "channel": signs(conv_width(model))}


def read_state(probes, state, window):
    """The ``k`` readings of one slot's ``state`` [H, P, N] and ``window``
    [K - 1, C] (oldest row first) under ``probes``, float32 [k]: what a
    family's step hands over for a mixer layer, and what :func:`mixer` reads
    of its own recurrence."""
    exact = jax.lax.Precision.HIGHEST   # a served step's default is bf16
    of_state = jnp.einsum("hpn,rh,rp,rn->r", state, probes["head"],
                          probes["row"], probes["column"], precision=exact)
    of_window = jnp.einsum("kc,k,c->", window, probes["age"],
                           probes["channel"], precision=exact)
    return jnp.concatenate([of_state, of_window[None]])


def mixer(model: dict, h, w, probes):
    """The Mamba-2 mixer of the normalised input ``h`` [T, d], a token at a
    time: its output [T, d], and of the state and the window each token
    leaves behind the readings [T, k] (:func:`read_state`) and the root sums
    of squares [T, 2] they are measured by."""
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, G, K = model["ssm_state_size"], model["n_groups"], model["conv_kernel"]
    inner, gn = H * P, G * N
    t = h.shape[0]
    proj = h @ _f32(w["ssm_in_w"])
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

    _, (y, readings, sizes) = jax.lax.scan(
        token, jnp.zeros((H, P, N)), (x, b, c, dt, windows))
    y = y + _f32(w["ssm_D"])[None, :, None] * x
    y = y.reshape(t, inner) * jax.nn.silu(z)
    grouped = y.reshape(t, G, inner // G)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + model["rms_norm_eps"])
    return ((grouped.reshape(t, inner) * _f32(w["ssm_norm_scale"])) @ _f32(
        w["ssm_out_w"]), readings, sizes)


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


def attention(model: dict, h, w):
    """Attention of the normalised input ``h`` [T, d]: nothing rotated."""
    t = h.shape[0]
    heads, g, d = model["n_head"], model["n_kv_head"], model["head_dim"]
    q = (h @ _f32(w["q_w"])).reshape(t, heads, d)
    kv = (h @ _f32(w["kv_w"])).reshape(t, 2, g, d)
    k, v = kv[:, 0], kv[:, 1]
    at = jnp.arange(t)
    seen = at[None, :] <= at[:, None]

    def head(a):        # a query head at a time
        qh, i = a
        group = i // (heads // g)
        kh = jax.lax.dynamic_index_in_dim(k, group, 1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, group, 1, keepdims=False)
        scores = (qh @ kh.T) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                              axis=-1) @ vh

    out = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.arange(heads)))
    return jnp.moveaxis(out, 0, 1).reshape(t, -1) @ _f32(w["attn_out_w"])


def relu2_mlp(h, up, down):
    return jnp.square(jax.nn.relu(h @ _f32(up))) @ _f32(down)


def choice_slack(biased, member):
    """The slack [T] of the sets ``member`` [T, E] under the biased scores
    ``biased`` = ``s + bias``: how far the weakest expert taken lies under
    the strongest left out, in their own unit."""
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


def experts(model: dict, h, w, gates):
    """``sum_e gates[:, e] * FFN_e(h)`` over the HELD experts, one at a
    time; what the other experts would add is left out."""
    first, count = model["held_experts"]

    d = h.shape[1]

    def one(y, e):      # the layout's zeros past d stay out of the products
        up, down, g = e
        return y + g[:, None] * relu2_mlp(h, up[:d], down[:, :d]), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (w["experts_up_w"], w["experts_down_w"],
         gates[:, first:first + count].T))
    return y


def block(model: dict, layer: int, x, w, handed, use, probes):
    """Layer ``layer``: the stream, the layer's own experts [T, k] (-1 from
    an attention layer) and the slack [T] of what was handed; from a mixer
    layer its own state readings, float32 in int32's bits, and in the
    slack's place the distance [T] of the readings handed
    (:func:`state_distance`; 0 in rows handed nothing)."""
    t = x.shape[0]
    kind = model["hybrid_pattern"][layer]
    none = (jnp.full((t, model["k"]), -1, jnp.int32),
            jnp.zeros((t,), jnp.float32))
    eps = model["rms_norm_eps"]
    if kind == "M":
        y, readings, sizes = mixer(model, rms_norm(x, w["ln1_scale"], eps),
                                   w, probes)
        return (x + y, jax.lax.bitcast_convert_type(readings, jnp.int32),
                jnp.where(use, state_distance(handed, readings, sizes), 0.0))
    if kind == "*":
        return (x + attention(model, rms_norm(x, w["ln1_scale"], eps), w),
                ) + none
    h = rms_norm(x, w["ln2_scale"], eps)
    gates, own, slack = route(model, h, w, handed, use)
    y = experts(model, h, w, gates) + relu2_mlp(h, w["shared_up_w"],
                                               w["shared_down_w"])
    return x + y, own.astype(jnp.int32), slack


def _frozen(v):
    return tuple(_frozen(x) for x in v) if isinstance(v, list) else v


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block_at(model_items, layer, x, stack, at, handed, use, probes):
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False),
        stack)
    return block(dict(model_items), layer, x, w, handed, use, probes)


@functools.partial(jax.jit, static_argnums=(0,))
def _head(eps, params, x):
    return rms_norm(x, params["lnf_scale"], eps) @ _f32(params["lm_head"]).T


def _handed(model: dict, t: int, choices):
    """``choices`` ({position: [n_layer, k] experts} or None) as the arrays
    the layers take: experts [n_layer, T, k] and which rows use them [T]. An
    attention layer names nothing (-1); a mixer layer's row is its state's
    readings."""
    n_layer, k = model["n_layer"], model["k"]
    handed = np.zeros((n_layer, t, k), np.int32)
    use = np.zeros(t, bool)
    for pos, sets in (choices or {}).items():
        sets = np.asarray(sets)
        if not 0 <= pos < t or sets.shape != (n_layer, k):
            raise ValueError(f"choices at position {pos} of {t}: shape "
                             f"{sets.shape}, wanted {(n_layer, k)}")
        for l, row in enumerate(sets.tolist()):
            if model["hybrid_pattern"][l] == "M":
                if not np.isfinite(np.asarray(row, np.int32).view(
                        np.float32)).all():
                    raise ValueError(
                        f"choices at position {pos}, layer {l}, a mixer: "
                        f"the row holds the {k} readings of the slot's "
                        f"state and window (read_state), float32 in "
                        f"int32's bits; these bits are not finite: {row}")
            elif model["hybrid_pattern"][l] != "E":
                if set(row) != {-1}:
                    raise ValueError(
                        f"choices at position {pos} name experts in layer "
                        f"{l}, which does not route: {row} (its row is -1 "
                        "throughout)")
            elif (len(set(row)) != k or min(row) < 0
                  or max(row) >= model["n_routed_experts"]):
                raise ValueError(
                    f"choices at position {pos}, layer {l}: {k} different "
                    f"experts of {model['n_routed_experts']}, got {row}")
        handed[:, pos], use[pos] = sets, True
    return handed, use


def state_slack(model: dict, distances):
    """The mixer layers' columns of ``distances`` [T, n_layer] in the
    slack's terms: 0 where a layer's distance is within its limit
    (``STATE_TOL``: nothing differs), else ``CHOICE_SLACK`` times the
    distance over the limit, which is over ``CHOICE_SLACK``."""
    out = np.array(distances, np.float32)
    pattern = model["hybrid_pattern"]
    for l, kind in enumerate(pattern):
        if kind == "M":
            limit = STATE_TOL["after routed" if "E" in pattern[:l]
                              else "exact"]
            out[:, l] = np.where(out[:, l] <= limit, 0.0,
                                 CHOICE_SLACK * out[:, l] / limit)
    return out


def forward(model: dict, params, ids, choices=None, distances=False):
    """One sequence ``ids`` [T] through the layers: the residual stream
    [T, d] after the last, this forward's own experts [T, n_layer, k] (-1 in
    an attention layer, its own state readings in a mixer layer: what a
    served step would hand over) and the slack of ``choices`` [T, n_layer]
    (0 in rows that were handed nothing, and in an attention layer; a mixer
    layer's by :func:`state_slack` or, with ``distances``, the distance
    itself)."""
    _check(model)
    items = tuple(sorted((k, _frozen(model[k]))
                         for k in KEYS + tuple(COVERS)))
    ids = jnp.asarray(ids, jnp.int32)
    handed, use = _handed(model, ids.shape[0], choices)
    probes = state_probes(model)
    own, slack = [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"][ids])
        for layer in range(model["n_layer"]):
            name, at = place(model, layer)
            x, o, s = _block_at(items, layer, x, params[name], jnp.int32(at),
                                handed[layer], use, probes)
            own.append(o)
            slack.append(s)
    slack = np.stack([np.asarray(s) for s in slack], axis=1)
    return (x, jnp.stack(own, axis=1),
            slack if distances else state_slack(model, slack))


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
    layer that does not route -1), and the one forward that uses them then
    also judges them: the value is (logits, {position: slack [n_layer]})."""
    x, _, slack = forward(model, params, ids, choices)
    out = head_logits(model, params, x, positions)
    if choices is None:
        return out
    slack = np.asarray(slack)
    return out, {pos: slack[pos] for pos in choices}


# ------------------------------------------------------------------ counts
def layers_of(model: dict, kind: str) -> int:
    return model["hybrid_pattern"].count(kind)


def conv_width(model: dict) -> int:
    return (model["mamba_num_heads"] * model["mamba_head_dim"]
            + 2 * model["n_groups"] * model["ssm_state_size"])


def mixer_params(model: dict) -> int:
    """One ``M`` layer: the in-projection, the convolution and its bias,
    ``dt_bias``, ``A_log``, ``D``, the gated norm, the out-projection."""
    d, H = model["d_model"], model["mamba_num_heads"]
    inner, cw = H * model["mamba_head_dim"], conv_width(model)
    return (d * (inner + cw + H) + cw * (model["conv_kernel"] + 1) + 3 * H
            + inner + inner * d)


def attention_params(model: dict) -> int:
    d, dh = model["d_model"], model["head_dim"]
    return (d * model["n_head"] * dh + 2 * d * model["n_kv_head"] * dh
            + model["n_head"] * dh * d)


def expert_params(model: dict) -> int:
    return 2 * model["d_model"] * model["moe_d_ff"]


def routed_params(model: dict) -> int:
    """One ``E`` layer as held: router and bias, shared expert, the held
    experts."""
    d, E = model["d_model"], model["n_routed_experts"]
    return (d * E + E + 2 * d * model["shared_d_ff"]
            + model["held_experts"][1] * expert_params(model))


def held_params(model: dict) -> int:
    """Weights the tree holds, the norms' gains included."""
    d = model["d_model"]
    return (layers_of(model, "M") * (mixer_params(model) + d)
            + layers_of(model, "*") * (attention_params(model) + d)
            + layers_of(model, "E") * (routed_params(model) + d)
            + 2 * model["vocab_size"] * d + d)


def cache_layers(model: dict) -> int:
    """Key and value layers a decode step walks: the ``*`` layers."""
    return layers_of(model, "*")


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = None) -> int:
    """What one more cached token costs: a key and a value row in every
    ``*`` layer, bf16 or, where the configuration keeps them so
    (``attention_float32``), float32. A mixer's state is its slot's
    (:func:`state_bytes_per_slot`)."""
    if kv_dtype_bytes is None:
        kv_dtype_bytes = 4 if model.get("attention_float32") else 2
    return (cache_layers(model) * 2 * model["n_kv_head"] * model["head_dim"]
            * kv_dtype_bytes)


def state_bytes_per_slot(model: dict) -> int:
    """The ``M`` layers' states and convolution windows of one sequence,
    float32, whatever its length."""
    state = (model["mamba_num_heads"] * model["mamba_head_dim"]
             * model["ssm_state_size"])
    window = (model["conv_kernel"] - 1) * conv_width(model)
    return layers_of(model, "M") * 4 * (state + window)


def decode_step_bytes(model: dict, live_kv_tokens: float,
                      state_slots: float = 0, active: int = None,
                      weight_dtype_bytes: int = 2,
                      kv_dtype_bytes: int = None) -> float:
    """What one decode step over the slot array has to move through HBM:
    every held matrix but the embedding table and the experts once, of the
    held experts the share ``active`` tokens touch (``1 - (1 - k/E)^active``
    of each layer's, all of them where ``active`` is None), the live rows of
    keys and values in the ``*`` layers, and the states and windows of
    ``state_slots`` slots, read AND written."""
    d = model["d_model"]
    all_experts = (layers_of(model, "E") * model["held_experts"][1]
                   * expert_params(model))
    touched = (1.0 if active is None else 1.0 - (
        1.0 - model["k"] / model["n_routed_experts"]) ** active)
    weights = (held_params(model) - model["vocab_size"] * d
               - (1.0 - touched) * all_experts)
    return (weights * weight_dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(model, kv_dtype_bytes)
            + 2.0 * state_slots * state_bytes_per_slot(model))
