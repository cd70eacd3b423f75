"""Plain reference forward for a looped language model of Ouro's shape
(``https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json``, row 9 of
the catalog beside the ``model-configs`` guide: 48 layers, hidden 2048, 16
heads of 128 and as many key-value heads, SiLU-gated MLP of 5632, RMSNorm
1e-6, rotary base 1e6, vocabulary 49152 untied, ``total_ut_steps`` 4), here
before its model as ``olmoe_ref`` was: the program has no RMSNorm, gated MLP,
bias-free linear or loop over its layer stack yet (PERF.md, section 7). One
set of ``n_layer`` blocks is applied ``total_ut_steps`` times:

    x = E[ids]
    for u in 0 .. total_ut_steps - 1:              (one set of weights)
      for l in 0 .. n_layer - 1:
        h = RMSNorm(x; g1_l);  q, k, v = h Wq_l, h Wk_l, h Wv_l      no bias
        q, k rotated over all of a head's dimensions, rotate-half form
             (dimension i pairs with i + dh/2), base ``rope_theta``
        a = softmax(q k^T / sqrt(dh), causal) v Wo_l ;  x = x + RMSNorm(a; g2_l)
        h = RMSNorm(x; g3_l);  m = (silu(h Wg_l) * h Wu_l) Wd_l
        x = x + RMSNorm(m; g4_l)
      x = RMSNorm(x; gf)                            (the next loop reads this)
    logits = x Whead^T                              (after the last loop; untied)

with ``RMSNorm(x; g) = x * rsqrt(mean(x^2) + eps) * g``. A loop attends to
the keys and values of its own pass, so a served path caches a key and a
value row per loop and layer: cache layer ``n_layer * u + l``,
``cache_layers(model)`` of them (192), in the order the forward applies them;
keys are cached after the rotation. The exit gate (a d -> 1 linear on each
loop's closing state, ``exit_gate_w``, ``exit_gate_b``) is held as weights and
never fires: ``early_exit_threshold`` is 1, and anything else is refused.
``assumed``, because the catalog's ``config`` does not say them and there is
no network here: the two norms after the sublayers (``sandwich_norm``), the
closing norm between loops (``loop_norm``), no biases, no QK-norm,
rotate-half. A configuration of this reference lists them under ``assumed``
and states them in its ``model`` group (``COVERS``); it refuses what it does
not cover (``_check``).

Float32 under ``jax.default_matmul_precision("highest")``, one sequence at a
time, no kernel, no cache, no batching, no function of the program
(``gpt_ref.rotary`` and ``mean_nll`` are this directory's).

**It is segmented** (``benchmark/README.md``, the ``reference`` row;
``lib/correct.py`` says why). Four loops over one set of seeded weights do not
contract: what bf16 rounding left after a loop is doubled by the next, and an
honest bf16 path of this shape ends a quarter of the logits' spread away from
this forward (``tools/deep_drift.py``, table (i)), so no tolerance on the far
logits tells it from a wrong one. The forward is therefore cut into stretches
of at most ``SEGMENT_BLOCKS`` block applications, the depth ``lib/correct.py``'s
tolerances were calibrated at, and the comparison holds each stretch by
itself, from the served path's own states at its entry:

- ``segments(model)``: the stretches in order, ``(loop, first layer, one past
  the last)``; with 48 layers and 4 loops 8 of them, loop ``u`` layers 0-23 and
  24-47, the loop's closing norm inside the second;
- ``segment(model, params, k, states)``: from the entry states ``[T, d]`` of
  every position of one sequence, the exit states ``[T, d]`` and the key and
  value rows ``[blocks, H, T, Dh]`` those blocks would cache;
- ``embed`` and ``head_logits`` for the two ends; ``logits`` is the chain of
  its own segments (``tests/test_ouro_ref.py`` holds that to 1e-5).

``SEGMENT_TOL`` is the most an honest bf16 path may show on one stretch, at
any position: for the exit states and for the rows (a row is one token's keys,
or values, of one cache layer over all heads), the root-mean-square
difference over the reference's own root mean square of that row, and the
largest difference over its largest entry; the rows of a stretch's first block
have limits of their own (``first_row_*``), because they come from the served
entry states through one norm, one product and the rotation and carry none of
the stretch's drift. Measured by ``tools/deep_drift.py`` on a TPU v5e (my chip
runs, PR 31; PERF.md section 6 has the tables): a bf16 stand-in for a served
path of these equations at the published widths, 48 x 4, weights N(0, 0.02)
rounded to bf16, gains 1, through a paged pool; 18 seeds x prompts of 64, 128,
256, 512 and 9 decoded tokens, 576 stretches, every position of each (the
first block's rows on 12 of the seeds). Each limit is at most 1.3 times the
largest honest reading, the room ``lib/correct.LOGIT_RMS_TOL`` has:

    quantity         honest, median   largest    limit
    state_rms        0.0148           0.02213    0.0287
    state_max        0.0303           0.04685    0.0609
    first_row_rms    0.0029           0.00305    0.0039
    first_row_max    0.0057           0.00717    0.0091
    row_rms          0.0149           0.02225    0.0286
    row_max          0.0198           0.02858    0.0364

A seed's largest ``state_rms`` lies between 0.0195 and 0.0221, its largest
``first_row_rms`` between 0.00301 and 0.00305. Pages rounded to 8 bits (one
scale a head's row) read 0.0072 to 0.0081 on ``first_row_rms`` in every
stretch of every seed, 2.4 times the honest largest (the ratio is arithmetic:
a 127-step grid on a 128-wide row beside three bf16 roundings), and are not
told from the honest path by any other quantity; every block's output rounded
to 8 bits reads 0.063 on ``state_rms``.

The limit is calibrated on a stand-in, not on the program: the PR that brings
the family reads the honest rows again with its own step before its cell is
accepted, as ``olmoe_ref``'s ``CHOICE_SLACK`` asks of a routed family.

It reads the parameter tree below; a family's ``init_params`` makes it.

- ``wte`` [V, d], ``lm_head`` [V, d], ``lnf_scale`` [d] (``gf``),
  ``exit_gate_w`` [d, 1], ``exit_gate_b`` [1] (never read);
- ``blocks``: leaves stacked over the ``n_layer`` layers: ``ln1_scale`` (g1),
  ``post_attn_scale`` (g2), ``ln2_scale`` (g3), ``post_mlp_scale`` (g4) [d];
  ``qkv_w`` [d, 3d], columns q | k | v, each split into heads of
  ``d / n_head`` columns; ``attn_out_w`` [d, d]; ``mlp_gate_w``, ``mlp_up_w``
  [d, f]; ``mlp_down_w`` [f, d].

``model`` is the ``model`` group of a configuration file: ``vocab_size``,
``n_layer``, ``n_head``, ``d_model``, ``d_ff``, ``total_ut_steps``,
``rope_theta``, ``rms_norm_eps``, and the keys of ``COVERS``. The weights
arrive in the served type and are upcast a block at a time.

Its counts (``lib/context.Context.count`` prefers them to ``lib/flops``'s):
``cache_layers``, ``kv_bytes_per_token``, ``decode_step_bytes``,
``train_flops_per_token``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import gpt_ref as G

SEGMENT_BLOCKS = 24
SEGMENT_TOL = {"state_rms": 0.0287, "state_max": 0.0609,
               "first_row_rms": 0.0039, "first_row_max": 0.0091,
               "row_rms": 0.0286, "row_max": 0.0364}

COVERS = {"sandwich_norm": True, "loop_norm": True, "tie_embeddings": False,
          "early_exit_threshold": 1.0}
NOT_COVERED = ("attention_bias", "mlp_bias", "qk_norm", "rope_scaling",
               "sliding_window")


def _check(model: dict) -> None:
    have = {key: model.get(key) for key in COVERS}
    have.update({key: model[key] for key in NOT_COVERED if model.get(key)})
    if model.get("n_kv_head", model["n_head"]) != model["n_head"]:
        have["n_kv_head"] = model["n_kv_head"]
    if have != COVERS:
        raise ValueError(f"ouro_ref covers {COVERS}, as many key and value "
                         f"heads as query heads and none of {NOT_COVERED}; "
                         f"the configuration says {have}")


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * G._f32(gain)


def block(model: dict, x, w):
    """One block on the residual stream ``x`` [T, d]: the stream after it and
    the keys (rotated) and values it would cache, [H, T, Dh] each."""
    t, d = x.shape
    n_head, eps = model["n_head"], model["rms_norm_eps"]
    dh = d // n_head
    theta = float(model["rope_theta"])
    qkv = rms_norm(x, w["ln1_scale"], eps) @ G._f32(w["qkv_w"])
    q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(t, n_head, dh)
               for i in range(3))
    q, k = G.rotary(q, dh, theta), G.rotary(k, dh, theta)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hts,shd->thd", probs, v).reshape(t, d) \
        @ G._f32(w["attn_out_w"])
    x = x + rms_norm(a, w["post_attn_scale"], eps)
    h = rms_norm(x, w["ln2_scale"], eps)
    m = (jax.nn.silu(h @ G._f32(w["mlp_gate_w"]))
         * (h @ G._f32(w["mlp_up_w"]))) @ G._f32(w["mlp_down_w"])
    x = x + rms_norm(m, w["post_mlp_scale"], eps)
    return x, k.transpose(1, 0, 2), v.transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnums=(0,))
def _block_at(model_items, x, blocks, layer):
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        blocks)
    return block(dict(model_items), x, w)


@functools.partial(jax.jit, static_argnums=(0,))
def _close_loop(model_items, params, x):
    return rms_norm(x, params["lnf_scale"], dict(model_items)["rms_norm_eps"])


def segments(model: dict) -> list:
    """The stretches of the forward in order, ``(loop, first layer, one past
    the last)``, each at most ``SEGMENT_BLOCKS`` block applications; a loop's
    closing norm belongs to its last stretch."""
    n = model["n_layer"]
    return [(u, first, min(first + SEGMENT_BLOCKS, n))
            for u in range(model["total_ut_steps"])
            for first in range(0, n, SEGMENT_BLOCKS)]


def segment(model: dict, params, k: int, states):
    """Stretch ``k`` from its entry states [T, d] (any float type) of every
    position of one sequence: the exit states [T, d] and the key and value
    rows [blocks, H, T, Dh] its blocks would cache, float32."""
    _check(model)
    items = G._frozen(model)
    _, first, stop = segments(model)[k]
    keys, values = [], []
    with jax.default_matmul_precision("highest"):
        x = G._f32(jnp.asarray(states))
        for layer in range(first, stop):
            x, kk, vv = _block_at(items, x, params["blocks"], jnp.int32(layer))
            keys.append(kk)
            values.append(vv)
        if stop == model["n_layer"]:
            x = _close_loop(items, params, x)
    return x, jnp.stack(keys), jnp.stack(values)


def embed(model: dict, params, ids):
    """The entry states of the first stretch, [T, d]."""
    return G._f32(params["wte"])[jnp.asarray(ids, jnp.int32)]


def hidden(model: dict, params, ids):
    """The last stretch's exit states for one sequence ``ids`` [T]: the chain
    of ``segment`` from ``embed``."""
    x = embed(model, params, ids)
    for k in range(len(segments(model))):
        x, _, _ = segment(model, params, k, x)
    return x


@jax.jit
def _head(params, x):
    return x @ G._f32(params["lm_head"]).T


def head_logits(model: dict, params, x, positions=None):
    """The head over the rows ``positions`` of the last stretch's exit states
    ``x`` [T, d] (the last loop's closing norm is inside that stretch); all
    rows if None."""
    x = G._f32(jnp.asarray(x))
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    with jax.default_matmul_precision("highest"):
        return _head(params, x)


def logits(model: dict, params, ids, positions=None):
    """Logits [len(positions), V] of one sequence; all positions if None."""
    return head_logits(model, params, hidden(model, params, ids), positions)


def loss(model: dict, params, batch_ids) -> float:
    return G.mean_nll(lambda ids: logits(model, params, ids), batch_ids)


# ------------------------------------------------------------------ counts
def cache_layers(model: dict) -> int:
    """Key and value layers a decode step walks: one a loop and layer."""
    return model["total_ut_steps"] * model["n_layer"]


def block_params(model: dict) -> int:
    """Matrix weights of one block: q, k, v, o and the gated MLP's three."""
    d = model["d_model"]
    return 4 * d * d + 3 * d * model["d_ff"]


def total_params(model: dict) -> int:
    d = model["d_model"]
    return (model["n_layer"] * (block_params(model) + 4 * d)
            + 2 * model["vocab_size"] * d + d + d + 1)


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """Keys and values of one cached token over every loop and layer."""
    return 2 * cache_layers(model) * model["d_model"] * kv_dtype_bytes


def decode_step_bytes(model: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int = 2,
                      kv_dtype_bytes: int = 2) -> float:
    """What one decode step over the slot array has to read from HBM: the
    block weights once a loop (4.93 GB in bf16 at the published sizes, four
    times: nothing on the chip holds them between loops), the head once, and
    the live keys and values of every loop and layer. Norm gains, the exit
    gate, activations and the tokens' embedding rows are thousands of times
    smaller and left out."""
    weights = (model["total_ut_steps"] * model["n_layer"] * block_params(model)
               + model["vocab_size"] * model["d_model"])
    return (weights * weight_dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(model, kv_dtype_bytes))


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward, ``lib/flops``'s convention (6 a weight a token,
    12 L d T for attention over the full causal square, recomputation not
    counted), with every block applied ``total_ut_steps`` times."""
    applied = cache_layers(model)
    return (6.0 * (applied * block_params(model)
                   + model["vocab_size"] * model["d_model"])
            + 12.0 * applied * model["d_model"] * seq_len)
