"""Power retention (Buckman, Gelada, Zhang, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239; the ``retention`` package's
``power_retention``) at degree 2: a normalised linear attention through a
feature map, the mixer of EVERY layer of a config that sets
``GPTConfig.retention``. It stands beside ``models/ssm.py`` and
``models/kda.py`` as the third state-keeping mixer (``gpt.state_mixer`` has
the contract): another recurrence, the same slot.
``benchmark/reference/brumby_ref.py`` has the equations as the quadratic form
over a whole sequence; here they are as the programs run them. For the normed
input ``h`` [.., T, d] of a layer with ``heads`` query heads over
``kv_heads`` key-value heads of ``head_dim`` (``H``, ``G``, ``D``):

    q = h W_q (H D);   [k | v] = h W_kv (2 G D);   gamma = h W_g (G)
    q, k <- RMSNorm_D(q), RMSNorm_D(k) a head (gains shared by the heads),
            then rotated by position over the whole head (the caller's
            ``rotate``: ``gpt._rope``, rotate-half)
    g = sigmoid(gamma + gate_offset)        a key-value head, float32
    S_t = g_t S_{t-1} + phi(k_t) v_t^T;     z_t = g_t z_{t-1} + phi(k_t)
    o_t = S_t^T phi(q_t) / (z_t . phi(q_t))   query head i reads key-value
                                              head i // (H / G)
    out = o W_out

``phi`` (:func:`phi`) is a map with ``phi(a) . phi(b) = (a . b)^2``: the
products ``a_i a_j`` by wrapped diagonal, ``phi[e, i] = c_e a_i a_{(i - e) mod
D}`` for ``e = 0 .. D / 2`` with ``c = (1, sqrt 2, .., sqrt 2, 1)``: a lane
roll and a product a diagonal, every diagonal ``D`` wide. The half diagonal
``e = D / 2`` holds each of its pairs twice at weight 1 where the symmetric map
holds it once at ``sqrt 2``, so this map is ``(D / 2 + 1) D`` wide (8,320 at
128) for the symmetric one's ``D (D + 1) / 2`` (8,256): 0.8% more, for rows of
whole lanes.

What a request carries from token to token is the **state a layer**, float32,
``[G, D / 2 + 2, D, D]``: block ``e <= D / 2`` is ``S`` of diagonal ``e`` laid
``[value channel, i]`` (a feature a lane: ``phi(k)`` and ``phi(q)`` then meet a
state row as they are made), and the last block's first ``D / 2 + 1`` rows are
``z``, a diagonal a row (``S`` with ``v`` extended by a 1, kept apart so that
no row is 129 wide). **No window**: the mixer has no convolution, its
``window_shape()`` is ``(0, 0)`` and the cache's second array
(``gpt.SSM_KEYS``) is empty. **It is told positions**: ``q`` and ``k`` are
rotated.

- :func:`mix_sequence`: ``T`` tokens a row from a given state, the chunked
  form: ``ops/pallas/retention_chunk`` on a TPU, :func:`scan_chunks` elsewhere.
  ``real`` [B]: only a row's first ``real`` tokens are real; a padded position
  has ``k`` 0 and ``g`` 1, which neither writes nor decays.
- :func:`mix_token`: one token a row, the recurrence, through
  ``ops/pallas/retention_decode`` over the stack of states where they lie.

Scopes (``profiling/trace.MODEL_SCOPES``): ``retention_in``,
``retention_scan`` or ``retention_update``, ``retention_out``; the caller
wraps them in ``retention``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .ssm import HIGHEST, _f32


@dataclasses.dataclass(frozen=True)
class RetentionMixer:
    """The mixer's sizes (``GPTConfig.retention``): a published config's
    ``num_attention_heads``, ``num_key_value_heads`` and ``head_dim``;
    ``chunk`` the prompt form's positions a chunk; ``gate_offset`` a constant
    added to the gate's logit (0 for trained weights; a seeded draw sets it so
    that the state remembers)."""
    heads: int
    kv_heads: int
    head_dim: int
    chunk: int = 128
    gate_offset: float = 0.0

    def __post_init__(self):
        if (min(self.heads, self.kv_heads, self.chunk) < 1
                or self.heads % self.kv_heads or self.head_dim < 2
                or self.head_dim % 2):
            raise ValueError(f"{self}: query heads in whole groups a "
                             "key-value head, an even head_dim")

    @property
    def group(self) -> int:
        """Query heads that read one key-value head's state."""
        return self.heads // self.kv_heads

    @property
    def diagonals(self) -> int:
        return self.head_dim // 2 + 1

    @property
    def features(self) -> int:
        """Width of :func:`phi`."""
        return self.diagonals * self.head_dim

    def state_shape(self) -> Tuple[int, int, int, int]:
        """A key-value head's state as it is kept: a block a diagonal [value
        channel, i], then the normaliser's block."""
        return (self.kv_heads, self.diagonals + 1, self.head_dim,
                self.head_dim)

    def window_shape(self) -> Tuple[int, int]:
        """No convolution: no window."""
        return (0, 0)

    def slot_bytes(self) -> int:
        """Float32 bytes one layer's state costs a sequence."""
        return 4 * math.prod(self.state_shape())

    def mixer_params(self, d_model: int) -> int:
        """Parameters of one mixer (62,955,776 at Brumby-14B's sizes)."""
        inner, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return (d_model * (inner + 2 * kv + self.kv_heads)
                + 2 * self.head_dim + inner * d_model)


def init_mixer(m: RetentionMixer, key, layers: int, d_model: int, normal,
               std, res_std) -> Dict[str, Any]:
    """The leaves of ``layers`` mixers, stacked: the matrices ``normal(key,
    shape, std)``, the head norms' gains ones."""
    k = jax.random.split(key, 4)
    inner, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return {
        "retention_q_w": normal(k[0], (layers, d_model, inner), std),
        "retention_kv_w": normal(k[1], (layers, d_model, 2 * kv), std),
        "retention_gate_w": normal(k[2], (layers, d_model, m.kv_heads), std),
        "retention_q_norm_scale": jnp.ones((layers, m.head_dim)),
        "retention_k_norm_scale": jnp.ones((layers, m.head_dim)),
        "retention_out_w": normal(k[3], (layers, inner, d_model), res_std),
    }


def diagonal_weights(head_dim: int) -> jnp.ndarray:
    """``c`` of :func:`phi`, [D / 2 + 1]."""
    return jnp.asarray([1.0] + [math.sqrt(2.0)] * (head_dim // 2 - 1) + [1.0],
                       jnp.float32)


def phi(a: jnp.ndarray) -> jnp.ndarray:
    """The feature map of ``a`` [.., D]: [.., D / 2 + 1, D] with ``phi[e, i] =
    c_e a_i a_{(i - e) mod D}``; ``sum(phi(a) * phi(b)) = (a . b)^2``."""
    D = a.shape[-1]
    rolled = jnp.stack([jnp.roll(a, e, axis=-1) for e in range(D // 2 + 1)],
                       axis=-2)
    return diagonal_weights(D)[:, None] * a[..., None, :] * rolled


def split_state(m: RetentionMixer, state):
    """(``S`` [.., G, D / 2 + 1, D_v, D], ``z`` [.., G, D / 2 + 1, D]) of a
    state as it is kept."""
    n = m.diagonals
    return state[..., :n, :, :], state[..., n, :n, :]


def join_state(m: RetentionMixer, S, z):
    """:func:`split_state`'s inverse; the normaliser's block zero past its
    rows."""
    pad = [(0, 0)] * (z.ndim - 2) + [(0, m.head_dim - m.diagonals), (0, 0)]
    return jnp.concatenate([S, jnp.pad(z, pad)[..., None, :, :]], axis=-3)


def _project_in(m: RetentionMixer, h, w, linear, eps, positions, rotate):
    """(q [.., T, H, D], k, v [.., T, G, D], the gate's logit [.., T, G]) of
    the normed input ``h`` [B, T, d]: float32, q and k normed a head and
    rotated."""
    with jax.named_scope("retention_in"):
        B, T, _ = h.shape
        q = _f32(linear(h, w["retention_q_w"], jnp.float32)).reshape(
            B, T, m.heads, m.head_dim)
        kv = _f32(linear(h, w["retention_kv_w"], jnp.float32)).reshape(
            B, T, 2, m.kv_heads, m.head_dim)
        gamma = _f32(linear(h, w["retention_gate_w"], jnp.float32))

        def normed(a, gain):
            return a * jax.lax.rsqrt(
                jnp.mean(a * a, axis=-1, keepdims=True) + eps) * _f32(gain)

        q = rotate(normed(q, w["retention_q_norm_scale"]), positions)
        k = rotate(normed(kv[:, :, 0], w["retention_k_norm_scale"]),
                   positions)
        return q, k, kv[:, :, 1], gamma + m.gate_offset


def _project_out(m: RetentionMixer, o, w, linear, out_type):
    with jax.named_scope("retention_out"):
        return linear(o.reshape(o.shape[:-2] + (m.heads * m.head_dim,))
                      .astype(out_type), w["retention_out_w"], None)


def _quotient(num, den):
    """``num / den``; 0 where nothing has been written yet (a padded row of a
    prompt that holds no token: ``num`` is 0 there too)."""
    return num / jnp.where(den > 0, den, 1.0)[..., None]


def scan_chunks(m: RetentionMixer, q, k, v, log_g, state):
    """The recurrence over ``T`` positions in chunks of ``m.chunk``: ``q`` [B,
    T, H, D], ``k``, ``v`` [B, T, G, D], ``log_g`` [B, T, G] (``k`` and
    ``log_g`` 0 at a padded position), ``state`` [B, G, D / 2 + 2, D, D];
    float32, full-precision products. Returns (``o`` [B, T, H, D], the state).

    With ``A`` the running sum of ``log_g`` inside a chunk, position ``t``
    sees the chunk's own ``s <= t`` through ``(q_t . k_s)^2 exp(A_t - A_s)``
    (the quadratic form, masked) and what came before through ``exp(A_t)
    phi(q_t)^T S_0``; the chunk hands on ``exp(A_C) S_0 + sum_s exp(A_C - A_s)
    phi(k_s) v_s^T``. Every exponent is of a difference that is at most 0.
    ``T`` is padded to whole chunks with ``k`` 0 and ``log_g`` 0."""
    num, den, state = _chunk_sums(m, q, k, v, log_g, state)
    return _quotient(num, den), state


@jax.jit(static_argnums=0)  # a program of its own name: trace.retention_stats
def _chunk_sums(m: RetentionMixer, q, k, v, log_g, state):
    """What :func:`scan_chunks` divides: (num [B, T, H, D], den, the state)."""
    B, T, H, D = q.shape
    G, r, C = m.kv_heads, m.group, min(m.chunk, T)
    pad = -T % C
    q, k, v, log_g = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                      for a in (q, k, v, log_g))
    n = (T + pad) // C
    upto = jnp.tril(jnp.ones((C, C), bool))

    def chunked(a):     # [B, n C, ...] -> [n, B, C, ...]
        return jnp.moveaxis(a.reshape((B, n, C) + a.shape[2:]), 1, 0)

    def one(s, xs):
        q, k, v, log_g = xs         # [B, C, G, r, D], [B, C, G, D], [B, C, G]
        S, z = split_state(m, s)
        A = jnp.cumsum(log_g, axis=1)                           # [B, C, G]
        A_t = jnp.moveaxis(A, 1, 2)                             # [B, G, C]
        seen = jnp.exp(jnp.where(upto, A_t[..., :, None] - A_t[..., None, :],
                                 -jnp.inf))                     # [B, G, t, s]
        scores = jnp.einsum("btgrd,bsgd->bgrts", q, k, precision=HIGHEST)
        scores = scores * scores * seen[:, :, None]
        fq = phi(q) * jnp.exp(A)[..., None, None, None]     # [B,C,G,r,e,D]
        num = (jnp.einsum("bgrts,bsgv->btgrv", scores, v, precision=HIGHEST)
               + jnp.einsum("btgrei,bgevi->btgrv", fq, S, precision=HIGHEST))
        den = (jnp.moveaxis(scores.sum(axis=-1), 3, 1)
               + jnp.einsum("btgrei,bgei->btgr", fq, z, precision=HIGHEST))
        left = jnp.exp(A[:, -1:] - A)                           # [B, C, G]
        fk = phi(k) * left[..., None, None]                     # [B,C,G,e,D]
        last = jnp.exp(A[:, -1])                                # [B, G]
        S = (S * last[..., None, None, None]
             + jnp.einsum("bsgei,bsgv->bgevi", fk, v, precision=HIGHEST))
        z = z * last[..., None, None] + fk.sum(axis=1)
        return join_state(m, S, z), (num, den)

    state, sums = jax.lax.scan(one, state, (
        chunked(q.reshape(B, n * C, G, r, D)), chunked(k), chunked(v),
        chunked(log_g)))
    num, den = (jnp.moveaxis(a, 0, 1).reshape((B, n * C, H) + a.shape[5:])
                for a in sums)
    return num[:, :T], den[:, :T], state


def mix_sequence(m: RetentionMixer, h, w: Dict[str, Any], state, window, *,
                 linear: Callable, eps: float, real=None, scale=None,
                 positions=None, rotate: Callable = None, impl=None):
    """The mixer over ``h`` [B, T, d] at ``positions`` [B, T] from ``state``
    [B, G, D / 2 + 2, D, D] (None: zeros, a sequence's start); ``window`` is
    the cache's empty second array. ``real`` [B] (None: all ``T``) real tokens
    a row. Returns (the sublayer's output [B, T, d], state, window) as the
    last real token left them. ``linear(h, leaf, out type)``: the caller's
    product (``gpt._wm``); ``rotate(a [B, T, heads, D], positions)``: its
    rotation; ``scale``: None (no multipliers); ``impl``: the chunked form's"""
    from ..ops.pallas.retention_chunk import retention_chunk
    assert scale is None, "a retention mixer has no multipliers"
    B, T, _ = h.shape
    if state is None:
        state = jnp.zeros((B,) + m.state_shape(), jnp.float32)
        window = jnp.zeros((B,) + m.window_shape(), jnp.float32)
    q, k, v, gamma = _project_in(m, h, w, linear, eps, positions, rotate)
    log_g = jax.nn.log_sigmoid(gamma)
    if real is not None:
        is_real = (jnp.arange(T)[None, :, None]
                   < jnp.asarray(real, jnp.int32)[:, None, None])
        log_g = jnp.where(is_real, log_g, 0.0)
        k = jnp.where(is_real[..., None], k, 0.0)
    with jax.named_scope("retention_scan"):
        o, state = retention_chunk(m, q, k, v, log_g, _f32(state), impl)
    return _project_out(m, o, w, linear, h.dtype), state, window


def mix_token(m: RetentionMixer, h, w: Dict[str, Any], states, windows, layer,
              active, *, linear: Callable, eps: float,
              impl: Optional[str] = None, live=None, scale=None,
              positions=None, rotate: Callable = None):
    """One token a decode slot: ``h`` [B, 1, d] at ``positions`` [B, 1],
    ``states`` [L, slots, G, D / 2 + 2, D, D] the whole stack (``windows``
    the empty one beside it), ``layer`` the mixer's place in it (it may be
    traced), ``active`` [B] which rows hold a request; ``B`` is the slot
    count and row ``b`` is slot ``b``. A row that holds none leaves its
    slot's state as it was. Returns (the sublayer's output [B, 1, d], states,
    windows)."""
    from ..ops.pallas.retention_decode import retention_decode

    assert scale is None, "a retention mixer has no multipliers"
    if h.shape[0] != states.shape[1]:
        raise ValueError(f"the states hold {states.shape[1]} decode slots, "
                         f"the step has {h.shape[0]} rows")
    q, k, v, gamma = _project_in(m, h, w, linear, eps, positions, rotate)
    with jax.named_scope("retention_update"):
        o, states = retention_decode(
            states, layer, q[:, 0], k[:, 0], v[:, 0],
            jax.nn.sigmoid(gamma[:, 0]), active, impl=impl, live=live)
    return _project_out(m, o[:, None], w, linear, h.dtype), states, windows
