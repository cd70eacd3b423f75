"""Kimi Delta Attention (KDA; the Kimi Linear report, arXiv:2510.26692, as
``kimi_linear`` runs it): a gated delta rule with a decay a channel, the mixer
of a layer that ``GPTConfig.kda_layers`` names. It stands beside
``models/ssm.py`` as the second state-keeping mixer: another recurrence, the
same slot. ``benchmark/reference/kimi_linear_ref.py`` has the equations as a
plain recurrence; here they are as the programs run them. For the normed input
``h`` [.., T, d] of a layer with ``heads`` heads of ``head_dim`` (``H``, ``D``;
``d_inner`` = their product; the decay's and the gate's low-rank
projections are ``D`` wide, as the source's modelling file builds them):

    [q | k | v | f | z | b] = h W_in           (3 d_inner | D | D | heads)
    [q | k | v] <- silu(causal depthwise conv_K([q | k | v]))      no bias
    q, k <- q / |q|, k / |k| a head;  q <- q D^-1/2
    g = -exp(A_log) softplus(f W_fb + dt_bias);  a = exp(g)   a head, channel
    beta = sigmoid(b)                                            a head
    S' = diag(a_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T;    o_t = S_t^T q_t           S [D_k, D_v] a head
    o <- RMSNorm_D(o) * w_norm * sigmoid(z W_gb);      out = o W_out

What a request carries from token to token is the **state a layer**, as the
Mamba-2 mixer's is: ``S`` a head, float32, kept TRANSPOSED as ``[heads, D_v,
D_k]`` (a key channel a lane: the decay, ``k`` and ``q`` then meet a state
row without a transposition, ``ops/pallas/kda_decode`` says how), and the
convolution's window, the last ``K - 1`` rows of ``[q | k | v]`` before the
convolution. ``gpt.init_cache`` and ``gpt.init_paged_cache`` keep them under
the names every state-keeping mixer's have (``gpt.SSM_KEYS``).

- :func:`mix_sequence`: ``T`` tokens a row from a given state, the chunked
  form (:func:`scan_chunks`). ``real`` [B]: only a row's first ``real`` tokens
  are real; a padded position has ``g`` 0 and ``beta`` 0, which neither decays
  nor writes, and the window is taken at the real end
  (``ssm.causal_conv``).
- :func:`mix_token`: one token a row, the recurrence, through
  ``ops/pallas/kda_decode`` over the whole stack of states where they lie.

Scopes (``profiling/trace.MODEL_SCOPES``): ``kda_in``, ``kda_conv``,
``kda_gates``, ``kda_scan`` or ``kda_update``, ``kda_gate_norm``, ``kda_out``;
the caller wraps them in ``kda``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .ssm import (HIGHEST, _f32, causal_conv, causal_conv_token,
                  draw_dt_bias)


@dataclasses.dataclass(frozen=True)
class KdaMixer:
    """The mixer's sizes (``GPTConfig.kda``): a published config's
    ``linear_attn_config`` (``num_heads``, ``head_dim``,
    ``short_conv_kernel_size``); ``chunk`` the prompt form's positions a
    chunk."""
    heads: int
    head_dim: int
    conv: int = 4
    chunk: int = 16

    def __post_init__(self):
        if (min(self.heads, self.head_dim, self.chunk) < 1 or self.conv < 2
                or self.chunk & (self.chunk - 1)):
            raise ValueError(f"{self}: a convolution of at least 2 taps, "
                             "chunks of a power of two")

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: ``[q | k | v]``."""
        return 3 * self.d_inner

    @property
    def in_width(self) -> int:
        return self.conv_width + 2 * self.head_dim + self.heads

    def state_shape(self) -> Tuple[int, int, int]:
        """A head's state as it is kept: [value channel, key channel]."""
        return (self.heads, self.head_dim, self.head_dim)

    def window_shape(self) -> Tuple[int, int]:
        return (self.conv - 1, self.conv_width)

    def slot_bytes(self) -> int:
        """Float32 bytes one layer's state and window cost a sequence."""
        return 4 * (math.prod(self.state_shape())
                    + math.prod(self.window_shape()))

    def mixer_params(self, d_model: int) -> int:
        """Parameters of one mixer (39,514,272 at 32 heads of 128 on 2304)."""
        return (d_model * self.in_width + self.conv_width * self.conv
                + 2 * self.head_dim * self.d_inner + self.d_inner
                + self.heads + self.head_dim + self.d_inner * d_model)


def init_mixer(m: KdaMixer, key, layers: int, d_model: int, normal, std,
               res_std) -> Dict[str, Any]:
    """The leaves of ``layers`` mixers, stacked: the matrices ``normal(key,
    shape, std)``, the convolutions' taps among them; ``A_log`` and
    ``dt_bias`` as ``ssm.init_mixer`` draws its own (``log(U[1, 16])`` a
    head; ``ssm.draw_dt_bias``, here a channel); the norm's gain ones."""
    k = jax.random.split(key, 7)
    return {
        "kda_in_w": normal(k[0], (layers, d_model, m.in_width), std),
        "kda_conv_w": normal(k[1], (layers, m.conv, m.conv_width), std),
        "kda_f_b_w": normal(k[2], (layers, m.head_dim, m.d_inner), std),
        "kda_g_b_w": normal(k[3], (layers, m.head_dim, m.d_inner), std),
        "kda_dt_bias": draw_dt_bias(k[5], (layers, m.d_inner)),
        "kda_A_log": jnp.log(jax.random.uniform(
            k[6], (layers, m.heads), jnp.float32, 1.0, 16.0)),
        "kda_norm_scale": jnp.ones((layers, m.head_dim)),
        "kda_out_w": normal(k[4], (layers, m.d_inner, d_model), res_std),
    }


def _project_in(m: KdaMixer, h, w, linear):
    """(``[q | k | v]`` before the convolution, the decay's low-rank input,
    the gate's, the write strength's logit): float32."""
    with jax.named_scope("kda_in"):
        proj = _f32(linear(h, w["kda_in_w"], jnp.float32))
        c, r = m.conv_width, m.head_dim
        return (proj[..., :c], proj[..., c:c + r], proj[..., c + r:c + 2 * r],
                proj[..., c + 2 * r:])


def _heads(m: KdaMixer, a):
    return a.reshape(a.shape[:-1] + (m.heads, m.head_dim))


def _qkv(m: KdaMixer, conv):
    """(q, k, v) [.., H, D] of the convolution's sums [.., 3 H D]: SiLU, then
    q and k at unit length a head, q times ``D^-1/2``."""
    q, k, v = (_heads(m, a) for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                 + 1e-6)
    return unit(q) * m.head_dim ** -0.5, unit(k), v


def _gates(m: KdaMixer, f, b, w, linear):
    """(``g`` [.., H, D] the log of the decay a channel, ``beta`` [.., H])."""
    with jax.named_scope("kda_gates"):
        g = _f32(linear(f, w["kda_f_b_w"], jnp.float32))
        g = jax.nn.softplus(g + _f32(w["kda_dt_bias"]))
        g = -jnp.exp(_f32(w["kda_A_log"]))[:, None] * _heads(m, g)
        return g, jax.nn.sigmoid(b)


def _finish(m: KdaMixer, o, z, w, eps: float, linear, out_type):
    """The norm a head, its gain, the low-rank gate and the out-projection;
    ``o`` [.., H, D], ``z`` [.., D]."""
    with jax.named_scope("kda_gate_norm"):
        gate = jax.nn.sigmoid(_f32(linear(z, w["kda_g_b_w"], jnp.float32)))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        o = (o * _f32(w["kda_norm_scale"])).reshape(gate.shape) * gate
    with jax.named_scope("kda_out"):
        return linear(o.astype(out_type), w["kda_out_w"], None)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` [.., C, C], ``C`` a
    power of two: ``a`` is nilpotent, so the inverse is the finite product
    ``(I - a)(I + a^2)(I + a^4)...`` of ``log2 C`` factors."""
    C = a.shape[-1]
    eye = jnp.eye(C, dtype=a.dtype)
    inv, power, n = eye - a, a, 1
    while 2 * n < C:
        power = jnp.matmul(power, power, precision=HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=HIGHEST)
        n *= 2
    return inv


def scan_chunks(m: KdaMixer, q, k, v, g, beta, state):
    """The recurrence over ``T`` positions in chunks of ``m.chunk``: ``q``,
    ``k``, ``v``, ``g`` [B, T, H, D], ``beta`` [B, T, H] (``g`` and ``beta``
    0 at a padded position), ``state`` [B, H, D_v, D_k]; float32, the
    products at full precision. Returns (``o`` [B, T, H, D], the state after
    ``T``).

    With ``gamma`` the running sum of ``g`` inside a chunk, every exponent is
    of ``gamma_t - gamma_j`` with ``j <= t``, taken a channel at a time, so
    none overflows whatever the decay: ``A[t, j] = beta_t sum_c k_t[c] k_j[c]
    exp(gamma_t[c] - gamma_j[c])`` below the diagonal, ``U = (I + A)^-1
    diag(beta) (V - (K exp(gamma)) S_0)``, ``o_t = S_0^T (q_t exp(gamma_t)) +
    sum_{j <= t} u_j (q_t . k_j exp(gamma_t - gamma_j))``, and the chunk hands
    on ``diag(exp(gamma_C)) S_0 + sum_j (k_j exp(gamma_C - gamma_j)) u_j^T``.
    ``T`` is padded to whole chunks with ``g`` 0 and ``beta`` 0, which move
    no state."""
    B, T, H, D = q.shape
    Q = min(m.chunk, 1 << max(T - 1, 0).bit_length())
    pad = -T % Q
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    n = (T + pad) // Q

    def chunked(a):     # [B, n Q, H, ...] -> [n, B, H, Q, ...]
        a = a.reshape((B, n, Q) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    below = jnp.tril(jnp.ones((Q, Q), bool), -1)
    upto = jnp.tril(jnp.ones((Q, Q), bool))

    def one(s, xs):
        q, k, v, g, beta = xs           # [B, H, Q, D]; beta [B, H, Q]
        gamma = jnp.cumsum(g, axis=2)
        # exp(gamma_t - gamma_j) a channel, 0 above the diagonal: [B,H,t,j,c]
        decay = jnp.exp(jnp.where(
            upto[:, :, None],
            gamma[:, :, :, None, :] - gamma[:, :, None, :, :], -jnp.inf))
        kj = k[:, :, None, :, :] * decay
        kk = jnp.sum(k[:, :, :, None, :] * kj, axis=-1)         # [B,H,t,j]
        qk = jnp.sum(q[:, :, :, None, :] * kj, axis=-1)
        inv = _unit_lower_inverse(
            jnp.where(below, kk, 0.0) * beta[..., None])
        into = jnp.exp(gamma)                        # from the chunk's start
        seen = jnp.einsum("bhtc,bhvc->bhtv", k * into, s, precision=HIGHEST)
        u = jnp.matmul(inv, beta[..., None] * (v - seen), precision=HIGHEST)
        o = (jnp.einsum("bhtc,bhvc->bhtv", q * into, s, precision=HIGHEST)
             + jnp.matmul(qk, u, precision=HIGHEST))
        left = jnp.exp(gamma[:, :, -1:] - gamma)     # to the chunk's end
        s = (s * into[:, :, -1][:, :, None, :]
             + jnp.einsum("bhjv,bhjc->bhvc", u, k * left, precision=HIGHEST))
        return s, o

    state, o = jax.lax.scan(one, state, tuple(
        chunked(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(B, n * Q, H, D)
    return o[:, :T], state


def mix_sequence(m: KdaMixer, h, w: Dict[str, Any], state, window, *,
                 linear: Callable, eps: float, real=None, scale=None,
                 positions=None, rotate=None):
    """The mixer over ``h`` [B, T, d] from ``state`` [B, H, D_v, D_k] and
    ``window`` [B, K - 1, 3 H D] (None: zeros, a sequence's start). ``real``
    [B] (None: all ``T``) real tokens a row. Returns (the sublayer's output
    [B, T, d], state, window) as the last real token left them. ``linear(h,
    leaf, out type)`` is the caller's matrix product (``gpt._wm``); ``scale``
    is ``ssm.mix_sequence``'s and has to be None: this mixer has no
    multipliers. ``positions`` and ``rotate`` (``gpt.state_mixer``'s
    contract) are taken and not used: nothing here is rotated."""
    assert scale is None, "a KDA mixer has no multipliers"
    B, T, _ = h.shape
    if state is None:
        state = jnp.zeros((B,) + m.state_shape(), jnp.float32)
        window = jnp.zeros((B,) + m.window_shape(), jnp.float32)
    qkv, f, z, b = _project_in(m, h, w, linear)
    with jax.named_scope("kda_conv"):
        conv, window = causal_conv(window, qkv, w["kda_conv_w"], real)
        q, k, v = _qkv(m, conv)
    g, beta = _gates(m, f, b, w, linear)
    if real is not None:
        is_real = (jnp.arange(T)[None, :, None]
                   < jnp.asarray(real, jnp.int32)[:, None, None])
        g = jnp.where(is_real[..., None], g, 0.0)
        beta = jnp.where(is_real, beta, 0.0)
    with jax.named_scope("kda_scan"):
        o, state = scan_chunks(m, q, k, v, g, beta, _f32(state))
    return _finish(m, o, z, w, eps, linear, h.dtype), state, window


def mix_token(m: KdaMixer, h, w: Dict[str, Any], states, windows, layer,
              active, *, linear: Callable, eps: float,
              impl: Optional[str] = None, live=None, scale=None,
              positions=None, rotate=None):
    """One token a decode slot: ``h`` [B, 1, d], ``states`` [L, slots, H,
    D_v, D_k] and ``windows`` [L, slots, K - 1, 3 H D] the whole stacks,
    ``layer`` the mixer's place in them (it may be traced), ``active`` [B]
    which rows hold a request; ``B`` is the slot count and row ``b`` is slot
    ``b``. A row that holds none leaves its slot's state and window as they
    were. Returns (the sublayer's output [B, 1, d], states, windows)."""
    from ..ops.pallas.kda_decode import kda_decode

    assert scale is None, "a KDA mixer has no multipliers"
    if h.shape[0] != states.shape[1]:
        raise ValueError(f"the states hold {states.shape[1]} decode slots, "
                         f"the step has {h.shape[0]} rows")
    qkv, f, z, b = _project_in(m, h[:, 0], w, linear)
    with jax.named_scope("kda_conv"):
        q, k, v = _qkv(m, causal_conv_token(windows, layer, qkv,
                                            w["kda_conv_w"]))
    g, beta = _gates(m, f, b, w, linear)
    with jax.named_scope("kda_update"):
        # the kernel shifts the live slots' windows too, where they lie
        o, states, windows = kda_decode(
            states, layer, q, k, v, jnp.exp(g), beta, active, impl=impl,
            live=live, windows=windows, new_row=qkv)
    out = _finish(m, o, z, w, eps, linear, h.dtype)
    return out[:, None], states, windows
