"""The Mamba-2 mixer (Dao & Gu 2024, as ``nemotron_h`` and ``falcon_h1``
run it): the one sublayer of a layer whose kind is ``M`` in
``GPTConfig.layer_pattern``, or the mixer that stands beside attention in
every layer of a config with ``ssm`` and no pattern.
``benchmark/reference/nemotron_h_ref.py`` and ``falcon_h1_ref.py`` have the
equations as a plain recurrence; here they are as the programs run them. For
the normed input ``h`` [.., T, d] of a layer with ``heads`` heads of
``head_dim`` (``d_inner`` = their product, whatever ``d`` is), a state of
``state`` a head and ``groups`` groups of heads:

    [z | xBC | dt] = h W_in          (d_inner | conv_width | heads)
    xBC <- silu(causal depthwise conv_K(xBC) + b)
    [x | B | C] = xBC                (d_inner | groups state | groups state)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t
    y <- RMSNorm_groups(y * silu(z)) * scale;     out = y W_out

A muP-parametrised model (``gpt.Multipliers``) multiplies ``h`` by one scalar
before ``W_in``, the projection's five segments ``z | x | B | C | dt`` by one
each, and ``out`` by one: ``scale`` = (input, segments, output) of
:func:`mix_sequence` and :func:`mix_token`, None where there is none.

What a request carries from token to token is the **state a layer**: ``S``
[heads, head_dim, state] and the convolution's window, the last ``K - 1``
rows of ``xBC`` before the convolution, both float32. It has no position
axis: a cache keeps one a sequence (``gpt.init_cache``) or one a decode slot
(``gpt.init_paged_cache``), whatever the length.

- :func:`mix_sequence`: ``T`` tokens a row from a given state, the chunked
  scan (:func:`scan_chunks`: inside a chunk of ``chunk`` positions matrix
  products under a decay mask, chunk to chunk the state). ``real`` [B]: only
  a row's first ``real`` tokens are real; the state and the window it hands
  back are what the last REAL token left (a padded position has ``dt`` 0,
  which neither decays nor adds, and the window is taken at the real end).
- :func:`mix_token`: one token a row, the recurrence, through
  ``ops/pallas/ssm_decode`` over the whole stack of states where they lie.

Scopes (``profiling/trace.MODEL_SCOPES``): ``ssm_in``, ``ssm_conv``,
``ssm_scan`` or ``ssm_update``, ``ssm_gate_norm``, ``ssm_out``; the caller
wraps them in ``ssm``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# the seeded ``dt_bias`` of every state-keeping mixer: a log-uniform step in
# [DT_MIN, DT_MAX], floored at DT_FLOOR (:func:`draw_dt_bias`)
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4


@dataclasses.dataclass(frozen=True)
class SsmMixer:
    """The mixer's sizes (``GPTConfig.ssm``): a published config's
    ``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``,
    ``conv_kernel``, ``chunk_size`` and, for the seeded ``dt_bias`` only,
    ``time_step_min`` / ``_max`` / ``_floor``."""
    heads: int
    head_dim: int
    state: int
    groups: int
    conv: int = 4
    chunk: int = 128
    dt_min: float = DT_MIN
    dt_max: float = DT_MAX
    dt_floor: float = DT_FLOOR

    def __post_init__(self):
        if (min(self.heads, self.head_dim, self.state, self.groups,
                self.chunk) < 1 or self.conv < 2
                or self.heads % self.groups
                or self.d_inner % self.groups):
            raise ValueError(f"{self}: heads in whole groups, a convolution "
                             "of at least 2 taps")

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: ``[x | B | C]``."""
        return self.d_inner + 2 * self.groups * self.state

    @property
    def in_width(self) -> int:
        return self.d_inner + self.conv_width + self.heads

    def state_shape(self) -> Tuple[int, int, int]:
        return (self.heads, self.head_dim, self.state)

    def window_shape(self) -> Tuple[int, int]:
        return (self.conv - 1, self.conv_width)

    def slot_bytes(self) -> int:
        """Float32 bytes one layer's state and window cost a sequence."""
        return 4 * (math.prod(self.state_shape())
                    + math.prod(self.window_shape()))

    def layer_params(self, d_model: int) -> int:
        """Parameters of one ``M`` layer, its norm's gain included."""
        return (d_model * self.in_width + self.conv_width * (self.conv + 1)
                + 3 * self.heads + self.d_inner + self.d_inner * d_model
                + d_model)


def draw_dt_bias(key, shape, dt_min: float = DT_MIN, dt_max: float = DT_MAX,
                 dt_floor: float = DT_FLOOR):
    """A seeded ``dt_bias`` [shape], float32: the inverse softplus of a
    log-uniform draw in [dt_min, dt_max] floored at dt_floor
    (``models/kda.py`` draws its own by this, at the defaults)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
    dt = jnp.maximum(dt, dt_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def init_mixer(m: SsmMixer, key, layers: int, d_model: int, normal, std,
               res_std) -> Dict[str, Any]:
    """The leaves of ``layers`` mixers, stacked: the matrices ``normal(key,
    shape, std)``, the convolution's taps among them (at N(0, 0.02) they
    leave ``x``, ``B`` and ``C`` near 0.02 and the state's share of ``y``
    under a thousandth of ``D x``; taps of U(-1 / sqrt(K), 1 / sqrt(K)), what
    a depthwise ``Conv1d`` starts from, make the state a third of ``y``, and
    a served routed model then drifts from its float32 reference for good
    after its first flipped expert: PERF.md PR 40 has both readings);
    ``A_log = log(U[1, 16])``; ``dt_bias`` by :func:`draw_dt_bias`; ``D``
    and the norm's gain ones; the convolution's bias zeros."""
    k = jax.random.split(key, 5)
    return {
        "ssm_in_w": normal(k[0], (layers, d_model, m.in_width), std),
        "ssm_conv_w": normal(k[1], (layers, m.conv, m.conv_width), std),
        "ssm_conv_b": jnp.zeros((layers, m.conv_width)),
        "ssm_dt_bias": draw_dt_bias(k[3], (layers, m.heads), m.dt_min,
                                    m.dt_max, m.dt_floor),
        "ssm_A_log": jnp.log(jax.random.uniform(
            k[4], (layers, m.heads), jnp.float32, 1.0, 16.0)),
        "ssm_D": jnp.ones((layers, m.heads)),
        "ssm_norm_scale": jnp.ones((layers, m.d_inner)),
        "ssm_out_w": normal(k[2], (layers, m.d_inner, d_model), res_std),
    }


def _f32(a):
    return a.astype(jnp.float32)


def causal_conv(window, x, taps, real=None):
    """The causal depthwise convolution of ``x`` [B, T, C] (float32) behind
    the ``K - 1`` rows ``window`` [B, K - 1, C] that came before it, ``taps``
    [K, C] (tap ``k`` on the row ``K - 1 - k`` before the current one): the
    sums [B, T, C], before any bias or activation, and the window the last
    REAL token leaves (``real`` [B] real tokens a row; None: all ``T``). The
    one convolution of every state-keeping mixer (``models/kda.py`` too)."""
    T, K = x.shape[1], taps.shape[0]
    rows = jnp.concatenate([_f32(window), x], axis=1)       # [B, K-1+T, C]
    taps = _f32(taps)
    conv = sum(rows[:, k:k + T] * taps[k] for k in range(K))
    if real is None:
        return conv, rows[:, T:]
    at = (jnp.asarray(real, jnp.int32)[:, None]
          + jnp.arange(K - 1)[None, :])                     # [B, K - 1]
    return conv, jnp.take_along_axis(rows, at[:, :, None], axis=1)


def causal_conv_token(windows, layer, x, taps):
    """:func:`causal_conv` of ONE new row a decode slot, ``x`` [slots, C],
    behind layer ``layer`` of the window stack ``windows`` [L, slots, K - 1,
    C], which is read and not written (the decode kernels shift it where it
    lies): the sums [slots, C]."""
    old = jax.lax.dynamic_index_in_dim(windows, layer, 0, keepdims=False)
    rows = jnp.concatenate([old, x[:, None]], axis=1)       # [slots, K, C]
    return jnp.sum(rows * _f32(taps), axis=1)


def segments(m: SsmMixer, by) -> jnp.ndarray:
    """One of ``by``'s five scalars a column of the in-projection, by the
    segment it lies in: ``z | x | B | C | dt``, float32 [in_width]."""
    gn = m.groups * m.state
    return jnp.repeat(jnp.asarray(by, jnp.float32),
                      jnp.asarray((m.d_inner, m.d_inner, gn, gn, m.heads)),
                      total_repeat_length=m.in_width)


def _project_in(m: SsmMixer, h, w, linear, scale=None):
    """(z, xBC before the convolution, dt after the softplus, A): float32."""
    with jax.named_scope("ssm_in"):
        if scale is not None and scale[0] != 1.0:
            h = h * scale[0]
        zxd = _f32(linear(h, w["ssm_in_w"], jnp.float32))
        if scale is not None:
            zxd = zxd * segments(m, scale[1])
        z = zxd[..., :m.d_inner]
        xbc = zxd[..., m.d_inner:m.d_inner + m.conv_width]
        dt = jax.nn.softplus(zxd[..., m.d_inner + m.conv_width:]
                             + _f32(w["ssm_dt_bias"]))
    return z, xbc, dt, -jnp.exp(_f32(w["ssm_A_log"]))


def _split_xbc(m: SsmMixer, xbc):
    lead = xbc.shape[:-1]
    gn = m.groups * m.state
    return (xbc[..., :m.d_inner].reshape(lead + (m.heads, m.head_dim)),
            xbc[..., m.d_inner:m.d_inner + gn].reshape(
                lead + (m.groups, m.state)),
            xbc[..., m.d_inner + gn:].reshape(lead + (m.groups, m.state)))


def _finish(m: SsmMixer, y, x, z, w, eps: float, linear, out_type,
            scale=None):
    """``y + D x``, the gate, the grouped norm and the out-projection; ``y``
    and ``x`` [.., heads, head_dim], ``z`` [.., d_inner]."""
    with jax.named_scope("ssm_gate_norm"):
        y = y + _f32(w["ssm_D"])[:, None] * x
        lead = z.shape[:-1]
        y = y.reshape(lead + (m.d_inner,)) * jax.nn.silu(z)
        g = y.reshape(lead + (m.groups, m.d_inner // m.groups))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        y = g.reshape(lead + (m.d_inner,)) * _f32(w["ssm_norm_scale"])
    with jax.named_scope("ssm_out"):
        out = linear(y.astype(out_type), w["ssm_out_w"], None)
        return out if scale is None or scale[2] == 1.0 else out * scale[2]


def scan_chunks(m: SsmMixer, x, dt, A, b, c, state):
    """The recurrence over ``T`` positions as the chunked scan: ``x`` [B, T,
    H, P], ``dt`` [B, T, H] (0 at a padded position), ``A`` [H], ``b`` and
    ``c`` [B, T, G, N], ``state`` [B, H, P, N]; float32, the products at full
    precision. Returns (``S_t C_t`` [B, T, H, P], the state after ``T``).

    With ``a_t = dt_t A`` and ``cum`` its running sum inside a chunk, position
    ``i`` of a chunk reads ``sum over j <= i of exp(cum_i - cum_j) (C_i . B_j)
    dt_j x_j`` (a masked [Q, Q] product a head) and ``exp(cum_i) S_in C_i``
    of the state the chunk was handed; the chunk hands on ``exp(cum_Q) S_in +
    sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T``. ``T`` is padded to whole
    chunks with ``dt`` 0, which moves no state."""
    B, T, H, P = x.shape
    G, N = b.shape[2:]
    Q = min(m.chunk, T)
    pad = -T % Q
    if pad:
        x, dt, b, c = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                               * (a.ndim - 2)) for a in (x, dt, b, c))
    n = (T + pad) // Q
    rep = H // G

    def chunked(a):     # [B, n Q, ...] -> [n, B, Q, ...]
        return jnp.moveaxis(a.reshape((B, n, Q) + a.shape[2:]), 1, 0)

    def one(s, xs):
        x, dt, b, c = xs                  # [B, Q, H, P], [B, Q, H], [B,Q,G,N]
        cum = jnp.cumsum(dt * A, axis=1)                        # [B, Q, H]
        dtx = x * dt[..., None]
        # position i against position j of its group's B: [B, G, Q, Q]
        cb = jnp.einsum("bign,bjgn->bgij", c, b, precision=HIGHEST)
        decay = jnp.exp(cum[:, :, None, :] - cum[:, None, :, :])  # [B,i,j,H]
        seen = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
        weight = (jnp.where(seen, decay, 0.0)
                  * jnp.repeat(cb, rep, axis=1).transpose(0, 2, 3, 1))
        y = jnp.einsum("bijh,bjhp->bihp", weight, dtx, precision=HIGHEST)
        ch = jnp.repeat(c, rep, axis=2)                         # [B, Q, H, N]
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bhpn,bihn->bihp", s, ch, precision=HIGHEST)
        left = jnp.exp(cum[:, -1:, :] - cum)                    # [B, Q, H]
        s = (jnp.exp(cum[:, -1])[:, :, None, None] * s
             + jnp.einsum("bjhp,bjhn->bhpn", dtx * left[..., None],
                          jnp.repeat(b, rep, axis=2), precision=HIGHEST))
        return s, y

    state, y = jax.lax.scan(one, state, tuple(
        chunked(a) for a in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, n * Q, H, P)
    return y[:, :T], state


def mix_sequence(m: SsmMixer, h, w: Dict[str, Any], state, window, *,
                 linear: Callable, eps: float, real=None, scale=None,
                 positions=None, rotate=None):
    """The mixer over ``h`` [B, T, d] from ``state`` [B, H, P, N] and
    ``window`` [B, K - 1, C] (None: zeros, a sequence's start). ``real``
    [B] (None: all ``T``) real tokens a row. Returns (the sublayer's output
    [B, T, d], state, window) as the last real token left them. ``linear(h,
    leaf, out type)`` is the caller's matrix product (``gpt._wm``);
    ``scale`` the multipliers (input, segments, output), None without.
    ``positions`` and ``rotate`` (``gpt.state_mixer``'s contract) are taken
    and not used: nothing here is rotated."""
    B, T, _ = h.shape
    if state is None:
        state = jnp.zeros((B,) + m.state_shape(), jnp.float32)
        window = jnp.zeros((B,) + m.window_shape(), jnp.float32)
    z, xbc, dt, A = _project_in(m, h, w, linear, scale)
    with jax.named_scope("ssm_conv"):
        conv, window = causal_conv(window, xbc, w["ssm_conv_w"], real)
        x, b, c = _split_xbc(m, jax.nn.silu(conv + _f32(w["ssm_conv_b"])))
        if real is not None:
            dt = jnp.where(jnp.arange(T)[None, :, None]
                           < jnp.asarray(real, jnp.int32)[:, None, None],
                           dt, 0.0)
    with jax.named_scope("ssm_scan"):
        y, state = scan_chunks(m, x, dt, A, b, c, _f32(state))
    return (_finish(m, y, x, z, w, eps, linear, h.dtype, scale), state,
            window)


def mix_token(m: SsmMixer, h, w: Dict[str, Any], states, windows, layer,
              active, *, linear: Callable, eps: float,
              impl: Optional[str] = None, live=None, scale=None,
              positions=None, rotate=None):
    """One token a decode slot: ``h`` [B, 1, d], ``states`` [L, slots, H, P,
    N] and ``windows`` [L, slots, K - 1, C] the whole stacks, ``layer`` the
    mixer's place in them (it may be traced), ``active`` [B] which rows hold
    a request; ``B`` is the slot count and row ``b`` is slot ``b``. A row
    that holds none leaves its slot's state and window as they were. Returns
    (the sublayer's output [B, 1, d], states, windows)."""
    from ..ops.pallas.ssm_decode import ssm_decode

    if h.shape[0] != states.shape[1]:
        raise ValueError(f"the states hold {states.shape[1]} decode slots, "
                         f"the step has {h.shape[0]} rows")
    z, xbc, dt, A = _project_in(m, h[:, 0], w, linear, scale)
    with jax.named_scope("ssm_conv"):
        conv = causal_conv_token(windows, layer, xbc, w["ssm_conv_w"])
        x, b, c = _split_xbc(m, jax.nn.silu(conv + _f32(w["ssm_conv_b"])))
    with jax.named_scope("ssm_update"):
        # the kernel shifts the live slots' windows too, where they lie:
        # the stack is read above and written by no XLA operation
        y, states, windows = ssm_decode(
            states, layer, x * dt[..., None], jnp.exp(dt * A), b, c, active,
            impl=impl, live=live, windows=windows, new_row=xbc)
    out = _finish(m, y, x, z, w, eps, linear, h.dtype, scale)
    return out[:, None], states, windows
