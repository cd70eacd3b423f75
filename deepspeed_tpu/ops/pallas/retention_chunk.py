"""A prompt's power retention (``models/retention.py``) in Pallas: the chunked
form of ``retention.scan_chunks`` over the chunks of one dispatch, a key-value
head's state in VMEM from the first chunk to the last.

``scan_chunks`` is right and slow: a chunk of 128 positions writes ``phi(q)
exp(A)`` out ([B, 128, 8, 5, 65, 128] float32, 170 MB a chunk and layer at
Brumby's 40 over 8 heads of 128) and ``phi(k)`` (34 MB), and reads and writes
the state (34.6 MB a layer) every chunk: on the order of a gigabyte of HBM
traffic for an algorithm whose inputs are 0.5 MB. Here the grid walks (row,
key-value head) and, innermost and in order, the dispatch's chunks:

- the head's state ``[D / 2 + 2, D, D]`` (4.33 MB) comes in once, at its first
  chunk, is updated in the output's block, which stays where it is while the
  chunks turn, and goes back once, in the layout ``retention.join_state``
  keeps (the normaliser's block zero past its rows);
- a grid step takes the chunk's ``q`` [C, r D] (the head's ``r`` query heads
  side by side along the lanes, as ``[B, T, H D]`` has them), ``k``, ``v`` [C,
  D] and the gate's running sum ``A`` [1, C], and makes ``phi(q)`` and
  ``phi(k)`` **a diagonal at a time** in VMEM (``c_e a_i a_{(i - e) mod D}``:
  a lane roll and a product): for diagonal ``e`` one ``[r C, D] x [D, D]``
  product against ``S_e`` for the read, ``phi_e(q) z_e`` added to a tile
  whose lane sums are the normaliser's part, and one ``[D + 8, C] x [C, D]``
  product of ``v^T`` (eight rows of ones under it: the normaliser is ``S``
  with ``v`` extended by a 1) and ``phi_e(k)`` for the write; before them the
  chunk's own masked quadratic part ``(q . k)^2 exp(A_t - A_s)``, ``s <= t``.
  Neither ``phi`` nor the ``[C, C]`` scores ever exist in HBM. The step
  hands back the numerators and, a query head a row, the denominators; the
  quotient is ``retention._quotient``'s, outside, as ``retention_decode``'s.

The same mathematics at the same precision: float32 operands, every product
``Precision.HIGHEST`` (six bf16 passes, as ``index_scores`` takes them inside
a kernel), a float32 state, exponents of differences that are at most 0, ``k``
0 and ``log_g`` 0 at a padded position moving no state, ``_quotient``'s 0
where nothing has been written.

``impl``: None = the kernel on a TPU where the tiles take the shape (``D``
and the chunk whole lanes of 128, ``T`` whole chunks after the padding
``scan_chunks`` does too, at most 8 query heads a key-value head), the plain
``scan_chunks`` elsewhere; "kernel" forces Pallas (interpret mode off the TPU,
any ``D`` and chunk that are multiples of 8); "plain" the ``jax.numpy`` form.
Forward only: serving (no train path takes a retention config).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...models import retention
from ...models.ssm import HIGHEST
from .flash_attention import _interpret
from .ssm_decode import LANES, _VMEM_LIMIT


def _fits(D: int, chunk: int, group: int) -> bool:
    """The tiles take the shape on a TPU: a head and a chunk in whole lanes
    (a chunk lies along the lanes of its scores' tile), the group's
    accumulators within the scoped limit."""
    return D % LANES == 0 and chunk % LANES == 0 and group <= 8


def retention_chunk(m: retention.RetentionMixer, q: jnp.ndarray,
                    k: jnp.ndarray, v: jnp.ndarray, log_g: jnp.ndarray,
                    state: jnp.ndarray, impl: Optional[str] = None):
    """``retention.scan_chunks``' arguments and results: ``q`` [B, T, H, D],
    ``k``, ``v`` [B, T, G, D], ``log_g`` [B, T, G] (``k`` and ``log_g`` 0 at
    a padded position), ``state`` [B, G, D / 2 + 2, D, D], float32; returns
    (``o`` [B, T, H, D], the state after ``T``)."""
    B, T, H, D = q.shape
    G, r = m.kv_heads, m.group
    # a prompt shorter than a chunk is one chunk of whole sublanes
    C = min(m.chunk, -(-T // 8) * 8)
    if impl is None:
        impl = ("kernel" if jax.default_backend() == "tpu" and _fits(D, C, r)
                else "plain")
    if impl == "plain":
        return retention.scan_chunks(m, q, k, v, log_g, state)
    if impl != "kernel":
        raise ValueError(f"impl must be None, 'kernel' or 'plain': {impl!r}")
    if D % 8 or C % 8:
        raise ValueError(f"the kernel takes heads and chunks in whole "
                         f"sublanes of 8: D {D}, chunks of {m.chunk}")
    f32 = jnp.float32
    q, k, v, log_g, state = (a.astype(f32) for a in (q, k, v, log_g, state))
    pad = -T % C
    if pad:
        q, k, v, log_g = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                  * (a.ndim - 2)) for a in (q, k, v, log_g))
    n, blocks = (T + pad) // C, m.diagonals + 1
    # the gate's running sum inside a chunk, a chunk a row [1, C], and what
    # is left of the state after the chunk, in every lane of a row [1, D]
    A = jnp.moveaxis(jnp.cumsum(log_g.reshape(B, n, C, G), axis=2), 3, 1)
    last = jnp.broadcast_to(jnp.exp(A[..., -1:]), (B, G, n, D))

    def tokens(width):      # chunk c of head g out of [B, T, heads x width]
        return pl.BlockSpec((None, C, width), lambda b, g, c: (b, c, g))

    def row(width):
        return pl.BlockSpec((None, None, None, 1, width),
                            lambda b, g, c: (b, g, c, 0, 0))

    state_spec = pl.BlockSpec((None, None, blocks, D, D),
                              lambda b, g, c: (b, g, 0, 0, 0))
    num, den, state = pl.pallas_call(
        functools.partial(_kernel, group=r),
        grid=(B, G, n),
        in_specs=[row(C), row(D), tokens(r * D), tokens(D), tokens(D),
                  state_spec],
        out_specs=[tokens(r * D),
                   pl.BlockSpec((None, None, None, r, C),
                                lambda b, g, c: (b, g, c, 0, 0)), state_spec],
        out_shape=[jax.ShapeDtypeStruct((B, n * C, H * D), f32),
                   jax.ShapeDtypeStruct((B, G, n, r, C), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # q a head a block, q exp(A), the numerators, the denominators'
        # lanes; z, a tile a diagonal
        scratch_shapes=[pltpu.VMEM((r * C, D), f32)] * 4 + [
            pltpu.VMEM((blocks - 1, 8, D), f32)],
        input_output_aliases={5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="retention_chunk",
    )(A[:, :, :, None, :], last[:, :, :, None, :],
      q.reshape(B, n * C, H * D), k.reshape(B, n * C, G * D),
      v.reshape(B, n * C, G * D), state)
    den = jnp.moveaxis(den, (2, 4), (1, 2)).reshape(B, n * C, H)
    return retention._quotient(num.reshape(B, n * C, H, D), den)[:, :T], state


def _kernel(a_ref, last_ref, q_ref, k_ref, v_ref, s_ref, o_ref, d_ref, so_ref,
            qs_ref, qa_ref, num_ref, den_ref, z_ref, *, group: int):
    """Grid step ``(b, g, c)``: chunk ``c`` of row ``b`` against key-value
    head ``g``'s state, which ``so_ref`` holds from chunk 0 on (``z`` in
    ``z_ref``, a diagonal a tile, until the last chunk lays it back)."""
    n_diag = s_ref.shape[0] - 1
    C, D = k_ref.shape
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _take():
        so_ref[...] = s_ref[...]
        for e in range(n_diag):
            z_ref[e] = jnp.broadcast_to(s_ref[n_diag, e:e + 1, :], (8, D))

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   precision=HIGHEST,
                                   preferred_element_type=jnp.float32)

    # A_s along the lanes and A_t along the sublanes of one [C, C] tile
    a_s = jnp.broadcast_to(a_ref[...], (C, C))
    a_t = a_s.T
    t = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    seen = jnp.exp(jnp.where(s <= t, a_t - a_s, -jnp.inf))
    col = a_t[:, :1]                                        # [C, 1]: A_t
    decay = jnp.broadcast_to(jnp.exp(col), (C, D))          # exp(A_t)
    left = jnp.broadcast_to(jnp.exp(a_s[:, C - 1:] - col), (C, D))
    last = last_ref[...]                                    # [1, D]: exp(A_C)
    k, v = k_ref[...], v_ref[...]
    k_left = k * left
    # v^T over eight rows of ones: the product with phi_e(k) is S_e's write
    # and, in its last rows, z_e's
    v_ones = jnp.concatenate([v.T, jnp.ones((8, C), jnp.float32)], axis=0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, D), 1)
    for h in range(group):      # the chunk's own part, a query head a block
        at = slice(h * C, (h + 1) * C)
        q = q_ref[:, h * D:(h + 1) * D]
        qs_ref[at, :] = q
        qa_ref[at, :] = q * decay
        scores = dot(q, k, ((1,), (1,)))                    # [t, s]
        scores = scores * scores * seen
        num_ref[at, :] = dot(scores, v, ((1,), (0,)))
        den_ref[at, :] = jnp.where(
            lane == 0, jnp.sum(scores, axis=1, keepdims=True), 0.0)

    half, root = D // 2, math.sqrt(2.0)

    def diagonal(e, carry):
        weight = jnp.where((e == 0) | (e == half), 1.0, root)   # c_e
        S, z = so_ref[e], z_ref[e]
        fq = qa_ref[...] * pltpu.roll(qs_ref[...], e, axis=1)   # over c_e
        num_ref[...] += dot(fq, S * weight, ((1,), (1,)))
        den_ref[...] += fq * (z[:1] * weight)
        fk = k_left * pltpu.roll(k, e, axis=1) * weight
        write = dot(v_ones, fk, ((1,), (0,)))                   # [D + 8, D]
        so_ref[e] = last * S + write[:D]
        z_ref[e] = last * z + write[D:]
        return carry

    # a loop, not 65 bodies: unrolled by 13 the step is 2.7% faster (8.19 ms
    # for 8.42 at [1, 2048]: scripts/retention_chunk_bench.py on the v5e)
    jax.lax.fori_loop(0, n_diag, diagonal, 0)
    ones = jnp.ones((8, D), jnp.float32)
    for h in range(group):
        at = slice(h * C, (h + 1) * C)
        o_ref[:, h * D:(h + 1) * D] = num_ref[at, :]
        # the lanes' sum a position, the positions along the lanes of a row
        d_ref[h:h + 1, :] = dot(ones, den_ref[at, :], ((1,), (1,)))[:1]

    @pl.when(c == pl.num_programs(2) - 1)
    def _lay_back():
        for e in range(n_diag):
            so_ref[n_diag, e:e + 1, :] = z_ref[e, :1, :]
