"""One decode step of a power-retention layer (``models/retention.py``) over
the slots that hold a request, in Pallas, the states updated where they lie.

A slot's state in one layer is, a key-value head, ``D / 2 + 1`` blocks ``S_e``
[D_v, D] (a wrapped diagonal ``e`` of the key's products a block, a value
channel a sublane, the product's first index ``i`` a lane) and one block whose
first ``D / 2 + 1`` rows are the normaliser ``z``: ``[G, D / 2 + 2, D, D]``
float32, 4.3 MB a head and 34.6 MB a layer at Brumby's 8 heads of 128. A step
decays it, writes the token's key and value into it and reads the key-value
head's ``r`` query heads off it:

    S <- g S + phi(k) v^T;   z <- g z + phi(k)
    o_h = S^T phi(q_h) / (z . phi(q_h))            h = 0 .. r - 1

``phi`` (``models/retention.phi``) is a lane roll and a product a diagonal, so
neither ``phi(k)`` nor the ``phi(q_h)`` ever exist in HBM: a grid step makes
the ``r + 1`` tables ``[D / 2 + 1, D]`` once in VMEM from the rows it is
handed (33 KB each) and walks its 4.3 MB block against them. Nothing is read
back out of the state before the write (no delta rule), so a block is walked
once: 8 rows of a diagonal's block a tile, ``g S + v phi(k)`` stored and the
new tile times each ``phi(q_h)`` added to ``r`` accumulators that live in
registers across the ``D / 2 + 1`` diagonals. All of it on the VPU in
float32, element for element as the recurrence has it.

:func:`retention_decode` takes the whole stack ``[layers, slots, G, D / 2 + 2,
D, D]`` with a layer index and hands it back through ``input_output_aliases``,
its grid the step's LIVE slots (``ssm_decode.live_slots``) times the
key-value heads: a slot that holds no request is neither read nor written, and
no copy of the stack exists beside it. ``jax.numpy`` over the stack
(:func:`retention_decode_reference`) copies it.

What a grid step is handed beside its block: one ``[24, D]`` tile (the ``r``
queries a row each, the key and the gate in every sublane of their eight
rows) and the value laid along the sublanes, ``[D, D]`` with ``v_u`` in every
lane of row ``u`` (64 KB, 1.5% of the block: the wrapper makes it, a tile of
``v`` meets a state ROW). It hands back ``[8 r, D]``: query head ``h``'s
numerator in rows ``8 h ..``, value channel ``8 j + u`` at sublane ``u`` of
lane ``j`` (a row's lane sum kept where a select puts it, as
``ssm_decode._keep_row_sums`` does), and its denominator in lane ``D / 8``.

``impl``: None = the kernel on a TPU where the tiles take the shape (``D`` a
multiple of 128, at most 8 query heads a key-value head), the ``jax.numpy``
recurrence elsewhere; "kernel" forces Pallas (interpret mode off the TPU, any
``D`` that is a multiple of 8); "gather" the recurrence.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...models.retention import _quotient, phi
from .flash_attention import _interpret
from .ssm_decode import LANES, _VMEM_LIMIT, live_slots


def retention_decode_reference(state, layer, q, k, v, gate, active):
    """The recurrence in ``jax.numpy``: what :func:`retention_decode`
    computes. Rows that hold no request keep their state and give zeros."""
    B, H, D = q.shape
    G = k.shape[1]
    n = D // 2 + 1
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    fk = phi(k)                                             # [B, G, e, D]
    # the write as one array beside the state (no update in place: a block
    # of ``n`` rows inside one of ``D`` is no aligned slice)
    write = jnp.concatenate([
        fk[:, :, :, None, :] * v[:, :, None, :, None],
        jnp.pad(fk, ((0, 0), (0, 0), (0, D - n), (0, 0)))[:, :, None]],
        axis=2)
    new = s * gate[:, :, None, None, None] + write
    fq = phi(q.reshape(B, G, H // G, D))                    # [B, G, r, e, D]
    num = jnp.sum(fq[:, :, :, :, None, :] * new[:, :, None, :n], axis=(3, 5))
    den = jnp.sum(fq * new[:, :, None, n, :n], axis=(3, 4))
    o = _quotient(num, den).reshape(B, H, D)
    keep = active[:, None, None]
    new = jnp.where(keep[..., None, None], new, s)
    return (jnp.where(keep, o, 0.0),
            jax.lax.dynamic_update_index_in_dim(state, new, layer, 0))


# diagonals a trip of the walk's loop: 65 at a head of 128 are 13 trips
_UNROLL = 5


def _fits(D: int, group: int) -> bool:
    """The tiles take the shape on a TPU."""
    return D % LANES == 0 and group <= 8


def retention_decode(state: jnp.ndarray, layer, q: jnp.ndarray,
                     k: jnp.ndarray, v: jnp.ndarray, gate: jnp.ndarray,
                     active: jnp.ndarray, impl: Optional[str] = None,
                     live=None):
    """``state`` [L, slots, G, D / 2 + 2, D, D] float32, ``layer`` (it may be
    traced); for each slot ``q`` [slots, H, D], ``k`` and ``v`` [slots, G,
    D], ``gate`` [slots, G] (the decay itself, not its log); ``active``
    [slots] bool. Returns (``o`` [slots, H, D] float32, zeros in a row that
    holds no request; the stack, layer ``layer`` of the active slots
    updated). ``live``: ``ssm_decode.live_slots(active)``, the same for
    every layer of a step (built here without it)."""
    L, slots, G, blocks, D, _ = state.shape
    H = q.shape[1]
    if (q.shape != (slots, H, D) or k.shape != (slots, G, D) or H % G
            or blocks != D // 2 + 2):
        raise ValueError(
            f"a state stack of {slots} decode slots and {G} key-value heads "
            f"of {blocks} blocks of {D} x {D} takes a row a slot and whole "
            f"groups of query heads: got q {q.shape} and k {k.shape}")
    r = H // G
    if impl is None:
        impl = ("kernel" if jax.default_backend() == "tpu" and _fits(D, r)
                else "gather")
    f32 = jnp.float32
    q, k, v, gate = (a.astype(f32) for a in (q, k, v, gate))
    if impl == "gather":
        return retention_decode_reference(state, layer, q, k, v, gate, active)
    if impl != "kernel":
        raise ValueError(f"impl must be None, 'kernel' or 'gather': {impl!r}")
    if D % 8 or r > 8:
        raise ValueError(f"the kernel walks tiles of 8 value channels and "
                         f"hands a key-value head at most 8 queries: D {D}, "
                         f"{r} query heads a key-value head")
    if live is None:
        live = live_slots(active)
    rows, n_live = live
    # the r queries a row each (zeros up to 8), the key and the gate in every
    # sublane of their eight rows; v_u in every lane of row u
    rows_in = jnp.concatenate([
        jnp.pad(q.reshape(slots, G, r, D), ((0, 0), (0, 0), (0, 8 - r),
                                            (0, 0))),
        jnp.broadcast_to(k[:, :, None, :], (slots, G, 8, D)),
        jnp.broadcast_to(gate[:, :, None, None], (slots, G, 8, D))], axis=2)
    v_rows = jnp.broadcast_to(v[..., None], (slots, G, D, D))

    def head(i, g, n):
        """The step's key-value head; past the live slots (which name the
        last of them again) the last head again, so that no block moves."""
        return jnp.where(i < jnp.maximum(n[0], 1), g, G - 1)

    def spec(*block):
        return pl.BlockSpec((None, None) + block,
                            lambda i, g, rows, n, layer: (
                                rows[i], head(i, g, n)) + (0,) * len(block))

    state_spec = pl.BlockSpec(
        (None, None, None, blocks, D, D),
        lambda i, g, rows, n, layer: (layer[0], rows[i], head(i, g, n), 0, 0,
                                      0))
    new, y = pl.pallas_call(
        functools.partial(_kernel, group=r),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # live slots, their number, layer
            grid=(slots, G),
            in_specs=[state_spec, spec(24, D), spec(D, D)],
            out_specs=[state_spec, spec(8 * r, D)],
            scratch_shapes=[pltpu.VMEM((r + 1, blocks - 1, 8, D), f32),
                            pltpu.VMEM((r + 1, -(-(blocks - 1) // 8) * 8, D),
                                       f32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((slots, G, 8 * r, D), f32)],
        # operands count the scalar prefetch: the stack is the fourth
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="retention_decode",
    )(rows, n_live, jnp.asarray(layer, jnp.int32).reshape(1), state, rows_in,
      v_rows)
    # value channel 8 j + u at sublane u of lane j; the denominator beside
    y = y.reshape(slots, G, r, 8, D)
    num = jnp.swapaxes(y[..., :D // 8], -1, -2).reshape(slots, G, r, D)
    den = y[..., 0, D // 8]
    o = _quotient(num, den).reshape(slots, H, D)
    # a row no grid step wrote holds whatever the buffer held
    return jnp.where(active[:, None, None], o, 0.0), new


def _kernel(_rows_ref, n_ref, _layer_ref, s_ref, in_ref, v_ref, o_ref, y_ref,
            phi_ref, rows_ref, *, group: int):
    """Grid step ``(i, g)``: key-value head ``g`` of the ``i``-th live slot's
    state in the layer, ``[D / 2 + 2, D, D]``. Steps past the live slots name
    the last live slot again: its blocks stay where they are and nothing is
    done; with no live slot at all, the steps of slot 0 hand its state back
    as it was."""
    n_diag = s_ref.shape[0] - 1
    D = s_ref.shape[-1]
    z_rows = rows_ref.shape[1]
    i = pl.program_id(0)
    n = n_ref[0]

    @pl.when(i < n)
    def _update():
        gate = in_ref[16:24, :]                         # [8, D], all equal
        # the tables: phi of the key (0) and of each query (1 + h), a
        # diagonal in every sublane of its tile (``phi_ref``, what a state
        # tile meets) and a diagonal a row (``rows_ref``, what z meets)
        rows_ref[...] = jnp.zeros_like(rows_ref)
        for t in range(group + 1):
            a = (in_ref[8:16, :] if t == 0 else jnp.broadcast_to(
                in_ref[t - 1:t, :], (8, D)))
            rolled = a
            for e in range(n_diag):
                tile = a * rolled
                if 0 < e < D // 2:
                    tile = tile * math.sqrt(2.0)
                phi_ref[t, e] = tile
                rows_ref[t, e:e + 1, :] = tile[:1]
                rolled = pltpu.roll(rolled, 1, axis=1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, D), 1)
        tiles = [jnp.zeros((8, D), jnp.float32)] * group
        unroll = _UNROLL if n_diag % _UNROLL == 0 else 1
        for j in range(D // 8):     # 8 value channels of every diagonal
            at = slice(8 * j, 8 * j + 8)
            v_tile = v_ref[at, :]

            def trip(t, acc):
                for u in range(unroll):
                    e = t * unroll + u
                    new = gate * s_ref[e, at, :] + v_tile * phi_ref[0, e]
                    o_ref[e, at, :] = new
                    acc = tuple(a + new * phi_ref[1 + h, e]
                                for h, a in enumerate(acc))
                return acc

            acc = jax.lax.fori_loop(
                0, n_diag // unroll, trip,
                tuple(jnp.zeros((8, D), jnp.float32) for _ in range(group)))
            # a row's lane sum in every lane of it; tile j keeps lane j
            tiles = [jnp.where(lane == j, jnp.sum(a, axis=-1, keepdims=True),
                               t) for a, t in zip(acc, tiles)]
        # the normaliser: a diagonal a row of the last block
        z = gate[:1] * s_ref[n_diag, :z_rows, :] + rows_ref[0]
        o_ref[n_diag, :z_rows, :] = z
        if z_rows < D:
            o_ref[n_diag, z_rows:, :] = s_ref[n_diag, z_rows:, :]
        for h in range(group):
            y_ref[8 * h:8 * h + 8, :] = jnp.where(
                lane == D // 8, jnp.sum(z * rows_ref[1 + h]), tiles[h])

    @pl.when((n == 0) & (i == 0))
    def _untouched():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)
