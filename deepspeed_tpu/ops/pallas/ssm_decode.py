"""One decode step of a Mamba-2 layer's recurrence over the slots that hold a
request, in Pallas, the states updated where they lie.

A slot's state in one layer is a ``[heads, head_dim, state]`` float32 array
(2 MB at Nemotron-3-Nano's 64 x 64 x 128, 4 MB at Falcon-H1's 32 x 128 x 256):
a step decays it, adds the token's outer product and reads the output off it,

    S <- exp(dt A) S + (dt x) B^T          y = S C

with ``dt``, ``A`` a head, ``x`` ``[head_dim]`` a head and ``B``, ``C``
``[state]`` a group of heads (8 groups of 8 there, 2 of 16 here). Little else
of a step moves as many bytes: Nemotron's 512 slots in four layers are 4.3 GB
read and written (held experts 5.1 read), Falcon-H1's 96 in six layers 4.9 GB.

:func:`ssm_decode` takes the whole stack ``[layers, slots, heads, head_dim,
state]`` with a layer index and hands it back through
``input_output_aliases``: the kernel's grid is the step's LIVE slots (a list
in scalar prefetch, the live slots first; the entries past them repeat the
last live slot, so that they move no block and do nothing), one slot's state
of the layer a step, every head of it: a slot that holds no request is
neither read nor written, and no copy of the stack exists beside it.

**How a step walks its block** (:func:`_plan`): a pass of heads at a time, as
whole ``[8, 128]`` tiles. The heads of a pass share ``B`` and ``C``; their
block ``[heads, head_dim, state]`` is taken as ``[rows, state]``, a state row
a sublane, and every operand reaches it without a lane slice, and leaves it
without a one-lane store, a head:

- ``dt x`` arrives as it lies, ``[heads x head_dim / 128, 128]``, and is laid
  along the sublanes by the MXU, 128 state rows a product: its three bfloat16
  pieces ``[24, 128]``, contracted over their rows with a one-hot ``[24,
  128]``, give ``[128, 128]`` with row ``u`` holding ``dt x`` of state row
  ``u`` in every lane. Each product term is a bfloat16 piece times 1 and the
  three sum to the float32 value exactly, so one pass at the MXU's default
  precision moves the values unchanged; the decay arrives a row of 128 lanes
  a head and is broadcast along the sublanes.
- The state's arithmetic is ``S * decay + dtx * B`` in float32 on the VPU,
  element for element as the recurrence has it: the new state is the parent
  walk's bit for bit (my chip runs, PERF.md PR 48).
- The read-out ``y = S C`` stays a float32 multiply and a sum along the
  lanes, a pass at a time: timed with no block moving, that walk takes 1.5
  us a slot at Nemotron's sizes where the same read-out as an MXU product at
  full precision (six passes) takes 5.2 (PERF.md, PR 48), both under the 6.7
  the block's two copies take, and it rounds nothing to bfloat16: ``y`` too
  is the parent walk's bit for bit. A row's sum comes back in every lane;
  tile ``k`` keeps lane ``k`` (one select over the pass, summed over its
  tiles), and the output leaves as ``[8, heads x head_dim / 8]`` in whole
  tiles (XLA turns it back).
- Every operation takes a pass's rows whole, a few dozen a pass: the kernel
  is traced for every layer of every program that holds it, and a first form
  with three operations a tile (64 tiles a pass) ran as fast and cost
  ``chat-decode`` 45 s of set-up on a warm compile cache (PERF.md, PR 48).

What the walk a head at a time paid for (my chip runs, PERF.md PR 48, no
block moving, us a slot at Nemotron's sizes): 11.4 with the decay sliced a
lane a head out of ``[1, heads]``, 6.9 with the decay a row of lanes a head
and all else as it was, 2.8 a group of heads at a time, 1.5 with ``dt x``
laid out by the MXU; its one-lane stores of ``y`` moved nothing measurable.
A shape the tiles cannot take (``head_dim`` no multiple of 8, ``state`` no
multiple of 128, ``heads x head_dim`` no multiple of 1024) is walked a head
at a time, as before, in the same kernel. Float32 throughout.

The same call shifts the layer's **convolution window** of each live slot,
``[K - 1, conv_width]`` float32 in a stack ``[layers, slots, K - 1,
conv_width]`` handed back through a second alias: the oldest row goes, the
step's new row (``xBC`` before the convolution) comes last. XLA reads the old
window before the call, for the convolution whose output the call's other
inputs are made from, and never writes the stack: left to XLA the update was
a ``dynamic_update_slice`` of the donated stack, which the TPU compiler
rematerialised at 448 slots and more, reading a window it had already
shifted (the served logits' rms 1.1 off the reference after eight steps: my
chip runs, PERF.md PR 40).

``impl``: None = the kernel on a TPU, the ``jax.numpy`` recurrence
(:func:`ssm_decode_reference`) elsewhere; "kernel" forces Pallas (interpret
mode off the TPU); "gather" the recurrence.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

# a slot's block of the layer's state in and out, each double-buffered: four
# times 2 MB at 64 heads of 64 x 128, four times 4 MB at 32 of 128 x 256,
# beside the 16 MB the compiler grants a kernel by default
_VMEM_LIMIT = 48 * 1024 * 1024
LANES = 128
# a pass's rows of the state, as values between its load and its store
_PASS_BYTES = 1024 * 1024


class Walk(NamedTuple):
    """How a grid step walks its slot's ``[H, P, N]`` block as whole tiles."""
    heads: int      # heads a pass: they share B and C
    rows: int       # state rows a pass, heads x head_dim: a multiple of 128
    products: int   # one-hot products a pass, rows / 128: [24, 128] pieces
                    # of dt x contracted with a one-hot [24, 128]


def _plan(H: int, P: int, N: int, G: int) -> Optional[Walk]:
    """The walk of one grid step from the call's shapes alone: the most heads
    of one group whose rows are whole blocks of 128 and fit ``_PASS_BYTES``;
    None where the tiles cannot take the shape, and the block is walked a
    head at a time: ``head_dim`` no multiple of 8 or ``state`` of 128 (a head
    is no whole tiles), ``heads x head_dim`` no multiple of 1024 (``dt x`` as
    it lies is no whole tiles), or no such pass. 8 heads a pass at both
    Nemotron-3-Nano's 64 x 64 x 128 in 8 groups (512 rows, a group) and
    Falcon-H1's 32 x 128 x 256 in 2 (1024 rows, half a group)."""
    rep = H // G
    if P % 8 or N % LANES or (H * P) % (8 * LANES):
        return None
    fits = [h for h in range(1, rep + 1)
            if rep % h == 0 and (h * P) % LANES == 0
            and h * P * N * 4 <= _PASS_BYTES]
    if not fits:
        return None
    heads = max(fits)
    return Walk(heads, heads * P, heads * P // LANES)


def live_slots(active: jnp.ndarray):
    """(the slots that hold a request first, in order, then the last of them
    repeated [B] int32; how many hold one [1] int32) from ``active`` [B]
    bool: the kernel's grid. With none live every entry is slot 0."""
    B = active.shape[0]
    n = active.sum().astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n - 1, 0)]
    live = jnp.where(jnp.arange(B) < n, order, jnp.where(n > 0, last, 0))
    return live.astype(jnp.int32), n.reshape(1)


def _shifted(windows, layer, new_row, active):
    """The window stack with layer ``layer`` of the active slots shifted by
    ``new_row`` [slots, C], in ``jax.numpy``."""
    old = jax.lax.dynamic_index_in_dim(windows, layer, 0, keepdims=False)
    new = jnp.concatenate([old[:, 1:], new_row[:, None].astype(old.dtype)],
                          axis=1)
    return jax.lax.dynamic_update_index_in_dim(
        windows, jnp.where(active[:, None, None], new, old), layer, 0)


def ssm_decode_reference(state, layer, dtx, decay, b, c, active):
    """The recurrence in ``jax.numpy``: what :func:`ssm_decode` computes.
    Rows that hold no request keep their state and give zeros."""
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    heads = s.shape[1]
    rep = heads // b.shape[1]
    bh = jnp.repeat(b, rep, axis=1)                         # [B, H, N]
    ch = jnp.repeat(c, rep, axis=1)
    new = (s * decay[:, :, None, None]
           + dtx[:, :, :, None] * bh[:, :, None, :])
    y = jnp.sum(new * ch[:, :, None, :], axis=-1)           # [B, H, P]
    keep = active[:, None, None]
    new = jnp.where(keep[..., None], new, s)
    return (jnp.where(keep, y, 0.0),
            jax.lax.dynamic_update_index_in_dim(state, new, layer, 0))


def ssm_decode(state: jnp.ndarray, layer, dtx: jnp.ndarray,
               decay: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
               active: jnp.ndarray, impl: Optional[str] = None, live=None,
               windows: Optional[jnp.ndarray] = None,
               new_row: Optional[jnp.ndarray] = None):
    """``state`` [L, slots, H, P, N] float32, ``layer`` (it may be traced);
    for each slot ``dtx`` [slots, H, P] (``dt x``), ``decay`` [slots, H]
    (``exp(dt A)``), ``b`` and ``c`` [slots, G, N] (head ``i`` reads group
    ``i // (H / G)``); ``active`` [slots] bool. Returns (``S C`` [slots, H,
    P] float32, zeros in a row that holds no request; the stack, layer
    ``layer`` of the active slots updated). ``live``: :func:`live_slots` of
    ``active``, the same for every layer of a step (built here without
    it). With ``windows`` [L, slots, K - 1, C] float32 and ``new_row``
    [slots, C], third: the windows, layer ``layer`` of the active slots
    shifted by their new row."""
    L, slots, H, P, N = state.shape
    G = b.shape[1]
    if dtx.shape != (slots, H, P) or H % G:
        raise ValueError(
            f"a state stack of {slots} decode slots and {H} heads of {P} "
            f"takes a row a slot: got dt x {dtx.shape} and {G} groups")
    if impl is None:
        impl = "kernel" if jax.default_backend() == "tpu" else "gather"
    f32 = jnp.float32
    dtx, decay, b, c = (a.astype(f32) for a in (dtx, decay, b, c))
    if impl == "gather":
        out = ssm_decode_reference(state, layer, dtx, decay, b, c, active)
        return out if windows is None else out + (
            _shifted(windows, layer, new_row, active),)
    if impl != "kernel":
        raise ValueError(f"impl must be None, 'kernel' or 'gather': {impl!r}")
    if live is None:
        live = live_slots(active)
    rows, n_live = live

    def row_spec(*block):
        return pl.BlockSpec((1,) + block,
                            lambda i, rows, n, layer: (rows[i],)
                            + (0,) * len(block))

    state_spec = pl.BlockSpec(
        (None, 1, H, P, N),
        lambda i, rows, n, layer: (layer[0], rows[i], 0, 0, 0))
    walk = _plan(H, P, N, G)
    if walk is None:    # a head at a time: its column of dt x [P, H], y too
        dtx_spec, dtx = row_spec(P, H), dtx.transpose(0, 2, 1)
        decay_spec, decay = row_spec(1, H), decay[:, None, :]
        y_block = (P, H)
    else:               # dt x as it lies, the decay a row of lanes a head
        dtx_spec = row_spec(H * P // LANES, LANES)
        dtx = dtx.reshape(slots, H * P // LANES, LANES)
        decay_spec = row_spec(H, LANES)
        decay = jnp.broadcast_to(decay[:, :, None], (slots, H, LANES))
        y_block = (8, H * P // 8)
    in_specs = [state_spec, dtx_spec, decay_spec, row_spec(G, N),
                row_spec(G, N)]
    out_specs = [state_spec, row_spec(*y_block)]
    out_shape = [jax.ShapeDtypeStruct(state.shape, state.dtype),
                 jax.ShapeDtypeStruct((slots,) + y_block, f32)]
    operands = [state, dtx, decay, b, c]
    # operands count the scalar prefetch: the stack is the fourth
    aliases = {3: 0}
    if windows is not None:
        K1, C = windows.shape[2:]
        window_spec = pl.BlockSpec(
            (None, 1, K1, C),
            lambda i, rows, n, layer: (layer[0], rows[i], 0, 0))
        in_specs += [window_spec, row_spec(1, C)]
        out_specs.append(window_spec)
        out_shape.append(jax.ShapeDtypeStruct(windows.shape, windows.dtype))
        operands += [windows, new_row.astype(windows.dtype)[:, None, :]]
        aliases[8] = 2
    out = pl.pallas_call(
        functools.partial(_kernel, heads=H, rep=H // G, walk=walk,
                          window=windows is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # live slots, their number, layer
            grid=(slots,), in_specs=in_specs, out_specs=out_specs),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="ssm_decode",
    )(rows, n_live, jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    # [P, H] a head at a time; [8, H P / 8] in tiles: state row 8 k + r at
    # sublane r of lane k
    y = out[1].transpose(0, 2, 1).reshape(slots, H, P)
    # a row no grid step wrote holds whatever the buffer held
    y = jnp.where(active[:, None, None], y, 0.0)
    return (y, out[0]) + tuple(out[2:])


def _pieces(x):
    """``x`` float32 as three float32 arrays that bfloat16 holds exactly and
    that sum to it exactly: the leading 8 bits of the mantissa, the next 8,
    the last 8."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    rest = x - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, mid, rest - mid


def _walk_tiles(walk: Walk, heads: int, rep: int, s_ref, dtx_ref, decay_ref,
                b_ref, c_ref, o_ref, y_ref):
    """A live slot's ``[H, P, N]`` block, ``walk.heads`` heads a pass, in
    operations over a pass's rows whole (the module's text says why)."""
    P, N = s_ref.shape[2:]
    f32 = jnp.float32
    tiles = walk.rows // 8
    piece_row = jax.lax.broadcasted_iota(jnp.int32, (24, LANES), 0) % 8
    ones = [jnp.where(piece_row == r, 1.0, 0.0).astype(f32) for r in range(8)]
    # tile k of a pass keeps lane (k0 + k) % 128 of its rows' sums
    tile = jax.lax.broadcasted_iota(jnp.int32, (tiles, 8, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tiles, 8, LANES), 2)
    pieces = {}     # a tile of dt x (1024 state rows) as [24, 128], once
    for h0 in range(0, heads, walk.heads):
        g, at = h0 // rep, slice(h0, h0 + walk.heads)
        decay = jnp.broadcast_to(
            decay_ref[0, at][:, None, :], (walk.heads, P, LANES)).reshape(
                walk.rows, LANES)
        # block q of 128 state rows is row q of dt x as it lies: row q % 8
        # of tile q // 8. Contracted over the 24 rows with ones in rows
        # q % 8 of the three pieces, [128, 128]: dt x of state row u in
        # every lane of row u, the float32 value exactly
        blocks = []
        for q in range(h0 * P // LANES, h0 * P // LANES + walk.products):
            t, r = divmod(q, 8)
            if t not in pieces:
                pieces[t] = jnp.concatenate(
                    _pieces(dtx_ref[0, 8 * t:8 * t + 8, :]), axis=0)
            blocks.append(jax.lax.dot_general(
                pieces[t], ones[r], (((0,), (0,)), ((), ())),
                preferred_element_type=f32))
        dtx = jnp.concatenate(blocks, axis=0)                   # [rows, 128]
        read = None
        for j in range(0, N, LANES):
            new = (s_ref[0, at, :, j:j + LANES].reshape(walk.rows, LANES)
                   * decay + dtx * b_ref[0, g:g + 1, j:j + LANES])
            o_ref[0, at, :, j:j + LANES] = new.reshape(walk.heads, P, LANES)
            part = new * c_ref[0, g:g + 1, j:j + LANES]
            read = part if read is None else read + part
        # a row's sum in every lane of it, [tiles, 8, 128]
        read = jnp.broadcast_to(jnp.sum(read, axis=-1, keepdims=True),
                                (walk.rows, LANES)).reshape(tiles, 8, LANES)
        k0 = h0 * P // 8            # y's lanes k0 ... hold the pass's tiles
        kept = jnp.where(lane == (tile + k0) % LANES, read, 0.0)
        k = 0
        while k < tiles:            # tiles that share 128 lanes of y
            at_lane = (k0 + k) % LANES
            upto = min(tiles, k + LANES - at_lane)
            y_ref[0, :, k0 + k:k0 + upto] = jnp.sum(kept[k:upto], axis=0)[
                :, at_lane:at_lane + upto - k]
            k = upto


def _kernel(_rows_ref, n_ref, _layer_ref, s_ref, dtx_ref, decay_ref, b_ref,
            c_ref, *rest, heads: int, rep: int, walk: Optional[Walk],
            window: bool):
    """Grid step ``i``: the state of the ``i``-th live slot in the layer,
    [H, P, N], by ``walk`` (:func:`_plan`; None: a head at a time). Steps
    past the live slots name the last live slot again: its blocks stay where
    they are and nothing is done; with no live slot at all, step 0 hands
    slot 0's state back as it was."""
    if window:
        w_ref, row_ref, o_ref, y_ref, wo_ref = rest
    else:
        o_ref, y_ref = rest
    i = pl.program_id(0)
    n = n_ref[0]

    @pl.when(i < n)
    def _update():
        if window:      # the oldest row goes, the step's row comes last
            wo_ref[0, :-1] = w_ref[0, 1:]
            wo_ref[0, -1:] = row_ref[0]
        if walk is not None:
            _walk_tiles(walk, heads, rep, s_ref, dtx_ref, decay_ref, b_ref,
                        c_ref, o_ref, y_ref)
            return
        for h in range(heads):
            g = h // rep
            new = (s_ref[0, h] * decay_ref[0, :, h:h + 1]
                   + dtx_ref[0, :, h:h + 1] * b_ref[0, g:g + 1, :])
            o_ref[0, h] = new
            y_ref[0, :, h:h + 1] = jnp.sum(new * c_ref[0, g:g + 1, :],
                                           axis=-1, keepdims=True)

    @pl.when((n == 0) & (i == 0))
    def _untouched():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)
        if window:
            wo_ref[...] = w_ref[...]
