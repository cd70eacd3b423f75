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
neither read nor written, and no copy of the stack exists beside it. Inside
a step the heads are walked one at a time, a ``[head_dim, state]`` tile
each, ``state`` along the lanes: ``dt x`` arrives transposed ``[head_dim,
heads]`` so that a head's column broadcasts along the lanes, the output
leaves as a column of ``[head_dim, heads]``. Float32 throughout.

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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

# a slot's block of the layer's state in and out, each double-buffered: four
# times 2 MB at 64 heads of 64 x 128, four times 4 MB at 32 of 128 x 256,
# beside the 16 MB the compiler grants a kernel by default
_VMEM_LIMIT = 48 * 1024 * 1024


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
    in_specs = [state_spec, row_spec(P, H), row_spec(1, H), row_spec(G, N),
                row_spec(G, N)]
    out_specs = [state_spec, row_spec(P, H)]
    out_shape = [jax.ShapeDtypeStruct(state.shape, state.dtype),
                 jax.ShapeDtypeStruct((slots, P, H), f32)]
    operands = [state, dtx.transpose(0, 2, 1), decay[:, None, :], b, c]
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
        functools.partial(_kernel, heads=H, rep=H // G,
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
    # a row no grid step wrote holds whatever the buffer held
    y = jnp.where(active[:, None, None], out[1].transpose(0, 2, 1), 0.0)
    return (y, out[0]) + tuple(out[2:])


def _kernel(_rows_ref, n_ref, _layer_ref, s_ref, dtx_ref, decay_ref, b_ref,
            c_ref, *rest, heads: int, rep: int, window: bool):
    """Grid step ``i``: the state of the ``i``-th live slot in the layer,
    [H, P, N], a head at a time. Steps past the live slots name the last
    live slot again: its blocks stay where they are and nothing is done;
    with no live slot at all, step 0 hands slot 0's state back as it
    was."""
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
