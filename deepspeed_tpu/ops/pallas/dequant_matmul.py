"""Dequant-fused matmul for the quantized-collective wire format.

``comm/quantized.py`` moves ZeRO traffic as per-block *affine* payloads
(uint8 ``q`` + fp32 ``scale``/``zero_point`` per trailing-dim block, possibly
edge-padded to a block multiple). The straightforward consumption path
materializes the dequantized fp copy (``dequantize_blockwise`` then matmul) —
an extra HBM-resident buffer per gathered window, and an extra HBM round trip
on the weight bytes. This kernel consumes the payload directly:

    out = x @ (q * scale + zero_point)        # dequantized per VMEM tile

so the int payload is the only resident wire artifact; dequantization happens
in the matmul's prologue on a ``(block_d, block_f)`` tile already in VMEM.
Same idea as :mod:`.int8_matmul` (the inference-side symmetric groupwise
format) but for the comm wire layout: affine (zero-point) blocks along the
trailing dimension, uint8 payload, possible edge padding trimmed at the end.

Off-TPU (or for ineligible shapes) the dispatcher falls back to XLA
``x @ dequantize_blockwise(...)`` — the payload is consumed by a reshape +
elementwise affine that XLA fuses into the matmul operand read, and the uint8
buffer is dead (donatable) after that single use, so no *persistent* fp copy
exists there either.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

_LANE = 128
_SUBLANE = 8


def _kernel(x_ref, q_ref, s_ref, z_ref, o_ref, acc_ref, *, n_d: int,
            block: int):
    di = pl.program_id(2)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = q_ref[...].astype(jnp.float32)   # [bd, bf] u8 -> f32
    s = s_ref[0]                         # [bd, bf // block] f32
    z = z_ref[0]                         # [bd, bf // block] f32
    bd, bf = w.shape
    w = (w.reshape(bd, bf // block, block) * s[:, :, None]
         + z[:, :, None]).reshape(bd, bf)
    x = x_ref[...].astype(jnp.float32)   # [bm, bd]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(di == n_d - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _eligible(M: int, D: int, Fp: int, block: int, block_m: int,
              block_d: int, block_f: int) -> bool:
    return (block % _LANE == 0
            and Fp % block == 0
            and M % block_m == 0 and D % block_d == 0 and Fp % block_f == 0
            and block_f % block == 0)


@functools.partial(jax.jit, static_argnames=("block", "block_m", "block_d",
                                             "block_f", "orig_size",
                                             "out_dtype"))
def _dequant_matmul_kernel_call(x, q, s2d, z2d, block, block_m, block_d,
                                block_f, orig_size, out_dtype):
    M, D = x.shape
    Fp = q.shape[1]
    nbf = block_f // block
    # scales/zero-points pre-tiled [Fp/block_f, D, nbf]: Mosaic requires a
    # block's trailing dim to be lane-divisible OR the full array dim — the
    # per-f-block tile (nbf columns) is only legal as a full trailing dim
    s3 = s2d.reshape(D, Fp // block_f, nbf).transpose(1, 0, 2)
    z3 = z2d.reshape(D, Fp // block_f, nbf).transpose(1, 0, 2)
    out = pl.pallas_call(
        functools.partial(_kernel, n_d=D // block_d, block=block),
        grid=(M // block_m, Fp // block_f, D // block_d),
        in_specs=[
            pl.BlockSpec((block_m, block_d), lambda mi, fi, di: (mi, di)),
            pl.BlockSpec((block_d, block_f), lambda mi, fi, di: (di, fi)),
            pl.BlockSpec((1, block_d, nbf), lambda mi, fi, di: (fi, di, 0)),
            pl.BlockSpec((1, block_d, nbf), lambda mi, fi, di: (fi, di, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_f), lambda mi, fi, di: (mi, fi)),
        out_shape=jax.ShapeDtypeStruct((M, Fp), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_f), jnp.float32)],
        interpret=_interpret(),
        name="dequant_matmul",
    )(x, q, s3, z3)
    return out[:, :orig_size]


def _kernel_enabled() -> bool:
    """Kernel path on a real TPU backend, or when interpret/Mosaic lowering is
    explicitly requested (tests / AOT flows). Unlike the tiny decode GEMMs in
    :mod:`.int8_matmul`, these are training-scale matmuls — interpret-mode
    execution on the CPU backend would be pathologically slow, so plain CPU
    runs take the XLA fallback unless DS_TPU_PALLAS_INTERPRET opts in."""
    return (jax.default_backend() == "tpu"
            or os.environ.get("DS_TPU_PALLAS_INTERPRET") is not None)


def dequant_matmul(x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray,
                   zero_point: jnp.ndarray, orig_size: int, bits: int = 8,
                   block_m: int = 256, block_d: int = 256,
                   block_f: int = 512) -> jnp.ndarray:
    """``x @ dequantize_blockwise(q, scale, zero_point)[:, :orig_size]``
    without materializing the dequantized weight in HBM.

    ``x``: [M, D] float. ``q``: [D, Fp] uint8 payload from
    :func:`~deepspeed_tpu.comm.quantized.quantize_blockwise` (8-bit; the
    packed int4 wire goes through the fallback). ``scale``/``zero_point``:
    [D, nb] fp32 per-block affine params; the block extent is ``Fp // nb``.
    ``orig_size``: the unpadded trailing dim of the weight.
    """
    from ...comm.quantized import dequantize_blockwise

    M, D = x.shape
    Dq, Fp = q.shape
    assert D == Dq, (x.shape, q.shape)
    if bits == 8:
        nb = scale.shape[-1]
        block = Fp // nb
        block_m = min(block_m, M)
        block_d = min(block_d, D)
        block_f = min(block_f, Fp)
        if (q.dtype == jnp.uint8 and _kernel_enabled()
                and _eligible(M, D, Fp, block, block_m, block_d, block_f)):
            return _dequant_matmul_kernel_call(
                x.astype(jnp.float32), q, scale.astype(jnp.float32),
                zero_point.astype(jnp.float32), block, block_m, block_d,
                block_f, orig_size, x.dtype)
    w = dequantize_blockwise(q, scale, zero_point, bits=bits,
                             orig_size=orig_size).astype(x.dtype)
    return x @ w
