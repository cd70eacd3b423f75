"""int8-weight matmul: dequantize per VMEM tile, never in HBM.

Capability parity with the reference's int8 inference GEMMs, which consume
quantized weights directly and dequantize inside the kernel
(``csrc/transformer/inference/csrc/dequantize.cu`` + the GEMM bindings in
``pt_binding.cpp``). On TPU this matters twice over for decode:

1. HBM CAPACITY — XLA-level dequantize-then-matmul materializes bf16 weight
   buffers (and, measured at 13B, layout-transposed copies of the s8 stacks);
   the kernel reads s8 straight from HBM and widens only a (block_d, block_f)
   tile in VMEM.
2. HBM BANDWIDTH — single-token decode is weight-bandwidth-bound, so moving
   s8 instead of bf16 halves the bytes per step: the same lever the
   reference's dequant-fused GEMMs pull on V100.

Quantization layout matches ``ops/quantizer/quantize`` as used by
``models/gpt.quantize_for_inference``: a weight [D, F] is flattened row-major
and split into contiguous ``group_size`` runs, so with ``F % group_size == 0``
the scales reshape to [D, F // group_size] — each scale covers a run along F
within one row.

Grid = (F / block_f, D / block_d): the contraction (D) axis is innermost, so
the f32 accumulator lives in VMEM scratch across its steps; x stays whole
(decode M = B*T is tiny) with rows padded to the 8-sublane tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

_LANE = 128
_SUBLANE = 8


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_d: int, group: int):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = q_ref[...].astype(jnp.float32)  # [bd, bf] s8 -> f32
    s = s_ref[0]  # [bd, bf // group] f32 (scales pre-tiled per f-block)
    bd, bf = w.shape
    w = (w.reshape(bd, bf // group, group) * s[:, :, None]).reshape(bd, bf)
    x = x_ref[...].astype(jnp.float32)  # [M, bd]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(di == n_d - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


_MAX_M = 256  # beyond this (large prefill) x + the f32 accumulator overflow
# VMEM — the XLA fallback is compute-bound there anyway


def _on_tpu() -> bool:
    """Kernel path when: on a TPU backend, in interpret mode (tests), OR
    when real Mosaic lowering is forced (DS_TPU_PALLAS_INTERPRET=0 — the
    AOT compile-only flow targets a TPU topology from a CPU host, where
    default_backend() says "cpu" but the program IS for TPU). Shared by the
    int8 and int4 dispatchers so the policy cannot diverge."""
    import os

    return (jax.default_backend() == "tpu" or _interpret()
            or os.environ.get("DS_TPU_PALLAS_INTERPRET") == "0")


def _eligible(M: int, D: int, F: int, group: int, block_d: int,
              block_f: int) -> bool:
    return (M <= _MAX_M
            and F % group == 0 and group % _LANE == 0
            and D % block_d == 0 and F % block_f == 0
            and block_f % group == 0)


@functools.partial(jax.jit, static_argnames=("group", "block_d", "block_f",
                                             "out_dtype"))
def _int8_matmul_kernel_call(x, q, s2d, group, block_d, block_f, out_dtype):
    M, D = x.shape
    F = q.shape[1]
    Mp = max(_SUBLANE, ((M + _SUBLANE - 1) // _SUBLANE) * _SUBLANE)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    # scales pre-tiled [F/block_f, D, block_f/group]: Mosaic requires a
    # block's last dim to be lane-divisible OR the full array dim — the
    # per-f-block scale tile (block_f/group columns) is only legal as a
    # full trailing dim
    nf = block_f // group
    s3 = s2d.reshape(D, F // block_f, nf).transpose(1, 0, 2)
    out = pl.pallas_call(
        functools.partial(_kernel, n_d=D // block_d, group=group),
        grid=(F // block_f, D // block_d),
        in_specs=[
            pl.BlockSpec((Mp, block_d), lambda fi, di: (0, di)),
            pl.BlockSpec((block_d, block_f), lambda fi, di: (di, fi)),
            pl.BlockSpec((1, block_d, nf), lambda fi, di: (fi, di, 0)),
        ],
        out_specs=pl.BlockSpec((Mp, block_f), lambda fi, di: (0, fi)),
        out_shape=jax.ShapeDtypeStruct((Mp, F), out_dtype),
        scratch_shapes=[pltpu.VMEM((Mp, block_f), jnp.float32)],
        interpret=_interpret(),
        name="int8_matmul",
    )(x, q, s3)
    return out[:M]


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Pack int4 values (stored sign-extended in int8, range [-8, 7]) two per
    byte along the LAST axis, half-split: byte j holds ``w[..., j]`` in its
    low nibble and ``w[..., j + F/2]`` in its high nibble. Half-split (vs
    pairwise interleave) keeps the kernel's unpack a lane-aligned
    whole-tile op — each output f-block reads one nibble of one packed tile.
    """
    F = q.shape[-1]
    assert F % 2 == 0, f"int4 packing needs an even last dim, got {F}"
    lo = q[..., : F // 2].astype(jnp.int32) & 0xF
    hi = q[..., F // 2:].astype(jnp.int32)
    return (lo | (hi << 4)).astype(jnp.int8)


def _unpack_nibble(t: jnp.ndarray, high: bool) -> jnp.ndarray:
    """Sign-extended int4 from a packed int32 tile (xor-sub trick)."""
    nib = ((t >> 4) if high else t) & 0xF
    return (nib ^ 8) - 8


def unpack_int4(q4: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int4`: [., F/2] packed -> [., F] int8."""
    t = q4.astype(jnp.int32)
    return jnp.concatenate(
        [_unpack_nibble(t, False), _unpack_nibble(t, True)],
        axis=-1).astype(jnp.int8)


def _kernel4(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_d: int, group: int):
    """One grid step consumes ONE packed tile and emits BOTH output halves
    (lo nibble -> output block fi, hi nibble -> block fi + n_f/2, stacked on
    the output's leading axis) — each packed byte is read from HBM exactly
    once per matmul, so decode weight traffic is a true QUARTER of bf16."""
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = q_ref[...].astype(jnp.int32)  # [bd, bf] packed bytes
    bd, bf = t.shape
    # [bd, 2*bf]: lo-half columns then hi-half columns
    w = jnp.concatenate(
        [_unpack_nibble(t, False), _unpack_nibble(t, True)],
        axis=1).astype(jnp.float32)
    s = s_ref[0]  # [bd, 2 * bf // group] f32 (lo-block + hi-block scales)
    w = (w.reshape(bd, 2 * bf // group, group)
         * s[:, :, None]).reshape(bd, 2 * bf)
    x = x_ref[...].astype(jnp.float32)  # [M, bd]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(di == n_d - 1)
    def _out():
        o_ref[0] = acc_ref[:, :bf].astype(o_ref.dtype)
        o_ref[1] = acc_ref[:, bf:].astype(o_ref.dtype)


def _eligible4(M: int, D: int, F: int, group: int, block_d: int,
               block_f: int) -> bool:
    n_f = F // block_f if F % block_f == 0 else 0
    return (M <= _MAX_M
            and F % group == 0 and group % _LANE == 0
            and D % block_d == 0 and F % block_f == 0
            and n_f % 2 == 0  # halves must tile into whole f-blocks
            and block_f % group == 0)


@functools.partial(jax.jit, static_argnames=("group", "block_d", "block_f",
                                             "out_dtype"))
def _int4_matmul_kernel_call(x, q4, s2d, group, block_d, block_f, out_dtype):
    M, D = x.shape
    F = q4.shape[1] * 2
    n_f = F // block_f
    nh = n_f // 2  # packed f-blocks (each serves two output blocks)
    Mp = max(_SUBLANE, ((M + _SUBLANE - 1) // _SUBLANE) * _SUBLANE)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    nf = block_f // group
    # scales for packed block k = output blocks k (lo) and k + n_f/2 (hi),
    # paired along the trailing dim: [nh, D, 2*nf]
    s3 = s2d.reshape(D, n_f, nf).transpose(1, 0, 2)
    s3 = jnp.concatenate([s3[:nh], s3[nh:]], axis=-1)
    out = pl.pallas_call(
        functools.partial(_kernel4, n_d=D // block_d, group=group),
        grid=(nh, D // block_d),
        in_specs=[
            pl.BlockSpec((Mp, block_d), lambda fi, di: (0, di)),
            pl.BlockSpec((block_d, block_f), lambda fi, di: (di, fi)),
            pl.BlockSpec((1, block_d, 2 * nf), lambda fi, di: (fi, di, 0)),
        ],
        # halves stacked on a leading axis: one grid step writes both
        out_specs=pl.BlockSpec((2, Mp, block_f), lambda fi, di: (0, 0, fi)),
        out_shape=jax.ShapeDtypeStruct((2, Mp, F // 2), out_dtype),
        scratch_shapes=[pltpu.VMEM((Mp, 2 * block_f), jnp.float32)],
        interpret=_interpret(),
        name="int4_matmul",
    )(x, q4, s3)
    return jnp.concatenate([out[0], out[1]], axis=-1)[:M]


def int4_matmul(x: jnp.ndarray, q4: jnp.ndarray, s: jnp.ndarray,
                group_size: int = 128, block_d: int = 256,
                block_f: int = 512) -> jnp.ndarray:
    """``x @ dequantize(unpack_int4(q4), s)`` without materializing the bf16
    (or even the unpacked s8) weight: nibbles widen per VMEM tile.

    x: [M, D]; q4: [D, F//2] packed int8 (:func:`pack_int4` half-split
    layout); s: flat scales for row-major ``group_size`` runs of the
    UNPACKED [D, F] weight. Decode moves a QUARTER of the bf16 weight
    bytes — GPT-NeoX-20B decode becomes chip-resident on one 16 GB v5e.
    Parity: the reference's 4-bit groupwise quantized inference GEMMs
    (``csrc/transformer/inference/csrc/dequantize.cu`` dequant-fused path).
    """
    M, D = x.shape
    Dq, F2 = q4.shape
    F = F2 * 2
    assert D == Dq, (x.shape, q4.shape)
    block_d = min(block_d, D)
    block_f = min(block_f, F)
    if not (_on_tpu() and _eligible4(M, D, F, group_size, block_d, block_f)):
        w = (unpack_int4(q4).astype(jnp.float32).reshape(-1, group_size)
             * s.astype(jnp.float32)[:, None]).reshape(D, F).astype(x.dtype)
        return x @ w
    s2d = s.reshape(D, F // group_size).astype(jnp.float32)
    return _int4_matmul_kernel_call(x, q4, s2d, group_size, block_d, block_f,
                                    x.dtype)


def int8_matmul(x: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
                group_size: int = 64, block_d: int = 256,
                block_f: int = 512) -> jnp.ndarray:
    """``x @ dequantize(q, s)`` without materializing the bf16 weight.

    x: [M, D] (float); q: [D, F] int8; s: flat scales for row-major
    ``group_size`` runs (``models/gpt.quantize_for_inference`` layout).
    Falls back to XLA dequantize-then-matmul off-TPU or for ineligible
    shapes/groupings.
    """
    M, D = x.shape
    Dq, F = q.shape
    assert D == Dq, (x.shape, q.shape)
    block_d = min(block_d, D)
    block_f = min(block_f, F)
    if not (_on_tpu() and _eligible(M, D, F, group_size, block_d, block_f)):
        # flat-group dequant (handles F % group != 0 — groups are runs of the
        # row-major flatten, the quantizer's only real invariant)
        w = (q.astype(jnp.float32).reshape(-1, group_size)
             * s.astype(jnp.float32)[:, None]).reshape(D, F).astype(x.dtype)
        return x @ w
    s2d = s.reshape(D, F // group_size).astype(jnp.float32)
    return _int8_matmul_kernel_call(x, q, s2d, group_size, block_d, block_f,
                                    x.dtype)
