"""Flash attention for TPU in Pallas.

Capability parity with the reference's fused attention kernels — training softmax
(``csrc/transformer/softmax_kernels.cu``) and the attention core of the fused
transformer layer (``csrc/transformer/ds_transformer_cuda.cpp``) — rebuilt as a
blockwise online-softmax kernel so the [T, T] score matrix never materializes in
HBM. This lifts the memory ceiling that forces full-recompute activation
checkpointing on long sequences (the reference's sparse-attention pillar targets the
same ceiling; blocksparse lives in ``blocksparse.py``).

Design (TPU-first, per the Pallas TPU guide):
- grid = (batch*heads, T/Bq): each program owns one q block in VMEM and streams
  k/v blocks with an online (max, sum) rescale — MXU does the two matmuls per
  block, VPU the rescale.
- causal masking skips whole k blocks above the diagonal: the fori_loop bound
  depends on the q block index, so work is triangular like the reference's
  ``attn_softmax`` triangular mode.
- fp32 accumulators; the saved logsumexp rides a 128-lane broadcast layout
  ([BH, T, 128]) because TPU VMEM tiles are (8, 128) — a bare [BH, T] residual
  would violate the layout constraints (same trick as jax's reference TPU kernel).
- backward = two kernels (dq over q blocks; dk/dv over k blocks) using the saved
  logsumexp; delta = rowsum(dO*O) is computed in-kernel from the o/do blocks.
  Wrapped in ``jax.custom_vjp``.
- ``interpret=True`` automatically off-TPU so the same code runs in CPU CI.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
LANES = 128  # TPU lane count; lse residual is broadcast across it


def _interpret() -> bool:
    # DS_TPU_PALLAS_INTERPRET=0 forces real Mosaic lowering even when the
    # process backend is CPU — the AOT compile-only flow (bench pipeline_aot)
    # targets a TPU topology from a CPU host, and interpret-mode HLO would
    # both misrepresent the real program and OOM the compiler
    env = os.environ.get("DS_TPU_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale: float, causal: bool, block_q: int, block_k: int,
                num_k_blocks: int, q_offset: int, stochastic_mode: bool):
    """One (q block, k block) tile of the online softmax. The k axis streams
    through the innermost grid dimension (whole-sequence k/v in VMEM trips
    the Mosaic scoped-VMEM limit past ~8k); the (acc, m, l) state lives in
    VMEM scratch, persisting across the revisited output window."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    bq = q_ref.shape[1]
    # stochastic mode (parity: ds_transformer_cuda.cpp:63 stochastic_mode —
    # speed over run-exactness): matmul operands stay in the input dtype so
    # the MXU runs its native bf16 pass (fp32 upcast costs multiple passes);
    # accumulation and the softmax state remain fp32
    lo = q_ref.dtype if stochastic_mode else jnp.float32

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(lo)  # [Bq, D]
        k = k_ref[0].astype(lo)  # [Bk, D]
        v = v_ref[0].astype(lo)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Bq, Bk]
        if causal:
            # q rows sit at absolute positions q_offset + qi*Bq + i
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_i = m_ref[:, :1]
        l_i = l_ref[:, :1]
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot(p.astype(lo), v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # only blocks intersecting the lower triangle of this q block
        pl.when(ki * block_k
                <= q_offset + qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l_i = l_ref[:, :1]
        l_safe = jnp.where(l_i == 0.0, 1.0, l_i)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(l_safe)  # [Bq, 1]
        lse_ref[0] = jnp.broadcast_to(lse, (bq, LANES))


def _fwd(q, k, v, sm_scale: float, causal: bool, block_q: int, block_k: int,
         stochastic_mode: bool = False):
    """q,k,v: [BH, T, D] -> (o [BH, T, D], lse [BH, T, LANES])."""
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    S = k.shape[1]
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k_blocks=S // block_k,
        q_offset=S - T, stochastic_mode=stochastic_mode)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, T // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),      # acc
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running sum
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------- bwd
# Backward kernels stream the CONTRACTED sequence axis through the grid
# (3D grid, innermost axis revisits the same output window, accumulating)
# instead of holding whole-sequence refs in VMEM — a [1, S, D] VMEM block
# trips the Mosaic scoped-VMEM limit (16M, double-buffered) past seq ~4-8k.
# Per grid step VMEM holds one (block_q, D) + one (block_k, D) tile set, so
# the sequence ceiling is gone; causal skipping is a pl.when on whole blocks
# (the out-of-triangle fetches still stream, the MXU work is skipped).


def _bwd_delta_kernel(o_ref, do_ref, delta_ref):
    """delta = rowsum(dO * O), computed ONCE per q block (it is k-invariant;
    recomputing it per streamed k block would re-DMA the o tile S/block_k
    times) and broadcast across lanes like the lse residual."""
    delta = jnp.sum(do_ref[0].astype(jnp.float32)
                    * o_ref[0].astype(jnp.float32), axis=-1, keepdims=True)
    delta_ref[0] = jnp.broadcast_to(delta, delta_ref.shape[1:])


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   sm_scale: float, causal: bool, block_q: int, block_k: int,
                   q_offset: int, stochastic_mode: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    lo = q_ref.dtype if stochastic_mode else jnp.float32

    @pl.when(ki == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    bq = q_ref.shape[1]

    def _compute():
        q = q_ref[0].astype(lo)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]  # [Bq, 1]
        delta = delta_ref[0][:, :1]  # [Bq, 1]
        k = k_ref[0].astype(lo)
        v = v_ref[0].astype(lo)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)  # [Bq, Bk]
        dp = jax.lax.dot_general(do.astype(lo), v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_ref[0] += jax.lax.dot(
            ds.astype(lo), k,
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)

    if causal:
        # any row of this q block can see the k block's first column?
        pl.when(ki * block_k
                <= q_offset + qi * block_q + block_q - 1)(_compute)
    else:
        _compute()


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, sm_scale: float, causal: bool,
                    block_q: int, block_k: int, q_offset: int,
                    stochastic_mode: bool):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    lo = k_ref.dtype if stochastic_mode else jnp.float32

    @pl.when(qi == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    bk = k_ref.shape[1]

    def _compute():
        k = k_ref[0].astype(lo)  # [Bk, D]
        v = v_ref[0].astype(lo)
        q = q_ref[0].astype(lo)  # [Bq, D]
        do_lo = do_ref[0].astype(lo)
        lse = lse_ref[0][:, :1]  # [Bq, 1]
        delta = delta_ref[0][:, :1]  # [Bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [Bq, Bk]
        if causal:
            bq = q.shape[0]
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_ref[0] += jax.lax.dot_general(
            p.astype(lo), do_lo, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(do_lo, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_ref[0] += jax.lax.dot_general(
            ds.astype(lo), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dk_ref.dtype)

    if causal:
        # does the last row of this q block reach the k block at all?
        pl.when(ki * block_k
                <= q_offset + qi * block_q + block_q - 1)(_compute)
    else:
        _compute()


def _bwd(sm_scale, causal, block_q, block_k, stochastic_mode, res, do):
    q, k, v, o, lse = res
    BH, T, D = q.shape
    S = k.shape[1]

    # prologue: delta = rowsum(dO*O) once per q row (k-invariant), in the
    # same 128-lane broadcast layout as the lse residual
    delta = pl.pallas_call(
        _bwd_delta_kernel,
        grid=(BH, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, LANES), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, LANES), jnp.float32),
        interpret=_interpret(),
        name="flash_bwd_delta",
    )(o, do)

    # accumulators are the (revisited) fp32 OUTPUT windows; cast at the end —
    # accumulating in bf16 across S/block_k grid steps would lose precision
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          q_offset=S - T, stochastic_mode=stochastic_mode),
        grid=(BH, T // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), jnp.float32),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          q_offset=S - T, stochastic_mode=stochastic_mode),
        grid=(BH, S // block_k, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------- api
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, stochastic_mode):
    o, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, stochastic_mode)
    return o


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, stochastic_mode):
    o, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, stochastic_mode)
    return o, (q, k, v, o, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, stochastic_mode, res, do):
    return _bwd(sm_scale, causal, block_q, block_k, stochastic_mode, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, S, H, D]
    v: jnp.ndarray,  # [B, S, H, D]
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 256,
    stochastic_mode: bool = False,
) -> jnp.ndarray:
    """Blockwise attention with online softmax; differentiable (custom VJP).

    ``stochastic_mode`` trades bit-exactness for speed (parity:
    ``csrc/transformer/ds_transformer_cuda.cpp:63``): matmul operands ride the
    input dtype onto the MXU's native bf16 pass instead of being upcast to
    fp32; accumulators and softmax state stay fp32. Off by default."""
    B, T, H, D = q.shape
    S = k.shape[1]
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    # shrink blocks to the largest 128-multiple that divides the sequence
    while block_q > 128 and T % block_q:
        block_q //= 2
    while block_k > 128 and S % block_k:
        block_k //= 2
    if T % block_q or S % block_k:
        raise ValueError(f"seq lens ({T},{S}) must divide blocks ({block_q},{block_k})")
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(D)
    # [B, T, H, D] -> [B*H, T, D]
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    o = _flash(qt, kt, vt, scale, causal, block_q, block_k,
               bool(stochastic_mode))
    return o.reshape(B, H, T, D).transpose(0, 2, 1, 3)
