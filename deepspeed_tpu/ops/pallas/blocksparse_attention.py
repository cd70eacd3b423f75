"""Blocksparse flash attention in Pallas.

Capability parity with the reference's Triton blocksparse attention core
(``ops/sparse_attention/matmul.py`` SDD/DSD blocksparse matmuls +
``softmax.py`` blocksparse softmax, composed by
``sparse_self_attention.py:11``): attention restricted to the active blocks of a
static block layout, with flash-style online softmax so neither the dense [T, T]
scores nor the sparse score blocks are ever materialized in HBM — one fused
kernel instead of the reference's three (SDD matmul, softmax, DSD matmul).

Structure (extends :mod:`.flash_attention`):
- the layout ``[H, nQ, nK]`` is static (numpy). Per (head, q-block) the active
  k-block indices are precomputed into a padded index table ``kidx [H, nQ, A]``
  with counts ``kcnt [H, nQ]``; the kernel's inner ``fori_loop`` runs only
  ``kcnt`` iterations and dynamically slices the k/v blocks it needs — compute
  and HBM traffic scale with layout density, not T².
- the index/count tables ride **scalar prefetch** (SMEM via
  ``pltpu.PrefetchScalarGridSpec``) — int32 control data is not tileable as a
  VMEM block, and Mosaic rejects (1, 1, A) blocks; SMEM residency is the TPU
  idiom for blocksparse index tables.
- backward mirrors it with the transposed table (active q-blocks per k-block)
  for dk/dv.
- causal masking is elementwise inside diagonal blocks; block-level causality is
  already encoded in the layout (configs mask the upper triangle).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANES, NEG_INF, _interpret


def layout_tables(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Static index tables from a [H, nQ, nK] 0/1 layout.

    Returns (kidx [H,nQ,A], kcnt [H,nQ], qidx [H,nK,Aq], qcnt [H,nK]) padded
    with 0 (padding entries are never read: the loop bound is the count).
    """
    H, nQ, nK = layout.shape
    max_k = max(1, int(layout.sum(axis=2).max()))
    max_q = max(1, int(layout.sum(axis=1).max()))
    kidx = np.zeros((H, nQ, max_k), np.int32)
    kcnt = np.zeros((H, nQ), np.int32)
    qidx = np.zeros((H, nK, max_q), np.int32)
    qcnt = np.zeros((H, nK), np.int32)
    for h in range(H):
        for i in range(nQ):
            cols = np.nonzero(layout[h, i])[0]
            kidx[h, i, : len(cols)] = cols
            kcnt[h, i] = len(cols)
        for j in range(nK):
            rows = np.nonzero(layout[h, :, j])[0]
            qidx[h, j, : len(rows)] = rows
            qcnt[h, j] = len(rows)
    return kidx, kcnt, qidx, qcnt


# --------------------------------------------------------------------------- fwd
def _fwd_kernel(kidx_ref, kcnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                H: int, sm_scale: float, causal: bool, block: int):
    h = jax.lax.rem(pl.program_id(0), H)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # [B, D]
    bq = q.shape[0]
    acc = jnp.zeros((bq, v_ref.shape[-1]), jnp.float32)
    m_i = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l_i = jnp.zeros((bq, 1), jnp.float32)
    q_pos = qi * block + jax.lax.broadcasted_iota(jnp.int32, (bq, block), 0)

    def body(a, carry):
        acc, m_i, l_i = carry
        ki = kidx_ref[h, qi, a]
        k = k_ref[0, pl.ds(ki * block, block), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [B, B]
        if causal:
            k_pos = ki * block + jax.lax.broadcasted_iota(jnp.int32, (bq, block), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot(p, v)
        return acc, m_new, l_new

    acc, m_i, l_i = jax.lax.fori_loop(0, kcnt_ref[h, qi], body, (acc, m_i, l_i))
    l_safe = jnp.where(l_i == 0.0, 1.0, l_i)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m_i + jnp.log(l_safe), (bq, LANES))


# --------------------------------------------------------------------------- bwd
def _bwd_dq_kernel(kidx_ref, kcnt_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
                   lse_ref, dq_ref, *, H: int, sm_scale: float, causal: bool,
                   block: int):
    h = jax.lax.rem(pl.program_id(0), H)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, :1]
    delta = jnp.sum(do * o, axis=-1, keepdims=True)
    bq = q.shape[0]
    q_pos = qi * block + jax.lax.broadcasted_iota(jnp.int32, (bq, block), 0)

    def body(a, dq):
        ki = kidx_ref[h, qi, a]
        k = k_ref[0, pl.ds(ki * block, block), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale
        if causal:
            k_pos = ki * block + jax.lax.broadcasted_iota(jnp.int32, (bq, block), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * sm_scale
        return dq + jax.lax.dot(ds, k)

    dq = jax.lax.fori_loop(0, kcnt_ref[h, qi], body,
                           jnp.zeros((bq, q.shape[-1]), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(qidx_ref, qcnt_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
                    lse_ref, dk_ref, dv_ref, *, H: int, sm_scale: float,
                    causal: bool, block: int):
    h = jax.lax.rem(pl.program_id(0), H)
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    bk = k.shape[0]
    k_pos = ki * block + jax.lax.broadcasted_iota(jnp.int32, (block, bk), 1)

    def body(a, carry):
        dk, dv = carry
        qi = qidx_ref[h, ki, a]
        q = q_ref[0, pl.ds(qi * block, block), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qi * block, block), :].astype(jnp.float32)
        o = o_ref[0, pl.ds(qi * block, block), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * block, block), :1]
        delta = jnp.sum(do * o, axis=-1, keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale
        if causal:
            q_pos = qi * block + jax.lax.broadcasted_iota(jnp.int32, (block, bk), 0)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * sm_scale
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        0, qcnt_ref[h, ki], body,
        (jnp.zeros((bk, k.shape[-1]), jnp.float32),
         jnp.zeros((bk, v.shape[-1]), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# --------------------------------------------------------------------------- glue
def _fwd(q, k, v, kidx, kcnt, H, sm_scale, causal, block):
    BH, T, D = q.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # kidx, kcnt in SMEM
        grid=(BH, T // block),
        in_specs=[
            pl.BlockSpec((1, block, D), lambda bh, i, *_: (bh, i, 0)),
            pl.BlockSpec((1, T, D), lambda bh, i, *_: (bh, 0, 0)),
            pl.BlockSpec((1, T, D), lambda bh, i, *_: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, D), lambda bh, i, *_: (bh, i, 0)),
            pl.BlockSpec((1, block, LANES), lambda bh, i, *_: (bh, i, 0)),
        ],
    )
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, H=H, sm_scale=sm_scale, causal=causal,
                          block=block),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name="blocksparse_fwd",
    )(kidx, kcnt, q, k, v)
    return o, lse


def _bwd(kidx, kcnt, qidx, qcnt, H, sm_scale, causal, block, res, do):
    q, k, v, o, lse = res
    BH, T, D = q.shape
    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, T // block),
        in_specs=[
            pl.BlockSpec((1, block, D), lambda bh, i, *_: (bh, i, 0)),
            pl.BlockSpec((1, T, D), lambda bh, i, *_: (bh, 0, 0)),
            pl.BlockSpec((1, T, D), lambda bh, i, *_: (bh, 0, 0)),
            pl.BlockSpec((1, block, D), lambda bh, i, *_: (bh, i, 0)),
            pl.BlockSpec((1, block, D), lambda bh, i, *_: (bh, i, 0)),
            pl.BlockSpec((1, block, LANES), lambda bh, i, *_: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, D), lambda bh, i, *_: (bh, i, 0)),
    )
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, H=H, sm_scale=sm_scale, causal=causal,
                          block=block),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        interpret=_interpret(),
        name="blocksparse_bwd_dq",
    )(kidx, kcnt, q, k, v, o, do, lse)

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, T // block),
        in_specs=[
            pl.BlockSpec((1, T, D), lambda bh, j, *_: (bh, 0, 0)),
            pl.BlockSpec((1, block, D), lambda bh, j, *_: (bh, j, 0)),
            pl.BlockSpec((1, block, D), lambda bh, j, *_: (bh, j, 0)),
            pl.BlockSpec((1, T, D), lambda bh, j, *_: (bh, 0, 0)),
            pl.BlockSpec((1, T, D), lambda bh, j, *_: (bh, 0, 0)),
            pl.BlockSpec((1, T, LANES), lambda bh, j, *_: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, D), lambda bh, j, *_: (bh, j, 0)),
            pl.BlockSpec((1, block, D), lambda bh, j, *_: (bh, j, 0)),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, H=H, sm_scale=sm_scale, causal=causal,
                          block=block),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        interpret=_interpret(),
        name="blocksparse_bwd_dkv",
    )(qidx, qcnt, q, k, v, o, do, lse)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _bs_attn(q, k, v, kidx, kcnt, qidx, qcnt, H, sm_scale, causal, block):
    o, _ = _fwd(q, k, v, kidx, kcnt, H, sm_scale, causal, block)
    return o


def _bs_fwd(q, k, v, kidx, kcnt, qidx, qcnt, H, sm_scale, causal, block):
    o, lse = _fwd(q, k, v, kidx, kcnt, H, sm_scale, causal, block)
    return o, (q, k, v, o, lse, kidx, kcnt, qidx, qcnt)


def _bs_bwd(H, sm_scale, causal, block, res, do):
    q, k, v, o, lse, kidx, kcnt, qidx, qcnt = res
    dq, dk, dv = _bwd(kidx, kcnt, qidx, qcnt, H, sm_scale, causal, block,
                      (q, k, v, o, lse), do)
    return dq, dk, dv, None, None, None, None


_bs_attn.defvjp(_bs_fwd, _bs_bwd)


def blocksparse_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    layout: np.ndarray,  # [H, T/block, T/block] static 0/1
    block: int,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    tables: Optional[Tuple] = None,  # precomputed layout_tables (caching)
) -> jnp.ndarray:
    """Attention restricted to the active blocks of ``layout``; differentiable."""
    B, T, H, D = q.shape
    if layout.shape != (H, T // block, T // block):
        raise ValueError(
            f"layout {layout.shape} != (H={H}, {T // block}, {T // block})")
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(D)
    if tables is None:
        tables = tuple(jnp.asarray(t) for t in layout_tables(layout))
    kidx, kcnt, qidx, qcnt = tables
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    o = _bs_attn(qt, kt, vt, kidx, kcnt, qidx, qcnt, H, scale, causal, block)
    return o.reshape(B, H, T, D).transpose(0, 2, 1, 3)
