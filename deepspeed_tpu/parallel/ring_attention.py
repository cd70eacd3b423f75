"""Ring attention: exact attention over sequences sharded across the ``sp`` axis.

The long-context pillar. The reference fork predates sequence parallelism — its
long-sequence story is blocksparse attention (``ops/sparse_attention/``) and
activation partitioning (``activation_checkpointing/checkpointing.py:372``); SURVEY.md
§5 directs this build to provide real SP as the capability equivalent.

Design (Ring Attention with blockwise softmax, à la Liu et al. 2023, TPU-first):

- Q/K/V live sharded on the sequence axis: ``P(batch, "sp", heads, None)`` — each
  of the S devices holds one contiguous sequence block.
- K/V blocks rotate around the ring with ``jax.lax.ppermute`` (neighbor hops over
  ICI) while each device's Q block stays resident. After S hops every Q block has
  seen every K/V block: exact attention, O(T/S) memory per device, compute
  overlapping the permute (XLA schedules the next block's matmul against the
  in-flight collective).
- The running (max, denominator, accumulator) triple is the same online-softmax
  recurrence the flash kernel uses, so precision matches the fused path (fp32
  accumulation).
- Causality: block ``j`` contributes to query block ``i`` fully when ``j < i``,
  with a triangular mask when ``j == i``, not at all when ``j > i`` (masked to
  ``-inf`` — all ranks run the same program, SPMD-style).

Autodiff gives the backward ring for free (transpose of ``ppermute`` is the
reverse permute), replacing hand-written backward comm.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = jnp.float32(-1e30)


def _block_update(q, k, v, m, l, acc, allowed_mask, scale):
    """One online-softmax accumulation step against K/V block (k, v).

    q: [B, Tq, H, Dh]; k/v: [B, Tk, H, Dh]; m/l: [B, H, Tq]; acc: [B, Tq, H, Dh];
    allowed_mask: [Tq, Tk] bool (True = may attend).
    """
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    s = jnp.where(allowed_mask[None, None, :, :], s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # all-masked rows keep m at -1e30; exp(s - m) is then exp(0)=1 on masked
    # entries — guard by masking p as well
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(allowed_mask[None, None, :, :], p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhts,bshd->bthd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def _ring_attention_local(q, k, v, axis_name: str, causal: bool,
                          softmax_scale: Optional[float]):
    """Per-shard body: q/k/v are the LOCAL sequence blocks [B, Tl, H, Dh]."""
    B, Tl, H, Dh = q.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(Dh)
    size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    m0 = jnp.full((B, H, Tl), _NEG_INF)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    acc0 = jnp.zeros((B, Tl, H, Dh), jnp.float32)
    tri = jnp.tril(jnp.ones((Tl, Tl), bool))  # intra-block causal mask

    # rotate K/V: source p sends to p-1, so at step r we hold block (my_idx + r) % S
    perm = [(p, (p - 1) % size) for p in range(size)]

    def step(carry, r):
        k_blk, v_blk, m, l, acc = carry
        j = (my_idx + r) % size  # origin of the block we currently hold
        if causal:
            allowed = jnp.where(
                j < my_idx, jnp.ones((Tl, Tl), bool),
                jnp.where(j == my_idx, tri, jnp.zeros((Tl, Tl), bool)))
        else:
            allowed = jnp.ones((Tl, Tl), bool)
        m, l, acc = _block_update(q, k_blk, v_blk, m, l, acc, allowed, scale)
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m, l, acc), None

    (k, v, m, l, acc), _ = jax.lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(size))
    # normalize; fully-masked rows (can't happen with causal: own block always
    # contributes) guarded by the max
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, T, H, Dh] — T sharded over `axis_name`
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    causal: bool = True,
    axis_name: str = "sp",
    batch_axes=("dp", "ep"),
    head_axis: Optional[str] = "tp",
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Exact attention with the sequence axis sharded over ``axis_name``.

    Composes with data parallelism (batch over ``batch_axes``) and tensor
    parallelism (heads over ``head_axis``): the ring only ever communicates over
    ``axis_name`` neighbors.
    """
    spec = P(batch_axes, axis_name, head_axis, None)
    body = functools.partial(
        _ring_attention_local, axis_name=axis_name, causal=causal,
        softmax_scale=softmax_scale)
    return shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
