"""Attention ops.

Capability parity with the reference's fused attention kernels
(``csrc/transformer/softmax_kernels.cu``, ``csrc/transformer/inference/csrc/softmax.cu``):
on TPU the fused path is (a) XLA's automatic fusion of the QK^T -> masked softmax -> V
chain for moderate sequence lengths, and (b) a Pallas flash-attention kernel
(:mod:`deepspeed_tpu.ops.pallas.flash_attention`) for long sequences where
materializing the [T, T] score matrix would blow HBM. This module is the dispatch
point; models call :func:`multihead_attention` and never pick a kernel themselves.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def causal_mask(q_len: int, kv_len: int, dtype=jnp.float32) -> jnp.ndarray:
    i = jnp.arange(q_len)[:, None] + (kv_len - q_len)
    j = jnp.arange(kv_len)[None, :]
    return (j <= i)  # [q, kv] bool


def dot_product_attention(
    q: jnp.ndarray,  # [B, T, H, Dh]
    k: jnp.ndarray,  # [B, S, H, Dh]
    v: jnp.ndarray,  # [B, S, H, Dh]
    causal: bool = True,
    bias: Optional[jnp.ndarray] = None,
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Reference (XLA-fused) attention. fp32 softmax accumulation regardless of the
    input dtype — same numerics stance as the reference's fused softmax kernels."""
    *_, q_len, _, head_dim = q.shape
    kv_len = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(head_dim)
    logits = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        mask = causal_mask(q_len, kv_len)
        logits = jnp.where(mask[None, None, :, :], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v)
    return out


def multihead_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    bias: Optional[jnp.ndarray] = None,
    use_flash: Optional[bool] = None,
    softmax_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    stochastic_mode: bool = False,
) -> jnp.ndarray:
    """Kernel dispatch: Pallas flash attention on TPU when eligible, XLA
    otherwise. ``block_q``/``block_k`` force the flash kernels' inner tile
    (autotunable; None: each kernel's own measured one);
    ``stochastic_mode`` is the speed-over-bit-exactness kernel flag (bf16
    MXU operands, fp32 accumulation — see ops/pallas/flash_attention.py)."""
    if use_flash is None:
        use_flash = _flash_eligible(q, k, bias)
    elif use_flash and bias is not None:
        # the flash kernel has no bias input (same reason the decode path
        # guards ALiBi); computing without it would be silently wrong
        from ..utils.logging import warning_once

        warning_once("flash attention forced on but an attention bias is "
                     "present (ALiBi?); falling back to XLA attention")
        use_flash = False
    if use_flash:
        try:
            from .pallas.flash_attention import flash_attention
        except ImportError:
            from ..utils.logging import warning_once

            warning_once("pallas flash attention unavailable; using XLA attention")
        else:
            fa = functools.partial(
                flash_attention, causal=causal, softmax_scale=softmax_scale,
                block_q=block_q, block_k=block_k,
                stochastic_mode=stochastic_mode)
            return _shard_mapped_kernel(fa, q, k, v)
    return dot_product_attention(q, k, v, causal=causal, bias=bias,
                                 softmax_scale=softmax_scale)


def _bound_mesh():
    """The mesh governing the current trace (None outside any mesh context)."""
    from ..runtime.topology import bound_mesh

    return bound_mesh()


def _shard_mapped_kernel(fa, q, k, v):
    """Run a Pallas attention kernel under multi-device SPMD.

    Mosaic custom calls cannot be auto-partitioned by GSPMD (XLA raises
    "wrap the call in a shard_map") — a plain call inside a jit over a >1
    device mesh would crash on real hardware. Attention is embarrassingly
    parallel over batch and heads, so when a mesh is bound we shard_map over
    the data-parallel batch axes and the tp head axis; each shard runs the
    kernel on its local [B/dp, T, H/tp, D] block. Sequence stays unsharded
    here — sp>1 routes to ring/Ulysses before kernel dispatch
    (models/gpt._attention_delta)."""
    mesh = _bound_mesh()
    if mesh is None:
        return fa(q, k, v)
    names = set(mesh.axis_names)
    batch_axes = tuple(a for a in ("dp", "ep") if a in names
                       and mesh.shape[a] > 1)
    head_axis = "tp" if "tp" in names and mesh.shape["tp"] > 1 else None
    if not batch_axes and head_axis is None:
        return fa(q, k, v)
    bsz = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    hsz = mesh.shape[head_axis] if head_axis else 1
    B, H = q.shape[0], q.shape[2]
    if B % bsz or H % hsz:
        raise ValueError(
            f"flash attention under SPMD needs batch {B} divisible by "
            f"{batch_axes}={bsz} and heads {H} by tp={hsz}")
    from jax import shard_map

    spec = jax.sharding.PartitionSpec(
        batch_axes or None, None, head_axis, None)
    return shard_map(fa, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def _flash_eligible(q, k, bias) -> bool:
    if bias is not None:
        return False
    if jax.default_backend() not in ("tpu",):
        return False
    # block tiling needs 128-divisible sequence lengths; any head_dim works
    # (lanes are padded), but tiny dims aren't worth the kernel
    return q.shape[-1] >= 64 and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0
