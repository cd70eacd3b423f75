"""Ulysses-style sequence parallelism: all-to-all head-scatter / sequence-gather.

Capability-equivalent long-context mechanism (SURVEY.md §5 "long-context pillar").
Complementary to :mod:`.ring_attention`:

- **ring**: K/V rotate; comm volume O(T·D) per device per step, S neighbor hops —
  best when T is huge and heads are few.
- **ulysses**: one ``all_to_all`` converts sequence sharding into head sharding,
  attention runs *locally* over the full sequence with H/S heads, a second
  ``all_to_all`` converts back — two collectives total, best when H ≥ S and T
  moderate. Maps directly onto ``jax.lax.all_to_all`` over the ``sp`` mesh axis
  (the reference's EP dispatch uses the same primitive shape, ``moe/sharded_moe.py:89``).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import dot_product_attention


def _ulysses_local(q, k, v, attn_fn: Callable, axis_name: str):
    """Per-shard body. In: [B, T/S, H, Dh] (sequence-sharded). all_to_all to
    [B, T, H/S, Dh], local attention over the full sequence, all_to_all back."""
    # scatter heads (axis 2), gather sequence (axis 1)
    q = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = attn_fn(q, k, v)
    # scatter sequence, gather heads: back to [B, T/S, H, Dh]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jnp.ndarray,  # [B, T, H, Dh] — T sharded over `axis_name`
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    causal: bool = True,
    axis_name: str = "sp",
    batch_axes=("dp", "ep"),
    head_axis: Optional[str] = "tp",
    attn_fn: Optional[Callable] = None,
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Sequence-parallel attention via two all-to-alls. The head count must divide
    by the ``axis_name`` extent (times ``head_axis`` extent if TP-sharded)."""
    if attn_fn is None:
        attn_fn = functools.partial(dot_product_attention, causal=causal,
                                    softmax_scale=softmax_scale)
    spec = P(batch_axes, axis_name, head_axis, None)
    body = functools.partial(_ulysses_local, attn_fn=attn_fn, axis_name=axis_name)
    return shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
