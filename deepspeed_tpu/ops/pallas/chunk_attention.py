"""Attention of a prompt chunk's queries over a request's cached rows under a
mask a query, for the TPU: what a chunk of a model that SELECTS the rows it
reads (``GPTConfig.index_topk``) needs of its full layers, where every query
has its own set of at most ``index_topk`` admitted rows among thousands.

``masked_chunk_attention(q [H, T, Dq], k [H, S, Dq], v [H, S, Dv], allowed
[T, S], live, shared=(q_s [H, T, Ds], k_s [S, Ds]))``: softmax over the keys
``allowed`` admits (the same for all heads), float32 scores and sums,
probabilities rounded to the values' type for the second product; keys at or
past ``live`` (traced) are neither read nor scored. ``shared``: a part of the
scores whose key is ONE row for all heads (latent attention's rotated key),
added to a head's own: kept apart, it costs ``S x Ds`` numbers and not ``H``
times that, and the heads' own keys stay whole lanes wide. The XLA form of
the same (``models/gpt._mla_table_attention``) writes a block's scores ``[H, T, 512]`` in float32 to HBM and reads them back
three times, 34 GB a chunk of 1024 at 16k keys and 128 heads; here a tile of
scores lives and dies in VMEM (the flash form), and a tile of keys past
``live`` keeps the last live tile's block index, so no copy is issued for it.
Forward only: serving.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# heads, queries and keys a grid step takes: 4 x 256 x 512 float32 scores are
# 2 MiB, the key and value tiles 1.3 MiB, double-buffered
_HEADS, _QUERIES, _KEYS = 4, 256, 512


def _interpret() -> bool:
    if os.environ.get("DS_TPU_PALLAS_INTERPRET") == "0":
        return False
    return jax.default_backend() != "tpu"


def _divisor(n: int, most: int) -> int:
    return max(d for d in range(1, most + 1) if n % d == 0)


def masked_chunk_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           allowed: jnp.ndarray, live, softmax_scale: float,
                           shared=None, impl: Optional[str] = None
                           ) -> jnp.ndarray:
    """[H, T, Dv] in ``v``'s type (the module docstring). ``impl``: None =
    the kernel on a TPU and the plain form elsewhere | "kernel" | "plain"."""
    H, T, Dq = q.shape
    S, Dv = k.shape[1], v.shape[2]
    if k.shape != (H, S, Dq) or v.shape[:2] != (H, S) \
            or allowed.shape != (T, S) or (shared is not None and (
                shared[0].shape[:2] != (H, T) or shared[1].shape != (
                    S, shared[0].shape[2]))):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}, allowed "
                         f"{allowed.shape}: wanted [H, T, Dq], [H, S, Dq], "
                         "[H, S, Dv], [T, S] (shared: [H, T, Ds], [S, Ds])")
    if impl is None:
        impl = "kernel" if jax.default_backend() == "tpu" else "plain"
    live = jnp.asarray(live, jnp.int32).reshape(1)
    if impl == "plain":
        return _plain(q, k, v, allowed, live[0], softmax_scale, shared)
    if impl != "kernel":
        raise ValueError(f"impl must be None, 'kernel' or 'plain': {impl!r}")
    hb = _divisor(H, _HEADS)
    tq, tk = math.gcd(T, _QUERIES), math.gcd(S, _KEYS)
    steps = S // tk

    def key_tile(j, live):      # a tile past the live rows: the last live one
        return jnp.minimum(j, jnp.maximum(live[0] - 1, 0) // tk)

    ins = [(q.astype(k.dtype), pl.BlockSpec(
                (hb, tq, Dq), lambda h, i, j, live: (h, i, 0))),
           (k, pl.BlockSpec(
               (hb, tk, Dq), lambda h, i, j, live: (h, key_tile(j, live), 0))),
           (v, pl.BlockSpec(
               (hb, tk, Dv), lambda h, i, j, live: (h, key_tile(j, live), 0))),
           (allowed.astype(jnp.int32), pl.BlockSpec(
               (tq, tk), lambda h, i, j, live: (i, key_tile(j, live))))]
    if shared is not None:
        Ds = shared[0].shape[2]
        ins += [(shared[0].astype(k.dtype), pl.BlockSpec(
                    (hb, tq, Ds), lambda h, i, j, live: (h, i, 0))),
                (shared[1].astype(k.dtype), pl.BlockSpec(
                    (tk, Ds), lambda h, i, j, live: (key_tile(j, live), 0)))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H // hb, T // tq, steps),
        in_specs=[spec for _, spec in ins],
        out_specs=pl.BlockSpec((hb, tq, Dv), lambda h, i, j, live: (h, i, 0)),
        scratch_shapes=[pltpu.VMEM((hb, tq, Dv), jnp.float32),
                        pltpu.VMEM((hb, tq, 1), jnp.float32),
                        pltpu.VMEM((hb, tq, 1), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=softmax_scale, tk=tk,
                          steps=steps, shared=shared is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, T, Dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=_interpret(),
        name="masked_chunk_attn",
    )(live, *(a for a, _ in ins))


def _kernel(live_ref, q_ref, k_ref, v_ref, allow_ref, *refs, sm_scale: float,
            tk: int, steps: int, shared: bool):
    """One (block of heads, tile of queries, tile of keys) step of the online
    softmax; both products batched over the heads on the MXU."""
    (qs_ref, ks_ref), refs = (refs[:2], refs[2:]) if shared else (
        (None, None), refs)
    o_ref, acc_ref, m_ref, l_ref = refs
    j = pl.program_id(2)
    live = live_ref[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * tk < live)
    def _tile():
        v = v_ref[...]
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)             # [hb, tq, tk]
        if shared:      # the one key of all heads: [hb tq, Ds] x [tk, Ds]
            hb, tq, ds = qs_ref.shape
            s = s + jax.lax.dot_general(
                qs_ref[...].reshape(hb * tq, ds), ks_ref[...],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).reshape(hb, tq, tk)
        s = s * sm_scale
        at = j * tk + jax.lax.broadcasted_iota(jnp.int32, allow_ref.shape, 1)
        seen = ((allow_ref[...] != 0) & (at < live))[None]
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a tile may hold no key a query's selection admits
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(j == steps - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _plain(q, k, v, allowed, live, scale, shared=None):
    """The same in one piece, for small shapes and as the kernel's check."""
    exact = (jax.lax.Precision.HIGHEST if k.dtype == jnp.float32 else None)
    s = jnp.einsum("htd,hsd->hts", q.astype(k.dtype), k, precision=exact,
                   preferred_element_type=jnp.float32)
    if shared is not None:
        s = s + jnp.einsum("htd,sd->hts", shared[0].astype(k.dtype),
                           shared[1].astype(k.dtype), precision=exact,
                           preferred_element_type=jnp.float32)
    seen = ((allowed != 0) & (jnp.arange(k.shape[1]) < live)[None, :])[None]
    p = jax.nn.softmax(jnp.where(seen, s * scale, NEG_INF), axis=-1)
    p = jnp.where(seen.any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("hts,hsd->htd", p.astype(v.dtype), v, precision=exact,
                      preferred_element_type=jnp.float32).astype(v.dtype)
