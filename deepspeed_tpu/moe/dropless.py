"""A dropless router over groups of experts and an expert layer that is told
which experts it holds.

``sharded_moe.gate`` is top-1 / top-2 with a capacity and one-hot
``[G, N, E, C]`` einsums: a token past an expert's capacity is dropped, and
every chip computes every expert's slots. Here no token is dropped and only
the held experts compute:

- :func:`route`: softmax over ALL experts in float32; a group's score is its
  largest probability, the ``topk_groups`` best groups are kept and the ``k``
  largest probabilities inside them are the token's experts (DeepSeek-V2's
  ``group_limited_greedy``); the gates are those probabilities, not
  renormalised (or, with ``norm_topk``, divided by their sum), times
  ``scale``. Ties go to the lower index, among groups and
  among experts.
- :func:`held_experts_ffn`: ``held = (first, count)`` names the experts whose
  weights this chip has. The (token, expert) assignments are sorted by
  expert, those that met a held expert first; a grouped matrix product
  (``jax.lax.ragged_dot``: rows of group ``e`` against expert ``e``'s matrix,
  each expert's weights read once) runs over the held experts' rows only;
  rows go back to their tokens weighted by their gates. What the other
  experts would add is left out: with ``held`` = all experts this is the
  whole layer, on one of several chips it is this chip's term of the sum an
  exchange would make.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp


def group_limited_topk(probs: jnp.ndarray, k: int, n_groups: int,
                       topk_groups: int) -> jnp.ndarray:
    """The ``k`` experts of each row of ``probs`` [N, E] (float32, positive):
    the largest inside the ``topk_groups`` groups whose largest member is
    largest. int32 [N, k], an unordered set a row."""
    n, e = probs.shape
    if n_groups > 1 and topk_groups < n_groups:
        best = probs.reshape(n, n_groups, e // n_groups).max(axis=-1)
        _, kept = jax.lax.top_k(best, topk_groups)
        keep = jnp.zeros((n, n_groups), bool).at[
            jnp.arange(n)[:, None], kept].set(True)
        probs = jnp.where(jnp.repeat(keep, e // n_groups, axis=1), probs,
                          -1.0)
    return jax.lax.top_k(probs, k)[1].astype(jnp.int32)


def route(logits: jnp.ndarray, k: int, n_groups: int = 1,
          topk_groups: int = 1, scale: float = 1.0, norm_topk: bool = False
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(experts [N, k] int32, gates [N, k] float32) from router logits
    [N, E] float32. ``norm_topk``: the ``k`` probabilities taken are divided
    by their sum before ``scale``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    chosen = group_limited_topk(probs, k, n_groups, topk_groups)
    gates = jnp.take_along_axis(probs, chosen, axis=1)
    if norm_topk:
        gates = gates / gates.sum(axis=1, keepdims=True)
    return chosen, gates * scale


def held_experts_ffn(h: jnp.ndarray, chosen: jnp.ndarray, gates: jnp.ndarray,
                     gate_w: jnp.ndarray, up_w: jnp.ndarray,
                     down_w: jnp.ndarray, held: Tuple[int, int],
                     act: Callable[[jnp.ndarray], jnp.ndarray] = jax.nn.silu,
                     layer=None, out=None) -> jnp.ndarray:
    """``sum over the held e in chosen[n] of gates[n, e] * FFN_e(h[n])`` for
    every token ``n``: ``h`` [N, d]; ``chosen``, ``gates`` [N, k] over the
    router's full width; ``gate_w``, ``up_w`` [count, d, f] and ``down_w``
    [count, f, d] the matrices of experts ``first .. first + count - 1``;
    ``FFN_e(x) = (act(x Wg_e) * x Wu_e) Wd_e``, the gated product in float32.
    [N, d] in ``h``'s type, or in ``out``, in which the three products are
    then accumulated and returned too.

    With a ``layer`` index (it may be traced) the matrices are every routed
    layer's stacks, [L, count, ...], and only that layer's are read, where
    they lie: the stack's (layer, expert) pairs are the product's groups and
    every other layer's group is empty. A layer loop that slices its experts
    out of the stack hands the product a copy of them, 1.9 GB a layer at 40
    experts of 5120 x 1536: 23 of a decode step's 50 ms (PERF.md, PR 34)."""
    first, count = held
    n, k = chosen.shape
    local = chosen - first
    mine = (local >= 0) & (local < count)
    expert = jnp.where(mine, local, count).reshape(-1)      # [N k]; count:
    order = jnp.argsort(expert, stable=True)                # not held, last
    sizes = jnp.zeros((count + 1,), jnp.int32).at[expert].add(1)[:count]
    if layer is not None:
        layers = gate_w.shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((layers * count,), jnp.int32), sizes,
            (jnp.asarray(layer, jnp.int32) * count,))
        gate_w, up_w, down_w = (w.reshape((layers * count,) + w.shape[2:])
                                for w in (gate_w, up_w, down_w))
    rows = h[order // k]                                    # [N k, d]

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=out)

    mid = (act(grouped(rows, gate_w).astype(jnp.float32))
           * grouped(rows, up_w).astype(jnp.float32))
    y = grouped(mid.astype(h.dtype), down_w)
    # rows past the held experts' belong to no group: whatever the product
    # left there is not read
    y = jnp.where(mine.reshape(-1)[order][:, None],
                  y.astype(jnp.float32) * gates.reshape(-1)[order][:, None],
                  0.0)
    back = jnp.argsort(order)                               # row of (n, j)
    return y[back].reshape(n, k, -1).sum(axis=1).astype(out or h.dtype)
