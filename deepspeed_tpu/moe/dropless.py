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
  among experts. ``score="sigmoid"``: an expert's score is the sigmoid of
  its own logit; with a ``bias`` an expert the experts are CHOSEN by score
  plus bias and weighed by the score without it (DeepSeek-V3's
  ``noaux_tc``, Nemotron-3's router).
- :func:`held_experts_ffn`: ``held = (first, count)`` names the experts whose
  weights this chip has. The (token, expert) assignments are sorted by
  expert, those that met a held expert first; a grouped matrix product
  (``ops/pallas/grouped_dot``: rows of group ``e`` against expert ``e``'s
  matrix, each touched expert's weights read once; ``jax.lax.ragged_dot``
  elsewhere than on a TPU) runs over the held experts' rows only;
  rows go back to their tokens weighted by their gates. What the other
  experts would add is left out: with ``held`` = all experts this is the
  whole layer, on one of several chips it is this chip's term of the sum an
  exchange would make.
- :func:`zero_experts`: a router may score more outputs than there are
  experts with weights (LongCat-Flash: 512 + 256). :func:`route` runs over all
  of them; an index at or past the real experts' count names a zero-compute
  expert, the identity, which ``held`` never covers (it is a range of the
  REAL experts), and what those give a token is its own input times the sum
  of their gates. No weights and no exchange: every chip applies it to its
  own tokens, so it is no part of any chip's share of the experts.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.grouped_dot import grouped_dot


def group_limited_topk(probs: jnp.ndarray, k: int, n_groups: int,
                       topk_groups: int, dropped: float = -1.0
                       ) -> jnp.ndarray:
    """The ``k`` experts of each row of ``probs`` [N, E] (float32, positive):
    the largest inside the ``topk_groups`` groups whose largest member is
    largest. int32 [N, k], an unordered set a row. ``dropped``: what an
    expert outside those groups scores, under every score inside them."""
    n, e = probs.shape
    if n_groups > 1 and topk_groups < n_groups:
        best = probs.reshape(n, n_groups, e // n_groups).max(axis=-1)
        _, kept = jax.lax.top_k(best, topk_groups)
        keep = jnp.zeros((n, n_groups), bool).at[
            jnp.arange(n)[:, None], kept].set(True)
        probs = jnp.where(jnp.repeat(keep, e // n_groups, axis=1), probs,
                          dropped)
    return jax.lax.top_k(probs, k)[1].astype(jnp.int32)


def route(logits: jnp.ndarray, k: int, n_groups: int = 1,
          topk_groups: int = 1, scale: float = 1.0, norm_topk: bool = False,
          score: str = "softmax", bias: Optional[jnp.ndarray] = None
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(experts [N, k] int32, gates [N, k] float32) from router logits
    [N, E] float32. ``norm_topk``: the ``k`` probabilities taken are divided
    by their sum before ``scale``. ``score``: "softmax" over the experts or
    "sigmoid" of each logit; ``bias`` [E]: added to the scores for the
    choice only (the scores stay positive under it or not: the group filter
    marks a dropped expert by -inf then)."""
    if score == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    elif score == "sigmoid":
        probs = jax.nn.sigmoid(logits.astype(jnp.float32))
    else:
        raise ValueError(f"score must be softmax or sigmoid, got {score!r}")
    if bias is None:
        chosen = group_limited_topk(probs, k, n_groups, topk_groups)
    else:
        chosen = group_limited_topk(probs + bias.astype(jnp.float32), k,
                                    n_groups, topk_groups, dropped=-jnp.inf)
    gates = jnp.take_along_axis(probs, chosen, axis=1)
    if norm_topk:
        gates = gates / gates.sum(axis=1, keepdims=True)
    return chosen, gates * scale


def held_experts_ffn(h: jnp.ndarray, chosen: jnp.ndarray, gates: jnp.ndarray,
                     gate_w: Optional[jnp.ndarray], up_w: jnp.ndarray,
                     down_w: jnp.ndarray, held: Tuple[int, int],
                     act: Callable[[jnp.ndarray], jnp.ndarray] = jax.nn.silu,
                     layer=None, out=None, split=None, impl: str = "auto"
                     ) -> jnp.ndarray:
    """``sum over the held e in chosen[n] of gates[n, e] * FFN_e(h[n])`` for
    every token ``n``: ``h`` [N, d]; ``chosen``, ``gates`` [N, k] over the
    router's full width; ``gate_w``, ``up_w`` [count, d, f] and ``down_w``
    [count, f, d] the matrices of experts ``first .. first + count - 1``;
    ``FFN_e(x) = (act(x Wg_e) * x Wu_e) Wd_e``, the gated product in float32;
    with ``gate_w`` None the experts have no gate, ``FFN_e(x) = act(x Wu_e)
    Wd_e`` (two products an expert, not three). ``split`` (float32 ``h``
    over bf16 matrices; ``gpt.split_bf16``): every product takes its rows,
    the middle too, as the bf16 pieces ``split`` makes of them, inside the
    one grouped product (``moe_two_pass``). Matrices with
    more rows than ``d`` (``up_w``) and more columns (``down_w``), zeros
    there (``GPTConfig.moe_rows``): ``h`` is padded to them and the first
    ``d`` columns come back.
    [N, d] in ``h``'s type, or in ``out``, in which the three products are
    then accumulated and returned too.

    With a ``layer`` index (it may be traced) the matrices are every routed
    layer's stacks, [L, count, ...], and only that layer's are read, where
    they lie: the stack's (layer, expert) pairs are the product's groups and
    every other layer's group is empty. A layer loop that slices its experts
    out of the stack hands the product a copy of them, 1.9 GB a layer at 40
    experts of 5120 x 1536: 23 of a decode step's 50 ms (PERF.md, PR 34).

    ``impl`` is ``grouped_dot``'s: "auto" (its kernel on a TPU,
    ``jax.lax.ragged_dot`` elsewhere), "kernel", "ragged"."""
    first, count = held
    n, k = chosen.shape
    local = chosen - first
    mine = (local >= 0) & (local < count)
    expert = jnp.where(mine, local, count).reshape(-1)      # [N k]; count:
    order = jnp.argsort(expert, stable=True)                # not held, last
    sizes = jnp.zeros((count + 1,), jnp.int32).at[expert].add(1)[:count]
    if layer is not None:
        layers = up_w.shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((layers * count,), jnp.int32), sizes,
            (jnp.asarray(layer, jnp.int32) * count,))
        gate_w, up_w, down_w = (
            None if w is None else w.reshape((layers * count,) + w.shape[2:])
            for w in (gate_w, up_w, down_w))
    rows = h[order // k]                                    # [N k, d]
    d, tall = h.shape[1], up_w.shape[-2]
    if tall > d:    # matrices laid out taller than the stream is wide: zeros
        rows = jnp.pad(rows, ((0, 0), (0, tall - d)))
    # float32 rows over bf16 matrices: a product takes each row as the bf16
    # pieces ``split`` makes of it (16 bits of mantissa with two, where one
    # pass keeps 8), side by side in the row's own group, so the matrices
    # are read once and the groups are that many times as long

    def grouped(a, w):
        if split is None:
            return grouped_dot(a, w, sizes, preferred_element_type=out,
                               impl=impl)
        parts = jnp.stack(split(a), axis=1)                 # [N k, pieces, .]
        y = grouped_dot(parts.reshape(-1, a.shape[1]), w,
                        parts.shape[1] * sizes,
                        preferred_element_type=jnp.float32, impl=impl)
        return y.reshape(parts.shape[:2] + (-1,)).sum(axis=1)

    if gate_w is None:
        mid = act(grouped(rows, up_w).astype(jnp.float32))
    else:
        mid = (act(grouped(rows, gate_w).astype(jnp.float32))
               * grouped(rows, up_w).astype(jnp.float32))
    y = grouped(mid if split else mid.astype(h.dtype), down_w)
    if tall > d:
        y = y[:, :d]
    # rows past the held experts' belong to no group: whatever the product
    # left there is not read
    y = jnp.where(mine.reshape(-1)[order][:, None],
                  y.astype(jnp.float32) * gates.reshape(-1)[order][:, None],
                  0.0)
    back = jnp.argsort(order)                               # row of (n, j)
    return y[back].reshape(n, k, -1).sum(axis=1).astype(out or h.dtype)


def zero_experts(h: jnp.ndarray, chosen: jnp.ndarray, gates: jnp.ndarray,
                 real: int) -> jnp.ndarray:
    """``(sum over the chosen e >= real of gates[n, e]) * h[n]`` for every
    token ``n``, float32 [N, d]: what the zero-compute (identity) experts a
    token chose give it. ``h`` [N, d] the routed layer's own input;
    ``chosen``, ``gates`` [N, k] of :func:`route` over ``real`` experts with
    weights and the zero-compute ones after them."""
    weight = jnp.where(chosen >= real, gates, 0.0).sum(axis=1)
    return weight[:, None].astype(jnp.float32) * h.astype(jnp.float32)
