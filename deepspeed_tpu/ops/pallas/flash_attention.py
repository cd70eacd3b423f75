"""Flash attention for TPU in Pallas.

Capability parity with the reference's fused attention kernels — training softmax
(``csrc/transformer/softmax_kernels.cu``) and the attention core of the fused
transformer layer (``csrc/transformer/ds_transformer_cuda.cpp``) — rebuilt as a
blockwise online-softmax kernel so the [T, T] score matrix never materializes in
HBM. This lifts the memory ceiling that forces full-recompute activation
checkpointing on long sequences (the reference's sparse-attention pillar targets the
same ceiling; blocksparse lives in ``blocksparse.py``).

Design (TPU-first, per the Pallas TPU guide):
- Four kernels, one call each a layer: ``flash_fwd``; ``flash_bwd_delta``
  (delta = rowsum(dO*O), once a row), ``flash_bwd_dq`` and ``flash_bwd_dkv``
  using the saved logsumexp. Wrapped in ``jax.custom_vjp``.
- grid = (heads / heads a step, owned rows / rows a step, walked rows /
  resident range). A step OWNS ``rows`` rows of ``heads`` heads (queries in
  ``flash_fwd`` and ``flash_bwd_dq``, keys in ``flash_bwd_dkv``) and has the
  operands it WALKS (k and v; q, dO, lse and delta in ``dkv``) resident in VMEM
  for the whole head where that fits: their block index does not change
  across a head's steps, so Pallas fetches them once a head. Inside the step a
  ``fori_loop`` walks the resident range in ``block_q`` x ``block_k`` tiles, the
  (max, sum, acc) state in VMEM scratch (the gradients in their revisited
  float32 output block) — MXU does the matmuls of a tile, VPU the rescale.
- causal work is triangular: the loop's bounds come from the owned tile's
  rows, tiles wholly above the diagonal are neither fetched nor visited, and
  only the tiles the diagonal crosses build a mask.
- ``_plan`` derives rows, heads and resident range from the shapes, the
  itemsize and a VMEM budget (double buffers counted) and asks for the scoped
  VMEM it reckons. A sequence beyond the budget STREAMS: the last grid axis
  runs over resident ranges, a step owns one tile of one head and the state
  persists across the axis (with range = tile that is a one-tile-a-step
  streamed grid); ranges the mask hides keep the previous range's block index,
  so they are not fetched either.
- fp32 accumulators; the saved logsumexp rides a 128-lane broadcast layout
  ([BH, T, 128]) because TPU VMEM tiles are (8, 128) — a bare [BH, T] residual
  would violate the layout constraints (same trick as jax's reference TPU kernel).
- ``interpret=True`` automatically off-TPU so the same code runs in CPU CI.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional, Tuple

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


# -------------------------------------------------------------------------- plan
# the default scoped VMEM limit: what a step's operand blocks may take, both
# pipeline buffers counted (the tiles' temporaries come on top, see _plan),
# and the least a call asks for
_VMEM_BLOCK_BYTES = 16 << 20
# (query, key) pairs x head dim that make a grid step worth tens of
# microseconds against the third of one a step costs by itself
_STEP_PAIRS_D = 1 << 28
# the inner tile (block_q, block_k) where the caller names none: the best of
# scripts/flash_tile_tune.py's sweep on a v5e at both train cells' shapes
# (PERF.md, PR 30). The forward pays per key tile for two lane reductions and
# a rescale of its state, so it wants its keys in few wide tiles; the
# backward kernels have neither and lose more to the masked half of the
# tiles the diagonal crosses
_TILE = {"fwd": (1024, 1024), "dq": (512, 512), "dkv": (512, 512)}


@dataclasses.dataclass(frozen=True)
class Plan:
    """How one kernel walks its operands (see the module docstring)."""

    rows: int       # rows of the owned operands a grid step takes
    heads: int      # heads a grid step takes
    resident: int   # rows of the walked operands resident in VMEM
    block_q: int    # the inner tile
    block_k: int
    grid: Tuple[int, int, int]
    vmem_bytes: int  # blocks (double-buffered) and scratch, lane padding counted
    vmem_limit: int  # the scoped VMEM the call asks for


def _padded(d: int) -> int:
    """A row of ``d`` elements as VMEM lays it out: whole lane tiles."""
    return -(-d // LANES) * LANES


def _row_bytes(kernel: str, d: int, itemsize: int) -> Tuple[int, int]:
    """Bytes one row of one head takes in VMEM, one buffer: (the operands the
    kernel owns, the operands it walks)."""
    x = _padded(d) * itemsize  # a row of q, k, v, o or dO
    f = _padded(d) * 4         # a row of a float32 gradient
    r = LANES * 4              # a lane-padded row of lse or delta
    return {"fwd": (2 * x + r, 2 * x),           # q, o, lse | k, v
            "dq": (2 * x + 2 * r + f, 2 * x),    # q, dO, lse, delta, dq | k, v
            "dkv": (2 * x + 2 * f, 2 * x + 2 * r),  # k, v, dk, dv | q, dO, lse, delta
            }[kernel]


def _tile(block: Optional[int], default: int, n: int) -> int:
    """The caller's inner tile or the kernel's own, shrunk to the largest
    128-multiple of it (by halving) that divides the sequence."""
    block = min(block or default, n)
    while block > 128 and n % block:
        block //= 2
    return block


def _plan(kernel: str, bh: int, t: int, s: int, d: int, itemsize: int,
          block_q: Optional[int] = None, block_k: Optional[int] = None) -> Plan:
    """A pure function of the call's shapes: ``kernel`` is ``fwd``, ``dq``
    (both own query rows and walk the keys) or ``dkv`` (owns key rows, walks
    the queries). The whole walked sequence is resident if it fits the
    budget beside one owned tile; then a step takes as many owned rows as
    fit, and if that is all of them as many heads as make the step worth its
    overhead. Otherwise it streams: one owned tile of one head a step, the
    longest resident range that fits."""
    block_q = _tile(block_q, _TILE[kernel][0], t)
    block_k = _tile(block_k, _TILE[kernel][1], s)
    if t % block_q or s % block_k:
        raise ValueError(
            f"seq lens ({t},{s}) must divide blocks ({block_q},{block_k})")
    own_row, walk_row = _row_bytes(kernel, d, itemsize)
    n_own, tile_own, n_walk, tile_walk = (
        (s, block_k, t, block_q) if kernel == "dkv"
        else (t, block_q, s, block_k))

    def blocks(heads, rows, resident):
        return 2 * heads * (rows * own_row + resident * walk_row)

    def fits(heads, rows, resident):
        return blocks(heads, rows, resident) <= _VMEM_BLOCK_BYTES

    def longest(n, tile, ok):
        """the largest multiple of ``tile`` dividing ``n`` that is ``ok``"""
        return max([tile] + [m for m in range(tile, n + 1, tile)
                             if n % m == 0 and ok(m)])

    resident = longest(n_walk, tile_walk, lambda r: fits(1, tile_own, r))
    rows, heads = tile_own, 1
    if resident == n_walk:
        rows = longest(n_own, tile_own, lambda r: fits(1, r, resident))
    if rows == n_own and resident == n_walk:
        fit = [1] + [h for h in range(2, bh + 1)
                     if bh % h == 0 and fits(h, rows, resident)]
        enough = [h for h in fit if h * t * s * d >= _STEP_PAIRS_D]
        heads = min(enough) if enough else max(fit)
    # fwd keeps one tile's (acc, max, sum) in scratch; a tile's scores, their
    # exponentials and the gradients of both are float32 temporaries
    scratch = (block_q * (_padded(d) + 2 * LANES) * 4 if kernel == "fwd"
               else 0)
    vmem = blocks(heads, rows, resident) + scratch
    return Plan(rows, heads, resident, block_q, block_k,
                (bh // heads, n_own // rows, n_walk // resident), vmem,
                max(_VMEM_BLOCK_BYTES,
                    vmem + 8 * block_q * block_k * 4 + (4 << 20)))


def _compiler_params(plan: Plan):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=plan.vmem_limit)


def _aligned(i, tile: int):
    """``i * tile`` as a slice start the compiler knows to be aligned."""
    return i * tile if isinstance(i, int) else pl.multiple_of(i * tile, tile)


def _each(n: int, fn) -> None:
    """``fn(i)`` for i in [0, n): a loop in the kernel unless n is 1."""
    if n == 1:
        fn(0)
    else:
        jax.lax.fori_loop(0, n, lambda i, _: fn(i), None)


def _each_tile(heads: int, tiles: int, fn) -> None:
    """``fn(head, tile)`` over the heads and owned tiles of a grid step."""
    _each(heads, lambda h: _each(tiles, lambda t: fn(h, t)))


def _loop(lo, hi, fn) -> None:
    jax.lax.fori_loop(lo, hi, lambda i, _: fn(i), None)


def _masked(s, row0, col0):
    """Scores of a tile whose first row sits at absolute position ``row0``
    and first column at ``col0``, keys after the query's position hidden."""
    q_pos = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos <= q_pos, s, NEG_INF)


def _key_tiles(row0, block_q: int, block_k: int, first, tiles: int,
               causal: bool):
    """For the query tile whose first row is at absolute position ``row0``,
    among the resident key tiles (``tiles`` of them, the range's first being
    tile ``first`` of the sequence): [0, full) need no mask, [full, end) are
    crossed by the diagonal, the rest are hidden."""
    if not causal:
        return tiles, tiles
    full = (row0 + 1) // block_k - first
    end = (row0 + block_q - 1) // block_k + 1 - first
    return jnp.clip(full, 0, tiles), jnp.clip(end, 0, tiles)


def _query_tiles(col0, block_q: int, block_k: int, q_offset: int, first,
                 tiles: int, causal: bool):
    """For the key tile whose first column is ``col0``, among the resident
    query tiles: [start, full) are crossed by the diagonal, [full, tiles)
    see the whole tile, those before ``start`` see none of it."""
    if not causal:
        return 0, 0
    start = (col0 - q_offset) // block_q - first
    full = (col0 - q_offset + block_k + block_q - 2) // block_q - first
    return jnp.clip(start, 0, tiles), jnp.clip(full, 0, tiles)


def _walked_kv(plan: Plan, causal: bool, q_offset: int):
    """Index map of k and v in ``fwd`` and ``dq``: resident range ``ri``, or
    the last one the step's rows can see where ``ri`` lies beyond it (an
    unchanged index is not fetched again)."""
    n = plan.grid[2]
    if not causal or n == 1:
        return lambda g, qi, ri: (g, ri, 0)

    def index(g, qi, ri):
        last = (q_offset + (qi + 1) * plan.rows - 1) // plan.resident
        return g, jnp.minimum(ri, jnp.clip(last, 0, n - 1)), 0
    return index


def _walked_q(plan: Plan, causal: bool, q_offset: int):
    """Index map of q, dO, lse and delta in ``dkv``: resident range ``ri``, or
    the first one that can see the step's keys where ``ri`` lies before it."""
    n = plan.grid[2]
    if not causal or n == 1:
        return lambda g, ki, ri: (g, ri, 0)

    def index(g, ki, ri):
        first = (ki * plan.rows - q_offset) // plan.resident
        return g, jnp.maximum(ri, jnp.clip(first, 0, n - 1)), 0
    return index


def _owned(g, i, ri):
    return g, i, 0


# --------------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale: float, causal: bool, plan: Plan, q_offset: int,
                stochastic_mode: bool):
    """The online softmax of ``plan.heads`` x ``plan.rows`` query rows over
    the resident keys: per query tile a loop over the key tiles it can see,
    the (acc, m, l) state in VMEM scratch, which persists across the last
    grid axis when the keys stream in several resident ranges."""
    qi = pl.program_id(1)
    ri = pl.program_id(2)
    bq, bk = plan.block_q, plan.block_k
    tiles = plan.resident // bk
    # stochastic mode (parity: ds_transformer_cuda.cpp:63 stochastic_mode —
    # speed over run-exactness): matmul operands stay in the input dtype so
    # the MXU runs its native bf16 pass (fp32 upcast costs multiple passes);
    # accumulation and the softmax state remain fp32
    lo = q_ref.dtype if stochastic_mode else jnp.float32

    def q_tile(h, t):
        rows = (h, pl.ds(_aligned(t, bq), bq))
        # q rows sit at absolute positions q_offset + their index
        row0 = q_offset + qi * plan.rows + t * bq

        @pl.when(ri == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        q = (q_ref[rows].astype(jnp.float32) * sm_scale).astype(lo)  # [Bq, D]

        def key_tile(j, masked):
            cols = (h, pl.ds(_aligned(j, bk), bk))
            k = k_ref[cols].astype(lo)  # [Bk, D]
            v = v_ref[cols].astype(lo)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if masked:
                s = _masked(s, row0, ri * plan.resident + j * bk)
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

        full, end = _key_tiles(row0, bq, bk, ri * tiles, tiles, causal)
        _loop(0, full, lambda j: key_tile(j, False))
        _loop(full, end, lambda j: key_tile(j, True))

        @pl.when(ri == pl.num_programs(2) - 1)
        def _finalize():
            l_i = l_ref[:, :1]
            l_safe = jnp.where(l_i == 0.0, 1.0, l_i)
            o_ref[rows] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
            lse = m_ref[:, :1] + jnp.log(l_safe)  # [Bq, 1]
            lse_ref[rows] = jnp.broadcast_to(lse, (bq, LANES))

    _each_tile(plan.heads, plan.rows // bq, q_tile)


def _fwd(q, k, v, sm_scale: float, causal: bool, block_q: Optional[int],
         block_k: Optional[int], stochastic_mode: bool = False):
    """q,k,v: [BH, T, D] -> (o [BH, T, D], lse [BH, T, LANES])."""
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    S = k.shape[1]
    plan = _plan("fwd", BH, T, S, D, q.dtype.itemsize, block_q, block_k)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, plan=plan,
        q_offset=S - T, stochastic_mode=stochastic_mode)
    rows = pl.BlockSpec((plan.heads, plan.rows, D), _owned)
    keys = pl.BlockSpec((plan.heads, plan.resident, D),
                        _walked_kv(plan, causal, S - T))
    o, lse = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=[rows, keys, keys],
        out_specs=[rows, pl.BlockSpec((plan.heads, plan.rows, LANES), _owned)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((plan.block_q, D), jnp.float32),      # acc
            pltpu.VMEM((plan.block_q, LANES), jnp.float32),  # running max
            pltpu.VMEM((plan.block_q, LANES), jnp.float32),  # running sum
        ],
        compiler_params=_compiler_params(plan),
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------- bwd
# The backward kernels walk the CONTRACTED sequence axis (dq the keys, dkv
# the queries) the way the forward walks its keys; their accumulators are the
# float32 output blocks, revisited across the last grid axis when the walked
# operands stream in several resident ranges.


def _bwd_delta_kernel(o_ref, do_ref, delta_ref, *, plan: Plan):
    """delta = rowsum(dO * O), computed ONCE per q row (it is k-invariant)
    and broadcast across lanes like the lse residual."""
    bq = plan.block_q

    def q_tile(h, t):
        rows = (h, pl.ds(_aligned(t, bq), bq))
        delta = jnp.sum(do_ref[rows].astype(jnp.float32)
                        * o_ref[rows].astype(jnp.float32),
                        axis=-1, keepdims=True)
        delta_ref[rows] = jnp.broadcast_to(delta, (bq, LANES))

    _each_tile(plan.heads, plan.rows // bq, q_tile)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   sm_scale: float, causal: bool, plan: Plan, q_offset: int,
                   stochastic_mode: bool):
    qi = pl.program_id(1)
    ri = pl.program_id(2)
    bq, bk = plan.block_q, plan.block_k
    tiles = plan.resident // bk
    lo = q_ref.dtype if stochastic_mode else jnp.float32

    def q_tile(h, t):
        rows = (h, pl.ds(_aligned(t, bq), bq))
        row0 = q_offset + qi * plan.rows + t * bq

        @pl.when(ri == 0)
        def _init():
            dq_ref[rows] = jnp.zeros((bq, dq_ref.shape[-1]), dq_ref.dtype)

        q = q_ref[rows].astype(lo)
        do = do_ref[rows].astype(lo)
        lse = lse_ref[rows][:, :1]  # [Bq, 1]
        delta = delta_ref[rows][:, :1]  # [Bq, 1]

        def key_tile(j, masked):
            cols = (h, pl.ds(_aligned(j, bk), bk))
            k = k_ref[cols].astype(lo)
            v = v_ref[cols].astype(lo)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                s = _masked(s, row0, ri * plan.resident + j * bk)
            p = jnp.exp(s - lse)  # [Bq, Bk]
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            dq_ref[rows] += jax.lax.dot(
                ds.astype(lo), k,
                preferred_element_type=jnp.float32).astype(dq_ref.dtype)

        full, end = _key_tiles(row0, bq, bk, ri * tiles, tiles, causal)
        _loop(0, full, lambda j: key_tile(j, False))
        _loop(full, end, lambda j: key_tile(j, True))

    _each_tile(plan.heads, plan.rows // bq, q_tile)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, sm_scale: float, causal: bool,
                    plan: Plan, q_offset: int, stochastic_mode: bool):
    ki = pl.program_id(1)
    ri = pl.program_id(2)
    bq, bk = plan.block_q, plan.block_k
    tiles = plan.resident // bq
    lo = k_ref.dtype if stochastic_mode else jnp.float32

    def k_tile(h, t):
        cols = (h, pl.ds(_aligned(t, bk), bk))
        col0 = ki * plan.rows + t * bk

        @pl.when(ri == 0)
        def _init():
            dk_ref[cols] = jnp.zeros((bk, dk_ref.shape[-1]), dk_ref.dtype)
            dv_ref[cols] = jnp.zeros((bk, dv_ref.shape[-1]), dv_ref.dtype)

        k = k_ref[cols].astype(lo)  # [Bk, D]
        v = v_ref[cols].astype(lo)

        def query_tile(i, masked):
            rows = (h, pl.ds(_aligned(i, bq), bq))
            q = q_ref[rows].astype(lo)  # [Bq, D]
            do = do_ref[rows].astype(lo)
            lse = lse_ref[rows][:, :1]  # [Bq, 1]
            delta = delta_ref[rows][:, :1]  # [Bq, 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [Bq, Bk]
            if masked:
                s = _masked(s, q_offset + ri * plan.resident + i * bq, col0)
            p = jnp.exp(s - lse)
            dv_ref[cols] += jax.lax.dot_general(
                p.astype(lo), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dv_ref.dtype)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            dk_ref[cols] += jax.lax.dot_general(
                ds.astype(lo), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dk_ref.dtype)

        start, full = _query_tiles(col0, bq, bk, q_offset, ri * tiles, tiles,
                                   causal)
        _loop(start, full, lambda i: query_tile(i, True))
        _loop(full, tiles, lambda i: query_tile(i, False))

    _each_tile(plan.heads, plan.rows // bk, k_tile)


def _bwd(sm_scale, causal, block_q, block_k, stochastic_mode, res, do):
    q, k, v, o, lse = res
    BH, T, D = q.shape
    S = k.shape[1]
    args = dict(sm_scale=sm_scale, causal=causal, q_offset=S - T,
                stochastic_mode=stochastic_mode)

    plan = _plan("dq", BH, T, S, D, q.dtype.itemsize, block_q, block_k)
    rows = pl.BlockSpec((plan.heads, plan.rows, D), _owned)
    row_stats = pl.BlockSpec((plan.heads, plan.rows, LANES), _owned)
    keys = pl.BlockSpec((plan.heads, plan.resident, D),
                        _walked_kv(plan, causal, S - T))

    # prologue: delta = rowsum(dO*O) once per q row (k-invariant), in the
    # same 128-lane broadcast layout as the lse residual
    delta = pl.pallas_call(
        functools.partial(_bwd_delta_kernel, plan=plan),
        grid=plan.grid[:2],
        in_specs=[pl.BlockSpec((plan.heads, plan.rows, D),
                               lambda g, qi: (g, qi, 0))] * 2,
        out_specs=pl.BlockSpec((plan.heads, plan.rows, LANES),
                               lambda g, qi: (g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, LANES), jnp.float32),
        interpret=_interpret(),
        name="flash_bwd_delta",
    )(o, do)

    # accumulators are the (revisited) fp32 OUTPUT blocks; cast at the end —
    # accumulating in bf16 across the key tiles would lose precision
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, plan=plan, **args),
        grid=plan.grid,
        in_specs=[rows, keys, keys, rows, row_stats, row_stats],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((BH, T, D), jnp.float32),
        compiler_params=_compiler_params(plan),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    plan = _plan("dkv", BH, T, S, D, q.dtype.itemsize, block_q, block_k)
    walked = _walked_q(plan, causal, S - T)
    queries = pl.BlockSpec((plan.heads, plan.resident, D), walked)
    query_stats = pl.BlockSpec((plan.heads, plan.resident, LANES), walked)
    key_rows = pl.BlockSpec((plan.heads, plan.rows, D), _owned)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, plan=plan, **args),
        grid=plan.grid,
        in_specs=[queries, key_rows, key_rows, queries, query_stats,
                  query_stats],
        out_specs=[key_rows, key_rows],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
        ],
        compiler_params=_compiler_params(plan),
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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    stochastic_mode: bool = False,
) -> jnp.ndarray:
    """Blockwise attention with online softmax; differentiable (custom VJP).

    ``block_q`` / ``block_k`` are the inner tile of all four kernels; left
    out, each kernel takes its own measured one (``_TILE``). Either way a tile
    shrinks to a 128-multiple that divides the sequence, or the call raises.

    ``stochastic_mode`` trades bit-exactness for speed (parity:
    ``csrc/transformer/ds_transformer_cuda.cpp:63``): matmul operands ride the
    input dtype onto the MXU's native bf16 pass instead of being upcast to
    fp32; accumulators and softmax state stay fp32. Off by default."""
    B, T, H, D = q.shape
    S = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(D)
    # [B, T, H, D] -> [B*H, T, D]
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    o = _flash(qt, kt, vt, scale, causal, block_q, block_k,
               bool(stochastic_mode))
    return o.reshape(B, H, T, D).transpose(0, 2, 1, 3)
