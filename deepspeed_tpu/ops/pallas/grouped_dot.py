"""The grouped product over a stack of matrices, in Pallas: rows of group
``e`` against matrix ``e``, each touched matrix moved from HBM once.

``grouped_dot(a, w, sizes)`` is ``jax.lax.ragged_dot``'s contract: ``a``
``[M, K]`` rows sorted by group, ``w`` ``[G, K, N]``, ``sizes`` ``[G]`` int32
rows a group; row ``r`` of the result is ``a[r] @ w[group of r]``; rows past
the last group belong to none, are not read, and what the result holds there
is unspecified (``ragged_dot`` writes zeros; nobody reads them:
``moe/dropless.held_experts_ffn`` masks them).

Every such product of a routed serve cell is bound by the matrices' bytes (a
decode step of 512 slots meets 64 experts of 3072 x 2048 with 24-48 rows
each: 805 MB for 1.6 ms of MXU), so the kernel is built around the stream of
matrices and nothing else waits:

- **A work list** (:func:`grouped_work_list`), built on the device from
  ``sizes``: the rows are cut into fixed tiles of ``tm`` and an item is a
  (group, row tile) pair in which the group has rows, in group order. A group
  without rows (every other layer's experts of a ``[L * count, K, N]`` stack,
  every expert no token chose) is no item. The grid's bound is the traced
  item count (as ``decode_attention.paged_work_list``'s).
- **The matrix streamed where it lies, once**: ``w`` stays in HBM
  (``memory_space=ANY``); the item that holds a group's first row waits for
  the group's matrix in one of ``_AHEAD + 1`` VMEM buffers and at once
  starts the copy of the group with rows ``_AHEAD`` further on, so
  ``_AHEAD`` copies are in flight whatever the items do: a group that
  straddles a tile boundary is two items over one resident matrix, where a
  pipelined block would stall its successor's copy behind the second. No
  slice of the stack, no copy in HBM. Two copies ahead and not one: behind a
  2 MB matrix one copy leaves the memory idle between its end and the next
  one's start (580 GB/s with one, 657 with two, no more with three; 12-16 MB
  matrices gain 1-2%: my chip runs, PERF.md PR 42).
- **Tiles as large as VMEM takes**: the whole ``[K, N]`` matrix a copy (2 MB
  Laguna's, 12.6 MB Nemotron's, 15.7 MB DeepSeek's, three buffers each)
  while they fit ``_W_VMEM_BYTES``, else the whole ``K`` by the widest strip
  of ``N`` (a multiple of 128 that divides it) that does, the strips the
  grid's outer axis. The row tile is 128, the MXU's height: a tile's product
  then costs what the matrix's load into the array costs (about 8 us at
  3072 x 2048 by the array's rate, under the 15 us its copy takes), and a group of up to 128 rows is
  one item or, across a boundary, two. ``vmem_limit_bytes`` is said from the
  same arithmetic (:func:`_plan`).
- A row tile's result block is visited by consecutive items (the groups that
  share it, in order), each writing its own rows under a mask: the block
  stays in VMEM between them and leaves once.

bf16 x bf16 on the MXU into float32, cast to ``preferred_element_type`` on
the way out, as ``ragged_dot``.

``impl``: "auto" = the kernel on a TPU where :func:`_plan` finds tiles,
``jax.lax.ragged_dot`` elsewhere; "kernel" forces Pallas (interpret mode off
the TPU); "ragged" XLA's.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

# the MXU's height: rows a tile
_ROW_TILE = 128
# VMEM the buffers of the matrix stream may take together, of the 128 MiB a
# v5e core has; the row tile, the result block and the product's float32
# working copy come on top (about 9 MiB at 3072 x 2048)
_W_VMEM_BYTES = 64 * 1024 * 1024
# copies in flight: the matrices of this many groups behind the one at work
# are on their way, in as many buffers beside its own
_AHEAD = 2


class GroupedWork(NamedTuple):
    """The (group, row tile) pairs a grouped product visits
    (:func:`grouped_work_list`)."""
    groups: jnp.ndarray     # [cap] int32: item -> group
    tiles: jnp.ndarray      # [cap] int32: item -> row tile
    starts: jnp.ndarray     # [G] int32: a group's first row
    ends: jnp.ndarray       # [G] int32: one past its last
    following: jnp.ndarray  # [G] int32: the next group with rows, G if none
    n_items: jnp.ndarray    # () int32


def grouped_work_list(sizes: jnp.ndarray, m: int, tm: int) -> GroupedWork:
    """Group ``g`` owns rows ``starts[g] .. ends[g] - 1`` of ``m`` and one
    item for every tile of ``tm`` rows it has a row in, first tile first, the
    groups following one another: ``sum(ceil-span of g over the tiles)``
    items, none for an empty group, one for a group inside a tile.
    ``n_items`` is their count; entries from there to the arrays' static end
    (``ceil(m / tm) + min(G, m) - 1``: every tile once and once more for
    every group that can begin inside one) repeat the last item and are
    never visited."""
    sizes = jnp.asarray(sizes, jnp.int32)
    n_groups = sizes.shape[0]
    ends = jnp.minimum(jnp.cumsum(sizes), m)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    some = ends > starts
    span = jnp.where(some, (ends - 1) // tm - starts // tm + 1, 0)
    item_ends = jnp.cumsum(span)
    cap = -(-m // tm) + min(n_groups, m) - 1
    i = jnp.arange(cap, dtype=jnp.int32)
    # the group whose items end after i: a compare against G ends, which
    # fuses (decode_attention.paged_work_list)
    groups = jnp.sum(i[:, None] >= item_ends[None, :], axis=1,
                     dtype=jnp.int32)
    groups = jnp.minimum(groups, n_groups - 1)
    tiles = starts[groups] // tm + jnp.minimum(
        i - (item_ends - span)[groups], span[groups] - 1)
    ids = jnp.arange(n_groups, dtype=jnp.int32)
    later = jax.lax.cummin(jnp.where(some, ids, n_groups), reverse=True)
    following = jnp.concatenate(
        [later[1:], jnp.full((1,), n_groups, jnp.int32)])
    return GroupedWork(groups, jnp.maximum(tiles, 0), starts, ends,
                       following, item_ends[-1])


def _plan(m: int, k: int, n: int, a_dtype, w_dtype, out_dtype
          ) -> Optional[Tuple[int, int, int]]:
    """(row tile, strip of N, ``vmem_limit_bytes``) from the shapes and types
    of a call, None where the kernel has no tiles for it: operands of two
    types, a matrix whose width is not whole lanes of 128 (Mosaic refuses
    its copy: 1856 columns, compile-only and my chip run, PR 42), or one
    whose whole-``K`` strips of 128 columns do not fit."""
    if jnp.dtype(a_dtype) != jnp.dtype(w_dtype) or n % 128:
        return None
    item = jnp.dtype(w_dtype).itemsize
    tm = _ROW_TILE if m >= _ROW_TILE else -(-m // 16) * 16
    tn = n
    if (_AHEAD + 1) * k * n * item > _W_VMEM_BYTES:
        fit = _W_VMEM_BYTES // ((_AHEAD + 1) * k * 128 * item)
        if fit < 1:
            return None
        lanes = n // 128
        tn = 128 * max(d for d in range(1, lanes + 1)
                       if lanes % d == 0 and d <= fit)
    vmem = ((_AHEAD + 1) * k * tn * item            # the stream's buffers
            + 2 * tm * k * item                     # the row tile, pipelined
            + 2 * tm * tn * jnp.dtype(out_dtype).itemsize   # the result block
            + 3 * tm * tn * 4                       # product, mask, old rows
            + 4 * 1024 * 1024)
    return tm, tn, vmem


def _cols(tn: int) -> int:
    """Columns one MXU product of a row tile covers: the widest of 512, 256
    and 128 that divides the strip."""
    return next(c for c in (512, 256, 128) if tn % c == 0)


def _kernel(grp_ref, tile_ref, start_ref, end_ref, next_ref, a_ref, w_ref,
            o_ref, wbuf, sem, cur, *, tm: int, tn: int, strips: int,
            n_groups: int):
    """One (strip of N, work item) step: item ``i`` is row tile
    ``tile_ref[i]`` of group ``g = grp_ref[i]``. ``cur`` holds which of the
    buffers has ``g``'s matrix; the item with the group's first row moves it
    on, waits for the copy started ``_AHEAD`` groups earlier and starts the
    one ``_AHEAD`` groups on (into the next strip behind a strip's last
    group)."""
    j, i = pl.program_id(0), pl.program_id(1)
    g, t = grp_ref[i], tile_ref[i]
    lo, hi = start_ref[g], end_ref[g]
    buffers = _AHEAD + 1

    def stream(group, strip, slot):
        src = (w_ref.at[group] if strips == 1
               else w_ref.at[group, :, pl.ds(strip * tn, tn)])
        return pltpu.make_async_copy(src, wbuf.at[slot], sem.at[slot])

    def after(group, strip, some):
        """The (group, strip) the stream takes behind this one, if any."""
        nxt = next_ref[group]
        more = nxt < n_groups
        return (jnp.where(more, nxt, grp_ref[0]),
                jnp.where(more, strip, strip + 1),
                some & (more | (strip + 1 < strips)))

    opening = (i == 0) & (j == 0)
    begins = t * tm <= lo       # the tile that holds the group's first row

    @pl.when(opening)
    def _open():
        cur[0] = 0
        ahead = (g, j, True)
        for slot in range(_AHEAD):
            pl.when(ahead[2])(stream(*ahead[:2], slot).start)
            ahead = after(*ahead)

    @pl.when(begins & jnp.logical_not(opening))
    def _move_on():
        cur[0] = jax.lax.rem(cur[0] + 1, buffers)

    slot = cur[0]

    @pl.when(begins)
    def _arrive():
        stream(g, j, slot).wait()
        ahead = (g, j, True)
        for _ in range(_AHEAD):
            ahead = after(*ahead)
        pl.when(ahead[2])(stream(
            *ahead[:2], jax.lax.rem(slot + _AHEAD, buffers)).start)

    rows = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (rows >= lo) & (rows < hi)
    a = a_ref[...]
    cols = _cols(tn)

    def strip(c, _):
        at = pl.ds(pl.multiple_of(c * cols, cols), cols)
        y = jnp.dot(a, wbuf[slot, :, at], preferred_element_type=jnp.float32)
        o_ref[:, at] = jnp.where(mine, y, o_ref[:, at].astype(jnp.float32)
                                 ).astype(o_ref.dtype)

    # a loop and not one product of the whole width: the unrolled product of
    # 128 x 5120 x 1536 is 1.4 MB of code a call, in every program
    jax.lax.fori_loop(0, tn // cols, strip, None)


def grouped_dot(a: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray,
                preferred_element_type=None, impl: str = "auto"
                ) -> jnp.ndarray:
    """``a[rows of group e] @ w[e]`` for every group: ``a`` [M, K] sorted by
    group, ``w`` [G, K, N], ``sizes`` [G] int32. [M, N] in
    ``preferred_element_type`` (None: ``a``'s type); rows past the last
    group hold nothing to read. ``impl``: the module's docstring."""
    m, k = a.shape
    n_groups, kw, n = w.shape
    if k != kw or sizes.shape != (n_groups,):
        raise ValueError(f"rows {a.shape} do not meet {w.shape} in groups "
                         f"{sizes.shape}")
    out_dtype = jnp.dtype(preferred_element_type or a.dtype)
    if impl not in ("auto", "kernel", "ragged"):
        raise ValueError(f"impl must be 'auto', 'kernel' or 'ragged': "
                         f"{impl!r}")
    plan = _plan(m, k, n, a.dtype, w.dtype, out_dtype)
    if impl == "auto":
        impl = ("kernel" if plan is not None
                and jax.default_backend() == "tpu" else "ragged")
    if impl == "ragged":
        return jax.lax.ragged_dot(
            a, w, sizes, preferred_element_type=preferred_element_type)
    if plan is None:
        raise ValueError(f"no tiles for {a.dtype} rows {a.shape} over "
                         f"{w.dtype} matrices {w.shape}")
    tm, tn, vmem = plan
    padded = -(-m // tm) * tm
    if padded > m:
        a = jnp.pad(a, ((0, padded - m), (0, 0)))
    work = grouped_work_list(sizes, padded, tm)
    strips = n // tn

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # groups, tiles, starts, ends, following
        grid=(strips, work.n_items),
        in_specs=[pl.BlockSpec((tm, k),
                               lambda j, i, grp, tile, *_: (tile[i], 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, i, grp, tile, *_: (tile[i], j)),
        scratch_shapes=[pltpu.VMEM((_AHEAD + 1, k, tn), w.dtype),
                        pltpu.SemaphoreType.DMA((_AHEAD + 1,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    kernel = functools.partial(_kernel, tm=tm, tn=tn, strips=strips,
                               n_groups=n_groups)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=_interpret(),
        name="grouped_dot",
    )(work.groups, work.tiles, work.starts, work.ends, work.following, a, w)
    return out[:m] if padded > m else out
