"""Operations and bytes of latent attention under a learned selection and
inside a window, computed from shapes beside ``lib/kernel_cost_mla.py``: what
the mathematics asks of one decode step, whatever implements it, for the
shares ``readers/prog_roofline_mla_select.py`` reports.

Nothing here looks at the program. A full layer attends over the rows its
selection kept, ``min(length, index_topk)`` a slot, each one row ``[c | k_r]``
for all heads (``kernel_cost_mla.paged_decode_mla`` of those rows: a program
that walks every live row under a mask reads more than this counts and shows
a lower share). A window layer attends over ``min(length, window)`` rows of
its own, wider, latent. The indexer scores every live index key: one key of
``dim`` numbers for all its heads, a dot product and a weighted relu a head.
"""

from __future__ import annotations

from .kernel_cost import Cost
from .kernel_cost_mla import paged_decode_mla


def attended_rows(rows: float, n_head: int, rank: int, rope: int,
                  itemsize: int = 2) -> Cost:
    """One layer's attention over ``rows`` cached rows (the selected ones of
    a full layer, the window's of a window layer): ``(rank + rope) *
    itemsize`` bytes and ``2 * n_head * (2 * rank + rope)`` operations a
    row."""
    return paged_decode_mla(rows, n_head, rank, rope, itemsize)


def index_scores(keys: float, heads: int, dim: int, itemsize: int = 2) -> Cost:
    """One full layer's index scores over ``keys`` live index keys: ``dim *
    itemsize`` bytes and ``2 * heads * dim`` operations a key (the relu and
    the heads' weighted sum are ``2 * heads`` more: a hundredth)."""
    return Cost(2.0 * keys * heads * dim, float(keys * dim * itemsize))
