"""Operations and bytes of the grouped-query decode kernel
(``paged_decode_gqa``), computed from its shapes beside ``lib/kernel_cost.py``:
what the algorithm needs for one call, for the kernel's share of its roofline.

Nothing here looks at the program. A cached token is a key row and a value
row for each of ``n_kv_head`` heads, ``head_dim`` wide, whatever the number of
query heads: each of ``n_head`` query heads scores its key-value head's key
(``head_dim`` multiply-adds) and weighs its value into the sum (``head_dim``
more). A window layer reads the rows inside its window only: the caller
counts them (``kv_rows``).
"""

from __future__ import annotations

from .kernel_cost import Cost


def paged_decode_gqa(kv_rows: float, n_head: int, n_kv_head: int,
                     head_dim: int, itemsize: int = 2) -> Cost:
    """One layer's call over ``kv_rows`` cached tokens (summed over the
    requests): every row is read once, ``2 * n_kv_head * head_dim *
    itemsize`` bytes, and takes ``4 * n_head * head_dim`` operations; queries
    and outputs are hundreds of times smaller. At 8 key-value heads of 128
    and 48 query heads that is 24,576 operations for 4,096 bytes, 6 a byte
    (8 at 64 heads): far under the v5e's ridge of 240.5, the bytes bound
    it."""
    return Cost(4.0 * kv_rows * n_head * head_dim,
                float(kv_rows * 2 * n_kv_head * head_dim * itemsize))
