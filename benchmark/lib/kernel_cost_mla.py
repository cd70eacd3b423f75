"""Operations and bytes of the latent-attention decode kernel
(``paged_decode_mla``), computed from its shapes beside ``lib/kernel_cost.py``:
what the algorithm needs for one call, for the kernel's share of its roofline.

Nothing here looks at the program. A cached token is one row ``[c_kv |
k_rope]`` of ``rank + rope`` numbers for all heads; with the key-value
up-projection absorbed into the query, each of ``n_head`` heads scores it
(``rank + rope`` multiply-adds) and weighs its first ``rank`` columns into the
sum (``rank`` multiply-adds). A pool that pads the row to whole lanes moves
more than this counts: the padding is the implementation's, and shows as a
lower share.
"""

from __future__ import annotations

from .kernel_cost import Cost


def paged_decode_mla(live_kv_tokens: float, n_head: int, rank: int, rope: int,
                     itemsize: int = 2) -> Cost:
    """One layer's call: every live row is read once, ``(rank + rope) *
    itemsize`` bytes, and takes ``2 * n_head * (2 * rank + rope)`` operations;
    queries and outputs are thousands of times smaller. At 128 heads, rank
    512 and rope 64 that is 278,528 operations for 1,152 bytes: 241.8 a byte,
    at the v5e's ridge of 240.5."""
    return Cost(2.0 * live_kv_tokens * n_head * (2 * rank + rope),
                float(live_kv_tokens * (rank + rope) * itemsize))
