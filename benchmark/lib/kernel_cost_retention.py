"""Operations and bytes of the power-retention decode kernel
(``retention_decode``), computed from its shapes beside ``lib/kernel_cost.py``:
what the algorithm needs for one call, for the kernel's share of its roofline.

Nothing here looks at the program. One call is one layer's recurrence for the
slots that hold a request: each key-value head's state ``S`` [F, head_dim]
and its normaliser ``z`` [F] are read, decayed, written to by the token's key
and value and written back, float32, and the key-value head's ``group`` query
heads are read off them. ``F`` is the width of the symmetric second power of a
``head_dim``-wide key, ``head_dim (head_dim + 1) / 2`` (8,256 at 128): the
least any form of the algorithm keeps, so a program whose feature map is wider
(one that keeps all ``head_dim^2`` products: 16,384) reads under its share,
as it should. Beside that the call takes ``q`` (``group x head_dim``), ``k``,
``v`` (``head_dim`` each) and the gate, and gives ``o`` (``group x
head_dim``) a key-value head: 0.1% of the state's bytes. A state element
takes 3 operations for the write (the decay's multiply, the outer product's
multiply, the add) and 2 a query head for the read: 13 at 5 query heads, 1.6 a
byte moved, far under the v5e's ridge of 240.5: the bytes bound it.
"""

from __future__ import annotations

from .kernel_cost import Cost


def features(head_dim: int) -> int:
    """Width of the symmetric second power of a ``head_dim``-wide vector."""
    return head_dim * (head_dim + 1) // 2


def retention_decode(slots: float, kv_heads: int, group: int, head_dim: int,
                     itemsize: int = 4) -> Cost:
    """One layer's call over ``slots`` live decode slots: ``kv_heads`` states
    a slot, ``group`` query heads reading each."""
    elements = kv_heads * features(head_dim) * (head_dim + 1)
    inputs = kv_heads * ((2 * group + 2) * head_dim + 1)
    return Cost((3.0 + 2.0 * group) * slots * elements,
                float(slots * (2 * elements + inputs) * itemsize))
