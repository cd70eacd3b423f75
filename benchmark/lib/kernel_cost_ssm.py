"""Operations and bytes of the Mamba-2 decode kernel (``ssm_decode``),
computed from its shapes beside ``lib/kernel_cost.py``: what the algorithm
needs for one call, for the kernel's share of its roofline.

Nothing here looks at the program. One call is one layer's recurrence for the
slots that hold a request: each slot's state ``[heads, head_dim, state]`` is
read, decayed, added to and written back, float32, and the output is read off
it. Beside that the call takes ``dt x`` and gives ``y`` (``heads x
head_dim`` each), the decay (``heads``) and ``B`` and ``C`` (``groups x
state`` each) a slot: a thousandth of the state's bytes. The same call shifts
the slot's convolution window where it lies (``window_rows x conv_width``,
the last ``K - 1`` rows of ``xBC``): read, written, and the step's new row
taken, 3.5% of the state's bytes at Nemotron-3-Nano's sizes. A state element
takes 5 operations (the decay's
multiply, the outer product's multiply and add, the output's multiply and
add): 0.6 a byte moved, far under the v5e's ridge of 240.5: the bytes bound
it.
"""

from __future__ import annotations

from .kernel_cost import Cost


def ssm_decode(slots: float, heads: int, head_dim: int, state: int,
               groups: int, window_rows: int = 0, itemsize: int = 4) -> Cost:
    """One layer's call over ``slots`` live decode slots; ``window_rows``:
    the rows of the convolution window the call shifts (``K - 1``; 0: the
    states alone)."""
    elements = heads * head_dim * state
    inputs = 2 * heads * head_dim + heads + 2 * groups * state
    conv_width = heads * head_dim + 2 * groups * state
    window = (2 * window_rows + 1) * conv_width if window_rows else 0
    return Cost(5.0 * slots * elements,
                float(slots * (2 * elements + inputs + window) * itemsize))
