"""Operations and bytes of each Pallas kernel, computed from its shapes: what
the algorithm needs for one call, for a kernel's share of its roofline.

Nothing here looks at the program. A cost is what the call must compute and
move given its inputs and outputs, not what an implementation happens to do:
the flash kernels keep their log-sum-exp and delta rows as ``[.., T, 128]``
float32 (one value broadcast over a lane tile); ``lse_lanes=128`` counts those
bytes as moved, the default counts the one float a row needs.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def floor_s(self, peaks) -> float:
        """The least time one call can take on a chip with these peaks."""
        return max(self.flops / peaks.bf16_flops,
                   self.bytes / peaks.hbm_bytes_per_s)

    def bound(self, peaks) -> str:
        return ("flops" if self.flops / peaks.bf16_flops
                >= self.bytes / peaks.hbm_bytes_per_s else "bytes")

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(self.flops + other.flops, self.bytes + other.bytes)


def attended_pairs(t: int, s: int, causal: bool) -> float:
    """(query, key) pairs one head scores: the full rectangle, or under a
    causal mask the part at or below the diagonal that ends at the last key
    (the kernels' ``q_offset = s - t``)."""
    return float(t * s - t * (t - 1) / 2) if causal else float(t * s)


def flash_fwd(bh: int, t: int, s: int, dh: int, causal: bool = True,
              itemsize: int = 2, lse_lanes: int = 1) -> Cost:
    """Forward: QK^T and PV, 4 operations a pair a head-dim element; reads
    q, k, v, writes o and one float32 log-sum-exp a row."""
    flops = 4.0 * bh * attended_pairs(t, s, causal) * dh
    moved = bh * dh * itemsize * (2 * t + 2 * s) + bh * t * 4 * lse_lanes
    return Cost(flops, float(moved))


def flash_bwd_delta(bh: int, t: int, dh: int, itemsize: int = 2,
                    lse_lanes: int = 1) -> Cost:
    """delta = rowsum(dO * O): reads o and dO, writes one float32 a row."""
    return Cost(2.0 * bh * t * dh,
                float(bh * t * dh * itemsize * 2 + bh * t * 4 * lse_lanes))


def flash_bwd_dq(bh: int, t: int, s: int, dh: int, causal: bool = True,
                 itemsize: int = 2, lse_lanes: int = 1) -> Cost:
    """dQ: recomputes the scores, then dP = dO V^T and dQ = dS K: three
    matrix products. Reads q, k, v, dO, lse, delta; writes dq in float32."""
    flops = 6.0 * bh * attended_pairs(t, s, causal) * dh
    moved = (bh * dh * itemsize * (2 * t + 2 * s) + bh * t * dh * 4
             + 2 * bh * t * 4 * lse_lanes)
    return Cost(flops, float(moved))


def flash_bwd_dkv(bh: int, t: int, s: int, dh: int, causal: bool = True,
                  itemsize: int = 2, lse_lanes: int = 1) -> Cost:
    """dK and dV: the scores again, dP, dV = P^T dO and dK = dS^T Q: four
    matrix products. Reads q, k, v, dO, lse, delta; writes dk, dv float32."""
    flops = 8.0 * bh * attended_pairs(t, s, causal) * dh
    moved = (bh * dh * itemsize * (2 * t + 2 * s) + 2 * bh * s * dh * 4
             + 2 * bh * t * 4 * lse_lanes)
    return Cost(flops, float(moved))


def paged_decode(live_kv_tokens: float, n_head: int, dh: int,
                 itemsize: int = 2) -> Cost:
    """One layer's paged decode call: every live key and value is read once
    and takes 4 operations a head-dim element (a score and a weighted sum);
    queries and outputs are thousands of times smaller."""
    elements = live_kv_tokens * n_head * dh
    return Cost(4.0 * elements, 2.0 * elements * itemsize)
