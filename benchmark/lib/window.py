"""The measured window: from one step boundary to the first one at or after
``seconds``. A rate is the work of the steps inside over the time between
those two boundaries; a latency is counted for a request that both started
and finished inside.

Every step ends in a host read of device results (``train_batch`` floats the
loss, ``scheduler.step`` reads the sampled tokens), so the host clock at a
boundary is synced with the device.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    steps: List[Dict[str, float]]      # work of each step inside
    step_s: List[float]                # how long each took, boundary to boundary
    paused: float = 0.0                # harness time at boundaries (profiler)

    @property
    def seconds(self) -> float:
        """Between the two boundaries, less what the harness itself spent at
        boundaries in between (starting and stopping the profiler in a traced
        run; nothing in a plain run)."""
        return self.t_close - self.t_open - self.paused

    def work(self, key: str) -> float:
        return sum(s.get(key, 0) for s in self.steps)

    def rate(self, key: str) -> float:
        """Work of the steps inside the window over the time between its two
        boundaries."""
        return self.work(key) / self.seconds

    def inside(self, t_start: Optional[float], t_end: Optional[float]) -> bool:
        return (t_start is not None and t_end is not None
                and t_start >= self.t_open and t_end <= self.t_close)


def run_window(step: Callable[[], Dict[str, float]], seconds: float,
               clock: Callable[[], float],
               on_boundary: Optional[Callable[[float, int], None]] = None
               ) -> Window:
    """Call ``step`` (which returns the work it did, by name) from a boundary
    until the first boundary at or after ``seconds``. ``on_boundary(elapsed,
    n_steps)`` runs at each boundary inside the window (the traced run starts
    and stops the profiler there); its time is the harness's own and is taken
    out of the window's length."""
    t_open = clock()
    steps: List[Dict[str, float]] = []
    step_s: List[float] = []
    paused = 0.0
    last = t_open
    while True:
        if on_boundary is not None:
            t = clock()
            on_boundary(t - t_open - paused, len(steps))
            last = clock()
            paused += last - t
        steps.append(step())
        now = clock()
        step_s.append(now - last)
        last = now
        if now - t_open - paused >= seconds:
            return Window(t_open, now, steps, step_s, paused)


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile (the value at rank ceil(p/100 * n)): always one
    of the samples, so a tail is a request that happened."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = max(1, -(-len(xs) * p // 100))
    return float(xs[int(rank) - 1])
