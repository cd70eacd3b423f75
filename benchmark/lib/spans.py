"""Spans recorded from the benchmark's own files around the calls into each
layer: name, start, end, parent, and what work the call carried. Kept in
memory; a traced run also writes each as a ``TraceAnnotation`` so the
profiler's host timeline carries the same names on the device's clock.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict, List, Optional


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    parent: Optional[int]          # index of the enclosing span
    meta: Dict[str, float]

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class SpanLog:
    def __init__(self, clock: Callable[[], float], annotate: bool = False):
        self.clock = clock
        self.spans: List[Span] = []
        self._open: List[int] = []
        self._annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        idx = len(self.spans)
        sp = Span(name, self.clock(), float("nan"),
                  self._open[-1] if self._open else None, dict(meta))
        self.spans.append(sp)
        self._open.append(idx)
        ann = contextlib.nullcontext()
        if self._annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        try:
            with ann:
                yield sp
        finally:
            sp.t1 = self.clock()
            self._open.pop()

    def named(self, name: str, t_open: float = float("-inf"),
              t_close: float = float("inf")) -> List[Span]:
        return [s for s in self.spans
                if s.name == name and s.t0 >= t_open and s.t1 <= t_close]

    def children(self, idx: int) -> List[Span]:
        return [s for s in self.spans if s.parent == idx]


class ExecutorProxy:
    """Forwards to the serving engine and records a span around each dispatch
    the scheduler makes, with the tokens it carried. Set as
    ``scheduler.executor``: the program is not touched."""

    def __init__(self, engine, log: SpanLog):
        self._engine = engine
        self._log = log

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def prefill_many(self, items):
        items = list(items)
        tokens = sum(len(it[1]) for it in items)
        with self._log.span("prefill", tokens=tokens, requests=len(items)):
            return self._engine.prefill_many(items)

    def prefill(self, slot, tokens, table_row, start=0):
        with self._log.span("prefill", tokens=len(tokens), requests=1):
            return self._engine.prefill(slot, tokens, table_row, start)

    def decode(self, tokens, tables, lengths, active, steps=1):
        live = int(lengths[active].sum())
        with self._log.span("decode", steps=int(steps),
                            active=int(active.sum()), live_kv_tokens=live):
            return self._engine.decode(tokens, tables, lengths, active,
                                       steps=steps)
