"""From a ``jax.profiler`` trace to busy time, idle gaps, per-operation time
and exposed collective time.

The reduction works on plain events, ``(name, start, end)`` in seconds per
device, so it can be checked on a small recorded trace
(``benchmark/tests/data``). ``load_xplane`` turns the profiler's
``.xplane.pb`` into them with nothing but JAX.

The interval arithmetic started as a copy of
``deepspeed_tpu/comm/runtime_accounting.overlap_from_events`` (which had never
run on a chip). What the chip's trace made necessary: operations nest on the
device's "XLA Ops" line (a ``while`` spans its body), so time per operation is
self time; and the traced window is bounded by the harness's own annotation,
not by the first and last device event, so that idle time at its edges
counts.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]            # name, start, end (seconds)

WINDOW_ANNOTATION = "bench.traced_window"
NO_SPAN = "_no_span_"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"       # a -start .. -done pair as one interval
_OPCODE = re.compile(r"\s*([\w-]+)\(")
_CALLS = re.compile(r"calls=%([\w.-]+)")
# searched in an operation's short name (instruction name and opcode); an
# async pair's -start and -done on the operations line are device time that
# computes nothing, the pair's interval on the async line is the transfer
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast")


def short_name(hlo: str) -> str:
    """The trace names a device operation by its whole HLO line. Keep the
    instruction's name and what it is: ``closed_call.13_mosaic`` for a Pallas
    kernel (``custom_call_target="tpu_custom_call"``), else name and opcode,
    ``copy.76_copy``, ``fusion.272_fusion``, ``all-gather.186_all-gather``
    (the labels PR 22's ledger lines carry). A fusion that wraps a collective
    (``kind=kCustom, calls=%all-reduce-scatter``) is named for what it calls:
    ``fusion.10_all-reduce-scatter``."""
    name, eq, rest = hlo.partition(" = ")
    if not eq or not name.startswith("%"):
        return hlo[:80]
    if rest.startswith("("):                 # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    m = _OPCODE.match(rest)
    if not m:
        return hlo[:80]
    kind = m.group(1)
    if 'custom_call_target="tpu_custom_call"' in hlo:
        kind = "mosaic"
    elif kind == "fusion":
        called = _CALLS.search(hlo)
        if called and COLLECTIVE.search(called.group(1)):
            kind = called.group(1)
    return f"{name[1:]}_{kind}"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge [(start, end), ...] into a disjoint sorted union."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def measure(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total overlap between two disjoint sorted interval unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events
            if e > t0 and s < t1]


def complement(busy: Sequence[Interval], t0: float, t1: float
               ) -> List[Interval]:
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def self_times(events: Sequence[Event]) -> List[Event]:
    """Split nested events of one line into pieces in which the named event is
    the innermost one running: ``(name, start, end)`` pieces that do not
    overlap. A ``while`` keeps only the time none of its body's operations
    cover."""
    out: List[Event] = []
    stack: List[List] = []                   # [name, end, cursor]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, end, cursor = stack.pop()
            if end > cursor:
                out.append((name, cursor, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            top = stack[-1]
            if s > top[2]:
                out.append((top[0], top[2], s))
            top[2] = max(top[2], s)
            e = min(e, top[1])               # a child never outlives its parent
        stack.append([name, e, s])
    close(float("inf"))
    return [ev for ev in out if ev[2] > ev[1]]


@dataclasses.dataclass
class Reduced:
    """What the readers read. Seconds; per-device quantities are means over
    the devices that ran anything."""

    window_s: float
    busy_s: float
    n_devices: int
    op_seconds: Dict[str, float]             # self time by operation name
    collective_s: float
    exposed_collective_s: float
    gaps_by_span: Dict[str, float]           # idle seconds by covering span
    busy_in_span: Dict[str, float]           # busy seconds inside each span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[List]:
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[name, secs] for name, secs in ranked[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        ranked = sorted(self.gaps_by_span.items(), key=lambda kv: -kv[1])
        return [[name, secs] for name, secs in ranked[:n]]


def _covering(spans: Sequence[Event], t: float) -> str:
    """Innermost harness span that covers instant ``t``."""
    best, best_len = NO_SPAN, float("inf")
    for name, s, e in spans:
        if s <= t < e and e - s < best_len:
            best, best_len = name, e - s
    return best


def reduce_events(device_ops: Dict[int, Sequence[Event]],
                  host_spans: Sequence[Event],
                  window: Optional[Interval] = None,
                  device_async: Optional[Dict[int, Sequence[Event]]] = None
                  ) -> Reduced:
    """``device_ops``: events of each device's operation line, which nest.
    ``device_async``: each device's async line, a start-to-done interval per
    pair. ``host_spans``: the harness's spans on the same clock. ``window``:
    the traced window; where None, the span named ``WINDOW_ANNOTATION``.

    Busy is the union of the operation line. A collective's time is its
    pieces on the operation line plus its intervals on the async line; the
    part during which no other operation runs on that device is exposed."""
    if window is None:
        marks = [(s, e) for n, s, e in host_spans if n == WINDOW_ANNOTATION]
        if not marks:
            raise ValueError("trace has no window annotation")
        window = (min(s for s, _ in marks), max(e for _, e in marks))
    t0, t1 = window
    spans = [ev for ev in clip(host_spans, t0, t1)
             if ev[0] != WINDOW_ANNOTATION]
    active = {d: clip(ops, t0, t1) for d, ops in device_ops.items()}
    active = {d: ops for d, ops in active.items() if ops}
    if not active:
        raise ValueError("no operation ran on a device in the traced window")
    n = len(active)
    op_seconds: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    in_span: Dict[str, float] = {}
    busy_s = coll_s = exposed_s = 0.0
    for dev, ops in active.items():
        pieces = self_times(ops)
        busy = union((s, e) for _, s, e in pieces)
        busy_s += measure(busy)
        transfers = clip((device_async or {}).get(dev, ()), t0, t1)
        coll = union([(s, e) for nm, s, e in pieces if COLLECTIVE.search(nm)]
                     + [(s, e) for nm, s, e in transfers
                        if COLLECTIVE.search(nm)])
        comp = union((s, e) for nm, s, e in pieces
                     if not COLLECTIVE.search(nm))
        coll_s += measure(coll)
        exposed_s += measure(coll) - intersect(coll, comp)
        for name, s, e in pieces:
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / n
        for s, e in complement(busy, t0, t1):
            # a gap belongs to the span the host was in when it began
            name = _covering(spans, s)
            gaps[name] = gaps.get(name, 0.0) + (e - s) / n
        for name in {nm for nm, _, _ in spans}:
            cover = union((s, e) for nm, s, e in spans if nm == name)
            in_span[name] = in_span.get(name, 0.0) + intersect(busy, cover) / n
    return Reduced(window_s=t1 - t0, busy_s=busy_s / n, n_devices=n,
                   op_seconds=op_seconds, collective_s=coll_s / n,
                   exposed_collective_s=exposed_s / n, gaps_by_span=gaps,
                   busy_in_span=in_span)


# ------------------------------------------------------------------ capture
def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


@dataclasses.dataclass
class Loaded:
    """Events in seconds on the profile's clock, operations under their short
    names."""

    device_ops: Dict[int, List[Event]]
    device_async: Dict[int, List[Event]]
    host: List[Event]


def load_xplane(path: str, span_names: Iterable[str],
                span_prefixes: Tuple[str, ...] = ()) -> Loaded:
    """Each TPU's operation and async lines, and the host events that are
    spans: the harness's by name, the program's by the prefixes of its
    names."""
    import jax

    def events(line, rename):
        return [(rename(ev.name), ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events if ev.duration_ns > 0]

    data = jax.profiler.ProfileData.from_file(path)
    wanted = set(span_names) | {WINDOW_ANNOTATION}
    out = Loaded({}, {}, [])
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.device_ops.setdefault(dev, []).extend(
                        events(line, short_name))
                elif line.name == ASYNC_LINE:
                    out.device_async.setdefault(dev, []).extend(
                        events(line, short_name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.host.extend(ev for ev in events(line, str)
                                if ev[0] in wanted
                                or ev[0].startswith(span_prefixes))
    return out


class Capture:
    """Start the profiler at the first step boundary at or after ``start_s``
    into the window and stop it at the first one ``length_s`` later. Used as
    the window's ``on_boundary``."""

    def __init__(self, trace_dir: str, clock, start_s: float,
                 length_s: float):
        self.trace_dir, self.clock = trace_dir, clock
        self.start_s, self.length_s = start_s, length_s
        self.state = "before"
        self._t_started = 0.0
        self._mark = None
        self.traced = None        # (start, stop) of the trace on ``clock``

    def __call__(self, elapsed: float, n_steps: int) -> None:
        import jax

        if self.state == "before" and elapsed >= self.start_s:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self._mark = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
            self._mark.__enter__()
            self.state, self._t_started = "tracing", elapsed
            self.traced = (self.clock(), float("inf"))
        elif (self.state == "tracing"
              and elapsed - self._t_started >= self.length_s):
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "tracing":
            self.traced = (self.traced[0], self.clock())
            self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"
