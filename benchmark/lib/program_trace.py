"""The program's own names in a run's trace.

``deepspeed_tpu/profiling/trace.py`` compiles names into the programs (Pallas
kernels, jitted programs, scopes) and writes host spans into the profiler's
trace (``serve.*``, ``engine.*``, ``train.*``) with their counts as stats.
This module reads them back from the ``.xplane.pb`` of a traced run, in one
pass: the program's spans with their stats, each device's "XLA Modules" line
by program name, and the operation line both under the harness's short names
(``lib/trace.short_name``) and by instruction name and owning program, which
is what ``program_scopes`` of the program is keyed by. Busy time, idle gaps
and self time are ``lib/trace``'s own reduction, run with the program's spans
as the host spans, so that a gap falls to the innermost program span.

Where the program has no such span or name (the parent of the PR that added
them), everything here is empty and the readers return None.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import json
import os
import re
import time
from typing import Dict, List, Optional, Tuple

from . import manifest
from . import trace as T
from .device import say

SPAN_PREFIXES = ("serve.", "engine.", "train.")
MODULES_LINE = "XLA Modules"
# where run.py's traced run leaves its trace (run.TRACE_DIR)
TRACE_DIR = os.path.join(manifest.ROOT, ".work", "trace")
SCOPES_DIR = "program_scopes"          # beside the trace, one file a module
_MODULE = re.compile(r"^(.*?)\((\d+)\)$")   # jit_decode_block_2(1528053957...)


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    stats: Dict[str, object]

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class ProgramTrace:
    window: Optional[T.Interval]            # the harness's traced window
    spans: List[Span]                       # the program's, whole inside it
    modules: Dict[int, List[T.Event]]       # device: (program, start, end)
    ops: Dict[int, List[T.Event]]           # device: (short name, start, end)
    instr: Dict[int, List[T.Event]]         # device: (instruction, start, end)
    device_async: Dict[int, List[T.Event]]
    trace_dir: Optional[str] = None
    # program -> the ids the "XLA Modules" line prints after its name: the
    # fingerprint of the module that ran, the same on every chip
    module_ids: Dict[str, List[int]] = dataclasses.field(default_factory=dict)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def prefixed(self, prefix: str) -> List[Span]:
        return [s for s in self.spans if s.name.startswith(prefix)]

    def inside(self, outer: Span, names) -> List[Span]:
        return [s for s in self.spans if s.name in names
                and s.t0 >= outer.t0 and s.t1 <= outer.t1]

    @functools.cached_property
    def reduced(self) -> Optional[T.Reduced]:
        """``lib/trace``'s reduction with the program's spans as host spans;
        None where no operation ran on a device (a CPU rehearsal)."""
        if self.window is None or not any(self.ops.values()):
            return None
        host = [(s.name, s.t0, s.t1) for s in self.spans]
        return T.reduce_events(self.ops, host, window=self.window,
                               device_async=self.device_async)

    @functools.cached_property
    def op_counts(self) -> Dict[str, float]:
        """Executions of each operation inside the window, by short name
        (mean over the devices that ran anything)."""
        t0, t1 = self.window
        active = [ops for ops in self.ops.values() if T.clip(ops, t0, t1)]
        out: Dict[str, float] = {}
        for ops in active:
            for name, _, _ in T.clip(ops, t0, t1):
                out[name] = out.get(name, 0.0) + 1.0 / len(active)
        return out

    @functools.cached_property
    def program_seconds(self) -> Dict[str, Tuple[float, float]]:
        """Program name -> (device seconds, executions) inside the window,
        means over the devices."""
        t0, t1 = self.window
        active = [m for m in self.modules.values() if T.clip(m, t0, t1)]
        out: Dict[str, Tuple[float, float]] = {}
        for mods in active:
            for name, s, e in mods:
                if s >= t0 and e <= t1:       # whole executions only
                    secs, n = out.get(name, (0.0, 0.0))
                    out[name] = (secs + (e - s) / len(active),
                                 n + 1.0 / len(active))
        return out

    @functools.cached_property
    def instr_seconds(self) -> Dict[str, Dict[str, float]]:
        """Program name -> {instruction name: self seconds} inside the window
        (mean over the devices): what joins with ``program_scopes``."""
        t0, t1 = self.window
        devs = [d for d, ops in self.instr.items() if T.clip(ops, t0, t1)]
        out: Dict[str, Dict[str, float]] = {}
        for d in devs:
            mods = sorted(self.modules.get(d, ()), key=lambda m: m[1])
            starts = [m[1] for m in mods]
            for name, s, e in T.self_times(T.clip(self.instr[d], t0, t1)):
                prog = _owner(mods, starts, s)
                by = out.setdefault(prog, {})
                by[name] = by.get(name, 0.0) + (e - s) / len(devs)
        return out

    @functools.cached_property
    def enclosing(self) -> Dict[str, Dict[str, Optional[str]]]:
        """Program name -> {instruction: the instruction whose event encloses
        its first event on the operation line, or None}: an operation of a
        loop's body lies inside the loop's ``while``."""
        t0, t1 = self.window
        out: Dict[str, Dict[str, Optional[str]]] = {}
        for d, ops in self.instr.items():
            mods = sorted(self.modules.get(d, ()), key=lambda m: m[1])
            starts = [m[1] for m in mods]
            stack: List[Tuple[str, float]] = []
            for name, s, e in sorted(T.clip(ops, t0, t1),
                                     key=lambda ev: (ev[1], -ev[2])):
                while stack and stack[-1][1] <= s:
                    stack.pop()
                out.setdefault(_owner(mods, starts, s), {}).setdefault(
                    name, stack[-1][0] if stack else None)
                stack.append((name, e))
        return out


def _owner(mods, starts, t: float) -> str:
    """The program whose execution covers instant ``t`` on that device."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][2] > t:
        return mods[i][0]
    return "_no_program_"


def _instruction(hlo: str) -> str:
    name = hlo.partition(" = ")[0]
    return name[1:] if name.startswith("%") else name[:80]


def load(path: str) -> ProgramTrace:
    """One pass over an ``.xplane.pb``."""
    import jax

    def sec(ev):
        return ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9

    data = jax.profiler.ProfileData.from_file(path)
    marks: List[T.Interval] = []
    spans: List[Span] = []
    out = ProgramTrace(None, [], {}, {}, {}, {})
    for plane in data.planes:
        m = T.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                events = [ev for ev in line.events if ev.duration_ns > 0]
                if line.name == T.OPS_LINE:
                    out.ops.setdefault(dev, []).extend(
                        (T.short_name(ev.name), *sec(ev)) for ev in events)
                    out.instr.setdefault(dev, []).extend(
                        (_instruction(ev.name), *sec(ev)) for ev in events)
                elif line.name == T.ASYNC_LINE:
                    out.device_async.setdefault(dev, []).extend(
                        (T.short_name(ev.name), *sec(ev)) for ev in events)
                elif line.name == MODULES_LINE:
                    for ev in events:
                        m = _MODULE.match(ev.name)
                        name = m.group(1) if m else ev.name
                        out.modules.setdefault(dev, []).append(
                            (name, *sec(ev)))
                        ids = out.module_ids.setdefault(name, [])
                        if m and int(m.group(2)) not in ids:
                            ids.append(int(m.group(2)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == T.WINDOW_ANNOTATION:
                        marks.append(sec(ev))
                    elif (ev.name.startswith(SPAN_PREFIXES)
                          and ev.duration_ns > 0):
                        spans.append(Span(ev.name, *sec(ev), dict(ev.stats)))
    if marks:
        out.window = (min(s for s, _ in marks), max(e for _, e in marks))
        t0, t1 = out.window
        spans = [s for s in spans if s.t0 >= t0 and s.t1 <= t1]
    out.spans = sorted(spans, key=lambda s: s.t0)
    return out


def from_plain(data: dict, trace_dir: Optional[str] = None) -> ProgramTrace:
    """A piece cut by ``tools/program_trace_slice.py`` (``tests/data``)."""
    def events(rows):
        return [(n, s, e) for n, s, e in rows]

    ops = {int(d): [(f"{i}_{k}" if joined else k, s, e)
                    for i, k, joined, s, e in rows]
           for d, rows in data["ops"].items()}
    instr = {int(d): [(i, s, e) for i, _, _, s, e in rows]
             for d, rows in data["ops"].items()}
    return ProgramTrace(
        tuple(data["window"]),
        [Span(n, a, b, dict(st)) for n, a, b, st in data["spans"]],
        {int(d): events(rows) for d, rows in data["modules"].items()},
        ops, instr,
        {int(d): events(rows) for d, rows in data["device_async"].items()},
        trace_dir, {k: list(v) for k, v in data["module_ids"].items()})


@functools.lru_cache(maxsize=2)
def _load_dir(trace_dir: str, path: str) -> ProgramTrace:
    loaded = load(path)
    loaded.trace_dir = trace_dir
    return loaded


def load_dir(trace_dir: str) -> ProgramTrace:
    """The newest trace under ``trace_dir``; loaded once per file."""
    return _load_dir(trace_dir, T.find_xplane(trace_dir))


def of(ctx) -> Optional[ProgramTrace]:
    """The trace of this run, or None where it left none. (A CPU rehearsal
    has a trace with no device plane, and ``ctx.traced`` unset: the program's
    spans are read from it all the same.) A trace written before this run's
    window opened is another run's."""
    try:
        path = T.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    opened = time.time() - (time.perf_counter() - ctx.window.t_open)
    if os.path.getmtime(path) < opened:
        return None
    pt = _load_dir(TRACE_DIR, path)
    return pt if pt.window is not None else None


# ------------------------------------------------------ scopes of a program
def scopes_of(pt: ProgramTrace, program: str) -> Optional[Dict[str, str]]:
    """``{instruction name: op_name}`` of the module the trace shows under
    ``jit_<program>``, or None where that cannot be had.

    The trace names the module by a fingerprint (``module_ids``). The file
    kept beside the trace under that fingerprint answers first: the process
    that ran the trace wrote it, and ``tools/program_gaps.py`` reads it later,
    in another process. Else the program's own table answers
    (``profiling/trace.program_scopes``: a compile, or a load from the
    persistent cache, paid here, after the window), and only with a program
    that compiles to that fingerprint. Another engine of the process may hold
    a program of the same name, and names such as ``fusion.12`` are in every
    program: a join with the wrong text would attribute without complaint."""
    ids = pt.module_ids.get(f"jit_{program}", [])
    if len(ids) != 1:
        if ids:
            say(f"{len(ids)} modules ran as jit_{program} ({ids}): their "
                "instructions cannot be told apart, no phases are read")
        return None
    kept = (os.path.join(pt.trace_dir, SCOPES_DIR, f"{program}.{ids[0]}.json")
            if pt.trace_dir else None)
    if kept and os.path.isfile(kept):
        with open(kept) as f:
            return json.load(f)
    try:
        from deepspeed_tpu.profiling import trace as names
        scopes = names.program_scopes(program, module_id=ids[0])
    except (ImportError, KeyError):
        return None         # a program without that table, or not this name
    except LookupError as e:
        say(f"no phases are read: {e}")
        return None
    if kept:
        os.makedirs(os.path.dirname(kept), exist_ok=True)
        with open(kept, "w") as f:
            json.dump(scopes, f)
    return scopes


def phase_seconds(pt: ProgramTrace, program: str
                  ) -> Optional[Tuple[Dict[str, float],
                                      Dict[Tuple[str, Optional[str]], float],
                                      Dict[str, float], float]]:
    """Self seconds of one program's operations inside the window by phase and
    by (phase, scope); the operations left without a name (their seconds are
    in phase ``other`` too); and the seconds that took a name from an
    enclosing operation. An operation the compiler put in (a layout copy, a
    slice) carries no ``op_name``: it takes the one of the nearest operation
    that encloses it on the device's operation line, so a copy inside the
    layer loop belongs to that loop's phase. ``program`` is the jitted
    function's name (``train_batch``); the device calls it
    ``jit_train_batch``. How an ``op_name`` reads as a phase is the program's
    own ``profiling/trace.phase_of``: a copy here would drift."""
    per_instr = pt.instr_seconds.get(f"jit_{program}")
    scopes = scopes_of(pt, program) if per_instr else None
    if scopes is None:
        return None
    from deepspeed_tpu.profiling import trace as names

    parent = pt.enclosing.get(f"jit_{program}", {})
    by_phase = dict.fromkeys(names.PHASES, 0.0)
    by_scope: Dict[Tuple[str, Optional[str]], float] = {}
    unnamed: Dict[str, float] = {}
    inherited = 0.0
    for name, secs in per_instr.items():
        at = name
        while at is not None and at not in scopes:
            at = parent.get(at)
        if at is None:
            unnamed[name] = secs
            key = ("other", None)
        else:
            key = names.phase_of(scopes[at])
            inherited += secs if at != name else 0.0
        by_phase[key[0]] += secs
        by_scope[key] = by_scope.get(key, 0.0) + secs
    return by_phase, by_scope, unnamed, inherited
