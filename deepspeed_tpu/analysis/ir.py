"""Program capture: jitted fn -> (jaxpr, StableHLO, optimized HLO) without executing.

Two IR levels, because the two bug classes live at different stages:

- The **closed jaxpr** (trace level) carries primitive identity — ``cond``
  branches, ``shard_map`` bodies, explicit collectives, callbacks, dtypes.
  Rules that reason about program *structure* (collective order, precision
  propagation, host callbacks) walk this.
- The **optimized HLO** (post-compile, after GSPMD partitioning) carries the
  collectives XLA actually inserted — the all-gathers a sharding constraint
  implies, their wire dtypes and byte counts. Rules that reason about what
  *moves on the wire* parse this. Compiling is optional (``compile=True``):
  it costs real time for big programs but nothing executes.

Donation is read from the StableHLO module: donated flat args carry a
``tf.aliasing_output`` attribute on ``@main``. That is the ground truth the
runtime honors — a ``donate_argnums`` the user *meant* to pass but didn't
simply won't be there.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend import source_info_util as _siu
from jax.extend.core import ClosedJaxpr, Jaxpr

# Explicit collective primitives (trace-level; what shard_map bodies call).
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "psum_scatter", "reduce_scatter", "pgather",
})

# Host-callback primitives: each forces a device->host round trip per step.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "callback", "outside_call",
})
DEBUG_CALLBACK_PRIMS = frozenset({"debug_callback"})

# XLA HLO instruction names for collectives (post-GSPMD).
HLO_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)

_HLO_ITEMSIZE = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_HLO_TYPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                          r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")
# the result type is either a tuple "(f32[..]{..}, ...)" (allow one level of
# nested parens: TPU tiled layouts render as "{1,0:T(8,128)(2,1)}") or a
# single space-free token — layout/memory-space annotations (":T(...)",
# ":S(5)") never contain spaces, so \S+ covers them on every backend
_HLO_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\((?:[^()]|\([^)]*\))*\)|\S+)\s+"
    r"(" + "|".join(HLO_COLLECTIVES) + r")(?:-start)?\(", re.MULTILINE)


@dataclasses.dataclass
class HloCollective:
    op: str           # e.g. "all-gather"
    dtypes: Tuple[str, ...]
    bytes: int        # result bytes summed over tuple elements
    line: str


@dataclasses.dataclass
class ProgramIR:
    """One captured program, both IR levels + input metadata."""

    name: str
    closed_jaxpr: ClosedJaxpr
    in_avals: List[Any]
    out_avals: List[Any]
    donated: List[bool]
    stablehlo: Optional[str] = None
    hlo: Optional[str] = None
    compiled: Any = None
    wire_records: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def jaxpr(self) -> Jaxpr:
        return self.closed_jaxpr.jaxpr

    def hlo_collectives(self) -> List[HloCollective]:
        """Collective instructions in the optimized (post-GSPMD) HLO."""
        if not self.hlo:
            return []
        out: List[HloCollective] = []
        for m in _HLO_COLLECTIVE_RE.finditer(self.hlo):
            type_str, op = m.group(1), m.group(2)
            dtypes, nbytes = [], 0
            for tm in _HLO_TYPE_RE.finditer(type_str):
                dt, dims = tm.group(1), tm.group(2)
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                dtypes.append(dt)
                nbytes += n * _HLO_ITEMSIZE.get(dt, 4)
            line = m.group(0).strip().rstrip("(")
            out.append(HloCollective(op=op, dtypes=tuple(dtypes),
                                     bytes=nbytes, line=line))
        return out


def aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    return int(np.prod(shape) if shape else 1) * np.dtype(dtype).itemsize


def source_line(eqn) -> str:
    """Best-effort ``file:line`` for an eqn (whatever the trace recorded)."""
    try:
        return _siu.summarize(eqn.source_info)
    except Exception:
        return ""


def sub_jaxprs(eqn) -> List[Tuple[str, Jaxpr]]:
    """Sub-jaxprs carried in an eqn's params (branches, bodies, calls),
    discovered structurally so new primitives keep working."""
    out: List[Tuple[str, Jaxpr]] = []
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for i, v in enumerate(vals):
            tag = f"{key}[{i}]" if isinstance(val, (tuple, list)) else key
            if isinstance(v, ClosedJaxpr):
                out.append((tag, v.jaxpr))
            elif isinstance(v, Jaxpr):
                out.append((tag, v))
    return out


def iter_eqns(jaxpr: Jaxpr, path: str = "") -> Iterator[Tuple[Any, str]]:
    """Yield ``(eqn, path)`` over a jaxpr and every nested sub-jaxpr."""
    for i, eqn in enumerate(jaxpr.eqns):
        here = f"{path}/{eqn.primitive.name}[{i}]"
        yield eqn, here
        for tag, sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub, f"{here}.{tag}")


def collective_signature(jaxpr: Jaxpr) -> List[Tuple[str, Tuple[str, ...]]]:
    """Ordered ``(primitive, axis_names)`` sequence of explicit collectives —
    the thing that must match across branches for SPMD ranks not to deadlock."""
    sig: List[Tuple[str, Tuple[str, ...]]] = []
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
            if isinstance(axes, (str, int)):
                axes = (axes,)
            sig.append((eqn.primitive.name, tuple(str(a) for a in axes)))
    return sig


def _donated_from_stablehlo(text: str, n_args: int) -> List[bool]:
    """Per-flat-arg donation flags from ``tf.aliasing_output`` markers on
    ``@main``. Falls back to all-False on signature mismatch (pruned args)."""
    m = re.search(r"func\.func\s+(?:public\s+)?@main\((.*?)\)\s*->",
                  text, re.DOTALL)
    if not m:
        return [False] * n_args
    # chunk by "%argN:" — attr dicts contain braces inside strings
    # (mhlo.sharding = "{devices=...}"), so brace-matching regexes truncate
    parts = re.split(r"%arg(\d+):", m.group(1))
    flags: Dict[int, bool] = {}
    for j in range(1, len(parts) - 1, 2):
        flags[int(parts[j])] = "tf.aliasing_output" in parts[j + 1]
    if not flags:
        return [False] * n_args
    return [flags.get(i, False) for i in range(n_args)]


def capture(fn: Callable, *args, name: str = "program",
            compile: bool = False, donate_argnums: Sequence[int] = (),
            static_argnums: Sequence[int] = (), **kwargs) -> ProgramIR:
    """Capture ``fn`` (plain or already-jitted) on abstract args.

    ``args`` may be real arrays or ``jax.ShapeDtypeStruct`` trees — nothing is
    executed either way. For a plain function, ``donate_argnums`` is forwarded
    to the wrapping ``jit`` so the donation rule sees what the runtime would.
    """
    jitted = fn
    if not hasattr(fn, "lower"):
        jitted = jax.jit(fn, donate_argnums=tuple(donate_argnums),
                         static_argnums=tuple(static_argnums))

    from ..comm.runtime_accounting import wire_ledger

    before = wire_ledger.snapshot()
    traced = jitted.trace(*args, **kwargs)  # shares the trace with lower()
    closed = traced.jaxpr
    lowered = traced.lower()
    # quantized collectives record into the wire ledger at trace time; the
    # delta tells the config rules what this trace put on the int wire
    wire_records = wire_ledger.delta(before)

    # make_jaxpr over an already-jitted fn yields one outer pjit eqn; unwrap it
    # so rules see the real body (and get donated_invars for free).
    donated: Optional[List[bool]] = None
    body = closed
    if (len(closed.jaxpr.eqns) == 1
            and closed.jaxpr.eqns[0].primitive.name == "pjit"
            and "jaxpr" in closed.jaxpr.eqns[0].params):
        eqn = closed.jaxpr.eqns[0]
        if list(eqn.invars) == list(closed.jaxpr.invars):
            body = eqn.params["jaxpr"]
            di = eqn.params.get("donated_invars")
            if di is not None:
                donated = list(di)

    stablehlo = lowered.as_text()
    if donated is None:
        donated = _donated_from_stablehlo(stablehlo,
                                          len(body.jaxpr.invars))

    hlo = None
    compiled = None
    if compile:
        compiled = lowered.compile()
        hlo = compiled.as_text()

    return ProgramIR(
        name=name,
        closed_jaxpr=body,
        in_avals=[v.aval for v in body.jaxpr.invars],
        out_avals=[v.aval for v in body.jaxpr.outvars],
        donated=donated,
        stablehlo=stablehlo,
        hlo=hlo,
        compiled=compiled,
        wire_records=wire_records,
    )
