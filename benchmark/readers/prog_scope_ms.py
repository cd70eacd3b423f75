"""Device self time of the operations of the programs matching ``pattern``
(``^jit_decode_block_(\\d+)$``) whose ``op_name`` lies under the scope
``scope`` (``ut_loop``: one pass of a stack that runs several times), per
decode step and pass, milliseconds. Steps: the executions that lie whole
inside the traced window, each counted as the steps its name says
(``steps_group``), and only their operations are read, so the time and the
count are of the same executions. Passes a step: the reference's
``cache_layers`` over the model's layers. An operation the compiler put in
carries no ``op_name`` and takes that of the nearest operation around it on
the device's line, as ``lib/program_trace.phase_seconds`` has it. The scope
names are the program's (``deepspeed_tpu/profiling/trace.MODEL_SCOPES``):
part of this metric's yardstick though they live outside ``benchmark/``.
Where the program compiles no such scope in (the parent of the PR that added
it, a model that runs its stack once) nothing is read."""

import bisect
import re

from ..lib import program_trace
from ..lib import trace as T
from ..lib.device import say


def _scoped_seconds(pt, name, scope):
    """(seconds under ``scope``, seconds of all operations, executions) of
    the whole executions of program ``name`` inside the window, means over
    the devices that ran it; None where its scopes cannot be had."""
    scopes = program_trace.scopes_of(pt, name[len("jit_"):])
    if scopes is None:
        return None
    parent = pt.enclosing.get(name, {})
    t0, t1 = pt.window
    under = total = runs = 0.0
    devices = 0
    for dev, mods in pt.modules.items():
        whole = [(s, e) for n, s, e in mods
                 if n == name and s >= t0 and e <= t1]
        if not whole:
            continue
        devices += 1
        runs += len(whole)
        ops = sorted(pt.instr.get(dev, ()), key=lambda ev: ev[1])
        starts = [ev[1] for ev in ops]
        for s, e in whole:
            inside = [ev for ev in ops[bisect.bisect_left(starts, s):
                                       bisect.bisect_right(starts, e)]
                      if ev[2] <= e]
            for instr, a, b in T.self_times(inside):
                at = instr
                while at is not None and at not in scopes:
                    at = parent.get(at)
                total += b - a
                if at is not None and scope in scopes[at].split("/"):
                    under += b - a
    if not devices:
        return None
    return under / devices, total / devices, runs / devices


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or not pt.modules or pt.window is None:
        return None
    pattern = re.compile(params["pattern"])
    group = params.get("steps_group")
    secs = steps = 0.0
    for name in sorted({n for mods in pt.modules.values()
                        for n, _, _ in mods}):
        m = pattern.search(name)
        if not m:
            continue
        found = _scoped_seconds(pt, name, params["scope"])
        if found is None:
            say(f"{name}: its scopes cannot be had, or no execution lies "
                f"whole inside the traced window; nothing read under "
                f"{params['scope']}")
            continue
        say(f"{name}: {1000 * found[0] / found[2]:.3f} ms an execution under "
            f"{params['scope']} of {1000 * found[1] / found[2]:.3f} ms of "
            f"operations, {found[2]:g} whole executions")
        secs += found[0]
        steps += found[2] * (int(m.group(group)) if group else 1)
    model = ctx.model
    passes = ctx.count("cache_layers")(model) // model["n_layer"]
    if not secs or not steps or not passes:
        return None
    return 1000.0 * secs / (steps * passes)
