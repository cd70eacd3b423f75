"""The share, percent, of the device time of the programs matching
``pattern`` (``^jit_decode_block_(\\d+)$``) that lies under any of the scopes
``scopes`` (``ssm`` and ``attn``: the two mixers of a layer that runs both)
or in a Mosaic call whose name matches one of ``kernels`` (``^ssm_decode``,
``^paged_decode_gqa``: a kernel's call may carry no ``op_name`` on the
device's line, and is then counted by its name, once). Of the executions
that lie whole inside the traced window, as ``prog_scope_ms`` reads them:
self times, an operation the compiler put in taking the scope of the nearest
operation around it. The scope names are the program's
(``deepspeed_tpu/profiling/trace.MODEL_SCOPES``): part of this metric's
yardstick though they live outside ``benchmark/``. Where the program
compiles no such scope in and runs no such kernel nothing is read."""

import bisect
import re

from ..lib import program_trace
from ..lib import trace as T
from ..lib.device import say


def _shares(pt, name, wanted, kernels):
    """(seconds under ``wanted`` or in ``kernels``, seconds of all
    operations) of the whole executions of program ``name`` inside the
    window, summed over the devices that ran it; None where its scopes
    cannot be had."""
    scopes = program_trace.scopes_of(pt, name[len("jit_"):])
    if scopes is None:
        return None
    parent = pt.enclosing.get(name, {})
    t0, t1 = pt.window
    under = total = 0.0
    for dev, mods in pt.modules.items():
        whole = [(s, e) for n, s, e in mods
                 if n == name and s >= t0 and e <= t1]
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
                if (at is not None and wanted & set(scopes[at].split("/"))
                        or any(k.search(instr) for k in kernels)):
                    under += b - a
    return under, total


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or not pt.modules or pt.window is None:
        return None
    pattern = re.compile(params["pattern"])
    kernels = [re.compile(k) for k in params.get("kernels", ())]
    under = total = 0.0
    for name in sorted({n for mods in pt.modules.values()
                        for n, _, _ in mods}):
        if not pattern.search(name):
            continue
        found = _shares(pt, name, set(params["scopes"]), kernels)
        if found is None:
            continue
        under += found[0]
        total += found[1]
    if not under or not total:
        return None
    say(f"{'+'.join(params['scopes'])}: {1000 * under:.3f} ms of "
        f"{1000 * total:.3f} ms of the decode programs' operations")
    return 100.0 * under / total
