"""Device self time of the operations of the programs matching ``pattern``
whose ``op_name`` lies under the scope ``scope``, milliseconds per unit of
work, beside ``prog_scope_ms`` (which divides by the passes of a looped
stack, and reads nothing for a model whose cache layers are fewer than its
layers). The time is of the executions that lie whole inside the traced
window (``prog_scope_ms._scoped_seconds``). The unit:

- ``steps_group``: a decode step; an execution counts as the steps its name
  says (group ``steps_group`` of ``pattern``: ``^jit_decode_block_(\\d+)$``);
- ``spans`` and ``stat`` (and ``per``, default 1): ``per`` of the stat
  ``stat`` summed over the program's spans named in ``spans`` inside the
  traced window (``real_tokens`` of the ``engine.prefill.*`` spans with
  ``per`` 1000: a thousand real prompt tokens). The spans are the host's and
  the executions the device's: a dispatch that straddles an edge of the window
  is in one sum and not the other, a few of hundreds.

The scope names are the program's (``deepspeed_tpu/profiling/trace.
MODEL_SCOPES``): part of this metric's yardstick though they live outside
``benchmark/``. Where the program compiles no such scope in (the parent of
the PR that added it) nothing is read."""

import re

from ..lib import program_trace
from ..lib.device import say
from .prog_scope_ms import _scoped_seconds


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
            continue
        say(f"{name}: {1000 * found[0] / found[2]:.3f} ms an execution under "
            f"{params['scope']} of {1000 * found[1] / found[2]:.3f} ms of "
            f"operations, {found[2]:g} whole executions")
        secs += found[0]
        steps += found[2] * (int(m.group(group)) if group else 1)
    if group:
        units = steps
    else:
        units = sum(float(s.stats.get(params["stat"], 0))
                    for name in params["spans"] for s in pt.named(name)
                    ) / float(params.get("per", 1))
    if not secs or not units:
        return None
    return 1000.0 * secs / units
