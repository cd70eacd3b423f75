"""Device self time of one phase of the train step, per step, milliseconds:
each operation of ``jit_train_batch`` in the traced window goes to ``forward``,
``recompute``, ``backward`` or ``optimizer`` by the ``op_name`` its instruction
carries in the compiled program, or that the operation around it carries
(``lib/program_trace.phase_seconds``). The four and what could not be
attributed add up to the program's busy time; a ``[bench]`` line says what
was left and names the largest such operations. How an ``op_name`` reads as a
phase is ``deepspeed_tpu/profiling/trace.phase_of`` over the scope names the
model and the train step compile in: that function and those names are part
of this metric's yardstick though they live outside ``benchmark/``."""

from ..lib import program_trace
from ..lib.device import say

PROGRAM = "train_batch"


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None or pt.reduced is None:
        return None
    steps = len(pt.named("train.step"))
    phases = program_trace.phase_seconds(pt, PROGRAM) if steps else None
    if phases is None:
        return None
    by_phase, by_scope, unnamed, inherited = phases
    if params["phase"] == "forward":        # said once, with the first phase
        busy = sum(by_phase.values())
        top = sorted(unnamed.items(), key=lambda kv: -kv[1])[:5]
        say(f"train step on the device, ms a step: "
            + ", ".join(f"{k} {1000 * v / steps:.2f}"
                        for k, v in by_phase.items())
            + f"; not attributed {100 * by_phase['other'] / busy:.2f}% of "
            f"{1000 * busy / steps:.2f} ({len(unnamed)} operations carry no "
            "op_name and lie inside none that does: "
            f"{[(n, round(1000 * s / steps, 3)) for n, s in top]}); "
            f"{1000 * inherited / steps:.2f} took the name of the operation "
            "around them")
        say("by phase and scope, ms a step: " + ", ".join(
            f"{p}/{s} {1000 * v / steps:.2f}" for (p, s), v in
            sorted(by_scope.items(), key=lambda kv: -kv[1])[:12]))
    return 1000.0 * by_phase[params["phase"]] / steps
