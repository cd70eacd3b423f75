"""Host-sync rules: callbacks and missed donations inside the step program.

A training step should be ONE async device dispatch. A host callback traced
into it forces a device→host→device round trip every step; a donatable input
that isn't donated doubles its HBM footprint for the program's whole lifetime
(the runtime must keep the un-donated original alive next to the new output).
The engine's own programs donate their state at the jit boundary
(``runtime/engine.py`` ``donate_argnums=(0,)`` on the fused step and
``(0, 1)`` on the micro/boundary jits; same discipline in ``runtime/aot.py``)
— these rules hold user programs to that bar.
"""

from __future__ import annotations

from collections import Counter
from typing import Iterable, List, Tuple

import numpy as np

from .core import AnalysisContext, Finding, Rule, Severity
from .ir import (
    CALLBACK_PRIMS,
    DEBUG_CALLBACK_PRIMS,
    ProgramIR,
    aval_bytes,
    iter_eqns,
    source_line,
)


class CallbackInStepRule(Rule):
    """Host callbacks traced into the step program."""

    rule_id = "host-sync/callback-in-step"
    default_severity = Severity.ERROR
    description = "host callbacks force a device<->host sync every step"

    def check_program(self, prog: ProgramIR,
                      ctx: AnalysisContext) -> Iterable[Finding]:
        for eqn, path in iter_eqns(prog.jaxpr):
            name = eqn.primitive.name
            if name in CALLBACK_PRIMS:
                src = source_line(eqn)
                yield self.finding(
                    f"{name} inside the step program — every step round-trips "
                    f"through the host (and blocks XLA's async dispatch)",
                    location=(f"{prog.name}:{path}"
                              + (f" ({src})" if src else "")),
                    suggestion="move host work outside the jitted step, or "
                               "accumulate on-device and fetch at a coarser "
                               "cadence",
                )
            elif name in DEBUG_CALLBACK_PRIMS:
                src = source_line(eqn)
                yield self.finding(
                    f"{name} (jax.debug.print/callback) inside the step "
                    f"program — fine while debugging, a per-step host sync "
                    f"in production",
                    location=(f"{prog.name}:{path}"
                              + (f" ({src})" if src else "")),
                    severity=Severity.WARNING,
                    suggestion="strip debug prints from the jitted step "
                               "before long runs",
                )


def _key(aval) -> Tuple:
    return (tuple(getattr(aval, "shape", ())),
            str(getattr(aval, "dtype", "?")))


class DonationMissRule(Rule):
    """Inputs that could alias an output buffer but were not donated.

    Grounded in the engine's own donation sites: the fused train step donates
    its state (``engine.py`` ``_train_batch_jit``/``_train_batches_jits``), the
    imperative micro/boundary jits donate state+grads, and the AOT report path
    donates params/master/opt (``aot.py``). A user ``pjit`` step that returns
    updated state without donating the old one holds both copies in HBM.
    """

    rule_id = "host-sync/donation-miss"
    default_severity = Severity.WARNING
    description = "donatable input buffers that are not donated"

    def check_program(self, prog: ProgramIR,
                      ctx: AnalysisContext) -> Iterable[Finding]:
        if len(prog.donated) != len(prog.in_avals):
            return  # signature mismatch (pruned args) — nothing trustworthy
        # outputs not already claimed by a donated input, by (shape, dtype)
        free_outs = Counter(_key(a) for a in prog.out_avals)
        for aval, don in zip(prog.in_avals, prog.donated):
            if don and free_outs.get(_key(aval), 0) > 0:
                free_outs[_key(aval)] -= 1
        for i, (aval, don) in enumerate(zip(prog.in_avals, prog.donated)):
            if don:
                continue
            nbytes = aval_bytes(aval)
            if nbytes < ctx.options.donation_bytes:
                continue
            k = _key(aval)
            if free_outs.get(k, 0) > 0:
                free_outs[k] -= 1
                yield self.finding(
                    f"input #{i} ({nbytes / 2**20:.1f} MB "
                    f"{np.dtype(aval.dtype).name}{list(aval.shape)}) matches "
                    f"an output buffer but is not donated — peak HBM carries "
                    f"both copies",
                    location=f"{prog.name}:arg{i}",
                    suggestion="pass donate_argnums for state-like inputs "
                               "that the program returns updated",
                )


def hostsync_rules() -> List[Rule]:
    return [CallbackInStepRule(), DonationMissRule()]


__all__ = ["CallbackInStepRule", "DonationMissRule", "hostsync_rules"]
