"""Fleet bench driver: the open-loop workload clock over a ReplicaRouter.

Mirror of :func:`~..serving.bench.run_continuous`, sharing its report
schema (``_report``) so a fleet run and a single-replica run score against
the same SLO with identical accounting.

``on_step(router, produced_total)`` is the chaos hook: a replica-kill
run uses it to SIGKILL/kill one replica mid-stream at a deterministic
point in the token trajectory.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Sequence

from ..serving.bench import _report
from ..serving.scheduler import Request
from .autoscale import FleetAutoscaler
from .router import ReplicaRouter


def run_fleet(router: ReplicaRouter, workload: Sequence[Request],
              max_wall_s: float = 600.0, slo_s: Optional[float] = None,
              on_step: Optional[Callable[[ReplicaRouter, int], None]] = None,
              autoscaler: Optional[FleetAutoscaler] = None) -> Dict:
    """Drive the router under the workload's arrival clock; fleet-level
    rejections are terminal (scored as shed). Returns the shared report
    schema plus fleet extras (replica counts, re-routes, survivor audit)."""
    pending = sorted(workload, key=lambda r: r.arrival_time)
    t0 = time.monotonic()
    i = 0
    produced_total = 0
    try:
        while i < len(pending) or not router.idle:
            now = time.monotonic() - t0
            if now > max_wall_s:
                break
            while i < len(pending) and pending[i].arrival_time <= now:
                router.submit(pending[i])
                i += 1
            if router.idle:
                if i < len(pending):
                    time.sleep(min(max(pending[i].arrival_time - now, 0.0),
                                   0.25))
                continue
            produced_total += router.step()
            if on_step is not None:
                on_step(router, produced_total)
            if autoscaler is not None:
                autoscaler.tick()
    finally:
        audit = router.audit_survivors()
    t_end = time.monotonic()
    return _report(workload, t0, t_end, "fleet", slo_s=slo_s, extra={
        "replicas_live": len(router.live_replicas),
        "replicas_dead": len(router.dead),
        "replicas_retired": len(router.retired),
        "reroutes": router.counters.get("request_rerouted", 0),
        "fleet_rejects": router.counters.get("fleet_reject", 0),
        "fleet_counters": dict(router.counters),
        "fleet_audit_ok": bool(audit["ok"]),
    })


__all__ = ["run_fleet"]
