"""Request-level serving benchmark: open-loop arrivals, TTFT + tokens/s.

Open loop (arrivals follow a Poisson clock regardless of completions) is the
honest serving load: a closed loop would slow the arrival rate down whenever
the server stalls, hiding exactly the tail it is supposed to expose. The
workload is synthetic but seeded, so A/B runs replay identical requests.

Runner: :func:`run_continuous` drives the paged continuous-batching stack
(``ServingEngine`` + ``ContinuousBatchingScheduler``) under the workload's
arrival clock. Useful tokens are each request's own ``max_new_tokens``.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .scheduler import ContinuousBatchingScheduler, Request, RequestState


def percentile(xs: Sequence[float], p: float) -> float:
    if not xs:
        return float("nan")
    xs = sorted(xs)
    idx = min(len(xs) - 1, int(round(p / 100.0 * (len(xs) - 1))))
    return float(xs[idx])


def make_open_loop_workload(n_requests: int, rate_rps: float,
                            prompt_len: tuple, max_new: tuple,
                            vocab_size: int, seed: int = 0,
                            eos_token_id: Optional[int] = None
                            ) -> List[Request]:
    """Poisson arrivals at ``rate_rps``; prompt/generation lengths uniform in
    the given inclusive ranges. Mixed lengths on purpose — the paged cache's
    whole value proposition is not paying max_len per request."""
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for _ in range(n_requests):
        t += float(rng.exponential(1.0 / rate_rps))
        pl = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        mn = int(rng.integers(max_new[0], max_new[1] + 1))
        out.append(Request(
            prompt=rng.integers(0, vocab_size, (pl,)).astype(np.int32),
            max_new_tokens=mn, eos_token_id=eos_token_id, arrival_time=t))
    return out


def _group_row(reqs: Sequence[Request], t0: float, t_end: float,
               slo_s: Optional[float]) -> Dict:
    """Per-tenant/per-tier sub-report: REJECTED requests count against THIS
    group's shed rate (not the fleet aggregate), and a group's misses stay
    its own — a flood victim's misses no longer dilute the flooder's
    stats."""
    ttft: List[float] = []
    goodput = 0
    late = 0
    shed = sum(1 for r in reqs if r.state is RequestState.REJECTED)
    expired = sum(1 for r in reqs if r.state is RequestState.EXPIRED)
    for r in reqs:
        arrive = t0 + r.arrival_time
        if r.t_first_token is not None:
            ttft.append(r.t_first_token - arrive)
        n = min(len(r.tokens), r.max_new_tokens)
        if r.t_done is not None:
            if slo_s is None or r.t_done - arrive <= slo_s:
                goodput += n
            else:
                late += 1
        elif (slo_s is not None
              and r.state not in (RequestState.REJECTED,
                                  RequestState.EXPIRED)
              and t_end - arrive > slo_s):
            late += 1

    def ms(x, nd=2):
        return None if x != x else round(x * 1e3, nd)

    accepted = len(reqs) - shed
    misses = expired + late
    return {
        "requests": len(reqs),
        "finished": sum(r.t_done is not None for r in reqs),
        "shed": shed,
        "shed_rate": round(shed / max(len(reqs), 1), 4),
        "deadline_misses": misses,
        "deadline_miss_rate": round(misses / max(accepted, 1), 4),
        "goodput_tokens": int(goodput),
        "preemptions": sum(r.preemptions for r in reqs),
        "ttft_p50_ms": ms(percentile(ttft, 50)),
        "ttft_p99_ms": ms(percentile(ttft, 99)),
    }


def _report(requests: Sequence[Request], t0: float, t_end: float,
            mode: str, extra: Optional[Dict] = None,
            slo_s: Optional[float] = None) -> Dict:
    """Shared report schema. ``slo_s`` is an EVALUATION deadline (arrival ->
    completion) applied identically to every run — it lets an uncontrolled
    baseline (which enforces nothing) be scored against the same SLO a
    controlled run enforces, so goodput/deadline-miss numbers are an honest
    A/B. TTFT percentiles cover accepted requests only (a shed request has
    no first token by construction — mixing it in as +inf would charge
    admission control for the latency it avoided)."""
    ttft, per_tok, total_tokens = [], [], 0
    goodput_tokens = 0
    late = 0
    for r in requests:
        arrive = t0 + r.arrival_time
        if r.t_first_token is not None:
            ttft.append(r.t_first_token - arrive)
        n = min(len(r.tokens), r.max_new_tokens)
        total_tokens += n
        if r.t_done is not None:
            if slo_s is None or r.t_done - arrive <= slo_s:
                goodput_tokens += n
            else:
                late += 1
        # every token delivered at once (t_done == t_first): per-token
        # cadence is undefined there, not 0
        if (r.t_done is not None and n > 1
                and r.t_done > r.t_first_token):
            per_tok.append((r.t_done - r.t_first_token) / (n - 1))

    def ms(x, nd=2):
        return None if x != x else round(x * 1e3, nd)  # NaN -> JSON null

    shed = [r for r in requests if r.state is RequestState.REJECTED]
    expired = [r for r in requests if r.state is RequestState.EXPIRED]
    accepted = len(requests) - len(shed)
    # accepted requests still unfinished at run end are the WORST outcomes
    # of an overloaded run — when an SLO is being scored and theirs already
    # lapsed, they count as misses, not as silent omissions (an uncontrolled
    # baseline hitting the wall cap would otherwise look artificially good)
    unfinished = [r for r in requests
                  if r.state not in (RequestState.REJECTED,
                                     RequestState.EXPIRED)
                  and r.t_done is None]
    if slo_s is not None:
        late += sum(1 for r in unfinished
                    if t_end - (t0 + r.arrival_time) > slo_s)
    misses = len(expired) + late
    wall = max(t_end - t0, 1e-9)
    row = {
        "mode": mode,
        "requests": len(requests),
        "finished": sum(r.t_done is not None for r in requests),
        "total_tokens": int(total_tokens),
        "wall_s": round(wall, 3),
        "tokens_per_sec": round(total_tokens / wall, 2),
        "ttft_p50_ms": ms(percentile(ttft, 50)),
        "ttft_p99_ms": ms(percentile(ttft, 99)),
        "per_token_p50_ms": ms(percentile(per_tok, 50), 3),
        "per_token_p99_ms": ms(percentile(per_tok, 99), 3),
        # overload/SLO accounting (docs/SERVING.md "Overload & failure")
        "shed": len(shed),
        "shed_rate": round(len(shed) / max(len(requests), 1), 4),
        "unfinished": len(unfinished),
        "deadline_misses": misses,
        "deadline_miss_rate": round(misses / max(accepted, 1), 4),
        "goodput_tokens_per_sec": round(goodput_tokens / wall, 2),
    }
    if slo_s is not None:
        row["slo_s"] = slo_s
    tagged = [r for r in requests
              if getattr(r, "tenant_id", None) is not None
              or getattr(r, "tier", None) is not None]
    if tagged:
        by_tier: Dict[str, List[Request]] = {}
        by_tenant: Dict[str, List[Request]] = {}
        for r in tagged:
            if r.tier is not None:
                by_tier.setdefault(str(r.tier), []).append(r)
            if r.tenant_id is not None:
                by_tenant.setdefault(str(r.tenant_id), []).append(r)
        row["by_tier"] = {k: _group_row(v, t0, t_end, slo_s)
                          for k, v in sorted(by_tier.items())}
        row["by_tenant"] = {k: _group_row(v, t0, t_end, slo_s)
                            for k, v in sorted(by_tenant.items())}
    if extra:
        row.update(extra)
    return row


def run_continuous(engine, workload: Sequence[Request],
                   max_wall_s: float = 600.0, slo_s: Optional[float] = None,
                   scheduler: Optional[ContinuousBatchingScheduler] = None
                   ) -> Dict:
    """Drive the scheduler under the workload's arrival clock. Rejected
    submissions (typed :class:`AdmissionVerdict`) are terminal — the driver
    does not retry them; they score as shed in the report. Pass
    ``scheduler`` to drive a hand-built one (the overload A/B constructs a
    capped and an uncapped scheduler over the same engine)."""
    sched = scheduler if scheduler is not None else engine.make_scheduler()
    pending = sorted(workload, key=lambda r: r.arrival_time)
    t0 = time.monotonic()
    i = 0
    try:
        while i < len(pending) or not sched.idle:
            now = time.monotonic() - t0
            if now > max_wall_s:
                break
            while i < len(pending) and pending[i].arrival_time <= now:
                sched.submit(pending[i])
                i += 1
            if sched.idle:
                if i < len(pending):  # nothing in flight: sleep to arrival
                    time.sleep(min(max(pending[i].arrival_time - now, 0.0),
                                   0.25))
                continue
            sched.step()
    finally:
        sched.close()
    t_end = time.monotonic()
    stats = dict(sched.page_stats)
    extra = {
        "decode_steps": sched.steps,
        "preemptions": sum(r.preemptions for r in workload),
        "num_slots": sched.num_slots,
        "hbm_token_slots": engine.hbm_token_slots(),
        "compiled_programs": len(engine.compile_log),
        "recovery_counters": dict(sched.counters),
        "pool_audit_ok": bool(sched.audit()["ok"]),
        # copy-on-write prefix reuse: physical/logical < 1 means shared
        # prompt prefixes actually collapsed into the same physical pages
        "page_stats": stats,
        "physical_logical_page_ratio": round(
            stats["physical"] / stats["logical"], 4)
        if stats["logical"] else None,
    }
    if sched.drafter is not None:
        # the speculation ledger: accept rate + the multi-token multiplier
        # (docs/SERVING.md "Speculative decoding" — how to read the A/B row)
        ss = dict(sched.spec_stats)
        ss["accept_rate"] = round(
            ss["accepted"] / max(ss["drafted"], 1), 4)
        # the multi-token multiplier: tokens a verify dispatch produced,
        # averaged over windows (1.0 == no better than plain decode)
        ss["tokens_per_dispatch"] = round(
            ss["committed_tokens"] / max(ss["windows"], 1), 3)
        extra["spec"] = ss
    return _report(workload, t0, t_end, "continuous", slo_s=slo_s,
                   extra=extra)
