"""Serving rules: decode hot paths that recompile per step, and admission
configs that accept unbounded work.

XLA compiles per input shape. A decode loop that feeds the growing context
back as a fresh shape ("cache" sliced to the valid length, prompt+generated
re-run each token, an un-padded per-request batch) silently compiles EVERY
step — seconds of compile per token of decode, the single worst serving
pathology and invisible until you read the logs. The inference/serving
engines record every compiled-program cache miss in ``compile_log``
(``{"kind", "shape", "time"}``); this rule audits that stream.
"""

from __future__ import annotations

from typing import Iterable, List

from .core import AnalysisContext, Finding, Rule, Severity

# ≥3 consecutive same-kind compiles whose shapes differ in exactly one
# dimension by the same small positive stride is the creeping-shape
# signature (stride = tokens appended per step). Bucketed shape sets
# (powers of two) double between misses — unequal strides, never flagged.
_MIN_RUN = 3
_MAX_STRIDE = 8


def _stride(prev, cur):
    """(dim, delta) when cur grows from prev in exactly one dimension by a
    small positive delta; None otherwise."""
    if len(prev) != len(cur):
        return None
    diffs = [(d, c - p) for d, (p, c) in enumerate(zip(prev, cur)) if c != p]
    if len(diffs) != 1:
        return None
    d, delta = diffs[0]
    if 0 < delta <= _MAX_STRIDE:
        return (d, delta)
    return None


class UnbucketedDecodeShapeRule(Rule):
    """A decode/generate hot path compiled ≥3 consecutive shapes creeping
    along one dimension at a fixed stride — the recompile-per-step bug."""

    rule_id = "serving/unbucketed-decode-shape"
    default_severity = Severity.ERROR
    description = "decode hot path recompiles per step (unbucketed shape)"

    def check_context(self, ctx: AnalysisContext) -> Iterable[Finding]:
        log = getattr(ctx, "compile_log", None)
        if log is None and ctx.engine is not None:
            log = getattr(ctx.engine, "compile_log", None)
        if not log:
            return
        by_kind = {}
        for ev in log:
            shape = tuple(ev.get("shape") or ())
            if shape:
                by_kind.setdefault(ev.get("kind", "?"), []).append(shape)
        for kind, shapes in by_kind.items():
            yield from self._check_stream(kind, shapes)

    def _check_stream(self, kind: str, shapes: List[tuple]
                      ) -> Iterable[Finding]:
        run = 1
        run_stride = None
        for i in range(1, len(shapes)):
            st = _stride(shapes[i - 1], shapes[i])
            if st is not None and (run_stride is None or st == run_stride):
                run += 1
                run_stride = st
                if run == _MIN_RUN:
                    d, delta = st
                    first, cur = shapes[i - run + 1], shapes[i]
                    yield self.finding(
                        f"'{kind}' compiled {run}+ consecutive shapes "
                        f"creeping along dim {d} by +{delta} per call "
                        f"(e.g. {first} -> {cur}) — every decode step is "
                        f"paying a fresh XLA compile",
                        location=f"compile_log[{kind}]",
                        suggestion="pad the dynamic dimension to a bucket "
                                   "(DeepSpeedInferenceConfig.decode_buckets "
                                   "/ serving shape buckets) or keep the "
                                   "cache fixed-shape with a traced valid "
                                   "length, so one compiled program serves "
                                   "every step",
                    )
                    return  # one finding per stream is enough signal
            elif st is not None:
                # a stride CHANGE still leaves the current pair as the start
                # of a new run — discarding it would delay detection by one
                # compile
                run = 2
                run_stride = st
            else:
                run = 1
                run_stride = None


class UnboundedAdmissionRule(Rule):
    """A serving config armed with no admission bound (``max_queue`` /
    ``max_queued_tokens``) and no deadlines — the overload-unsafe default.

    Under sustained open-loop load ``submit()`` then accepts every request:
    the queue grows host RAM without limit, queued requests age past any
    client timeout before their first token, and the eventual collapse is a
    process OOM instead of a typed rejection at the front door
    (docs/SERVING.md "Overload & failure"). The check reads the engine's
    ``ServingConfig`` (``engine.serving``) — any one of the four knobs armed
    silences it, because each bounds accepted work in SOME dimension (depth,
    token backlog, or time)."""

    rule_id = "serving/unbounded-admission"
    default_severity = Severity.WARNING
    description = "serving admission has no queue bound and no deadlines"

    def check_context(self, ctx: AnalysisContext) -> Iterable[Finding]:
        cfg = getattr(ctx.engine, "serving", None) \
            if ctx.engine is not None else None
        if cfg is None or not hasattr(cfg, "max_queue"):
            return  # not a serving engine (or a pre-overload-control one)
        armed = getattr(cfg, "overload_armed", None)
        if armed is None:  # duck-typed config without the property
            armed = any(
                getattr(cfg, k, None) is not None
                for k in ("max_queue", "max_queued_tokens",
                          "ttft_deadline_s", "request_deadline_s"))
        if armed:
            return
        yield self.finding(
            "serving admission is unbounded: no max_queue, no "
            "max_queued_tokens, and no TTFT/end-to-end deadlines — under "
            "sustained overload submit() accepts work the pool can never "
            "serve in time (host-RAM queue growth, unbounded tail latency, "
            "eventual OOM instead of a typed rejection)",
            location="ServingConfig",
            suggestion="set max_queue (queue depth) and/or "
                       "max_queued_tokens (token-budget backpressure), and "
                       "arm ttft_deadline_s/request_deadline_s so expired "
                       "work is evicted — see docs/SERVING.md "
                       "'Overload & failure'",
        )


class DenseKVAtCapacityRule(Rule):
    """A serving config that is plainly KV-capacity-bound — quantized
    WEIGHT stacks, or a scheduler showing pool-pressure evidence — while
    ``kv_bits`` is unset, so the pools still spend dense bytes per token.

    Mirrors ``config/quantized-wire-missing``: the operator armed one half
    of the quantization story and the compiled/served program contradicts
    the intent. Quantized weights mean decode HBM is KV-dominated (the
    weight bytes already shrank 2-4x); pool-pressure evidence (recompute
    preemptions, shed/backlog rejections) means the pool is the admission
    bottleneck RIGHT NOW. Either way int8 KV pages (``kv_bits=8``) roughly
    double max decode slots at fixed HBM (docs/SERVING.md "KV quantization
    & prefix caching") — leaving them dense is goodput on the table."""

    rule_id = "serving/dense-kv-at-capacity"
    default_severity = Severity.WARNING
    description = "serving at KV-capacity limits with dense (unquantized) pages"

    def check_context(self, ctx: AnalysisContext) -> Iterable[Finding]:
        eng = ctx.engine
        cfg = getattr(eng, "serving", None) if eng is not None else None
        if cfg is None or not hasattr(cfg, "kv_bits"):
            return  # not a serving engine (or a pre-kv-quantization one)
        if getattr(cfg, "kv_bits", None):
            return  # pools already quantized
        reasons = []
        qkv = None
        try:
            qkv = eng.params.get("blocks", {}).get("qkv_w")
        except AttributeError:
            pass
        if isinstance(qkv, dict) and ({"q", "s"} <= set(qkv)
                                      or {"q4", "s"} <= set(qkv)):
            reasons.append(
                "the weight stacks are int8/int4 (decode HBM is now "
                "KV-dominated)")
        sched = getattr(eng, "last_scheduler", None)
        counters = getattr(sched, "counters", None) or {}
        pressure = {k: counters[k] for k in
                    ("preemption", "request_shed") if counters.get(k)}
        if pressure:
            reasons.append(
                f"the last serving run hit pool-capacity pressure "
                f"({', '.join(f'{k}={v}' for k, v in pressure.items())})")
        if not reasons:
            return
        yield self.finding(
            "serving from dense KV pages at the capacity limit: "
            + " and ".join(reasons)
            + " while kv_bits is unset — int8 KV pages hold ~2x the tokens "
              "(int4 ~4x) in the same pool HBM, directly raising max decode "
              "slots and goodput at saturation",
            location="ServingConfig.kv_bits",
            suggestion="set ServingConfig(kv_bits=8) (with num_slots='auto' "
                       "the AOT fit ladder re-sizes slots from the quantized "
                       "pool bytes); greedy outputs stay within the "
                       "documented quantization tolerance — see "
                       "docs/SERVING.md 'KV quantization & prefix caching'",
        )


class FleetWithoutFailoverRule(Rule):
    """A fleet config running >= 2 replicas with NO failure detection
    armed: neither a heartbeat deadline (hung-replica eviction) nor a
    re-route budget (dead-replica work recovery).

    A single replica dying loses its own in-flight work — painful but
    bounded, and the supervisor restarts it. A FLEET exists precisely so
    replica death is survivable; with both knobs off, the router keeps a
    dead or wedged replica in the placement set forever (every request
    routed there is silently lost, a hung replica never trips anything)
    and re-routes nothing — multi-replica cost, single-replica
    availability. The check reads a router-shaped object
    (``inference/fleet.ReplicaRouter``: a ``replicas`` sequence plus a
    ``config`` with the failover pair) handed to the analyzer as the
    engine, e.g. ``analyze_compile_log(router)``."""

    rule_id = "serving/fleet-without-failover"
    default_severity = Severity.WARNING
    description = "multi-replica fleet with no heartbeat or re-route armed"

    def check_context(self, ctx: AnalysisContext) -> Iterable[Finding]:
        obj = ctx.engine
        cfg = getattr(obj, "config", None) if obj is not None else None
        replicas = getattr(obj, "replicas", None)
        if (replicas is None or cfg is None
                or not hasattr(cfg, "reroute_budget")):
            return  # not a fleet router
        try:
            n = len(replicas)
        except TypeError:
            return
        if n < 2:
            return  # one replica: death is the supervisor's problem
        armed = getattr(cfg, "failover_armed", None)
        if armed is None:  # duck-typed config without the property
            armed = (getattr(cfg, "heartbeat_deadline_s", None) is not None
                     or (getattr(cfg, "reroute_budget", 0) or 0) >= 1)
        if armed:
            return
        yield self.finding(
            f"fleet runs {n} replicas with no failover armed: "
            f"heartbeat_deadline_s is unset (a hung replica is never "
            f"evicted from placement) and reroute_budget < 1 (a dead "
            f"replica's in-flight and queued requests are dropped instead "
            f"of re-issued to survivors) — multi-replica cost with "
            f"single-replica availability",
            location="FleetConfig",
            suggestion="arm FleetConfig(heartbeat_deadline_s=...) so hung "
                       "replicas fail over, and/or reroute_budget >= 1 so "
                       "a dead replica's accepted work re-routes with kept "
                       "tokens — see docs/SERVING.md 'Fleet'",
        )


class SpeculationWithoutGreedyGateRule(Rule):
    """A speculative drafter is armed while the acceptance path is NOT
    greedy/temperature-0.

    Longest-prefix acceptance is output-preserving ONLY under greedy
    decoding: the verifier's argmax at position i is what a non-speculative
    step would have produced, so accepting drafts that match it provably
    changes nothing. With sampled acceptance (``sampling_temperature`` != 0,
    or a non-"greedy" ``spec_acceptance``) that proof evaporates — correct
    sampled speculation needs rejection sampling against the draft
    distribution, which this stack does not implement, so the config is
    silently changing the output distribution."""

    rule_id = "serving/speculation-without-greedy-gate"
    default_severity = Severity.WARNING
    description = "speculative drafter armed without a greedy acceptance gate"

    def check_context(self, ctx: AnalysisContext) -> Iterable[Finding]:
        cfg = getattr(ctx.engine, "serving", None) \
            if ctx.engine is not None else None
        if cfg is None or not hasattr(cfg, "spec_drafter"):
            return  # not a serving engine (or a pre-speculation one)
        drafter = getattr(cfg, "spec_drafter", None)
        if not drafter:
            return  # no speculation armed
        temp = getattr(cfg, "sampling_temperature", 0.0) or 0.0
        acceptance = getattr(cfg, "spec_acceptance", "greedy")
        if temp == 0.0 and acceptance == "greedy":
            return  # the output-preserving configuration
        yield self.finding(
            f"drafter '{drafter}' is armed but the acceptance path is not "
            f"greedy (sampling_temperature={temp}, "
            f"spec_acceptance={acceptance!r}) — longest-prefix acceptance "
            f"only preserves outputs under temperature-0 decoding; this "
            f"config silently changes the output distribution",
            location="ServingConfig.spec_drafter",
            suggestion="serve greedily (sampling_temperature=0.0, "
                       "spec_acceptance='greedy') — see docs/SERVING.md "
                       "'Speculative decoding'",
        )


class UntieredMultiTenantRule(Rule):
    """Multiple distinct ``tenant_id``s observed in the serving submit
    evidence while no SLO-tier config is armed — the
    ``serving/unbounded-admission`` pattern one level up: admission is
    (maybe) bounded, but every tenant shares ONE class, so a single batch
    tenant flooding ``submit()`` degrades every interactive user
    identically. The scheduler records every tenant it has seen
    (``tenants_seen``); ≥2 of them with ``ServingConfig.tiers`` unset means
    the multi-tenant contract is running without its isolation machinery
    (WFQ, per-tier partitions, the degradation ladder)."""

    rule_id = "serving/untiered-multi-tenant"
    default_severity = Severity.WARNING
    description = "multiple tenants served with no SLO-tier config armed"

    def check_context(self, ctx: AnalysisContext) -> Iterable[Finding]:
        eng = ctx.engine
        cfg = getattr(eng, "serving", None) if eng is not None else None
        sched = getattr(eng, "last_scheduler", None) if eng is not None \
            else None
        if sched is None:
            return  # no serving run to audit (or a raw compile_log list)
        seen = getattr(sched, "tenants_seen", None)
        if seen is None or len(seen) < 2:
            return  # pre-tenancy scheduler, or effectively single-tenant
        armed = getattr(cfg, "tiers_armed", None) if cfg is not None else None
        if armed is None:  # duck-typed config without the property
            armed = bool(getattr(cfg, "tiers", None)) if cfg is not None \
                else getattr(sched, "tiers", None) is not None
        if armed:
            return
        names = sorted(str(t) for t in seen)
        shown = ", ".join(names[:4]) + ("..." if len(names) > 4 else "")
        yield self.finding(
            f"{len(names)} distinct tenant_ids observed ({shown}) with no "
            f"tier config armed — every tenant competes in one FIFO class, "
            f"so one batch tenant flooding submit() inflates every other "
            f"tenant's TTFT/deadline misses identically (no fair queueing, "
            f"no per-tier shed partitions, no degradation ladder)",
            location="ServingConfig.tiers",
            suggestion="set ServingConfig(tiers=True) (the built-in "
                       "interactive/standard/batch ladder) or a TierConfig "
                       "mapping, and map tenants via ServingConfig("
                       "tenants={...}) — see docs/SERVING.md "
                       "'Multi-tenancy & SLO tiers'",
        )


def serving_rules() -> List[Rule]:
    # TpCollectiveOrderRule lives with the collective-order family but is
    # registered HERE (once): serving_rules() feeds both default_rules()
    # and the analyze_compile_log audit, so the tp serving check runs in
    # both without double-registering in the default set.
    from .rules_collectives import TpCollectiveOrderRule

    return [UnbucketedDecodeShapeRule(), UnboundedAdmissionRule(),
            DenseKVAtCapacityRule(), FleetWithoutFailoverRule(),
            SpeculationWithoutGreedyGateRule(), UntieredMultiTenantRule(),
            TpCollectiveOrderRule()]
