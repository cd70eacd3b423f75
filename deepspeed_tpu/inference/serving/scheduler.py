"""Continuous-batching scheduler: per-decode-step admit / evict / preempt.

The unit of scheduling is a **decode slot**: the decode program is compiled
ONCE for a fixed slot count (the admission limit — ``runtime/aot.py``'s
``find_max_decode_batch`` verdict, see :func:`serving_admission_limit`), and
every step runs all slots whether occupied or not. Requests flow:

    submit -> queue -> [admit: alloc pages, chunked prefill] -> slot
           -> one token per scheduler step -> [finish: free pages, evict]

against the static-batch ``InferenceEngine.generate`` baseline this recycles
a slot the moment its request finishes instead of holding it until the whole
batch drains — at equal HBM (same pool, same slot count) the decode steps
spend no work on finished sequences.

Page growth is on demand: a slot crossing a page boundary allocates one page
mid-flight; when the pool is exhausted the most-recently-admitted other slot
is **preempted** (pages freed, request requeued at the FRONT with its
generated tokens kept — re-admission re-prefills prompt+tokens, the
vLLM-style recompute preemption), so the oldest work always completes.

The scheduler is host-pure and device-free: all device work goes through an
*executor* with two methods (implemented by ``serving.engine.ServingEngine``;
tests drive a fake):

- ``prefill(slot, tokens, table_row) -> first_token`` — run the context,
  write its KV into the slot's pages, return the next-token sample
  (optional ``prefill_many(items) -> {slot: first_token}`` batches one
  admission cycle).
- ``decode(tokens, tables, lengths, active, steps=1) -> [steps, num_slots]``
  — ``steps`` fixed-shape decode steps over every slot as one dispatch
  (a flat ``[num_slots]`` return is accepted only for ``steps == 1``).

Production hardening (docs/SERVING.md "Overload & failure"):

- **overload control** — ``submit`` returns a typed
  :class:`AdmissionVerdict`; past ``max_queue`` / ``max_queued_tokens`` the
  configured shed policy rejects the newest request (default) or sheds the
  largest queued one to make room. No unbounded host-RAM queue, no
  accepting work the pool can never serve in time.
- **deadlines** — per-request TTFT and end-to-end deadlines (defaults from
  the scheduler) are checked every step: expired requests are evicted,
  their pages freed, and a ``deadline_miss`` recovery event recorded.
- **dispatch fault recovery** — every executor call is bracketed by the
  resilience watchdog's serving phases and the chaos plan's dispatch
  injectors, retried on the shared ``backoff_delay`` curve, and — when a
  whole episode fails — healed by preempt-and-requeue (kept-token
  semantics) with the offending decode block shape quarantined after K
  failures. Every recovery path ends in a :meth:`audit` pass: page
  conservation is an enforced invariant, not a hope.

Copy-on-write prefix caching (docs/SERVING.md "KV quantization & prefix
caching"): with a :class:`~.paging.PrefixIndex` attached, admission SHAREs
the physical pages of the longest indexed page-aligned prompt prefix
(allocator refcounts) instead of allocating them, the prefill scatter
starts past the borrowed pages, and a successful prefill registers the
request's own full prompt pages for later arrivals. :meth:`audit` then
additionally proves every refcount matches its slot references and that no
shared page can ever be written (it lies wholly below every referencing
slot's write frontier).
"""

from __future__ import annotations

import dataclasses
import enum
import itertools
import time
from collections import deque
from contextlib import nullcontext
from typing import (Any, Deque, Dict, List, Optional, Sequence, Set,
                    Tuple)

import numpy as np

from ...profiling import trace
from ...resilience.chaos import (sdc_flip_fault, serving_dispatch_fault,
                                 serving_tenant_flood)
from ...resilience.retry import backoff_delay
from .paging import (PageAllocator, PrefixIndex, pages_for,
                     prefix_chain_hashes)
from .speculate import AdaptiveSpecK, spec_k_ladder
from .tenancy import (BROWNOUT_STAGES, DEFAULT_TIER, BrownoutConfig,
                      BrownoutController, StartTimeFairQueue, TenantConfig,
                      TierConfig, TokenBucket, sacrifice_key, tier_rank)


class RequestState(enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    FINISHED = "finished"
    REJECTED = "rejected"   # shed at/after submit (overload or unservable)
    EXPIRED = "expired"     # missed its deadline; evicted, pages freed
    HANDOFF = "handoff"     # prefilled on a prefill-role scheduler; pages
    #                         staged for export to a decode-role replica


class ServingFaultError(RuntimeError):
    """The executor failed ``dispatch_failure_budget`` consecutive dispatch
    episodes (each already retried) — the serving process is sick beyond
    what preempt-and-requeue can heal; the supervisor should recycle it."""


class _DispatchFailure(RuntimeError):
    """Internal: one dispatch episode (all retry attempts) failed."""

    def __init__(self, kind: str, attempts: int, last: BaseException):
        super().__init__(f"{kind} dispatch failed after {attempts} attempts: "
                         f"{last!r}")
        self.kind = kind
        self.attempts = attempts
        self.last = last


@dataclasses.dataclass(frozen=True)
class AdmissionVerdict:
    """The typed result of :meth:`ContinuousBatchingScheduler.submit`.

    ``reason``: ``admitted`` | ``unservable`` (prompt+max_new can never fit
    the serving bound — a caller bug, not load) | ``queue_full`` |
    ``token_backlog`` (the admission queue's token-budget backpressure
    estimate is exhausted) | ``draining`` (the scheduler is in a graceful
    drain — finishing accepted work, admitting nothing new) |
    ``rate_limited`` (the tenant's token bucket is empty — its contracted
    rate, not system load) | ``brownout`` (the degradation ladder has
    closed this tier's admission; docs/SERVING.md "Multi-tenancy & SLO
    tiers"). ``shed_rid``: under the ``reject_largest`` policy, the rid of
    the queued request evicted to make room."""

    admitted: bool
    reason: str = "admitted"
    detail: str = ""
    shed_rid: Optional[int] = None

    def __bool__(self) -> bool:
        return self.admitted


_rid = itertools.count()


@dataclasses.dataclass(eq=False)
class Request:
    """One generation request plus its lifecycle bookkeeping.

    ``eq=False``: requests compare by identity. Field equality was never
    meaningful (the ndarray prompt makes generated ``__eq__`` raise on any
    same-length comparison) and the queue's ``remove()`` must match THE
    request object, not a lookalike."""

    prompt: np.ndarray                  # [T] int32
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    arrival_time: float = 0.0           # offset into the workload (open loop)
    # deadlines, seconds from t_submit (None -> the scheduler's defaults):
    # TTFT is enforced while queued (first token lands at admission), the
    # end-to-end deadline for the whole lifetime
    ttft_deadline_s: Optional[float] = None
    deadline_s: Optional[float] = None
    # multi-turn affinity key (inference/fleet): requests sharing a
    # session_id are routed to the same replica so its prefix-cache pages
    # stay hot; a lone scheduler ignores it
    session_id: Optional[str] = None
    # disaggregated prefill/decode: a request arriving WITH a KV payload
    # (an ``export_pages`` product from a prefill-role scheduler) admits by
    # IMPORTING the pages instead of prefilling — cleared after the import,
    # so a later preemption falls back to the normal kept-token re-prefill
    kv_payload: Optional[dict] = None
    # multi-tenancy (docs/SERVING.md "Multi-tenancy & SLO tiers"): plain
    # fields so they ride request_spec / the subprocess protocol verbatim.
    # tier is resolved at submit (request override > tenant config >
    # DEFAULT_TIER) and stamped back here so every downstream event,
    # handoff, and ledger row carries it
    tenant_id: Optional[str] = None
    tier: Optional[str] = None
    rid: int = dataclasses.field(default_factory=lambda: next(_rid))

    # lifecycle (filled by the scheduler)
    state: RequestState = RequestState.QUEUED
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None     # clock at the (first) slot claim
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None
    preemptions: int = 0
    reject_reason: Optional[str] = None  # set when REJECTED/EXPIRED
    # per-request speculation ledger (draft positions offered to the
    # verifier / confirmed by it — the request-level accept-rate row)
    spec_drafted: int = 0
    spec_accepted: int = 0

    @property
    def context_len(self) -> int:
        """Tokens whose KV must be live to continue this request."""
        return len(self.prompt) + len(self.tokens)

    @property
    def done(self) -> bool:
        return (len(self.tokens) >= self.max_new_tokens
                or (self.eos_token_id is not None and self.tokens
                    and self.tokens[-1] == self.eos_token_id))

    @property
    def work_tokens(self) -> int:
        """Remaining worst-case token work: what the backpressure estimate
        charges this request against ``max_queued_tokens`` (prompt KV to
        prefill + tokens still to decode)."""
        return len(self.prompt) + self.max_new_tokens - len(self.tokens)


SHED_POLICIES = ("reject_newest", "reject_largest")


class ContinuousBatchingScheduler:
    def __init__(self, executor: Any, num_slots: int, num_pages: int,
                 page_size: int, pages_per_seq: int, decode_block: int = 1,
                 max_context: Optional[int] = None, clock=time.monotonic,
                 max_queue: Optional[int] = None,
                 max_queued_tokens: Optional[int] = None,
                 shed_policy: str = "reject_newest",
                 ttft_deadline_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 dispatch_retries: int = 2,
                 retry_base_delay: float = 0.02,
                 retry_max_delay: float = 0.25,
                 quarantine_after: int = 2,
                 dispatch_failure_budget: int = 8,
                 recovery_log: Any = None, watchdog: Any = None,
                 prefix_cache: Optional[PrefixIndex] = None,
                 drafter: Any = None, spec_k: int = 4,
                 spec_adaptive: bool = True, role: str = "both",
                 tiers: Optional[Dict[str, TierConfig]] = None,
                 tenants: Optional[Dict[str, TenantConfig]] = None,
                 brownout: Optional[BrownoutConfig] = None,
                 latency_preempt_budget: int = 2,
                 page_fingerprints: bool = False,
                 pages_scan_per_step: int = 1):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if shed_policy not in SHED_POLICIES:
            raise ValueError(f"shed_policy {shed_policy!r} not in "
                             f"{SHED_POLICIES}")
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be both|prefill|decode, got "
                             f"{role!r}")
        self.executor = executor
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.pages_per_seq = int(pages_per_seq)
        if not (1 <= decode_block <= self.page_size):
            raise ValueError(f"decode_block {decode_block} outside "
                             f"[1, page_size]")
        self.decode_block = int(decode_block)
        # what the executor's model adds to a serve.decode span from the
        # lengths (``model.decode_counts``); None from one without a model
        self._model_counts = getattr(executor, "decode_counts", None)
        # the engine's model-length bound can sit BELOW the page capacity by
        # a partial page — admission must honor the tighter of the two
        self.max_context = int(max_context if max_context is not None
                               else pages_per_seq * page_size)
        self.allocator = PageAllocator(num_pages)
        self.clock = clock
        # overload control / deadlines
        self.max_queue = None if max_queue is None else int(max_queue)
        self.max_queued_tokens = (None if max_queued_tokens is None
                                  else int(max_queued_tokens))
        self.shed_policy = shed_policy
        self.ttft_deadline_s = ttft_deadline_s
        self.deadline_s = deadline_s
        # dispatch fault recovery
        self.dispatch_retries = int(dispatch_retries)
        self.retry_base_delay = float(retry_base_delay)
        self.retry_max_delay = float(retry_max_delay)
        self.quarantine_after = int(quarantine_after)
        self.dispatch_failure_budget = int(dispatch_failure_budget)
        self.recovery_log = recovery_log
        self.watchdog = watchdog
        self._owns_watchdog = False  # set by ServingEngine.make_scheduler
        # shared-prefix page reuse (copy-on-write; None = off): admission
        # looks the prompt's page-aligned prefix up in the index and SHAREs
        # those physical pages instead of allocating fresh ones
        self.prefix_cache = prefix_cache
        # silent-corruption defense for immutable KV (docs/RESILIENCE.md
        # "Data integrity"): pages behind the write frontier are stamped
        # with a content fingerprint when they become shareable (prefix
        # registration, handoff staging) and re-verified at every trust
        # boundary (share-time claim, background scan, recovery audit). A
        # mismatch evicts the page from the prefix index and unwinds
        # borrowers to a clean re-prefill — never a blind retry.
        self.page_fingerprints = bool(page_fingerprints)
        self.pages_scan_per_step = max(0, int(pages_scan_per_step))
        self._page_fp: Dict[int, int] = {}
        self._page_scan_rr = 0  # round-robin cursor over stamped pages
        # cumulative page accounting: logical = pages every admission asked
        # for, physical = pages actually allocated, shared = pages served
        # from the prefix index — physical/logical is the bench row's
        # page-reuse ratio
        self.page_stats: Dict[str, int] = {
            "logical": 0, "physical": 0, "shared": 0}
        # multi-tenancy (docs/SERVING.md "Multi-tenancy & SLO tiers"):
        # tiers=None keeps the scheduler byte-for-byte FIFO; with a tier
        # table armed the queue is ordered by start-time-fair-queueing
        # virtual time (per-tenant flows weighted by tier), admission
        # partitions are per tier, and the brownout ladder degrades batch
        # before standard before interactive under sustained pressure
        self.tiers = dict(tiers) if tiers else None
        self.tenants: Dict[str, TenantConfig] = dict(tenants) if tenants \
            else {}
        self._wfq = StartTimeFairQueue() if self.tiers else None
        self._buckets: Dict[str, TokenBucket] = {}
        self.brownout = (BrownoutController(brownout or BrownoutConfig())
                         if self.tiers else None)
        self.brownout_stage = 0
        # how many times one batch request may be displaced by a queued
        # interactive request before it becomes preemption-immune (0
        # disables latency preemption; pool-pressure preemption is never
        # budgeted — it is a capacity fact, not a policy choice)
        self.latency_preempt_budget = int(latency_preempt_budget)
        if self.tiers is not None:
            total_reserved = sum(t.reserved_slots for t in
                                 self.tiers.values())
            if total_reserved >= self.num_slots:
                raise ValueError(
                    f"tier slot reservations ({total_reserved}) must leave "
                    f"at least one unreserved slot of {self.num_slots}")
        # distinct tenant ids observed at submit (tiered or not) — the
        # evidence the serving/untiered-multi-tenant dslint rule reads
        self.tenants_seen: Set[str] = set()
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * self.num_slots
        self._slot_pages: List[List[int]] = [[] for _ in range(self.num_slots)]
        # leading pages of each slot that are BORROWED (shared prefix) —
        # the audit's no-write-on-shared invariant is anchored here
        self._slot_shared: List[int] = [0] * self.num_slots
        self._admit_seq: List[int] = [0] * self.num_slots  # admission order
        # the slots whose next input is a first token of THIS step's
        # admission (the ``fresh`` count of its serve.decode span)
        self._fresh: Set[int] = set()
        self._admissions = 0
        self.tables = np.zeros((self.num_slots, self.pages_per_seq), np.int32)
        self.lengths = np.zeros(self.num_slots, np.int32)
        self.next_input = np.zeros(self.num_slots, np.int32)
        self.finished: List[Request] = []
        self.shed: List[Request] = []      # REJECTED (submit-time or policy)
        self.expired: List[Request] = []   # EXPIRED (deadline misses)
        self.counters: Dict[str, int] = {}
        self.steps = 0
        self._draining = False
        self._dispatch_count = 0           # chaos injection index
        # disaggregated prefill/decode (docs/SERVING.md "Tensor parallel &
        # disaggregation"): a "prefill" scheduler stops each request after
        # its first token and STAGES the slot for handoff — the pages stay
        # owned (export-before-free) until the decode side acknowledges via
        # complete_handoff(). A "decode" scheduler admits kv_payload
        # requests by importing pages instead of prefilling.
        self.role = role
        self._handoffs: Dict[int, dict] = {}      # rid -> staged entry
        self._handoff_slots: Set[int] = set()
        self.handed_off: List[Request] = []       # completed exports
        # failed dispatch EPISODES in a row, per kind: a healthy prefill
        # path must not mask a dead decode path (or vice versa) — the
        # admit/fail/requeue cycle would spin forever against a shared
        # counter that every successful prefill resets
        self._consecutive_failures: Dict[str, int] = {}
        self._block_failures: Dict[int, int] = {}
        self._quarantined_blocks: Set[int] = set()
        # speculative decoding (docs/SERVING.md "Speculative decoding"):
        # a drafter proposes up to k tokens per slot, ONE verify dispatch
        # scores k+1 positions, longest-prefix greedy acceptance commits
        # only the confirmed prefix — rejected suffixes were never written
        self.drafter = drafter
        self._spec_ctl = (AdaptiveSpecK(spec_k_ladder(spec_k),
                                        adaptive=spec_adaptive)
                          if drafter is not None else None)
        self.spec_stats: Dict[str, Any] = {
            "drafter": getattr(drafter, "kind", None),
            "windows": 0,           # verify dispatches
            "drafted": 0,           # draft positions offered (k x slots)
            "accepted": 0,          # draft positions confirmed
            "committed_tokens": 0,  # tokens produced by verify windows
            "full_accept_windows": 0,   # slot-windows: every real draft hit
            "full_reject_windows": 0,   # slot-windows: real drafts, none hit
            "fallback_steps": 0,    # steps with no drafts -> plain decode
        }

    # ------------------------------------------------------------ bookkeeping
    @property
    def active_slots(self) -> List[int]:
        """Slots actively DECODING — a staged handoff still occupies its
        slot (pages owned until the decode side acks) but never decodes,
        never expires as "running", and is never a preemption victim."""
        return [i for i, r in enumerate(self.slots)
                if r is not None and r.state is RequestState.RUNNING]

    @property
    def idle(self) -> bool:
        return (not self.queue and not self.active_slots
                and not self._handoffs)

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        """A drain was requested and every accepted request has since left
        the system (finished, expired, or shed by policy) — the point at
        which ``close()`` loses no work."""
        return self._draining and self.idle

    def drain(self) -> None:
        """Graceful, idempotent drain: stop admitting NEW submissions
        (``submit`` returns a typed ``draining`` rejection) while queued and
        running requests keep stepping to completion. The autoscaler's
        scale-down path is ``drain()`` -> ``step()`` until :attr:`drained`
        -> ``close()`` — accepted work is never dropped, where an abrupt
        ``close()`` would strand every in-flight request."""
        if not self._draining:
            self._draining = True
            self._record("drain_started", queued=len(self.queue),
                         active=len(self.active_slots))

    @property
    def queued_tokens(self) -> int:
        """The admission queue's token-backpressure estimate: worst-case
        tokens of work (prompt KV + remaining generation) the queue already
        holds. What ``max_queued_tokens`` bounds."""
        return sum(r.work_tokens for r in self.queue)

    def _record(self, event: str, value: float = 1.0, **fields: Any) -> None:
        self.counters[event] = self.counters.get(event, 0) + 1
        if self.recovery_log is not None:
            try:
                self.recovery_log.record(event, value=value, step=self.steps,
                                         **fields)
            except Exception:  # event export must never fail serving
                pass

    # ------------------------------------------------------------- tenancy
    def _tenant_fields(self, req: Request) -> Dict[str, Any]:
        """Per-tenant attribution stamped onto recovery events: absent for
        untenanted traffic, so the pre-tier event schema is unchanged."""
        f: Dict[str, Any] = {}
        if req.tenant_id is not None:
            f["tenant_id"] = req.tenant_id
        if req.tier is not None:
            f["tier"] = req.tier
        return f

    def _resolve_tier(self, req: Request) -> Optional[str]:
        """Effective tier of a submission (request override > tenant config
        > DEFAULT_TIER), stamped back onto the request. None when untiered
        (the request's tier field is left as-is for the ledger)."""
        if req.tenant_id:
            self.tenants_seen.add(req.tenant_id)
        if self.tiers is None:
            return None
        tier = req.tier
        if tier is None and req.tenant_id in self.tenants:
            tier = self.tenants[req.tenant_id].tier
        if tier not in self.tiers:
            tier = DEFAULT_TIER if DEFAULT_TIER in self.tiers \
                else min(self.tiers, key=tier_rank)
        req.tier = tier
        return tier

    def _rate_limit_ok(self, req: Request, tcfg: TierConfig) -> bool:
        """Per-tenant token bucket (work tokens/s): tenant override first,
        tier default second, unlimited when neither sets a rate."""
        if req.tenant_id is None:
            return True
        ten = self.tenants.get(req.tenant_id)
        rate = (ten.rate_tokens_per_s if ten is not None
                and ten.rate_tokens_per_s is not None
                else tcfg.rate_tokens_per_s)
        if rate is None:
            return True
        bucket = self._buckets.get(req.tenant_id)
        if bucket is None:
            burst = (ten.rate_burst_tokens if ten is not None
                     and ten.rate_burst_tokens is not None
                     else tcfg.rate_burst_tokens)
            bucket = TokenBucket(rate, burst)
            self._buckets[req.tenant_id] = bucket
        return bucket.try_take(req.work_tokens, self.clock())

    def _victim_key(self, slot: int) -> tuple:
        """Preemption-victim ordering (``max()`` wins): untiered, pure
        newest-first; tiered, batch slots die before interactive ones,
        newest-first within a tier — the growing-slot rule is preserved
        because the grower itself can still win."""
        if self.tiers is None:
            return (0, self._admit_seq[slot])
        return sacrifice_key(self.slots[slot].tier, self._admit_seq[slot])

    def _mark_shed(self, req: Request, reason: str, detail: str = "") -> None:
        req.state = RequestState.REJECTED
        req.reject_reason = reason
        self.shed.append(req)
        if (self.brownout is not None
                and reason in ("queue_full", "token_backlog",
                               "shed_for_smaller")):
            # only ORGANIC pressure feeds the ladder: counting its own
            # brownout sheds (or rate-limit/drain rejections) as pressure
            # would latch the ladder at its deepest stage forever
            self.brownout.observe("shed", self.clock())
        self._record("request_shed", rid=req.rid, reason=reason,
                     work_tokens=req.work_tokens, detail=detail[:200],
                     **self._tenant_fields(req))

    def submit(self, req: Request) -> AdmissionVerdict:
        """Admission control. Returns a typed verdict — the caller sees WHY
        a request was turned away (unservable vs overload) instead of a
        silently growing queue. A rejected request is marked
        ``RequestState.REJECTED`` and never enters the queue."""
        tier = self._resolve_tier(req)
        if self.brownout is not None:
            self.brownout.observe("submit", self.clock())
        if self._draining:
            detail = (f"request {req.rid} rejected: scheduler is draining "
                      f"({len(self.queue)} queued + "
                      f"{len(self.active_slots)} running to finish)")
            self._mark_shed(req, "draining", detail)
            return AdmissionVerdict(False, "draining", detail)
        worst = len(req.prompt) + req.max_new_tokens
        pool = self.allocator.num_pages - 1  # page 0 reserved
        if (worst > self.max_context
                or pages_for(worst, self.page_size) > self.pages_per_seq
                or pages_for(worst, self.page_size) > pool):
            # the pool bound matters too: a request needing more pages than
            # EXIST can never admit (queue head-of-line spins forever) and,
            # admitted mid-way, would self-preempt in an infinite
            # recompute loop once it outgrows the pool
            detail = (
                f"request {req.rid}: prompt+max_new={worst} tokens exceeds "
                f"the serving bound (max_context={self.max_context}, "
                f"pages_per_seq={self.pages_per_seq} x page_size="
                f"{self.page_size}, pool={pool} pages) — reject at the "
                f"front door, not mid-decode")
            self._mark_shed(req, "unservable", detail)
            return AdmissionVerdict(False, "unservable", detail)
        tcfg = self.tiers[tier] if tier is not None else None
        if tcfg is not None:
            # degradation ladder: from shed_batch onward, new batch-tier
            # work is turned away at the front door (reversible — the
            # ladder steps back down when pressure clears)
            if self.brownout_stage >= 1 and tier == "batch":
                detail = (f"request {req.rid} rejected: brownout stage "
                          f"{BROWNOUT_STAGES[self.brownout_stage]!r} sheds "
                          f"batch-tier admissions")
                self._mark_shed(req, "brownout", detail)
                return AdmissionVerdict(False, "brownout", detail)
            if not self._rate_limit_ok(req, tcfg):
                detail = (f"request {req.rid} rejected: tenant "
                          f"{req.tenant_id!r} token bucket empty "
                          f"({req.work_tokens} work tokens requested)")
                self._mark_shed(req, "rate_limited", detail)
                return AdmissionVerdict(False, "rate_limited", detail)
        # overload control: queue-depth cap, then the token-budget estimate
        # (per-tier partitions when a tier table is armed)
        verdict = self._admission_control(req)
        if not verdict.admitted:
            return verdict
        if req.ttft_deadline_s is None:
            req.ttft_deadline_s = (tcfg.ttft_deadline_s
                                   if tcfg is not None
                                   and tcfg.ttft_deadline_s is not None
                                   else self.ttft_deadline_s)
        if req.deadline_s is None:
            req.deadline_s = (tcfg.deadline_s
                              if tcfg is not None
                              and tcfg.deadline_s is not None
                              else self.deadline_s)
        req.state = RequestState.QUEUED
        if req.t_submit is None:
            req.t_submit = self.clock()
        if self._wfq is not None:
            # SFQ virtual-time tags: per-tenant flows, tier-weighted —
            # a tenant's backlog chains behind itself, never behind
            # another tenant's
            req._wfq_start, req._wfq_finish = self._wfq.stamp(
                req.tenant_id or "_anon", tcfg.weight, req.work_tokens)
        self.queue.append(req)
        return verdict

    def _admission_control(self, req: Request) -> AdmissionVerdict:
        # untiered: one global partition (the whole queue, the global
        # knobs). Tiered: the request competes only against its OWN tier's
        # queued work, bounded by the tier's knobs (global fallback) — a
        # batch flood exhausts the batch partition and draws token_backlog
        # verdicts while interactive admission stays open.
        tcfg = (self.tiers.get(req.tier)
                if self.tiers is not None and req.tier is not None else None)
        if tcfg is None:
            pool = list(self.queue)
            max_q, max_t = self.max_queue, self.max_queued_tokens
        else:
            pool = [r for r in self.queue
                    if (r.tier or DEFAULT_TIER) == req.tier]
            max_q = (tcfg.max_queue if tcfg.max_queue is not None
                     else self.max_queue)
            max_t = (tcfg.max_queued_tokens
                     if tcfg.max_queued_tokens is not None
                     else self.max_queued_tokens)

        def over(queued: List[Request]) -> bool:
            depth = max_q is not None and len(queued) >= max_q
            tokens = (max_t is not None
                      and sum(r.work_tokens for r in queued)
                      + req.work_tokens > max_t)
            return depth or tokens

        if not over(pool):
            return AdmissionVerdict(True)
        if self.shed_policy == "reject_largest":
            # plan the shed set FIRST: the largest queued request(s) — the
            # cheapest goodput to sacrifice per freed token — each strictly
            # larger than the incoming one (shedding down trades goodput
            # away). Victims are only actually sacrificed if the incoming
            # request then fits; otherwise nobody dies for a rejection.
            # Tiered, victims come from the request's own partition only.
            sim = list(pool)
            victims: List[Request] = []
            while sim and over(sim):
                v = max(sim, key=lambda r: r.work_tokens)
                if v.work_tokens <= req.work_tokens:
                    break
                sim.remove(v)
                victims.append(v)
            if not over(sim):
                for v in victims:
                    self.queue.remove(v)
                    self._mark_shed(v, "shed_for_smaller",
                                    f"shed for request {req.rid}")
                return AdmissionVerdict(
                    True, shed_rid=victims[-1].rid if victims else None)
        over_depth = max_q is not None and len(pool) >= max_q
        reason = "queue_full" if over_depth else "token_backlog"
        detail = (
            f"request {req.rid} rejected ({reason}): "
            + (f"tier {req.tier!r} " if tcfg is not None else "")
            + f"queue depth {len(pool)}"
            + (f"/{max_q}" if max_q is not None else "")
            + f", queued work {sum(r.work_tokens for r in pool)} tokens"
            + (f"/{max_t}" if max_t is not None else ""))
        self._mark_shed(req, reason, detail)
        return AdmissionVerdict(False, reason, detail)

    def _release(self, slot: int) -> None:
        if self.drafter is not None:
            try:
                self.drafter.release(slot)
            except Exception:  # drafter state is advisory, never fatal
                pass
        released = self.allocator.free(self._slot_pages[slot])
        if self.prefix_cache is not None and released:
            # a page whose LAST reference died is about to be recycled — it
            # must never serve another request's prefix lookup
            self.prefix_cache.forget(released)
        for p in released:
            # recycled page: its old content stamp is meaningless
            self._page_fp.pop(p, None)
        self._slot_pages[slot] = []
        self._slot_shared[slot] = 0
        self.tables[slot] = 0
        self.lengths[slot] = 0
        self.next_input[slot] = 0
        self.slots[slot] = None

    def _finish(self, slot: int) -> None:
        req = self.slots[slot]
        req.state = RequestState.FINISHED
        req.t_done = self.clock()
        self.finished.append(req)
        # the per-tenant goodput row (value = tokens delivered): with the
        # shed/miss/preemption events this completes the Serving/* ledger's
        # by-tenant accounting (docs/SERVING.md "Multi-tenancy & SLO tiers")
        self._record("request_finished", value=float(len(req.tokens)),
                     rid=req.rid, tokens=len(req.tokens),
                     **self._tenant_fields(req))
        self._release(slot)

    def _preempt(self, slot: int, why: str = "pool") -> None:
        """Recompute-style preemption: pages freed, generated tokens KEPT;
        re-admission prefills prompt+tokens (greedy decode reproduces the
        exact state, no quality loss — only recomputed FLOPs).

        ``why="pool"`` is page pressure; ``why="latency"`` is tier-aware
        displacement by a protected request, charged against the victim's
        bounded yield budget (:attr:`latency_preempt_budget`). Either way
        the victim requeues at the front with its original SFQ tags —
        oldest work still completes."""
        req = self.slots[slot]
        req.preemptions += 1
        if why == "latency":
            req._latency_preempts = getattr(req, "_latency_preempts", 0) + 1
        req.state = RequestState.QUEUED
        self._record("preemption", rid=req.rid, why=why,
                     tokens_done=len(req.tokens),
                     **self._tenant_fields(req))
        self._release(slot)
        self.queue.appendleft(req)

    # ------------------------------------------------------------- deadlines
    def _expire(self, req: Request, where: str, now: float) -> None:
        req.state = RequestState.EXPIRED
        req.reject_reason = f"deadline_{where}"
        self.expired.append(req)
        t0 = req.t_submit if req.t_submit is not None else now
        if self.brownout is not None:
            self.brownout.observe("miss", now)
        self._record("deadline_miss", value=now - t0,
                     rid=req.rid, where=where,
                     tokens_done=len(req.tokens),
                     **self._tenant_fields(req))

    def _sweep_deadlines(self) -> int:
        """Evict expired requests (queued: TTFT or e2e deadline already
        blown; running: e2e deadline blown — pages freed). Returns the
        number evicted; any eviction is a recovery action, so the page
        audit runs."""
        now = self.clock()
        evicted = 0
        for req in [r for r in self.queue]:
            t0 = req.t_submit if req.t_submit is not None else now
            # TTFT only applies while the first token is still owed — a
            # preempted request back in the queue has already delivered it
            miss_ttft = (req.ttft_deadline_s is not None
                         and req.t_first_token is None
                         and now - t0 > req.ttft_deadline_s)
            miss_e2e = (req.deadline_s is not None
                        and now - t0 > req.deadline_s)
            if miss_ttft or miss_e2e:
                self.queue.remove(req)
                self._expire(req, "queued", now)
                evicted += 1
        for slot in self.active_slots:
            req = self.slots[slot]
            t0 = req.t_submit if req.t_submit is not None else now
            if req.deadline_s is not None and now - t0 > req.deadline_s:
                self._release(slot)
                self._expire(req, "running", now)
                evicted += 1
        if evicted:
            self._audit_after_recovery("deadline_sweep")
        return evicted

    # ------------------------------------------------------ dispatch bracket
    def _phase(self, kind: str):
        if self.watchdog is None:
            return nullcontext()
        return self.watchdog.phase(f"serving_{kind}")

    def _dispatch(self, kind: str, fn, *args: Any, **kw: Any) -> Any:
        """One dispatch episode: chaos injection + watchdog phase bracket +
        bounded retry on the shared backoff curve. The chaos hook fires
        INSIDE the phase (an injected stall is observed by the deadline
        machinery) and BEFORE the executor call (an injected raise never
        tears device state, so the in-place retry is sound)."""
        attempts = self.dispatch_retries + 1
        last: Optional[BaseException] = None
        for attempt in range(1, attempts + 1):
            idx = self._dispatch_count
            self._dispatch_count += 1
            try:
                with self._phase(kind):
                    serving_dispatch_fault(kind, idx)
                    out = fn(*args, **kw)
                self._consecutive_failures[kind] = 0
                return out
            except Exception as e:
                last = e
                self._record("dispatch_error", kind=kind, attempt=attempt,
                             error=f"{type(e).__name__}: {e}"[:200])
                if attempt < attempts:
                    time.sleep(backoff_delay(attempt, self.retry_base_delay,
                                             self.retry_max_delay))
        raise _DispatchFailure(kind, attempts, last)

    def _on_dispatch_episode_failed(self, fail: _DispatchFailure,
                                    affected: List[int],
                                    block: Optional[int] = None) -> None:
        """A whole dispatch episode (all retries) failed: quarantine the
        decode block shape after K failures, preempt-and-requeue the
        affected slots (kept-token semantics — greedy re-prefill reproduces
        the exact state), audit the pool, and give up loudly once the
        consecutive-failure budget is spent."""
        if block is not None and block > 1:
            n = self._block_failures.get(block, 0) + 1
            self._block_failures[block] = n
            if (n >= self.quarantine_after
                    and block not in self._quarantined_blocks):
                self._quarantined_blocks.add(block)
                self._record("block_quarantined", value=block, block=block,
                             failures=n)
        # newest-admitted first keeps the requeue order FIFO-consistent:
        # appendleft of newest..oldest leaves the oldest at the queue head
        for slot in sorted(affected, key=lambda s: self._admit_seq[s],
                           reverse=True):
            if self.slots[slot] is not None:
                self._preempt(slot)
        n = self._consecutive_failures.get(fail.kind, 0) + 1
        self._consecutive_failures[fail.kind] = n
        self._record("dispatch_failed", kind=fail.kind,
                     attempts=fail.attempts, consecutive=n,
                     error=f"{type(fail.last).__name__}: {fail.last}"[:200])
        self._audit_after_recovery(f"dispatch_failed[{fail.kind}]")
        # what the device holds after a failed episode is not ours to know:
        # no starvation is counted up to the next dispatch
        trace.drained(None)
        if n >= self.dispatch_failure_budget:
            raise ServingFaultError(
                f"{n} consecutive {fail.kind} dispatch episodes failed "
                f"(budget {self.dispatch_failure_budget}); last: "
                f"{fail}") from fail.last

    # ----------------------------------------------------------- page audit
    def audit(self) -> Dict[str, Any]:
        """The allocator conservation invariant plus the scheduler-side
        cross-checks. With copy-on-write sharing, conservation means free +
        Σ(unique allocated) == total with every refcount >= 1 (allocator
        side), each page's refcount equals the number of slot references it
        actually has, and — the write-safety half — NO slot can ever write
        a shared page: every page referenced by more than one slot must lie
        entirely below each referencing slot's write frontier (a full
        prefix page), because the next append lands at ``lengths[slot]``."""
        fp_fn = (getattr(self.executor, "fingerprint_pages", None)
                 if self.page_fingerprints else None)
        rep = (self.allocator.audit(expected_fingerprints=self._page_fp,
                                    fingerprint_fn=fp_fn)
               if fp_fn is not None else self.allocator.audit())
        errors: List[str] = list(rep["errors"])
        refs: Dict[int, int] = {}
        for s_idx, pages in enumerate(self._slot_pages):
            if len(pages) != len(set(pages)):
                errors.append(f"slot {s_idx} lists a page twice")
            for p in pages:
                refs[p] = refs.get(p, 0) + 1
        if set(refs) != self.allocator.allocated_ids:
            leaked = sorted(self.allocator.allocated_ids - set(refs))
            foreign = sorted(set(refs) - self.allocator.allocated_ids)
            if leaked:
                errors.append(f"pages allocated but owned by no slot "
                              f"(leak): {leaked}")
            if foreign:
                errors.append(f"slot-held pages unknown to the allocator: "
                              f"{foreign}")
        for p, n in refs.items():
            have = self.allocator.refcount(p)
            if have != n:
                errors.append(f"page {p}: {n} slot reference(s) vs "
                              f"allocator refcount {have} (leaked refcount)")
        for s_idx, pages in enumerate(self._slot_pages):
            if s_idx in self._handoff_slots:
                # a staged handoff is read-only by construction: its table
                # row is parked on the sink page (lengths 0), so the
                # frontier invariants below do not apply — conservation and
                # refcount checks above still do
                continue
            frontier = int(self.lengths[s_idx])
            # the borrowed-prefix bookkeeping must agree with reality: the
            # slot borrowed its first _slot_shared pages, so the write
            # frontier can never sit inside them
            if self._slot_shared[s_idx] * self.page_size > frontier:
                errors.append(
                    f"slot {s_idx} records {self._slot_shared[s_idx]} "
                    f"borrowed prefix pages but its write frontier "
                    f"{frontier} is inside them")
            for idx, p in enumerate(pages):
                if (self.allocator.refcount(p) > 1
                        and (idx + 1) * self.page_size > frontier):
                    errors.append(
                        f"shared page {p} (table index {idx}) reaches slot "
                        f"{s_idx}'s write frontier {frontier} — a decode "
                        f"append could land on a shared page")
        rep["errors"] = errors
        rep["ok"] = not errors
        rep["page_stats"] = dict(self.page_stats)
        return rep

    def _audit_after_recovery(self, context: str) -> None:
        rep = self.audit()
        if not rep["ok"]:
            self._record("page_audit_failed", context=context,
                         errors="; ".join(rep["errors"])[:400])
            raise RuntimeError(
                f"page conservation broken after {context}: {rep['errors']}")

    # ---------------------------------------------- KV-page data integrity
    def _stamp_pages(self, pages: List[int]) -> None:
        """Fingerprint pages whose content just became IMMUTABLE (full
        prefix pages at registration, staged handoff pages). Stamp-once:
        a page already stamped keeps its first-writer fingerprint — a
        re-stamp would bless whatever bytes are there now, corrupt or not.
        Stamps die with the page in :meth:`_release`."""
        if not self.page_fingerprints:
            return
        fn = getattr(self.executor, "fingerprint_pages", None)
        todo = [p for p in pages if p not in self._page_fp]
        if fn is None or not todo:
            return
        for p, fp in zip(todo, fn(todo)):
            self._page_fp[p] = int(fp)

    def _verify_pages(self, pages: List[int], context: str) -> List[int]:
        """Re-fingerprint stamped pages and return the mismatches (each
        recorded as a typed ``sdc_detected`` event). Unstamped pages are
        skipped — they are still behind an active write frontier."""
        fn = getattr(self.executor, "fingerprint_pages", None)
        check = [p for p in pages if p in self._page_fp]
        if fn is None or not check:
            return []
        bad = [p for p, fp in zip(check, fn(check))
               if int(fp) != self._page_fp[p]]
        for p in bad:
            self._record("sdc_detected", domain="kv_page", page=int(p),
                         context=context,
                         refcount=self.allocator.refcount(p))
        return bad

    def _quarantine_page(self, page: int, context: str) -> None:
        """Containment + healing for a corrupt KV page: forget it in the
        prefix index (no future admission borrows it), void its stamp, and
        preempt every slot referencing it — recompute-style, so each victim
        re-prefills prompt + kept tokens into clean pages and greedy decode
        reproduces the exact stream. Never a blind retry over rotten KV."""
        if self.prefix_cache is not None:
            self.prefix_cache.forget([page])
        self._page_fp.pop(page, None)
        victims = [i for i, pages in enumerate(self._slot_pages)
                   if page in pages and self.slots[i] is not None
                   and i not in self._handoff_slots]
        for i in victims:
            self._preempt(i, why="sdc")
        self._record("sdc_healed", domain="kv_page", page=int(page),
                     context=context, victims=len(victims))
        self._audit_after_recovery(f"sdc_{context}")

    def _integrity_scan(self) -> None:
        """Budgeted background sweep: verify up to ``pages_scan_per_step``
        stamped pages round-robin per scheduler step, quarantining any
        mismatch. Also the serving consumption point for the chaos plan's
        ``flip_bit_at`` (domain ``kv_page``): the flip lands in a real
        stamped page's pool content so detection exercises the same path
        production corruption would take."""
        flip = sdc_flip_fault(self.steps, scope="serving")
        if flip is not None and self._page_fp:
            corrupt = getattr(self.executor, "corrupt_page_bit", None)
            if corrupt is not None:
                # prefer a SHARED page: the worst blast radius (several
                # borrowers) is the one worth rehearsing
                shared = [p for p in sorted(self._page_fp)
                          if self.allocator.refcount(p) > 1]
                target = (shared or sorted(self._page_fp))[0]
                corrupt(target)
                self._record("chaos_injected", kind="sdc_flip",
                             page=int(target))
        stamped = sorted(self._page_fp)
        if not stamped or self.pages_scan_per_step <= 0:
            return
        k = min(self.pages_scan_per_step, len(stamped))
        start = self._page_scan_rr % len(stamped)
        batch = [stamped[(start + j) % len(stamped)] for j in range(k)]
        self._page_scan_rr = (start + k) % len(stamped)
        for p in self._verify_pages(batch, "scan"):
            self._quarantine_page(p, "scan")

    def close(self) -> None:
        """Stop a watchdog the engine created for this scheduler (no-op for
        caller-owned or absent watchdogs)."""
        if self._owns_watchdog and self.watchdog is not None:
            self.watchdog.stop()
            self.watchdog = None

    # ------------------------------------------------------------ admission
    def _claim_pages(self, req: Request, need: int) -> Optional[tuple]:
        """Pages for one admission: shared prefix pages from the index
        (refcount bumped, copy-on-write) + fresh ones for the rest. Returns
        (pages, shared_count) or None (and claims NOTHING) when the pool
        cannot cover the unshared remainder. The shared count is always <
        ``need``: the append frontier (position ctx, first decode write) is
        past the page-aligned prompt prefix, so the page it lands in is
        always privately owned.

        Hot-path discipline: admission retries EVERY step while the queue
        head is pool-blocked, so the prompt's hash chain is computed once
        and cached on the request, the free-list is probed BEFORE any
        refcount is taken (no share-then-unwind churn per retry), and hit
        statistics count only the admission that proceeds."""
        shared: List[int] = []
        hashes = ()
        if self.prefix_cache is not None:
            hashes = getattr(req, "_prefix_hashes", None)
            if hashes is None:
                hashes = prefix_chain_hashes(np.asarray(req.prompt),
                                             self.page_size)
                req._prefix_hashes = hashes
            shared = self.prefix_cache.lookup_chain(hashes)[:need]
        if shared and self.page_fingerprints:
            # trust boundary: these pages are about to serve ANOTHER
            # request's prefix — re-fingerprint before the refcount bump.
            # A mismatch truncates the borrow at the first corrupt page
            # (its suffix chains through it, so it is unusable too) and
            # quarantines: index eviction + borrower unwind, then this
            # admission proceeds as a partial/complete cache miss.
            bad = self._verify_pages(shared, "share")
            if bad:
                cut = min(shared.index(p) for p in bad)
                for p in bad:
                    self._quarantine_page(p, "share")
                shared = shared[:cut]
        if not self.allocator.can_alloc(need - len(shared)):
            return None
        if shared:
            self.allocator.share(shared)
        own = self.allocator.alloc(need - len(shared))
        if own is None:  # chaos alloc_fail_at fires through the normal path
            if shared:
                self.prefix_cache.forget(self.allocator.free(shared))
            return None
        if self.prefix_cache is not None:
            self.prefix_cache.count(hashes, shared)
        self.page_stats["logical"] += need
        self.page_stats["physical"] += len(own)
        self.page_stats["shared"] += len(shared)
        return shared + own, len(shared)

    def _peek_queued(self, blocked: Set[str]) -> Optional[Request]:
        """Non-mutating admission pick: the request :meth:`_pick_queued`
        would return, WITHOUT advancing SFQ virtual time."""
        if self.tiers is None:
            return self.queue[0] if self.queue else None
        best: Optional[Request] = None
        best_key = None
        for r in self.queue:
            tier = r.tier or DEFAULT_TIER
            if tier in blocked:
                continue
            if self.brownout_stage >= 3 and tier_rank(tier) > 0:
                continue
            key = (getattr(r, "_wfq_start", 0.0),
                   getattr(r, "_wfq_finish", 0.0), r.rid)
            if best_key is None or key < best_key:
                best, best_key = r, key
        return best

    def _pick_queued(self, blocked: Set[str]) -> Optional[Request]:
        """The next queued request to admit. Untiered: the FIFO head.
        Tiered: the minimum SFQ virtual-time tag (start, finish, rid) among
        requests whose tier is neither pool-blocked this cycle (``blocked``
        — per-tier head-of-line, so a pool-blocked batch head cannot block
        interactive admission) nor held by the brownout ladder
        (``hold_standard``: only interactive reaches a slot)."""
        best = self._peek_queued(blocked)
        if best is not None and self._wfq is not None:
            self._wfq.on_select(getattr(best, "_wfq_start", 0.0))
        return best

    def _reserve_shortfall(self, tier: str) -> int:
        """Free slots that must be LEFT OPEN when admitting ``tier``: the
        summed ``reserved_slots`` of every more-protected tier. Strict
        headroom — a protected tier's RUNNING requests do not repay its
        reservation (crediting them would let lower tiers fill every other
        slot the moment one interactive request runs, putting the next
        arrival right back behind a standard decode). Protected tiers
        admit past their reservation through the normal queue: the reserve
        is a floor on instant availability, not a cap on use."""
        if self.tiers is None:
            return 0
        rank = tier_rank(tier)
        return sum(tc.reserved_slots for name, tc in self.tiers.items()
                   if tc.reserved_slots and tier_rank(name) < rank)

    def _latency_preempt(self, blocked: Set[str],
                         pending: Set[int]) -> Optional[Tuple[int, Request]]:
        """Tier-aware latency preemption: every slot is busy but the fair-
        queue head is an INTERACTIVE request — sacrifice the newest
        batch-tier slot (kept-token requeue) rather than make the
        protected tier wait out a batch decode. Only interactive
        displaces, and only batch is ever displaced: standard queues like
        everyone else, and an interactive-vs-interactive conflict is real
        contention, not a noisy neighbor. A victim already displaced
        :attr:`latency_preempt_budget` times is IMMUNE — that bound is
        what keeps the WFQ starvation-freedom property: under a sustained
        interactive storm a batch request yields at most budget times,
        then holds its slot to completion. Returns the freed slot and the
        request it was freed FOR (force-admitted by the caller — the
        displaced victim keeps its original minimal SFQ tag, so selection
        alone cannot be trusted to not hand the slot straight back)."""
        if (self.tiers is None or not self.queue
                or self.latency_preempt_budget <= 0):
            return None
        head = self._peek_queued(blocked)
        if head is None or tier_rank(head.tier or DEFAULT_TIER) != 0:
            return None
        # ``pending`` excludes slots claimed earlier in THIS admission
        # cycle: they sit in the phase-2 prefill batch, and evicting one
        # would leave a stale batch entry prefilling into a slot that no
        # longer belongs to its request
        victims = [s for s in self.active_slots
                   if s not in pending
                   and tier_rank(self.slots[s].tier) >= tier_rank("batch")
                   and (getattr(self.slots[s], "_latency_preempts", 0)
                        < self.latency_preempt_budget)]
        if not victims:
            return None
        victim = max(victims, key=self._victim_key)
        self._preempt(victim, why="latency")
        self._wfq.on_select(getattr(head, "_wfq_start", 0.0))
        self._audit_after_recovery("latency_preempt")
        return victim, head

    def _admit(self) -> int:
        # phase 1: claim slots + pages for everything that fits this cycle
        batch = []  # (slot, context tokens, first unshared position)
        free = deque(s for s in range(self.num_slots)
                     if self.slots[s] is None)
        blocked: Set[str] = set()  # tiers pool-blocked this cycle
        forced: Optional[Request] = None  # latency-preempt beneficiary
        with trace.span(trace.SERVE_ADMIT_CLAIM):
            while True:
                if not free:
                    grab = self._latency_preempt(
                        blocked, {slot for slot, _, _ in batch})
                    if grab is None:
                        break
                    slot, forced = grab
                    free.append(slot)
                slot = free[0]
                req = (forced if forced is not None
                       else self._pick_queued(blocked))
                forced = None
                if req is None:
                    break
                if len(free) <= self._reserve_shortfall(
                        req.tier or DEFAULT_TIER):
                    # admitting would eat a more-protected tier's reserved
                    # slot: this tier sits the cycle out, the slot stays open
                    blocked.add(req.tier or DEFAULT_TIER)
                    continue
                if req.kv_payload is not None:
                    # disaggregated handoff arrival: admit by IMPORTING the
                    # prefill replica's exported pages — no prefill dispatch
                    if self._admit_import(slot, req):
                        free.popleft()
                    elif self.tiers is None:
                        break  # pool-blocked (FIFO) or the import failed
                    else:
                        blocked.add(req.tier or DEFAULT_TIER)
                    continue
                ctx = req.context_len
                # +1: the first decode step appends its token's KV at
                # position ctx, which may open a fresh page
                need = pages_for(ctx + 1, self.page_size)
                claim = self._claim_pages(req, need)
                if claim is None:
                    if self.tiers is None:
                        # head-of-line blocking keeps FIFO order under pressure
                        break
                    blocked.add(req.tier or DEFAULT_TIER)
                    continue
                free.popleft()
                pages, shared = claim
                self.queue.remove(req)
                self._slot_pages[slot] = pages
                self._slot_shared[slot] = shared
                self.tables[slot] = 0
                self.tables[slot, :len(pages)] = pages
                tokens = np.concatenate(
                    [np.asarray(req.prompt, np.int32),
                     np.asarray(req.tokens, np.int32)]) if req.tokens else \
                    np.asarray(req.prompt, np.int32)
                self.lengths[slot] = ctx
                self.slots[slot] = req
                self._admissions += 1
                self._admit_seq[slot] = self._admissions
                req.state = RequestState.RUNNING
                if req.t_admit is None:
                    req.t_admit = self.clock()
                batch.append((slot, tokens, shared * self.page_size))
        if not batch:
            return 0
        # phase 2: prefill the whole admission cycle — batched when the
        # executor supports it ([rows, chunk] dispatches of as many rows as
        # the cycle's short prompts need, instead of one per request). A
        # failed episode (retries exhausted) unwinds the WHOLE admission
        # cycle back to the queue: no request has appended a token yet, so
        # requeue-with-kept-tokens is exact. With prefix
        # sharing the executor additionally receives each row's first
        # UNSHARED position — its KV scatter must never touch a borrowed
        # page (the prefill forward still runs the full context).
        self._stage_decode([slot for slot, _, _ in batch])
        prefilling = trace.span(trace.SERVE_ADMIT_PREFILL, lambda: {
            "rids": trace.join_rids(self.slots[slot].rid
                                    for slot, _, _ in batch)})
        try:
            with prefilling:
                if hasattr(self.executor, "prefill_many"):
                    if self.prefix_cache is not None:
                        items = [(slot, toks, self.tables[slot], start)
                                 for slot, toks, start in batch]
                    else:  # legacy 3-tuple protocol for start-less executors
                        items = [(slot, toks, self.tables[slot])
                                 for slot, toks, _ in batch]
                    results = self._dispatch(
                        "prefill", self.executor.prefill_many, items)
                else:
                    results = {}
                    for slot, toks, start in batch:
                        args = (slot, toks, self.tables[slot])
                        if self.prefix_cache is not None:
                            args += (start,)
                        results[slot] = int(self._dispatch(
                            "prefill", self.executor.prefill, *args))
        except _DispatchFailure as fail:
            self._on_dispatch_episode_failed(fail,
                                             [slot for slot, _, _ in batch])
            return 0
        with trace.span(trace.SERVE_ADMIT_COMMIT):
            for slot, _, _ in batch:
                req = self.slots[slot]
                first = int(results[slot])
                self.next_input[slot] = first
                self._fresh.add(slot)
                # prefill's sample is the next NEW token whether this is a
                # fresh admission (prompt only) or a post-preemption
                # re-prefill (prompt + kept tokens): append it either way
                req.tokens.append(first)
                if req.t_first_token is None:
                    req.t_first_token = self.clock()
                if self.prefix_cache is not None:
                    # the slot's full prompt pages now hold canonical KV —
                    # index them so later arrivals with the same prefix share
                    # (first writer wins; entries die with the page)
                    self.prefix_cache.register(np.asarray(req.prompt),
                                               self._slot_pages[slot])
                    # the registered full-prefix pages are immutable from
                    # here (every position written, frontier past them):
                    # stamped so share/scan/audit can prove they never drift
                    n_full = len(np.asarray(req.prompt)) // self.page_size
                    self._stamp_pages(self._slot_pages[slot][:n_full])
                if req.done:
                    self._finish(slot)
                elif self.role == "prefill":
                    self._stage_handoff(slot)
        return len(batch)

    def _stage_decode(self, fresh: List[int]) -> None:
        """Let the executor enqueue this step's decode dispatch right behind
        the cycle's last prefill program, the first tokens taken from the
        device (``stage_decode``): the chip goes from the one into the other
        while the host still waits for those tokens, commits them and comes
        back to ask (``_decode_step``, whose ``decode`` then only fetches).
        The executor is handed :meth:`_staged_args` to call once the prefill
        programs are queued, so that nothing here stands between a prompt
        and its first program. The step runs in today's order instead where
        staging could not be exact or would not be this step: a request that
        could end on its first token (its slot would not decode), growth
        that needs a preemption (``_decode_step``'s to choose), a drafter
        armed (the step verifies), a prefill replica (it hands off), an
        executor without the call."""
        stage = getattr(self.executor, "stage_decode", None)
        if (stage is None or self.drafter is not None
                or self.role == "prefill"):
            return
        if any(r.eos_token_id is not None
               or r.max_new_tokens - len(r.tokens) < 2
               for r in (self.slots[s] for s in fresh)):
            return
        stage(lambda: self._staged_args(fresh))

    def _staged_args(self, fresh: List[int]) -> Optional[tuple]:
        """What ``_decode_step`` does before its dispatch, done before the
        admission's wait for the slots that will be active, ``fresh`` (this
        cycle's claims) among them: their lengths are known, their budgets
        one token shorter than they read now. ``decode``'s arguments, or
        None where the pool cannot grow without a preemption."""
        block = self._block_size(owed=fresh)
        active = self.active_slots
        with trace.span(trace.SERVE_GROW):
            for slot in active:
                if not self._ensure_page(slot, horizon=block):
                    return None  # what it claimed _decode_step would too
        mask = np.zeros(self.num_slots, bool)
        mask[active] = True
        return (self.next_input.copy(), self.tables.copy(),
                self.lengths.copy(), mask, block)

    # --------------------------------------------- disaggregated handoff
    def _stage_handoff(self, slot: int) -> None:
        """A prefill-role scheduler just delivered a request's first token:
        stage its pages for export instead of decoding. The slot's table
        row is parked on the sink page so a concurrent decode dispatch for
        OTHER slots can never write into the staged pages (a stray append
        would dirty a quantized page's scale before export); the page order
        is snapshotted in the entry."""
        req = self.slots[slot]
        req.state = RequestState.HANDOFF
        # KV live on this replica: everything prefilled — the freshly
        # sampled first token's KV is NOT written yet (the decode side
        # writes it at its own first decode step)
        live = req.context_len - 1
        n_pages = pages_for(live, self.page_size) if live else 0
        self._handoffs[req.rid] = {
            "rid": req.rid, "slot": slot, "request": req,
            "page_ids": list(self._slot_pages[slot][:n_pages]),
            "context_len": live, "popped": False}
        self._handoff_slots.add(slot)
        self.tables[slot] = 0
        self.lengths[slot] = 0
        self.next_input[slot] = 0
        # staged pages are read-only until the decode side acks — stamp
        # them so the background scan covers the staging window and the
        # export's payload fingerprints attest bytes that were still clean
        self._stamp_pages(self._slot_pages[slot][:n_pages])
        self._record("handoff_staged", rid=req.rid, pages=n_pages,
                     context_len=live)

    @property
    def pending_handoff_rids(self) -> Set[int]:
        """Rids staged (popped or not) whose pages this replica still owns."""
        return set(self._handoffs)

    def pop_handoffs(self) -> List[dict]:
        """Staged handoff entries not yet handed to the transport, WITHOUT
        freeing anything (export-before-free: the pages stay owned and
        refcounted until :meth:`complete_handoff`). Each entry carries the
        request, its page ids in table order, and the live context length;
        the caller serializes the pages (``ServingEngine.export_pages``)
        and ships them to a decode-role replica."""
        out = []
        for e in self._handoffs.values():
            if not e["popped"]:
                e["popped"] = True
                out.append(e)
        return out

    def complete_handoff(self, rid: int, ok: bool = True) -> bool:
        """The decode side acknowledged (``ok=True``) — or the handoff was
        orphaned and the router re-routed the request (``ok=False``) —
        either way THIS replica's ownership ends: free the staged pages,
        recycle the slot, audit. Returns False for an unknown rid (already
        completed; idempotent)."""
        e = self._handoffs.pop(rid, None)
        if e is None:
            return False
        slot = e["slot"]
        req = self.slots[slot]
        self._handoff_slots.discard(slot)
        if req is not None:
            if ok:
                self.handed_off.append(req)
            self._release(slot)
        self._record("handoff_complete" if ok else "handoff_aborted",
                     rid=rid)
        self._audit_after_recovery(
            f"handoff_{'complete' if ok else 'abort'}")
        return True

    def _admit_import(self, slot: int, req: Request) -> bool:
        """Admission of a handoff arrival: claim this replica's own pages,
        install the exported KV into them (``executor.import_pages``), and
        seed the slot mid-stream — lengths at the live context, next input
        the already-delivered first token. Page ids need not match across
        replicas; only the table ORDER is the contract."""
        ctx = req.context_len
        # first decode write lands at position ctx-1 (the handed-off
        # token's KV) — pages must cover it
        need = pages_for(ctx, self.page_size)
        pages = (self.allocator.alloc(need)
                 if self.allocator.can_alloc(need) else None)
        if pages is None:
            return False
        self.queue.remove(req)
        live = ctx - 1
        n_kv = pages_for(live, self.page_size) if live else 0
        try:
            self._dispatch("import_kv", self.executor.import_pages,
                           pages[:n_kv], req.kv_payload)
        except _DispatchFailure as fail:
            # nothing installed durably matters — the claim unwinds whole
            # and the request requeues intact for another import attempt
            self.allocator.free(pages)
            self.queue.appendleft(req)
            self._on_dispatch_episode_failed(fail, [])
            return False
        self.page_stats["logical"] += need
        self.page_stats["physical"] += need
        self._slot_pages[slot] = pages
        self._slot_shared[slot] = 0
        self.tables[slot] = 0
        self.tables[slot, :len(pages)] = pages
        self.lengths[slot] = live
        self.next_input[slot] = int(req.tokens[-1])
        self.slots[slot] = req
        self._admissions += 1
        self._admit_seq[slot] = self._admissions
        req.state = RequestState.RUNNING
        if req.t_admit is None:
            req.t_admit = self.clock()
        # consumed: a later preemption re-prefills prompt+kept tokens — the
        # payload's KV no longer covers the grown context
        req.kv_payload = None
        if req.t_first_token is None:
            req.t_first_token = self.clock()
        self._record("handoff_import", rid=req.rid, pages=n_kv,
                     context_len=live)
        return True

    def _ensure_page(self, slot: int, horizon: int = 1) -> bool:
        """Make sure pages exist for write positions ``lengths[slot]`` up to
        ``lengths[slot] + horizon - 1`` (a decode block appends ``horizon``
        tokens between scheduling points)."""
        last_pi = (int(self.lengths[slot]) + horizon - 1) // self.page_size
        if last_pi >= self.pages_per_seq:
            raise RuntimeError(
                f"slot {slot} outgrew pages_per_seq — admission bound broken")
        for pi in range(last_pi + 1):
            if self.tables[slot, pi] != 0:
                continue
            page = self.allocator.alloc(1)
            if page is None:
                return False
            self._slot_pages[slot].append(page[0])
            self.tables[slot, pi] = page[0]
            self.page_stats["logical"] += 1
            self.page_stats["physical"] += 1
        return True

    # ------------------------------------------------------------ one step
    def _decode_stats(self, steps: int, active, mask) -> Dict[str, int]:
        """The counts of a ``serve.decode`` span (``profiling/trace.py``):
        the dispatch's steps and active slots, the tokens their caches hold
        and the tokens the pool can hold (page 0, the sink, holds none); the
        pages those caches cover with the first step's token (what the paged
        kernel's grid walks) and the slots of every table (what it would
        walk, dead slots and all); the active slots whose input token is a
        first token of this step's admission (``fresh``; ``fresh_on_device``
        is the executor's, once the dispatch is back). What the cache holds
        and a dispatch over it reads is the executor's model's to say."""
        held = self.lengths[mask]
        stats = {"steps": steps, "active": len(active),
                 "live_kv_tokens": int(held.sum()),
                 "pool_tokens": (self.allocator.num_pages - 1)
                 * self.page_size,
                 "live_pages": int((held // self.page_size + 1).sum()),
                 "table_slots": self.tables.size,
                 "fresh": len(self._fresh.intersection(active)),
                 "fresh_on_device": 0}
        if self._model_counts is not None:
            stats.update(self._model_counts(held, steps))
        return stats

    def _block_size(self, owed: Sequence[int] = ()) -> int:
        """Steps safely runnable as one compiled block: no slot may finish
        early (wasted work), no eos can fire unseen (eos requests decode
        step-by-step), and page growth for the whole horizon must be
        coverable up front. Rounded down to a power of two so the engine
        compiles at most log2(decode_block)+1 block shapes. The slots in
        ``owed`` are still owed their prefill's token, which comes out of
        their budget before the block does."""
        if self.decode_block <= 1:
            return 1
        active = self.active_slots
        reqs = [self.slots[s] for s in active]
        if any(r.eos_token_id is not None for r in reqs):
            return 1
        remaining = min(r.max_new_tokens - len(r.tokens) - (s in owed)
                        for s, r in zip(active, reqs))
        k = 1
        while k * 2 <= min(remaining, self.decode_block):
            k *= 2
        while k > 1 and k in self._quarantined_blocks:
            k //= 2  # shapes that keep failing dispatch are off the menu
        return k

    def step(self) -> int:
        """Expire blown deadlines, admit what fits, then run one decode step
        (or one safe decode BLOCK, or — with a drafter armed — one
        speculative verify window) over the slot array. Returns tokens
        produced."""
        with trace.step_span(trace.SERVE_STEP, self.steps):
            with trace.span(trace.SERVE_HOUSEKEEPING):
                self._maybe_tenant_flood()
                if self.brownout is not None:
                    self._brownout_tick()
                self._sweep_deadlines()
                if self.page_fingerprints:
                    # scan BEFORE admission so a rotted page is quarantined
                    # before this step's admissions could borrow it
                    self._integrity_scan()
            self._fresh.clear()
            self._admit()
            if not self.active_slots:
                return 0
            if self.drafter is not None:
                produced = self._spec_step()
                if produced is not None:
                    return produced
                # no slot had a draftable history this step: fall back to the
                # plain decode path (speculation must never cost a step)
                self.spec_stats["fallback_steps"] += 1
            return self._decode_step()

    def _brownout_tick(self) -> None:
        """Poll the degradation ladder; on a transition, record the typed
        ``tier_brownout`` event, apply the stage's mechanics, and prove
        page conservation (every ladder transition is a recovery action)."""
        stage = self.brownout.decide(self.clock())
        if stage == self.brownout_stage:
            return
        prev, self.brownout_stage = self.brownout_stage, stage
        if stage >= 2 and prev < 2:
            # clamp_batch: cap the EXISTING batch backlog's generation
            # budget so it drains capacity back faster (new batch work is
            # already shed at stage >= 1). Never below what is already
            # generated — a clamped running request simply finishes now.
            for req in list(self.queue) + [self.slots[s]
                                           for s in self.active_slots]:
                if req is None or req.tier != "batch":
                    continue
                clamp = self.tiers["batch"].brownout_max_new
                if clamp is not None and req.max_new_tokens > clamp:
                    req.max_new_tokens = max(clamp, len(req.tokens), 1)
        self._record("tier_brownout", value=float(stage), stage=stage,
                     stage_name=BROWNOUT_STAGES[stage], prev=prev,
                     direction="enter" if stage > prev else "exit")
        self._audit_after_recovery("tier_brownout")

    def _maybe_tenant_flood(self) -> None:
        """Noisy-neighbor chaos: an armed ``FaultPlan.tenant_flood_at``
        injects a one-shot burst of batch-tier submissions from one tenant
        through the REAL ``submit()`` path at this step."""
        burst = serving_tenant_flood(self.steps)
        if burst is None:
            return
        vocab = max(int(burst["vocab"]), 2)
        p_len = max(int(burst["prompt_tokens"]), 1)
        for i in range(int(burst["requests"])):
            prompt = (np.arange(1, p_len + 1, dtype=np.int32)
                      * (i + 3)) % (vocab - 1) + 1
            self.submit(Request(prompt=prompt.astype(np.int32),
                                max_new_tokens=int(burst["max_new"]),
                                tenant_id=burst["tenant_id"],
                                tier="batch"))
        self._record("tenant_flood", value=float(burst["requests"]),
                     requests=int(burst["requests"]),
                     tenant_id=burst["tenant_id"])

    def _spec_step(self) -> Optional[int]:
        """One speculation window: draft up to k tokens per active slot,
        verify k+1 positions in ONE dispatch (in-program longest-prefix
        greedy acceptance + accepted-prefix KV commit), apply the accepted
        tokens. Returns tokens produced, or None when no slot produced a
        draft (caller falls back to plain decode)."""
        k = self._spec_ctl.k
        W = k + 1
        drafts: Dict[int, np.ndarray] = {}
        for slot in self.active_slots:
            req = self.slots[slot]
            try:
                d = np.asarray(self.drafter.draft(
                    slot, req.rid, np.asarray(req.prompt, np.int32),
                    req.tokens, k), np.int32)[:k]
            except Exception as e:  # a broken drafter must not stop serving
                self._record("drafter_error",
                             error=f"{type(e).__name__}: {e}"[:200])
                d = np.empty(0, np.int32)
            drafts[slot] = d
        if not any(len(d) for d in drafts.values()):
            return None
        # page growth for each slot's commit horizon (never beyond its
        # remaining budget — commits are budget-truncated in-program),
        # preempting newest-first under pool pressure like the block path
        with trace.span(trace.SERVE_GROW):
            for slot in list(self.active_slots):
                req = self.slots[slot]
                if req is None:
                    continue
                horizon = max(min(W, req.max_new_tokens - len(req.tokens)), 1)
                while not self._ensure_page(slot, horizon=horizon):
                    victim = max(self.active_slots, key=self._victim_key)
                    self._preempt(victim)
                    if victim == slot:
                        break
        active = self.active_slots
        if not active:
            return 0
        win = np.zeros((self.num_slots, W), np.int32)
        eos = np.full(self.num_slots, -1, np.int32)
        budget = np.zeros(self.num_slots, np.int32)
        offered: Dict[int, int] = {}
        for slot in active:
            req = self.slots[slot]
            win[slot, 0] = self.next_input[slot]
            d = drafts.get(slot, np.empty(0, np.int32))
            win[slot, 1:1 + len(d)] = d
            offered[slot] = len(d)
            if req.eos_token_id is not None:
                eos[slot] = req.eos_token_id
            budget[slot] = req.max_new_tokens - len(req.tokens)
        mask = np.zeros(self.num_slots, bool)
        mask[active] = True
        verifying = trace.span(trace.SERVE_DECODE,
                               lambda: self._decode_stats(W, active, mask))
        try:
            with verifying:
                outs, n_acc = self._dispatch(
                    "verify", self.executor.verify, win, self.tables.copy(),
                    self.lengths.copy(), mask, eos, budget)
        except _DispatchFailure as fail:
            # nothing was committed (the injected raise fires before the
            # executor call): every slot requeues with exactly its tokens,
            # the healed rerun is greedy-identical — mid-window preemption
            # is the same kept-token contract as mid-block
            self._on_dispatch_episode_failed(fail, active)
            return 0
        outs = np.asarray(outs)
        n_acc = np.asarray(n_acc)
        self.steps += 1
        produced = 0
        step_offered = step_accepted = 0
        with trace.span(trace.SERVE_COMMIT):
            for slot in active:
                req = self.slots[slot]
                if req is None or req.state is not RequestState.RUNNING:
                    continue
                n = int(n_acc[slot])
                self.lengths[slot] += n   # the n accepted inputs' KV is cached
                acc_drafts = max(n - 1, 0)
                dr = offered[slot]
                req.spec_drafted += dr
                req.spec_accepted += min(acc_drafts, dr)
                step_offered += dr
                step_accepted += min(acc_drafts, dr)
                if dr:
                    if acc_drafts >= dr:
                        self.spec_stats["full_accept_windows"] += 1
                    elif acc_drafts == 0:
                        self.spec_stats["full_reject_windows"] += 1
                for i in range(n):
                    req.tokens.append(int(outs[slot, i]))
                    produced += 1
                if n:
                    self.next_input[slot] = req.tokens[-1]
                if req.done:
                    self._finish(slot)
        self.spec_stats["windows"] += 1
        self.spec_stats["drafted"] += step_offered
        self.spec_stats["accepted"] += step_accepted
        self.spec_stats["committed_tokens"] += produced
        self._spec_ctl.observe(step_offered, step_accepted)
        # the per-step ledger row the fleet autoscaler's summarize_events
        # merges: accept_rate + tokens_per_dispatch + drafter kind
        self._record(
            "spec_window", value=float(produced), k=k,
            drafted=step_offered, accepted=step_accepted,
            accept_rate=round(step_accepted / max(step_offered, 1), 4),
            tokens_per_dispatch=produced,
            drafter=self.spec_stats["drafter"])
        return produced

    def _decode_step(self) -> int:
        block = self._block_size()
        # page growth for the block horizon, preempting newest-first under
        # pool pressure
        with trace.span(trace.SERVE_GROW):
            for slot in list(self.active_slots):
                if self.slots[slot] is None:
                    continue
                while not self._ensure_page(slot, horizon=block):
                    # newest-admitted work yields FIRST, including the
                    # growing slot itself, so an old request is never evicted
                    # by a younger grower (oldest work always completes). With
                    # tiers armed, batch slots are sacrificed before
                    # interactive ones (newest-first within a tier)
                    victim = max(self.active_slots, key=self._victim_key)
                    self._preempt(victim)
                    if victim == slot:
                        break
        active = self.active_slots
        if not active:
            return 0
        block = min(block, self._block_size())  # preemption may shrink it
        mask = np.zeros(self.num_slots, bool)
        mask[active] = True
        decoding = trace.span(trace.SERVE_DECODE,
                              lambda: self._decode_stats(block, active, mask))
        try:
            with decoding:
                out = np.asarray(self._dispatch(
                    "decode", self.executor.decode, self.next_input.copy(),
                    self.tables.copy(), self.lengths.copy(), mask,
                    steps=block))
                said = getattr(self.executor, "decode_said", None)
                if said:    # what the dispatch said of itself, now it is back
                    decoding.set_metadata(**said)
        except _DispatchFailure as fail:
            # no token from this episode was observed: every active slot
            # requeues with exactly the tokens it had, so the healed rerun
            # is greedy-identical to a fault-free one
            self._on_dispatch_episode_failed(fail, active, block=block)
            return 0
        if out.ndim == 1:  # simple executors may return a flat SINGLE step
            if block != 1:
                raise ValueError(
                    f"executor returned a flat token vector for a "
                    f"{block}-step decode block; multi-step decode must "
                    f"return [steps, num_slots]")
            out = out[None]
        self.steps += 1
        produced = 0
        with trace.span(trace.SERVE_COMMIT):
            for k in range(block):
                for slot in active:
                    req = self.slots[slot]
                    if req is None or req.state is not RequestState.RUNNING:
                        continue
                    self.lengths[slot] += 1  # input token's KV now cached
                    tok = int(out[k, slot])
                    req.tokens.append(tok)
                    self.next_input[slot] = tok
                    produced += 1
                    if req.done:
                        self._finish(slot)
        return produced

    def run_to_completion(self, max_steps: int = 100_000) -> None:
        """Drain queue + slots (closed-loop; the open-loop driver lives in
        ``serving.bench``)."""
        for _ in range(max_steps):
            if self.idle:
                return
            self.step()
        raise RuntimeError(f"scheduler did not drain in {max_steps} steps")
