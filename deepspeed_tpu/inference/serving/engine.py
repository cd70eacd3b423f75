"""Serving engine: bucketed chunked prefill + fixed-slot paged decode.

The device half of the continuous-batching stack (the host half is
``scheduler.ContinuousBatchingScheduler``). Three compiled program families,
each with a bounded shape set:

- **decode** — ONE program: ``models/gpt.paged_decode_step`` over the fixed
  decode slot array [num_slots], greedy-sampled in-program. Every serving
  step replays this executable regardless of which requests occupy the
  slots; nothing about request arrival order can cause a recompile.
- **prefill** — one program per chunk bucket (powers of two up to
  ``prefill_chunk``): the prompt streams through in fixed-size chunks, so
  prompt length changes the chunk COUNT, not the compiled shapes. Where
  attention is plain and pools and weights are dense (``_chunk_to_pages``)
  a chunk goes straight into the request's pages and reads the chunks
  before it back from them (``paged_prefill_step(chunk=)``), its position
  a traced scalar; the last chunk returns the first token. Elsewhere
  (latent rows, key-value heads, quantized pools or weights, tp) the
  chunks fill a dense scratch cache through the contiguous-cache forward
  and never touch the page pool until the final scatter. A prompt of at
  most one chunk goes straight to pages too (``paged_prefill_step``);
  several of them admitted in one cycle share a [rows, chunk] dispatch
  whose row count is bucketed as the chunk is (``_batch_rows``).
- **scatter** — one program, after the dense chunks only:
  ``write_prompt_kv`` placing the prefilled
  dense K/V into the request's pages, a cache layer at a time and a
  [page piece, Dh] block a head with every index named
  (``gpt._write_prompt_pages``), so the donated pool is updated where it
  lies and the program holds no copy of it; a window layer's last rows go
  into the ring of the request's decode slot (``gpt._write_ring``), a
  state-keeping mixer's state and convolution window (``GPTConfig.ssm``,
  ``GPTConfig.kda``) whole
  into the slot: the chunks carry them in the scratch cache, each told its
  real tokens, so that a padded tail moves neither.
- **place** — one tiny program a row bucket and one for a lone prompt: an
  admission's first tokens, still on the device, into the token vector of
  the decode dispatch the scheduler staged behind them
  (:meth:`ServingEngine.stage_decode`).

Every first build of any of these is recorded in ``compile_log`` (and the
optional monitor) — the evidence stream the
``serving/unbucketed-decode-shape`` dslint rule audits.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from ...models import gpt as gpt_mod
from ...profiling import trace
from ...utils.logging import log_dist
from .buckets import bucket_for, default_buckets, record_compile
from .model import KDA_ROUTED_PAIRS, ServedModel  # noqa: F401 (the bound's home moved)
from .paging import pages_for
from .scheduler import ContinuousBatchingScheduler

# Tokens of one admission-batch dispatch past which a wider program buys
# little: its time grows with its rows (the MXU bounds it, the read of the
# weights no longer does), so more prompts go out as more dispatches. On a
# v5e a 1.4 B model's [4, 128] takes 11.3 ms and [8, 128] 21.4: a row is 6%
# cheaper in the wider one, and every program of the ladder costs every
# start of every engine 0.3-0.6 s (PERF.md section 6, PR 35).
BATCH_TOKENS = 512

def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name from a handoff payload — including the ml_dtypes
    extension types (bfloat16) plain numpy can't look up by string."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


@dataclasses.dataclass
class _StagedDecode:
    """A decode dispatch that ``prefill_many`` enqueued behind its prefill
    programs (:meth:`ServingEngine.stage_decode`): its arguments, the token
    vector being what the host will hand ``decode`` if nothing moves between
    (filled in when the first tokens are back); what it returned, left on
    the device; and the first tokens it took from there."""

    tokens: np.ndarray
    tables: np.ndarray
    lengths: np.ndarray
    steps: int
    result: tuple               # (tokens [steps, slots], routing)
    fresh: int

    def answers(self, tokens, tables, lengths, steps: int) -> bool:
        return (steps == self.steps
                and np.array_equal(tokens, self.tokens)
                and np.array_equal(tables, self.tables)
                and np.array_equal(lengths, self.lengths))


@dataclasses.dataclass
class ServingConfig:
    """Knobs for the serving path. ``num_slots`` is the admission limit —
    pass an int you trust, or "auto" to derive it from the AOT fit ladder
    (``runtime.aot.serving_admission_limit``, compile-time verdicts only)."""

    num_slots: Union[int, str] = 4
    page_size: int = 64
    max_model_len: int = 1024           # prompt + generation bound
    num_pages: Optional[int] = None     # default: every slot can max out
    prefill_chunk: int = 128
    # quantized KV pages (docs/SERVING.md "KV quantization & prefix
    # caching"): 8 or 4 stores the pools int8/int4 with per-(head, page)
    # scales, dequantized inside the decode kernel — 2x/4x the token
    # capacity at fixed HBM vs bf16 pools (4x/8x vs fp32). None = dense.
    kv_bits: Optional[int] = None
    # copy-on-write shared-prefix page reuse: requests whose prompts begin
    # with the same page-aligned token blocks share physical pages through
    # the allocator refcounts + PrefixIndex hash chains
    enable_prefix_cache: bool = False
    # KV-page integrity (docs/RESILIENCE.md "Data integrity"): fingerprint
    # pages as they freeze behind the write frontier (prefix registration,
    # handoff staging) and verify at every trust boundary — prefix share,
    # handoff import, recovery audits, plus a budgeted background sweep of
    # pages_scan_per_step stamped pages per scheduler step. A mismatch
    # evicts the page and re-prefills its borrowers (greedy-identical heal)
    page_fingerprints: bool = False
    pages_scan_per_step: int = 1
    # decode block: when no scheduling event (admission, page growth, eos,
    # slot finish) can occur within the next K steps, the scheduler runs K
    # decode steps as ONE compiled scan — K-1 host round-trips saved per
    # block. Must be <= page_size (inactive slots park on the sink page for
    # at most one page worth of steps).
    decode_block: int = 4
    # ---- speculative decoding (docs/SERVING.md "Speculative decoding"):
    # a drafter proposes up to spec_k tokens per slot; one paged verify
    # dispatch scores k+1 positions; longest-prefix GREEDY acceptance
    # commits only the confirmed prefix, so speculation provably never
    # changes outputs. spec_k is the ladder CEILING — the adaptive
    # controller collapses k toward 1 when drafts stop landing.
    spec_drafter: Optional[str] = None       # None | "ngram" | "draft_model"
    spec_k: int = 4
    spec_adaptive: bool = True
    spec_ngram: int = 3                      # max suffix n-gram order
    spec_draft_model: Optional[str] = None   # PRESETS name: num_slots="auto"
    #                                          HBM accounting + default draft
    # acceptance path: the engine implements greedy (temperature-0) only —
    # the invariant that makes longest-prefix acceptance output-preserving.
    # A nonzero temperature with a drafter armed is the misconfiguration
    # the dslint rule flags (sampled acceptance needs rejection sampling,
    # which nothing here implements).
    sampling_temperature: float = 0.0
    dtype: str = "bfloat16"
    kernel_impl: Optional[str] = None   # None=auto | "kernel" | "gather"
    # ---- tensor-parallel replica (docs/SERVING.md "Tensor parallel &
    # disaggregation"): tp > 1 shards the weight stacks, paged pools and
    # every serving program across the first `tp` devices of a dedicated
    # ("tp",) mesh (inference/serving/tp.py). The scheduler, page
    # allocator, speculation and chaos machinery are mesh-oblivious; tp2
    # output is greedy-identical to tp1.
    tp: int = 1
    # ---- disaggregated prefill/decode role. "both" (default) = the fused
    # single-replica engine; "prefill" = fill pages + first token, then
    # hand the request off (scheduler HANDOFF state -> fleet forwarding);
    # "decode" = accept page-handoff admissions. Roles gate which program
    # families warm up eagerly — the rest stay lazily compilable so
    # failover (a decode replica re-prefilling an orphaned request) still
    # works, it just pays a mid-traffic compile.
    role: str = "both"
    eos_token_id: Optional[int] = None
    model_name: Optional[str] = None    # for num_slots="auto"
    # ---- overload control + deadlines (docs/SERVING.md "Overload &
    # failure"). All default OFF — the overload-unsafe default the dslint
    # rule `serving/unbounded-admission` warns about; production configs
    # should arm max_queue (and usually deadlines).
    max_queue: Optional[int] = None          # admission queue depth cap
    max_queued_tokens: Optional[int] = None  # queued-work token budget
    shed_policy: str = "reject_newest"       # or "reject_largest"
    ttft_deadline_s: Optional[float] = None  # default per-request deadlines
    request_deadline_s: Optional[float] = None
    # ---- SLO tiers / multi-tenancy (docs/SERVING.md "Multi-tenancy & SLO
    # tiers"). Default OFF: tiers=None keeps the scheduler's FIFO queue and
    # seed-identical behavior. tiers=True (or "default") arms the built-in
    # interactive/standard/batch ladder; a mapping of TierConfig/dict
    # overrides merges over the defaults. tenants maps tenant_id to a
    # TenantConfig / dict / bare tier name. Both are validated eagerly in
    # ServingEngine.__init__ via tenancy.resolve_tiers / resolve_tenants.
    tiers: Union[None, bool, str, dict] = None
    tenants: Optional[dict] = None
    # degradation-ladder (brownout) controller knobs — only read when tiers
    # are armed; see tenancy.BrownoutConfig for semantics
    brownout_window_s: float = 5.0
    brownout_enter_shed_rate: float = 0.25
    brownout_enter_misses: int = 2
    brownout_exit_shed_rate: float = 0.05
    brownout_min_dwell_s: float = 1.0
    # ---- dispatch fault recovery
    dispatch_retries: int = 2
    quarantine_after: int = 2                # failures before a decode block
    #                                          shape is quarantined
    dispatch_failure_budget: int = 8         # consecutive failed episodes
    #                                          before ServingFaultError
    prefill_deadline_s: Optional[float] = None  # watchdog phase deadlines
    decode_deadline_s: Optional[float] = None
    watchdog_poll_s: float = 0.25
    stacks_dir: Optional[str] = None         # stall stack dumps land here

    @property
    def pages_per_seq(self) -> int:
        return pages_for(self.max_model_len, self.page_size)

    @property
    def spec_k_set(self) -> tuple:
        """The bounded draft-length ladder (compile one verify program per
        entry; empty when no drafter is configured)."""
        if not self.spec_drafter:
            return ()
        from .speculate import spec_k_ladder

        return spec_k_ladder(self.spec_k)

    @property
    def overload_armed(self) -> bool:
        """Whether ANY admission bound or deadline protects this config —
        what the ``serving/unbounded-admission`` rule checks."""
        return (self.max_queue is not None
                or self.max_queued_tokens is not None
                or self.ttft_deadline_s is not None
                or self.request_deadline_s is not None)

    @property
    def tiers_armed(self) -> bool:
        """Whether SLO-tier scheduling is configured — what the
        ``serving/untiered-multi-tenant`` rule checks when it sees multiple
        tenant_ids in the submit evidence."""
        return bool(self.tiers)

    def resolved_tiers(self):
        """Validated (tiers, tenants, brownout) triple for the scheduler —
        (None, {}, None) when tiers are unarmed."""
        from .tenancy import (BrownoutConfig, resolve_tenants, resolve_tiers)

        tiers = resolve_tiers(self.tiers)
        tenants = resolve_tenants(self.tenants, tiers)
        brownout = None
        if tiers is not None:
            brownout = BrownoutConfig(
                window_s=float(self.brownout_window_s),
                enter_shed_rate=float(self.brownout_enter_shed_rate),
                enter_misses=int(self.brownout_enter_misses),
                exit_shed_rate=float(self.brownout_exit_shed_rate),
                min_dwell_s=float(self.brownout_min_dwell_s))
        return tiers, tenants, brownout


class ServingEngine:
    """Executor over a GPT config + params (see module docstring)."""

    def __init__(self, cfg: gpt_mod.GPTConfig, params,
                 serving: Optional[ServingConfig] = None, monitor=None,
                 draft=None):
        self.cfg = cfg
        self.serving = serving or ServingConfig()
        self.monitor = monitor
        self.compile_log: List[dict] = []
        # draft-model speculation: (GPTConfig, params) of the SMALL model
        # that proposes tokens for this engine's target (speculate.py)
        self.draft = draft
        s = self.serving
        if s.max_model_len > cfg.max_seq_len and not (cfg.rotary or cfg.alibi):
            raise ValueError(
                f"max_model_len {s.max_model_len} exceeds the model's learned "
                f"position table ({cfg.max_seq_len})")
        if s.sampling_temperature:
            raise NotImplementedError(
                "serving programs sample greedily (temperature 0) — the "
                "invariant speculative acceptance relies on; "
                f"sampling_temperature={s.sampling_temperature} is not "
                "implemented")
        if s.spec_drafter and not (1 <= s.spec_k <= 16):
            raise ValueError(f"spec_k {s.spec_k} outside [1, 16]")
        if s.role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be both|prefill|decode, got "
                             f"{s.role!r}")
        # what the engine and its scheduler know of the model (model.py): of a
        # tensor-parallel replica the same answers over a ("tp",) mesh (tp.py).
        # The ladder's floor of two rows makes a [2, prefill_chunk] dispatch
        self.tp_context = None
        if int(s.tp or 1) > 1:
            from .tp import TPContext

            self.tp_context = TPContext(cfg, s)
        self.model = self.tp_context or ServedModel(cfg, s)
        self.model.check(max(BATCH_TOKENS, 2 * s.prefill_chunk))
        # tier/tenant specs fail fast at engine construction, not first
        # submit — resolved_tiers() raises on malformed configs
        s.resolved_tiers()
        self.num_slots = self._resolve_slots()
        self.num_pages = (s.num_pages if s.num_pages is not None
                          else self.num_slots * s.pages_per_seq + 1)
        self.dtype = self.model.dtype
        self.params, self.paged_cache = self.model.place(
            params, self.num_pages, self.num_slots)
        self._chunk_to_pages = self.model.chunk_to_pages
        # the residual stream at cfg.state_layers from the last prefill (one
        # array a dispatch, [rows, boundaries, tokens, d]) and the last
        # decode dispatch ([steps, slots, boundaries, d]): outputs of the
        # programs that filled the pages, left on the device; the benchmark's
        # segmented comparison reads them (benchmark/families)
        self.prefill_states: list = []
        self.decode_states = None
        # what every program dispatched so far said of itself when it was
        # traced (model.program_counts), by (program, rows); and of the last
        # decode dispatch (decode_said): its program's, a routed model's
        # counts of it, int [steps, 4] (gpt.routing_of; 5 where the router
        # also scores zero-compute experts: they ride the tokens' fetch), and
        # the first tokens it took before the host had read them
        self._said: dict = {}
        self.decode_grouped: dict = {}
        self.decode_routing = None
        self.decode_fresh_on_device = 0
        self.decode_counts = self.model.decode_counts
        # the decode dispatch the scheduler staged behind its next admission
        # (stage_decode): what will say its arguments, waiting for
        # prefill_many; the dispatch prefill_many enqueued, waiting for decode
        self._stage_args: Optional[Callable[[], Optional[tuple]]] = None
        self._staged: Optional[_StagedDecode] = None
        log_dist(self.model.describe(self.num_pages, self.num_slots))
        self.last_scheduler = None  # most recent make_scheduler product —
        # the capacity-pressure evidence dslint's dense-kv-at-capacity reads
        # prefill's contiguous scratch cache: chunks append at chunk-aligned
        # positions, so it must cover the bucket-padded context
        chunks = -(-s.max_model_len // s.prefill_chunk)
        self._dense_S = chunks * s.prefill_chunk
        self._chunk_buckets = default_buckets(
            min(32, s.prefill_chunk), s.prefill_chunk)
        if not (1 <= s.decode_block <= s.page_size):
            raise ValueError(f"decode_block {s.decode_block} must be in "
                             f"[1, page_size={s.page_size}]")
        self._prefill_fns = {}
        self._prefill_paged_fns = {}
        self._prefill_fused_fns = {}
        self._prefill_batch_fns = {}
        self._batch_ladders = {}    # chunk bucket -> its built row buckets
        self._decode_fns = {}
        self._place_fns = {}
        self._verify_fns = {}
        self._scatter_fn = None

    def _resolve_slots(self) -> int:
        s = self.serving
        if s.num_slots != "auto":
            return int(s.num_slots)
        if not s.model_name:
            raise ValueError("num_slots='auto' needs model_name for the AOT "
                             "fit ladder")
        from ...runtime.aot import serving_admission_limit

        # kv_bits reaches the fit ladder: the compiled probe serves from
        # quantized pools, so "auto" sizes slots from the KV bytes the pool
        # ACTUALLY holds (a dense-page ladder under-admits ~2x at int8).
        # With speculation armed, the drafter's HBM (draft params + dense
        # draft cache + k-token verify activations) is charged against the
        # same budget so "auto" stays honest (aot.speculation_hbm_bytes).
        draft_model = None
        if s.spec_drafter:
            # price the draft model that will ACTUALLY be resident: an
            # explicit draft=(cfg, params) pair wins over the preset name
            draft_model = (self.draft[0] if self.draft is not None
                           else s.spec_draft_model)
        # tp + role reach the ladder too: a tp replica's per-chip HBM holds
        # 1/tp of the weights and pools, and a prefill-only replica never
        # pays the drafter/verify residency (aot prices per-role program
        # sets since PR 16)
        limit = serving_admission_limit(
            s.model_name, prompt=min(128, s.max_model_len),
            gen=min(128, s.max_model_len), kv_bits=s.kv_bits or 0,
            page_size=s.page_size, draft_model=draft_model,
            spec_k=(s.spec_k if s.spec_drafter else 0),
            spec_max_len=s.max_model_len, tp=int(s.tp or 1), role=s.role)
        if limit["max_slots"] < 1:
            raise ValueError(
                f"AOT fit ladder found no decode batch that fits for "
                f"{s.model_name}: {limit}")
        log_dist(f"serving: admission limit {limit['max_slots']} slots "
                 f"(AOT fit ladder, {s.model_name})")
        return int(limit["max_slots"])

    # -------------------------------------------------------------- programs
    def _log_compile(self, kind: str, shape: Tuple[int, ...]) -> None:
        record_compile(self.compile_log, self.monitor,
                       "Serving/compile_events", kind, shape)

    @staticmethod
    def _program(name: str, fn, donate: int):
        """``fn`` jitted under ``name``: the device's "XLA Modules" line then
        reads ``jit_<name>`` (``profiling/trace.py``)."""
        return jax.jit(trace.named(fn, name), donate_argnums=(donate,))

    def _call(self, program, *args, rows=None, span=None):
        """Dispatch ``program``; its first dispatch also enters it, with
        the arguments' shapes, in the table ``trace.program_scopes`` reads,
        and keeps what a program of its kind (its name's first word) says of
        itself (``model.program_counts``), which ``span`` then says at every
        dispatch. A program dispatched at several row counts names each as
        ``rows``: every shape is a module of its own in a trace. The
        dispatch's return ends a starvation of the device, where the last
        wait began one (``trace.fed``)."""
        said = self._said.get((program, rows))
        if said is None:
            trace.register_program(program.__name__, program, args)
            # the trace is the one the dispatch below makes: jit keeps it
            said = self._said[program, rows] = self.model.program_counts(
                program.__name__.partition("_")[0],
                program.trace(*args).jaxpr)
        if span is not None and said:
            span.set_metadata(**said)
        # whoever reads a trace asks for the scopes of the program that ran
        # in it, maybe after this engine went out of scope
        trace.hold_if_traced(program.__name__, program)
        out = program(*args)
        trace.fed(program.__name__)
        return out

    def _slot_args(self, slots) -> tuple:
        """The decode slot(s) a prefill program is told, where the cache
        keeps rings or states; nothing where it does not."""
        return (jnp.asarray(slots, jnp.int32),) if self.model.rings else ()

    def _slots_or_first(self, slots: tuple, rows: int) -> tuple:
        """A prefill program's slot argument as ``[rows]`` slots: what the
        caller named or, lowered without it (``benchmark/tools/
        compile_only.py`` hands every engine the arguments of one without
        rings), the first ``rows`` slots, which sizes the same program."""
        if not self.model.rings:
            return ()
        if not slots:
            return (jnp.arange(rows, dtype=jnp.int32),)
        return (jnp.reshape(slots[0], (rows,)),)

    def _get_prefill(self, chunk: int):
        """The chunk program of an engine whose chunks fill the dense scratch
        cache: ``real`` of the chunk's tokens are the prompt's, which
        ``ends`` among them or goes on. The head runs on the last real token
        of a prompt that ends, whose greedy next token the program returns;
        an earlier chunk's is 0. Lowered without them (``benchmark/tools/
        compile_only.py``), every token is real and the prompt ends."""
        if chunk not in self._prefill_fns:
            self._log_compile("serving_prefill", (1, chunk))

            def fn(params, ids, cache, real=None, ends=True):
                last = jnp.where(ends, (chunk if real is None else real) - 1,
                                 -1)
                logits, cache, states = self.model.forward_with_cache(
                    params, ids, cache, real, last=last[None])
                return jnp.argmax(logits[0]).astype(jnp.int32), cache, states

            self._prefill_fns[chunk] = self._program(
                f"prefill_chunk_{chunk}", fn, 2)
        return self._prefill_fns[chunk]

    def _get_prefill_to_pages(self, chunk: int):
        """The chunk program of an engine whose chunks write their own pages
        (``_chunk_to_pages``): the tokens at positions ``pos .. pos + chunk``
        of a prompt of ``length`` go through ``gpt.paged_prefill_step``,
        which writes each layer's rows into the pages ``table`` names and
        reads the chunks before them back from there. ``pos`` is traced, so
        one program a bucket serves every chunk; the prompt's last chunk
        returns its greedy next token, an earlier one 0. It carries the name
        of ``_get_prefill``'s program: an engine runs one of the two."""
        if chunk not in self._prefill_paged_fns:
            self._log_compile("serving_prefill", (1, chunk))
            align = self.serving.prefill_chunk

            def fn(params, ids, paged, table, length, start, pos, *slot):
                last, paged, states = self.model.prefill_pages(
                    params, ids, paged, table[None], length[None],
                    start[None], *self._slots_or_first(slot, 1),
                    chunk=(pos, align))
                return jnp.argmax(last[0]).astype(jnp.int32), paged, states

            self._prefill_paged_fns[chunk] = self._program(
                f"prefill_chunk_{chunk}", fn, 2)
        return self._prefill_paged_fns[chunk]

    def _get_prefill_fused(self, chunk: int):
        """Single-dispatch prefill for contexts <= one chunk: dense forward,
        page scatter, and the next-token argmax fused into one program (the
        common short-prompt admission path — 3 dispatches + a host sync
        collapse into 1)."""
        if chunk not in self._prefill_fused_fns:
            self._log_compile("serving_prefill_fused", (1, chunk))

            def fn(params, ids, paged, table, length, start, *slot):
                slot = self._slots_or_first(slot, 1)
                # start > 0: shared prefix pages already hold [0, start) —
                # never write a borrowed page (start is traced, so shared
                # and unshared admissions hit the same compiled program)
                last, paged, states = self.model.prefill_pages(
                    params, ids, paged, table[None], length[None],
                    start[None], *slot)
                return jnp.argmax(last[0]).astype(jnp.int32), paged, states

            self._prefill_fused_fns[chunk] = self._program(
                f"prefill_fused_{chunk}", fn, 2)
        return self._prefill_fused_fns[chunk]

    def _get_prefill_batch(self, chunk: int):
        """Admission-batch prefill: the short prompts admitted in one
        scheduler cycle prefill as [rows, chunk] dispatches of this one
        function, ``rows`` a bucket of ``_batch_rows(chunk)`` (any row count
        up to ``num_slots`` lowers). Rows that hold no prompt carry length
        0 + sink tables, so their writes drop."""
        if chunk not in self._prefill_batch_fns:
            def fn(params, ids, paged, tables, lengths, starts, *slots):
                last, paged, states = self.model.prefill_pages(
                    params, ids, paged, tables, lengths, starts,
                    *self._slots_or_first(slots, ids.shape[0]))
                return (jnp.argmax(last, axis=-1).astype(jnp.int32), paged,
                        states)

            self._prefill_batch_fns[chunk] = self._program(
                f"prefill_batch_{chunk}", fn, 2)
        return self._prefill_batch_fns[chunk]

    def _batch_rows(self, chunk: int) -> Tuple[int, ...]:
        """The row buckets of ``chunk``'s admission-batch program: powers of
        two from 2 while a dispatch stays within ``BATCH_TOKENS`` and
        ``num_slots``. The first call for a chunk bucket builds the whole
        ladder on the sink page before it returns, so that no number of
        short prompts in a later cycle compiles anything."""
        if chunk not in self._batch_ladders:
            ladder = self._row_ladder(chunk)
            for rows in ladder:
                self._log_compile("serving_prefill_batch", (rows, chunk))
                self._dispatch_batch(chunk, rows, ())
            self._batch_ladders[chunk] = ladder
        return self._batch_ladders[chunk]

    def _row_ladder(self, chunk: int) -> Tuple[int, ...]:
        """Powers of two from 2 while ``rows x chunk`` stays within
        ``BATCH_TOKENS`` and ``rows`` within ``num_slots``."""
        return tuple(b for b in default_buckets(
            2, max(2, BATCH_TOKENS // chunk)) if b <= self.num_slots)

    def _dispatch_batch(self, chunk: int, rows: int, group, span=None):
        """One [rows, chunk] dispatch of ``group``'s prompts, a row each from
        the top; the rows beyond keep length 0 and the sink table. Returns
        (first tokens [rows], states), both left on the device."""
        ids = np.zeros((rows, chunk), np.int32)
        tables = np.zeros((rows, self.serving.pages_per_seq), np.int32)
        lengths = np.zeros(rows, np.int32)
        starts = np.zeros(rows, np.int32)
        slots = np.zeros(rows, np.int32)
        for j, (slot, t, row, start) in enumerate(group):
            ids[j, :len(t)] = t
            tables[j] = row
            lengths[j] = len(t)
            starts[j] = start
            slots[j] = slot
        toks, self.paged_cache, states = self._call(
            self._get_prefill_batch(chunk), self.params, jnp.asarray(ids),
            self.paged_cache, jnp.asarray(tables), jnp.asarray(lengths),
            jnp.asarray(starts), *self._slot_args(slots), rows=rows,
            span=span)
        return toks, states

    def _get_decode(self, steps: int = 1):
        """The decode program for a ``steps``-long block (the scheduler uses
        only 1 and ``decode_block``, so at most two shapes compile)."""
        if steps not in self._decode_fns:
            self._log_compile("serving_decode", (steps, self.num_slots))
            impl = self.serving.kernel_impl

            def one(cache, toks, tables, lengths, params):
                logits, cache, states, routing = self.model.decode_step(
                    params, toks, cache, tables, lengths, impl)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32), cache,
                        (states, routing))

            if steps == 1:
                def fn(params, cache, toks, tables, lengths):
                    nxt, cache, (states, routing) = one(
                        cache, toks, tables, lengths, params)
                    return nxt[None], cache, states[None], routing[None]
            else:
                def fn(params, cache, toks, tables, lengths):
                    def body(carry, _):
                        toks, lengths, cache = carry
                        nxt, cache, rest = one(cache, toks, tables,
                                               lengths, params)
                        # a slot that holds no request stays at length 0
                        lengths = jnp.where(lengths > 0, lengths + 1, 0) \
                            if rest[1].size else lengths + 1
                        return (nxt, lengths, cache), (nxt,) + rest

                    (_, _, cache), (out, states, routing) = jax.lax.scan(
                        body, (toks, lengths, cache), None, length=steps)
                    return out, cache, states, routing

            self._decode_fns[steps] = self._program(
                f"decode_block_{steps}", fn, 1)
        return self._decode_fns[steps]

    def _get_place(self, rows: int):
        """The program that puts ``rows`` first tokens, left on the device by
        the prefill program that sampled them, into the token vector of a
        staged decode dispatch: row ``j`` into slot ``slots[j]``, a row whose
        slot is ``num_slots`` (a batch row that held no prompt) nowhere.
        ``rows`` 1 takes a lone prompt's scalar."""
        if rows not in self._place_fns:
            self._log_compile("serving_place", (rows, self.num_slots))

            def fn(toks, firsts, slots):
                return toks.at[slots].set(jnp.reshape(firsts, (rows,)),
                                          mode="drop")

            self._place_fns[rows] = self._program(
                f"place_first_{rows}", fn, 0)
        return self._place_fns[rows]

    def _place(self, toks, firsts, slots):
        """One dispatch of the place program for ``len(slots)`` rows."""
        program = self._get_place(len(slots))
        toks = program(toks, firsts, slots)
        trace.fed(program.__name__)
        return toks

    def _warm_place(self) -> None:
        """Build every shape of the place program, once, before a staged
        step needs one: a lone prompt's and one a row bucket that any chunk
        bucket's admission batch can have (the ladder of the smallest chunk
        bucket holds the others'). No number of prompts in a later cycle
        then compiles anything."""
        if self._place_fns:
            return
        toks = jnp.zeros(self.num_slots, jnp.int32)
        for rows in (1,) + self._row_ladder(min(self._chunk_buckets)):
            toks = self._place(
                toks, jnp.zeros((rows,) if rows > 1 else (), jnp.int32),
                np.full(rows, self.num_slots, np.int32))

    def _get_verify(self, W: int):
        """The speculative verification program for a ``W``-token window
        (W = k+1, k from the bounded ``spec_k_set`` ladder — at most
        log2(spec_k)+1 shapes ever compile). One dispatch: score every
        window position over the paged pool
        (``models/gpt.paged_verify_step``), greedy longest-prefix
        acceptance (truncated at a per-row eos and the remaining max_new
        budget) computed IN-program, and the accepted prefix's KV committed
        with sequential-append semantics (``commit_window_kv``). Returns
        (outputs [slots, W], n_accept [slots]) — n_accept counts both the
        tokens to append and the cache-length advance (they are equal by
        construction)."""
        if W not in self._verify_fns:
            self._log_compile("serving_verify", (W, self.num_slots))
            impl = self.serving.kernel_impl

            def fn(params, cache, toks, tables, lengths, eos, budget):
                logits, win_k, win_v = self.model.verify_step(
                    params, toks, cache, tables, lengths, impl)
                outs = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # longest-prefix greedy acceptance: draft i (toks[:, i+1])
                # survives iff it equals the target's output at position i
                # AND every earlier draft survived
                agree = (toks[:, 1:] == outs[:, :-1]).astype(jnp.int32)
                n = 1 + jnp.sum(jnp.cumprod(agree, axis=1), axis=1)
                # an accepted eos ends the request AT that token
                is_eos = (outs == eos[:, None]) & (eos[:, None] >= 0)
                eos_pos = jnp.argmax(is_eos, axis=1)
                n = jnp.where(jnp.any(is_eos, axis=1),
                              jnp.minimum(n, eos_pos + 1), n)
                # never accept past max_new (budget 0 = inactive slot:
                # nothing commits, nothing is written anywhere)
                n = jnp.clip(n, 0, jnp.maximum(budget, 0))
                cache = self.model.commit_window(cache, win_k, win_v,
                                                 tables, lengths, n)
                return outs, n, cache

            self._verify_fns[W] = self._program(f"verify_w{W}", fn, 1)
        return self._verify_fns[W]

    def _get_scatter(self):
        if self._scatter_fn is None:
            self._log_compile("serving_scatter", (self._dense_S,))

            def fn(paged, dense, table, length, start, *slot):
                return self.model.write_prompt(
                    paged, dense, table, length, start,
                    *(s[0] for s in self._slots_or_first(slot, 1)))

            self._scatter_fn = self._program("scatter", fn, 0)
        return self._scatter_fn

    # -------------------------------------------------------------- executor
    def prefill(self, slot: int, tokens: np.ndarray,
                table_row: np.ndarray, start: int = 0) -> int:
        """Chunked prefill of one request's context; writes its KV into the
        slot's pages (and, where window layers keep rings, the last rows of
        those layers into decode slot ``slot``'s ring); returns the greedy
        next token. ``start`` > 0 skips the
        scatter of positions [0, start) — those live in shared prefix pages
        the request only borrows (the forward still computes the full
        context; sharing saves pages, not prefill FLOPs)."""
        self._drop_stage()
        tok = self._enqueue_prefill(slot, tokens, table_row, start)
        with trace.span(trace.ENGINE_PREFILL_SAMPLE) as wait:
            tok = int(tok)
        trace.drained(wait)     # nothing is queued behind one prompt
        return tok

    def _enqueue_prefill(self, slot: int, tokens: np.ndarray,
                         table_row: np.ndarray, start: int = 0):
        """:meth:`prefill` up to its last dispatch: the greedy next token as
        the device will hold it, not waited for."""
        s = self.serving
        tokens = np.asarray(tokens, np.int32)
        T = int(tokens.shape[0])
        if T < 1 or T > s.max_model_len:
            raise ValueError(f"context length {T} outside (0, "
                             f"{s.max_model_len}]")
        if T <= s.prefill_chunk:  # fused short-prompt path: one dispatch
            chunk = bucket_for(T, self._chunk_buckets)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :T] = tokens
            with trace.span(trace.ENGINE_PREFILL_FUSED, lambda: {
                    "real_tokens": T, "padded_tokens": chunk,
                    "head_tokens": 1}) as span:
                tok, self.paged_cache, states = self._call(
                    self._get_prefill_fused(chunk),
                    self.params, jnp.asarray(ids), self.paged_cache,
                    jnp.asarray(table_row, jnp.int32), jnp.int32(T),
                    jnp.int32(start), *self._slot_args(slot), span=span)
            self.prefill_states = [states]
            return tok
        paged = self._chunk_to_pages
        if paged:   # host values: they ride each chunk's own dispatch
            table, scalars = np.asarray(table_row, np.int32), (
                np.int32(T), np.int32(start))
        else:
            with trace.span(trace.ENGINE_PREFILL_SCRATCH):
                cache = self.model.dense_cache(1, self._dense_S)
        pos = 0
        self.prefill_states = []
        while pos < T:
            rem = T - pos
            chunk = (s.prefill_chunk if rem >= s.prefill_chunk
                     else bucket_for(rem, self._chunk_buckets))
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :min(rem, chunk)] = tokens[pos:pos + chunk]
            ends = rem <= chunk     # the head runs where the prompt ends
            with trace.span(trace.ENGINE_PREFILL_CHUNK, lambda: {
                    "real_tokens": min(rem, chunk), "padded_tokens": chunk,
                    "paged_tokens": chunk if paged else 0,
                    "head_tokens": int(ends)}) as span:
                if paged:   # the chunk into its pages; the last one's token
                    tok, self.paged_cache, states = self._call(
                        self._get_prefill_to_pages(chunk), self.params, ids,
                        self.paged_cache, table, *scalars, np.int32(pos),
                        *self._slot_args(slot), span=span)
                else:
                    tok, cache, states = self._call(
                        self._get_prefill(chunk), self.params, ids, cache,
                        np.int32(min(rem, chunk)), np.bool_(ends), span=span)
            self.prefill_states.append(states)
            pos += chunk
        if not paged:
            with trace.span(trace.ENGINE_PREFILL_SCATTER):
                self.paged_cache = self._call(
                    self._get_scatter(), self.paged_cache, cache,
                    jnp.asarray(table_row, jnp.int32), jnp.int32(T),
                    jnp.int32(start), *self._slot_args(slot))
        return tok

    def prefill_many(self, items) -> dict:
        """Prefill one admission cycle's requests: short prompts (<= one
        chunk) share [rows, chunk] dispatches, ``rows`` the bucket of
        ``_batch_rows`` that holds them (more than the top bucket go out as
        several dispatches of it); longer prompts take the serial chunked
        path. The cycle's first tokens are fetched once, after its last
        dispatch; only a prompt that keeps the dense scratch cache is waited
        for where it ends, so that two such caches never coexist. A decode
        dispatch staged for this cycle (:meth:`stage_decode`) is enqueued
        behind the cycle's last prefill program, before that fetch.
        ``items``: [(slot, tokens, table_row)] or
        [(slot, tokens, table_row, start)] (shared-prefix admissions);
        returns {slot: first_token}."""
        s = self.serving
        stage_args = self._drop_stage()
        out = {}
        items = [(it[0], np.asarray(it[1], np.int32), it[2],
                  int(it[3]) if len(it) > 3 else 0) for it in items]
        short = [it for it in items if len(it[1]) <= s.prefill_chunk]
        alone = {}      # slot -> first token, on the device
        for slot, t, row, start in items:
            if len(t) > s.prefill_chunk:
                if self._chunk_to_pages:
                    alone[slot] = self._enqueue_prefill(slot, t, row, start)
                else:
                    out[slot] = self.prefill(slot, t, row, start)
        groups, firsts = [], []
        if len(short) == 1:  # no batching win; reuse the fused single path
            slot, t, row, start = short[0]
            alone[slot] = self._enqueue_prefill(slot, t, row, start)
        elif short:
            chunk = bucket_for(max(len(t) for _, t, _, _ in short),
                               self._chunk_buckets)
            ladder = self._batch_rows(chunk)
            groups = [short[at:at + ladder[-1]]
                      for at in range(0, len(short), ladder[-1])]
            self.prefill_states = []
        for group in groups:
            rows = bucket_for(len(group), ladder)
            with trace.span(trace.ENGINE_PREFILL_BATCH, lambda: {
                    "real_tokens": sum(len(t) for _, t, _, _ in group),
                    "padded_tokens": rows * chunk,
                    "head_tokens": rows}) as span:
                toks, states = self._dispatch_batch(chunk, rows, group, span)
            self.prefill_states.append(states)
            firsts.append(toks)
        # the prefill programs are queued: now the stage's arguments
        args = stage_args() if stage_args is not None else None
        staged = (self._enqueue_staged(args, out, alone, groups, firsts)
                  if args is not None else None)
        if alone or firsts:
            with trace.span(trace.ENGINE_PREFILL_SAMPLE) as wait:
                alone, firsts = jax.device_get((alone, firsts))
            if staged is None:  # else the decode is queued behind them
                trace.drained(wait)
        out.update((slot, int(tok)) for slot, tok in alone.items())
        for group, toks in zip(groups, firsts):
            for j, (slot, _, _, _) in enumerate(group):
                out[slot] = int(toks[j])
        if staged is not None:  # what the host will hand decode
            for slot, tok in out.items():
                staged.tokens[slot] = tok
            self._staged = staged
        return out

    def stage_decode(self, args: Callable[[], Optional[tuple]]) -> None:
        """Hand over, before the admission it follows, the decode dispatch
        the caller will ask for right after it. ``args`` is called by the
        next :meth:`prefill_many` once its prefill programs are queued and
        returns :meth:`decode`'s arguments ``(tokens, tables, lengths,
        active, steps)`` as they will be then, but for the tokens of the
        slots that call fills, which are not sampled yet (or None: nothing
        is staged). ``prefill_many`` enqueues the dispatch behind its last
        prefill program, those tokens taken from where the prefill programs
        left them, so the device goes from the one into the other while the
        host still waits for the first tokens; the :meth:`decode` that names
        the same arguments only fetches. A stage lasts until the next call
        of ``prefill``, ``prefill_many``, ``decode`` or ``verify``; a
        ``decode`` whose arguments differ drops it and dispatches anew (a
        decode step writes the rows of its positions again, so the redo is
        exact; a state a slot is not rewound: a scheduler stages only what
        nothing but a failed episode, which preempts every slot, can make
        differ)."""
        self._warm_place()
        self._drop_stage()
        self._stage_args = args

    def _drop_stage(self):
        """Forget what was staged; returns the arguments' source that was
        still waiting for ``prefill_many``, if any."""
        args, self._stage_args, self._staged = self._stage_args, None, None
        return args

    def _enqueue_staged(self, args: tuple, out: dict, alone: dict,
                        groups: list, firsts: list) -> _StagedDecode:
        """The staged decode dispatch behind a cycle's prefill programs:
        the host's tokens for the running slots and for the prompts already
        waited for (``out``), the rest placed from the device."""
        for tok in (*alone.values(), *firsts):
            # the first tokens start for the host now, so that they arrive
            # when their prefill ends and not when the decode does
            tok.copy_to_host_async()
        tokens, tables, lengths, _, steps = args
        tokens = np.array(tokens, np.int32)
        for slot, tok in out.items():
            tokens[slot] = tok
        with trace.span(trace.ENGINE_DECODE_ENQUEUE):
            toks = jnp.asarray(tokens)
            for slot, tok in alone.items():
                toks = self._place(toks, tok, np.full(1, slot, np.int32))
            for group, first in zip(groups, firsts):
                slots = np.full(first.shape[0], self.num_slots, np.int32)
                slots[:len(group)] = [slot for slot, _, _, _ in group]
                toks = self._place(toks, first, slots)
            result = self._enqueue_decode(toks, tables, lengths, steps)
        return _StagedDecode(
            tokens.copy(), np.asarray(tables), np.asarray(lengths),
            int(steps), result,
            fresh=len(alone) + sum(len(group) for group in groups))

    def _enqueue_decode(self, toks, tables, lengths, steps: int) -> tuple:
        """One dispatch of the decode program; (tokens, routing counts), on
        the device."""
        program = self._get_decode(steps)
        args = (self.params, self.paged_cache, toks,
                jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32))
        out, self.paged_cache, self.decode_states, routing = self._call(
            program, *args)
        return out, routing

    def decode(self, tokens: np.ndarray, tables: np.ndarray,
               lengths: np.ndarray, active: np.ndarray,
               steps: int = 1) -> np.ndarray:
        """``steps`` fixed-shape decode steps over every slot as one
        dispatch; returns [steps, num_slots] sampled tokens (inactive slots
        write to the reserved sink page and their outputs are ignored).
        Where ``prefill_many`` has enqueued this very dispatch already
        (:meth:`stage_decode`), only its tokens are fetched."""
        del active  # the program runs all slots; masking is host-side
        staged = self._staged
        self._drop_stage()
        if staged is not None and staged.answers(tokens, tables, lengths,
                                                 steps):
            (out, routing), fresh = staged.result, staged.fresh
        else:
            with trace.span(trace.ENGINE_DECODE_ENQUEUE):
                out, routing = self._enqueue_decode(
                    jnp.asarray(tokens, jnp.int32), tables, lengths, steps)
            fresh = 0
        self.decode_fresh_on_device = fresh
        self.decode_grouped = self._said[self._get_decode(steps), None]
        with trace.span(trace.ENGINE_DECODE_FETCH) as wait:
            if routing.size:    # a few ints beside the tokens, one fetch
                out, routing = jax.device_get((out, routing))
                self.decode_routing = np.asarray(routing)
            out = np.asarray(out)
        trace.drained(wait)
        return out

    @property
    def decode_said(self) -> dict:
        """What the last decode dispatch adds to its ``serve.decode`` span
        once it is back, the one thing a scheduler then reads of its executor:
        its program's counts, ``fresh_on_device``, ``trace.ROUTING_STATS``."""
        fresh, routing = self.decode_fresh_on_device, self.decode_routing
        return {**self.decode_grouped,
                **({"fresh_on_device": fresh} if fresh else {}),
                **(trace.routing_stats(routing) if routing is not None
                   else {})}

    def verify(self, tokens: np.ndarray, tables: np.ndarray,
               lengths: np.ndarray, active: np.ndarray, eos: np.ndarray,
               budget: np.ndarray):
        """Speculative verification executor call: ``tokens`` [slots, W]
        windows (verified input + drafts), per-slot ``eos`` (-1 = none) and
        remaining-budget vectors. Returns (outputs [slots, W],
        n_accept [slots]); the accepted prefix's KV is already committed."""
        del active  # the program runs all slots; masking rides budget == 0
        self._drop_stage()
        W = int(np.asarray(tokens).shape[1])
        with trace.span(trace.ENGINE_DECODE_ENQUEUE):
            outs, n, self.paged_cache = self._call(
                self._get_verify(W),
                self.params, self.paged_cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(tables, jnp.int32),
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(eos, jnp.int32), jnp.asarray(budget, jnp.int32))
        with trace.span(trace.ENGINE_DECODE_FETCH) as wait:
            outs, n = np.asarray(outs), np.asarray(n)
        trace.drained(wait)
        return outs, n

    # ----------------------------------------------- disaggregated handoff
    def export_pages(self, page_ids) -> dict:
        """Serialize the KV held in ``page_ids`` (a request's block-table
        prefix, in table order) for a prefill->decode handoff. Returns a
        payload of raw little-endian buffers per pool tensor — quantized
        pools ship their int8/int4-packed payload plus fp32 per-page scales,
        so an int8 pool serializes ~4x cheaper than fp32 (the EQuARX-style
        cheap wire the disaggregation design rides). The pages themselves
        are NOT freed here: the scheduler keeps ownership until the decode
        side acknowledges (export-before-free)."""
        self.model.refuse("export_pages (page handoff)")
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        tensors = {}
        for key, arr in self.paged_cache.items():
            # every pool tensor indexes pages on axis 2:
            # pages [L, H, P, ps, Dq], scales [L, H, P]
            sel = np.asarray(arr[:, :, ids])
            tensors[key] = {"dtype": sel.dtype.name,
                            "shape": list(sel.shape),
                            "data": sel.tobytes()}
        payload = {"page_ids": [int(p) for p in np.asarray(page_ids)],
                   "tensors": tensors}
        if self.serving.page_fingerprints:
            # stamp the exact bytes crossing the trust boundary; the
            # importer re-fingerprints and refuses a torn transfer
            from ...resilience.integrity import payload_fingerprints

            payload["fingerprints"] = payload_fingerprints(tensors)
        return payload

    def import_pages(self, page_ids, payload: dict) -> None:
        """Install a handoff payload (``export_pages`` on the prefill side)
        into locally-owned pages. ``page_ids`` are THIS engine's freshly
        claimed pages, in the same table order the exporter used — the page
        numbers themselves need not match across replicas, only the order."""
        self.model.refuse("import_pages (page handoff)")
        src = payload["tensors"]
        if set(src) != set(self.paged_cache):
            raise ValueError(
                f"handoff pool mismatch: payload has {sorted(src)}, engine "
                f"pools are {sorted(self.paged_cache)} (kv_bits must match "
                f"across prefill and decode replicas)")
        stamp = payload.get("fingerprints")
        if stamp:
            # any stamped payload is verified regardless of the local flag:
            # the exporter paid for the stamp precisely so a torn transfer
            # is refused here rather than decoded into garbage tokens
            from ...resilience.integrity import verify_payload_fingerprints

            bad = verify_payload_fingerprints(src, stamp)
            if bad:
                raise ValueError(
                    "handoff payload failed fingerprint verification "
                    f"({bad}) — refusing the transfer")
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        cache = dict(self.paged_cache)
        for key, rec in src.items():
            dt = _np_dtype(rec["dtype"])
            vals = np.frombuffer(rec["data"], dtype=dt).reshape(rec["shape"])
            if list(vals.shape[2:3]) != [len(np.asarray(page_ids))]:
                raise ValueError(
                    f"handoff {key}: payload carries {vals.shape[2]} pages, "
                    f"importer claimed {len(np.asarray(page_ids))}")
            cache[key] = cache[key].at[:, :, ids].set(
                jnp.asarray(vals, cache[key].dtype))
        # .at[].set may drop a mesh's sharding: back where the steps expect it
        self.paged_cache = self.model.place_cache(cache)

    def fingerprint_pages(self, page_ids) -> list:
        """Fingerprint the CURRENT pool contents of ``page_ids``: one crc
        per page, chained across every pool tensor in sorted-key order so a
        flip in any of k/v (or their quantization scales) changes the page's
        print. This is the scheduler's scan/audit primitive — pulled to host
        once per call, so callers budget the page count."""
        from ...resilience.fingerprint import CHECKSUMS, preferred_checksum

        fn = CHECKSUMS[preferred_checksum()]
        ids = np.asarray(page_ids, np.int32)
        if ids.size == 0:
            return []
        host = {key: np.asarray(arr[:, :, jnp.asarray(ids)])
                for key, arr in sorted(self.paged_cache.items())}
        out = []
        for j in range(ids.size):
            crc = 0
            for key in sorted(host):
                crc = fn(np.ascontiguousarray(host[key][:, :, j]).tobytes(),
                         crc)
            out.append(int(crc))
        return out

    def corrupt_page_bit(self, page: int) -> None:
        """Chaos-only: flip one real bit in ``page``'s content in the first
        pool tensor — the scheduler's ``flip_bit_at`` (domain ``kv_page``)
        injection lands here so SDC detection is exercised against genuine
        pool bytes, not a mocked flag."""
        key = sorted(self.paged_cache)[0]
        arr = self.paged_cache[key]
        host = np.array(arr[:, :, int(page)])  # forced writable host copy
        flat = host.reshape(-1).view(np.uint8)
        flat[flat.size // 2] ^= 0x01
        cache = dict(self.paged_cache)
        cache[key] = arr.at[:, :, int(page)].set(
            jnp.asarray(host, arr.dtype))
        self.paged_cache = self.model.place_cache(cache)

    def warmup(self) -> int:
        """Compile every serving program shape before traffic arrives:
        fused prefill per chunk bucket, the chunked long-prompt path (+
        scatter) when configured, and both decode block sizes. Safe against
        live state — warmup tokens carry all-zero block tables and zero
        lengths, so every write lands on the reserved sink page. Returns the
        number of compiled programs."""
        s = self.serving
        sink_row = np.zeros(s.pages_per_seq, np.int32)
        # per-role program sets: a decode-specialist replica admits page
        # handoffs (import, no prefill programs); a prefill specialist never
        # decodes past the first token. The skipped families stay lazily
        # compilable for failover — they just aren't paid for up front
        # (aot.serving_admission_limit prices the same split).
        if s.role != "decode":
            for chunk in self._chunk_buckets:
                # cap at prefill_chunk: the top bucket can exceed it
                # (non-pow2 prefill_chunk) and a longer probe would take the
                # chunked path, leaving the fused/batch programs for this
                # bucket uncompiled
                t = np.zeros(min(chunk, s.prefill_chunk, s.max_model_len),
                             np.int32)
                self.prefill(0, t, sink_row)
                if self.num_slots >= 2:  # the batch program, every row bucket
                    self.prefill_many([(0, t, sink_row), (1, t, sink_row)])
            if s.max_model_len > s.prefill_chunk:
                # the chunked long-prompt path: full chunks compile ONE
                # program, but the final partial chunk lands on any
                # REACHABLE bucket — compile each (a long prompt's remainder
                # must not pay a mid-traffic compile). Bucket b is reachable
                # when some legal remainder maps to it, even if
                # prefill_chunk + b itself overshoots max_model_len.
                max_rem = s.max_model_len - s.prefill_chunk
                prev = 0
                for b in self._chunk_buckets:
                    if max_rem > prev:
                        n = s.prefill_chunk + min(b, max_rem)
                        self.prefill(0, np.zeros(n, np.int32), sink_row)
                    prev = b
        zeros = np.zeros(self.num_slots, np.int32)
        tables = np.zeros((self.num_slots, s.pages_per_seq), np.int32)
        mask = np.zeros(self.num_slots, bool)
        if s.role != "prefill":
            steps_set = {1}
            k = 1
            while k * 2 <= s.decode_block:  # scheduler's power-of-two blocks
                k *= 2
                steps_set.add(k)
            for steps in sorted(steps_set):
                self.decode(zeros, tables, zeros, mask, steps=steps)
            self._warm_place()
            # every verify window shape in the spec ladder (budget all-zero:
            # nothing commits, every write is masked to nowhere)
            for k in s.spec_k_set:
                self.verify(np.zeros((self.num_slots, k + 1), np.int32),
                            tables, zeros, mask,
                            np.full(self.num_slots, -1, np.int32), zeros)
        # a mesh's programs traced for the tp-collective-order dslint audit
        self.model.capture_programs(self)
        return len(self.compile_log)

    # -------------------------------------------------------------- assembly
    def make_scheduler(self, clock=time.monotonic, recovery_log=None
                       ) -> ContinuousBatchingScheduler:
        """Assemble the scheduler with the config's overload/deadline/fault
        knobs. ``recovery_log`` (a
        :class:`~deepspeed_tpu.resilience.events.RecoveryLog`) receives the
        serving recovery trail; when omitted and a monitor is attached, a
        monitor-only log is created so ``Serving/*`` scalars still flow. A
        watchdog is created (and owned by the scheduler — ``close()`` stops
        it) when either serving phase deadline is armed."""
        s = self.serving
        if recovery_log is None and self.monitor is not None:
            from ...resilience.events import RecoveryLog

            recovery_log = RecoveryLog(monitor=self.monitor, role="serving",
                                       prefix="Serving")
        watchdog = None
        owns = False
        if s.prefill_deadline_s or s.decode_deadline_s:
            from ...resilience.watchdog import HealthWatchdog

            deadlines = {}
            if s.prefill_deadline_s:
                deadlines["serving_prefill"] = float(s.prefill_deadline_s)
            if s.decode_deadline_s:
                deadlines["serving_decode"] = float(s.decode_deadline_s)
                # a speculative verify window is one decode-analog dispatch
                # (k+1 positions, weights read once) — it rides the decode
                # deadline so arming spec never silently disarms the PR 7
                # stall ladder
                deadlines["serving_verify"] = float(s.decode_deadline_s)
            watchdog = HealthWatchdog(
                deadlines, poll_interval=s.watchdog_poll_s,
                recovery_log=recovery_log,
                stacks_dir=s.stacks_dir).start()
            owns = True
        prefix_cache = None
        if s.enable_prefix_cache:
            from .paging import PrefixIndex

            prefix_cache = PrefixIndex(s.page_size)
        drafter = None
        if s.spec_drafter:
            from .speculate import make_drafter

            drafter = make_drafter(self, s)
        tiers, tenants, brownout = s.resolved_tiers()
        sched = ContinuousBatchingScheduler(
            executor=self, num_slots=self.num_slots,
            num_pages=self.num_pages, page_size=s.page_size,
            pages_per_seq=s.pages_per_seq,
            decode_block=s.decode_block,
            max_context=s.max_model_len, clock=clock,
            max_queue=s.max_queue, max_queued_tokens=s.max_queued_tokens,
            shed_policy=s.shed_policy, ttft_deadline_s=s.ttft_deadline_s,
            deadline_s=s.request_deadline_s,
            dispatch_retries=s.dispatch_retries,
            quarantine_after=s.quarantine_after,
            dispatch_failure_budget=s.dispatch_failure_budget,
            recovery_log=recovery_log, watchdog=watchdog,
            prefix_cache=prefix_cache, drafter=drafter, spec_k=s.spec_k,
            spec_adaptive=s.spec_adaptive, role=s.role,
            tiers=tiers, tenants=tenants, brownout=brownout,
            page_fingerprints=s.page_fingerprints,
            pages_scan_per_step=s.pages_scan_per_step)
        sched._owns_watchdog = owns
        self.last_scheduler = sched
        return sched

    def hbm_token_slots(self) -> int:
        """Token capacity of the pool (page 0 excluded) — the "equal HBM
        budget" side of the static-batch A/B."""
        return (self.num_pages - 1) * self.serving.page_size

    def kv_bytes_per_token(self) -> float:
        """HBM bytes a cached token costs (``model.kv_bytes_per_token``)."""
        return self.model.kv_bytes_per_token()

    def slot_bytes(self) -> int:
        """HBM bytes a decode slot costs (``model.slot_bytes``)."""
        return self.model.slot_bytes()
