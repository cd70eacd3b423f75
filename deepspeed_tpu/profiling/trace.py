"""Names for what runs: the one tracing mechanism of the package.

One vocabulary, two sinks. Names are compiled into the programs (a Pallas
kernel's ``name=``, a jitted program's function name, ``jax.named_scope`` in the
model and the train step) and cost nothing at run time. A host span
(:func:`span`, :func:`step_span`) is always kept in the record, a bounded ring
in memory on ``time.perf_counter``'s clock: name, start, end, the step it lies
in and its counts (:func:`recorded`, :func:`slowest`, :func:`clear`); every
backend compile or cache load joins it as an ``xla.compile`` event, every
stretch in which the engine had nothing queued on the device as a
``device.starved`` event (:func:`drained`, :func:`fed`), every garbage
collection of a millisecond or more as ``host.gc``. Inside a
profiler session (``jax.profiler.trace(dir)`` or the profiler server,
``docs/TRACING.md``) the same ``with`` also writes a
``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation``, on the profiler's
clock beside the device timeline. There is no config key, no environment
variable, no exporter and no file of this package's own: "off" is the record
alone.

This module is the one place that knows the vocabulary:

* span names, dotted ``layer.phase[.part]`` (the constants below);
* scope names (``SCOPES``) and how an HLO ``op_name`` maps to a phase of the
  train step (:func:`phase_of`);
* the table of hot programs (:func:`register_program`) from which
  :func:`program_scopes` recovers ``{instruction name: op_name}``: the device
  trace names an operation by its HLO instruction without its metadata, the
  compiled text of the same program carries both.

The benchmark's per-layer metrics are computed from these names, from the
record's entries and counts and from :func:`phase_of` (``PERF.md`` section
3): they are part of its yardstick though they live here. A change that
claims a gain on such a metric leaves what the metric reads as it is.
"""

from __future__ import annotations

import collections
import contextlib
import copy
import dataclasses
import gc
import heapq
import itertools
import re
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax

# ------------------------------------------------------------- host spans
# A span's counts are named beside it. Each has a reader: a per-layer metric
# of the benchmark or a procedure of docs/TRACING.md. A count nobody reads is
# not recorded.
# serving scheduler (inference/serving/scheduler.py)
SERVE_STEP = "serve.step"                      # step_num
SERVE_HOUSEKEEPING = "serve.housekeeping"
SERVE_ADMIT_CLAIM = "serve.admit.claim"
SERVE_ADMIT_PREFILL = "serve.admit.prefill"    # rids
SERVE_ADMIT_COMMIT = "serve.admit.commit"
SERVE_GROW = "serve.grow"
# serve.decode: steps, active, live_kv_tokens, pool_tokens, live_pages,
# table_slots, fresh, fresh_on_device (the scheduler's own); what the dispatch
# said of itself once it is back (the executor's ``decode_said``): of a routed
# model ROUTING_STATS and GROUPED_STATS, of one whose full layers select the
# rows they read INDEX_STATS; and the model's half, from the lengths
# (inference/serving/model.py ``decode_counts``): cache_layers; kv_rows_full
# and kv_rows_window where window layers keep rings; GQA_STATS, MLA_STATS or
# PAGED_STATS by the decode kernel; STATE_STATS; SELECT_STATS
SERVE_DECODE = "serve.decode"
SERVE_COMMIT = "serve.commit"
# serving engine (inference/serving/engine.py)
ENGINE_PREFILL_SCRATCH = "engine.prefill.scratch"  # the dense scratch cache
ENGINE_PREFILL_FUSED = "engine.prefill.fused"      # real_tokens,
ENGINE_PREFILL_CHUNK = "engine.prefill.chunk"      # padded_tokens,
ENGINE_PREFILL_SCATTER = "engine.prefill.scatter"
ENGINE_PREFILL_BATCH = "engine.prefill.batch"      # head_tokens (each
#                                                    of the three says all
#                                                    three, a chunk also
#                                                    paged_tokens; of a model
#                                                    of retention mixers also
#                                                    RETENTION_STATS)
ENGINE_PREFILL_SAMPLE = "engine.prefill.sample"    # the host waits here
ENGINE_DECODE_ENQUEUE = "engine.decode.enqueue"
ENGINE_DECODE_FETCH = "engine.decode.fetch"        # the host waits here
# train engine (runtime/engine.py)
TRAIN_STEP = "train.step"                      # step_num
TRAIN_PLACE_BATCH = "train.place_batch"
TRAIN_DISPATCH = "train.dispatch"
TRAIN_SYNC = "train.sync"
TRAIN_POST = "train.post"
# an event of the record alone, put there by this module's jax.monitoring
# listener: a backend compile or a load from the persistent cache
XLA_COMPILE = "xla.compile"                    # fun_name
# events of the record alone, known only once they are over (a profiler
# session has the device's own line): from t0 to t1 no program of this
# thread's engine was queued on the device. ``after``: the span in which the
# host read back the last thing it had queued (its exit is t0); ``by``: the
# program whose dispatch returned at t1 (:func:`drained`, :func:`fed`)
DEVICE_STARVED = "device.starved"              # after, by
# a garbage collection that lasted GC_KEPT_NS or more (``gc.callbacks``)
HOST_GC = "host.gc"                            # generation

SPAN_PREFIXES = ("serve.", "engine.", "train.")
# ``rids`` joins with this: the profiler's encoding splits a value at a comma
RID_SEPARATOR = " "
MAX_RIDS = 16

# what a routed model's decode dispatch adds to its serve.decode span, from
# the counts its program returned beside the tokens (models/gpt.routing_of),
# summed over the dispatch's steps: the active rows' (token, expert)
# assignments, those that met an expert this chip holds, the held experts
# that met any (summed over the routed layers), and the most one held expert
# met in one layer of one step
ROUTING_STATS = ("routed_total", "routed_local", "experts_hit",
                 "expert_load_max")
# and of a model whose router also scores zero-compute experts
# (GPTConfig.moe_zero_experts), whose program returns a fifth count: the
# assignments that took one, an identity that no chip's share holds
ROUTED_ZERO = "routed_zero"


# what every decode dispatch's serve.decode span says of first tokens: the
# active slots whose input token is a first token of this step's admission,
# and those of them the decode program took from the device before the host
# had read them (the staged dispatch: scheduler._stage_decode)
FRESH_STATS = ("fresh", "fresh_on_device")

# what a model whose mixers keep a state a decode slot (GPTConfig.ssm, a
# Mamba-2 mixer; GPTConfig.kda, a delta-rule one; GPTConfig.retention, power
# retention, whose every layer keeps one and none keeps a row: gpt.state_mixer)
# adds to its serve.decode span: the slots whose states a step of the dispatch
# updated, and the bytes of states and convolution windows read and written,
# all its mixers, all the dispatch's steps; the mixers that keep one, and the
# rows of keys and values the dispatch's steps read beside the states, every
# cache layer and step (a layer with both mixers walks both)
STATE_STATS = ("state_slots", "state_bytes", "state_layers", "kv_rows")


# what a model with fewer key-value heads than query heads adds to its
# serve.decode span: the page tiles the groups of ``paged_decode_gqa``'s grid
# fetch over the block tables for the dispatch's first token (a request's
# pages in groups of ``gqa_pages_per_step``, the last group's tiles past its
# last page fetched and masked: over them, ``live_pages`` is the share of
# the fetched tiles that held rows), and the pages a grid step takes
GQA_STATS = ("gqa_group_tiles", "gqa_pages_per_step")

# the same of a model whose latent layers read pages: the page tiles the
# groups of ``paged_decode_mla``'s grid fetch over the block tables (under a
# selection too), and the pages a grid step takes
MLA_STATS = ("mla_group_tiles", "mla_pages_per_step")

# the same of a model whose every query head has a key head of its own: the
# page tiles the steps of ``paged_decode``'s grid take over the block tables
# (one past a request's end is scored and masked, not copied), and the pages
# a grid step takes (1 over a quantized pool and for heads of another width
# than 128, whose step is a page)
PAGED_STATS = ("paged_group_tiles", "paged_pages_per_step")

# what a model whose full layers read a learned selection of their rows
# (GPTConfig.index_topk) adds to its serve.decode span: the index keys its
# steps' indexers scored and the rows its full layers attended over, summed
# over the active slots, those layers and the dispatch's steps (a slot of
# ``n`` cached tokens scores ``n + 1`` keys with its new one and keeps
# ``min(n + 1, index_topk)``)
SELECT_STATS = ("index_rows", "selected_rows")

# What a program says of itself when it is traced for its first dispatch, the
# same for every dispatch of it: a pair (the forms of one job it runs, those of
# them that are our kernel), read off its jaxpr (:func:`kernel_stats`).
# GROUPED_STATS: a routed model's grouped products over the held experts
# (moe/dropless.held_experts_ffn: two or three a routed layer a step), and
# those that lower to ops/pallas/grouped_dot and not to XLA's ragged-dot.
# INDEX_STATS: the indexer's scores of a model whose full layers select the
# rows they read (one a selecting layer a step), and those that
# ops/pallas/index_scores takes over the pages where the index keys lie and
# not the plain form over gathered keys (the program models/gpt._index_scores).
# RETENTION_STATS: the chunked forms of a model whose mixers are power
# retention (one a layer), and those that ops/pallas/retention_chunk takes
# and not the plain form (models/retention.scan_chunks, whose sums are the
# program _chunk_sums).
GROUPED_STATS = ("grouped_products", "grouped_kernel")
INDEX_STATS = ("index_products", "index_kernel")
RETENTION_STATS = ("retention_scans", "retention_kernel")


class KernelStats(NamedTuple):
    kernel: str     # the ``pallas_call``'s name
    plain: Callable[[str, dict], bool]  # is (equation, parameters) the form
    #                 that is not our kernel
    said_by: str    # the kind of program whose spans say the pair: "decode"
    #                 (serve.decode), "prefill" (engine.prefill.fused / .batch
    #                 / .chunk)


KERNEL_STATS: Dict[Tuple[str, str], KernelStats] = {
    GROUPED_STATS: KernelStats(
        "grouped_dot", lambda name, params: name.startswith("ragged_dot"),
        "decode"),
    INDEX_STATS: KernelStats(
        "index_scores", lambda name, params: params.get("name")
        == "_index_scores", "decode"),
    RETENTION_STATS: KernelStats(
        "retention_chunk", lambda name, params: params.get("name")
        == "_chunk_sums", "prefill"),
}


def kernel_stats(jaxpr, stats: Tuple[str, str]) -> Dict[str, int]:
    """The pair ``stats`` (a key of ``KERNEL_STATS``) of one run of the
    program ``jaxpr`` (a ``ClosedJaxpr``): the forms of its row's job, the
    kernel by the ``pallas_call``'s name and the plain form by the row's
    matcher, and those of them that are the kernel. A scan's body counts once
    a trip, a conditional's branches as the one with most, any other nested
    program once."""
    row = KERNEL_STATS[stats]

    def walk(jp) -> Tuple[int, int]:
        total = kernel = 0
        for eqn in jp.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                ours = eqn.params["name"] == row.kernel
                total, kernel = total + ours, kernel + ours
                continue
            if row.plain(name, eqn.params):
                total += 1
                continue
            inner = [walk(getattr(sub, "jaxpr", sub))
                     for v in eqn.params.values()
                     for sub in (v if isinstance(v, (list, tuple)) else (v,))
                     if hasattr(getattr(sub, "jaxpr", sub), "eqns")]
            if not inner:
                continue
            if name == "cond":
                inner = [max(inner)]
            trips = eqn.params["length"] if name == "scan" else 1
            total += trips * sum(t for t, _ in inner)
            kernel += trips * sum(k for _, k in inner)
        return total, kernel

    return dict(zip(stats, walk(jaxpr.jaxpr)))


def routing_stats(counts) -> Dict[str, int]:
    """``ROUTING_STATS`` of one dispatch from its steps' counts [steps, 4];
    with a fifth column also ``ROUTED_ZERO``."""
    total, local, hit, _, *zero = counts.sum(axis=0).tolist()
    return dict(zip(ROUTING_STATS + (ROUTED_ZERO,),
                    (total, local, hit, int(counts[:, 3].max()), *zero)))


# ------------------------------------------------------------- scope names
# ut_loop: one pass of a stack that runs more than once (GPTConfig.ut_steps),
# around its blocks and the loop_norm that closes it. Inside attn, latent
# attention's projections: mla_q (the low-rank query path), mla_kv (the
# latent and the rotated key), mla_absorb (W_kvb into the query and out of
# the output; mla_expand: a prompt chunk's cached rows expanded by W_kvb
# for the chunk kernel). Inside attn, of a model with kinds of attention layer
# (GPTConfig.attn_period): attn_full and attn_window, the whole sublayer of a
# layer of that kind; inside attn_full, of a kind that reads a learned
# selection of its rows (GPTConfig.index_topk): index, the indexer's
# projections, its scores over the cached index keys and the top-k, in the
# decode and the prefill programs. In mlp's place in a routed layer: moe_router,
# moe_experts (the grouped products over the held experts), moe_shared.
# ssm: the Mamba-2 mixer (models/ssm.py), the one sublayer of an ``M`` layer
# of GPTConfig.layer_pattern or, in a config with ssm and no pattern, the
# mixer beside attention in every layer: ssm and attn then stand side by
# side under one block, each around its own branch. Inside ssm: ssm_in (the
# in-projection, dt's softplus), ssm_conv (the causal convolution and the
# window it hands on), ssm_scan (a prompt: the chunked scan) or ssm_update (a
# decode step: the ssm_decode kernel over the slots' states), ssm_gate_norm,
# ssm_out. A layer of such a pattern that is attention or a routed
# feed-forward alone keeps attn and mlp.
# kda: the Kimi Delta Attention mixer (models/kda.py) of a layer that
# GPTConfig.kda_layers names, in attn's place; the layer's feed-forward keeps
# mlp. Inside kda: kda_in (the in-projection), kda_conv (the three causal
# convolutions, the unit lengths), kda_gates (the decay a channel, the write
# strength), kda_scan (a prompt: the chunked form) or kda_update (a decode
# step: the kda_decode kernel over the slots' states), kda_gate_norm,
# kda_out.
# Of a layer with two attention sub-blocks and one routed branch across them
# (GPTConfig.moe_shortcut), beside attn a sub-block: dense_ffn around each of
# its two dense MLPs, and routed_branch around the routed branch, with
# moe_router, moe_experts and moe_zero (the zero-compute experts' term: the
# branch's input times their gates) inside it; neither lies under the other
# or under mlp, which such a layer does not have.
# retention: the power-retention mixer (models/retention.py) of every layer
# of a config with GPTConfig.retention, in attn's place; the layer's
# feed-forward keeps mlp. Inside retention: retention_in (the projections, the
# head norms, the rotation, the gate's logit), retention_scan (a prompt: the
# chunked form) or retention_update (a decode step: the retention_decode
# kernel over the slots' states), retention_out.
MODEL_SCOPES = ("embed", "blocks", "attn", "mlp", "kv_write", "head_loss",
                "ut_loop", "loop_norm", "mla_q", "mla_kv", "mla_absorb",
                "mla_expand",
                "moe_router", "moe_experts", "moe_shared", "attn_full",
                "attn_window", "index", "ssm", "ssm_in", "ssm_conv", "ssm_scan",
                "ssm_update", "ssm_gate_norm", "ssm_out", "kda", "kda_in",
                "kda_conv", "kda_gates", "kda_scan", "kda_update",
                "kda_gate_norm", "kda_out", "retention", "retention_in",
                "retention_scan", "retention_update", "retention_out",
                "dense_ffn", "routed_branch", "moe_zero")
STEP_SCOPES = ("grad_reduce", "grad_clip", "optimizer")
SCOPES = MODEL_SCOPES + STEP_SCOPES

PHASES = ("forward", "recompute", "backward", "optimizer", "other")


Counts = Optional[Callable[[], Dict[str, Any]]]

# -------------------------------------------------------------- the record
# A 40 s window of the densest benchmark cell records about 10 k spans.
RECORD_SPANS = 65_536
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# (name, start ns, end ns, step, counts or None), appended at a span's exit:
# deque.append is atomic, so any thread may record
_ring: collections.deque = collections.deque(maxlen=RECORD_SPANS)
_now = time.perf_counter_ns
_in_session = jax.profiler.TraceAnnotation.is_enabled


class _Here(threading.local):
    step: Optional[int] = None     # of the step span this thread is inside
    # (ns, span name) of the wait whose exit left the device with nothing
    # of this thread's engine queued, until a dispatch feeds it again
    dry: Optional[Tuple[int, str]] = None


_here = _Here()


class Recorded(NamedTuple):
    """One entry of the record, seconds on ``time.perf_counter``'s clock."""

    name: str
    t0: float
    t1: float
    step: Optional[int]            # ``step_num`` of the enclosing step span
    counts: Dict[str, Any]

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class SlowStep(NamedTuple):
    step: Recorded
    seconds: Dict[str, float]      # spans and xla.compile inside it, by name
    compiled: List[str]            # ``fun_name`` of each xla.compile inside


class _Span:
    """One ``with``, two sinks: the record always; ``ann``, the profiler's
    annotation, where a session was on when the span was made. The record's
    two clock reads lie inside the annotation's, so both hold the same span."""

    __slots__ = ("name", "stats", "ann", "t0", "t1")

    def __init__(self, name: str, stats: Optional[Dict[str, Any]], ann):
        self.name, self.stats, self.ann = name, stats, ann

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, kind, exc, tb):
        self.t1 = t1 = _now()
        if self.ann is not None:
            self.ann.__exit__(kind, exc, tb)
        _ring.append((self.name, self.t0, t1, _here.step, self.stats))

    def set_metadata(self, **stats) -> None:
        """Counts known only once the work is done, to both sinks."""
        self.stats = {**(self.stats or {}), **stats}
        if self.ann is not None:
            self.ann.set_metadata(**stats)


class _StepSpan(_Span):
    """``serve.step`` / ``train.step``: what this thread records until it
    exits, the step span itself included, carries its ``step_num``."""

    __slots__ = ("step", "outer")

    def __init__(self, name: str, step: int, ann):
        super().__init__(name, None, ann)
        self.step = step

    def __enter__(self):
        self.outer, _here.step = _here.step, self.step
        return super().__enter__()

    def __exit__(self, kind, exc, tb):
        super().__exit__(kind, exc, tb)
        _here.step = self.outer


def span(name: str, counts: Counts = None) -> _Span:
    """A host span: always in the record, inside a profiler session also an
    annotation on the profiler's clock. ``counts`` returns the event's
    stats (ints, floats, short strings without commas) and is called once,
    here::

        with span(SERVE_DECODE, lambda: {"steps": block, "active": n}):
            ...
    """
    stats = counts() if counts is not None else None
    ann = (jax.profiler.TraceAnnotation(name, **(stats or {}))
           if _in_session() else None)
    return _Span(name, stats, ann)


def step_span(name: str, step: int) -> _StepSpan:
    """A step span: the profiler's step analysis groups device work under it
    (``step_num`` is its one stat), and the record's entries inside it carry
    its number."""
    step = int(step)
    ann = (jax.profiler.StepTraceAnnotation(name, step_num=step)
           if _in_session() else None)
    return _StepSpan(name, step, ann)


def drained(wait: Optional[_Span]) -> None:
    """Called where the host has just read back, inside the span ``wait``,
    the result of the last thing it had queued: the device holds nothing of
    this thread's engine from ``wait``'s exit (its own stamp, no second clock
    read) until the next :func:`fed`. ``None`` drops the mark without an
    event: the engine cannot know (a dispatch episode that failed)."""
    _here.dry = None if wait is None else (wait.t1, wait.name)


def fed(by: str) -> None:
    """Called where the dispatch of the program ``by`` has returned. After a
    :func:`drained` it ends the starvation: one ``device.starved`` event from
    the wait's exit to now, under the step this dispatch lies in (the device
    cannot have begun before the call returned; it may have begun a little
    before that). Otherwise an attribute check."""
    dry = _here.dry
    if dry is not None:
        _here.dry = None
        _ring.append((DEVICE_STARVED, dry[0], _now(), _here.step,
                      {"after": dry[1], "by": by}))


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        t1 = _now()
        _ring.append((XLA_COMPILE, t1 - int(secs * 1e9), t1, _here.step,
                      {"fun_name": str(kw.get("fun_name", "?"))}))


jax.monitoring.register_event_duration_secs_listener(_on_duration)

GC_KEPT_NS = 1_000_000
_gc_began = [0]        # a collection holds the interpreter: one at a time


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    now = _now()
    if phase == "start":
        _gc_began[0] = now
    elif now - _gc_began[0] >= GC_KEPT_NS:
        _ring.append((HOST_GC, _gc_began[0], now, _here.step,
                      {"generation": info["generation"]}))


gc.callbacks.append(_on_gc)


def recorded(since: Optional[float] = None) -> List[Recorded]:
    """The record's entries by start, oldest first; with ``since`` (seconds
    on ``time.perf_counter``'s clock) those that began at or after it."""
    out = [Recorded(n, a * 1e-9, b * 1e-9, step, stats or {})
           for n, a, b, step, stats in list(_ring)]
    if since is not None:
        out = [e for e in out if e.t0 >= since]
    return sorted(out, key=lambda e: (e.t0, -e.t1))


def slowest(step_name: str, n: int = 3, since: Optional[float] = None
            ) -> List[SlowStep]:
    """The ``n`` longest recorded ``step_name`` steps, longest first, each
    with the seconds of the spans and events inside it by name (of its own
    step number, contained in its interval) and the functions that compiled
    in it. A ``device.starved`` event begins in one step and ends in a
    later one, whose number it carries: it counts by the part of it that
    lies inside the step's interval, whatever its number (of every engine,
    where several threads drive one each)."""
    entries = recorded(since)
    out = []
    for s in heapq.nlargest(n, (e for e in entries if e.name == step_name),
                            key=lambda e: e.dur):
        seconds: Dict[str, float] = {}
        compiled = []
        for e in entries:
            if e.name == DEVICE_STARVED:
                part = min(e.t1, s.t1) - max(e.t0, s.t0)
                if part > 0:
                    seconds[e.name] = seconds.get(e.name, 0.0) + part
            elif (e is not s and e.step == s.step and e.t0 >= s.t0
                    and e.t1 <= s.t1):
                seconds[e.name] = seconds.get(e.name, 0.0) + e.dur
                if e.name == XLA_COMPILE:
                    compiled.append(e.counts["fun_name"])
        out.append(SlowStep(s, seconds, compiled))
    return out


def clear() -> None:
    """Forget the record."""
    _ring.clear()


def join_rids(rids) -> str:
    """The first ``MAX_RIDS`` request ids as one stat value."""
    return RID_SEPARATOR.join(map(str, itertools.islice(rids, MAX_RIDS)))


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: ``jax.jit`` calls the program ``jit_<name>``, and
    so does the device's "XLA Modules" line."""
    fn.__name__ = name
    return fn


# ------------------------------------------------------------ hot programs
@dataclasses.dataclass
class _Program:
    jitted: Any                        # weak reference to the jitted function
    args: Tuple[Any, ...]              # ShapeDtypeStructs, no array
    mesh: Any = None                   # bound while lowering, where given
    scopes: Optional[Dict[str, str]] = None
    # module id of a trace -> whether this program compiles to that module
    modules: Dict[int, bool] = dataclasses.field(default_factory=dict)
    held: Any = None                   # the function itself: hold_if_traced


# Two engines of one process both build a ``train_batch``: a name holds every
# live registration, the newest last.
_programs: Dict[str, List[_Program]] = {}


def _spec(x: Any) -> jax.ShapeDtypeStruct:
    if isinstance(x, jax.Array):
        # an uncommitted array goes where the others are: keep no sharding
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    return jax.ShapeDtypeStruct(jax.numpy.shape(x), jax.numpy.result_type(x))


def _live(name: str) -> List[_Program]:
    return [p for p in _programs.get(name, ()) if p.jitted() is not None]


def register_program(name: str, jitted: Callable, args: Tuple[Any, ...],
                     mesh: Any = None) -> None:
    """Remember a hot program at its first dispatch: the jitted function
    (weakly: the table keeps no engine alive) and its arguments' shapes,
    dtypes and shardings. A name registered again is held beside the first
    while both live."""
    live = _live(name)
    for prog in live:
        prog.held = None
    _programs[name] = live + [_Program(
        weakref.ref(jitted), jax.tree_util.tree_map(_spec, tuple(args)),
        mesh)]


def hold_if_traced(name: str, jitted: Callable) -> None:
    """Called where a registered program is dispatched. Outside a profiler
    session a flag check. Inside one the table keeps the function itself:
    whoever reads that trace asks :func:`program_scopes` for the program that
    ran in it, and may ask after the engine that built it has gone out of
    scope, when only the garbage collector's timing decided whether a weak
    reference still answered (the dp4 train cell lost its phases that way,
    PERF.md, PR 30). Held until a newer program registers under ``name``; a
    program that never ran in a session is held weakly as before."""
    if not _in_session():
        return
    for prog in _programs.get(name, ()):
        if prog.jitted() is jitted:
            prog.held = jitted


def programs() -> List[str]:
    """Names of the registered programs whose function is still alive."""
    return [n for n in _programs if _live(n)]


_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def parse_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` from a compiled program's text."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _compile(prog: _Program, module_id: Optional[int]) -> None:
    """Fill ``prog.scopes`` and, for ``module_id``, ``prog.modules``. The
    device's "XLA Modules" line calls an execution ``jit_<name>(<id>)``; the
    id is the program's fingerprint, the same in every process and on every
    chip that runs that program, and the serialized executable carries it as a
    varint (read on the v5e, PR 24). Lowering may trace the step function
    again, and this package accounts at trace time: the wire ledger and the
    comms logger are left as they were found."""
    from ..comm.comm import comms_logger
    from ..comm.runtime_accounting import wire_ledger

    kept = (copy.deepcopy(wire_ledger.records),
            copy.deepcopy(comms_logger.records))
    try:
        with (jax.set_mesh(prog.mesh) if prog.mesh is not None
              else contextlib.nullcontext()):
            compiled = prog.jitted().lower(*prog.args).compile()
    finally:
        wire_ledger.records, comms_logger.records = kept
    prog.scopes = parse_scopes(compiled.as_text())
    if module_id is not None:
        blob = compiled.runtime_executable().serialize()
        prog.modules[module_id] = _varint(module_id) in blob


def program_scopes(name: str, module_id: Optional[int] = None
                   ) -> Dict[str, str]:
    """``{HLO instruction name: op_name}`` of a registered program.

    Lowers and compiles it on demand (a load where the persistent cache holds
    it) and parses ``as_text()``. Nothing here runs unless asked, so the cost
    falls after the measured window, on whoever asks.

    ``module_id`` is the number a trace prints after the program's name. With
    it, the answer is that of the live registration which compiles to that
    very module, and a ``LookupError`` where none does: instruction names
    such as ``fusion.12`` exist in every program, so a join with another
    program's text would attribute without complaint. Without it, the newest
    live registration of the name answers."""
    live = _live(name)
    if not live:
        raise KeyError(f"no live program is registered as {name!r}")
    for prog in reversed(live):
        if prog.scopes is None or (module_id is not None
                                   and module_id not in prog.modules):
            _compile(prog, module_id)
        if module_id is None or prog.modules[module_id]:
            return prog.scopes
    raise LookupError(
        f"none of the {len(live)} live programs registered as {name!r} "
        f"compiles to module {module_id}: the trace is of another program")


_WRAPPED = re.compile(r"^(?:\w+\()+([\w.\-]+)\)+$")     # transpose(jvp(attn))


def phase_of(op_name: str) -> Tuple[str, Optional[str]]:
    """(phase, scope) of an HLO ``op_name`` such as
    ``jit(train_batch)/transpose(jvp(blocks))/while/body/closed_call/
    checkpoint/rematted_computation/mlp/dot_general``.

    Phase: ``recompute`` under ``rematted_computation`` (the forward run again
    inside the backward pass), else ``backward`` under ``transpose(``, else
    ``optimizer`` under the step's own scopes (``grad_reduce`` counts as
    backward, as the reference's ``backward_allreduce`` timer does), else
    ``forward`` under ``jvp(`` or a model scope, else ``other``. Scope: the
    innermost entry that is one of ``SCOPES``, or None."""
    parts = op_name.split("/")
    scope = None
    for part in parts:
        m = _WRAPPED.match(part)
        bare = m.group(1) if m else part
        if bare in SCOPES:
            scope = bare
    if "rematted_computation" in parts:
        return "recompute", scope
    if "transpose(" in op_name or scope == "grad_reduce":
        return "backward", scope
    if scope in STEP_SCOPES:
        return "optimizer", scope
    if "jvp(" in op_name or scope in MODEL_SCOPES:
        return "forward", scope
    return "other", scope
