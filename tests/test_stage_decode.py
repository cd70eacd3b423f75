"""The decode dispatch of a step that admits is enqueued before the admission's
first tokens are read (``ContinuousBatchingScheduler._stage_decode`` ->
``ServingEngine.stage_decode`` -> ``prefill_many``): those tokens go from the
prefill programs into the decode program on the device. Held here to the order
it replaces, which the same engine runs when ``stage_decode`` is hidden from
the scheduler: every request's tokens are the same, on every prefill path, for
a cache with rings and one with a state a slot, and wherever the step falls
back to the old order; the engine drops a stage its next call does not answer;
and the first-token stamp still precedes the decode's wait.
"""

import dataclasses
import json
import os
import types

import numpy as np
import pytest

import jax

from benchmark.lib import manifest, program_trace
from benchmark.readers import prog_span_ratio
from deepspeed_tpu.inference.serving import (Request, RequestState,
                                             ServingConfig, ServingEngine)
from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.profiling import trace
from deepspeed_tpu.resilience import FaultPlan, install_plan

PLAIN = dataclasses.replace(G.PRESETS["tiny"], max_seq_len=256)
PAGE, CHUNK, MAX_LEN = 8, 16, 64
CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs")
# (prompt tokens, new tokens): over 4 slots the first cycle admits two short
# prompts (one batch dispatch), a chunked one and one more short one; those
# behind them come in alone (fused) or in twos as slots free
TRAFFIC = [(5, 6), (9, 5), (40, 7), (12, 4), (3, 9), (33, 3), (7, 2), (16, 5)]


class Unstaged:
    """The engine with ``stage_decode`` hidden: the scheduler then runs the
    order the tree before ran (as it does over every fake executor)."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        if name == "stage_decode":
            raise AttributeError(name)
        return getattr(self._engine, name)


class Recorder:
    """Forwards to the engine and keeps the names of the scheduler's calls in
    order; ``clock`` counts them, so a stamp says between which two calls it
    was taken. A staged ``decode`` is marked: it found its dispatch in
    flight."""

    def __init__(self, engine):
        self._engine = engine
        self.calls = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def clock(self) -> float:
        return float(len(self.calls))

    def stage_decode(self, args):
        self.calls.append("stage_decode")

        def asked():    # by prefill_many, once its programs are queued
            self.calls.append("staged args")
            return args()

        return self._engine.stage_decode(asked)

    def prefill_many(self, items):
        out = self._engine.prefill_many(items)
        self.calls.append("prefill_many")
        return out

    def decode(self, *args, **kw):
        self.calls.append("decode")     # from here on the host waits for it
        out = self._engine.decode(*args, **kw)
        if self._engine.decode_fresh_on_device:
            self.calls[-1] = "decode, staged"
        return out


def _tiny(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        config = json.load(f)
    family = manifest.family_of(config)
    cfg = family.config(config["model"])
    return cfg, family.init_params(cfg, jax.random.PRNGKey(0))


def _plain():
    return PLAIN, G.init_params(PLAIN, jax.random.PRNGKey(0))


MODELS = {
    # plain attention: fused, batch and chunk-to-pages programs all leave
    # their token on the device
    "plain": (_plain, True),
    # the same held to the dense scratch cache: a chunked prompt's token is on
    # the host, the short ones' on the device, in one cycle
    "plain, dense chunks": (_plain, False),
    # key-value heads, rings a slot for the window layers, routed: dense path
    "rings": (lambda: _tiny("tiny-laguna-serve"), None),
    # a Mamba-2 state a slot, which a decode step advances where it lies
    "states": (lambda: _tiny("tiny-nemotron-h-serve"), None),
}
_ENGINES = {}


def _engine(model, decode_block=4, **serving):
    """One engine a (model, serving) pair for the whole file: every run
    finishes its requests and frees its pages, so the next starts clean."""
    key = (model, decode_block, tuple(sorted(serving.items())))
    if key not in _ENGINES:
        build, paged = MODELS[model]
        cfg, params = build()
        engine = ServingEngine(cfg, params, ServingConfig(**{**dict(
            num_slots=4, page_size=PAGE, max_model_len=MAX_LEN,
            prefill_chunk=CHUNK, dtype="float32",
            decode_block=decode_block), **serving}))
        if paged is not None:
            assert engine._chunk_to_pages
            engine._chunk_to_pages = paged
        _ENGINES[key] = engine
    return _ENGINES[key]


def _requests(traffic=TRAFFIC, vocab=60, **kw):
    rng = np.random.default_rng(3)
    return [Request(prompt=rng.integers(1, vocab, n).astype(np.int32),
                    max_new_tokens=m, **kw) for n, m in traffic]


def _run(engine, reqs, staged=True, executor=None, **sched):
    """(tokens a request, the serve.decode counts a dispatch) of one run."""
    s = engine.make_scheduler(**sched)
    s.executor = executor or (engine if staged else Unstaged(engine))
    s.retry_base_delay = s.retry_max_delay = 0.001
    trace.clear()
    for r in reqs:
        s.submit(r)
    s.run_to_completion(max_steps=500)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert s.audit()["ok"] and s.allocator.allocated_pages == 0
    decodes = [e.counts for e in trace.recorded()
               if e.name == trace.SERVE_DECODE]
    return [list(r.tokens) for r in reqs], decodes


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    install_plan(None)
    yield
    install_plan(None)


@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_a_staged_run_is_token_for_token_the_unstaged_one(model, block):
    engine = _engine(model, decode_block=block)
    want, plain = _run(engine, _requests(), staged=False)
    got, decodes = _run(engine, _requests())
    assert got == want
    assert [len(t) for t in got] == [m for _, m in TRAFFIC]
    # the old order reads every first token before it dispatches
    assert sum(d["fresh"] for d in plain) == len(TRAFFIC)
    assert not any(d["fresh_on_device"] for d in plain)
    # staged, every first token is some dispatch's input, and the decode
    # program took it from the device unless its prompt kept the dense
    # scratch cache (chunked: 40 and 33 tokens), whose token the host holds
    assert all(d["fresh_on_device"] <= d["fresh"] <= d["active"]
               for d in decodes)
    assert sum(d["fresh"] for d in decodes) == len(TRAFFIC)
    dense = 0 if engine._chunk_to_pages else 2
    assert sum(d["fresh_on_device"] for d in decodes) == len(TRAFFIC) - dense
    assert [d["steps"] for d in decodes] == [d["steps"] for d in plain]


def _fails_a_prefill_episode():
    # the first dispatch episode is the first cycle's prefill: all three
    # attempts raise, the cycle goes back to the queue, its stage is dropped
    install_plan(FaultPlan(dispatch_raise_at=0, dispatch_raise_times=3))


# why a step is not staged: (traffic, request fields, serving fields,
# scheduler set-up, whether any LATER step of the run may still be staged)
FALLBACKS = {
    "one token left": ([(5, 1), (9, 1), (40, 1), (12, 1)], {}, {}, None,
                       False),
    "an eos token": (TRAFFIC, {"eos_token_id": 59}, {}, None, False),
    # 9 pages of 8 for four requests that need 14: growth has to preempt
    "a pool that preempts": ([(14, 12), (15, 12), (13, 12), (12, 12)], {},
                             {"num_pages": 10}, None, True),
    "a failed prefill episode": (TRAFFIC, {}, {}, _fails_a_prefill_episode,
                                 True),
    "a drafter": (TRAFFIC, {}, {"spec_drafter": "ngram", "spec_k": 2}, None,
                  False),
}


@pytest.mark.parametrize("why", sorted(FALLBACKS))
def test_a_step_that_cannot_be_staged_runs_the_old_order(why):
    traffic, fields, serving, arm, later = FALLBACKS[why]
    engine = _engine("plain", **serving)
    want, _ = _run(engine, _requests(traffic, **fields), staged=False)
    if arm:
        arm()
    rec = Recorder(engine)
    got, decodes = _run(engine, _requests(traffic, **fields), executor=rec)
    assert got == want
    staged = sum(d["fresh_on_device"] for d in decodes)
    assert (staged > 0) == later
    if why == "a pool that preempts":
        # the first cycle's growth fits and is staged; a later step's does
        # not, and that step reads its tokens first
        assert "decode, staged" in rec.calls and "decode" in rec.calls
        assert sum(d["fresh"] for d in decodes) > len(traffic)  # re-admitted
    if why == "a failed prefill episode":
        # staged, never prefilled: its arguments are never asked for, and
        # the next cycle stages anew
        assert rec.calls[:3] == ["stage_decode", "stage_decode",
                                 "staged args"]


def test_a_prefill_replica_never_stages():
    engine = _engine("plain", role="prefill")
    rec = Recorder(engine)
    s = engine.make_scheduler()
    s.executor = rec
    for r in _requests(TRAFFIC[:3]):
        s.submit(r)
    s.step()
    assert rec.calls == ["prefill_many"] and len(s.pop_handoffs()) == 3


def _cycle(engine, staged, tokens=None, lengths=None):
    """One admission cycle by hand on slots 0-2 of 4 (a batch of two, a
    chunked prompt), then the decode: (first tokens, decode's tokens)."""
    width = engine.serving.pages_per_seq
    prompts = [p.prompt for p in _requests(TRAFFIC[:3])]
    tables = np.zeros((4, width), np.int32)
    lens = np.zeros(4, np.int32)
    page = 1
    for j, p in enumerate(prompts):
        n = len(p) // PAGE + 1
        tables[j, :n] = range(page, page + n)
        page += n
        lens[j] = len(p)
    active = lens > 0
    if staged:
        engine.stage_decode(
            lambda: (np.zeros(4, np.int32), tables, lens, active, 1))
    firsts = engine.prefill_many(
        [(j, p, tables[j]) for j, p in enumerate(prompts)])
    nxt = np.array([firsts[0], firsts[1], firsts[2], 0], np.int32)
    if tokens is not None:
        nxt[:3] = tokens
    if lengths is not None:
        lens = lengths
    out = engine.decode(nxt, tables, lens, active, steps=1)
    return firsts, out


@pytest.mark.parametrize("what", ["answers", "other tokens", "other lengths"])
def test_a_decode_that_differs_from_the_stage_drops_it(what):
    """The stage is answered only by the very dispatch it named; anything
    else dispatches anew and reads what the unstaged engine reads."""
    tokens = [3, 4, 5] if what == "other tokens" else None
    lengths = (np.array([5, 9, 0, 0], np.int32)     # the third slot gone
               if what == "other lengths" else None)
    a, b = (ServingEngine(*_plain(), ServingConfig(
        num_slots=4, page_size=PAGE, max_model_len=MAX_LEN,
        prefill_chunk=CHUNK, dtype="float32")) for _ in range(2))
    want = _cycle(a, False, tokens, lengths)
    got = _cycle(b, True, tokens, lengths)
    assert got[0] == want[0] and np.array_equal(got[1], want[1])
    assert b.decode_fresh_on_device == (3 if what == "answers" else 0)
    assert a.decode_fresh_on_device == 0 and b._staged is None
    # and the steps after it read the same cache
    (firsts_a, out_a), (firsts_b, out_b) = (_cycle(e, False) for e in (a, b))
    assert firsts_a == firsts_b and np.array_equal(out_a, out_b)


def test_a_stage_whose_cycle_is_ignored_reaches_no_later_decode():
    """``warm_shapes`` throws ``prefill_many``'s result away and
    ``correct.serve_whole`` then calls ``prefill`` and ``decode`` with the
    host's tokens: a stage left in flight is not theirs."""
    engine = _engine("plain")
    width = engine.serving.pages_per_seq
    sink = np.zeros(width, np.int32)
    zeros = np.zeros(4, np.int32)
    t = np.ones(5, np.int32)
    tables = np.zeros((4, width), np.int32)
    args = (zeros, tables, zeros, zeros > 0, 1)
    engine.stage_decode(lambda: args)
    engine.prefill_many([(0, t, sink), (1, t, sink)])
    assert engine._staged is not None and engine._staged.fresh == 2
    engine.prefill(0, t, sink)
    assert engine._staged is None
    engine.decode(zeros, tables, zeros, zeros > 0, steps=1)
    assert engine.decode_fresh_on_device == 0
    # nor does one that was never enqueued outlive the next call
    engine.stage_decode(lambda: args)
    engine.decode(zeros, tables, zeros, zeros > 0, steps=1)
    assert engine._stage_args is None and engine._staged is None
    assert engine.decode_fresh_on_device == 0
    # and one whose arguments turn out not to be had stages nothing
    engine.stage_decode(lambda: None)
    engine.prefill_many([(0, t, sink), (1, t, sink)])
    assert engine._stage_args is None and engine._staged is None


def test_the_first_token_is_stamped_before_the_decode_is_waited_for():
    engine = _engine("plain")
    rec = Recorder(engine)
    s = engine.make_scheduler(clock=rec.clock)
    s.executor = rec
    reqs = _requests(TRAFFIC[:4])
    for r in reqs:
        s.submit(r)
    s.step()
    assert rec.calls == ["stage_decode", "staged args", "prefill_many",
                         "decode, staged"]
    # after prefill_many returned (3 calls made), before decode was entered
    assert [r.t_first_token for r in reqs] == [3.0] * 4
    s.run_to_completion()


def test_the_place_programs_are_built_once_under_their_own_names():
    """One shape a row bucket and one for a lone prompt, all built by the
    first stage: no cycle compiles one, however many prompts it admits."""
    engine = ServingEngine(*_plain(), ServingConfig(
        num_slots=8, page_size=PAGE, max_model_len=MAX_LEN,
        prefill_chunk=CHUNK, dtype="float32"))
    engine.warmup()
    names = {rows: fn.__name__ for rows, fn in engine._place_fns.items()}
    # chunk buckets 16 (and 32): batches of 2..8 rows, 8 slots
    assert names == {1: "place_first_1", 2: "place_first_2",
                     4: "place_first_4", 8: "place_first_8"}
    logged = len(engine.compile_log)
    traffic = [(4, 3)] * 7 + [(40, 3)] + [(6, 2)] * 3 + [(5, 4)]
    _, decodes = _run(engine, _requests(traffic))
    assert sum(d["fresh_on_device"] for d in decodes) == len(traffic)
    assert len(engine.compile_log) == logged
    assert all(fn._cache_size() == 1 for fn in engine._place_fns.values())


@pytest.mark.parametrize("model, share", [
    ("plain", 100.0), ("plain, dense chunks", 75.0), ("parent", None)])
def test_first_tok_on_device_pct_reads_the_decode_spans_counts(
        model, share, monkeypatch):
    """The metric's reader over a tiny engine's own record."""
    spec = manifest.load_metric("first_tok_on_device_pct")
    assert spec["reader"] == "prog_span_ratio"
    (entry,) = [m for m in manifest.listed()["per_layer"]
                if m["name"] == spec["name"]]
    assert entry["moves"] == spec["moves"] == "out_tok_s"
    # not long-decode, the bypass: its traced slice holds no admission, so
    # there the reader finds no ``fresh`` to divide by (PERF.md section 7)
    assert entry["workloads"] == [
        "pythia-1.4b-serve.batch-decode", "laguna-xs.2-serve.mixed-decode",
        "nemotron-3-nano-serve.chat-decode",
        "falcon-h1-34b-serve.long-answer"]
    _, decodes = _run(_engine("plain" if model == "parent" else model),
                      _requests())
    if model == "parent":       # its spans carry neither count
        decodes = [{k: v for k, v in d.items() if "fresh" not in k}
                   for d in decodes]
    spans = [types.SimpleNamespace(stats=d) for d in decodes]
    monkeypatch.setattr(program_trace, "of", lambda ctx: types.SimpleNamespace(
        named=lambda name: spans if name == trace.SERVE_DECODE else []))
    assert prog_span_ratio.read(None, spec["params"]) == share
