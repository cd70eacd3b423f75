"""The record of ``profiling/trace.py``: every host span is kept in a bounded
ring of the program's own, whether or not a profiler session is on, with the
step it lies in and its counts; compiles join it as ``xla.compile`` events;
``recorded`` and ``slowest`` read it; inside a session the profile and the
record hold the same spans. All on the CPU: the record is host code."""

import collections
import contextlib
import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt as G
from deepspeed_tpu.profiling import trace


@pytest.fixture(autouse=True)
def fresh_record():
    trace.drained(None)
    trace.clear()
    yield
    trace.drained(None)
    trace.clear()


def test_a_span_outside_a_session_is_recorded():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    before = time.perf_counter()
    with trace.step_span(trace.SERVE_STEP, 41):
        with trace.span(trace.SERVE_DECODE,
                        lambda: {"steps": 2, "active": 3}):
            pass
    with trace.span(trace.ENGINE_PREFILL_SCRATCH):    # inside no step
        pass
    after = time.perf_counter()
    step, decode, scratch = trace.recorded()
    assert (step.name, step.step, step.counts) == (trace.SERVE_STEP, 41, {})
    assert (decode.name, decode.step) == (trace.SERVE_DECODE, 41)
    assert decode.counts == {"steps": 2, "active": 3}
    assert (scratch.name, scratch.step, scratch.counts) == (
        trace.ENGINE_PREFILL_SCRATCH, None, {})
    # on time.perf_counter's clock, in seconds: what the harness stamps with
    for e in (step, decode, scratch):
        assert before <= e.t0 <= e.t1 <= after and e.dur == e.t1 - e.t0


def test_set_metadata_reaches_the_record():
    """Counts known once the work is done (a routed decode's): both kinds of
    span forward them, and they join what the span was made with."""
    with trace.span(trace.SERVE_DECODE, lambda: {"steps": 1}) as decoding:
        decoding.set_metadata(**trace.routing_stats(
            np.array([[6, 2, 3, 2], [6, 1, 2, 4]], np.int32)))
    with trace.span(trace.SERVE_COMMIT) as bare:
        bare.set_metadata(rows=5)
    decode, commit = trace.recorded()
    assert decode.counts == {"steps": 1, "routed_total": 12,
                             "routed_local": 3, "experts_hit": 5,
                             "expert_load_max": 4}
    assert set(trace.ROUTING_STATS) < set(decode.counts)
    assert commit.counts == {"rows": 5}


def test_a_count_set_once_the_work_is_done_replaces_the_one_made_with():
    """``trace.FRESH_STATS``: ``serve.decode`` is made with ``fresh`` and a
    ``fresh_on_device`` of 0; the executor's count of the staged dispatch it
    answered takes its place when the tokens are back."""
    with trace.span(trace.SERVE_DECODE, lambda: {
            "steps": 1, "fresh": 3, "fresh_on_device": 0}) as decoding:
        decoding.set_metadata(fresh_on_device=2)
    (decode,) = trace.recorded()
    assert decode.counts == {"steps": 1, "fresh": 3, "fresh_on_device": 2}
    assert trace.FRESH_STATS == ("fresh", "fresh_on_device")


def test_nesting_is_by_containment():
    """An entry holds no parent: a span lies inside the one whose interval
    contains it, and ``recorded`` lists the outer one first."""
    with trace.step_span(trace.SERVE_STEP, 0):
        with trace.span(trace.SERVE_ADMIT_PREFILL):
            with trace.span(trace.ENGINE_PREFILL_FUSED):
                pass
            with trace.span(trace.ENGINE_PREFILL_SAMPLE):
                pass
        with trace.span(trace.SERVE_DECODE):
            pass
    got = trace.recorded()
    assert [e.name for e in got] == [
        trace.SERVE_STEP, trace.SERVE_ADMIT_PREFILL,
        trace.ENGINE_PREFILL_FUSED, trace.ENGINE_PREFILL_SAMPLE,
        trace.SERVE_DECODE]
    step, admit, fused, sample, decode = got
    for outer, inner in ((step, admit), (admit, fused), (admit, sample),
                         (step, decode)):
        assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert fused.t1 <= sample.t0 and admit.t1 <= decode.t0


def test_a_step_inside_a_step_restores_the_outer_number():
    with trace.step_span(trace.TRAIN_STEP, 1):
        with trace.step_span(trace.SERVE_STEP, 9):
            with trace.span(trace.SERVE_GROW):
                pass
        with trace.span(trace.TRAIN_POST):
            pass
    with trace.span(trace.TRAIN_POST):
        pass
    assert [(e.name, e.step) for e in trace.recorded()] == [
        (trace.TRAIN_STEP, 1), (trace.SERVE_STEP, 9), (trace.SERVE_GROW, 9),
        (trace.TRAIN_POST, 1), (trace.TRAIN_POST, None)]


def test_the_ring_drops_the_oldest(monkeypatch):
    assert trace._ring.maxlen == trace.RECORD_SPANS == 65_536
    monkeypatch.setattr(trace, "_ring", collections.deque(maxlen=4))
    for i in range(7):
        with trace.span(trace.SERVE_GROW, lambda i=i: {"i": i}):
            pass
    assert [e.counts["i"] for e in trace.recorded()] == [3, 4, 5, 6]
    trace.clear()
    assert trace.recorded() == []


def test_recorded_since_filters():
    for i in range(3):
        with trace.span(trace.SERVE_GROW, lambda i=i: {"i": i}):
            pass
    first, second, third = trace.recorded()
    assert trace.recorded(since=second.t0) == [second, third]
    assert trace.recorded(since=third.t1 + 1.0) == []
    assert trace.recorded(since=0.0) == [first, second, third]


def test_threads_append_without_loss():
    """More threads than cores, a short switch interval: every span of every
    thread is in the ring, under its own thread's step number."""
    threads, spans = min(4 * (os.cpu_count() or 2), 64), 400
    assert threads * (spans + 1) < trace.RECORD_SPANS

    def work(k):
        with trace.step_span(trace.TRAIN_STEP, k):
            for i in range(spans):
                with trace.span(trace.TRAIN_POST, lambda: {"thread": k}):
                    pass

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=work, args=(k,))
                for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(was)
    got = [e for e in trace.recorded() if e.name != trace.HOST_GC]
    assert len(got) == threads * (spans + 1)
    posts = [e for e in got if e.name == trace.TRAIN_POST]
    assert all(e.step == e.counts["thread"] for e in posts)
    assert collections.Counter(e.step for e in posts) == {
        k: spans for k in range(threads)}


def test_a_compile_inside_a_step_is_recorded_with_its_number():
    def never_compiled_before(x):
        return x * 3 + 1

    x = jnp.ones(3)
    jax.block_until_ready(x)
    trace.clear()
    with trace.step_span(trace.TRAIN_STEP, 12):
        with trace.span(trace.TRAIN_DISPATCH):
            jax.block_until_ready(jax.jit(never_compiled_before)(x))
    with trace.step_span(trace.TRAIN_STEP, 13):
        with trace.span(trace.TRAIN_DISPATCH):
            jax.block_until_ready(jax.jit(never_compiled_before)(x))
    got = trace.recorded()
    compiles = [e for e in got if e.name == trace.XLA_COMPILE]
    mine = [e for e in compiles
            if "never_compiled_before" in e.counts["fun_name"]]
    assert len(mine) == 1 and mine[0].step == 12 and mine[0].dur > 0
    assert all(e.step == 12 for e in compiles)        # none in the warm step
    (step,) = [e for e in got if e.name == trace.TRAIN_STEP and e.step == 12]
    (dispatch,) = [e for e in got
                   if e.name == trace.TRAIN_DISPATCH and e.step == 12]
    # the event ends at the instant the compile reports and lasts its seconds
    assert step.t0 <= dispatch.t0 <= mine[0].t0 <= mine[0].t1 <= dispatch.t1
    slow = trace.slowest(trace.TRAIN_STEP, n=1)[0]
    assert slow.step.step == 12
    assert any("never_compiled_before" in f for f in slow.compiled)
    assert slow.seconds[trace.XLA_COMPILE] >= mine[0].dur


def test_slowest_names_the_step_made_slow_and_the_span_that_held_it():
    for k in range(6):
        with trace.step_span(trace.SERVE_STEP, k):
            with trace.span(trace.SERVE_ADMIT_CLAIM):
                pass
            with trace.span(trace.SERVE_DECODE):
                with trace.span(trace.ENGINE_DECODE_FETCH):
                    if k == 4:
                        time.sleep(0.05)
    with trace.step_span(trace.TRAIN_STEP, 4):        # another step's name
        time.sleep(0.06)
    first, second, third = trace.slowest(trace.SERVE_STEP)
    assert first.step.step == 4 and first.step.dur >= 0.05
    assert first.step.dur > second.step.dur >= third.step.dur
    assert first.seconds[trace.ENGINE_DECODE_FETCH] >= 0.05
    assert first.seconds[trace.SERVE_DECODE] >= first.seconds[
        trace.ENGINE_DECODE_FETCH]
    assert first.seconds[trace.SERVE_ADMIT_CLAIM] < 0.01
    assert set(first.seconds) == {trace.SERVE_ADMIT_CLAIM, trace.SERVE_DECODE,
                                  trace.ENGINE_DECODE_FETCH}
    assert first.compiled == []
    assert len(trace.slowest(trace.SERVE_STEP, n=2)) == 2
    # since: the steps that began at or after it
    after = [s.step.step for s in trace.slowest(
        trace.SERVE_STEP, n=9, since=first.step.t1)]
    assert after == [5]
    assert trace.slowest("no.such.step") == []


# ------------------------------------------- a device run dry and fed again
def test_a_drain_then_a_feed_is_one_event():
    """From the wait's own exit stamp to the return of the dispatch that
    followed, under the step the dispatch lies in."""
    with trace.step_span(trace.SERVE_STEP, 6):
        with trace.span(trace.ENGINE_DECODE_FETCH) as wait:
            pass
        trace.drained(wait)
    before = time.perf_counter()
    with trace.step_span(trace.SERVE_STEP, 7):
        trace.fed("decode_block_4")
    fetch = [e for e in trace.recorded()
             if e.name == trace.ENGINE_DECODE_FETCH][0]
    (starved,) = [e for e in trace.recorded()
                  if e.name == trace.DEVICE_STARVED]
    assert starved.counts == {"after": trace.ENGINE_DECODE_FETCH,
                              "by": "decode_block_4"}
    assert starved.step == 7 and fetch.step == 6
    assert starved.t0 == fetch.t1 and starved.t1 >= before


def test_a_feed_with_no_drain_writes_nothing():
    with trace.step_span(trace.TRAIN_STEP, 0):
        trace.fed("train_batch")
        trace.fed("train_batch")
    assert [e.name for e in trace.recorded()] == [trace.TRAIN_STEP]


def test_only_the_first_feed_after_a_drain_writes():
    with trace.span(trace.TRAIN_SYNC) as wait:
        pass
    trace.drained(wait)
    for by in ("prefill_chunk_512", "scatter", "decode_block_4"):
        trace.fed(by)
    (starved,) = [e for e in trace.recorded()
                  if e.name == trace.DEVICE_STARVED]
    assert starved.counts == {"after": trace.TRAIN_SYNC,
                              "by": "prefill_chunk_512"}
    assert starved.step is None           # fed outside any step


def test_a_mark_dropped_leaves_no_event():
    """Where the engine cannot know what the device holds (a dispatch
    episode that failed), the time up to the next dispatch is not counted."""
    with trace.span(trace.ENGINE_DECODE_FETCH) as wait:
        pass
    trace.drained(wait)
    trace.drained(None)
    trace.fed("decode_block_1")
    assert trace.DEVICE_STARVED not in {e.name for e in trace.recorded()}


def test_the_mark_is_per_thread():
    """An engine is driven by one thread: another thread's dispatch does not
    end this one's starvation, nor see it."""
    with trace.span(trace.ENGINE_DECODE_FETCH) as wait:
        pass
    trace.drained(wait)
    other = threading.Thread(target=trace.fed, args=("train_batch",))
    other.start()
    other.join(timeout=10)
    assert trace.DEVICE_STARVED not in {e.name for e in trace.recorded()}
    trace.fed("decode_block_2")
    (starved,) = [e for e in trace.recorded()
                  if e.name == trace.DEVICE_STARVED]
    assert starved.counts["by"] == "decode_block_2"


def test_slowest_lists_a_starvation_by_its_part_inside_the_step():
    """The event begins under one step's fetch and ends at the next step's
    first dispatch: each step's account holds the part that lies in it."""
    with trace.step_span(trace.SERVE_STEP, 3):
        with trace.span(trace.ENGINE_DECODE_FETCH) as wait:
            pass
        trace.drained(wait)
        time.sleep(0.02)                  # serve.commit
    with trace.step_span(trace.SERVE_STEP, 4):
        time.sleep(0.05)                  # a slow claim before the dispatch
        trace.fed("prefill_fused_128")
    (starved,) = [e for e in trace.recorded()
                  if e.name == trace.DEVICE_STARVED]
    slow, fast = trace.slowest(trace.SERVE_STEP, n=2)
    assert (slow.step.step, fast.step.step) == (4, 3)
    assert 0.05 <= slow.seconds[trace.DEVICE_STARVED] <= slow.step.dur
    assert 0.02 <= fast.seconds[trace.DEVICE_STARVED] <= fast.step.dur
    assert (slow.seconds[trace.DEVICE_STARVED]
            + fast.seconds[trace.DEVICE_STARVED]) <= starved.dur


def test_a_long_collection_is_an_event_and_a_short_one_is_not():
    """``host.gc``: a collection of a millisecond or more, with its
    generation and the step it fell in; the thousands of short ones a
    second are not kept."""
    import gc

    assert trace._on_gc in gc.callbacks
    gc.collect()                          # whatever the tests before left
    trace.clear()
    with trace.step_span(trace.SERVE_STEP, 2):
        gc.collect(0)                     # nothing to do: microseconds
    assert trace.HOST_GC not in {e.name for e in trace.recorded()}
    junk = []
    for _ in range(200_000):              # cycles for the oldest generation
        a = []
        a.append(a)
        junk.append(a)
    del junk, a
    with trace.step_span(trace.SERVE_STEP, 3):
        gc.collect()
    kept = [e for e in trace.recorded() if e.name == trace.HOST_GC]
    assert kept and all(e.dur >= trace.GC_KEPT_NS * 1e-9 for e in kept)
    assert kept[-1].counts == {"generation": 2} and kept[-1].step == 3
    (slow,) = trace.slowest(trace.SERVE_STEP, n=1)
    assert slow.step.step == 3 and slow.seconds[trace.HOST_GC] >= 1e-3


# ------------------------------------------------- the two sinks in a session
CFG = G.GPTConfig(vocab_size=64, d_model=32, n_layer=2, n_head=4,
                  max_seq_len=128)


@contextlib.contextmanager
def _session(trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _profiled(trace_dir):
    """[(name, seconds)] of the program's spans in the profile, by start."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.start_ns, ev.name, ev.duration_ns * 1e-9)
                        for ev in line.events
                        if ev.name.startswith(trace.SPAN_PREFIXES)]
    return [(name, dur) for _, name, dur in sorted(out)]


def test_the_profile_and_the_record_hold_the_same_spans(tmp_path):
    """One ``with`` writes both sinks. The record's two clock reads lie
    inside the annotation's (one C call apart at either end), so a step's
    duration in the record is the profile's less about a microsecond; a
    thread switched out between the two reads adds a scheduling quantum, so
    each step is held to 5 ms and their median to 0.1 ms."""
    from deepspeed_tpu.inference.serving import (Request, ServingConfig,
                                                 ServingEngine)

    engine = ServingEngine(
        CFG, G.init_params(CFG, jax.random.PRNGKey(0)), ServingConfig(
            num_slots=3, page_size=8, max_model_len=64, prefill_chunk=16,
            dtype="float32", decode_block=2, max_queue=64))
    sched = engine.make_scheduler(clock=time.perf_counter)
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=rng.integers(1, 64, n).astype(np.int32),
                    max_new_tokens=m)
            for n, m in [(5, 8), (9, 6), (40, 9), (12, 4)]]
    with _session(str(tmp_path)):
        trace.clear()
        for r in reqs:
            sched.submit(r)
        sched.run_to_completion()
        everything = trace.recorded()
        # xla.compile, device.starved and host.gc are the record's alone
        record = [e for e in everything
                  if e.name.startswith(trace.SPAN_PREFIXES)]
        assert {e.name for e in everything} - {e.name for e in record} <= {
            trace.XLA_COMPILE, trace.DEVICE_STARVED, trace.HOST_GC}
        assert trace.DEVICE_STARVED in {e.name for e in everything}
    profile = _profiled(str(tmp_path))
    assert collections.Counter(n for n, _ in profile) == collections.Counter(
        e.name for e in record)
    assert {trace.SERVE_STEP, trace.SERVE_ADMIT_PREFILL,
            trace.ENGINE_PREFILL_CHUNK, trace.ENGINE_PREFILL_SAMPLE,
            trace.ENGINE_DECODE_FETCH} <= {e.name for e in record}
    # the record keeps a span's counts: a plain engine's chunks wrote pages
    chunks = [e.counts for e in record
              if e.name == trace.ENGINE_PREFILL_CHUNK]
    assert chunks and all(c["paged_tokens"] == c["padded_tokens"] == 16
                          for c in chunks)
    assert {c["head_tokens"] for c in chunks} == {0, 1}
    assert trace.ENGINE_PREFILL_SCRATCH not in {e.name for e in record}
    theirs = [d for n, d in profile if n == trace.SERVE_STEP]
    ours = [e.dur for e in record if e.name == trace.SERVE_STEP]
    apart = [a - b for a, b in zip(theirs, ours)]
    assert len(apart) >= 4
    assert all(-1e-6 <= d <= 5e-3 for d in apart), apart
    assert sorted(apart)[len(apart) // 2] <= 1e-4, apart
    # a request's stamps are on the record's clock: its admission cycle is
    # the serve.admit.prefill whose rids hold it, and contains t_admit ..
    # t_first_token
    for r in reqs:
        (cycle,) = [e for e in record if e.name == trace.SERVE_ADMIT_PREFILL
                    and str(r.rid) in e.counts["rids"].split()]
        assert r.t_submit <= r.t_admit <= cycle.t0
        assert cycle.t1 <= r.t_first_token <= r.t_done


# ------------------------------------- the engines' marks of drained and fed
DISPATCHES = (trace.ENGINE_PREFILL_FUSED, trace.ENGINE_PREFILL_CHUNK,
              trace.ENGINE_PREFILL_BATCH, trace.ENGINE_PREFILL_SCATTER,
              trace.ENGINE_DECODE_ENQUEUE)


def _engine(draft=None, **serving):
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    return ServingEngine(
        CFG, G.init_params(CFG, jax.random.PRNGKey(0)), ServingConfig(**{
            **dict(num_slots=3, page_size=8, max_model_len=64,
                   prefill_chunk=16, dtype="float32", decode_block=2,
                   max_queue=64), **serving}), draft=draft)


def _starved(entries, after=None):
    return [e for e in entries if e.name == trace.DEVICE_STARVED
            and after in (None, e.counts["after"])]


def _no_starvation_lasts_through_a_dispatch(entries, more=()):
    """Every dispatch feeds: a starvation ends inside the first span that
    dispatches a program after it began (or the first of ``more``, the
    intervals of dispatches that have no span), never after it."""
    spans = [(e.t0, e.t1, e.name) for e in entries
             if e.name in DISPATCHES] + list(more)
    for s in _starved(entries):
        through = [n for a, b, n in spans if s.t0 <= a and b < s.t1]
        assert not through, (s, through)


@pytest.mark.parametrize("staged", [False, True])
def test_an_admissions_wait_leaves_the_device_dry_unless_a_decode_is_queued(
        staged):
    """``prefill_many`` marks the device drained at the exit of
    ``engine.prefill.sample`` only where nothing was queued behind the
    prompts; the decode's fetch always does."""
    engine = _engine(num_slots=4)
    prompts = [np.ones(5, np.int32), np.ones(9, np.int32)]
    tables = np.zeros((4, engine.serving.pages_per_seq), np.int32)
    tables[0, :1], tables[1, :2] = 1, (2, 3)
    lens = np.array([5, 9, 0, 0], np.int32)
    if staged:
        engine.stage_decode(
            lambda: (np.zeros(4, np.int32), tables, lens, lens > 0, 1))
    trace.clear()
    firsts = engine.prefill_many(
        [(j, p, tables[j]) for j, p in enumerate(prompts)])
    (sample,) = [e for e in trace.recorded()
                 if e.name == trace.ENGINE_PREFILL_SAMPLE]
    if staged:
        assert trace._here.dry is None
    else:
        assert trace._here.dry == (round(sample.t1 * 1e9), sample.name)
    assert not _starved(trace.recorded())
    engine.decode(np.array([firsts[0], firsts[1], 0, 0], np.int32), tables,
                  lens, lens > 0, steps=1)
    assert engine.decode_fresh_on_device == (2 if staged else 0)
    got = trace.recorded()
    (fetch,) = [e for e in got if e.name == trace.ENGINE_DECODE_FETCH]
    assert trace._here.dry == (round(fetch.t1 * 1e9), fetch.name)
    if staged:      # the decode only fetched: nothing ran dry in between
        assert not _starved(got)
    else:
        (starved,) = _starved(got)
        assert starved.counts == {"after": trace.ENGINE_PREFILL_SAMPLE,
                                  "by": "decode_block_1"}
        assert starved.t0 == sample.t1 and starved.t1 <= fetch.t0


def _fake_scheduler():
    """``scripts/trace_cost.py``'s executor: the engine's spans and marks
    with no model under them, every prompt over its chunk on the dense
    path."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    try:
        import trace_cost
    finally:
        sys.path.pop(0)
    from deepspeed_tpu.inference.serving import ContinuousBatchingScheduler

    return ContinuousBatchingScheduler(
        trace_cost.SpanningExecutor(cache_layers=2), num_slots=3,
        num_pages=25, page_size=64, pages_per_seq=8, decode_block=2,
        clock=time.perf_counter), 5, "prefill_chunk"


def _dense_scheduler():
    engine = _engine()
    engine._chunk_to_pages = False      # as latent rows or key-value heads
    return engine.make_scheduler(clock=time.perf_counter), 1, \
        "prefill_chunk_16"


@pytest.mark.parametrize("make", [_dense_scheduler, _fake_scheduler])
def test_a_dense_admission_of_two_prompts_runs_dry_between_them(make):
    """A prompt on the dense path is waited for where it ends: from there
    to the return of the next prompt's first chunk the device has nothing,
    the scratch cache's zero fill being no program."""
    from deepspeed_tpu.inference.serving import Request

    sched, scale, chunk = make()
    trace.clear()
    for n in (40, 36):
        sched.submit(Request(prompt=np.ones(n * scale, np.int32),
                             max_new_tokens=4))
    sched.step()
    got = trace.recorded()
    (cycle,) = [e for e in got if e.name == trace.SERVE_ADMIT_PREFILL]
    first, second = [e for e in got
                     if e.name == trace.ENGINE_PREFILL_SAMPLE]
    _, scratch = [e for e in got if e.name == trace.ENGINE_PREFILL_SCRATCH]
    between, behind = _starved(got, trace.ENGINE_PREFILL_SAMPLE)
    assert between.counts["by"] == chunk and between.step == cycle.step
    assert between.t0 == first.t1 and between.t1 < second.t0
    assert between.t0 <= scratch.t0 and scratch.t1 <= between.t1
    assert cycle.t0 <= between.t0 and between.t1 <= cycle.t1
    # and after the second prompt, until the decode goes out
    assert behind.t0 == second.t1
    assert behind.counts["by"].startswith("decode_block")
    _no_starvation_lasts_through_a_dispatch(got)
    sched.run_to_completion()
    sched.close()


@pytest.mark.parametrize("kind", ["staged", "dense", "drafted"])
def test_no_starvation_lasts_through_a_dispatch(kind):
    """The call sites are whole: over a run with admissions, staged steps,
    steps that were not staged and, with a draft model, drafted steps, every
    starvation ends at the first dispatch after it began."""
    from deepspeed_tpu.inference.serving import Request

    engine = (_engine(spec_drafter="draft_model", spec_k=4, draft=(
        CFG, G.init_params(CFG, jax.random.PRNGKey(0))))
        if kind == "drafted" else _engine())
    engine._chunk_to_pages = kind != "dense"
    sched = engine.make_scheduler(clock=time.perf_counter)
    drafts = []
    if kind == "drafted":
        inner = sched.drafter.draft

        def draft(*args, **kw):
            t0 = time.perf_counter()
            out = inner(*args, **kw)
            if len(out):
                drafts.append((t0, time.perf_counter(), "draft"))
            return out

        sched.drafter.draft = draft
    rng = np.random.default_rng(1)
    trace.clear()
    for n, m in [(5, 18), (9, 16), (40, 19), (12, 4), (20, 15)]:
        sched.submit(Request(prompt=rng.integers(1, 64, n).astype(np.int32),
                             max_new_tokens=m))
    sched.run_to_completion()
    sched.close()
    got = trace.recorded()
    _no_starvation_lasts_through_a_dispatch(got, drafts)
    steps = [e for e in got if e.name == trace.SERVE_STEP]
    fetched = _starved(got, trace.ENGINE_DECODE_FETCH)
    # a step's fetch is its last wait: one starvation a step but the first
    assert len(fetched) == len(steps) - 1
    assert all(e.counts["by"].startswith((
        "prefill_", "decode_block_", "verify_w", "draft_", "place_first_"))
        for e in _starved(got))
    sampled = _starved(got, trace.ENGINE_PREFILL_SAMPLE)
    if kind == "staged":    # the decode was queued behind every admission
        assert not sampled
    elif kind == "dense":   # the chunked prompt was waited for where it ends
        assert sampled[0].counts["by"] == "prefill_batch_16"
    else:                   # a drafter armed: no step is staged
        assert drafts and sampled
        assert {e.counts["by"].rstrip("0123456789") for e in fetched} >= {
            "draft_feed_"}, collections.Counter(
                (e.counts["after"], e.counts["by"]) for e in _starved(got))


# ------------------------------------------------ the grouped products' counts
def _routed_layers(impl):
    """Three routed layers in a scan, a fourth under a conditional beside a
    branch without one: seven grouped products of a gated layer's three."""
    from deepspeed_tpu.moe import dropless

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((3, 4, 128, 128)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((8, 128)), jnp.float32)
    chosen = jnp.asarray(rng.integers(0, 4, (8, 2)), jnp.int32)
    gates = jnp.ones((8, 2), jnp.float32)

    def layer(x, i):
        return dropless.held_experts_ffn(x, chosen, gates, w, w, w, (0, 4),
                                         layer=i, impl=impl)

    def program(x, flag):
        x, _ = jax.lax.scan(lambda c, i: (layer(c, i), None), x,
                            jnp.arange(3))
        return jax.lax.cond(flag, lambda c: layer(c, 0), lambda c: c, x)

    return jax.make_jaxpr(program)(h, True)


@pytest.mark.parametrize("impl,kernel", [("ragged", 0), ("kernel", 12),
                                         ("auto", 0)])
def test_grouped_stats_count_the_products_a_program_runs(impl, kernel):
    """``trace.GROUPED_STATS``: a scan's body once a trip, a conditional as
    its larger branch; the products that lower to ``grouped_dot`` are told
    by the kernel's name, and off the TPU "auto" lowers none to it."""
    assert trace.kernel_stats(_routed_layers(impl),
                              trace.GROUPED_STATS) == {
        "grouped_products": 12, "grouped_kernel": kernel}
    assert trace.GROUPED_STATS == ("grouped_products", "grouped_kernel")


def test_a_routed_engines_decode_span_says_its_grouped_products():
    """``serve.decode`` of a routed model carries ``GROUPED_STATS``, read
    off the decode program when it is traced for its first dispatch: three
    products a routed layer a step, none through the kernel off the TPU."""
    from test_latent_routed_model import CFG, TestDeepseekV2 as family

    from deepspeed_tpu.inference.serving import Request

    engine = family.new_engine(G.init_params(CFG, jax.random.PRNGKey(0)))
    sched = engine.make_scheduler()
    for row in family.ids(2, 20, seed=3):
        sched.submit(Request(prompt=row, max_new_tokens=6))
    sched.run_to_completion()
    sched.close()
    decodes = [e.counts for e in trace.recorded()
               if e.name == trace.SERVE_DECODE]
    routed = CFG.n_layer - 1            # the leading layer is dense
    assert decodes and all(
        c["grouped_products"] == 3 * routed * c["steps"]
        and c["grouped_kernel"] == 0 for c in decodes)
    assert {c["steps"] for c in decodes} == {1, 2}


# The model's half of a ``serve.decode`` span for the tiny configuration of
# every served family: what the parent's ``scheduler._decode_stats`` said
# (commit c26ffd6, PR 59) over four slots of which three hold 5, 40 and 100
# tokens, two steps a dispatch, the scheduler's own eight stats left out.
DECODE_COUNTS = {
    "tiny-serve": {"cache_layers": 2, "paged_group_tiles": 11,
                   "paged_pages_per_step": 1},
    "tiny-ouro-serve": {"cache_layers": 4, "paged_group_tiles": 11,
                        "paged_pages_per_step": 1},
    "tiny-deepseek-v2-serve": {"cache_layers": 3, "mla_group_tiles": 14,
                               "mla_pages_per_step": 2},
    "tiny-laguna-serve": {"cache_layers": 5, "kv_rows_full": 145,
                          "kv_rows_window": 21, "gqa_group_tiles": 14,
                          "gqa_pages_per_step": 2},
    "tiny-nemotron-h-serve": {"cache_layers": 1, "gqa_group_tiles": 14,
                              "gqa_pages_per_step": 2, "state_slots": 3,
                              "state_bytes": 202752, "state_layers": 3,
                              "kv_rows": 299},
    "tiny-falcon-h1-serve": {"cache_layers": 3, "gqa_group_tiles": 14,
                             "gqa_pages_per_step": 2, "state_slots": 3,
                             "state_bytes": 202752, "state_layers": 3,
                             "kv_rows": 897},
    "tiny-dots3-note-serve": {"cache_layers": 5, "kv_rows_full": 145,
                              "kv_rows_window": 31, "mla_group_tiles": 14,
                              "mla_pages_per_step": 2, "index_rows": 598,
                              "selected_rows": 154},
    "tiny-kimi-linear-serve": {"cache_layers": 2, "mla_group_tiles": 14,
                               "mla_pages_per_step": 2, "state_slots": 3,
                               "state_bytes": 182784, "state_layers": 7,
                               "kv_rows": 598},
    "tiny-brumby-serve": {"cache_layers": 0, "state_slots": 3,
                          "state_bytes": 110592, "state_layers": 3,
                          "kv_rows": 0},
}
MODEL_FACTS = ("cache_layers", "attn_window", "state_bytes", "state_layers",
               "gqa_pages_per_step", "mla_pages_per_step",
               "paged_pages_per_step", "index_layers", "index_topk")


@pytest.mark.parametrize("name", [*sorted(DECODE_COUNTS), None],
                         ids=lambda name: name or "an executor with no model")
def test_a_decode_span_says_the_models_counts_beside_the_schedulers(name):
    """No stat moved when the model's facts left the scheduler: a served
    family's ``model.decode_counts`` is what the parent's scheduler said of
    it (no program is dispatched: host arithmetic over the configuration and
    the serving sizes), the scheduler merges it with its own eight, takes no
    model fact itself, and an executor without ``decode_counts`` (the fake
    executors of the scheduler's own tests) gets the eight alone."""
    import inspect
    import types

    from benchmark.lib import manifest
    from deepspeed_tpu.inference.serving import (
        ContinuousBatchingScheduler, ServingConfig)
    from deepspeed_tpu.inference.serving.model import ServedModel
    from served_contract import config_file

    executor, said = None, {}
    if name is not None:
        config = config_file(name)
        model = ServedModel(
            manifest.family_of(config).config(dict(config["model"])),
            ServingConfig(num_slots=4, **config["engine"]))
        said = model.decode_counts(np.asarray([5, 40, 100]), 2)
        assert said == DECODE_COUNTS[name]
        executor = types.SimpleNamespace(decode_counts=model.decode_counts)
    assert not set(MODEL_FACTS) & set(inspect.signature(
        ContinuousBatchingScheduler.__init__).parameters)
    sched = ContinuousBatchingScheduler(
        executor=executor, num_slots=4, num_pages=33, page_size=16,
        pages_per_seq=8)
    sched.lengths[:] = [0, 5, 40, 100]
    sched._fresh = {2, 0}
    assert sched._decode_stats(
        2, [1, 2, 3], np.asarray([False, True, True, True])) == {
            "steps": 2, "active": 3, "live_kv_tokens": 145,
            "pool_tokens": 512, "live_pages": 1 + 3 + 7, "table_slots": 32,
            "fresh": 1, "fresh_on_device": 0, **said}


def test_reading_a_programs_counts_costs_no_second_trace():
    """``ServingEngine._call`` reads what a program says of itself off
    ``program.trace(*args)`` at its first dispatch, and the dispatch reuses
    that trace: the model's step is traced once a program, not twice (what
    keeps the counts out of ``setup_s``)."""
    engine = _engine()
    traced = collections.Counter()
    step = engine.model.decode_step

    def counting(*args):
        traced["decode_step"] += 1
        return step(*args)

    engine.model.decode_step = counting
    zeros = np.zeros(3, np.int32)
    tables = np.zeros((3, engine.serving.pages_per_seq), np.int32)
    for _ in range(2):
        engine.decode(zeros, tables, zeros, np.zeros(3, bool))
    assert traced["decode_step"] == 1
    assert engine.decode_said == {} and engine.decode_grouped == {}
