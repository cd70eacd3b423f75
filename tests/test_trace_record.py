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
    trace.clear()
    yield
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
    got = trace.recorded()
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
        record = [e for e in trace.recorded() if e.name != trace.XLA_COMPILE]
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
    assert trace.grouped_stats(_routed_layers(impl)) == {
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
