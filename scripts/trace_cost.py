#!/usr/bin/env python3
"""What the package's host spans cost on this host's CPU, microseconds
(``docs/TRACING.md`` "What it costs"). No device is needed:

    JAX_PLATFORMS=cpu python3 scripts/trace_cost.py

Three ways of ``profiling/trace.span`` / ``step_span`` are timed on the same
loops: ``record`` (the package as it is: the record always, an annotation
inside a session), ``annotation`` (a bare ``TraceAnnotation``, its counts
never called: the package before it kept a record) and ``nothing`` (a null
context manager: the loop itself). The loops: one span without counts; the
five spans of a ``train.step``; and ``serve.step`` of the benchmark's
``batch-decode`` traffic (96 slots of 32 pages, prompts 64-256 x outputs
16-80, decode block 4) through the real scheduler over an executor that
computes nothing and opens the serving engine's spans as the engine does,
so that every counts function runs on the slot array's real state; the loops
also mark the device drained after each wait and fed at each dispatch as the
engines do (``trace.drained``, ``trace.fed``: a ``device.starved`` event a
starvation), which the other two ways leave out. Then the
first loop again inside a profiler session (host tracer level 2, no Python
tracer: the benchmark's).
"""

import contextlib
import itertools
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from deepspeed_tpu.inference.serving import (  # noqa: E402
    ContinuousBatchingScheduler, Request, bucket_for)
from deepspeed_tpu.inference.serving.model import DecodeFacts  # noqa: E402
from deepspeed_tpu.profiling import trace  # noqa: E402

RECORD = (trace.span, trace.step_span, trace.drained, trace.fed)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        pass


_NULL = _Null()
WAYS = {
    "record": RECORD,
    "annotation": (lambda name, counts=None:
                   jax.profiler.TraceAnnotation(name),
                   lambda name, step: jax.profiler.StepTraceAnnotation(
                       name, step_num=int(step)),
                   lambda wait: None, lambda by: None),
    "nothing": (lambda name, counts=None: _NULL, lambda name, step: _NULL,
                lambda wait: None, lambda by: None),
}


@contextlib.contextmanager
def way(name):
    trace.span, trace.step_span, trace.drained, trace.fed = WAYS[name]
    try:
        yield
    finally:
        trace.span, trace.step_span, trace.drained, trace.fed = RECORD


def one_span(n):
    for _ in range(n):
        with trace.span(trace.SERVE_GROW):
            pass


def counted_span(n):
    for _ in range(n):
        with trace.span(trace.SERVE_DECODE, lambda: {"steps": 1, "active": 2}):
            pass


def train_step(n):
    for k in range(n):
        with trace.step_span(trace.TRAIN_STEP, k):
            with trace.span(trace.TRAIN_PLACE_BATCH):
                pass
            with trace.span(trace.TRAIN_DISPATCH):
                trace.fed("train_batch")
            with trace.span(trace.TRAIN_SYNC) as wait:
                pass
            trace.drained(wait)
            with trace.span(trace.TRAIN_POST):
                pass


class SpanningExecutor:
    """``ServingEngine``'s executor surface with its spans and their counts
    (``inference/serving/engine.py``: ``prefill``, ``prefill_many``,
    ``decode``), its marks of a device drained and fed, and no model under
    them."""

    CHUNK, BUCKETS, LADDER = 128, (32, 64, 128), (2, 4)

    def __init__(self, cache_layers: int):
        # the model's half of a serve.decode span: here the layers alone
        self.decode_counts = DecodeFacts(
            page_size=64, cache_layers=cache_layers).counts

    def prefill(self, slot, tokens, table_row, start=0):
        T = len(tokens)
        if T <= self.CHUNK:
            chunk = bucket_for(T, self.BUCKETS)
            with trace.span(trace.ENGINE_PREFILL_FUSED, lambda: {
                    "real_tokens": T, "padded_tokens": chunk,
                    "head_tokens": 1}):
                trace.fed("prefill_fused")
            with trace.span(trace.ENGINE_PREFILL_SAMPLE) as wait:
                pass
            trace.drained(wait)
            return 1
        with trace.span(trace.ENGINE_PREFILL_SCRATCH):
            pass
        pos = 0
        while pos < T:
            rem = T - pos
            chunk = (self.CHUNK if rem >= self.CHUNK
                     else bucket_for(rem, self.BUCKETS))
            with trace.span(trace.ENGINE_PREFILL_CHUNK, lambda: {
                    "real_tokens": min(rem, chunk), "padded_tokens": chunk,
                    "paged_tokens": 0, "head_tokens": int(rem <= chunk)}):
                trace.fed("prefill_chunk")
            pos += chunk
        with trace.span(trace.ENGINE_PREFILL_SCATTER):
            trace.fed("scatter")
        with trace.span(trace.ENGINE_PREFILL_SAMPLE) as wait:
            pass
        trace.drained(wait)
        return 1

    def prefill_many(self, items):
        out = {it[0]: self.prefill(*it) for it in items
               if len(it[1]) > self.CHUNK}
        short = [it for it in items if len(it[1]) <= self.CHUNK]
        if len(short) == 1:
            out[short[0][0]] = self.prefill(*short[0])
        elif short:
            chunk = bucket_for(max(len(it[1]) for it in short), self.BUCKETS)
            for at in range(0, len(short), self.LADDER[-1]):
                group = short[at:at + self.LADDER[-1]]
                rows = bucket_for(len(group), self.LADDER)
                with trace.span(trace.ENGINE_PREFILL_BATCH, lambda: {
                        "real_tokens": sum(len(it[1]) for it in group),
                        "padded_tokens": rows * chunk,
                        "head_tokens": rows}):
                    trace.fed("prefill_batch")
            with trace.span(trace.ENGINE_PREFILL_SAMPLE) as wait:
                pass
            trace.drained(wait)
            out.update((it[0], 1) for it in short)
        return out

    def decode(self, tokens, tables, lengths, active, steps=1):
        with trace.span(trace.ENGINE_DECODE_ENQUEUE):
            trace.fed("decode_block")
        with trace.span(trace.ENGINE_DECODE_FETCH) as wait:
            out = np.ones((steps, len(tokens)), np.int32)
        trace.drained(wait)
        return out


def serve_steps(n):
    """Seconds of each of ``n`` ``scheduler.step()`` calls under
    ``batch-decode``'s closed loop, the top-up between steps left out, and
    the spans a step opened. The grid's order is fixed, so step ``i`` does
    the same work in every call."""
    sched = ContinuousBatchingScheduler(
        SpanningExecutor(cache_layers=48), num_slots=96, num_pages=481,
        page_size=64, pages_per_seq=32, decode_block=4,
        clock=time.perf_counter, dispatch_retries=0)
    grid = itertools.cycle(np.random.default_rng(23).permutation(
        list(itertools.product((64, 96, 128, 160, 192, 224, 256),
                               (16, 32, 48, 64, 80)))).tolist())

    def top_up():
        for _ in range(96 - len(sched.active_slots) - len(sched.queue)):
            p, o = next(grid)
            sched.submit(Request(prompt=np.ones(p, np.int32),
                                 max_new_tokens=o))

    for _ in range(200):                   # every slot has turned over
        top_up()
        sched.step()
    before, spent = len(trace.recorded()), []
    for _ in range(n):
        top_up()
        t0 = time.perf_counter()
        sched.step()
        spent.append(time.perf_counter() - t0)
    spans = (len(trace.recorded()) - before) / n
    sched.close()
    return spent, spans


def per(loop, n, repeats=7):
    """Microseconds a turn of ``loop(n)``: the least of ``repeats``, since
    whatever else the host does only ever adds."""
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        loop(n)
        times.append((time.perf_counter() - t0) / n * 1e6)
    return min(times)


def main():
    out = {"host_cpus": os.cpu_count()}
    for name in WAYS:
        with way(name):
            out[f"span_us.{name}"] = per(one_span, 200_000)
            out[f"train_step_us.{name}"] = per(train_step, 40_000)
    with way("record"):
        out["span_with_counts_us.record"] = per(counted_span, 200_000)
    # the scheduler's own work a step (a few hundred microseconds of Python)
    # varies more than the spans cost. Step i does the same work in every
    # round, and what else the host does only ever adds: the ways alternate
    # over many rounds, each step counts at the least any round of its way
    # took for it, and the rounds' medians are printed beside that
    rounds = {name: [] for name in WAYS}
    spans = 0.0
    for _ in range(15):
        for name in WAYS:
            trace.clear()
            with way(name):
                secs, n = serve_steps(400)
            rounds[name].append(secs)
            spans = n if name == "record" else spans
    for name, runs in rounds.items():
        out[f"serve_step_us.{name}"] = 1e6 * statistics.mean(
            min(step) for step in zip(*runs))
        out[f"serve_step_median_us.{name}"] = 1e6 * statistics.median(
            statistics.mean(run) for run in runs)
    out["serve_step_spans"] = spans
    for key in ("train_step_us", "serve_step_us"):
        out[f"{key}.record_less_nothing"] = (out[f"{key}.record"]
                                             - out[f"{key}.nothing"])
        out[f"{key}.record_less_annotation"] = (out[f"{key}.record"]
                                                - out[f"{key}.annotation"])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            for name in ("record", "annotation"):
                with way(name):
                    out[f"span_in_session_us.{name}"] = per(one_span, 20_000)
        finally:
            jax.profiler.stop_trace()
    print(json.dumps({k: round(v, 3) for k, v in out.items()}, indent=1))


if __name__ == "__main__":
    main()
