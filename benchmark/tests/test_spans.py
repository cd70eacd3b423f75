"""Spans and the readers that read them."""

import numpy as np
import pytest

from benchmark.lib.context import Context
from benchmark.lib.spans import ExecutorProxy, SpanLog
from benchmark.lib.window import Window
from benchmark.readers import (request_percentile, span_mean, span_per_group,
                               span_per_work, window_per_step, window_rate)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class Engine:
    num_slots = 4

    def __init__(self, clock):
        self.clock = clock

    def prefill_many(self, items):
        self.clock.t += 0.1 * len(items)
        return {it[0]: 7 for it in items}

    def decode(self, tokens, tables, lengths, active, steps=1):
        self.clock.t += 0.3
        return np.zeros((steps, len(tokens)), np.int32)


def drive(clock, log, proxy, cycles):
    for n_admit in cycles:
        with log.span("sched.step"):
            clock.t += 0.01                       # the scheduler's own time
            if n_admit:
                proxy.prefill_many([(i, np.zeros(50, np.int32), None)
                                    for i in range(n_admit)])
            proxy.decode(np.zeros(4, np.int32), None, np.full(4, 10),
                         np.array([True, True, True, False]), steps=2)


def make_ctx(clock, log, requests=()):
    w = Window(0.0, clock.t, [{"out_tokens": 8, "prompt_tokens": 100}] * 3,
               [clock.t / 3] * 3)
    return Context(cell={}, window=w, spans=log, requests=list(requests),
                   facts={}, device_kind="none", chips=2, setup_s=1.0)


def test_proxy_forwards_and_records():
    clock = Clock()
    log = SpanLog(clock)
    proxy = ExecutorProxy(Engine(clock), log)
    assert proxy.num_slots == 4                   # everything else forwards
    drive(clock, log, proxy, [2, 0, 1])
    names = [s.name for s in log.spans]
    assert names == ["sched.step", "prefill", "decode", "sched.step", "decode",
                     "sched.step", "prefill", "decode"]
    pre = log.named("prefill")
    assert [s.meta["tokens"] for s in pre] == [100, 50]
    assert [s.meta["requests"] for s in pre] == [2, 1]
    dec = log.named("decode")[0]
    assert dec.meta == {"steps": 2, "active": 3, "live_kv_tokens": 30}
    assert log.spans[1].parent == 0 and log.spans[0].parent is None


def test_span_readers():
    clock = Clock()
    log = SpanLog(clock)
    drive(clock, log, ExecutorProxy(Engine(clock), log), [2, 0, 1])
    ctx = make_ctx(clock, log)
    assert span_mean.read(ctx, {"span": "decode", "scale": 1000.0}) == \
        pytest.approx(300.0)
    assert span_mean.read(ctx, {"span": "sched.step", "self": True,
                                "scale": 1000.0}) == pytest.approx(10.0)
    # admission cycles that admitted: 0.2 s and 0.1 s
    assert span_per_group.read(ctx, {"span": "prefill"}) == pytest.approx(0.15)
    assert span_per_work.read(ctx, {"span": "prefill", "work": "tokens",
                                    "scale": 1e6}) == pytest.approx(2000.0)
    assert span_mean.read(ctx, {"span": "train_batch"}) is None
    assert window_rate.read(ctx, {"work": "out_tokens"}) == pytest.approx(
        24 / clock.t)
    assert window_rate.read(ctx, {"work": "out_tokens", "per_chip": True}) == \
        pytest.approx(12 / clock.t)
    assert window_per_step.read(ctx, {}) == pytest.approx(clock.t / 3)


class Req:
    def __init__(self, t_submit, t_first, t_done, n):
        self.t_submit, self.t_first_token, self.t_done = (t_submit, t_first,
                                                          t_done)
        self.tokens = [0] * n


def test_request_percentiles_keep_to_the_window():
    clock = Clock()
    clock.t = 10.0
    reqs = [Req(1.0, 1.5, 3.5, 5),        # ttft 0.5, tpot 0.5
            Req(2.0, 2.1, 9.0, 1),        # one token: no tpot
            Req(8.0, 9.0, None, 3),       # first token inside, not finished
            Req(9.5, None, None, 0)]      # no first token yet
    ctx = make_ctx(clock, SpanLog(clock), reqs)
    assert request_percentile.read(
        ctx, {"quantity": "ttft", "p": 95}) == pytest.approx(1.0)
    assert request_percentile.read(
        ctx, {"quantity": "tpot", "p": 50}) == pytest.approx(0.5)
    with pytest.raises(ValueError):
        request_percentile.read(ctx, {"quantity": "e2e", "p": 50})
