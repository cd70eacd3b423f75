"""Serve cells: ``ServingEngine`` + ``ContinuousBatchingScheduler`` under a
closed loop, measured between ``scheduler.step()`` boundaries.

The loop has as many callers as the engine has slots: at each boundary every
caller whose request finished sends its next one, so requests in flight
(running or queued) always number ``slots``. Admission is never starved and
the queue never grows.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from . import correct
from .context import Run
from .device import say
from .manifest import family_of, plugin
from .spans import ExecutorProxy, SpanLog
from .window import run_window


def build(cell: dict, devices, seed: int, setup: Dict[str, float]):
    """Weights from the seed on the device, in the served type; the engine;
    the shapes this traffic uses, warmed."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    config, traffic = cell["config_file"], cell["traffic_file"]
    family = family_of(config)
    cfg = family.config(config["model"])
    eng = dict(config["engine"])
    dtype = jnp.dtype(eng.get("dtype", "bfloat16"))

    t0 = time.perf_counter()
    make = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda x: x.astype(dtype), family.init_params(cfg, key)))
    params = jax.block_until_ready(make(jax.random.PRNGKey(seed)))
    setup["weights"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    engine = ServingEngine(cfg, params, ServingConfig(
        num_slots=int(traffic["slots"]), num_pages=int(traffic["pages"]),
        **eng))
    gen = plugin("generators", traffic["generator"]).Traffic(
        traffic, config["model"]["vocab_size"], seed)
    warm_shapes(engine, gen.prompt_lengths())
    setup["compile_or_load"] = time.perf_counter() - t0
    return cfg, params, engine, gen


def warm_shapes(engine, prompt_lengths: List[int]) -> None:
    """Run once every program this traffic can reach, on the sink page: each
    prompt length alone (fused or chunked path, and the eager index of its
    last chunk), the admission-batch program of each bucket that two short
    prompts can share, and the decode blocks the scheduler picks from."""
    s = engine.serving
    sink = np.zeros(s.pages_per_seq, np.int32)
    short = [n for n in prompt_lengths if n <= s.prefill_chunk]
    for n in prompt_lengths:
        engine.prefill(0, np.zeros(n, np.int32), sink)
    if engine.num_slots >= 2:
        for n in short:  # one call per bucket compiles it; repeats are cheap
            t = np.zeros(n, np.int32)
            engine.prefill_many([(0, t, sink), (1, t, sink)])
    zeros = np.zeros(engine.num_slots, np.int32)
    tables = np.zeros((engine.num_slots, s.pages_per_seq), np.int32)
    mask = np.zeros(engine.num_slots, bool)
    k = 1
    while k <= s.decode_block:
        engine.decode(zeros, tables, zeros, mask, steps=k)
        k *= 2


class ClosedLoop:
    """The callers. ``top_up`` is called at every step boundary."""

    def __init__(self, sched, gen, slots: int):
        from deepspeed_tpu.inference.serving.scheduler import Request

        self._Request = Request
        self.sched, self.gen, self.slots = sched, gen, slots
        self.submitted: list = []

    def _submit(self, item) -> None:
        prompt, n_out = item
        req = self._Request(prompt=prompt, max_new_tokens=int(n_out))
        self.sched.submit(req)     # a refusal shows in the request's state
        self.submitted.append(req)

    def first_fill(self) -> list:
        first = []
        for item in self.gen.first_fill(self.slots):
            self._submit(item)
            first.append(self.submitted[-1])
        return first

    def top_up(self) -> None:
        in_flight = len(self.sched.active_slots) + len(self.sched.queue)
        for _ in range(self.slots - in_flight):
            self._submit(self.gen.next())


def measure(cell: dict, engine, gen, seconds: float, clock, spans: SpanLog,
            on_boundary, clog, setup: Dict[str, float],
            verdict: correct.Verdict) -> Run:
    from deepspeed_tpu.inference.serving.scheduler import RequestState

    sched = engine.make_scheduler(clock=clock)
    sched.executor = ExecutorProxy(engine, spans)
    loop = ClosedLoop(sched, gen, engine.num_slots)

    def step() -> Dict[str, float]:
        loop.top_up()
        n_spans, n_done = len(spans.spans), len(sched.finished)
        with spans.span("sched.step"):
            produced = sched.step()
        new = spans.spans[n_spans:]
        pre = [s for s in new if s.name == "prefill"]
        return {"prompt_tokens": sum(s.meta["tokens"] for s in pre),
                # a prefill appends its request's first output token
                "out_tokens": produced + sum(s.meta["requests"] for s in pre),
                "finished": len(sched.finished) - n_done, "steps": 1}

    # warm-up under the real traffic: until every slot has turned over once
    first = loop.first_fill()
    t0 = clock()
    n_warm = 0
    while any(r.state is RequestState.RUNNING or r.state is RequestState.QUEUED
              for r in first):
        step()
        n_warm += 1
        if n_warm > 10_000:
            raise RuntimeError("first fill never finished")
    setup["warm_up"] = clock() - t0
    say(f"warm-up under traffic: {n_warm} steps")

    n_done, n_sent = len(sched.finished), len(loop.submitted)
    compile_mark = clog.mark()
    window = run_window(step, seconds, clock, on_boundary)

    finished = sched.finished[n_done:]
    sent = loop.submitted[n_sent:]           # submitted inside the window
    problems = []
    wrong = [r for r in finished if len(r.tokens) != r.max_new_tokens]
    if wrong:
        problems.append(f"{len(wrong)} finished requests have a wrong "
                        "token count")
    refused = [r for r in sent
               if r.state in (RequestState.EXPIRED, RequestState.REJECTED)]
    preempted = sum(r.preemptions for r in loop.submitted)
    if preempted:
        problems.append(f"{preempted} preemptions: the pool is too small for "
                        "this traffic, prompt tokens were prefilled twice")
    audit = sched.audit()
    if not audit.get("ok", False):
        problems.append(f"page audit not clean: {audit}")
    first_tokens = sum(1 for r in sent
                       if window.inside(r.t_submit, r.t_first_token))
    need = int(cell["traffic_file"].get("min_ttft_samples", 0))
    if first_tokens < need:
        problems.append(f"{first_tokens} requests were sent and got their "
                        f"first token inside the window, the tail wants {need}")
    sched.close()
    decodes = spans.named("decode", window.t_open, window.t_close)
    facts = {
        "slots": engine.num_slots,
        "mean_decode_block": (sum(s.meta["steps"] for s in decodes)
                              / max(1, len(decodes))),
        "scheduler_steps": len(window.steps),
        "first_tokens_inside": first_tokens,
        "finished_inside": sum(1 for r in sent
                               if window.inside(r.t_submit, r.t_done)),
        "preemptions": preempted,
    }
    return Run(window, len(finished) + len(refused),
               len(wrong) + len(refused), problems, facts, verdict,
               compile_mark, requests=sent)


def run(cell: dict, devices, seed: int, seconds: float, clock, spans: SpanLog,
        capture, setup: Dict[str, float], clog) -> Run:
    cfg, params, engine, gen = build(cell, devices, seed, setup)
    t0 = time.perf_counter()
    verdict = correct.serve_logits(cell, cfg, params, engine, seed)
    setup["reference_check"] = time.perf_counter() - t0
    return measure(cell, engine, gen, seconds, clock, spans, capture, clog,
                   setup, verdict)
