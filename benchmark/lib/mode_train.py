"""Train cells: ``deepspeed_tpu.initialize`` -> ``train_batch`` on seeded
token ids, measured between ``train_batch`` boundaries, whole steps only."""

from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from . import correct
from .context import Run
from .device import say
from .manifest import family_of, plugin
from .spans import SpanLog
from .window import run_window

WARM_STEPS = 2          # after the first (compiling) step
CHECK_SEQUENCES = 4


def build(cell: dict, devices, seed: int, setup: Dict[str, float]):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.runtime.topology import MeshTopology

    config, traffic = cell["config_file"], cell["traffic_file"]
    model = config["model"]
    family = family_of(config)
    gen = plugin("generators", traffic["generator"]).Traffic(
        traffic, model["vocab_size"], seed)
    if gen.seq_len > model["max_seq_len"]:
        raise ValueError("traffic sequence longer than the model's context")
    chips = len(devices)
    ds = dict(config["engine"])
    ds["train_micro_batch_size_per_gpu"] = gen.micro_batch_per_chip
    ds["mesh"] = {"dp": chips}
    t0 = time.perf_counter()
    module = family.module(family.config(model))
    topo = (MeshTopology.create(dp=chips, devices=devices)
            if chips < len(jax.devices()) else None)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=module, topology=topo, config=ds, seed=seed % (2 ** 31))
    jax.block_until_ready(engine.state)
    setup["weights"] = time.perf_counter() - t0
    return engine, gen


def warm_and_check(cell: dict, engine, gen, chips: int,
                   setup: Dict[str, float]) -> tuple:
    """The first step compiles, on a batch of a few repeated sequences whose
    loss the reference can afford; then ``WARM_STEPS`` ordinary ones. The
    reference reads the compute-type weights that first step will use."""
    sample = gen.sample_batch(chips, CHECK_SEQUENCES)
    t0 = time.perf_counter()
    want = correct.reference_loss(cell, engine.state["params"],
                                  sample[:CHECK_SEQUENCES])
    setup["reference_check"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    first = float(engine.train_batch({"input_ids": sample})["loss"])
    setup["compile_or_load"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(WARM_STEPS):
        engine.train_batch({"input_ids": gen.batch(chips)})
    setup["warm_up"] = time.perf_counter() - t0
    return first, correct.train_loss(cell, first, want, CHECK_SEQUENCES)


def run(cell: dict, devices, seed: int, seconds: float, clock, spans: SpanLog,
        capture, setup: Dict[str, float], clog) -> Run:
    engine, gen = build(cell, devices, seed, setup)
    chips = len(devices)
    first_loss, verdict = warm_and_check(cell, engine, gen, chips, setup)
    losses: List[float] = []
    tokens = gen.micro_batch_per_chip * chips * gen.seq_len

    def step() -> Dict[str, float]:
        batch = {"input_ids": gen.batch(chips)}
        with spans.span("train_batch", tokens=tokens):
            losses.append(float(engine.train_batch(batch)["loss"]))
        return {"tokens": tokens, "steps": 1}

    compile_mark = clog.mark()
    window = run_window(step, seconds, clock, capture)
    problems = []
    bad = sum(1 for x in losses if not math.isfinite(x))
    if bad:
        problems.append(f"{bad} steps with a non-finite loss")
    tail = float(np.mean(losses[-3:]))
    if not tail < first_loss - 0.05:
        problems.append(f"loss did not fall: first {first_loss:.4f}, "
                        f"last three {tail:.4f}")
    say(f"losses: first {first_loss:.4f}, window "
        f"{[round(x, 3) for x in losses[:3]]} ... "
        f"{[round(x, 3) for x in losses[-3:]]}")
    steps = [s.dur for s in spans.named("train_batch", window.t_open,
                                        window.t_close)]
    facts = {"steps": len(losses), "tokens_per_step": tokens,
             "seq_len": gen.seq_len, "step_s_min": min(steps),
             "step_s_max": max(steps)}
    return Run(window, len(losses), bad, problems, facts, verdict,
               compile_mark, keep=engine)
