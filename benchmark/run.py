#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run: it loads, warms up, measures for ``--seconds`` between
step boundaries, prints one JSON line last and exits. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from the
harness's spans and a profiler trace of a few seconds of the window. A listed
cell refuses to run without a TPU; a cell whose file says ``"rehearsal": true``
runs on the CPU and prints no device metric. See ``benchmark/README.md``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import manifest  # noqa: E402
from benchmark.lib.device import (CompileLog, NoChip, describe,  # noqa: E402
                                  memory_peak, pin_environment,
                                  place_compile_cache, require_devices, say)

TRACE_DIR = os.path.join(manifest.ROOT, ".work", "trace")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def read_metrics(names, ctx) -> dict:
    """Each metric's own reader, found by the kind its file names. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for name in names:
        spec = manifest.load_metric(name)
        value = manifest.plugin("readers", spec["reader"]).read(
            ctx, spec.get("params", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def rehearse_readers(names, ctx) -> list:
    """On the CPU a reader may lack its device (no peaks for "cpu"): run each,
    report which gave a value, print none."""
    ran = []
    for name in names:
        try:
            ran += list(read_metrics([name], ctx))
        except ValueError as e:
            say(f"rehearsal: {name}: {e}")
    return ran


def main(argv=None) -> int:
    args = parse(argv)
    cell = manifest.load_cell(args.workload)
    config = cell["config_file"]
    rehearsal = bool(cell.get("rehearsal"))
    chips = int(cell["chips"])
    if rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    pin_environment()
    setup = {}
    import jax  # noqa: F401

    # bringing up the TPU runtime took 7 to 22 s on one machine within an hour
    # (PERF.md, PR 23) and no PR can change it: it is timed and left out of
    # setup_s, which would otherwise drown what a PR moves into set-up
    t0 = time.perf_counter()
    try:
        devices = require_devices(chips, rehearsal)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    reach_chip = time.perf_counter() - t0
    cache_dir = place_compile_cache()
    clog = CompileLog()
    from benchmark.lib import trace as trace_mod
    from benchmark.lib.context import Context
    from benchmark.lib.program_trace import SPAN_PREFIXES
    from benchmark.lib.spans import SpanLog

    mode = manifest.plugin("lib", f"mode_{config['mode']}")
    setup["import"] = time.perf_counter() - T_START - reach_chip
    say(f"cell {cell['name']} seed {args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {cache_dir}")

    clock = time.perf_counter
    spans = SpanLog(clock, annotate=bool(args.trace))
    capture = None
    if args.trace:
        capture = trace_mod.Capture(
            TRACE_DIR, clock, float(cell.get("trace_start_s", 1.0)),
            float(cell.get("trace_seconds", 4.0)))
    mark = clog.mark()
    run = mode.run(cell, devices, args.seed, args.seconds, clock, spans,
                   capture, setup, clog)
    if capture is not None:
        capture.stop()
    in_window = clog.since(run.compile_mark)
    window = run.window
    setup_s = window.t_open - T_START - reach_chip
    setup["other"] = setup_s - sum(setup.values())
    total = clog.since(mark)
    say(f"setup_s {setup_s:.2f} (and {reach_chip:.2f} to bring up the "
        "runtime, not counted) = "
        + ", ".join(f"{k} {v:.2f}" for k, v in setup.items())
        + f"; {total['programs']} programs compiled or loaded in "
        f"{total['seconds']:.1f}s, cache hits {total['cache_hits']} misses "
        f"{total['cache_misses']}")
    say(f"window {window.seconds:.3f}s, {len(window.steps)} steps; "
        + ", ".join(f"{k} {v:.4g}" for k, v in run.facts.items()))
    # where a run reads far off, this line says whether one step stalled
    longest = max(range(len(window.step_s)), key=window.step_s.__getitem__)
    say(f"steps: median {statistics.median(window.step_s):.4f}s, longest "
        f"{window.step_s[longest]:.4f}s (step {longest}), "
        f"{sum(window.step_s):.3f}s in all")

    problems = list(run.problems)
    if in_window["programs"]:
        problems.append(f"{in_window['programs']} programs compiled inside "
                        f"the window: {in_window['names'][:5]}")
    if not run.verdict.ok:
        problems.append("outputs disagree with the reference: "
                        + "; ".join(run.verdict.notes))
    for p in problems:
        say(f"PROBLEM: {p}")

    ctx = Context(cell=cell, window=window, spans=spans,
                  requests=run.requests, facts=run.facts,
                  device_kind=devices[0].device_kind, chips=chips,
                  setup_s=setup_s)
    device = describe(devices, memory_peak(devices))
    result = {"correct": not problems, "attempted": run.attempted,
              "failed": run.failed}
    if args.trace:
        # the program's spans beside the harness's: an idle gap goes to the
        # innermost, so the breakdown names engine.prefill.sample or
        # train.sync where the harness's own spans stop at prefill or
        # train_batch
        loaded = trace_mod.load_xplane(
            trace_mod.find_xplane(TRACE_DIR), {s.name for s in spans.spans},
            SPAN_PREFIXES)
        if rehearsal and not loaded.device_ops:
            say("rehearsal: no device plane in the trace; per-layer metrics "
                "from spans only")
        else:
            ctx.trace = trace_mod.reduce_events(
                loaded.device_ops, loaded.host,
                device_async=loaded.device_async)
            ctx.traced = capture.traced
            device["busy_s"] = ctx.trace.busy_s
            device["window_s"] = ctx.trace.window_s
            result["breakdown"] = {"device_ops": ctx.trace.top_ops(),
                                   "idle_gaps": ctx.trace.top_gaps()}
    names = cell["per_layer"] if args.trace else cell["end_to_end"]
    if rehearsal:
        # a CPU run carries no device metric: it says which readers ran
        result["metrics"] = {}
        result["rehearsal"] = True
        say("rehearsal: readers that found something to read: "
            + ", ".join(rehearse_readers(names, ctx)))
    else:
        result["metrics"] = read_metrics(names, ctx)
    result["device"] = device
    ordered = {k: result[k] for k in ("correct", "attempted", "failed",
                                      "metrics", "device")}
    ordered.update({k: v for k, v in result.items() if k not in ordered})
    print(json.dumps(ordered), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
