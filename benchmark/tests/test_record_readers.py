"""The readers of the program's own record of its spans (``lib/record.py``,
``readers/rec_*.py``, ``readers/request_stamps.py``) on hand-made records,
windows and stamps, and in the two rehearsal cells that list their metrics,
where the program writes the record and the readers read it in one process.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import manifest, record
from benchmark.lib.context import Context
from benchmark.lib.manifest import CHECKOUT
from benchmark.lib.window import Window
from benchmark.readers import (rec_span_share, rec_step_host_ms,
                               rec_step_longest, request_stamps)
from deepspeed_tpu.profiling import trace

NEW = ["win_admit_pct", "win_admit_pct.backlog", "win_decode_pct",
       "sched_host_win_ms", "step_longest_x", "step_longest_x.backlog",
       "step_longest_x.train", "ttft_queue_p95_ms"]
T_OPEN = 100.0
STEP, GAP = 1.0, 0.02                  # a step, and the harness between two
PARTS = (("serve.housekeeping", 0.01, ()),
         ("serve.admit.prefill", 0.30, ("engine.prefill.chunk",)),
         ("serve.decode", 0.60, ("engine.decode.fetch",)),
         ("serve.commit", 0.04, ()))   # 0.05 s of a step under no span


def a_step(t0, k, long_by=0.0, compiled=()):
    """One ``serve.step`` from ``t0``: its parts one after another, each with
    one engine span filling it; ``long_by`` seconds more inside the decode's
    fetch, and the compiles there."""
    out, at = [], t0
    for name, dur, inner in PARTS:
        dur += long_by if name == "serve.decode" else 0.0
        out.append(trace.Recorded(name, at, at + dur, k, {}))
        out += [trace.Recorded(n, at, at + dur, k, {}) for n in inner]
        if name == "serve.decode":
            out += [trace.Recorded(trace.XLA_COMPILE, at + 0.1, at + 0.2, k,
                                   {"fun_name": f}) for f in compiled]
        at += dur
    return [trace.Recorded("serve.step", t0, t0 + STEP + long_by, k, {})] + out


def a_record(steps=4, long_step=None, long_by=0.0, compiled=()):
    """Warm-up spans before the window, then ``steps`` steps inside it."""
    entries = [trace.Recorded("serve.step", T_OPEN - 5.0, T_OPEN - 4.0, 0, {}),
               trace.Recorded("serve.decode", T_OPEN - 4.9, T_OPEN - 4.1, 0,
                              {})]
    at = T_OPEN + GAP
    for k in range(steps):
        more = long_by if k == long_step else 0.0
        entries += a_step(at, k + 1, more, compiled if more else ())
        at += STEP + more + GAP
    return sorted(entries, key=lambda e: (e.t0, -e.t1)), at - GAP


def a_ctx(t_close, steps=4, paused=0.0, requests=(), traced=None):
    w = Window(T_OPEN, t_close, [{}] * steps, [STEP + GAP] * steps, paused)
    return Context(cell={}, window=w, spans=None, requests=list(requests),
                   facts={}, device_kind="none", chips=1, setup_s=0.0,
                   traced=traced)


def plant(monkeypatch, entries):
    """The program's record reads as ``entries`` (``slowest`` reads it
    through ``recorded``)."""
    monkeypatch.setattr(trace, "recorded", lambda since=None: [
        e for e in entries if since is None or e.t0 >= since])


def params_of(name):
    spec = manifest.load_metric(name)
    return manifest.plugin("readers", spec["reader"]), spec["params"]


def read(name, ctx):
    reader, params = params_of(name)
    return reader.read(ctx, params)


def test_the_shares_and_the_hosts_time_add_up_to_the_window(monkeypatch,
                                                            capsys):
    entries, t_close = a_record(steps=4)
    plant(monkeypatch, entries)
    ctx = a_ctx(t_close)
    admit, decode = read("win_admit_pct", ctx), read("win_decode_pct", ctx)
    host = read("sched_host_win_ms", ctx)
    seconds = 4 * STEP + 4 * GAP
    assert ctx.window.seconds == pytest.approx(seconds)
    assert admit == pytest.approx(100 * 4 * 0.30 / seconds)
    assert decode == pytest.approx(100 * 4 * 0.60 / seconds)
    assert host == pytest.approx(1000 * 0.10)      # 1.0 less 0.30 and 0.60
    assert read("win_admit_pct.backlog", ctx) == admit
    # what is missing from 100 is the harness between the steps
    total = admit + decode + 100 * host / 1000 * 4 / seconds
    assert total == pytest.approx(100 * 4 * STEP / seconds)
    assert 100 - total == pytest.approx(100 * 4 * GAP / seconds)
    # the warm-up's spans, before the window, are in none of them
    assert len(record.of(ctx).named("serve.step")) == 4
    assert ("100.000 ms a step over the 4 steps of the window"
            in capsys.readouterr().out)


def test_the_harness_pauses_are_taken_out_of_the_denominator(monkeypatch):
    entries, t_close = a_record(steps=2)
    plant(monkeypatch, entries)
    paused = 0.5                       # the profiler started at a boundary
    ctx = a_ctx(t_close + paused, steps=2, paused=paused)
    assert read("win_decode_pct", ctx) == pytest.approx(
        100 * 2 * 0.60 / (2 * STEP + 2 * GAP))


@pytest.mark.parametrize("case", ["wrapped", "empty", "no_record"])
def test_no_whole_window_gives_nothing_and_says_so(case, monkeypatch, capsys):
    entries, t_close = a_record(steps=3)
    if case == "wrapped":              # the ring's oldest began in the window
        plant(monkeypatch, [e for e in entries if e.t0 > T_OPEN + 0.5])
    elif case == "empty":
        plant(monkeypatch, [])
    else:                              # the parent: a program with no record
        monkeypatch.delattr(trace, "recorded")
    ctx = a_ctx(t_close, steps=3)
    for name in NEW[:-1]:
        assert read(name, ctx) is None, name
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench]")]
    assert len(said) == 1              # once a run, not once a reader
    assert {"wrapped": "wrapped", "empty": "empty",
            "no_record": "keeps no record"}[case] in said[0]


def test_a_cell_without_the_span_reads_nothing(monkeypatch):
    entries, t_close = a_record(steps=2)
    plant(monkeypatch, entries)
    assert read("step_longest_x.train", a_ctx(t_close, steps=2)) is None
    assert rec_span_share.read(a_ctx(t_close, steps=2),
                               {"span": "serve.grow"}) is None
    assert rec_step_host_ms.read(a_ctx(t_close, steps=2), {
        "span": "train.step", "less": ["train.sync"]}) is None


def test_the_longest_step_and_the_programs_account_of_it(monkeypatch, capsys):
    entries, t_close = a_record(steps=5, long_step=3, long_by=6.0,
                                compiled=("jit(decode_block_4)",))
    plant(monkeypatch, entries)
    ctx = a_ctx(t_close, steps=5)
    assert read("step_longest_x", ctx) == pytest.approx(7.0)
    assert read("step_longest_x.backlog", ctx) == pytest.approx(7.0)
    (line,) = set(ln for ln in capsys.readouterr().out.splitlines()
                  if "longest serve.step" in ln)
    assert "7.0000 s (step_num 4, " in line and "7.00 x the median" in line
    # the phase that held it, largest first, and the compile that fell in it
    assert line.index("serve.decode 6.6000") < line.index(
        "serve.admit.prefill 0.3000")
    assert "engine.decode.fetch 6.6000" in line
    assert "xla.compile 0.1000" in line
    assert line.endswith("compiled in it: jit(decode_block_4)")
    # an even run reads 1
    plant(monkeypatch, a_record(steps=5)[0])
    assert read("step_longest_x", a_ctx(t_close, steps=5)) == pytest.approx(1)


def test_the_traced_slices_host_time_beside_the_rests(monkeypatch, capsys):
    """Inside ``ctx.traced`` the profiler ran: the host's own time a step
    there, beside the steps outside it, is what a session costs the host."""
    entries, t_close = a_record(steps=6)
    slow = []
    for e in entries:                  # steps 2 and 3: 0.01 s more of host
        if e.step in (2, 3) and e.name == "serve.step":
            e = e._replace(t0=e.t0 - 0.005, t1=e.t1 + 0.005)
        slow.append(e)
    plant(monkeypatch, slow)
    starts = sorted(e.t0 for e in slow if e.name == "serve.step"
                    and e.t0 > T_OPEN)
    ctx = a_ctx(t_close, steps=6, traced=(starts[1] - 0.001,
                                          starts[3] - 0.001))
    assert read("sched_host_win_ms", ctx) == pytest.approx(
        1000 * (0.10 + 2 * 0.01 / 6))
    out = capsys.readouterr().out
    assert ("110.000 ms over the 2 steps inside the traced slice (the "
            "profiler on), 100.000 ms over the 4 outside it (1.100 x)") in out
    # a step's waits taken out, for a train cell's line
    assert rec_step_longest.read(ctx, {
        "span": "serve.step", "less": ["serve.decode"]}) == pytest.approx(
            1.01)
    assert "serve.step less serve.decode: 410.000 ms over the 2 steps" in \
        capsys.readouterr().out
    # no traced slice (a plain run, a rehearsal): no such line
    read("sched_host_win_ms", a_ctx(t_close, steps=6))
    assert "traced slice" not in capsys.readouterr().out


def test_queue_wait_of_the_requests_ttft_counts():
    def req(t_submit, queued, t_first):
        return types.SimpleNamespace(
            t_submit=t_submit, t_admit=None if queued is None
            else t_submit + queued, t_first_token=t_first)

    inside = [req(T_OPEN + 1 + i, 0.001 * i, T_OPEN + 1.5 + i)
              for i in range(20)]
    before = req(T_OPEN - 1.0, 5.0, T_OPEN + 5.0)    # sent before the window
    unfinished = req(T_OPEN + 2.0, 9.0, None)        # no first token yet
    never_admitted = req(T_OPEN + 3.0, None, None)
    ctx = a_ctx(T_OPEN + 30.0, requests=inside + [before, unfinished,
                                                  never_admitted])
    assert read("ttft_queue_p95_ms", ctx) == pytest.approx(18.0)  # rank 19
    reader, params = params_of("ttft_queue_p95_ms")
    assert reader is request_stamps
    assert reader.read(ctx, dict(params, p=50)) == pytest.approx(9.0)
    # ttft itself, by the same reader, agrees with request_percentile's rule
    assert reader.read(ctx, dict(params, to="t_first_token", p=95,
                                 scale=1.0)) == pytest.approx(0.5)
    assert read("ttft_queue_p95_ms", a_ctx(T_OPEN + 30.0)) is None


def test_the_new_metrics_are_listed_where_they_are_read():
    bench = manifest.listed()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW
    serve = {w["name"] for w in bench["workloads"]
             if "out_tok_s" in manifest.reported(bench, w["name"])[0]}
    train = {w["name"] for w in bench["workloads"]
             if "train_tok_s_chip" in manifest.reported(bench, w["name"])[0]}
    for name in ("win_admit_pct", "win_decode_pct", "sched_host_win_ms",
                 "step_longest_x"):
        assert set(by_name[name]["workloads"]) == serve and len(serve) == 3
    assert set(by_name["step_longest_x.train"]["workloads"]) == train
    for name in NEW:
        spec = manifest.load_metric(name)
        assert spec["reader"].startswith(("rec_", "request_stamps"))
        assert spec["source"] == "program_span"


def run(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3000000019", "--seconds", "1", "--trace", "1"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell,step", [("tiny-serve.record", "serve.step"),
                                       ("tiny-train.record", "train.step")])
def test_rehearsal_every_new_reader_finds_something_to_read(cell, step):
    """The program writes its record and the readers read it in one process:
    every metric the cell lists is read, and the longest step's line names
    that step's spans."""
    listed = manifest.load_cell(cell)["per_layer"]
    assert set(listed) & set(NEW)
    done = run(cell)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["metrics"] == {}
    found = "rehearsal: readers that found something to read: "
    (line,) = [ln for ln in done.stdout.splitlines() if found in ln]
    assert set(line.split(found, 1)[1].split(", ")) == set(listed)
    longest = [ln for ln in done.stdout.splitlines()
               if ln.startswith(f"[bench] longest {step} ")]
    assert longest and "compiled in it: nothing" in longest[0]
    inner = {"serve.step": "serve.decode", "train.step": "train.sync"}[step]
    assert f"{inner} 0." in longest[0]
    assert "PROBLEM" not in done.stdout


def test_every_new_metric_is_rehearsed_by_some_cell():
    rehearsed = set()
    for cell in manifest.rehearsal_cells():
        rehearsed |= set(manifest.load_cell(cell)["per_layer"])
    assert set(NEW) <= rehearsed
