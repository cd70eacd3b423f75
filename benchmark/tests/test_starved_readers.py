"""The readers of the program's ``device.starved`` events
(``readers/rec_starved_share.py``, ``rec_starved_per.py``) on hand-made
records, as ``test_record_readers.py`` builds them, and in the rehearsal cells
that list their metrics, where the engines write the events and the readers
read them in one process."""

import json

import pytest
from test_record_readers import (GAP, STEP, T_OPEN, a_ctx, a_record, plant,
                                 read, run)

from benchmark.lib import manifest, program_trace
from benchmark.lib import trace as T
from benchmark.readers import rec_starved_share
from deepspeed_tpu.profiling import trace

NEW = ["starved_win_pct", "starved_win_pct.backlog", "starved_win_pct.train",
       "starved_step_ms", "starved_admit_ms"]
FETCH, SAMPLE = trace.ENGINE_DECODE_FETCH, trace.ENGINE_PREFILL_SAMPLE
# a step of test_record_readers.PARTS: housekeeping 0.01, the admission 0.30
# (one engine.prefill.chunk fills it), the decode 0.60 (its fetch fills it),
# the commit 0.04, then 0.05 s under no span; GAP between two steps
FED_AT = 0.03                  # into the admission's chunk: its dispatch back
TURNAROUND = 0.04 + 0.05 + GAP + 0.01 + FED_AT


def starved(t0, t1, step, after, by="prefill_chunk_512"):
    return trace.Recorded(trace.DEVICE_STARVED, t0, t1, step,
                          {"after": after, "by": by})


def a_starved_record(steps=4, admitting=2):
    """``a_record`` with the engine's events: the device dry from each step's
    fetch to the return of the next step's first dispatch (the one from the
    warm-up's last fetch began before the window), and in step ``admitting``
    twice between two prompts of its admission."""
    entries, t_close = a_record(steps=steps)
    fetches = sorted(e.t1 for e in entries if e.name == FETCH)
    chunks = sorted(e.t0 for e in entries
                    if e.name == trace.ENGINE_PREFILL_CHUNK)
    events = [starved(T_OPEN - 4.1, chunks[0] + FED_AT, 1, FETCH)]
    events += [starved(fetches[k], chunks[k + 1] + FED_AT, k + 2, FETCH)
               for k in range(steps - 1)]
    at = chunks[admitting - 1]
    events += [starved(at + 0.10, at + 0.12, admitting, SAMPLE),
               starved(at + 0.20, at + 0.22, admitting, SAMPLE)]
    return sorted(entries + events, key=lambda e: (e.t0, -e.t1)), t_close


def test_the_share_and_the_two_means(monkeypatch):
    entries, t_close = a_starved_record()
    plant(monkeypatch, entries)
    ctx = a_ctx(t_close)
    whole = 3 * TURNAROUND + 2 * 0.02
    assert read("starved_win_pct", ctx) == pytest.approx(
        100 * whole / (4 * (STEP + GAP)))
    for name in ("starved_win_pct.backlog", "starved_win_pct.train"):
        assert read(name, ctx) == read("starved_win_pct", ctx)
    # the host between two steps, over the window's four steps
    assert read("starved_step_ms", ctx) == pytest.approx(
        1000 * 3 * TURNAROUND / 4)
    # between two prompts, over the one admission of four that holds any
    assert read("starved_admit_ms", ctx) == pytest.approx(1000 * 2 * 0.02)


def test_the_split_by_the_span_the_host_was_in_sums_to_the_whole(
        monkeypatch, capsys):
    entries, t_close = a_starved_record()
    plant(monkeypatch, entries)
    ctx = a_ctx(t_close)
    rec = rec_starved_share.record.of(ctx)
    events = rec_starved_share.events(ctx, rec)
    assert len(events) == 5            # not the one that began in the warm-up
    parts = rec_starved_share.split(rec, events)
    assert parts == pytest.approx({
        trace.SERVE_COMMIT: 3 * 0.04, trace.SERVE_STEP: 3 * 0.05,
        rec_starved_share.OUTSIDE: 3 * GAP, trace.SERVE_HOUSEKEEPING: 3 * 0.01,
        trace.ENGINE_PREFILL_CHUNK: 3 * FED_AT + 2 * 0.02})
    assert sum(parts.values()) == pytest.approx(sum(e.dur for e in events))
    # a collection inside the commit takes its part under its own name
    gc = trace.Recorded(trace.HOST_GC, events[0].t0 + 0.01,
                        events[0].t0 + 0.03, 1, {"generation": 2})
    plant(monkeypatch, sorted(entries + [gc], key=lambda e: (e.t0, -e.t1)))
    ctx = a_ctx(t_close)
    again = rec_starved_share.split(rec_starved_share.record.of(ctx), events)
    assert again[trace.HOST_GC] == pytest.approx(0.02)
    assert again[trace.SERVE_COMMIT] == pytest.approx(3 * 0.04 - 0.02)
    # the line: milliseconds a step, largest first
    capsys.readouterr()
    read("starved_win_pct", a_ctx(t_close))
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if "by the span the host was in" in ln]
    assert f"device.starved {1000 * (3 * TURNAROUND + 0.04) / 4:.3f} ms a " \
        "serve.step over 5 events" in line
    assert line.index("serve.step 37.500") < line.index(
        "engine.prefill.chunk 32.500") < line.index(
            "serve.commit 25.000") < line.index("host.gc 5.000")


@pytest.mark.parametrize("case", ["wrapped", "empty", "no_event"])
def test_no_whole_window_or_no_such_event_gives_nothing(case, monkeypatch):
    entries, t_close = a_starved_record(steps=3)
    if case == "wrapped":              # the ring's oldest began in the window
        plant(monkeypatch, [e for e in entries if e.t0 > T_OPEN + 0.5])
    elif case == "empty":
        plant(monkeypatch, [])
    else:                              # the parent: a program without them
        plant(monkeypatch, [e for e in entries
                            if e.name != trace.DEVICE_STARVED])
        monkeypatch.delattr(trace, "DEVICE_STARVED")
    ctx = a_ctx(t_close, steps=3)
    for name in NEW:
        assert read(name, ctx) is None, name


def test_a_cell_that_waits_for_no_prompt_reads_no_admission_metric(
        monkeypatch):
    entries, t_close = a_starved_record()
    plant(monkeypatch, [e for e in entries
                        if e.counts.get("after") != SAMPLE])
    ctx = a_ctx(t_close)
    assert read("starved_admit_ms", ctx) is None
    assert read("starved_step_ms", ctx) == pytest.approx(
        1000 * 3 * TURNAROUND / 4)
    # a program that writes the events and wrote none in the window reads 0
    plant(monkeypatch, [e for e in entries
                        if e.name != trace.DEVICE_STARVED])
    assert read("starved_win_pct", a_ctx(t_close)) == 0.0
    assert read("starved_step_ms", a_ctx(t_close)) is None


def test_the_traced_slice_beside_the_devices_idle(monkeypatch, capsys):
    """The events inside ``ctx.traced`` beside the slice's idle seconds that
    began under the same waits; one that straddles an edge of the slice
    holds the harness's pause there and is in no sum."""
    entries, t_close = a_starved_record(steps=6)
    events = [e for e in entries if e.name == trace.DEVICE_STARVED
              and e.t0 > T_OPEN]
    fetched = [e for e in events if e.counts["after"] == FETCH]
    # the profiler starts and stops at a boundary, between two steps: the
    # slice opens inside the second turnaround and closes inside the fifth
    traced = (fetched[1].t0 + 0.10, fetched[4].t0 + 0.10)
    ctx = a_ctx(t_close, steps=6, traced=traced)
    ctx.trace = T.Reduced(
        window_s=traced[1] - traced[0], busy_s=traced[1] - traced[0] - 0.5,
        n_devices=1, op_seconds={}, collective_s=0.0,
        exposed_collective_s=0.0, busy_in_span={},
        gaps_by_span={FETCH: 0.32, SAMPLE: 0.08, "engine.prefill.chunk": 0.07,
                      "serve.admit.claim": 0.03})
    plant(monkeypatch, entries)
    # the same slice on the profiler's clock, 5 s off: the device ended 0.01 s
    # before each fetch gave its tokens and began 0.02 s after the dispatch
    off, inside = 5.0, fetched[2:4]
    gaps = [(e.t0 - 0.01 + off, e.t1 + 0.02 + off) for e in inside]
    gaps.insert(1, (inside[1].t0 - 0.30 + off, inside[1].t0 - 0.296 + off))
    w0, w1 = traced[0] + off, traced[1] + off
    monkeypatch.setattr(program_trace, "of", lambda ctx: program_trace.
                        ProgramTrace(
        (w0, w1), [program_trace.Span(e.name, e.t0 + off, e.t1 + off, {})
                   for e in entries if e.name in (trace.SERVE_STEP, FETCH)
                   and e.t0 >= traced[0] - 0.95 and e.t1 <= traced[1]],
        {}, {0: [("fusion.1_fusion", a, b) for a, b in T.complement(
            gaps, w0, w1)]}, {}, {}))
    whole = 3 * TURNAROUND + 2 * 0.02      # five turnarounds less the two
    assert read("starved_win_pct", ctx) == pytest.approx(
        100 * whole / ctx.window.seconds)
    assert read("starved_step_ms", ctx) == pytest.approx(
        1000 * 3 * TURNAROUND / 6)
    out = capsys.readouterr().out
    (line,) = [ln for ln in out.splitlines()
               if "inside the traced slice:" in ln]
    # the two inside it (and the admission's two lie before it)
    assert f"{2 * TURNAROUND:.4f} s in 2 events, for 0.4000 s of the " \
        "device's idle that began under engine.decode.fetch or " \
        f"engine.prefill.sample ({2 * TURNAROUND / 0.4:.3f} of it) and " \
        "0.5000 s of idle in all; the other 0.1000 s" in line
    assert line.endswith("engine.prefill.chunk 0.0700, serve.admit.claim "
                         "0.0300")
    assert ("of that idle, by the profiler's clock, 2 gaps met an event: the "
            "device had ended 0.0200 s before the host had its result (the "
            "read-back, 10000 us a gap), device and event were both dry "
            "0.3000 s, the device began 0.0400 s after the dispatch had "
            "returned (the launch, 20000 us a gap) and 0.0000 s of the events "
            "lay where it was at work again; 1 gaps met none, 0.0040 s "
            "between the operations of a running program") in out
    assert ("device.starved a serve.step: 100.000 ms over the 3 steps inside "
            "the traced slice (the profiler on), 63.333 ms over the 3 outside "
            "it (1.579 x)") in out
    # no traced slice (a plain run, a rehearsal on the CPU): no such line
    read("starved_win_pct", a_ctx(t_close, steps=6))
    assert "traced slice" not in capsys.readouterr().out


def test_the_new_metrics_are_listed_where_they_are_read():
    bench = manifest.listed()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])            # appended together, in this order
    assert names[at:at + len(NEW)] == NEW
    cells = {metric: {w["name"] for w in bench["workloads"]
                      if metric in manifest.reported(bench, w["name"])[0]}
             for metric in ("out_tok_s", "prompt_tok_s", "train_tok_s_chip")}
    assert all(cells.values())
    for name in NEW:
        spec = manifest.load_metric(name)
        entry = by_name[name]
        assert spec["reader"].startswith("rec_starved")
        assert spec["source"] == entry["source"] == "program_counter"
        assert all(spec[k] == entry[k]
                   for k in ("unit", "better", "layer", "moves"))
        if name != "starved_admit_ms":  # the dense path's cells alone
            assert set(entry["workloads"]) == cells[entry["moves"]]
    assert set(by_name["starved_admit_ms"]["workloads"]) < cells["out_tok_s"]


@pytest.mark.parametrize("cell", [
    "tiny-serve.starved", "tiny-train.starved",
    "tiny-deepseek-v2-serve.starved"])
def test_rehearsal_every_new_reader_finds_something_to_read(cell):
    """The engines write the events and the readers read them in one
    process: every metric the cell lists is read, and the split names the
    span the feeding dispatch lay in."""
    listed = manifest.load_cell(cell)["per_layer"]
    assert set(listed) <= set(NEW)
    done = run(cell)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["metrics"] == {}
    found = "rehearsal: readers that found something to read: "
    (line,) = [ln for ln in done.stdout.splitlines() if found in ln]
    assert set(line.split(found, 1)[1].split(", ")) == set(listed)
    split = [ln for ln in done.stdout.splitlines()
             if ln.startswith("[bench] device.starved ")
             and "by the span the host was in" in ln]
    assert split and rec_starved_share.OUTSIDE in split[0]
    assert ("train.dispatch" if "train" in cell
            else "engine.decode.enqueue") in split[0]
    assert "PROBLEM" not in done.stdout


def test_every_new_metric_is_rehearsed_by_some_cell():
    rehearsed = set()
    for cell in manifest.rehearsal_cells():
        rehearsed |= set(manifest.load_cell(cell)["per_layer"])
    assert set(NEW) <= rehearsed
