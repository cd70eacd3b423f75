"""The trace reducer on a hand-made trace and on two pieces of traces recorded
on the chip (``data/``: 70 ms of the batch-decode cell on one chip, 60 ms of
the dp4 train cell on two of its four chips; cut with
``tools/trace_slice.py`` from my chip runs, PR 23)."""

import json
import os

import pytest

from benchmark.lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load(name):
    with open(os.path.join(DATA, name)) as f:
        d = json.load(f)
    tup = lambda m: {int(k): [tuple(e) for e in v]  # noqa: E731
                     for k, v in m.items()}
    return (tup(d["device_ops"]), [tuple(e) for e in d["host"]],
            tuple(d["window"]), tup(d["device_async"]))


def brute_force(ops, transfers, window):
    """The same quantities the slow way, for one device: cut the window at
    every event edge; in each piece the operation that runs is the covering
    event that started last (the innermost of a nest)."""
    t0, t1 = window
    edges = sorted({t0, t1}
                   | {x for _, s, e in list(ops) + list(transfers)
                      for x in (s, e) if t0 < x < t1})
    busy = coll = exposed = 0.0
    by_name = {}
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        covering = [(s, -e, n) for n, s, e in ops if s <= mid < e]
        inner = max(covering)[2] if covering else None
        moving = any(s <= mid < e and T.COLLECTIVE.search(n)
                     for n, s, e in transfers)
        if inner is not None:
            busy += b - a
            by_name[inner] = by_name.get(inner, 0.0) + b - a
        inner_coll = inner is not None and bool(T.COLLECTIVE.search(inner))
        if inner_coll or moving:
            coll += b - a
            if inner is None or inner_coll:
                exposed += b - a
    return busy, coll, exposed, by_name


def test_hand_made_trace():
    ops = {0: [("while.1_while", 1.0, 5.0),          # spans its body
               ("fusion.1_fusion", 1.0, 2.0),
               ("closed_call.2_mosaic", 2.0, 3.5),
               ("all-gather.3_all-gather", 3.5, 4.0),  # 4.0-5.0: while's own
               ("copy.4_copy", 6.0, 7.0)]}
    transfers = {0: [("collective-permute-start.5_collective-permute-start",
                      4.5, 6.5)]}
    host = [("sched.step", 0.0, 5.5), ("decode", 0.5, 5.2),
            ("sched.step", 5.5, 8.0)]
    r = T.reduce_events(ops, host, window=(0.0, 8.0), device_async=transfers)
    assert r.window_s == 8.0 and r.busy_s == pytest.approx(5.0)
    assert r.idle_share == pytest.approx(3.0 / 8.0)
    assert r.op_seconds == pytest.approx({
        "while.1_while": 1.0, "fusion.1_fusion": 1.0,
        "closed_call.2_mosaic": 1.5, "all-gather.3_all-gather": 0.5,
        "copy.4_copy": 1.0})
    # collective: 3.5-4.0 on the line, 4.5-6.5 in flight; the while (4.5-5.0)
    # and the copy (6.0-6.5) compute under the transfer
    assert r.collective_s == pytest.approx(2.5)
    assert r.exposed_collective_s == pytest.approx(1.5)
    # idle: 0-1 and 7-8 began under sched.step alone, 5-6 inside decode; a
    # gap goes whole to the innermost span the host was in when it began
    assert r.gaps_by_span == pytest.approx({"sched.step": 2.0, "decode": 1.0})
    assert r.busy_in_span["decode"] == pytest.approx(4.0)
    assert r.top_ops(1) == [["closed_call.2_mosaic", 1.5]]


def test_self_times_of_a_nest():
    pieces = T.self_times([("outer", 0.0, 10.0), ("a", 1.0, 4.0),
                           ("b", 2.0, 3.0), ("c", 6.0, 7.0)])
    total = {}
    for n, s, e in pieces:
        total[n] = total.get(n, 0.0) + e - s
    assert total == pytest.approx({"outer": 6.0, "a": 2.0, "b": 1.0, "c": 1.0})
    assert T.measure(T.union((s, e) for _, s, e in pieces)) == 10.0


# the recorded pieces, with the numbers they must give
RECORDED = [
    ("trace_serve_1chip.json",
     dict(busy_s=0.062858319, idle_pct=10.2024014, mosaic_pct=3.9153115,
          collective_s=0.0, exposed_s=0.0)),
    ("trace_train_dp4.json",
     dict(busy_s=0.0497229395, idle_pct=17.1284342, mosaic_pct=3.0371555,
          collective_s=0.008931474, exposed_s=0.006735928)),
]


@pytest.mark.parametrize("name,want", RECORDED)
def test_recorded_trace_gives_known_numbers(name, want):
    ops, host, window, transfers = load(name)
    r = T.reduce_events(ops, host, window=window, device_async=transfers)
    mosaic = sum(s for n, s in r.op_seconds.items() if n.endswith("_mosaic"))
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-6)
    assert 100 * r.idle_share == pytest.approx(want["idle_pct"], rel=1e-6)
    assert 100 * mosaic / r.busy_s == pytest.approx(want["mosaic_pct"],
                                                    rel=1e-6)
    assert r.collective_s == pytest.approx(want["collective_s"], abs=1e-9)
    assert r.exposed_collective_s == pytest.approx(want["exposed_s"], abs=1e-9)
    assert sum(r.gaps_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-9)


@pytest.mark.parametrize("name", [n for n, _ in RECORDED])
def test_recorded_trace_agrees_with_the_slow_way(name):
    ops, host, window, transfers = load(name)
    r = T.reduce_events(ops, host, window=window, device_async=transfers)
    n = len(ops)
    busy = coll = exposed = 0.0
    by_name = {}
    for dev, events in ops.items():
        b, c, x, names = brute_force(events, transfers.get(dev, ()), window)
        busy, coll, exposed = busy + b / n, coll + c / n, exposed + x / n
        for k, v in names.items():
            by_name[k] = by_name.get(k, 0.0) + v / n
    assert r.busy_s == pytest.approx(busy, rel=1e-9)
    assert r.collective_s == pytest.approx(coll, abs=1e-12)
    assert r.exposed_collective_s == pytest.approx(exposed, abs=1e-12)
    assert r.op_seconds == pytest.approx(by_name, abs=1e-12)


def test_short_names():
    assert T.short_name(
        '%closed_call.13 = bf16[96,16,1,128]{3,2,1,0:T(2,128)(2,1)S(1)} '
        'custom-call(s32[96]{0} %x), custom_call_target="tpu_custom_call"'
    ) == "closed_call.13_mosaic"
    assert T.short_name(
        "%while.5 = (s32[]{:T(128)}, bf16[96,1,2048]{2,0,1:T(8,128)(2,1)}) "
        "while((s32[]) %tuple), condition=%c, body=%b") == "while.5_while"
    assert T.short_name(
        "%fusion.10 = bf16[50304,512]{1,0} fusion(bf16[50304,2048]{1,0} "
        "%fusion.411), kind=kCustom, calls=%all-reduce-scatter"
    ) == "fusion.10_all-reduce-scatter"
    assert T.short_name(
        "%copy-start.38 = (bf16[4,6]{1,0}, u32[]{:S(2)}) copy-start("
        "bf16[4,6]{1,0} %all-gather.184)") == "copy-start.38_copy-start"
    assert not T.COLLECTIVE.search("copy-start.38_copy-start")
    assert T.COLLECTIVE.search("all-gather.186_all-gather")
    assert T.short_name("PjitFunction(f)") == "PjitFunction(f)"


def test_window_annotation_bounds_the_window():
    ops = {0: [("fusion.1_fusion", 2.0, 3.0)]}
    host = [(T.WINDOW_ANNOTATION, 1.0, 5.0), ("train_batch", 1.5, 4.0)]
    r = T.reduce_events(ops, host)
    assert r.window_s == 4.0 and r.busy_s == 1.0
    assert r.gaps_by_span == pytest.approx({T.NO_SPAN: 1.0,
                                            "train_batch": 2.0})
    with pytest.raises(ValueError):
        T.reduce_events(ops, [("train_batch", 1.5, 4.0)])
    with pytest.raises(ValueError):
        T.reduce_events({0: []}, host)


def test_load_xplane_keeps_harness_spans_by_name_and_program_spans_by_prefix(
        tmp_path):
    """What ``run.py`` asks of a traced run's file: its own span names, and
    whatever the program wrote under its prefixes; nothing else of the host's
    timeline."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_ANNOTATION):
        with jax.profiler.TraceAnnotation("decode"):
            with jax.profiler.TraceAnnotation("engine.decode.fetch"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
        with jax.profiler.TraceAnnotation("somebody.else"):
            pass
    jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    by_name = {n for n, _, _ in T.load_xplane(path, {"decode"}).host}
    assert by_name == {"decode", T.WINDOW_ANNOTATION}
    both = T.load_xplane(path, {"decode"}, ("engine.", "serve."))
    assert {n for n, _, _ in both.host} == {
        "decode", "engine.decode.fetch", T.WINDOW_ANNOTATION}
    (inner,) = [ev for ev in both.host if ev[0] == "engine.decode.fetch"]
    (outer,) = [ev for ev in both.host if ev[0] == "decode"]
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    # the gap the host sat in the inner span for goes to the inner span
    assert T._covering(both.host, (inner[1] + inner[2]) / 2) == \
        "engine.decode.fetch"
