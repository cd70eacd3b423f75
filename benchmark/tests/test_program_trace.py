"""``lib/program_trace`` and the readers of the program's own names, on a
hand-made trace whose every number can be checked by eye, and on two pieces
recorded on the chip with the PR that added the names (``data/program_*.json``,
cut with ``tools/program_trace_slice.py`` from my chip runs, PR 24)."""

import json
import os

import pytest

from benchmark.lib import program_trace as P
from benchmark.lib.context import Context
from benchmark.lib.window import Window
from benchmark.readers import (prog_host_gap_ms, prog_module_ms, prog_op_ms,
                               prog_phase_ms, prog_roofline, prog_span_mean,
                               prog_span_ratio)
from benchmark.tools import program_gaps

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OP = "jit(train_batch)/"
MODULE_ID = 3886139467775638049     # as the "XLA Modules" line prints it
SCOPES = {
    "while.1": OP + "jvp(blocks)/while",
    "flash_fwd.1": OP + "jvp(blocks)/while/body/attn/flash_fwd/pallas_call",
    "fusion.2": OP + "jvp(blocks)/while/body/mlp/dot_general",
    "flash_fwd.3": OP + "transpose(jvp(blocks))/while/body/checkpoint/"
                   "rematted_computation/attn/flash_fwd/pallas_call",
    "flash_bwd_delta.4": OP + "transpose(jvp(blocks))/while/body/attn/"
                         "flash_bwd_delta/pallas_call",
    "flash_bwd_dq.5": OP + "transpose(jvp(blocks))/while/body/attn/"
                      "flash_bwd_dq/pallas_call",
    "flash_bwd_dkv.6": OP + "transpose(jvp(blocks))/while/body/attn/"
                       "flash_bwd_dkv/pallas_call",
    "fusion.7": OP + "optimizer/cond/branch_1_fun/mul",
}


def step_ops(t):
    """One train step on the device from ``t``: a loop of 1.0 s holding a
    forward kernel and a matmul, then recompute, backward and the update.
    ``copy.8`` (inside the loop) and ``copy.9`` carry no op_name."""
    return [
        ("while.1", "while", t, t + 1.0),
        ("flash_fwd.1", "mosaic", t + 0.1, t + 0.3),
        ("fusion.2", "fusion", t + 0.3, t + 0.8),
        ("copy.8", "copy", t + 0.8, t + 0.9),
        ("flash_fwd.3", "mosaic", t + 1.0, t + 1.2),
        ("flash_bwd_delta.4", "mosaic", t + 1.2, t + 1.25),
        ("flash_bwd_dq.5", "mosaic", t + 1.25, t + 1.55),
        ("flash_bwd_dkv.6", "mosaic", t + 1.55, t + 1.95),
        ("fusion.7", "fusion", t + 1.95, t + 2.05),
        ("copy.9", "copy", t + 2.05, t + 2.1),
    ]


def train_trace(tmp_path, scopes_kept=True):
    ops = step_ops(1.0) + step_ops(4.0)
    spans = []
    for t in (0.9, 3.9):
        spans += [P.Span("train.step", t, t + 2.9, {"step_num": 1}),
                  P.Span("train.place_batch", t, t + 0.05, {}),
                  P.Span("train.dispatch", t + 0.05, t + 0.1, {}),
                  P.Span("train.sync", t + 0.1, t + 2.8, {}),
                  P.Span("train.post", t + 2.8, t + 2.9, {})]
    pt = P.ProgramTrace(
        (0.5, 7.0), spans,
        {0: [("jit_train_batch", 1.0, 3.1), ("jit_train_batch", 4.0, 6.1)]},
        {0: [(f"{i}_{k}", s, e) for i, k, s, e in ops]},
        {0: [(i, s, e) for i, _, s, e in ops]}, {}, str(tmp_path),
        {"jit_train_batch": [MODULE_ID]})
    if scopes_kept:
        kept = tmp_path / P.SCOPES_DIR
        kept.mkdir(parents=True)
        (kept / f"train_batch.{MODULE_ID}.json").write_text(
            json.dumps(SCOPES))
    return pt


def ctx_for(monkeypatch, pt, model=None, facts=None, chips=1):
    monkeypatch.setattr(P, "of", lambda ctx: pt)
    w = Window(0.0, 10.0, [{}], [10.0])
    return Context(cell={"config_file": {"model": model or {}}}, window=w,
                   spans=None, requests=[], facts=facts or {},
                   device_kind="TPU v5 lite", chips=chips, setup_s=0.0)


def test_ops_belong_to_the_program_that_ran_them(tmp_path):
    pt = train_trace(tmp_path)
    per = pt.instr_seconds
    assert set(per) == {"jit_train_batch"}
    got = per["jit_train_batch"]
    assert got["while.1"] == pytest.approx(2 * 0.2)       # self time only
    assert pt.enclosing["jit_train_batch"]["copy.8"] == "while.1"
    assert pt.enclosing["jit_train_batch"]["copy.9"] is None
    assert got["fusion.2"] == pytest.approx(2 * 0.5)
    assert sum(got.values()) == pytest.approx(pt.reduced.busy_s)
    assert pt.program_seconds == {"jit_train_batch": (pytest.approx(4.2), 2)}
    assert pt.op_counts["flash_fwd.1_mosaic"] == 2


def test_phases_partition_the_busy_time(tmp_path, monkeypatch, capsys):
    pt = train_trace(tmp_path)
    ctx = ctx_for(monkeypatch, pt)
    ms = {ph: prog_phase_ms.read(ctx, {"phase": ph})
          for ph in ("forward", "recompute", "backward", "optimizer")}
    # the loop's own 0.2, the kernel, the matmul, and the copy inside the
    # loop, which carries no name and takes the loop's
    assert ms["forward"] == pytest.approx(1000.0)
    assert ms["recompute"] == pytest.approx(200.0)
    assert ms["backward"] == pytest.approx(750.0)
    assert ms["optimizer"] == pytest.approx(100.0)
    by_phase, by_scope, unnamed, inherited = P.phase_seconds(
        pt, "train_batch")
    assert unnamed == {"copy.9": pytest.approx(0.1)}
    assert inherited == pytest.approx(0.2)
    assert by_scope[("forward", "blocks")] == pytest.approx(0.4 + 0.2)
    assert sum(by_phase.values()) == pytest.approx(pt.reduced.busy_s)
    assert by_scope[("recompute", "attn")] == pytest.approx(0.4)
    said = capsys.readouterr().out
    assert "not attributed 2.38% of 2100.00" in said and "copy.9" in said


def test_phases_are_read_from_the_module_that_ran_only(tmp_path, monkeypatch,
                                                       capsys):
    """Another engine of the process holds a program of the same name (as
    ``tests/test_trace_names.py`` leaves one behind when both directories run
    in one process). Its text has a ``fusion.2`` and a ``while.1`` too, and is
    not what the trace shows: no phase is read, and a line says why. With the
    scopes kept beside the trace, the live table is not asked."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.profiling import trace as names

    def train_batch(x):
        with jax.named_scope("optimizer"):
            return jnp.tanh(x) @ x

    other = jax.jit(train_batch)
    names.register_program("train_batch", other, (jnp.ones((4, 4)),))
    try:
        pt = train_trace(tmp_path, scopes_kept=False)
        ctx = ctx_for(monkeypatch, pt)
        assert P.scopes_of(pt, "train_batch") is None
        assert prog_phase_ms.read(ctx, {"phase": "forward"}) is None
        said = capsys.readouterr().out
        assert "no phases are read" in said and str(MODULE_ID) in said
        kept = train_trace(tmp_path / "kept")
        assert P.scopes_of(kept, "train_batch") == SCOPES
        two = train_trace(tmp_path / "two")
        two.module_ids["jit_train_batch"].append(5)
        assert P.scopes_of(two, "train_batch") is None
        assert "cannot be told apart" in capsys.readouterr().out
    finally:
        names._programs["train_batch"] = [
            p for p in names._programs["train_batch"]
            if p.jitted() is not other]


def test_kernel_time_and_roofline(tmp_path, monkeypatch, capsys):
    pt = train_trace(tmp_path)
    model = {"n_head": 2, "d_model": 128}
    ctx = ctx_for(monkeypatch, pt, model,
                  {"seq_len": 1024, "tokens_per_step": 2048})
    train = {"span": "train.step"}
    assert prog_op_ms.read(ctx, dict(train, pattern="^flash_fwd")
                           ) == pytest.approx(400.0)
    assert prog_op_ms.read(ctx, dict(train, pattern="^flash_bwd_")
                           ) == pytest.approx(750.0)
    assert prog_op_ms.read(ctx, dict(train, pattern="^nothing")) is None
    from benchmark.lib import kernel_cost as K
    from benchmark.lib.peaks import device_peaks

    peaks = device_peaks("TPU v5 lite")
    one = K.flash_fwd(2 * 2, 1024, 1024, 64).floor_s(peaks)   # B 2 x H 2
    got = prog_roofline.read(ctx, {"kernel": "flash_fwd"})
    assert got == pytest.approx(100 * 4 * one / 0.8)          # 4 calls, 0.8 s
    bwd = sum(c.floor_s(peaks) for c in (
        K.flash_bwd_delta(4, 1024, 64), K.flash_bwd_dq(4, 1024, 1024, 64),
        K.flash_bwd_dkv(4, 1024, 1024, 64)))
    assert prog_roofline.read(ctx, {"kernel": "flash_bwd"}) == pytest.approx(
        100 * 2 * bwd / 1.5)
    assert "bound by flops" in capsys.readouterr().out
    # the floor holds for one call a layer over the chip's whole micro-batch
    for engine in ({"gradient_accumulation_steps": 4}, {"mesh": {"tp": 2}}):
        ctx.cell["config_file"]["engine"] = engine
        assert prog_roofline.read(ctx, {"kernel": "flash_fwd"}) is None
        assert "the step is split by" in capsys.readouterr().out
    ctx.cell["config_file"]["engine"] = {"mesh": {"dp": 4, "tp": 1}}
    assert prog_roofline.read(ctx, {"kernel": "flash_fwd"}) == pytest.approx(
        got)


def test_gaps_fall_to_the_innermost_program_span(tmp_path, monkeypatch):
    pt = train_trace(tmp_path)
    gaps = pt.reduced.gaps_by_span
    # 0.5-1.0: the gap begins outside every span until 0.9; 3.1-4.0 begins
    # while the host waits in train.sync; 6.1-7.0 likewise
    assert gaps["train.sync"] == pytest.approx(0.9 + 0.9)
    assert gaps[P.T.NO_SPAN] == pytest.approx(0.5)
    ctx = ctx_for(monkeypatch, pt)
    assert prog_host_gap_ms.read(ctx, {"span": "train.step"}
                                 ) == pytest.approx(1000 * 2.3 / 2)
    lines = program_gaps.report(pt, ["train_batch"])
    text = "\n".join(lines)
    assert "train.sync" in text and "jit_train_batch" in text
    assert "flash_bwd_dkv.6_mosaic" in text and "recompute/attn" in text


def serve_trace():
    spans = [
        P.Span("serve.step", 0.0, 1.0, {"step_num": 3}),
        P.Span("serve.admit.prefill", 0.1, 0.4, {"rids": "7 8"}),
        P.Span("engine.prefill.batch", 0.1, 0.3,
               {"real_tokens": 150, "padded_tokens": 512}),
        P.Span("engine.prefill.sample", 0.3, 0.4, {}),
        P.Span("serve.decode", 0.5, 0.9,
               {"steps": 2, "active": 3, "live_kv_tokens": 1000}),
        P.Span("engine.decode.enqueue", 0.5, 0.52, {}),
        P.Span("engine.decode.fetch", 0.52, 0.9, {}),
        P.Span("serve.step", 1.0, 1.5, {"step_num": 4}),
        P.Span("engine.prefill.fused", 1.0, 1.1,
               {"real_tokens": 50, "padded_tokens": 64}),
        P.Span("serve.decode", 1.2, 1.45,
               {"steps": 1, "active": 3, "live_kv_tokens": 1006}),
        P.Span("engine.decode.enqueue", 1.2, 1.21, {}),
    ]
    modules = {0: [("jit_prefill_batch_128", 0.1, 0.35),
                   ("jit_decode_block_2", 0.52, 0.88),
                   ("jit_prefill_fused_64", 1.0, 1.08),
                   ("jit_decode_block_1", 1.21, 1.43)]}
    ops = [("paged_decode.3", "mosaic", 0.55, 0.65),
           ("paged_decode.3", "mosaic", 0.70, 0.80),
           ("fusion.9", "fusion", 0.80, 0.88),
           ("paged_decode.5", "mosaic", 1.25, 1.40)]
    return P.ProgramTrace(
        (0.0, 1.5), spans, modules,
        {0: [(f"{i}_{k}", s, e) for i, k, s, e in ops]},
        {0: [(i, s, e) for i, _, s, e in ops]}, {})


def test_serve_readers(monkeypatch):
    pt = serve_trace()
    model = {"n_layer": 2, "n_head": 4, "d_model": 512}
    ctx = ctx_for(monkeypatch, pt, model)
    decode = {"pattern": "^jit_decode_block_(\\d+)$", "steps_group": 1}
    assert prog_module_ms.read(ctx, decode) == pytest.approx(
        1000 * (0.36 + 0.22) / 3)
    assert prog_module_ms.read(ctx, {"pattern": "^jit_prefill_batch_\\d+$"}
                               ) == pytest.approx(250.0)
    assert prog_module_ms.read(ctx, {"pattern": "^jit_fn$"}) is None
    assert prog_span_mean.read(ctx, {"span": "engine.decode.enqueue"}
                               ) == pytest.approx(15.0)
    less = {"span": "serve.step",
            "less": ["serve.admit.prefill", "serve.decode"]}
    assert prog_span_mean.read(ctx, less) == pytest.approx(
        1000 * ((1.0 - 0.3 - 0.4) + (0.5 - 0.25)) / 2)
    useful = {"span": "engine.prefill.batch", "of": "real_tokens",
              "over": "padded_tokens"}
    assert prog_span_ratio.read(ctx, useful) == pytest.approx(100 * 150 / 512)
    assert prog_span_ratio.read(ctx, dict(useful, span="engine.nothing")
                                ) is None
    text = "\n".join(program_gaps.report(pt, []))
    assert "150        512   29.3%  engine.prefill.batch" in text
    assert "50         64   78.1%  engine.prefill.fused" in text
    kernel = {"pattern": "^paged_decode", "span": "serve.decode",
              "count": "steps"}
    assert prog_op_ms.read(ctx, kernel) == pytest.approx(1000 * 0.35 / 3)
    # bytes of the live keys and values, both layers, each step of each block
    per_token = 2 * 2 * 512 * 2
    need = per_token * ((1000 + 3) + (1000 + 6) + (1006 + 3))
    assert prog_roofline.read(ctx, {"kernel": "paged_decode"}
                              ) == pytest.approx(100 * need / 819e9 / 0.35)


def test_readers_find_nothing_where_the_program_has_no_names(monkeypatch):
    """The parent of the PR that added the names: a trace with the harness's
    window and device operations, and none of the program's spans."""
    pt = P.ProgramTrace((0.0, 1.0), [], {0: [("jit_fn", 0.1, 0.9)]},
                        {0: [("closed_call.13_mosaic", 0.1, 0.9)]},
                        {0: [("closed_call.13", 0.1, 0.9)]}, {})
    ctx = ctx_for(monkeypatch, pt, {"n_layer": 2, "n_head": 4,
                                    "d_model": 512},
                  {"seq_len": 8, "tokens_per_step": 16})
    for reader, params in (
            (prog_op_ms, {"pattern": "^flash_fwd", "span": "train.step"}),
            (prog_roofline, {"kernel": "flash_fwd"}),
            (prog_roofline, {"kernel": "paged_decode"}),
            (prog_phase_ms, {"phase": "forward"}),
            (prog_host_gap_ms, {"span": "train.step"}),
            (prog_module_ms, {"pattern": "^jit_decode_block_(\\d+)$",
                              "steps_group": 1}),
            (prog_span_mean, {"span": "engine.decode.enqueue"})):
        assert reader.read(ctx, params) is None
    monkeypatch.setattr(P, "of", lambda ctx: None)        # no trace at all
    assert prog_span_mean.read(ctx, {"span": "serve.step"}) is None


# ------------------------------------------------ pieces recorded on the chip
def recorded(name, tmp_path):
    with open(os.path.join(DATA, name)) as f:
        data = json.load(f)
    kept = tmp_path / P.SCOPES_DIR
    kept.mkdir()
    for module, scopes in data["scopes"].items():   # <program>.<module id>
        (kept / f"{module}.json").write_text(json.dumps(scopes))
    return P.from_plain(data, str(tmp_path))


def test_recorded_serve_piece(tmp_path, monkeypatch):
    """75 ms of the batch-decode cell: one admission (a 192-token prompt as
    chunks of 128 and 64, the scatter, the wait for its first token) and the
    start of a decode dispatch over 96 slots."""
    pt = recorded("program_serve_1chip.json", tmp_path)
    names = [s.name for s in pt.spans]
    assert names[:4] == ["serve.step", "serve.housekeeping",
                         "serve.admit.claim", "serve.admit.prefill"]
    (prefill,) = pt.named("serve.admit.prefill")
    assert prefill.stats == {"rids": 197}
    (decode,) = pt.named("serve.decode")
    assert decode.stats == {"steps": 1, "active": 96, "live_kv_tokens": 16270}
    programs = pt.program_seconds
    assert programs["jit_prefill_chunk_128"] == (pytest.approx(7.708908e-3), 1)
    assert programs["jit_scatter"][0] == pytest.approx(39.331e-3, rel=1e-3)
    # the decode program was cut at the piece's edge, 15 ms in
    assert programs["jit_decode_block_1"] == (pytest.approx(15.11477e-3), 1)
    assert not any(n in ("jit_fn", "jit_fused") for n in programs)
    red = pt.reduced
    assert red.busy_s + sum(red.gaps_by_span.values()) == pytest.approx(
        red.window_s)
    # the device idles while the host is still placing the chunk's inputs,
    # and again between the first token's read and the decode dispatch
    assert set(red.gaps_by_span) <= {
        "serve.step", "serve.admit.prefill", "engine.prefill.chunk",
        "engine.prefill.sample", "serve.admit.commit", "serve.grow",
        "serve.decode", "engine.decode.enqueue", "engine.decode.fetch",
        "serve.admit.claim", "serve.housekeeping", P.T.NO_SPAN}
    assert pt.op_counts["paged_decode.9_mosaic"] == 2
    per = pt.instr_seconds
    assert "paged_decode.9" in per["jit_decode_block_1"]
    assert sum(sum(v.values()) for v in per.values()) == pytest.approx(
        red.busy_s)
    ctx = ctx_for(monkeypatch, pt, {"n_layer": 24, "n_head": 16,
                                    "d_model": 2048})
    assert ("192        192  100.0%  engine.prefill.chunk"
            in "\n".join(program_gaps.report(pt, [])))
    assert prog_module_ms.read(ctx, {"pattern": "^jit_prefill_chunk_128$"}
                               ) == pytest.approx(7.708908)
    assert prog_span_mean.read(ctx, {"span": "engine.prefill.sample"}
                               ) == pytest.approx(52.8, abs=0.2)
    kernel_ms = prog_op_ms.read(ctx, {"pattern": "^paged_decode",
                                      "span": "serve.decode",
                                      "count": "steps"})
    assert kernel_ms == pytest.approx(
        1000 * red.op_seconds["paged_decode.9_mosaic"])
    share = prog_roofline.read(ctx, {"kernel": "paged_decode"})
    assert 0.0 < share < 100.0


def test_recorded_train_piece(tmp_path, monkeypatch):
    """50 ms of the backward pass of the gpt2 train cell: three layers'
    recomputed forward and backward kernels, inside one ``train.step``."""
    pt = recorded("program_train_1chip.json", tmp_path)
    assert [s.name for s in pt.spans] == ["train.step", "train.sync"]
    assert pt.spans[0].stats["step_num"] == 5
    assert pt.module_ids == {"jit_train_batch": [MODULE_ID]}
    mosaic = {n for n in pt.reduced.op_seconds if n.endswith("_mosaic")}
    assert mosaic == {"flash_fwd.24_mosaic", "flash_bwd_delta.14_mosaic",
                      "flash_bwd_dq.14_mosaic", "flash_bwd_dkv.14_mosaic"}
    by_phase, by_scope, unnamed, _ = P.phase_seconds(pt, "train_batch")
    busy = pt.reduced.busy_s
    assert sum(by_phase.values()) == pytest.approx(busy)
    assert by_phase["forward"] == 0.0 and by_phase["optimizer"] == 0.0
    assert by_phase["recompute"] > 0.2 * busy
    assert by_phase["backward"] > 0.5 * busy
    assert by_phase["other"] < 0.02 * busy
    # the forward kernel run again inside the backward pass keeps its name;
    # its scope is what tells it from the first run
    fwd = pt.reduced.op_seconds["flash_fwd.24_mosaic"]
    assert by_scope[("recompute", "attn")] >= fwd
    ctx = ctx_for(monkeypatch, pt, {"n_head": 16, "d_model": 1024},
                  {"seq_len": 1024, "tokens_per_step": 16384})
    assert prog_phase_ms.read(ctx, {"phase": "backward"}) == pytest.approx(
        1000 * by_phase["backward"])
    fwd_share = prog_roofline.read(ctx, {"kernel": "flash_fwd"})
    bwd_share = prog_roofline.read(ctx, {"kernel": "flash_bwd"})
    assert 3.0 < fwd_share < 6.0 and 6.0 < bwd_share < 12.0
    assert prog_op_ms.read(ctx, {"pattern": "^flash_fwd",
                                 "span": "train.step"}
                           ) == pytest.approx(1000 * fwd)
