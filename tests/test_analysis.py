"""dslint static analyzer: every rule family fires on a deliberately-broken
program and stays silent on a known-good one.

The broken programs are minimal renderings of the real bug classes:
replicated big param under ZeRO-3, fp32 matmul leak out of a bf16 path,
missed donation of a state-sized buffer, cond branches disagreeing on their
collective order inside shard_map, and a quantization knob the traced program
contradicts. The clean baseline is the shipped TINY GPT engine.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.analysis import (
    AnalysisError,
    AnalysisOptions,
    Severity,
    analyze_engine,
    analyze_fn,
    cli,
)
from deepspeed_tpu.models import GPTConfig, build_gpt
from deepspeed_tpu.models.api import Module

TINY = GPTConfig(vocab_size=256, n_layer=2, n_head=4, d_model=64,
                 max_seq_len=64)


def tiny_engine(stage=3, micro=4, **zero_over):
    model, _ = build_gpt(TINY)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": stage, **zero_over},
            "steps_per_print": 0,
        })
    return engine


def flat_module(shape=(64, 96), n=1):
    """A Module with ``n`` weight leaves of ``shape`` and a quadratic loss —
    small, no gather machinery, no gpt_config."""

    def init(rng):
        return {f"w{i}": jnp.zeros(shape, jnp.float32) for i in range(n)}

    def apply(params, batch, rngs=None, train=True, **kw):
        x = batch["x"]
        loss = sum(jnp.mean((x @ w[:x.shape[-1], :x.shape[-1]]) ** 2)
                   for w in params.values()) + jnp.mean(x ** 2)
        return loss, {}

    return Module(init=init, apply=apply)


# --------------------------------------------------------------------- clean
def test_clean_engine_no_findings(devices):
    """The shipped engine must lint clean: no WARNING/ERROR on any family."""
    engine = tiny_engine(stage=3)
    report = analyze_engine(engine, compile=True)
    bad = [f for f in report.findings if f.severity >= Severity.WARNING]
    assert not bad, report.render()


def test_clean_quantized_engine_no_errors(devices):
    """qw8 engine: int wire present, so the config rule stays silent."""
    engine = tiny_engine(stage=3, zero_quantized_weights=True)
    report = analyze_engine(engine)
    assert not report.errors(), report.render()
    assert not report.by_rule("config/quantized-wire-missing")


# ------------------------------------------------------------------ sharding
def test_replicated_large_array_fires_once(devices):
    """ZeRO-3 declared, but the single param leaf has no mesh-divisible dim
    (7 x 513) — the policy falls back to replication and the rule must say
    so."""
    model = flat_module(shape=(7, 513))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                # SGD without momentum: no opt-state leaves, so the single
                # param leaf is the only replicated buffer to flag
                "optimizer": {"type": "SGD", "params": {"lr": 1e-3}},
                "zero_optimization": {
                    "stage": 3, "stage3_param_persistence_threshold": 0},
                "steps_per_print": 0})
    batch = {"x": jax.ShapeDtypeStruct((8, 7), jnp.float32)}
    report = analyze_engine(
        engine, batch=batch,
        options=AnalysisOptions(replicated_bytes=1024, donation_bytes=1 << 30))
    hits = report.by_rule("sharding/replicated-large-array")
    assert len(hits) == 1, report.render()
    assert hits[0].severity == Severity.ERROR


def test_replicated_rule_silent_when_policy_shards(devices):
    engine = tiny_engine(stage=3)
    report = analyze_engine(
        engine, options=AnalysisOptions(replicated_bytes=1024))
    assert not report.by_rule("sharding/replicated-large-array"), \
        report.render()


# ----------------------------------------------------------------- precision
def test_fp32_leak_fires_once(devices):
    def leaky(x, w):
        h = x.astype(jnp.float32) @ w.astype(jnp.float32)  # the leak
        return jnp.sum(h.astype(jnp.bfloat16))

    x = jax.ShapeDtypeStruct((128, 128), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((128, 128), jnp.bfloat16)
    report = analyze_fn(leaky, x, w, name="leaky")
    hits = report.by_rule("precision/fp32-leak")
    assert len(hits) == 1, report.render()


def test_fp32_leak_silent_on_clean_bf16(devices):
    def clean(x, w):
        h = x @ w  # stays bf16; fp32 only after the matmul
        return jnp.sum(h.astype(jnp.float32))

    x = jax.ShapeDtypeStruct((128, 128), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((128, 128), jnp.bfloat16)
    report = analyze_fn(clean, x, w, name="clean")
    assert not report.by_rule("precision/fp32-leak"), report.render()


def test_low_precision_accumulation_fires(devices):
    """The realistic rendering: the backward of a broadcast-add sums 4M bf16
    cotangents in bf16 (jnp.sum itself upcasts its accumulator — the forward
    path is fine; the cotangent reduction is where the tail gets dropped)."""

    def fwd(x, b):
        return jnp.sum(((x + b).astype(jnp.float32)) ** 2)

    x = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((2048,), jnp.bfloat16)
    report = analyze_fn(jax.grad(fwd, argnums=1), x, b, name="bcast-bwd")
    assert len(report.by_rule("precision/low-precision-accumulation")) == 1, \
        report.render()


# ----------------------------------------------------------------- host-sync
def test_callback_in_step_fires_once(devices):
    def with_callback(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return jnp.sum(y)

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    report = analyze_fn(with_callback, x, name="cb")
    hits = report.by_rule("host-sync/callback-in-step")
    assert len(hits) == 1, report.render()
    assert hits[0].severity == Severity.ERROR


def test_donation_miss_fires_once_and_donating_fixes_it(devices):
    def step(state, batch):
        return state + batch.sum(), jnp.mean(batch)

    state = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MB
    batch = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    report = analyze_fn(step, state, batch, name="nodonate")
    assert len(report.by_rule("host-sync/donation-miss")) == 1, report.render()

    fixed = analyze_fn(step, state, batch, name="donated",
                       donate_argnums=(0,))
    assert not fixed.by_rule("host-sync/donation-miss"), fixed.render()


# ----------------------------------------------------- collective order
def test_divergent_branch_collectives_fires_once(devices):
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()), ("dp",))

    def body(x, flag):
        def with_psum(v):
            return jax.lax.psum(v, "dp")

        def without(v):
            return v * 2.0

        return jax.lax.cond(flag[0] > 0, with_psum, without, x)

    fn = shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                   out_specs=P("dp"), check_vma=False)
    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    flag = jax.ShapeDtypeStruct((1,), jnp.int32)
    report = analyze_fn(fn, x, flag, name="divergent", mesh=mesh)
    hits = report.by_rule("collective/divergent-branch-order")
    assert len(hits) == 1, report.render()
    assert hits[0].severity == Severity.ERROR


def test_balanced_branch_collectives_silent(devices):
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()), ("dp",))

    def body(x, flag):
        def a(v):
            return jax.lax.psum(v * 2.0, "dp")

        def b(v):
            return jax.lax.psum(v + 1.0, "dp")

        return jax.lax.cond(flag[0] > 0, a, b, x)

    fn = shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                   out_specs=P("dp"), check_vma=False)
    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    flag = jax.ShapeDtypeStruct((1,), jnp.int32)
    report = analyze_fn(fn, x, flag, name="balanced", mesh=mesh)
    assert not report.by_rule("collective/divergent-branch-order"), \
        report.render()


def test_collective_in_while_predicate_fires(devices):
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()), ("dp",))

    def body(x):
        def cond(c):
            return jax.lax.psum(jnp.sum(c), "dp") < 100.0

        return jax.lax.while_loop(cond, lambda c: c * 2.0, x)

    fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                   check_vma=False)
    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    report = analyze_fn(fn, x, name="whilecoll", mesh=mesh)
    assert len(report.by_rule("collective/collective-in-while-predicate")) == 1


# -------------------------------------------------------------------- config
def test_quantized_wire_missing_fires_once(devices):
    """zero_quantized_weights promised, but the model has no gather path —
    the traced step moves no int payload and the knob is inert."""
    model = flat_module(shape=(64, 96))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3,
                                      "zero_quantized_weights": True},
                "steps_per_print": 0})
    batch = {"x": jax.ShapeDtypeStruct((8, 64), jnp.float32)}
    report = analyze_engine(engine, batch=batch)
    hits = report.by_rule("config/quantized-wire-missing")
    assert len(hits) == 1, report.render()
    assert hits[0].severity == Severity.ERROR


def test_quantized_weights_below_stage3_warns(devices):
    engine = tiny_engine(stage=2, zero_quantized_weights=True)
    report = analyze_engine(engine)
    assert report.by_rule("config/quantized-weights-below-stage3")
    # inert-wire is the ERROR-level companion: below stage 3 the gathers the
    # knob targets don't exist, so the wire is empty too
    assert report.by_rule("config/quantized-wire-missing")


# ------------------------------------------------------------- engine gating
def test_analysis_config_block_runs_at_init(devices):
    model, _ = build_gpt(TINY)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3},
                "analysis": {"enabled": True},
                "steps_per_print": 0})
    assert engine._analysis_pending is False  # ran at init (gpt batch synth)


def test_analysis_fail_on_error_raises_at_first_step(devices):
    """Non-GPT model: init defers (no batch to synthesize); the first
    train_batch analyzes with the real batch and raises on the inert-knob
    ERROR before executing anything."""
    model = flat_module(shape=(64, 96))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3,
                                      "zero_quantized_weights": True},
                "analysis": {"enabled": True},
                "steps_per_print": 0})
    assert engine._analysis_pending is True
    with pytest.raises(AnalysisError):
        engine.train_batch({"x": np.zeros((8, 64), np.float32)})


# ------------------------------------------------------------------- pipe/CLI
def test_mpmd_schedule_pairing_sound():
    from deepspeed_tpu.runtime.pipe.mpmd import validate_schedule_pairing

    for m, s in [(2, 2), (4, 2), (8, 4), (3, 3)]:
        assert validate_schedule_pairing(m, s) == [], (m, s)


@pytest.mark.parametrize("row", cli.TARGETS, ids=lambda r: r["name"])
def test_cli_target_is_analyzable(row):
    """Each row of the CLI's own table names a real preset and maps to a
    config the runtime validates; the default target is one of them."""
    from deepspeed_tpu.models.gpt import PRESETS
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    names = [r["name"] for r in cli.TARGETS]
    assert len(set(names)) == len(names) == 10 and cli.DEFAULT_BENCH in names
    assert row["model"] in PRESETS
    cfg = DeepSpeedConfig.load(cli._row_to_ds_config(row), world_size=1)
    assert cfg.train_micro_batch_size_per_gpu == row["micro_bs"]
    assert cfg.zero_optimization.stage == row.get("stage", 0)


def test_profiler_reports_static_flops(devices):
    from deepspeed_tpu.profiling import profile_compiled_fn

    a = jnp.ones((64, 64), jnp.float32)
    prof = profile_compiled_fn(lambda x: x @ x, a)
    assert prof["flops"] > 0
    assert prof["flops_source"] in ("compiled", "lowered")


# ------------------------------------------------------------ config/resilience
def test_checkpoint_uncommitted_load_rule(tmp_path):
    """Resume config pointing at a COMMIT-less tag warns at lint time; a
    committed tag (or nothing to resume) stays silent."""
    from deepspeed_tpu.analysis.core import AnalysisContext
    from deepspeed_tpu.analysis.rules_config import CheckpointUncommittedLoadRule
    from deepspeed_tpu.resilience import commit_tag
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    rule = CheckpointUncommittedLoadRule()
    tag_dir = tmp_path / "global_step5"
    (tag_dir / "state").mkdir(parents=True)
    (tag_dir / "state" / "state.msgpack").write_bytes(b"x" * 32)
    cfg = DeepSpeedConfig.load({
        "train_micro_batch_size_per_gpu": 1,
        "resilience": {"enabled": True, "save_dir": str(tmp_path),
                       "resume_tag": "global_step5"}})
    findings = list(rule.check_context(AnalysisContext(config=cfg)))
    assert len(findings) == 1
    assert "COMMIT" in findings[0].message
    assert findings[0].severity == Severity.WARNING

    commit_tag(str(tag_dir))  # now committed -> silent
    assert not list(rule.check_context(AnalysisContext(config=cfg)))

    # resume_tag naming a directory that does not exist -> flagged
    cfg_missing = DeepSpeedConfig.load({
        "train_micro_batch_size_per_gpu": 1,
        "resilience": {"enabled": True, "save_dir": str(tmp_path),
                       "resume_tag": "global_step99"}})
    findings = list(rule.check_context(AnalysisContext(config=cfg_missing)))
    assert len(findings) == 1 and "does not exist" in findings[0].message

    # fresh run (no latest, no pin): nothing to resume, nothing to flag
    fresh = tmp_path / "fresh"
    fresh.mkdir()
    cfg_fresh = DeepSpeedConfig.load({
        "train_micro_batch_size_per_gpu": 1,
        "resilience": {"enabled": True, "save_dir": str(fresh)}})
    assert not list(rule.check_context(AnalysisContext(config=cfg_fresh)))


def test_rollback_without_data_cursor_rule(tmp_path):
    """Divergence rollback armed without a cursor-checkpointable dataloader
    warns; declaring the cursor (config flag or resume_state_provider)
    silences it, as does leaving the sentinel off."""
    from deepspeed_tpu.analysis.core import AnalysisContext
    from deepspeed_tpu.analysis.rules_config import RollbackWithoutDataCursorRule
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    rule = RollbackWithoutDataCursorRule()

    def cfg(sentinel):
        return DeepSpeedConfig.load({
            "train_micro_batch_size_per_gpu": 1,
            "resilience": {"enabled": True, "save_dir": str(tmp_path),
                           "sentinel": sentinel}})

    armed = cfg({"enabled": True})
    findings = list(rule.check_context(AnalysisContext(config=armed)))
    assert len(findings) == 1
    assert findings[0].severity == Severity.WARNING
    assert findings[0].rule_id == "config/rollback-without-data-cursor"

    # declared cursor-checkpointable -> silent
    declared = cfg({"enabled": True, "cursor_checkpointable": True})
    assert not list(rule.check_context(AnalysisContext(config=declared)))

    # a registered resume_state_provider on the engine -> silent
    class _Eng:
        resume_state_provider = staticmethod(lambda: {"cursor": 0})

    assert not list(rule.check_context(
        AnalysisContext(config=armed, engine=_Eng())))

    # sentinel off -> nothing armed, nothing to flag
    off = cfg({"enabled": False})
    assert not list(rule.check_context(AnalysisContext(config=off)))


# ----------------------------------------------- coverage gaps + meta-test
def test_unaccounted_collective_fires_and_silent():
    """Quantized collectives configured, yet the post-GSPMD HLO moves a
    full-precision all-gather: fires with the op + bytes named. Silent when
    the payload is int (that IS the quantized wire) and when no
    quantization is configured."""
    from deepspeed_tpu.analysis.core import AnalysisContext
    from deepspeed_tpu.analysis.ir import ProgramIR
    from deepspeed_tpu.analysis.rules_sharding import UnaccountedCollectiveRule
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    rule = UnaccountedCollectiveRule()
    cjx = jax.make_jaxpr(lambda x: x)(1.0)

    def prog(hlo):
        return ProgramIR(name="p", closed_jaxpr=cjx, in_avals=[],
                         out_avals=[], donated=[], hlo=hlo)

    qcfg = DeepSpeedConfig.load({
        "train_micro_batch_size_per_gpu": 1,
        "zero_optimization": {"stage": 2, "zero_quantized_gradients": True}})
    f32_ag = ("  %ag = f32[1048576]{0} all-gather(f32[131072]{0} %p0), "
              "dimensions={0}\n")
    hits = list(rule.check_program(prog(f32_ag),
                                   AnalysisContext(config=qcfg)))
    assert len(hits) == 1, hits
    assert hits[0].rule_id == "sharding/unaccounted-collective"
    assert "all-gather" in hits[0].message and "4.0 MB" in hits[0].message

    # int payload: that IS the quantized wire -> silent
    s8_ag = ("  %ag = s8[4194304]{0} all-gather(s8[524288]{0} %p0), "
             "dimensions={0}\n")
    assert not list(rule.check_program(prog(s8_ag),
                                       AnalysisContext(config=qcfg)))
    # no quantization configured -> nothing to cross-check -> silent
    plain = DeepSpeedConfig.load({"train_micro_batch_size_per_gpu": 1})
    assert not list(rule.check_program(prog(f32_ag),
                                       AnalysisContext(config=plain)))


def test_f64_present_fires_and_silent(devices):
    def promoting(x):
        return jnp.sum(x.astype(jnp.float64) * 2.0)

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    with jax.enable_x64(True):
        report = analyze_fn(promoting, x, name="f64leak")
    hits = report.by_rule("precision/f64-present")
    assert len(hits) == 1, report.render()
    assert hits[0].severity == Severity.ERROR

    report = analyze_fn(lambda x: jnp.sum(x * 2.0), x, name="f32clean")
    assert not report.by_rule("precision/f64-present"), report.render()


def test_shard_map_signature_inventory_and_silent(devices):
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()), ("dp",))

    def body(x):
        return jax.lax.psum(x, "dp")

    fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                   check_vma=False)
    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    report = analyze_fn(fn, x, name="smap", mesh=mesh)
    hits = report.by_rule("collective/shard-map-signature")
    assert len(hits) == 1, report.render()
    assert hits[0].severity == Severity.INFO
    assert "psum" in hits[0].message

    # no shard_map in the program -> no inventory line
    report = analyze_fn(lambda x: jnp.sum(x), x, name="plain")
    assert not report.by_rule("collective/shard-map-signature")


def test_loss_scale_dtype_rule_fires_and_silent():
    from types import SimpleNamespace

    from deepspeed_tpu.analysis.core import AnalysisContext
    from deepspeed_tpu.analysis.rules_config import LossScaleDtypeRule

    rule = LossScaleDtypeRule()

    def eng(dtype):
        return SimpleNamespace(
            pc=SimpleNamespace(loss_scaling=True),
            state={"scaler": SimpleNamespace(
                scale=jnp.asarray(1024.0, dtype))})

    hits = list(rule.check_context(AnalysisContext(engine=eng(jnp.bfloat16))))
    assert len(hits) == 1 and hits[0].rule_id == "config/loss-scale-dtype"
    assert not list(rule.check_context(
        AnalysisContext(engine=eng(jnp.float32))))


def test_rules_silent_on_clean_programs(devices):
    """The fire-only-tested rules, pinned silent by id on known-good inputs
    (the other half of the fire/silent contract the meta-test enforces)."""
    from deepspeed_tpu.analysis import analyze_compile_log

    # clean fp32 reduction, no callbacks, no while predicates
    x = jax.ShapeDtypeStruct((2048, 2048), jnp.float32)
    report = analyze_fn(lambda x: jnp.sum(x ** 2), x, name="cleansum")
    for rid in ("precision/low-precision-accumulation",
                "host-sync/callback-in-step",
                "collective/collective-in-while-predicate"):
        assert not report.by_rule(rid), report.render()

    # clean tiny engine: the quantized-collective gates have nothing to flag
    report = analyze_engine(tiny_engine(stage=3))
    for rid in ("collective/unoverlapped-quantized-collective",
                "config/quantized-weights-below-stage3"):
        assert not report.by_rule(rid), report.render()

    # serving: bounded admission and an armed fleet stay out of the report
    from types import SimpleNamespace

    from deepspeed_tpu.inference.serving import ServingConfig

    bounded = SimpleNamespace(serving=ServingConfig(max_queue=8),
                              compile_log=[])
    assert not analyze_compile_log(bounded).by_rule(
        "serving/unbounded-admission")
    fleet = SimpleNamespace(
        replicas=[object(), object()],
        config=SimpleNamespace(heartbeat_deadline_s=None, reroute_budget=2),
        compile_log=[])
    assert not analyze_compile_log(fleet).by_rule(
        "serving/fleet-without-failover")
    bucketed = [{"kind": "decode", "shape": (1, b)} for b in (8, 16, 32, 64)]
    assert not analyze_compile_log(bucketed).by_rule(
        "serving/unbucketed-decode-shape")


def test_meta_every_rule_documented_and_tested():
    """Every shipped rule id (default_rules — the compile-log serving set is
    a subset) must have a docs/STATIC_ANALYSIS.md catalog heading and be
    exercised from tests at least twice (the fire + silent convention),
    referenced by rule id or by rule class name."""
    import glob
    import os

    from deepspeed_tpu.analysis import default_rules

    rules = default_rules()
    ids = [r.rule_id for r in rules]
    assert len(ids) == len(set(ids)), "duplicate rule ids"
    # the pipeline-prover family is registered in the default set
    for rid in ("pipe/unpaired-send-recv", "pipe/schedule-deadlock",
                "pipe/stale-weight-application"):
        assert rid in ids

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "STATIC_ANALYSIS.md")) as fh:
        doc = fh.read()
    sources = ""
    for path in sorted(glob.glob(os.path.join(root, "tests", "*.py"))):
        with open(path) as fh:
            sources += fh.read()

    missing_doc = [r.rule_id for r in rules
                   if f"### `{r.rule_id}`" not in doc]
    assert not missing_doc, (
        f"rules without a docs/STATIC_ANALYSIS.md heading: {missing_doc}")
    undocumented = [r.rule_id for r in rules if not r.description]
    assert not undocumented, f"rules without a description: {undocumented}"
    untested = [
        r.rule_id for r in rules
        if sources.count(r.rule_id) + sources.count(type(r).__name__) < 2]
    assert not untested, (
        f"rules without a fire + silent test reference: {untested}")


def test_cli_list_json_emits_rule_registry():
    """--list --json: machine-readable per-rule family/severity/doc-anchor,
    with every anchor resolving to a real docs/STATIC_ANALYSIS.md heading."""
    import io
    import json
    from contextlib import redirect_stdout

    from deepspeed_tpu.analysis import cli, default_rules

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(["--list", "--json"])
    assert rc == 0
    data = json.loads(buf.getvalue())
    assert {r["rule_id"] for r in data["rules"]} == {
        r.rule_id for r in default_rules()}
    for r in data["rules"]:
        assert r["family"] == r["rule_id"].split("/")[0]
        assert r["severity"] in ("ERROR", "WARNING", "INFO")
        assert r["description"]
        assert r["doc_anchor"].startswith("docs/STATIC_ANALYSIS.md#"), r
    assert data["configs"] and all("name" in c for c in data["configs"])


def test_cli_json_mode_gates_on_error_findings(monkeypatch):
    """The --json path must exit 2 on ERROR findings exactly like the text
    path (CI parses the JSON *and* trusts the exit code)."""
    import io
    import json
    from contextlib import redirect_stdout

    from deepspeed_tpu.analysis import cli
    from deepspeed_tpu.analysis.core import Finding, Report

    bad = Report(findings=[Finding(
        rule_id="pipe/schedule-deadlock", severity=Severity.ERROR,
        location="x", message="injected")])
    monkeypatch.setattr(cli, "analyze_row", lambda row, **kw: bad)

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(["--json"])
    assert rc == 2
    out = json.loads(buf.getvalue())
    assert out["findings"][0]["severity"] == "ERROR"

    with redirect_stdout(io.StringIO()):
        assert cli.main(["--json", "--fail-on", "never"]) == 0
        assert cli.main([]) == 2  # text path gates identically


def test_cli_schedules_gate_proves_and_prices():
    """--schedules: every generated schedule in the matrix proves clean, and
    both interleaved and zero-bubble beat 1F1B's static bubble at equal
    microbatches (the PR's headline row, CI-gated)."""
    import io
    import json
    from contextlib import redirect_stdout

    from deepspeed_tpu.analysis import cli

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(["--schedules", "--json"])
    assert rc == 0
    for entry in json.loads(buf.getvalue()):
        assert entry["n_errors"] == 0
        by_kind = {rep["schedule"].split("[")[0]: rep
                   for rep in entry["schedules"]}
        assert all(rep["ok"] for rep in by_kind.values())
        b1 = by_kind["1f1b"]["bubble"]["bubble_frac"]
        assert by_kind["interleaved"]["bubble"]["bubble_frac"] < b1
        assert by_kind["zero-bubble"]["bubble"]["bubble_frac"] < b1
