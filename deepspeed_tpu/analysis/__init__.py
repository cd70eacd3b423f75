"""``dslint`` — static analysis for engine/pjit programs (jaxpr + HLO).

Catches the GSPMD-silent bug classes before they burn accelerator time:
sharding (silent replication, unaccounted wire traffic), precision (fp32
leaks out of the bf16 path, low-precision accumulation), host-sync (callbacks
in the step, missed donations), collective order (the shard_map/multihost
deadlock class), and config knobs the compiled program contradicts.

Three entry points:

- ``engine.analyze()`` / :func:`analyze_engine` — analyze a live engine's
  fused train program + its state/config (all rule families).
- :func:`analyze_fn` — analyze any function/pjit program on abstract args.
- ``python -m deepspeed_tpu.analysis`` — CLI over its table of train configs
  (:mod:`deepspeed_tpu.analysis.cli`).

Nothing here executes device code: programs are traced/lowered (optionally
compiled with ``compile=True`` for the post-GSPMD HLO rules) and walked.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

from .core import (
    AnalysisContext,
    AnalysisError,
    AnalysisOptions,
    Analyzer,
    Finding,
    Report,
    Rule,
    Severity,
)
from .ir import ProgramIR, capture
from .rules_collectives import collective_rules
from .rules_config import config_rules
from .rules_hostsync import hostsync_rules
from .rules_offload import offload_rules
from .rules_pipeline import pipeline_rules
from .rules_precision import precision_rules
from .rules_resilience import resilience_rules
from .rules_serving import serving_rules
from .rules_sharding import sharding_rules
from .schedule import ScheduleIR, prove_schedule, schedule_report


def default_rules() -> List[Rule]:
    """The shipped rule set, all nine families."""
    return (sharding_rules() + precision_rules() + hostsync_rules()
            + collective_rules() + config_rules() + serving_rules()
            + offload_rules() + pipeline_rules() + resilience_rules())


def options_from_config(block) -> AnalysisOptions:
    """Resolve an ``analysis`` config block (``runtime/config.py``) into
    :class:`AnalysisOptions`."""
    if block is None:
        return AnalysisOptions()
    return AnalysisOptions(
        replicated_bytes=int(float(getattr(
            block, "replicated_mb_threshold", 16.0)) * 2**20),
        donation_bytes=int(float(getattr(
            block, "donation_mb_threshold", 1.0)) * 2**20),
        include=tuple(getattr(block, "include", ()) or ()),
        exclude=tuple(getattr(block, "exclude", ()) or ()),
    )


def _abstract(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def synthesize_batch(engine, seq: Optional[int] = None):
    """An abstract ``train_batch`` input for a GPT-family engine (the layout
    ``engine.train_batch`` expects: ``[gas, bs, seq]`` when gas>1). Returns
    None when the model doesn't expose a ``gpt_config`` to synthesize from."""
    import jax
    import jax.numpy as jnp

    cfg = getattr(engine.model, "gpt_config", None)
    if cfg is None:
        return None
    seq = int(seq or min(cfg.max_seq_len, 512))
    bs = engine.micro_batch_size * engine.topo.data_parallel_size
    shape = (engine.gas, bs, seq) if engine.gas > 1 else (bs, seq)
    return {"input_ids": jax.ShapeDtypeStruct(shape, jnp.int32)}


def analyze_engine(engine, batch: Any = None, compile: bool = False,
                   options: Optional[AnalysisOptions] = None,
                   rules: Optional[Sequence[Rule]] = None,
                   seq: Optional[int] = None) -> Report:
    """Analyze an engine's fused train program without executing it.

    ``batch``: a sample batch (arrays or ShapeDtypeStructs) in the layout
    ``train_batch`` takes; synthesized from ``model.gpt_config`` when omitted.
    ``compile=True`` additionally runs the XLA pipeline to get the post-GSPMD
    HLO (enables the wire-traffic cross-check; costs compile time, executes
    nothing).
    """
    import jax

    from ..runtime.topology import mesh_context

    if options is None and getattr(engine.config, "analysis", None) is not None:
        options = options_from_config(engine.config.analysis)
    ctx = AnalysisContext(engine=engine, config=engine.config,
                          mesh=engine.mesh,
                          options=options or AnalysisOptions())
    analyzer = Analyzer(rules=rules, options=ctx.options)

    if engine._onebit or engine._offload or engine._param_stream:
        # host-runner engines interleave host work: their step is not one
        # jitted program to capture — run the context rules and say so
        report = analyzer.run([], ctx)
        report.findings.append(Finding(
            rule_id="analysis/partial",
            severity=Severity.INFO,
            location="engine",
            message="host-runner engine (1-bit / offload / param-stream): "
                    "program-level rules skipped, context rules only",
        ))
        return report

    if batch is None:
        batch = synthesize_batch(engine, seq=seq)
        if batch is None:
            raise ValueError(
                "analyze_engine: pass a sample batch (the model exposes no "
                "gpt_config to synthesize one from)")
    else:
        batch = engine._apply_curriculum(batch)
        cast = (engine.pc.compute_dtype
                if (engine.config.fp16.enabled and engine.config.fp16.auto_cast)
                else None)

        def to_aval(x):
            import jax.numpy as jnp

            x = x if hasattr(x, "dtype") else jnp.asarray(x)
            dt = (cast if cast is not None
                  and jnp.issubdtype(x.dtype, jnp.floating) else x.dtype)
            return jax.ShapeDtypeStruct(x.shape, dt)

        batch = jax.tree_util.tree_map(to_aval, batch)

    state_avals = _abstract(engine.state)
    rng_aval = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    with mesh_context(engine.mesh):
        prog = capture(engine._train_batch_jit, state_avals, batch, rng_aval,
                       name="train_batch", compile=compile)
    return analyzer.run([prog], ctx)


def analyze_compile_log(engine_or_log,
                        rules: Optional[Sequence[Rule]] = None) -> Report:
    """Audit an Inference/Serving engine's compiled-program cache-miss
    stream (``engine.compile_log``) — or a raw list of
    ``{"kind", "shape"}`` events — for the recompile-per-step pathology
    (``serving/unbucketed-decode-shape``). Pure host analysis: no tracing,
    no device work."""
    if isinstance(engine_or_log, (list, tuple)):
        ctx = AnalysisContext(compile_log=list(engine_or_log))
    else:
        ctx = AnalysisContext(engine=engine_or_log)
    return Analyzer(rules=rules or serving_rules(),
                    options=ctx.options).run([], ctx)


def analyze_schedule(schedules,
                     rules: Optional[Sequence[Rule]] = None) -> Report:
    """Prove pipeline-schedule IR(s) (:class:`~.schedule.ScheduleIR`, or a
    list of them) through the analyzer: per-channel send/recv pairing,
    deadlock-freedom, weight-version consistency (``pipe/*`` rules —
    docs/STATIC_ANALYSIS.md "Pipeline schedules"). Pure host analysis: no
    tracing, no device work."""
    ctx = AnalysisContext(schedules=schedules)
    report = Analyzer(rules=rules or pipeline_rules(),
                      options=ctx.options).run([], ctx)
    irs = schedules if isinstance(schedules, (list, tuple)) else [schedules]
    report.programs = [ir.name for ir in irs]
    return report


def analyze_fn(fn: Callable, *args, name: str = "program",
               donate_argnums: Sequence[int] = (), compile: bool = False,
               config: Any = None, mesh: Any = None,
               options: Optional[AnalysisOptions] = None,
               rules: Optional[Sequence[Rule]] = None, **kwargs) -> Report:
    """Analyze any function / pjit program on (abstract) args."""
    prog = capture(fn, *args, name=name, compile=compile,
                   donate_argnums=donate_argnums, **kwargs)
    if mesh is None:
        # best effort: the ambient mesh, if the caller bound one
        try:
            from ..runtime.topology import get_topology

            topo = get_topology()
            mesh = topo.mesh if topo is not None else None
        except Exception:
            mesh = None
    ctx = AnalysisContext(config=config, mesh=mesh,
                          options=options or AnalysisOptions())
    return Analyzer(rules=rules, options=ctx.options).run([prog], ctx)


__all__ = [
    "Severity", "Finding", "Rule", "Report", "Analyzer", "AnalysisContext",
    "AnalysisOptions", "AnalysisError", "ProgramIR", "capture",
    "default_rules", "options_from_config", "analyze_engine", "analyze_fn",
    "analyze_compile_log", "analyze_schedule", "synthesize_batch",
    "offload_rules", "pipeline_rules", "resilience_rules", "ScheduleIR",
    "prove_schedule", "schedule_report",
]
