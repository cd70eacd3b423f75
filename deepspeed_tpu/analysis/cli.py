"""``python -m deepspeed_tpu.analysis`` — dslint over the :data:`TARGETS` table.

Builds the engine a target row describes, captures its fused train program
WITHOUT executing a step, and runs the rule families. For models too large to
materialize on the local host, falls back to the abstract AOT path
(``runtime/aot.py``'s ``fused_train_step`` over ``ShapeDtypeStruct`` state —
nothing allocated).

Exit status: 0 clean (or warnings only), 2 when ERROR-severity findings exist
(``--fail-on never`` disables), 1 on usage errors. CI gates on this
(``scripts/verify_tier1.sh``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

# the default target: quantized ZeRO-3 param gathers
DEFAULT_BENCH = "gpt2-125m-zero3-qw8"

# above this many params the real engine (materialized state) is replaced by
# the abstract AOT capture — the analyzer must never OOM the host it guards
ABSTRACT_PARAM_FLOOR = int(4e8)


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


# quantized ZeRO collectives at one geometry; fp32 compute because the wire
# ratio is measured against the logical dtype
_QZ = {"model": "gpt2-125m", "micro_bs": 4, "seq": 512, "precision": "fp32"}

#: the train configs the CLI analyzes, each with the keys ``_row_to_ds_config``,
#: ``_build_model`` and ``analyze_row`` read (the three ``zero3-qw8*`` names are
#: one program to the analyzer). The host-offload rows go through the abstract
#: path.
TARGETS: List[Dict[str, Any]] = [
    {"name": "gpt2-125m-zero3-fp", **_QZ, "stage": 3},
    {"name": "gpt2-125m-zero3-qw8", **_QZ, "stage": 3,
     "quantized_weights": True},
    {"name": "gpt2-125m-zero2-fp", **_QZ, "stage": 2},
    {"name": "gpt2-125m-zero2-qg8", **_QZ, "stage": 2,
     "quantized_gradients": True},
    {"name": "gpt2-125m-zero3-qw8-overlap", **_QZ, "stage": 3,
     "quantized_weights": True},
    {"name": "gpt2-125m-zero3-qw8-inline", **_QZ, "stage": 3,
     "quantized_weights": True},
    {"name": "gpt2-1.3b-infinity", "model": "gpt2-1.3b", "micro_bs": 16,
     "seq": 1024, "offload": "param_stream", "keep_layers": 2},
    {"name": "gpt-neox-6.7b-infinity", "model": "gpt-neox-6.7b",
     "micro_bs": 16, "seq": 1024, "offload": "param_stream",
     "keep_layers": 2},
    {"name": "bloom-7b1-infinity-streamed", "model": "bloom-7b1",
     "micro_bs": 4, "seq": 1024, "offload": "param_stream",
     "keep_layers": 2},
    {"name": "gpt2-1.3b-offload-opt", "model": "gpt2-1.3b", "micro_bs": 8,
     "seq": 1024, "offload": "optimizer", "stage": 1, "loss_chunk": 128},
]


def _doc_anchors() -> Dict[str, str]:
    """rule_id -> GitHub-style anchor into docs/STATIC_ANALYSIS.md, parsed
    from the actual headings so the links cannot drift from the doc."""
    path = os.path.join(_repo_root(), "docs", "STATIC_ANALYSIS.md")
    anchors: Dict[str, str] = {}
    try:
        lines = open(path, encoding="utf-8").read().splitlines()
    except OSError:
        return anchors
    for ln in lines:
        if not ln.startswith("#"):
            continue
        text = ln.lstrip("#").strip().replace("`", "")
        slug = re.sub(r"[^\w\- ]", "", text.lower()).strip().replace(" ", "-")
        for rid in re.findall(r"`([a-z0-9_\-]+/[a-z0-9_\-]+)`", ln):
            anchors.setdefault(rid, f"docs/STATIC_ANALYSIS.md#{slug}")
    return anchors


def rule_registry() -> List[Dict[str, Any]]:
    """Machine-readable registry of the shipped rule set: per-rule family,
    severity, description, and doc anchor (``--list --json``)."""
    from . import default_rules

    anchors = _doc_anchors()
    return [{
        "rule_id": r.rule_id,
        "family": r.rule_id.split("/", 1)[0],
        "severity": r.default_severity.name,
        "description": r.description,
        "doc_anchor": anchors.get(r.rule_id),
    } for r in default_rules()]


#: the (micro, stages, vstages) matrix the --schedules gate proves — the
#: 8-stage row is the shape of an 8-device mesh
SCHEDULE_MATRIX = [(4, 2, 2), (8, 4, 2), (16, 8, 2)]


def run_schedules(as_json: bool, fail_on: str) -> int:
    """Prove the shipped schedule generators (1F1B / interleaved /
    zero-bubble) over :data:`SCHEDULE_MATRIX` through the ``pipe/*`` rules
    and report static bubble %% per schedule. Pure host math; the CI
    pipeline gate runs this."""
    from . import analyze_schedule
    from .schedule import schedule_report
    from ..runtime.pipe.mpmd import (generate_1f1b_ir,
                                     generate_interleaved_ir,
                                     generate_zero_bubble_ir)

    had_error = False
    out = []
    for m, s, v in SCHEDULE_MATRIX:
        irs = [generate_1f1b_ir(m, s), generate_interleaved_ir(m, s, v),
               generate_zero_bubble_ir(m, s)]
        report = analyze_schedule(irs)
        had_error |= bool(report.errors())
        entry = {"num_micro": m, "num_stages": s,
                 "n_errors": len(report.errors()),
                 "schedules": [schedule_report(ir) for ir in irs]}
        out.append(entry)
        if not as_json:
            print(f"== m={m} s={s}: {len(report.errors())} error(s)")
            for rep in entry["schedules"]:
                bubble = rep["bubble"]
                frac = (f"{bubble['bubble_frac']:.4f}"
                        if bubble is not None else "n/a")
                print(f"  {rep['schedule']:<28} proof="
                      f"{'ok' if rep['ok'] else 'REJECTED'} "
                      f"bubble={frac} "
                      f"peak_buffers={rep['peak_activation_buffers']}")
            for f in report.findings:
                print(f.render())
    if as_json:
        print(json.dumps(out, indent=2))
    return 2 if (had_error and fail_on == "error") else 0


def _row_to_ds_config(row: Dict[str, Any]) -> Dict[str, Any]:
    """target row -> DeepSpeed config dict."""
    zero_cfg: Dict[str, Any] = {"stage": row.get("stage", 0)}
    if row.get("quantized_weights"):
        zero_cfg["zero_quantized_weights"] = True
    if row.get("quantized_gradients"):
        zero_cfg["zero_quantized_gradients"] = True
    if row.get("quantize_bits"):
        zero_cfg["zero_quantize_bits"] = int(row["quantize_bits"])
    if row.get("offload") == "param_stream":
        zero_cfg["offload_param"] = {
            "device": "cpu", "buffer_count": row.get("keep_layers", 2)}
    elif row.get("offload") == "optimizer":
        zero_cfg["offload_optimizer"] = {"device": "cpu"}
    return {
        "train_micro_batch_size_per_gpu": row["micro_bs"],
        "gradient_accumulation_steps": int(row.get("gas", 1)),
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": row.get("precision", "bf16") != "fp32"},
        "zero_optimization": zero_cfg,
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }


def _build_model(row: Dict[str, Any]):
    from ..models import build_gpt
    from ..models import gpt as gpt_mod

    mcfg = gpt_mod.PRESETS[row["model"]]
    if row.get("remat", True):
        mcfg = dataclasses.replace(
            mcfg, remat=True,
            remat_policy=row.get("remat_policy", "nothing_saveable"))
    if row.get("loss_chunk"):
        mcfg = dataclasses.replace(mcfg, loss_chunk=int(row["loss_chunk"]))
    return build_gpt(mcfg)


def analyze_row(row: Dict[str, Any], compile: bool = False,
                seq: Optional[int] = None):
    """Analyze one row of :data:`TARGETS`. Returns a Report."""
    from . import analyze_engine
    from ..models import gpt as gpt_mod

    mcfg = gpt_mod.PRESETS[row["model"]]
    if mcfg.num_params() > ABSTRACT_PARAM_FLOOR or row.get("offload"):
        return _analyze_row_abstract(row, compile=compile, seq=seq)

    import deepspeed_tpu

    model, _ = _build_model(row)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=_row_to_ds_config(row))
    return analyze_engine(engine, compile=compile,
                          seq=seq or row.get("seq"))


def _analyze_row_abstract(row: Dict[str, Any], compile: bool = False,
                          seq: Optional[int] = None):
    """Big-model path: the engine-shaped AOT step over abstract state —
    program rules only, nothing materialized (``runtime/aot.py`` pattern)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from . import Analyzer, AnalysisContext, capture
    from ..runtime.aot import fused_train_step
    from ..runtime.config import DeepSpeedConfig
    from ..runtime.topology import MeshTopology, mesh_context
    from ..runtime.zero.gather import gather_window
    from ..runtime.zero.policy import ZeroShardingPolicy
    from ..ops.optimizers import get_optimizer

    model, mcfg = _build_model(row)
    ds_config = DeepSpeedConfig.load(_row_to_ds_config(row),
                                     world_size=jax.device_count())
    topo = MeshTopology.create(dp=-1)
    policy = ZeroShardingPolicy(topo, ds_config.zero_optimization)
    tmap = jax.tree_util.tree_map
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = get_optimizer("AdamW", {"lr": 3e-4, "weight_decay": 0.1})
    opt_shapes = jax.eval_shape(opt.init, shapes)
    step = fused_train_step(model, opt, gas=int(row.get("gas", 1)))

    base_specs = model.specs(shapes)
    sh = lambda spec: NamedSharding(topo.mesh, spec)  # noqa: E731
    pspec = tmap(lambda s, b: policy.param_spec(s.shape, b), shapes, base_specs)
    ospec = tmap(lambda s, b: policy.opt_spec(s.shape, b), shapes, base_specs)

    def abstract(tree, spec_tree, dtype=None):
        return tmap(lambda s, p: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=sh(p)), tree, spec_tree)

    opt_spec_tree = opt.state_spec(tmap(lambda p: sh(p), ospec), sh(P()))
    a_opt = tmap(lambda s, shd: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=shd), opt_shapes, opt_spec_tree)
    seq = int(seq or row.get("seq", 512))
    bshape = (row["micro_bs"] * topo.data_parallel_size, seq)
    gas = int(row.get("gas", 1))
    bspec = topo.batch_spec(1)
    if gas > 1:
        bshape = (gas,) + bshape
        bspec = P(None, *tuple(bspec))
    a_batch = {"input_ids": jax.ShapeDtypeStruct(
        bshape, jnp.int32, sharding=NamedSharding(topo.mesh, bspec))}
    a_rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compute = jnp.bfloat16 if ds_config.bf16.enabled else jnp.float32

    with mesh_context(topo.mesh), gather_window(ds_config.zero_optimization):
        prog = capture(
            jax.jit(step, donate_argnums=(0, 1, 2)),
            abstract(shapes, pspec, compute),
            abstract(shapes, ospec, jnp.float32),
            a_opt, a_batch, a_rng,
            name=f"aot:{row['name']}", compile=compile)
    ctx = AnalysisContext(config=ds_config, mesh=topo.mesh)
    return Analyzer().run([prog], ctx)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.analysis",
        description="dslint: static analysis of engine/pjit programs "
                    "(sharding, precision, host-sync, collective-order, "
                    "config rules)")
    parser.add_argument(
        "target", nargs="?", default=DEFAULT_BENCH,
        help=f"train-config name (default: {DEFAULT_BENCH})")
    parser.add_argument("--list", action="store_true",
                        help="list analyzable train configs (and, with "
                             "--json, the full rule registry) and exit")
    parser.add_argument("--schedules", action="store_true",
                        help="prove the shipped pipeline-schedule "
                             "generators (1F1B/interleaved/zero-bubble) "
                             "and report static bubble %% (pipe/* rules)")
    parser.add_argument("--all", action="store_true",
                        help="sweep every train config")
    parser.add_argument("--compile", action="store_true",
                        help="also run XLA to get post-GSPMD HLO (enables "
                             "the wire-traffic rules; slower)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit the report as JSON")
    parser.add_argument("--seq", type=int, default=None,
                        help="override the analyzed sequence length")
    parser.add_argument("--fail-on", choices=("error", "never"),
                        default="error",
                        help="exit 2 on ERROR findings (default) or never")
    args = parser.parse_args(argv)

    if args.schedules:
        return run_schedules(args.as_json, args.fail_on)

    rows = TARGETS
    by_name = {r["name"]: r for r in rows}
    if args.list:
        if args.as_json:
            print(json.dumps({
                "rules": rule_registry(),
                "configs": [{"name": r["name"], "model": r["model"],
                             "stage": r.get("stage", 0),
                             "micro_bs": r["micro_bs"]} for r in rows],
            }, indent=2))
            return 0
        for r in rows:
            print(f"{r['name']:<32} model={r['model']} "
                  f"stage={r.get('stage', 0)} micro_bs={r['micro_bs']}")
        print()
        for r in rule_registry():
            print(f"{r['rule_id']:<36} [{r['severity']:<7}] "
                  f"{r['description']}")
        return 0

    targets = rows if args.all else [by_name.get(args.target)]
    if targets == [None]:
        print(f"unknown train config {args.target!r}; --list shows options",
              file=sys.stderr)
        return 1

    had_error = False
    reports = []
    for row in targets:
        report = analyze_row(row, compile=args.compile, seq=args.seq)
        had_error |= bool(report.errors())
        if args.as_json:
            reports.append({"config": row["name"], **report.to_dict()})
        else:
            print(f"== {row['name']}")
            print(report.render())
    if args.as_json:
        print(json.dumps(reports if args.all else reports[0], indent=2))
    return 2 if (had_error and args.fail_on == "error") else 0


if __name__ == "__main__":
    sys.exit(main())
