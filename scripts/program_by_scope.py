#!/usr/bin/env python3
"""A traced benchmark run's programs by scope: device self time of the whole
executions of each program matching ``pattern`` inside the traced window,
milliseconds an execution, summed by the scope of ``docs/TRACING.md`` its
operations lie under (the innermost of ``SCOPES`` on an operation's path; a
Mosaic call under its kernel's name).

    python3 benchmark/run.py --workload dots3-note-serve.long-notes --seed 1 --seconds 40 --trace 1
    JAX_PLATFORMS=cpu python3 scripts/program_by_scope.py benchmark/.work/trace '^jit_prefill_chunk_1024$' [index]

With a scope after the pattern, also the twelve instructions that take most
under it, each with the end of its ``op_name``.

Run it on the machine that made the trace, in a process of its own after the
run (``chiprun -- bash -c "... && ..."``): it reads the trace directory and
the scopes the run kept beside it (``program_scopes/``), as
``benchmark/tools/program_gaps.py`` does, or a recorded piece
(``benchmark/tests/data/program_*.json``), and prints one JSON line a
program.
"""
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.lib import program_trace  # noqa: E402
from benchmark.lib import trace as T  # noqa: E402

# innermost first: an operation counts under the first of these on its path
SCOPES = ("index", "mla_expand", "kv_write", "kv_read", "mla_absorb", "mla_q",
          "mla_kv", "attn_window", "attn_full", "attn", "moe_router",
          "moe_experts", "moe_shared", "mlp", "ssm", "kda", "head_loss",
          "embed", "blocks")


def main(argv):
    if os.path.isdir(argv[0]):
        pt = program_trace.load_dir(argv[0])
    else:   # a recorded piece (benchmark/tools/program_trace_slice.py)
        with open(argv[0]) as f:
            data = json.load(f)
        kept = os.path.join(tempfile.mkdtemp(), program_trace.SCOPES_DIR)
        os.makedirs(kept)
        for module, scopes in data["scopes"].items():
            with open(os.path.join(kept, module + ".json"), "w") as f:
                json.dump(scopes, f)
        pt = program_trace.from_plain(data, os.path.dirname(kept))
    pattern = re.compile(argv[1] if len(argv) > 1 else "^jit_")
    t0, t1 = pt.window
    for name in sorted({n for mods in pt.modules.values()
                        for n, _, _ in mods if pattern.search(n)}):
        scopes = program_trace.scopes_of(pt, name[len("jit_"):])
        if scopes is None:
            print(json.dumps({"program": name, "scopes": None}))
            continue
        parent = pt.enclosing.get(name, {})
        by, runs, ops_under = {}, 0, {}
        for dev, mods in pt.modules.items():
            whole = [(s, e) for n, s, e in mods
                     if n == name and s >= t0 and e <= t1]
            runs += len(whole)
            ops = sorted(pt.instr.get(dev, ()), key=lambda ev: ev[1])
            for s, e in whole:
                inside = [ev for ev in ops if s <= ev[1] and ev[2] <= e]
                for instr, a, b in T.self_times(inside):
                    at = instr
                    while at is not None and at not in scopes:
                        at = parent.get(at)
                    parts = scopes[at].split("/") if at is not None else []
                    under = next((p for p in SCOPES if p in parts), "other")
                    if "pallas_call" in parts:  # a kernel, by its name
                        under += ":" + parts[parts.index("pallas_call") - 1]
                    by[under] = by.get(under, 0.0) + (b - a)
                    if len(argv) > 2 and argv[2] in parts:
                        ops_under[instr] = ops_under.get(instr, 0.0) + (b - a)
        if runs:
            print(json.dumps({
                "program": name, "executions": runs,
                "ms": round(1e3 * sum(by.values()) / runs, 3),
                "by_scope_ms": {k: round(1e3 * v / runs, 3) for k, v in
                                sorted(by.items(), key=lambda kv: -kv[1])}}))
            for instr, secs in sorted(ops_under.items(),
                                      key=lambda kv: -kv[1])[:12]:
                print(json.dumps({"instruction": instr,
                                  "ms": round(1e3 * secs / runs, 3),
                                  "op_name": scopes.get(instr, "")[-120:]}))


if __name__ == "__main__":
    main(sys.argv[1:])
