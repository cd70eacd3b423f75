#!/usr/bin/env python3
"""What a builder looks at before writing a ``perf_opt`` claim: for a trace
directory, the device's idle gaps by the innermost span of the program the
host was in, device time per named program, self time per named kernel, the
prompt tokens each prefill path of the serving engine was given against the
tokens it ran padded to (``real_tokens`` and ``padded_tokens`` of the
``engine.prefill.*`` spans), and per phase and scope of a program whose scopes
were kept beside the trace (a traced benchmark run keeps ``train_batch``'s;
``lib/program_trace.scopes_of``).

    python3 benchmark/tools/program_gaps.py <trace dir> [program ...]

The window is the harness's ``bench.traced_window`` annotation. Seconds are
device seconds inside it, means over the devices that ran anything.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def report(pt, programs) -> list:
    """The lines ``main`` prints."""
    from benchmark.lib import program_trace

    red = pt.reduced
    if red is None:
        return ["no operation ran on a device in the traced window"]
    lines = [f"window {red.window_s:.4f}s, busy {red.busy_s:.4f}s, idle "
             f"{100 * red.idle_share:.2f}% on {red.n_devices} device(s)",
             "idle gaps by the program's innermost span (s, share of idle):"]
    idle = sum(red.gaps_by_span.values()) or 1.0
    lines += [f"  {secs:9.5f}  {100 * secs / idle:5.1f}%  {name}"
              for name, secs in red.top_gaps(12)]
    lines.append("device time per program (s, executions, ms each):")
    for name, (secs, n) in sorted(pt.program_seconds.items(),
                                  key=lambda kv: -kv[1][0]):
        lines.append(f"  {secs:9.5f}  {n:7.1f}  {1000 * secs / n:9.3f}  {name}")
    lines.append("self time per Mosaic kernel (s, calls, ms each):")
    for name, secs in red.top_ops(len(red.op_seconds)):
        if name.endswith("_mosaic"):
            n = pt.op_counts.get(name, 0.0) or 1.0
            lines.append(f"  {secs:9.5f}  {n:7.1f}  {1000 * secs / n:9.3f}  "
                         f"{name}")
    padded = {}
    for s in pt.prefixed("engine.prefill."):
        if "padded_tokens" in s.stats:
            real, pad = padded.get(s.name, (0, 0))
            padded[s.name] = (real + s.stats["real_tokens"],
                              pad + s.stats["padded_tokens"])
    if padded:
        lines.append("prompt tokens by prefill path (real, padded, share):")
        lines += [f"  {real:9d}  {pad:9d}  {100 * real / pad:5.1f}%  {name}"
                  for name, (real, pad) in sorted(padded.items())]
    for program in programs:
        phases = program_trace.phase_seconds(pt, program)
        if phases is None:
            lines.append(f"{program}: no scopes of the module that ran "
                         "beside the trace")
            continue
        by_phase, by_scope, unnamed, inherited = phases
        busy = sum(by_phase.values()) or 1.0
        lines.append(f"{program}: self time by phase (s, share):")
        lines += [f"  {v:9.5f}  {100 * v / busy:5.1f}%  {k}"
                  for k, v in by_phase.items()]
        lines.append(f"{program}: by phase and scope:")
        lines += [f"  {v:9.5f}  {100 * v / busy:5.1f}%  {p}/{s}"
                  for (p, s), v in sorted(by_scope.items(),
                                          key=lambda kv: -kv[1])]
        lines.append(f"{program}: {inherited:.5f}s took the name of the "
                     f"operation around them; {len(unnamed)} operations have "
                     f"none, {sum(unnamed.values()):.5f}s; the largest: "
                     + ", ".join(f"{n} {s:.5f}" for n, s in sorted(
                         unnamed.items(), key=lambda kv: -kv[1])[:8]))
    return lines


def main(argv) -> int:
    from benchmark.lib import program_trace

    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    pt = program_trace.load_dir(argv[0])
    if pt.window is None:
        print("the trace has no bench.traced_window annotation",
              file=sys.stderr)
        return 1
    kept = os.path.join(argv[0], program_trace.SCOPES_DIR)
    programs = argv[1:] or sorted({        # files are <program>.<id>.json
        f.rsplit(".", 2)[0]
        for f in (os.listdir(kept) if os.path.isdir(kept) else ())
        if f.endswith(".json")})
    print("\n".join(report(pt, programs)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
