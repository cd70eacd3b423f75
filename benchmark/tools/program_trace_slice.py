#!/usr/bin/env python3
"""Cut a short piece out of a profiler trace into a small JSON file that keeps
what ``lib/program_trace`` reads: the program's spans with their stats, the
"XLA Modules" line with each program's module id, the operations by
instruction name and kind, and of each module's kept scopes (``<trace dir>/program_scopes``) the instructions the
piece holds. ``tools/trace_slice.py`` keeps none of these. The recorded pieces
``benchmark/tests/data/program_*.json`` were made with it;
``program_trace.from_plain`` loads them.

    python3 benchmark/tools/program_trace_slice.py <trace dir> <out.json> <start_s> <length_s> [devices]

``start_s`` counts from the start of the traced window. Events and spans that
reach over an edge are cut at it.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from benchmark.lib import program_trace
    from benchmark.lib import trace as T

    trace_dir, out, start, length = (argv[0], argv[1], float(argv[2]),
                                     float(argv[3]))
    n_dev = int(argv[4]) if len(argv) > 4 else 1
    pt = program_trace.load_dir(trace_dir)
    t0 = pt.window[0] + start
    t1 = t0 + length

    def cut(events):
        return [[n, round(s - t0, 9), round(e - t0, 9)]
                for n, s, e in T.clip(events, t0, t1)]

    devs = sorted(pt.ops)[:n_dev]
    ops = {}
    for d in devs:      # short name = instruction + "_" + kind: keep both once
        rows = []
        for (short, s, e), (instr, _, _) in zip(pt.ops[d], pt.instr[d]):
            if e > t0 and s < t1:
                kind = (short[len(instr) + 1:] if short.startswith(instr + "_")
                        else None)
                rows.append([instr, kind if kind is not None else short,
                             kind is not None,
                             round(max(s, t0) - t0, 9), round(min(e, t1) - t0, 9)])
        ops[str(d)] = rows
    held = {row[0] for rows in ops.values() for row in rows}
    modules = {str(d): cut(pt.modules.get(d, [])) for d in devs}
    ran = {name for rows in modules.values() for name, _, _ in rows}
    scopes = {}         # file name less ".json": <program>.<module id>
    kept = os.path.join(trace_dir, program_trace.SCOPES_DIR)
    for f in sorted(os.listdir(kept) if os.path.isdir(kept) else ()):
        with open(os.path.join(kept, f)) as fh:
            scopes[f[:-5]] = {k: v for k, v in json.load(fh).items()
                              if k in held}
    data = {
        "window": [0.0, round(t1 - t0, 9)],
        "spans": [[s.name, round(max(s.t0, t0) - t0, 9),
                   round(min(s.t1, t1) - t0, 9), s.stats]
                  for s in pt.spans if s.t1 > t0 and s.t0 < t1],
        "modules": modules,
        "module_ids": {name: ids for name, ids in pt.module_ids.items()
                       if name in ran},
        "ops": ops,
        "device_async": {str(d): cut(pt.device_async.get(d, []))
                         for d in devs},
        "scopes": scopes,
    }
    with open(out, "w") as f:
        json.dump(data, f, separators=(",", ":"))
    print(f"{out}: {os.path.getsize(out)} bytes, "
          f"{sum(len(v) for v in ops.values())} operations, "
          f"{len(data['spans'])} spans")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
