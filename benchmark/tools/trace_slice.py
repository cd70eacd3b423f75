#!/usr/bin/env python3
"""Cut a short piece out of a profiler trace into a small JSON file of plain
events (short operation names, seconds), the form ``lib/trace.reduce_events``
takes. The recorded traces under ``benchmark/tests/data`` were made with it.

    python3 benchmark/tools/trace_slice.py <trace dir> <out.json> <start_s> <length_s> [devices]

``start_s`` counts from the start of the traced window.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from benchmark.lib import trace as T

    trace_dir, out, start, length = (argv[0], argv[1], float(argv[2]),
                                     float(argv[3]))
    n_dev = int(argv[4]) if len(argv) > 4 else 1
    spans = ("sched.step", "prefill", "decode", "train_batch")
    loaded = T.load_xplane(T.find_xplane(trace_dir), spans)
    t_open = min(s for n, s, _ in loaded.host if n == T.WINDOW_ANNOTATION)
    t0, t1 = t_open + start, t_open + start + length

    def cut(events):
        return [[n, round(s - t0, 9), round(e - t0, 9)]
                for n, s, e in T.clip(events, t0, t1)]

    devs = sorted(loaded.device_ops)[:n_dev]
    data = {"window": [0.0, round(t1 - t0, 9)],
            "device_ops": {str(d): cut(loaded.device_ops[d]) for d in devs},
            "device_async": {str(d): cut(loaded.device_async.get(d, []))
                             for d in devs},
            "host": [ev for ev in cut(loaded.host)
                     if ev[0] != T.WINDOW_ANNOTATION]}
    with open(out, "w") as f:
        json.dump(data, f, separators=(",", ":"))
    print(f"{out}: {os.path.getsize(out)} bytes, "
          f"{sum(len(v) for v in data['device_ops'].values())} operations")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
