#!/usr/bin/env python3
"""Print the structure of a profiler trace: planes, lines, how many events
each holds and a few of them. Look at one trace by hand before writing a
pattern against it.

    python3 benchmark/tools/xplane_dump.py <file.xplane.pb | trace dir> [n]
"""

from __future__ import annotations

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    import jax

    from benchmark.lib.trace import find_xplane

    path = argv[0] if argv[0].endswith(".pb") else find_xplane(argv[0])
    show = int(argv[1]) if len(argv) > 1 else 6
    data = jax.profiler.ProfileData.from_file(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{t0 * 1e-9:.6f}s .. {t1 * 1e-9:.6f}s")
            by_name = collections.Counter()
            for e in events:
                by_name[e.name] += e.duration_ns
            for name, ns in by_name.most_common(show):
                print(f"      {ns * 1e-9:10.6f}s  {name[:100]}")
            e = events[len(events) // 2]
            try:
                stats = dict(e.stats)
            except Exception as err:  # stats are optional; say why not
                stats = {"<no stats>": repr(err)}
            print(f"      sample stats of {e.name[:60]!r}: "
                  f"{ {k: str(v)[:60] for k, v in list(stats.items())[:8]} }")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
