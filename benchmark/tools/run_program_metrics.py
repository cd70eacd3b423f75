#!/usr/bin/env python3
"""One run of one cell that also reports the per-layer metrics read from the
program's own names (``docs/TRACING.md``): ``benchmark/run.py`` with the names
of ``program_metrics/<cell>.json`` added to the cell's ``per_layer`` list.

    python3 benchmark/tools/run_program_metrics.py --workload <cell> --seed <n> --seconds <s> --trace 1

The harness takes a cell's metrics from ``workloads/<cell>.json`` alone, and
PR 24 could edit no file the benchmark had: until a ``benchmark`` PR appends
those names there and their entries to ``BENCHMARK.json`` (unit, layer and
``moves`` are in each ``metrics/<name>.json``), the driver's runs do not
report them and this tool does. Everything else is ``run.py``'s: same
arguments, same last line, same exit codes.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402
from benchmark.lib import manifest  # noqa: E402


def program_metrics(cell: str) -> list:
    """The names listed for ``cell``, none where it has no file."""
    path = os.path.join(manifest.ROOT, "program_metrics", f"{cell}.json")
    if not os.path.isfile(path):
        return []
    with open(path) as f:
        return json.load(f)["per_layer"]


def main(argv=None) -> int:
    load_cell = manifest.load_cell

    def with_program_metrics(name: str) -> dict:
        cell = load_cell(name)
        cell["per_layer"] = cell["per_layer"] + program_metrics(name)
        return cell

    manifest.load_cell = with_program_metrics
    try:
        return run.main(argv)
    finally:
        manifest.load_cell = load_cell


if __name__ == "__main__":
    sys.exit(main())
