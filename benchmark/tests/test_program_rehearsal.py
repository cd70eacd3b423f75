"""The rehearsal cells with the program's own spans, through
``tools/run_program_metrics.py`` (the cell files do not list these metrics
yet): a traced run on the CPU has no device plane, so the readers of device
time find nothing and say so by their absence, and the readers of the
program's spans and counts report."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import manifest
from benchmark.lib.manifest import CHECKOUT
from benchmark.tools.run_program_metrics import program_metrics


def run(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmark/tools/run_program_metrics.py",
         "--workload", cell, "--seed", "3000000019", "--seconds", "1",
         "--trace", "1"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)


FOUND = "rehearsal: readers that found something to read: "


def found(stdout):
    (line,) = [ln for ln in stdout.splitlines() if FOUND in ln]
    return set(line.split(FOUND, 1)[1].split(", "))


@pytest.mark.parametrize("cell,spans_read,device_only", [
    ("tiny-serve.tiny-closed",
     {"decode_enqueue_ms", "sched_host_ms",
      "prefill_sample_wait_ms.backlog"},
     {"paged_decode_kernel_ms", "paged_decode_roofline_pct",
      "decode_prog_ms", "prefill_chunk_ms.backlog"}),
    ("tiny-train.tiny-steady", set(),
     {"flash_fwd_ms", "flash_bwd_roofline_pct", "train_fwd_ms",
      "train_opt_ms", "train_host_gap_ms"})])
def test_rehearsal_lists_the_readers_of_program_spans(cell, spans_read,
                                                      device_only):
    done = run(cell)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["metrics"] == {}
    got = found(done.stdout)
    assert spans_read <= got
    assert not device_only & got
    assert "PROBLEM" not in done.stdout


def test_waiting_metrics_are_ready_for_their_cells():
    """What a ``benchmark`` PR appends to a cell file and to
    ``BENCHMARK.json`` has to pass ``test_manifest`` then: a metric file with
    a reader, and ``moves`` naming an end-to-end metric the cell reports."""
    folder = os.path.join(manifest.ROOT, "program_metrics")
    cells = sorted(f[:-5] for f in os.listdir(folder))
    assert len(cells) == 6
    for name in cells:
        cell = manifest.load_cell(name)
        names = program_metrics(name)
        assert names and len(names) == len(set(names))
        assert not set(names) & set(cell["per_layer"])
        for n in names:
            spec = manifest.load_metric(n)
            manifest.plugin("readers", spec["reader"])
            assert spec["source"] in ("device_trace", "program_span")
            assert spec["moves"] in cell["end_to_end"], (name, n)
    assert program_metrics("no-such-cell") == []
