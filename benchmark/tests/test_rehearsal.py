"""Every rehearsal cell (a cell file that ``BENCHMARK.json`` does not list: a
later PR's is run here without an edit) runs end to end on the CPU, as a new
process each, and a listed cell refuses to run without a TPU.
``tiny-moe-train.tiny-steady`` is the architecture that came as files: its
``correct`` is its own reference's loss against the first step's."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import manifest
from benchmark.lib.manifest import CHECKOUT


def run(cell, trace, seconds="1"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3000000019", "--seconds", seconds, "--trace", str(trace)],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize(
    "cell,trace", [("tiny-serve.tiny-closed", 0)]
    + [(cell, 1) for cell in manifest.rehearsal_cells()])
def test_rehearsal_cell(cell, trace):
    done = run(cell, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["metrics"] == {}            # a CPU run has no device metric
    assert result["device"]["platform"] == "cpu"
    assert "setup_s" in done.stdout and "PROBLEM" not in done.stdout


def test_listed_cell_refuses_without_a_tpu():
    done = run("gpt2-medium-train.steady-1k", 0)
    assert done.returncode == 2
    assert "no TPU" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())
