"""The metrics read from the program's own names. On the two rehearsal cells
that list them: a traced run on the CPU has no device plane, so the readers of
device time find nothing and say so by their absence, and the readers of the
program's spans and counts report. On the pieces recorded on the chip
(``data/program_*.json``): every such metric that ``BENCHMARK.json`` lists is
read by its own file's reader and parameters, so a later PR's metric is
exercised here by its file and its entry alone."""

import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import manifest
from benchmark.lib import program_trace as P
from benchmark.lib.context import Context
from benchmark.lib.manifest import CHECKOUT
from benchmark.lib.window import Window


def run(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3000000019", "--seconds", "1", "--trace", "1"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)


FOUND = "rehearsal: readers that found something to read: "


def found(stdout):
    (line,) = [ln for ln in stdout.splitlines() if FOUND in ln]
    return set(line.split(FOUND, 1)[1].split(", "))


@pytest.mark.parametrize("cell,spans_read,device_only", [
    ("tiny-serve.tiny-closed",
     {"decode_enqueue_ms", "sched_host_ms",
      "prefill_sample_wait_ms.backlog", "prefill_useful_tok_pct"},
     {"paged_decode_kernel_ms", "paged_decode_roofline_pct",
      "decode_prog_ms", "prefill_chunk_ms.backlog", "admit_batch_ms"}),
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


PROGRAM_READERS = ("prog_",)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# read on the pieces when this test was written; more may, none of these fewer
READ_ON_THE_PIECES = {
    "paged_decode_kernel_ms", "paged_decode_roofline_pct", "decode_prog_ms",
    "decode_enqueue_ms", "sched_host_ms", "decode_prog_ms.backlog",
    "prefill_chunk_ms.backlog", "prefill_sample_wait_ms.backlog",
    "flash_fwd_ms", "flash_bwd_ms", "flash_fwd_roofline_pct",
    "flash_bwd_roofline_pct", "train_fwd_ms", "train_recompute_ms",
    "train_bwd_ms", "train_opt_ms", "train_host_gap_ms"}


def piece(mode, folder):
    """75 ms of ``batch-decode`` or 50 ms of the gpt2 train cell's backward
    pass, with the scopes kept beside the trace as a run keeps them."""
    with open(os.path.join(DATA, f"program_{mode}_1chip.json")) as f:
        data = json.load(f)
    kept = folder / P.SCOPES_DIR
    kept.mkdir(parents=True)
    for module, scopes in data["scopes"].items():
        (kept / f"{module}.json").write_text(json.dumps(scopes))
    return P.from_plain(data, str(folder))


def test_program_metrics_read_the_recorded_pieces(tmp_path, monkeypatch):
    """Each metric on the program's names that ``BENCHMARK.json`` lists, in
    each of its cells: a file whose reader exists, a ``moves`` the cell
    reports, and the reader run with the file's own parameters over the piece
    recorded in the cell's mode, which gives a finite number or nothing. No
    list of names stands between a new metric and this test: a metric file
    and an entry bring it here."""
    pieces = {mode: piece(mode, tmp_path / mode)
              for mode in ("serve", "train")}
    seen, read = {}, set()
    for w in manifest.listed()["workloads"]:
        cell = manifest.load_cell(w["name"])
        mode, traffic = cell["config_file"]["mode"], cell["traffic_file"]
        facts = {}
        if mode == "train":
            facts = {"seq_len": traffic["seq_len"],
                     "tokens_per_step": traffic["seq_len"] * cell["chips"]
                     * traffic["micro_batch_per_chip"]}
        ctx = Context(cell=cell, window=Window(0.0, 10.0, [{}], [10.0]),
                      spans=None, requests=[], facts=facts,
                      device_kind="TPU v5 lite", chips=cell["chips"],
                      setup_s=0.0)
        monkeypatch.setattr(P, "of", lambda ctx, pt=pieces[mode]: pt)
        for n in cell["per_layer"]:
            spec = manifest.load_metric(n)
            if not spec["reader"].startswith(PROGRAM_READERS):
                continue
            assert spec["source"] in ("device_trace", "program_span",
                                      "program_counter")
            assert spec["moves"] in cell["end_to_end"], (w["name"], n)
            value = manifest.plugin("readers", spec["reader"]).read(
                ctx, spec.get("params", {}))
            assert value is None or math.isfinite(value), (w["name"], n)
            if value is not None:
                read.add(n)
            seen.setdefault(n, []).append(w["name"])
    assert READ_ON_THE_PIECES <= read
    assert {"admit_batch_ms", "prefill_useful_tok_pct"} <= set(seen)
    assert sum(len(v) >= 2 for v in seen.values()) >= 9     # both train cells


def test_a_later_prs_additions_pass_the_lint(tmp_path):
    """What a PR that may edit no file of the benchmark brings, laid over a
    copy of it: a metric on the program's names for a cell that exists (a
    metric file and an entry), and a new listed cell of a configuration with
    a family and a reference of its own that reports the metrics of a cell
    that is there (a cell, a traffic and a configuration file, entries). The
    tests that read ``BENCHMARK.json`` pass on the copy; every other test of
    this directory reads no list of it."""
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(manifest.ROOT, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    old, new = "pythia-1.4b-serve.batch-decode", "pythia-1.4b-serve.rerun"
    entry = {"name": "scatter_ms", "unit": "ms", "better": "lower",
             "source": "device_trace", "layer": "serve engine",
             "moves": "out_tok_s", "workloads": [old]}
    spec = dict({k: v for k, v in entry.items() if k != "workloads"},
                reader="prog_module_ms", params={"pattern": "^jit_scatter$"})
    (tmp_path / "benchmark" / "metrics" / "scatter_ms.json").write_text(
        json.dumps(spec))
    bench["per_layer"].append(entry)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if old in m.get("workloads", []):
            m["workloads"] = m["workloads"] + [new]
    (listed,) = [w for w in bench["workloads"] if w["name"] == old]
    bench["workloads"].append(dict(listed, name=new, config="other-block",
                                   traffic="rerun"))
    shutil.copy(tmp_path / "benchmark" / "traffic" / "batch-decode.json",
                tmp_path / "benchmark" / "traffic" / "rerun.json")
    (config,) = [c for c in bench["configs"] if c["name"] == listed["config"]]
    with open(tmp_path / config["file"]) as f:
        sizes = json.load(f)
    bench["configs"].append(dict(
        config, name="other-block", file="benchmark/configs/other-block.json"))
    (tmp_path / "benchmark" / "configs" / "other-block.json").write_text(
        json.dumps(dict(sizes, name="other-block", family="other_block",
                        reference="other_block_ref")))
    for folder, here, there in (("families", "gpt", "other_block"),
                                ("reference", "gpt_ref", "other_block_ref")):
        shutil.copy(tmp_path / "benchmark" / folder / f"{here}.py",
                    tmp_path / "benchmark" / folder / f"{there}.py")
    (tmp_path / "benchmark" / "workloads" / f"{new}.json").write_text(
        json.dumps(bench["workloads"][-1]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=CHECKOUT)
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "benchmark/tests/test_manifest.py",
         "benchmark/tests/test_program_rehearsal.py::"
         "test_program_metrics_read_the_recorded_pieces"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-1000:]
    here = subprocess.run(
        [sys.executable, "-c",
         "from benchmark.lib import manifest as m; print(m.CHECKOUT); "
         "print(*m.load_cell('pythia-1.4b-serve.rerun')['per_layer'])"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    where, names = here.stdout.splitlines()
    assert os.path.samefile(where, tmp_path)     # the copy was what was read
    assert "scatter_ms" in names.split()
