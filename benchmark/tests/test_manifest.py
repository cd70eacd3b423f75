"""A lint of ``BENCHMARK.json`` against its contract and against the data
files the harness actually reads: the two may not drift apart."""

import json
import os
import re

import pytest

from benchmark.lib import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj)\w*size|_dim$|"
                    r"_rank$|head_size|head_dim|expansion|experts_per_tok|"
                    r"^d_model$|^d_ff$|^n_embd$")
MAX_RUN_SECONDS = 51


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(manifest.CHECKOUT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def line(s, limit=200):
    return isinstance(s, str) and 1 <= len(s) <= limit and "\n" not in s \
        and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["command"]) <= 32 and all(map(line, bench["command"]))
    assert bench["paths"] == ["benchmark"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= MAX_RUN_SECONDS
    for word in bench["command"][1:]:
        assert not word.startswith("/") and ".." not in word
    assert bench["command"][1].startswith("benchmark/")


def test_names_units_and_keys(bench):
    for group, keys, optional in (
            ("configs", {"name", "source", "file", "reduced", "why"}, set()),
            ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
            ("end_to_end", {"name", "unit", "better", "bound", "source"},
             {"workloads"}),
            ("per_layer", {"name", "unit", "better", "source", "layer",
                           "moves"}, {"workloads"})):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        for e in bench[group]:
            assert keys <= set(e) <= keys | optional, (group, e["name"])
            assert NAME.match(e["name"]), e["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["per_layer"]:
        assert line(m["layer"]) and "moves" in m
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in bench["workloads"]:
        assert line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_counts(bench):
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_configs(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["name"] in used, f"{c['name']} has no cell"
        assert line(c["source"]) and line(c["why"])
        assert PATH.match(c["file"]) and c["file"].startswith("benchmark/")
        with open(os.path.join(manifest.CHECKOUT, c["file"])) as f:
            data = json.load(f)
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTHS.search(key), key
            assert key in data, f"{key} is not a key of {c['file']}"
        assert data["mode"] in ("train", "serve")
    assert used == {c["name"] for c in bench["configs"]}


def cells_of(bench, metric):
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def test_every_cell_matches_its_file(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    for w in bench["workloads"]:
        cell = manifest.load_cell(w["name"])
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        assert cell["config_file"]["chips"] == w["chips"]
        assert not cell.get("rehearsal")
        reported = {n for n, m in e2e.items() if w["name"] in cells_of(bench, m)}
        assert set(cell["end_to_end"]) == reported
        assert "setup_s" in reported and len(reported) >= 2
        traced = {n for n, m in layer.items()
                  if w["name"] in cells_of(bench, m)}
        assert set(cell["per_layer"]) == traced and traced


def test_every_metric_matches_its_file(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    known = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        spec = manifest.load_metric(m["name"])
        for key in ("unit", "better", "source"):
            assert spec[key] == m[key], (m["name"], key)
        manifest.plugin("readers", spec["reader"])
        assert set(cells_of(bench, m)) <= known
    for m in bench["per_layer"]:
        spec = manifest.load_metric(m["name"])
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        moved = set(cells_of(bench, e2e[m["moves"]]))
        assert set(cells_of(bench, m)) <= moved, (
            f"{m['name']} is reported where {m['moves']} is not")


def test_files_are_named_from_the_characters_of_a_name():
    for folder, _, files in os.walk(manifest.ROOT):
        if any(part.startswith(".") or part == "__pycache__"
               for part in folder.split(os.sep)):
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), manifest.CHECKOUT)
            assert PATH.match(rel), rel


def test_rehearsal_cells_are_data_only(bench):
    listed = {w["name"] for w in bench["workloads"]}
    found = {f[:-5] for f in os.listdir(os.path.join(manifest.ROOT,
                                                     "workloads"))}
    extra = found - listed
    assert extra == {"tiny-serve.tiny-closed", "tiny-train.tiny-steady"}
    for name in extra:
        assert manifest.load_cell(name)["rehearsal"] is True
