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
    """The cells ``metric`` is reported in. Without ``workloads``: every cell
    for an end-to-end metric, every cell that reports the metric it ``moves``
    for a per-layer one (the contract's rule, and ``manifest.reported``'s)."""
    if "workloads" in metric:
        return metric["workloads"]
    if "moves" in metric:
        (moved,) = [m for m in bench["end_to_end"]
                    if m["name"] == metric["moves"]]
        return cells_of(bench, moved)
    return [w["name"] for w in bench["workloads"]]


def test_every_cell_matches_its_file(bench):
    """A listed cell's file says where it runs and why, as ``BENCHMARK.json``
    does, and not what it reports: that is ``BENCHMARK.json``'s alone, and
    ``load_cell`` hands the harness exactly the names the driver will look
    for in the cell's lines."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    for w in bench["workloads"]:
        with open(os.path.join(manifest.ROOT, "workloads",
                               f"{w['name']}.json")) as f:
            own = json.load(f)
        said = {"name", "config", "traffic", "chips", "why"}
        assert said <= set(own) <= said | {"trace_start_s",
                                           "trace_seconds"}, w["name"]
        cell = manifest.load_cell(w["name"])
        for key in sorted(said):
            assert cell[key] == w[key], (w["name"], key)
        assert cell["config_file"]["chips"] == w["chips"]
        reported = [n for n, m in e2e.items() if w["name"] in cells_of(bench, m)]
        assert cell["end_to_end"] == reported
        assert "setup_s" in reported and len(reported) >= 2
        traced = [n for n, m in layer.items()
                  if w["name"] in cells_of(bench, m)]
        assert cell["per_layer"] == traced and traced
        assert len(set(reported)) == len(reported)
        assert len(set(traced)) == len(traced)


def test_a_listed_cell_may_not_list_its_own_metrics(bench, monkeypatch):
    name = bench["workloads"][0]["name"]
    load = manifest._load

    def with_a_list(kind, n):
        data = load(kind, n)
        if kind == "workloads":
            data["per_layer"] = ["train_step_ms"]
        return data

    monkeypatch.setattr(manifest, "_load", with_a_list)
    with pytest.raises(manifest.ManifestError, match="BENCHMARK.json's"):
        manifest.load_cell(name)


def test_an_entry_adds_a_metric_to_a_cell_that_exists(bench, monkeypatch):
    """What a later PR does: a metric file (here one that is there) and an
    entry under ``per_layer``. With ``workloads`` the cells named report it;
    without, every cell that reports what it ``moves``."""
    serve = [w["name"] for w in bench["workloads"]
             if w["config"] == "pythia-1.4b-serve"]
    assert len(serve) >= 2
    entry = dict(next(m for m in bench["per_layer"]
                      if m["name"] == "device_idle_pct.train"))
    more = dict(bench, per_layer=bench["per_layer"] + [
        dict(entry, name="named.cells", workloads=[serve[1]]),
        {k: v for k, v in dict(entry, name="by.moves",
                               moves="train_tok_s_chip").items()
         if k != "workloads"}])
    monkeypatch.setattr(manifest, "listed", lambda: more)
    trains = set(cells_of(bench, next(
        m for m in bench["end_to_end"] if m["name"] == "train_tok_s_chip")))
    for w in bench["workloads"]:
        got = manifest.load_cell(w["name"])["per_layer"]
        assert ("named.cells" in got) == (w["name"] == serve[1])
        assert ("by.moves" in got) == (w["name"] in trains)
        assert [n for n in got if n not in ("named.cells", "by.moves")] == \
            manifest.reported(bench, w["name"])[1]


def test_an_unlisted_cell_must_say_rehearsal(monkeypatch, bench):
    fewer = dict(bench, workloads=bench["workloads"][1:])
    monkeypatch.setattr(manifest, "listed", lambda: fewer)
    with pytest.raises(manifest.ManifestError, match="rehearsal"):
        manifest.load_cell(bench["workloads"][0]["name"])


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
    """Every cell file that ``BENCHMARK.json`` does not list is a rehearsal
    cell: it says so, runs on the CPU, and lists its own metrics, each of
    which has a file and a reader. A new architecture brings its own."""
    listed = {w["name"] for w in bench["workloads"]}
    found = {f[:-5] for f in os.listdir(os.path.join(manifest.ROOT,
                                                     "workloads"))}
    assert listed <= found
    extra = manifest.rehearsal_cells()
    assert set(extra) == found - listed
    assert {"tiny-serve.tiny-closed", "tiny-train.tiny-steady"} <= set(extra)
    for name in extra:
        cell = manifest.load_cell(name)
        assert cell["rehearsal"] is True
        assert cell["config_file"]["mode"] in ("train", "serve")
        assert "setup_s" in cell["end_to_end"] and cell["per_layer"]
        for n in cell["end_to_end"] + cell["per_layer"]:
            manifest.plugin("readers", manifest.load_metric(n)["reader"])


@pytest.mark.parametrize("kind,loader,default", [
    ("family", manifest.family_of, "benchmark.families.gpt"),
    ("reference", manifest.reference_of, "benchmark.reference.gpt_ref")])
def test_a_configuration_names_its_family_and_reference(bench, kind, loader,
                                                        default):
    """Absent: ``gpt`` and ``gpt_ref``. Every listed configuration's loads,
    the module its file names or that one. A name with no module is an error
    that names the file looked for."""
    folder = "families" if kind == "family" else "reference"
    for c in bench["configs"]:
        with open(os.path.join(manifest.CHECKOUT, c["file"])) as f:
            data = json.load(f)
        assert loader(data).__name__ == (
            f"benchmark.{folder}.{data[kind]}" if kind in data else default)
    assert loader({}).__name__ == default
    with pytest.raises(manifest.ManifestError) as e:
        loader({kind: "no_such_block"})
    assert os.path.join(manifest.ROOT, folder, "no_such_block.py") in str(e.value)
    with pytest.raises(manifest.ManifestError, match="bad"):
        loader({kind: "../lib/device"})
