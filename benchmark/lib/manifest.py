"""Find a cell, a configuration, a traffic mix or a metric by its name.

Everything that belongs to one of them is a data file named after it; a reader
kind, a generator kind, a model family or a reference is a module named after
it. Nothing here lists names: a later PR brings a cell, a metric or an
architecture by adding files and entries in ``BENCHMARK.json``.

Which metrics a cell reports is said once. For a listed cell it is
``BENCHMARK.json``: an end-to-end metric is reported where its ``workloads``
name the cell (everywhere without the key), a per-layer metric where its
``workloads`` name the cell or, without the key, wherever the metric it
``moves`` is reported. That is the rule the driver holds a run to, so a run
reports what the driver expects by construction, and a PR adds a metric to a
cell that exists with a metric file and an entry. Only a rehearsal cell, which
``BENCHMARK.json`` does not list, carries its own two lists in its file.
"""

from __future__ import annotations

import functools
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmark/
CHECKOUT = os.path.dirname(ROOT)


class ManifestError(ValueError):
    pass


def _load(kind: str, name: str) -> dict:
    path = os.path.join(ROOT, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise ManifestError(f"no {kind} file for {name!r} ({path})")
    with open(path) as f:
        data = json.load(f)
    data.setdefault("name", name)
    return data


@functools.lru_cache(maxsize=1)
def listed() -> dict:
    """``BENCHMARK.json`` of this checkout."""
    path = os.path.join(CHECKOUT, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise ManifestError(f"no {path}")
    with open(path) as f:
        return json.load(f)


def reported(bench: dict, cell: str) -> tuple:
    """(end-to-end names, per-layer names) ``bench`` has ``cell`` report, in
    its order."""
    end_to_end = [m["name"] for m in bench["end_to_end"]
                  if cell in m.get("workloads", [cell])]
    per_layer = [m["name"] for m in bench["per_layer"]
                 if (cell in m["workloads"] if "workloads" in m
                     else m["moves"] in end_to_end)]
    return end_to_end, per_layer


def load_cell(name: str) -> dict:
    """workloads/<cell>.json joined with its configuration and traffic files
    and the metrics it reports."""
    cell = _load("workloads", name)
    cell["config_file"] = _load("configs", cell["config"])
    cell["traffic_file"] = _load("traffic", cell["traffic"])
    if cell.get("rehearsal"):
        return cell
    own = {"end_to_end", "per_layer"} & set(cell)
    if own:
        raise ManifestError(
            f"workloads/{name}.json lists {sorted(own)}: a listed cell's "
            "metrics are BENCHMARK.json's, only a rehearsal cell lists its own")
    bench = listed()
    if name not in {w["name"] for w in bench["workloads"]}:
        raise ManifestError(
            f"cell {name!r} is not under workloads in BENCHMARK.json and its "
            "file does not say \"rehearsal\": true")
    cell["end_to_end"], cell["per_layer"] = reported(bench, name)
    return cell


def rehearsal_cells() -> list:
    """The cell files ``BENCHMARK.json`` does not list, by name: each is a
    rehearsal cell, and the tests run every one on the CPU."""
    listed_cells = {w["name"] for w in listed()["workloads"]}
    return sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "workloads"))
                  if f.endswith(".json") and f[:-5] not in listed_cells)


def load_metric(name: str) -> dict:
    return _load("metrics", name)


def plugin(kind: str, name: str):
    """<kind>/<name>.py (a reader, a generator, a mode, a family, a
    reference), imported by file name."""
    if not name.replace("_", "").isalnum():
        raise ManifestError(f"bad {kind} name {name!r}")
    path = os.path.join(ROOT, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise ManifestError(f"no module {kind}/{name}.py ({path})")
    return importlib.import_module(f"benchmark.{kind}.{name}")


def family_of(config: dict):
    """The adapter to the program's model code that ``configs/<config>.json``
    names as its ``family`` (``families/<family>.py``; absent: ``gpt``)."""
    return plugin("families", config.get("family", "gpt"))


def reference_of(config: dict):
    """The plain reference that ``configs/<config>.json`` names as its
    ``reference`` (``reference/<reference>.py``; absent: ``gpt_ref``)."""
    return plugin("reference", config.get("reference", "gpt_ref"))
