"""Find a cell, a configuration, a traffic mix or a metric by its name.

Everything that belongs to one of them is a data file named after it; a reader
kind or a generator kind is a module named after it. Nothing here lists names:
a later PR brings a cell by adding files.
"""

from __future__ import annotations

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


def load_cell(name: str) -> dict:
    """workloads/<cell>.json joined with its configuration and traffic
    files."""
    cell = _load("workloads", name)
    cell["config_file"] = _load("configs", cell["config"])
    cell["traffic_file"] = _load("traffic", cell["traffic"])
    return cell


def load_metric(name: str) -> dict:
    return _load("metrics", name)


def plugin(kind: str, name: str):
    """readers/<name>.py or generators/<name>.py, imported by file name."""
    if not name.replace("_", "").isalnum():
        raise ManifestError(f"bad {kind} name {name!r}")
    if not os.path.isfile(os.path.join(ROOT, kind, f"{name}.py")):
        raise ManifestError(f"no module {kind}/{name}.py")
    return importlib.import_module(f"benchmark.{kind}.{name}")
