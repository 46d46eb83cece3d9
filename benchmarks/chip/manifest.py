"""``BENCHMARK.json`` and the files it names, found by name.

    configs/<config>.json          a configuration: its sizes and source
    traffic/<traffic>.json         a traffic mix or job; ``kind`` names the
                                   driver in ``kinds/<kind>.py``
    limits/<cell>.json             the limits of a cell's correctness check
    metrics/<metric>.py            a per-layer metric's reader, ``read(run)``

A later cell, mix or metric is a new file and a new entry; no file here
changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def limits(cell_name: str) -> dict:
    return json.loads((HERE / "limits" / f"{cell_name}.json").read_text())


def end_to_end(bench: dict, cell_name: str) -> list:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(bench: dict, cell_name: str) -> list:
    """The per-layer metrics this cell reports: those that list it.  Every
    per-layer metric lists its cells under ``workloads``."""
    return [m for m in bench["per_layer"] if cell_name in m["workloads"]]


def reader(metric_name: str):
    """The ``read`` function of ``metrics/<metric_name>.py``."""
    path = HERE / "metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip.metrics." + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
