"""``BENCHMARK.json`` and the files it names, found by name.

    configs/<config>.json          a configuration: its sizes and source
    traffic/<traffic>.json         a traffic mix or job; ``kind`` names the
                                   driver in ``kinds/<kind>.py``
    limits/<cell>.json             the limits of a cell's correctness check
    metrics/<metric>.py            a per-layer metric's reader, ``read(run)``
    reference/<family>.py          a model family, named by a configuration's
                                   ``model["family"]``

A family module gives:

    leaf_specs(model)              ``path -> (shape, kind, std)`` of every
                                   leaf of the program's parameter tree
    loss(model, get, get_rows, tokens, labels, precision)
                                   the float32 reference loss
    logits(model, get, get_rows, tokens)
                                   float32 logits, for the CPU agreement tests
    forward_flops(model, batch, seq)
                                   model FLOPs of one forward
    CPU_CASES                      ``{id: model}``, small models that the CPU
                                   tests run against the program

The family owns its whole tree: leading dense layers, attention of several
kinds, an expert axis are its own business.  The reference asks for a leaf
through ``get(path, layer)``, in float32.  A leaf whose first axis is the
layer is asked for one layer at a time, and its z then starts at flat
offset ``layer · prod(shape[1:])``; any other leaf is asked for whole
(``layer`` None).  The embedding is the leaf ``embed``, asked for by rows,
``get_rows(ids)``.

A later cell, mix, metric or family is a new file and a new entry; no file
here changes.
"""
from __future__ import annotations

import importlib
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


FAMILY_API = ("leaf_specs", "loss", "logits", "forward_flops", "CPU_CASES")


def _family_module(name: str):
    """``reference/<name>.py`` if it gives the family API, else None."""
    if not name.isidentifier():
        return None
    full = f"{__package__}.reference.{name}"
    try:
        mod = importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        return None
    return mod if all(hasattr(mod, a) for a in FAMILY_API) else None


def families() -> list:
    """The names of the family modules under ``reference/``."""
    return [p.stem for p in sorted((HERE / "reference").glob("*.py"))
            if not p.stem.startswith("_")
            and _family_module(p.stem) is not None]


def family(name: str):
    """The module of model family ``name``: ``reference/<name>.py``."""
    mod = _family_module(name)
    if mod is None:
        raise KeyError(f"no model family {name!r}: benchmarks/chip/reference/"
                       f" has the families {families()}")
    return mod
