"""BENCHMARK.json resolves: every cell's configuration, traffic and limits,
every per-layer metric's reader, and the contract's shape rules."""
from __future__ import annotations

import json
import re

import pytest

from benchmarks.chip import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

WIDTHS = {"hidden_size", "intermediate_size", "ffn_dim", "head_dim",
          "word_embed_proj_dim", "num_experts_per_tok"}
BENCH = manifest.load()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (manifest.ROOT / p).is_dir(), p
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_resolve(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    config = manifest.config(cell["config"])
    assert config["name"] == cell["config"]
    traffic = manifest.traffic(cell["traffic"])
    assert (manifest.HERE / "kinds" / f"{traffic['kind']}.py").is_file()
    limits = manifest.limits(cell["name"])
    assert limits and all(v >= 0 for v in limits.values())
    e2e = {m["name"] for m in manifest.end_to_end(BENCH, cell["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.per_layer(BENCH, cell["name"])


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entries(cfg):
    assert NAME.match(cfg["name"])
    path = manifest.ROOT / cfg["file"]
    assert path.is_file() and str(path).startswith(str(manifest.HERE))
    data = json.loads(path.read_text())
    assert data["source"] == cfg["source"]
    assert sorted(cfg["reduced"]) == sorted(data["reduced"])
    for key in cfg["reduced"]:
        assert not key.endswith(("_dim", "_rank")) and key not in WIDTHS, \
            f"{key} names a width"
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader_and_moves(metric):
    """Each per-layer metric has a reader of its own and lists its cells,
    and the end-to-end metric it ``moves`` is reported by every one."""
    assert callable(manifest.reader(metric["name"]))
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert metric["workloads"]
    for name in metric["workloads"]:
        manifest.cell(BENCH, name)
        reported = {m["name"] for m in manifest.end_to_end(BENCH, name)}
        assert metric["moves"] in reported, (metric["name"], name)


def test_layers_name_one_thing_each():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    for layer in layers:
        assert 1 <= len(layer) <= 200 and "\n" not in layer


def test_readers_find_nothing_without_their_job():
    """A reader that finds nothing to read returns nothing."""
    for metric in BENCH["per_layer"]:
        assert manifest.reader(metric["name"])({"job": "none"}) is None
