"""BENCHMARK.json against the benchmark's contract and its own files."""

import importlib
import json
import os
import re

import pytest

from benchmark.harness import HERE, ROOT, applies, load_json, load_manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = load_manifest()
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51 and isinstance(MANIFEST["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in MANIFEST["paths"])
    assert not any(w.startswith("/") or ".." in w for w in MANIFEST["command"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_names_units_and_sources(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in MANIFEST["end_to_end"]:
        assert set(metric) <= allowed | {"bound"} and 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_names_are_unique_and_setup_is_everywhere():
    for group in (METRICS, MANIFEST["workloads"], MANIFEST["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    setup = next(m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_metrics(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"] and "\t" not in cell["why"]
    spec = load_json("workloads", cell["name"] + ".json")
    assert spec["config"] == cell["config"] and spec["chips"] == cell["chips"]
    assert spec["why"] == cell["why"]
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    importlib.import_module(f"benchmark.kinds.{spec['kind']}")
    config = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    end = [m["name"] for m in MANIFEST["end_to_end"] if applies(m, cell["name"])]
    assert "setup_s" in end and len(end) >= 2
    layer = [m for m in MANIFEST["per_layer"] if applies(m, cell["name"])]
    assert layer and all(m["moves"] in end for m in layer)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_file_and_a_reader(metric):
    spec = load_json("layers", metric["name"] + ".json")
    assert spec["layer"] == metric["layer"] and spec["moves"] == metric["moves"]
    assert spec["unit"] == metric["unit"]
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_file_lists_what_it_changed(config):
    spec = json.load(open(os.path.join(ROOT, config["file"])))
    assert spec["source"] == config["source"] and config["source"].startswith("https://")
    assert sorted(spec["reduced"]) == sorted(config["reduced"]) and len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    widths = re.compile(r"(_dim|_rank|hidden|intermediate|n_embd|n_inner|head)")
    assert not any(widths.search(k) for k in config["reduced"] if k != "hidden_act")
    for module in ("flops", "reference"):
        importlib.import_module(f"benchmark.{module}.{spec['system'][module]}")


def test_every_file_under_paths_is_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in MANIFEST["paths"]:
        for dirpath, dirnames, files in os.walk(os.path.join(ROOT, base)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                if not f.endswith(".pyc"):
                    assert ok.match(os.path.relpath(os.path.join(dirpath, f), ROOT))
    assert HERE.endswith("benchmark")
