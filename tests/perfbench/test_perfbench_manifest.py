"""BENCHMARK.json against the contract's limits and the benchmark's files."""

import json
import os
import re

import pytest

from perfbench_paths import BENCH, DATA, ROOT

import trace_reduce

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}


def cells_of(metric: dict) -> list:
    return metric.get("workloads") or list(CELLS)


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert isinstance(MANIFEST["run_seconds"], int)
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
        assert not path.startswith("/") and ".." not in path
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)


def test_command_names_only_files_under_paths():
    for word in MANIFEST["command"][1:]:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"])


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert config["source"].startswith("https://")
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    with open(os.path.join(ROOT, config["file"])) as f:
        spec = json.load(f)
    assert spec["name"] == config["name"]
    assert spec["source"] == config["source"]
    assert spec["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank|hidden|intermediate|head)", key)
    for key in ("assumed", "deployment", "chips", "engine", "tiny",
                "trace_seconds", "reference"):
        assert key in spec
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])


def named_code_faults(spec: dict, bench: str) -> list:
    """What is wrong with the code a configuration's file names (the
    extension contract in the docstring of perfbench/run.py)."""
    named = {"adapters": spec.get("adapter"), "costs": spec.get("costs"),
             "references": spec["reference"].get("module")}
    faults = [f"no file perfbench/{folder}/{file}"
              for folder, file in named.items()
              if file is not None
              and not os.path.isfile(os.path.join(bench, folder, file))]
    kernels = spec.get("kernels", [])
    faults += [f"kernel name {k!r} is not a name" for k in kernels
               if not NAME.match(k)]
    taken = list(trace_reduce.KERNELS) + kernels
    faults += [f"kernel name {k!r} appears twice" for k in set(taken)
               if taken.count(k) > 1]
    if kernels and not spec.get("costs"):
        faults.append("kernels named, but no costs module to reckon them")
    return faults


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_names_only_code_that_is_there(config):
    with open(os.path.join(ROOT, config["file"])) as f:
        spec = json.load(f)
    assert named_code_faults(spec, BENCH) == []


def test_named_code_that_is_missing_or_named_twice_is_found(tmp_path):
    with open(os.path.join(DATA, "own_code", "config.json")) as f:
        spec = json.load(f)
    # Not installed here: the three files it names are not under perfbench/.
    assert named_code_faults(spec, BENCH) == [
        "no file perfbench/adapters/own_code.py",
        "no file perfbench/costs/own_code.py",
        "no file perfbench/references/own_code.py"]
    for folder in ("adapters", "costs", "references"):
        os.makedirs(tmp_path / folder)
        (tmp_path / folder / "own_code.py").write_text("")
    assert named_code_faults(spec, str(tmp_path)) == []
    twice = {**spec, "kernels": ["reshape_squeeze", "flash_attention"]}
    assert named_code_faults(twice, str(tmp_path)) == [
        "kernel name 'flash_attention' appears twice"]
    alone = {k: v for k, v in twice.items() if k != "costs"}
    assert "kernels named, but no costs module to reckon them" in \
        named_code_faults(alone, str(tmp_path))


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    config = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        assert json.load(f)["chips"] == cell["chips"]
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["name"] == cell["traffic"]
    assert len(traffic["rows_by_client"]) == traffic["clients"]
    end_to_end = [m["name"] for m in MANIFEST["end_to_end"]
                  if cell["name"] in cells_of(m)]
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    assert any(cell["name"] in cells_of(m) for m in MANIFEST["per_layer"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader(metric):
    end_to_end = metric in MANIFEST["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if end_to_end else {"layer", "moves"}
    assert set(metric) <= allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        moved = next(m for m in MANIFEST["end_to_end"]
                     if m["name"] == metric["moves"])
        # The metric it moves is reported wherever this one is.
        assert set(cells_of(metric)) <= set(cells_of(moved))
        assert 1 <= len(metric["layer"]) <= 200
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"
    assert os.path.exists(
        os.path.join(BENCH, "metrics", metric["name"] + ".py"))


def test_names_are_unique():
    for group in (METRICS, MANIFEST["workloads"], MANIFEST["configs"]):
        names = [entry["name"] for entry in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_check_budget_fits_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (MANIFEST["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200
