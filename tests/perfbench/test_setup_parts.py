"""`setup_s` split by the program's own start-up record (ISSUE 62): six
readers over engine_stats `startup` and the run's `t_start` / `t_open`.
The three parts add up to `setup_s` on one clock; every reader returns
None — never raises — over a program that keeps no record (the parent);
and one rehearsed cell shows both processes' stamps really are one
clock."""

import gzip
import json
import os

import pytest

from perfbench_paths import ROOT

import run
from test_perfbench_extension import checkout_with_the_program
from test_perfbench_rehearsal import run_cell

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
PARTS = ("setup_before_engine_s", "setup_engine_init_s",
         "setup_after_engine_s")
INSIDE = ("setup_warmup_s", "setup_trace_lower_s", "setup_fresh_compiles")
# A warm run of `mistral-7b.decode-saturated` as its child reported it and
# its parent stamped it, cut to what the readers read.
META = {"t_start": 4120.250, "t_open": 4147.625, "setup_s": 27.375}
STARTUP = {
    "t_begin": 4127.500, "t_end": 4143.000,
    "stages": {"init": 15.4375, "place_params": 1.25, "pools": 0.125,
               "warmup": 13.5, "release_heap": 0.25},
    "compile": {"executables": 58, "cache_hits": 58, "fresh_compiles": 0,
                "trace_s": 3.5, "lower_s": 4.25, "backend_s": 2.75,
                "cache_retrieval_s": 2.5},
    "warmup_compile": {"executables": 41, "cache_hits": 41,
                       "fresh_compiles": 0, "trace_s": 3.25, "lower_s": 4.0,
                       "backend_s": 2.5, "cache_retrieval_s": 2.25},
    "executables": [{"step": "decode", "steps": 8, "greedy": True,
                     "seconds": 1.5, "backend_s": 0.25, "cache_hit": True}],
}
PARENTS_STATS = {"platform": "tpu", "device_count": 1,
                 "compiles": {"executables": 58, "cache_hits": 58,
                              "fresh_compiles": 0},
                 "warmup_compiles": {"executables": 41, "cache_hits": 41,
                                     "fresh_compiles": 0}}


def context(stats_ready: dict):
    return run.Context(stats_ready=stats_ready, setup_s=META["setup_s"],
                       samples={"meta": dict(META)})


def test_the_three_parts_add_up_to_setup_s():
    ctx = context({"startup": STARTUP})
    parts = [run.read_metric(name, ctx) for name in PARTS]
    assert parts == [7.25, 15.5, 4.625]
    assert sum(parts) == run.read_metric("setup_s", ctx) == META["setup_s"]


def test_what_lies_inside_the_constructor():
    ctx = context({"startup": STARTUP})
    assert run.read_metric("setup_warmup_s", ctx) == 13.5
    assert run.read_metric("setup_trace_lower_s", ctx) == 7.75
    assert run.read_metric("setup_fresh_compiles", ctx) == 0.0
    assert run.read_metric("setup_warmup_s", ctx) \
        <= run.read_metric("setup_engine_init_s", ctx)
    cold = {**STARTUP, "compile": {**STARTUP["compile"], "cache_hits": 0.0,
                                   "fresh_compiles": 58.0}}
    assert run.read_metric("setup_fresh_compiles",
                           context({"startup": cold})) == 58.0
    unwarmed = {**STARTUP, "stages": {"init": 2.0, "place_params": 1.25,
                                      "pools": 0.125}}
    assert run.read_metric("setup_warmup_s",
                           context({"startup": unwarmed})) is None


@pytest.mark.parametrize("name", PARTS + INSIDE)
def test_reader_returns_none_over_the_parents_stats(name):
    assert run.read_metric(name, context(PARENTS_STATS)) is None


@pytest.mark.parametrize("name", PARTS + INSIDE)
def test_entry_moves_setup_s_in_every_cell(name):
    entries = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    entry = entries[0]
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert "workloads" not in entry
    assert entry["unit"] == ("count" if name == "setup_fresh_compiles"
                             else "s")
    assert entry["source"] == ("program_span" if name in PARTS
                               or name == "setup_warmup_s"
                               else "program_counter")
    for cell in MANIFEST["workloads"]:
        assert name in [m["name"] for m in
                        run.metrics_for(MANIFEST, cell["name"], "per_layer")]
    assert os.path.exists(
        os.path.join(ROOT, "perfbench", "metrics", name + ".py"))


def test_a_rehearsed_cell_reports_the_six_on_one_clock(tmp_path):
    """The whole command on the CPU at toy size, traced: the child's stamps
    (the engine's `time.monotonic()`) and the parent's (`t_start`,
    `t_open`) lie on one clock, in order, and the parts fill `setup_s`."""
    root = checkout_with_the_program(tmp_path)
    cell, seed = "mistral-7b.decode-saturated", 2147483951
    proc = run_cell(root, cell, trace=1, seed=seed)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = {name: line["metrics"][name]["value"] for name in PARTS + INSIDE}
    out = os.path.join(root, "perfbench", "out", cell)
    with gzip.open(os.path.join(
            out, f"seed{seed}.trace1.samples.json.gz"), "rt") as f:
        meta = json.load(f)["meta"]
    assert all(got[name] > 0.0 for name in PARTS)
    assert sum(got[name] for name in PARTS) == pytest.approx(
        meta["setup_s"], abs=1e-6)
    assert 0.0 < got["setup_warmup_s"] < got["setup_engine_init_s"]
    assert 0.0 < got["setup_trace_lower_s"] < got["setup_engine_init_s"]
    assert got["setup_fresh_compiles"] >= 0.0
    assert line["metrics"]["setup_fresh_compiles"]["unit"] == "count"
    # Every run's server log says what its start cost, a cold run's too.
    with open(os.path.join(out, f"seed{seed}.trace1.server.log")) as f:
        started = [json.loads(text) for text in f
                   if '"msg":"engine started"' in text]
    assert len(started) == 1
    rows = started[0]["executables"]
    assert sum(row["backend_s"] for row in rows) == pytest.approx(
        started[0]["warmup_compile"]["backend_s"], abs=1e-3)
    assert started[0]["slowest_executable"] in rows
    assert started[0]["t_end"] - started[0]["t_begin"] == pytest.approx(
        got["setup_engine_init_s"], abs=1e-6)
