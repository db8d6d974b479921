"""Each cell's command end to end on the CPU at toy size (`--tiny`): the
same processes, sockets, traffic generator, readers and last-line contract
as on the chip; platform=cpu, and never a device metric."""

import argparse
import gzip
import json
import os
import subprocess
import sys
import time

import pytest

from perfbench_paths import ROOT

import loadgen
import run
from test_perfbench_extension import checkout_with_the_program

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
DEVICE_METRICS = {m["name"] for m in MANIFEST["per_layer"]
                  if m["source"] == "device_trace"}


def run_cell(root: str, cell: str, trace: int, seed: int = 2147483659):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", "3",
         "--trace", str(trace), "--tiny"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    return proc


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_rehearses_on_the_cpu(cell):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    proc = run_cell(ROOT, cell, trace=0)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > entry_clients(entry)
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == entry["chips"]
    wanted = {m["name"] for m in MANIFEST["end_to_end"]
              if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) == wanted
    assert all(m["value"] > 0 for m in line["metrics"].values())
    reference = line["reference"]
    assert reference["ok"] and reference["tokens"] == 32
    assert reference["why"] == [] and len(reference["margins"]) == 32
    # Each number the reference clause compares, beside its limit (stderr).
    assert "perfbench: reference: outliers 0 (limit " in proc.stderr
    samples = os.path.join(ROOT, "perfbench", "out", cell,
                           "seed2147483659.trace0.samples.json.gz")
    with gzip.open(samples, "rt") as f:
        assert json.load(f)["meta"]["reference_margins"] == reference["margins"]


def entry_clients(entry: dict) -> int:
    path = os.path.join(ROOT, "perfbench", "traffic", entry["traffic"] + ".json")
    with open(path) as f:
        return json.load(f)["clients"]


def test_traced_rehearsal_reports_counts_but_no_device_metric():
    cell = MANIFEST["workloads"][0]["name"]
    proc = run_cell(ROOT, cell, trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert not set(line["metrics"]) & DEVICE_METRICS
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < line["metrics"]["avg_lanes"]["value"] <= 16


def test_cancellations_counted_after_the_close_leave_the_run_correct(monkeypatch):
    """The race of ISSUE 27 made certain: the end reading is taken twenty
    25 ms block periods after the harness cancelled its streams, so the
    engine HAS counted them as failed, and the run is still correct."""
    stop = loadgen.ClosedLoop.stop

    def stop_then_let_the_engine_count(self):
        stop(self)
        time.sleep(0.5)

    monkeypatch.setattr(loadgen.ClosedLoop, "stop",
                        stop_then_let_the_engine_count)
    cell, seed = "mistral-7b.decode-saturated", 2147483693
    result = run.run(argparse.Namespace(
        workload=cell, seed=seed, seconds=3.0, trace=0, tiny=True))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result) == {
        "correct", "attempted", "failed", "metrics", "device",
        "generator_late_ms_max", "reference", "faults", "engine_restarts"}
    path = os.path.join(ROOT, "perfbench", "out", cell,
                        f"seed{seed}.trace0.samples.json.gz")
    with gzip.open(path, "rt") as f:
        meta = json.load(f)["meta"]
    assert meta["failed_in_window"] == 0 and meta["why_incorrect"] == []
    # What the clause before this PR would have read as a failed request.
    assert 0 < meta["failed_after_close"] <= meta["cancelled_by_harness"]


def test_a_control_laid_over_the_reference_names_its_clause(tmp_path):
    """A run made incorrect on purpose: in a copy of the benchmark the
    plain reference loses its rotary embedding (a disagreement is
    symmetric, and no file of the program may change). The whole run is
    driven; `correct` comes out false, and the clauses that failed are in
    the result line's `reference.why`, on stderr and in the samples' meta."""
    root = checkout_with_the_program(tmp_path)
    with open(os.path.join(root, "perfbench", "reference.py"), "a") as f:
        f.write("\n\ndef rotary(x, positions, theta):\n    return x\n")
    cell, seed = "mixtral-8x7b-tp4.chat-closed", 2147483777
    proc = run_cell(root, cell, trace=0, seed=seed)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    reference = line["reference"]
    assert not reference["ok"] and len(reference["margins"]) == 32
    assert reference["why"], reference
    assert any("mean_margin" in clause for clause in reference["why"])
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith("perfbench: not correct: the served sample "
                           "disagrees with the plain reference: ")
    assert all(clause in last for clause in reference["why"])
    path = os.path.join(root, "perfbench", "out", cell,
                        f"seed{seed}.trace0.samples.json.gz")
    with gzip.open(path, "rt") as f:
        meta = json.load(f)["meta"]
    assert meta["reference_margins"] == reference["margins"]
    assert meta["why_incorrect"] == [last.split("not correct: ", 1)[1]]
