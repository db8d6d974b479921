"""A configuration, a traffic mix, a cell and a per-layer metric are each
added by new files and new entries only: shown on a temporary copy of the
benchmark. And the benchmark refuses to run without the system under test."""

import json
import os
import shutil
import subprocess
import sys

from perfbench_paths import ROOT


def copy_benchmark(tmp_path) -> str:
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    return root


def run(root: str, cell: str, trace: int):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    return subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "2",
         "--trace", str(trace), "--tiny"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )


def test_new_cell_config_traffic_and_metric_are_only_new_files(tmp_path):
    root = copy_benchmark(tmp_path)
    os.symlink(os.path.join(ROOT, "polykey_tpu"),
               os.path.join(root, "polykey_tpu"))
    bench = os.path.join(root, "perfbench")
    before = {}
    for folder, _, files in os.walk(bench):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                before[path] = f.read()

    with open(os.path.join(bench, "configs", "mistral-7b.json")) as f:
        spec = json.load(f)
    spec["name"] = "dummy-model"
    spec["tiny"]["model"]["num_hidden_layers"] = 1
    with open(os.path.join(bench, "configs", "dummy-model.json"), "w") as f:
        json.dump(spec, f)
    with open(os.path.join(bench, "traffic", "dummy-mix.json"), "w") as f:
        json.dump({
            "name": "dummy-mix", "loop": "closed", "clients": 3,
            "think_ms": [0, 20], "shared_prefix_tokens": 16,
            "first_request_phasing": False,
            "tiny": {"divide_prompt": 1, "divide_output": 1, "min_tokens": 8},
            "rows_by_client": [[[40, 16], [24, 8]]] * 3,
        }, f)
    with open(os.path.join(bench, "metrics", "dummy_blocks.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return float(ctx.stats_close['blocks_dispatched'])"
                " - float(ctx.stats_open['blocks_dispatched'])\n")
    with open(os.path.join(bench, "metrics", "dummy_nothing.py"), "w") as f:
        f.write("def read(ctx):\n    return None\n")

    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "dummy-model", "source": spec["source"],
        "file": "perfbench/configs/dummy-model.json", "reduced": [],
        "why": "test"})
    manifest["workloads"].append({
        "name": "dummy-model.dummy-mix", "config": "dummy-model",
        "traffic": "dummy-mix", "chips": 1, "why": "test"})
    for name in ("dummy_blocks", "dummy_nothing"):
        manifest["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "Dispatch pipeline",
            "moves": "tpot_ms_mean", "workloads": ["dummy-model.dummy-mix"]})
    with open(path, "w") as f:
        json.dump(manifest, f)

    proc = run(root, "dummy-model.dummy-mix", trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["dummy_blocks"]["value"] > 0
    # A reader that finds nothing to read is left out of the line.
    assert "dummy_nothing" not in line["metrics"]
    # Metrics of other cells stay out; cell-less ones come along.
    assert "avg_lanes" not in line["metrics"]
    assert "compiles_in_window" in line["metrics"]
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, f"{path} was edited"


def test_refuses_without_the_system_under_test(tmp_path):
    root = copy_benchmark(tmp_path)
    proc = run(root, "mistral-7b.decode-saturated", trace=0)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_refuses_an_unknown_cell_and_a_cpu_pin_without_tiny(tmp_path):
    # In a copy: a run clears its cell's out/ directory of the last sample
    # and verdict, which a rehearsal on another test worker may be using.
    root = copy_benchmark(tmp_path)
    os.symlink(os.path.join(ROOT, "polykey_tpu"),
               os.path.join(root, "polykey_tpu"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    base = [sys.executable, os.path.join(root, "perfbench", "run.py"),
            "--seed", "1", "--seconds", "1", "--trace", "0"]
    unknown = subprocess.run(base + ["--workload", "no.such-cell"],
                             capture_output=True, text=True, env=env)
    assert unknown.returncode != 0 and unknown.stdout.strip() == ""
    pinned = subprocess.run(
        base + ["--workload", "mistral-7b.decode-saturated"],
        capture_output=True, text=True, env=env)
    assert pinned.returncode != 0 and pinned.stdout.strip() == ""
    assert "JAX_PLATFORMS=cpu" in pinned.stderr
