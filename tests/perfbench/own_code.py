"""Adds the test configuration `own-code` to a copy of the benchmark, as
new files and new BENCHMARK.json entries only: an adapter, a plain
reference, a costs module, a kernel name and that kernel's reader, all
of an architecture the harness's code does not know
(tests/perfbench/data/own_code/). No JAX import here."""

import json
import os
import shutil

from perfbench_paths import DATA

SOURCE = os.path.join(DATA, "own_code")
NAME = "own-code"
CELL = "own-code.decode-saturated"
FILES = {
    "config.json": "configs/own-code.json",
    "adapter.py": "adapters/own_code.py",
    "reference.py": "references/own_code.py",
    "costs.py": "costs/own_code.py",
    "reshape_squeeze_roofline.py": "metrics/reshape_squeeze_roofline.py",
}


def install(root: str) -> None:
    """`root` holds BENCHMARK.json and perfbench/; nothing there is edited
    but the manifest, which gains entries."""
    bench = os.path.join(root, "perfbench")
    for source, target in FILES.items():
        path = os.path.join(bench, target)
        assert not os.path.exists(path), f"{path} is there already"
        os.makedirs(os.path.dirname(path), exist_ok=True)
        shutil.copy(os.path.join(SOURCE, source), path)
    with open(os.path.join(SOURCE, "config.json")) as f:
        spec = json.load(f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": NAME, "source": spec["source"],
        "file": "perfbench/configs/own-code.json", "reduced": [],
        "why": "a block the harness does not know, for the extension contract"})
    manifest["workloads"].append({
        "name": CELL, "config": NAME, "traffic": "decode-saturated",
        "chips": 1, "why": "the decode-saturated mix on the own-code block"})
    manifest["per_layer"].append({
        "name": "reshape_squeeze_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernels", "moves": "tpot_ms_mean",
        "workloads": [CELL]})
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1)
