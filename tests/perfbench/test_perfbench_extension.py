"""A configuration, a traffic mix, a cell and a per-layer metric are each
added by new files and new entries only: shown on a temporary copy of the
benchmark, once for a renamed copy of a configuration the harness knows
and once for a block it does not know, which brings its own adapter,
plain reference, byte counts and kernel name (the extension contract in
the docstring of perfbench/run.py; tests/perfbench/data/own_code/). And
the benchmark refuses to run without the system under test."""

import gzip
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from perfbench_paths import BENCH, DATA, ROOT

import extension
import kernel_costs
import own_code
import run as harness
import trace_reduce


def copy_benchmark(tmp_path) -> str:
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    return root


def snapshot(bench: str) -> dict:
    before = {}
    for folder, _, files in os.walk(bench):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                before[path] = f.read()
    return before


def assert_unchanged(before: dict) -> None:
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, f"{path} was edited"


def checkout_with_the_program(tmp_path) -> str:
    root = copy_benchmark(tmp_path)
    os.symlink(os.path.join(ROOT, "polykey_tpu"),
               os.path.join(root, "polykey_tpu"))
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


def add_renamed_copy(root: str) -> str:
    """A copy of mistral-7b under another name, a traffic mix and two
    metric readers: what the harness's own functions fit."""
    bench = os.path.join(root, "perfbench")
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
    return "dummy-model.dummy-mix"


@pytest.fixture(scope="module")
def own_code_run(tmp_path_factory):
    """ONE rehearsal of the cell of the block the harness does not know,
    shared by the tests below: (checkout, files before, finished run)."""
    root = checkout_with_the_program(tmp_path_factory.mktemp("own_code"))
    before = snapshot(os.path.join(root, "perfbench"))
    own_code.install(root)
    return root, before, run(root, own_code.CELL, trace=1)


@pytest.mark.parametrize("kind", ["renamed-copy", "own-code"])
def test_new_cell_config_traffic_and_metric_are_only_new_files(
        kind, tmp_path, request):
    if kind == "own-code":
        root, before, proc = request.getfixturevalue("own_code_run")
    else:
        root = checkout_with_the_program(tmp_path)
        before = snapshot(os.path.join(root, "perfbench"))
        proc = run(root, add_renamed_copy(root), trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    # Metrics of other cells stay out; cell-less ones come along.
    assert "avg_lanes" not in line["metrics"]
    assert "compiles_in_window" in line["metrics"]
    if kind == "own-code":
        # The verdict is the new reference module's, and says so; the
        # harness's own reference refuses this block (test below).
        assert line["reference"]["ok"] and line["reference"]["tokens"] == 32
        assert line["reference"]["module"] == "own_code.py"
        assert line["metrics"]["kv_pages_used_peak_share"]["value"] > 0
    else:
        assert "module" not in line["reference"]
        assert line["metrics"]["dummy_blocks"]["value"] > 0
        # A reader that finds nothing to read is left out of the line.
        assert "dummy_nothing" not in line["metrics"]
    assert_unchanged(before)


def test_own_code_verdict_is_its_reference_modules(own_code_run, monkeypatch):
    """The run's own sample judged again in this process, through
    server_child.run_reference on the tree the adapter's `weights` hook
    makes from the same seed: by the module the file names (as in the
    run), by the harness's own reference (which does not know the block),
    and by a copy of the module made wrong on purpose. The last two turn
    the same sample `correct: false` with the clause named; and the
    adapter's `release` hook, not the harness's, frees the engine."""
    import server_child

    root, _, proc = own_code_run
    assert proc.returncode == 0, proc.stderr[-3000:]
    bench = os.path.join(root, "perfbench")
    monkeypatch.setattr(extension, "HERE", bench)
    monkeypatch.setattr(extension, "_loaded", {})
    with open(os.path.join(bench, "configs", "own-code.json")) as f:
        spec = json.load(f)
    model_cfg = server_child.hook(spec, "model_config", None)(spec, True)
    config = server_child.hook(
        spec, "engine_config", server_child.engine_config_from)(spec, True)
    assert model_cfg.use_post_norms and model_cfg.activation == "gelu_tanh"
    assert config.model == model_cfg.name == "own-code-tiny"
    params = server_child.hook(spec, "weights", None)(
        spec, True, config, model_cfg, 5)      # run()'s seed
    out_dir = os.path.join(bench, "out", own_code.CELL)

    right = os.path.join(bench, "references", "own_code.py")
    with open(right) as f:
        text = f.read()
    with open(os.path.join(bench, "references", "wrong.py"), "w") as f:
        f.write(text + "\n\ndef rms_norm(x, weight, eps):\n    return x\n")

    def judged(group: dict) -> dict:
        engine = types.SimpleNamespace(
            params=params, model_cfg=model_cfg, paged=object(),
            own_pool=object())
        server_child.run_reference(engine, out_dir, {**spec, "reference": group})
        assert engine.paged is None and engine.own_pool is None
        with open(os.path.join(out_dir, "reference.json")) as f:
            return json.load(f)

    limits = spec["reference"]
    ran = json.loads(proc.stdout.strip().splitlines()[-1])["reference"]
    again = judged(limits)
    assert again["ok"] and again["module"] == "own_code.py"
    assert again["margins"] == pytest.approx(ran["margins"], abs=1e-4)
    for group in ({k: v for k, v in limits.items() if k != "module"},
                  {**limits, "module": "wrong.py"}):
        verdict = judged(group)
        assert not verdict["ok"] and verdict.get("module") == group.get("module")
        assert any(c.startswith("outliers ") for c in verdict["why"]), verdict
        decided = harness.decide_correct({}, {}, {}, [], verdict)
        assert decided["correct"] is False
        assert all(clause in decided["why"][0] for clause in verdict["why"])
    # A module that is not there is an incorrect run, not a lost one.
    missing = judged({**limits, "module": "absent.py"})
    assert not missing["ok"] and "perfbench/references/absent.py" in missing["error"]


def test_costs_module_is_the_named_one_or_the_default(own_code_run, monkeypatch):
    root, _, _ = own_code_run
    bench = os.path.join(root, "perfbench")
    monkeypatch.setattr(extension, "HERE", bench)
    monkeypatch.setattr(extension, "_loaded", {})
    with open(os.path.join(BENCH, "configs", "mistral-7b.json")) as f:
        known = json.load(f)
    with open(os.path.join(bench, "configs", "own-code.json")) as f:
        unknown = json.load(f)
    assert kernel_costs.for_spec(known) is kernel_costs
    own = kernel_costs.for_spec(unknown)
    assert own.__file__ == os.path.join(bench, "costs", "own_code.py")
    assert kernel_costs.for_spec(unknown) is own        # loaded once
    # The default formulas cannot even read this file's words.
    with pytest.raises(KeyError):
        kernel_costs.decode_step_bytes(unknown, 100.0)
    with pytest.raises(ValueError):
        kernel_costs.for_spec({"costs": "../run.py"})
    with pytest.raises(FileNotFoundError):
        kernel_costs.for_spec({"costs": "absent.py"})

    def ctx(spec):
        """One stream of 99 + 1 tokens live all through a 10 s capture
        whose decode blocks of 8 steps took 80 ms on the device."""
        request = {"times": [-1.0, 20.0], "counts": [1, 1], "final": None,
                   "prompt_tokens": 99}
        return harness.Context(
            spec=spec, peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            trace={"modules": {"jit__decode_fn": {"durations_s": [0.08]}},
                   "kernels": {"reshape_squeeze": {"total_s": 1e-3, "count": 4}}},
            samples={"meta": {"traced": {"start": 0.0, "stop": 10.0}},
                     "requests": [request]})

    for spec, costs in ((known, kernel_costs), (unknown, own)):
        needed = costs.decode_step_bytes(spec, 100.0)
        assert harness.read_metric("decode_mbu", ctx(spec)) == pytest.approx(
            100.0 * needed / 0.010 / 819e9)
    # Same widths: the block's own count is the default's plus its four
    # norm gains a layer, which the default does not know of.
    assert (own.decode_step_bytes(unknown, 100.0)
            - kernel_costs.decode_step_bytes(known, 100.0)) == 32 * 4 * 4096 * 2
    # The reader the configuration brought for the kernel it named.
    share = harness.read_metric("reshape_squeeze_roofline", ctx(unknown))
    pool = 2048 * 16 * 8 * 128 * 2
    assert share == pytest.approx(100.0 * (pool / 819e9) * 4 / 1e-3)


def test_a_configurations_kernel_names_are_summed_beside_the_four():
    with gzip.open(os.path.join(DATA, "recorded_trace.json.gz"), "rt") as f:
        recorded = json.load(f)
    plain = trace_reduce.reduce(recorded)
    more = trace_reduce.reduce(
        recorded, ["reshape_squeeze", "paged_kv", "flash_attention"])
    assert set(plain["kernels"]) == {"paged_kv_write", "paged_attention_decode",
                                     "flash_attention"}
    for name, entry in plain["kernels"].items():
        assert more["kernels"][name] == entry
    # The new name gets the operations that start with it ...
    squeezed = sum(v["total_s"] for k, v in plain["ops"].items()
                   if k.split("/")[1].startswith("reshape_squeeze"))
    assert more["kernels"]["reshape_squeeze"]["total_s"] == \
        pytest.approx(squeezed) and squeezed > 0.02
    assert set(more["kernels"]["reshape_squeeze"]["by_program"]) == \
        {"jit__decode_fn"}
    # ... and a prefix of one of the four takes nothing from it.
    assert "paged_kv" not in more["kernels"]
    assert {k: v for k, v in more.items() if k != "kernels"} == \
        {k: v for k, v in plain.items() if k != "kernels"}


def test_hashed_weights_fill_a_named_norm_and_a_one_dimensional_leaf():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import weights
    from polykey_tpu.models.quant import QuantizedTensor

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype)

    shapes = {
        "layers": {
            "latent_norm": shape(2, 64),
            "gate_bias": shape(64),
            "router": shape(2, 64, 8),
            "down": QuantizedTensor(q=shape(2, 128, 64, dtype=jnp.int8),
                                    s=shape(2, 64)),
        },
        "final_norm": shape(64),
    }
    # By the harness's own list of norms the new gain is taken for a matrix.
    plain = jax.jit(lambda: weights.filled(shapes, 7))()
    assert np.asarray(plain["layers"]["latent_norm"]).min() < 0
    ones = weights.NORMS + ("latent_norm",)
    made = jax.jit(lambda: weights.filled(shapes, 7, ones))()
    assert np.asarray(made["layers"]["latent_norm"]).min() == 1.0
    assert np.asarray(made["final_norm"]).max() == 1.0
    bias = np.asarray(made["layers"]["gate_bias"])
    assert bias.shape == (64,) and 0 < np.abs(bias).max() <= 3 ** 0.5 / 8
    assert np.asarray(made["layers"]["router"]).std() == \
        pytest.approx(64 ** -0.5, rel=0.1)
    assert made["layers"]["down"].q.dtype == jnp.int8


def test_refuses_without_the_system_under_test(tmp_path):
    root = copy_benchmark(tmp_path)
    proc = run(root, "mistral-7b.decode-saturated", trace=0)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_refuses_an_unknown_cell_and_a_cpu_pin_without_tiny(tmp_path):
    # In a copy: a run clears its cell's out/ directory of the last sample
    # and verdict, which a rehearsal on another test worker may be using.
    root = checkout_with_the_program(tmp_path)
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
