"""chip_smoke.py's contract, rehearsed on the CPU (`--tiny`): the same
code path as the on-chip run — server child over a real socket, health
wait, unary + streamed + concurrent requests, engine_stats, SIGTERM — at
tiny-llama size. The TPU-only clauses (platform, Mosaic custom calls) are
checked by the chip run itself."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("POLYKEY_")}
    # Four slots instead of sixteen: fewer warm-up shapes to compile (the
    # one place this test trades the default geometry for suite time).
    env.update(JAX_PLATFORMS="cpu", POLYKEY_MAX_DECODE_SLOTS="4", **extra)
    return env


def _last_json(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except ValueError:
        return None


def test_tiny_rehearsal_passes_and_a_failed_request_fails_it():
    """Two rehearsals side by side: a clean one exits 0 with the result
    line; one whose first request fails (injected tokenizer error) exits
    non-zero and prints no result."""
    runs = {
        "clean": _env(),
        "faulty": _env(POLYKEY_FAULTS="tokenizer-error@1"),
    }
    procs = {
        name: subprocess.Popen(
            [sys.executable, SMOKE, "--tiny"], env=env, cwd=ROOT,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for name, env in runs.items()
    }
    out = {name: p.communicate(timeout=600) for name, p in procs.items()}

    assert procs["clean"].returncode == 0, out["clean"][1][-2000:]
    result = _last_json(out["clean"][0])
    assert result == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu",
                   "count": result["device"]["count"]},
    }
    assert "platform=cpu" in out["clean"][0]
    assert "cold_start_seconds=" in out["clean"][0]

    assert procs["faulty"].returncode != 0
    assert _last_json(out["faulty"][0]) is None
    assert "chip_smoke FAILED" in out["faulty"][1]


def test_refuses_without_starting_a_server(tmp_path):
    """The clause decided before any child starts: a full-size run pinned
    to the CPU (JAX_PLATFORMS=cpu, no --tiny)."""
    run = subprocess.run(
        [sys.executable, SMOKE], env=_env(), cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert run.returncode != 0
    assert _last_json(run.stdout) is None
    assert "chip_smoke FAILED" in run.stderr


def test_fails_alone_in_a_directory(tmp_path):
    """The script alone, without the program, must fail and print no
    result (the driver runs it that way)."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    run = subprocess.run(
        [sys.executable, "chip_smoke.py", "--tiny"], env=_env(PYTHONPATH=""),
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert run.returncode != 0
    assert _last_json(run.stdout) is None


def test_prefill_cover_check_rehearses_on_the_cpu():
    """scripts/tpu_prefill_cover_check.py at the configuration's tiny size:
    the two-row prefill agrees with the one-row window (float32: to
    rounding), so the script the chip runs is known to run."""
    run = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "scripts", "tpu_prefill_cover_check.py"),
         "--tiny", "--reps", "1"],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert run.returncode == 0, run.stderr[-2000:]
    lines = run.stdout.strip().splitlines()
    assert lines[0].startswith("device cpu")
    assert lines[-1] == "PASS"
    assert "first token" in run.stdout
