"""Config loader parity tests (/root/reference/internal/config/config.go)."""

import os

import pytest

from polykey_tpu.gateway.config import (
    ConfigLoader,
    NetworkTester,
    RuntimeDetector,
    RuntimeEnvironment,
    parse_duration,
)


class _FixedDetector(RuntimeDetector):
    def __init__(self, runtime):
        self._runtime = runtime

    def detect_runtime(self):
        return self._runtime


def _clear_env(monkeypatch):
    for var in (
        "POLYKEY_SERVER_ADDR",
        "POLYKEY_TIMEOUT",
        "POLYKEY_LOG_LEVEL",
        "POLYKEY_ENV",
        "KUBERNETES_SERVICE_HOST",
        "container",
    ):
        monkeypatch.delenv(var, raising=False)


def test_defaults(monkeypatch):
    _clear_env(monkeypatch)
    cfg = ConfigLoader(_FixedDetector(RuntimeEnvironment.LOCAL)).load([])
    assert cfg.timeout == 5.0
    assert cfg.log_level == "info"
    assert cfg.environment == "development"
    assert cfg.server_address == "localhost:50051"


def test_flags(monkeypatch):
    _clear_env(monkeypatch)
    cfg = ConfigLoader(_FixedDetector(RuntimeEnvironment.LOCAL)).load(
        ["-server", "example:1234", "-timeout", "10s", "-log-level", "debug",
         "-env", "production"]
    )
    assert cfg.server_address == "example:1234"
    assert cfg.timeout == 10.0
    assert cfg.log_level == "debug"
    assert cfg.environment == "production"


def test_env_overrides_flags(monkeypatch):
    # Load() applies env after flags, so env wins (config.go Load()).
    _clear_env(monkeypatch)
    monkeypatch.setenv("POLYKEY_SERVER_ADDR", "env-host:9")
    monkeypatch.setenv("POLYKEY_TIMEOUT", "500ms")
    cfg = ConfigLoader(_FixedDetector(RuntimeEnvironment.LOCAL)).load(
        ["-server", "flag-host:8", "-timeout", "10s"]
    )
    assert cfg.server_address == "env-host:9"
    assert cfg.timeout == 0.5


def test_malformed_env_timeout_is_ignored(monkeypatch):
    _clear_env(monkeypatch)
    monkeypatch.setenv("POLYKEY_TIMEOUT", "not-a-duration")
    cfg = ConfigLoader(_FixedDetector(RuntimeEnvironment.LOCAL)).load([])
    assert cfg.timeout == 5.0


@pytest.mark.parametrize(
    "runtime,expected",
    [
        (RuntimeEnvironment.KUBERNETES, "polykey-service:50051"),
        (RuntimeEnvironment.DOCKER, "polykey-server:50051"),
        (RuntimeEnvironment.CONTAINERD, "polykey-server:50051"),
        (RuntimeEnvironment.PODMAN, "polykey-server:50051"),
        (RuntimeEnvironment.LOCAL, "localhost:50051"),
    ],
)
def test_address_autodetection(monkeypatch, runtime, expected):
    _clear_env(monkeypatch)
    cfg = ConfigLoader(_FixedDetector(runtime)).load([])
    assert cfg.server_address == expected


def test_k8s_detection_via_env(monkeypatch):
    _clear_env(monkeypatch)
    monkeypatch.setenv("KUBERNETES_SERVICE_HOST", "10.0.0.1")
    assert RuntimeDetector().detect_runtime() == RuntimeEnvironment.KUBERNETES


def test_podman_detection_via_env(monkeypatch):
    _clear_env(monkeypatch)
    monkeypatch.setenv("container", "podman")
    assert RuntimeDetector().detect_runtime() == RuntimeEnvironment.PODMAN


@pytest.mark.parametrize(
    "text,seconds",
    [("5s", 5.0), ("500ms", 0.5), ("1m30s", 90.0), ("2h", 7200.0),
     ("250us", 0.00025), ("3", 3.0)],
)
def test_parse_duration(text, seconds):
    assert parse_duration(text) == pytest.approx(seconds)


def test_parse_duration_rejects_garbage():
    with pytest.raises(ValueError):
        parse_duration("10 parsecs")


def test_network_tester_refused():
    with pytest.raises(ConnectionError):
        # Port 1 on localhost is essentially guaranteed closed.
        NetworkTester().test_connection("127.0.0.1:1", timeout=0.5)


def test_engine_config_from_env(monkeypatch):
    """Every POLYKEY_* engine knob must actually reach EngineConfig —
    a knob that parses to nowhere silently misleads operators."""
    from polykey_tpu.engine.config import EngineConfig

    env = {
        "POLYKEY_MODEL": "tiny-mixtral",
        "POLYKEY_DTYPE": "float32",
        "POLYKEY_QUANTIZE": "1",
        "POLYKEY_MAX_DECODE_SLOTS": "8",
        "POLYKEY_PAGE_SIZE": "32",
        "POLYKEY_NUM_PAGES": "256",
        "POLYKEY_MAX_SEQ_LEN": "1024",
        "POLYKEY_PREFILL_BUCKETS": "64,256",
        "POLYKEY_PREFILL_CHUNK": "64",
        "POLYKEY_DECODE_BLOCK": "4",
        "POLYKEY_COMPILE_WARMUP": "true",
        "POLYKEY_TP": "2",
        "POLYKEY_DP": "2",
        "POLYKEY_EP": "2",
        "POLYKEY_SP": "2",
        "POLYKEY_DRAFT_MODEL": "tiny-llama",
        "POLYKEY_SPEC_GAMMA": "3",
        "POLYKEY_NUM_SLICES": "2",
        "POLYKEY_ADAPTIVE_BLOCK": "0",
        "POLYKEY_ADAPTIVE_GAMMA": "0",
    }
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    cfg = EngineConfig.from_env()
    assert cfg.model == "tiny-mixtral"
    assert cfg.quantize and cfg.compile_warmup
    assert (cfg.max_decode_slots, cfg.page_size, cfg.num_pages) == (8, 32, 256)
    assert cfg.prefill_buckets == (64, 256)
    assert (cfg.prefill_chunk, cfg.decode_block_steps) == (64, 4)
    assert (cfg.tp, cfg.dp, cfg.ep, cfg.sp) == (2, 2, 2, 2)
    assert (cfg.draft_model, cfg.spec_gamma) == ("tiny-llama", 3)
    assert cfg.num_slices == 2
    assert cfg.quantize_bits == 8
    # The adaptive knobs default ON; "0" must pin them off.
    assert not cfg.adaptive_block and not cfg.adaptive_gamma
    cfg.validate()


def test_engine_config_int4_env(monkeypatch):
    """POLYKEY_QUANTIZE=int4 selects 4-bit weight-only quantization."""
    from polykey_tpu.engine.config import EngineConfig

    monkeypatch.setenv("POLYKEY_QUANTIZE", "int4")
    cfg = EngineConfig.from_env()
    assert cfg.quantize and cfg.quantize_bits == 4
    cfg.validate()


def test_lookahead_has_one_environment_name(monkeypatch):
    """POLYKEY_DISPATCH_LOOKAHEAD (DEPLOY.md's row) is the only name the
    pipeline depth is read from; the alias that used to stand beside it
    is not a knob."""
    from polykey_tpu.engine.config import EngineConfig

    monkeypatch.delenv("POLYKEY_DISPATCH_LOOKAHEAD", raising=False)
    monkeypatch.setenv("POLYKEY_LOOKAHEAD", "5")
    assert EngineConfig.from_env().lookahead_blocks == \
        EngineConfig.lookahead_blocks
    monkeypatch.setenv("POLYKEY_DISPATCH_LOOKAHEAD", "3")
    assert EngineConfig.from_env().lookahead_blocks == 3


def test_engine_config_has_no_host_sync_emulation():
    """The speculative round has one crossing schedule, the device-
    resident one; the field that emulated the older one for an A/B is not
    part of the configuration."""
    import dataclasses

    from polykey_tpu.engine.config import EngineConfig

    names = {f.name for f in dataclasses.fields(EngineConfig)}
    assert "spec_host_sync" not in names
    assert {"spec_gamma", "adaptive_gamma", "draft_model"} <= names


def _record_config_updates(monkeypatch):
    """Swap jax.config.update for a recorder (the test process's real
    JAX config must not change under the other tests)."""
    import jax

    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


_MIN_SECS = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
_PERSIST_ALL = ("jax_persistent_cache_min_compile_time_secs", 0.0)


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set => JAX already reads it; the program
    reports it and writes no cache directory of its own."""
    import polykey_tpu.engine.config as ec

    monkeypatch.delenv("POLYKEY_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv(_MIN_SECS, raising=False)
    calls = _record_config_updates(monkeypatch)
    assert ec.enable_persistent_compile_cache() == str(tmp_path)
    assert calls == [_PERSIST_ALL]


def test_compile_cache_defaults_to_checkout(monkeypatch):
    """Unset => <checkout>/.jax_cache, computed from the package's
    location (never ~, a temp name, a pid or a time): the directory is
    part of JAX's cache key, so it must not move between runs."""
    import polykey_tpu
    import polykey_tpu.engine.config as ec

    monkeypatch.delenv("POLYKEY_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv(_MIN_SECS, raising=False)
    calls = _record_config_updates(monkeypatch)
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(polykey_tpu.__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert ec.enable_persistent_compile_cache() == want
    assert ec.enable_persistent_compile_cache() == want   # stable
    assert set(calls) == {("jax_compilation_cache_dir", want), _PERSIST_ALL}


def test_compile_cache_threshold_from_outside_wins(monkeypatch, tmp_path):
    """The program persists every executable (a warm start compiles
    nothing) unless JAX's own threshold variable says otherwise."""
    import polykey_tpu.engine.config as ec

    monkeypatch.delenv("POLYKEY_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv(_MIN_SECS, "2.5")
    calls = _record_config_updates(monkeypatch)
    assert ec.enable_persistent_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_written_where_placed(tmp_path):
    """End to end in a fresh process: with the directory placed from
    outside, a sub-second compile after enable_persistent_compile_cache()
    lands its entry there (JAX's default threshold would skip it)."""
    import subprocess
    import sys

    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
    )
    env.pop("POLYKEY_COMPILE_CACHE", None)
    env.pop(_MIN_SECS, None)
    code = (
        "import polykey_tpu.engine.config as ec\n"
        "print(ec.enable_persistent_compile_cache())\n"
        "import jax, jax.numpy as jnp\n"
        "jax.jit(lambda x: (x * 3 + 1).sum())(jnp.arange(1237.0))"
        ".block_until_ready()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir()), "compile cache wrote no entries"


def test_persistent_compile_cache_opt_out(monkeypatch):
    """POLYKEY_COMPILE_CACHE=0 disables the cache entirely."""
    import polykey_tpu.engine.config as ec

    monkeypatch.setenv("POLYKEY_COMPILE_CACHE", "0")
    calls = _record_config_updates(monkeypatch)
    assert ec.enable_persistent_compile_cache() is None
    assert calls == []
