"""Start-up measured inside the program (ISSUE 62): the constructor's
phases, the record of the newest construction (`engine_stats["startup"]`,
the `engine started` line), the compile census's seconds, and a compile
while serving naming the phase that paid for it. Tiny model, CPU: counts,
identities and orderings — a duration only against another of the same
clock."""

import dataclasses
import io
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from polykey_tpu.engine import device
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.metrics import EngineMetrics
from polykey_tpu.gateway.jsonlog import Logger
from polykey_tpu.obs.timeline import LOOP_PHASES, PHASES, open_phase, phase

from test_engine import _collect

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = EngineConfig(
    model="tiny-llama",
    tokenizer="byte",
    dtype="float32",
    max_decode_slots=2,
    page_size=8,
    num_pages=61,                       # this file's own shapes
    max_seq_len=64,
    prefill_buckets=(16,),
    max_new_tokens_cap=16,
    decode_block_steps=4,
    compile_warmup=True,
    warm_sampled_variants=False,
)
STAGES = {"init", "place_params", "pools", "warmup", "release_heap"}
COMPILE_KEYS = {"executables", "cache_hits", "fresh_compiles", "trace_s",
                "lower_s", "backend_s", "cache_retrieval_s"}


def lines_of(stream: io.StringIO, msg: str) -> list:
    return [line for line in map(json.loads, stream.getvalue().splitlines())
            if line["msg"] == msg]


@pytest.fixture(scope="module")
def built():
    """(engine, its logger's stream): one warmed construction."""
    stream = io.StringIO()
    eng = InferenceEngine(CONFIG, logger=Logger(stream=stream))
    yield eng, stream
    eng.shutdown()


# -- the record ----------------------------------------------------------------


def test_stages_lie_inside_init_and_do_not_overlap(built):
    eng, _ = built
    record = eng.stats()["startup"]
    assert set(record) == {"t_begin", "t_end", "stages", "compile",
                           "warmup_compile", "executables",
                           "executable_store"}
    # No cache directory in this process: no store, and no row loaded.
    assert record["executable_store"] is None
    assert not any(row["loaded"] for row in record["executables"])
    stages = record["stages"]
    assert set(stages) == STAGES
    assert all(seconds >= 0.0 for seconds in stages.values())
    inside = sum(stages[name] for name in STAGES - {"init"})
    # Stages that overlapped would add up to more than the phase that
    # holds them all; `init` is the constructor but its first lines.
    assert inside <= stages["init"] <= record["t_end"] - record["t_begin"]
    assert record["t_begin"] < record["t_end"] <= time.monotonic()
    entered = eng.metrics.phase_count
    assert [entered[name] for name in sorted(STAGES)] == [1] * len(STAGES)
    assert entered["warm_call"] == len(record["executables"])
    assert eng.metrics.phase_seconds["warm_call"] <= stages["warmup"]


def test_one_row_a_warm_call_and_their_backend_seconds_add_up(built):
    eng, _ = built
    record = eng.stats()["startup"]
    rows = record["executables"]
    # One bucket x the group sizes of two slots, greedy only; a merge a
    # group size; the solo and the full decode block; the retire.
    assert [row["step"] for row in rows] == [
        "prefill", "merge", "prefill", "merge", "decode", "decode", "retire"]
    assert [(row["bucket"], row["rows"], row["greedy"])
            for row in rows if row["step"] == "prefill"] == [
        (16, 1, True), (16, 2, True)]
    assert [row["steps"] for row in rows if row["step"] == "decode"] == [1, 4]
    for row in rows:
        assert row["seconds"] >= row["backend_s"] >= 0.0
        assert row["cache_hit"] in (True, False)
    warm, whole = record["warmup_compile"], record["compile"]
    assert set(warm) == set(whole) == COMPILE_KEYS
    assert sum(row["backend_s"] for row in rows) == pytest.approx(
        warm["backend_s"], abs=1e-4)
    assert 0 < warm["executables"] <= whole["executables"]
    assert warm["executables"] == warm["cache_hits"] + warm["fresh_compiles"]
    for key in ("trace_s", "lower_s", "backend_s"):
        assert 0.0 < warm[key] <= whole[key]
    # JAX's three stages are disjoint stretches of the constructing
    # thread's time (a nested trace is counted once).
    assert (whole["trace_s"] + whole["lower_s"] + whole["backend_s"]
            <= record["stages"]["init"])


def test_engine_started_is_logged_once_and_names_the_slowest(built):
    eng, stream = built
    started = lines_of(stream, "engine started")
    assert len(started) == 1
    line, record = started[0], eng.stats()["startup"]
    for key, value in record.items():
        assert line[key] == value
    assert line["slowest_executable"] == max(
        record["executables"], key=lambda row: row["seconds"])
    assert line["level"] == "INFO"
    # Nothing compiled outside start-up, so nothing was said of it.
    assert lines_of(stream, "compile while serving") == []


def test_the_record_crosses_the_gateways_struct(built):
    from google.protobuf.json_format import MessageToDict
    from google.protobuf.struct_pb2 import Struct

    eng, _ = built
    stats = eng.stats()
    message = Struct()
    message.update({"startup": stats["startup"],
                    "compiles": stats["compiles"]})
    crossed = MessageToDict(message)
    assert crossed["startup"]["t_begin"] == stats["startup"]["t_begin"]
    assert crossed["startup"]["stages"] == stats["startup"]["stages"]
    assert len(crossed["startup"]["executables"]) == 7
    assert crossed["compiles"]["by_phase"]["startup"] > 0
    assert "warmup_compiles" not in stats


def test_without_warmup_the_record_is_whole():
    config = dataclasses.replace(CONFIG, compile_warmup=False, num_pages=62)
    eng = InferenceEngine(config)
    try:
        record = eng.stats()["startup"]
    finally:
        eng.shutdown()
    assert set(record["stages"]) == {"init", "place_params", "pools"}
    assert record["executables"] == [] and record["warmup_compile"] == {}
    assert set(record["compile"]) == COMPILE_KEYS
    assert record["t_begin"] < record["t_end"]
    assert eng.metrics.phase_count["warm_call"] == 0


# -- the persistent cache ------------------------------------------------------


@pytest.fixture
def cache_dirs(tmp_path):
    """Two empty directories, and JAX's persistent cache as a serving
    process places it (every executable kept), put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    def use(directory) -> None:
        jax.config.update("jax_compilation_cache_dir", str(directory))
        compilation_cache.reset_cache()
        jax.clear_caches()          # nothing is left in memory either

    try:
        yield use, tmp_path / "first", tmp_path / "second"
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


def constructed(config) -> dict:
    eng = InferenceEngine(config)
    eng.shutdown()
    return eng.stats()["startup"]


def test_a_second_start_loads_and_a_fresh_directory_compiles(cache_dirs):
    use, first, second = cache_dirs
    config = dataclasses.replace(CONFIG, num_pages=63)
    use(first)
    cold = constructed(config)
    # A first start compiles; what it hits is its own work, a module it
    # builds twice (the same zeros for two pools).
    assert cold["compile"]["fresh_compiles"] > 0
    assert cold["compile"]["cache_hits"] < cold["compile"]["fresh_compiles"]
    assert not any(row["cache_hit"] for row in cold["executables"]
                   if row["step"] in ("prefill", "decode"))
    assert cold["executable_store"]["built"] == len(cold["executables"])
    use(first)
    warm = constructed(config)
    assert warm["compile"]["fresh_compiles"] == 0
    # The warm-up executables come whole out of the executable store
    # beside the cache (ISSUE 63, tests/test_executable_store.py): JAX's
    # cache is read for the others alone (the seeded init, the pools).
    assert all(row["loaded"] and not row["cache_hit"]
               for row in warm["executables"])
    assert warm["compile"]["cache_hits"] == cold["compile"]["executables"] \
        - cold["warmup_compile"]["executables"]
    assert 0.0 < warm["compile"]["cache_retrieval_s"] \
        <= warm["compile"]["backend_s"]
    # For those, tracing and lowering are paid again (the cache's key is
    # computed from the lowered module); for warm-up's, nothing is.
    assert warm["compile"]["trace_s"] > 0.0 and warm["compile"]["lower_s"] > 0.0
    assert warm["warmup_compile"]["trace_s"] == 0.0
    assert warm["warmup_compile"]["lower_s"] == 0.0
    assert warm["warmup_compile"]["executables"] == 0
    use(second)
    other = constructed(config)
    assert other["compile"]["fresh_compiles"] == \
        cold["compile"]["fresh_compiles"]
    assert other["compile"]["cache_hits"] == cold["compile"]["cache_hits"]


# -- a compile while serving ---------------------------------------------------


def test_a_shape_served_unwarmed_says_which_phase_compiled_it():
    stream = io.StringIO()
    config = dataclasses.replace(
        CONFIG, compile_warmup=False, num_pages=64, decode_block_steps=3)
    before = device.compile_counts()["by_phase"]
    eng = InferenceEngine(config, logger=Logger(stream=stream))
    try:
        request = GenRequest(prompt="unwarmed", max_new_tokens=5)
        eng.submit(request)
        _tokens, done, error = _collect(request)
        assert error is None and done is not None
        stats = eng.stats()
    finally:
        eng.shutdown()
    said = lines_of(stream, "compile while serving")
    by_name = {line["executable"]: line for line in said}
    prefill, decode = by_name["jit(_prefill_fn)"], by_name["jit(_decode_fn)"]
    assert prefill["phase"] == "prefill"
    assert (prefill["bucket"], prefill["rows"]) == (16, 16)
    assert decode["phase"] == "decode" and decode["lanes"] == 1
    assert decode["steps"] in (1, 3)
    for line in said:
        assert line["level"] == "WARN" and line["seconds"] > 0.0
        assert line["thread"] == "polykey-engine"
        assert line["phase"] not in ("startup", "none")
    after = stats["compiles"]["by_phase"]
    assert after["prefill"] > before.get("prefill", 0)
    assert after["decode"] > before.get("decode", 0)
    moved = sum(after.values()) - sum(before.values())
    assert moved == len(said) + stats["startup"]["compile"]["executables"]
    # The record is the constructor's: what serving compiled is not in it.
    assert lines_of(stream, "engine started")[0]["compile"] == \
        stats["startup"]["compile"]


# -- the census and the primitive ----------------------------------------------


def test_census_seconds_are_disjoint_stretches_of_the_compiling_thread():
    device.install_compile_census()

    @jax.jit
    def nested(x):
        # jax.numpy's own jitted functions: a trace event each, inside
        # this function's.
        for _ in range(20):
            x = jnp.where(jnp.clip(x, 0.0, 1.0) > 0.5, jnp.tanh(x), x + 1.0)
        return x

    before, began = device.compile_counts(), time.monotonic()
    nested(jnp.ones((7, 3), jnp.float32)).block_until_ready()
    wall, after = time.monotonic() - began, device.compile_counts()
    spent = device.compile_delta(before, after)
    assert spent["executables"] >= 1 and spent["trace_s"] > 0.0
    assert spent["lower_s"] > 0.0 and spent["backend_s"] > 0.0
    # log_elapsed_time reads time.time(); leave it a millisecond.
    assert (spent["trace_s"] + spent["lower_s"] + spent["backend_s"]
            <= wall + 1e-3)
    assert after["by_phase"].get("none", 0) > before["by_phase"].get("none", 0)
    for key in after:
        if key != "by_phase":
            assert after[key] >= before[key]


def test_open_phase_is_the_threads_innermost():
    metrics = EngineMetrics()
    assert open_phase() is None
    with phase(metrics, "process", seq=4):
        assert open_phase() == ("process", {"seq": 4})
        with phase(metrics, "readback_wait"):
            assert open_phase() == ("readback_wait", {})
            seen = []
            other = threading.Thread(target=lambda: seen.append(open_phase()))
            other.start()
            other.join(10.0)
            assert seen == [None]
        assert open_phase() == ("process", {"seq": 4})
    assert open_phase() is None
    with pytest.raises(RuntimeError):
        with phase(metrics, "admit"):
            raise RuntimeError("a phase that raises still closes")
    assert open_phase() is None and metrics.phase_count["admit"] == 1


def test_loop_phases_and_their_sum_are_what_they_were(built, monkeypatch):
    import importlib

    assert LOOP_PHASES == ("admit", "restore", "chunk", "dispatch",
                           "resolve", "process", "idle_wait")
    assert {name for name, (level, _) in PHASES.items()
            if level == "startup"} == STAGES | {"warm_call"}
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perfbench"))
    run = importlib.import_module("run")
    eng, _ = built
    opened = eng.stats()
    request = GenRequest(prompt="loop phases", max_new_tokens=9)
    eng.submit(request)
    _tokens, done, error = _collect(request)
    assert error is None and done is not None
    deadline = time.monotonic() + 60.0
    while eng.busy or eng._inflight_q:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    closed = eng.stats()
    # Serving moved no start-up phase, and the reader of the loop phases
    # reads the six that work less the readback, as before.
    for name in STAGES | {"warm_call"}:
        assert closed["phase_seconds"][name] == opened["phase_seconds"][name]
        assert closed["phase_count"][name] == opened["phase_count"][name]
    work = sum(closed["phase_seconds"][n] - opened["phase_seconds"][n]
               for n in LOOP_PHASES if n != "idle_wait")
    waited = (closed["phase_seconds"]["readback_wait"]
              - opened["phase_seconds"]["readback_wait"])
    blocks = closed["blocks_dispatched"] - opened["blocks_dispatched"]
    ctx = run.Context(stats_open=opened, stats_close=closed)
    got = run.read_metric("host_ms_per_decode_block", ctx)
    assert blocks > 0
    assert got == pytest.approx(1000.0 * (work - waited) / blocks, rel=1e-6)


def test_a_restart_brings_its_start_up_phases_along():
    old, fresh = EngineMetrics(), EngineMetrics()
    old.on_phase("init", 2.0)
    old.on_phase("process", 1.0)
    fresh.on_phase("init", 3.0)
    fresh.on_phase("warm_call", 0.5)
    fresh.on_phase("process", 9.0)      # not a start-up phase: stays
    old.adopt_startup(fresh)
    assert old.phase_seconds["init"] == 5.0 and old.phase_count["init"] == 2
    assert old.phase_seconds["warm_call"] == 0.5
    assert old.phase_seconds["process"] == 1.0
