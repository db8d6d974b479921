"""The executable store (ISSUE 63, engine/executables.py): a second
construction on the same cache directory LOADS every warm-up executable
and serves from what it loaded; the key moves with everything an
executable depends on; a bad entry, a refused call and a lost race are
counted and never fatal; no cache directory means no store. Tiny model,
CPU: counts and identities, never a time."""

import contextlib
import dataclasses
import functools
import json
import os
import pickle
import shutil
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu.engine import device, executables
from polykey_tpu.engine.config import EngineConfig, compile_cache_dir
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.executables import (
    SUBDIRECTORY,
    ExecutableStore,
    StoredStep,
    source_digest,
)
from polykey_tpu.models.config import get_config
from polykey_tpu.models.transformer import init_params

from test_engine import _collect

CONFIG = EngineConfig(
    model="tiny-llama",
    tokenizer="byte",
    dtype="float32",
    max_decode_slots=2,
    page_size=8,
    num_pages=59,                       # this file's own shapes
    max_seq_len=64,
    prefill_buckets=(16,),
    max_new_tokens_cap=16,
    decode_block_steps=4,
    compile_warmup=True,
    warm_sampled_variants=True,
)
# Greedy variants alone: what a test REBUILDS, it builds half of.
LEAN = dataclasses.replace(CONFIG, warm_sampled_variants=False)
GREEDY = dict(prompt="the store", max_new_tokens=7)
SAMPLED = dict(prompt="the store", max_new_tokens=7, temperature=0.9,
               top_p=0.9, seed=11)


@contextlib.contextmanager
def placed(directory):
    """`directory` as the process's compile cache (every executable kept,
    nothing left in memory: the next compile is XLA's own), put back
    afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_dir", str(directory))
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        yield directory
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


@pytest.fixture
def cache_dir(tmp_path):
    with placed(tmp_path / "cache") as directory:
        yield directory


@functools.cache
def weights() -> dict:
    """One host tree for every construction here (target and draft): the
    seeded init's hundred small executables are not this file's matter."""
    return jax.device_get(init_params(
        jax.random.PRNGKey(0), get_config(CONFIG.model), jnp.float32))


def served(config, *requests, logger=None):
    """(start-up record, stats after serving, tokens of each request) of
    one construction that served `requests` one after another."""
    eng = InferenceEngine(config, params=weights(), logger=logger,
                          draft_params=weights())
    try:
        record = eng.stats()["startup"]
        answers = []
        for fields in requests:
            request = GenRequest(**fields)
            eng.submit(request)
            tokens, done, error = _collect(request)
            assert error is None and done is not None
            answers.append(tokens)
        return record, eng.stats(), answers
    finally:
        eng.shutdown()


def entries(cache) -> list:
    return sorted((cache / SUBDIRECTORY).iterdir())


# -- a second start loads ------------------------------------------------------


@pytest.fixture(scope="module")
def first_start(tmp_path_factory):
    """(cache directory, record, stats, answers) of ONE construction on
    an empty directory that then served a greedy and a sampled request."""
    with placed(tmp_path_factory.mktemp("first") / "cache") as directory:
        return (directory, *served(CONFIG, GREEDY, SAMPLED))


@pytest.fixture
def filled(first_start, cache_dir):
    """The first start's record, stats and answers beside a directory of
    this test's own that holds a copy of the store it filled (and none
    of JAX's own cache: what a test rebuilds, XLA compiles)."""
    directory, record, stats, answers = first_start
    shutil.copytree(directory / SUBDIRECTORY, cache_dir / SUBDIRECTORY)
    return cache_dir, record, stats, answers


def test_a_first_start_builds_and_writes_every_row(first_start):
    cache, record, _stats, _answers = first_start
    rows, store = record["executables"], record["executable_store"]
    assert rows and not any(row["loaded"] for row in rows)
    assert (store["built"], store["loaded"]) == (len(rows), 0)
    assert store["unreadable"] == 0 and store["fallback_calls"] == 0
    assert len(entries(cache)) == len(rows)
    assert store["bytes"] == sum(p.stat().st_size for p in entries(cache))
    assert not [p for p in entries(cache) if p.suffix == ".tmp"]


def test_a_second_start_loads_every_row_and_traces_nothing(filled):
    _cache, first, _stats, _answers = filled
    record, _stats, _answers = served(CONFIG)
    rows, store = record["executables"], record["executable_store"]
    assert [row["step"] for row in rows] == \
        [row["step"] for row in first["executables"]]
    assert all(row["loaded"] and not row["cache_hit"] for row in rows)
    assert (store["loaded"], store["built"]) == (len(rows), 0)
    assert store["bytes"] == first["executable_store"]["bytes"]
    assert store["load_s"] > 0.0 and store["store_s"] == 0.0
    # The census saw no executable made in warm-up: nothing traced,
    # nothing lowered, no compile and no read of JAX's cache.
    warm = record["warmup_compile"]
    assert warm["executables"] == 0 and warm["fresh_compiles"] == 0
    assert warm["trace_s"] == 0.0 and warm["lower_s"] == 0.0
    assert warm["backend_s"] == 0.0
    assert first["warmup_compile"]["lower_s"] > 0.0


@pytest.mark.parametrize("request_fields", [GREEDY, SAMPLED],
                         ids=["greedy", "sampled"])
def test_a_loaded_start_serves_the_same_tokens(filled, request_fields):
    _cache, _record, _stats, (greedy, sampled) = filled
    before = device.compile_counts()["executables"]
    record, stats, (tokens,) = served(CONFIG, request_fields)
    assert record["executable_store"]["built"] == 0
    assert tokens == (greedy if request_fields is GREEDY else sampled)
    # Serving went through the table: nothing fell back, and from the
    # constructor's first line to the last token the process made no
    # executable but what placing the weights and the pools takes.
    assert stats["startup"]["executable_store"]["fallback_calls"] == 0
    assert stats["compiles"]["executables"] - before == \
        record["compile"]["executables"]
    assert record["warmup_compile"]["executables"] == 0


def test_the_handles_are_stored_steps_that_still_lower(filled):
    eng = InferenceEngine(CONFIG, params=weights())
    try:
        assert isinstance(eng._jit_decode, StoredStep)
        assert isinstance(eng._jit_prefill, StoredStep)
        # What the jitted function offers is still there (the linters'
        # and the tests' `_cache_size`, `lower`).
        assert eng._jit_decode._cache_size() == 0
        assert callable(eng._jit_retire.lower)
    finally:
        eng.shutdown()


# -- the key -------------------------------------------------------------------


def test_source_digest_reads_bytes_and_inner_paths_not_the_checkout(tmp_path):
    def tree(root, body=b"x = 1\n"):
        (root / "pkg" / "ops").mkdir(parents=True)
        (root / "pkg" / "a.py").write_bytes(body)
        (root / "pkg" / "ops" / "b.py").write_bytes(b"y = 2\n")
        (root / "pkg" / "notes.txt").write_bytes(b"not hashed")
        return str(root / "pkg")

    one, two = tree(tmp_path / "one"), tree(tmp_path / "elsewhere" / "two")
    changed = tree(tmp_path / "three", body=b"x = 2\n")
    assert source_digest(one) == source_digest(two)
    assert source_digest(one) != source_digest(changed)
    os.rename(os.path.join(two, "a.py"), os.path.join(two, "c.py"))
    source_digest.cache_clear()
    assert source_digest(one) != source_digest(two)
    # The package's own digest is read once a process.
    assert source_digest() == source_digest()
    assert source_digest.cache_info().hits >= 1


def _key(store, *, static=4, shape=(3, 5), dtype=np.float32, extra=None):
    args = (np.zeros(shape, dtype), {"w": jnp.ones((2,), jnp.float32)})
    if extra is not None:
        args += (extra,)
    return store.key_text("toy", {"steps": static}, args, {})


@pytest.mark.parametrize("change", [
    dict(static=5), dict(shape=(3, 6)), dict(dtype=np.int32),
    dict(extra=np.float32(0.0)),
], ids=["static", "leaf-shape", "leaf-dtype", "tree"])
def test_another_argument_is_another_key(tmp_path, change):
    store = ExecutableStore(str(tmp_path), jax.devices()[:1])
    assert _key(store) == _key(store)
    assert _key(store, **change) != _key(store)
    assert store.path_of("toy", _key(store, **change)) != \
        store.path_of("toy", _key(store))


def test_other_devices_sources_or_settings_are_another_key(
        tmp_path, monkeypatch):
    one = ExecutableStore(str(tmp_path), jax.devices()[:1])
    assert _key(ExecutableStore(str(tmp_path), jax.devices()[:1])) == _key(one)
    assert _key(ExecutableStore(str(tmp_path), jax.devices()[:2])) != _key(one)
    assert _key(ExecutableStore(str(tmp_path), jax.devices()[1:2])) != _key(one)
    # A weak-typed scalar, a committed array and a sharded one each say so.
    args = lambda leaf: one.key_text("toy", {}, (leaf,), {})  # noqa: E731
    assert args(3) != args(np.int32(3))
    host, placed = np.zeros((8,), np.float32), jax.device_put(
        np.zeros((8,), np.float32), jax.devices()[0])
    assert args(host) != args(placed) != args(jnp.zeros((8,), jnp.float32))
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", "") + " ")
    assert _key(ExecutableStore(str(tmp_path), jax.devices()[:1])) != _key(one)
    monkeypatch.undo()
    monkeypatch.setattr(executables, "source_digest", lambda: "0" * 64)
    assert _key(ExecutableStore(str(tmp_path), jax.devices()[:1])) != _key(one)
    # No path of the checkout or of the cache is in it.
    assert str(tmp_path) not in _key(one)
    assert os.path.dirname(executables._PACKAGE_ROOT) not in _key(one)


def test_a_changed_source_builds_and_does_not_load(filled, monkeypatch):
    cache, first, _stats, _answers = filled
    monkeypatch.setattr(executables, "source_digest", lambda: "f" * 64)
    record, _stats, _answers = served(LEAN)
    rows, store = record["executables"], record["executable_store"]
    assert not any(row["loaded"] for row in rows)
    assert (store["loaded"], store["built"]) == (0, len(rows))
    # The old entries are dead bytes beside the new ones.
    assert len(entries(cache)) == len(first["executables"]) + len(rows)
    monkeypatch.undo()
    again, _stats, _answers = served(LEAN)
    assert again["executable_store"]["loaded"] == len(rows)


def test_another_static_or_shape_is_built_beside_what_was_there(filled):
    cache, first, _stats, _answers = filled
    config = dataclasses.replace(LEAN, decode_block_steps=3)
    record, _stats, _answers = served(config)
    rows = record["executables"]
    loaded = {row["step"] for row in rows if row["loaded"]}
    built = {row["step"] for row in rows if not row["loaded"]}
    # The block's length is a static of the decode step alone.
    assert built == {"decode"} and "prefill" in loaded and "merge" in loaded
    assert len(entries(cache)) > len(first["executables"])


# -- failures are misses -------------------------------------------------------


class _Lines:
    def __init__(self):
        self.warned = []

    def warn(self, msg, **fields):
        self.warned.append((msg, fields))

    def info(self, msg, **fields):
        pass


@pytest.mark.parametrize("damage", ["truncated", "garbage", "empty"])
def test_a_bad_entry_is_a_counted_miss_that_is_rebuilt(filled, damage):
    cache, first, _stats, (greedy, _sampled) = filled
    victims = [p for p in entries(cache) if p.name.startswith("decode-")]
    for path in victims:
        raw = path.read_bytes()
        path.write_bytes({"truncated": raw[: len(raw) // 2],
                          "garbage": os.urandom(4096), "empty": b""}[damage])
    logger = _Lines()
    record, _stats, (tokens,) = served(LEAN, GREEDY, logger=logger)
    store = record["executable_store"]
    rows = record["executables"]
    met = sum(row["step"] == "decode" for row in rows)
    assert 0 < met < len(victims)       # the sampled blocks were not read
    assert store["unreadable"] == store["built"] == met
    assert store["loaded"] == len(rows) - met
    assert [row["loaded"] for row in rows] == \
        [row["step"] != "decode" for row in rows]
    assert tokens == greedy
    said = [fields for msg, fields in logger.warned
            if msg == "executable store entry unreadable"]
    assert len(said) == 1 and said[0]["path"] in map(str, victims)
    # Rewritten whole: the next start loads them all.
    again, _stats, _answers = served(LEAN)
    assert again["executable_store"]["loaded"] == len(rows)
    assert again["executable_store"]["unreadable"] == 0


def _toy_step(store, name="toy"):
    jitted = jax.jit(lambda x, scale, *, power: (x * scale) ** power,
                     static_argnames=("power",))
    return StoredStep(store, name, jitted, ("power",))


def _warm(step, *args, **kwargs):
    key, found = step.load(args, kwargs, False)
    if found is None:
        step.keep(key, step.lower(*args, **kwargs).compile(), first_hand=True)
    return found is not None


def test_a_refused_or_unwarmed_call_falls_back_and_is_counted(tmp_path):
    logger = _Lines()
    store = ExecutableStore(str(tmp_path), jax.devices()[:1], logger)
    step = _toy_step(store)
    x = np.arange(6, dtype=np.float32)
    assert not _warm(step, x, np.float32(2.0), power=2)
    np.testing.assert_allclose(step(x, np.float32(2.0), power=2), (x * 2) ** 2)
    assert store.counts()["fallback_calls"] == 0
    assert step._cache_size() == 0        # the jitted function never ran
    # Same statics, same shapes, another dtype: the executable refuses,
    # the jitted step answers, and the next such call goes straight there.
    ints = np.arange(6, dtype=np.int32)
    for calls in (1, 2):
        np.testing.assert_allclose(
            step(ints, np.float32(2.0), power=2), (ints * 2.0) ** 2)
        assert store.counts()["fallback_calls"] == calls
    # Another static, another shape: never warmed.
    np.testing.assert_allclose(step(x, np.float32(2.0), power=3), (x * 2) ** 3)
    np.testing.assert_allclose(
        step(x[:3], np.float32(1.0), power=2), x[:3] ** 2)
    assert store.counts()["fallback_calls"] == 4
    said = [fields for msg, fields in logger.warned
            if msg == "call served by the jitted step, not a stored "
            "executable"]
    assert len(said) == 1 and said[0]["step"] == "toy"
    assert "TypeError" in said[0]["reason"]
    # A second process's view: the entry loads, and serves.
    other = _toy_step(ExecutableStore(str(tmp_path), jax.devices()[:1]))
    assert _warm(other, x, np.float32(2.0), power=2)
    np.testing.assert_allclose(other(x, np.float32(2.0), power=2), (x * 2) ** 2)


def test_an_unwarmed_variant_is_served_by_the_jitted_step(cache_dir):
    record, stats, (tokens,) = served(LEAN, SAMPLED)
    assert len(tokens) == SAMPLED["max_new_tokens"]
    assert record["executable_store"]["fallback_calls"] == 0
    assert stats["startup"]["executable_store"]["fallback_calls"] > 0
    assert stats["compiles"]["by_phase"].get("prefill", 0) > 0


def test_an_executable_nobody_compiled_here_is_kept_only_if_it_survives(
        tmp_path):
    """XLA:CPU drops the compiled functions of an executable it LOADED
    when it serializes it again: such a step is not written there."""
    logger = _Lines()
    store = ExecutableStore(str(tmp_path), jax.devices()[:1], logger)
    step = _toy_step(store)
    x = np.arange(6, dtype=np.float32)
    args, kwargs = (x, np.float32(2.0)), {"power": 2}
    key, found = step.load(args, kwargs, False)
    assert found is None
    step.keep(key, step.lower(*args, **kwargs).compile(), first_hand=False)
    assert store._reserializes() is (jax.default_backend() != "cpu")
    assert os.path.exists(key[0]) is store._reserializes()
    assert store.counts()["built"] == 1
    assert (store.counts()["bytes"] > 0) is store._reserializes()
    np.testing.assert_allclose(step(*args, **kwargs), (x * 2) ** 2)
    if not store._reserializes():
        assert [msg for msg, _ in logger.warned] == \
            ["executable store entry not written"]


_ONE_START = """
import json, sys
sys.path[:0] = [{root!r}, {tests!r}]
import conftest                         # the suite's platform and devices
import test_executable_store as t
with t.placed({cache!r}):
    record, _stats, (tokens,) = t.served(t.LEAN, t.GREEDY)
print(json.dumps([record["executable_store"], len(record["executables"]),
                  tokens]))
"""


def test_two_starts_racing_on_an_empty_directory_leave_whole_entries(
        cache_dir):
    """Two replicas, a process each, start together on nothing: whoever
    compiles an executable writes it whole (the other may find it in
    JAX's cache meanwhile); a third start loads every one."""
    tests = os.path.dirname(os.path.abspath(__file__))
    code = _ONE_START.format(
        root=os.path.dirname(tests), tests=tests, cache=str(cache_dir))
    racers = [
        subprocess.Popen([sys.executable, "-c", code], text=True,
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for _ in range(2)
    ]
    outs = [racer.communicate(timeout=600) for racer in racers]
    for racer, (_out, err) in zip(racers, outs):
        assert racer.returncode == 0, err[-2000:]
    (one, rows, tokens_one), (two, _rows, tokens_two) = (
        json.loads(out.strip().splitlines()[-1]) for out, _err in outs)
    assert tokens_one == tokens_two
    assert one["built"] + one["loaded"] == rows == two["built"] + two["loaded"]
    assert one["unreadable"] == two["unreadable"] == 0
    names = [p.name for p in entries(cache_dir)]
    assert len(names) == rows and not [n for n in names if n.endswith(".tmp")]
    third, _stats, (tokens,) = served(LEAN, GREEDY)
    assert third["executable_store"]["loaded"] == rows
    assert third["executable_store"]["unreadable"] == 0
    assert tokens == tokens_one


def test_many_writers_of_one_entry_never_show_a_reader_half_of_it(tmp_path):
    store = ExecutableStore(str(tmp_path), jax.devices()[:1])
    step = _toy_step(store)
    x = np.arange(6, dtype=np.float32)
    args, kwargs = (x, np.float32(2.0)), {"power": 2}
    key, _found = step.load(args, kwargs, False)
    compiled = step.lower(*args, **kwargs).compile()
    stop = threading.Event()

    def write():
        while not stop.is_set():
            store.write(key[0], key[1], compiled, None, None, True)

    writers = [threading.Thread(target=write) for _ in range(3)]
    for writer in writers:
        writer.start()
    try:
        reader = ExecutableStore(str(tmp_path), jax.devices()[:1])
        for _ in range(40):
            found = reader.load(key[0], key[1], False)
            assert found is None or found[1]["key"] == key[1]
    finally:
        stop.set()
        for writer in writers:
            writer.join(60.0)
    assert reader.counts()["unreadable"] == 0
    assert reader.counts()["loaded"] > 0
    assert os.listdir(store.directory) == [os.path.basename(key[0])]


# -- no directory, no store ----------------------------------------------------


def test_the_opt_out_reads_and_writes_nothing(cache_dir, monkeypatch):
    monkeypatch.setenv("POLYKEY_COMPILE_CACHE", "0")
    assert compile_cache_dir() is None
    eng = InferenceEngine(LEAN, params=weights())
    try:
        assert eng._executables is None
        assert not isinstance(eng._jit_decode, StoredStep)
        assert type(eng._jit_decode) is type(jax.jit(lambda: 0))
        record = eng.stats()["startup"]
    finally:
        eng.shutdown()
    assert record["executable_store"] is None
    assert all(row["loaded"] is False for row in record["executables"])
    assert not (cache_dir / SUBDIRECTORY).exists()
    monkeypatch.delenv("POLYKEY_COMPILE_CACHE")
    assert compile_cache_dir() == str(cache_dir)


def test_no_cache_directory_or_no_warm_up_means_no_store(cache_dir):
    eng = InferenceEngine(dataclasses.replace(CONFIG, compile_warmup=False),
                          params=weights())
    try:
        assert eng._executables is None
        assert eng.stats()["startup"]["executable_store"] is None
    finally:
        eng.shutdown()
    assert not (cache_dir / SUBDIRECTORY).exists()
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache_dir() is None


# -- a mesh, the speculative engine, the host tier ----------------------------


@pytest.mark.parametrize("changes, steps", [
    (dict(tp=2), {"prefill", "merge", "decode", "retire"}),
    (dict(draft_model="tiny-llama", spec_gamma=2),
     {"prefill", "merge", "spec", "retire"}),
    (dict(prefix_cache=True, num_pages=28, host_kv_bytes=8 << 20,
          host_kv_resident_pages=24),
     {"prefill", "merge", "decode", "retire", "kv_gather", "kv_restore"}),
], ids=["mesh", "spec", "hostkv"])
def test_every_kind_of_engine_loads_what_it_built(cache_dir, changes, steps):
    config = dataclasses.replace(LEAN, **changes)
    built, built_stats, (first,) = served(config, GREEDY)
    rows = built["executables"]
    assert {row["step"] for row in rows} == steps
    assert built["executable_store"]["built"] == len(rows)
    loaded, loaded_stats, (second,) = served(config, GREEDY)
    assert all(row["loaded"] for row in loaded["executables"])
    assert loaded["executable_store"]["loaded"] == len(rows)
    assert loaded_stats["startup"]["executable_store"]["fallback_calls"] == 0
    assert second == first
    # What warm-up read off the steps it built, the loaded start says too.
    for name in ("warmup_mosaic_calls", "warmup_collectives"):
        assert loaded_stats[name] == built_stats[name]
    assert set(built_stats["warmup_mosaic_calls"]) == {"prefill", "decode"}
    if "tp" in changes:
        assert built_stats["warmup_collectives"]["decode"] > 0
        # One chip's entries are no use to two.
        other, _stats, _answers = served(LEAN)
        assert other["executable_store"]["loaded"] == 0


def test_entries_hold_what_they_say(filled):
    cache, record, _stats, _answers = filled
    names = {p.name.split("-")[0] for p in entries(cache)}
    assert names == {row["step"] for row in record["executables"]}
    entry = pickle.loads(entries(cache)[0].read_bytes())
    assert set(entry) == {"format", "key", "executable", "in_tree",
                          "out_tree", "kernels", "collectives"}
    assert entry["format"] == executables.FORMAT
    assert f"sources {source_digest()}" in entry["key"]
    shutil.rmtree(cache / SUBDIRECTORY)
    again, _stats, _answers = served(LEAN)
    assert again["executable_store"]["loaded"] == 0
