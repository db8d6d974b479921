"""Host-memory KV tier (ISSUE 15): two-tier paging under the block
allocator — cold-page offload to host RAM, page-aware restore
scheduling, and the restart-durable prefix cache.

The acceptance bar mirrors the prefix-cache suite's: sharing pages
across tiers must be invisible to the math (greedy streams identical
with the tier forced on vs off, fp32 AND int8-KV), lifetime must
balance (host pool + allocator + cache account for every page under
cap/LRU pressure), the durable store must survive a restart with warm
TTFT (and reject corrupt state files cleanly), and a lane whose pages
are resident must never wait on one whose pages are in flight."""

import dataclasses
import os
import queue
import time

import numpy as np
import pytest

from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.kv_cache import AllocationError, BlockAllocator, HostKVPool
from polykey_tpu.engine.prefix_cache import (
    TIER_DEVICE,
    TIER_HOST,
    PrefixCache,
    PrefixStateStore,
)
from polykey_tpu.models.config import get_config

# Tight device pool (23 usable pages at 8-token pages, 64-token seqs)
# so a handful of cached sessions oversubscribes it and spills; the
# resident floor makes retirements spill aggressively.
CFG = EngineConfig(
    model="tiny-llama",
    tokenizer="byte",
    dtype="float32",
    max_decode_slots=4,
    page_size=8,
    num_pages=24,
    max_seq_len=64,
    prefill_buckets=(16, 32),
    prefill_chunk=16,
    max_new_tokens_cap=16,
    prefix_cache=True,
    host_kv_bytes=64 << 20,
    host_kv_resident_pages=12,
)

# All-device reference: same math, pool big enough that nothing spills.
REF_CFG = dataclasses.replace(
    CFG, num_pages=128, host_kv_bytes=0, host_kv_resident_pages=0,
)

# Sticky sessions whose aggregate KV exceeds the tiny pool; revisits
# fault spilled prefixes back in.
SESSION_PROMPTS = [
    f"session {s} header padded out to be long enough xx" for s in range(4)
]
STICKY_MIX = SESSION_PROMPTS + [
    SESSION_PROMPTS[0], SESSION_PROMPTS[2],
    SESSION_PROMPTS[1], SESSION_PROMPTS[3],
]


def _collect(request, timeout=120.0):
    tokens, done, error = [], None, None
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            kind, value = request.out.get(timeout=deadline - time.monotonic())
        except queue.Empty:
            break
        if kind == "token":
            tokens.append(value)
        elif kind == "done":
            done = value
            break
        else:
            error = value
            break
    return tokens, done, error


def _serve(config, prompts, max_new=8, engine=None):
    eng = engine or InferenceEngine(config)
    outs = []
    try:
        for p in prompts:            # sequential: later prompts see cache
            r = GenRequest(prompt=p, max_new_tokens=max_new)
            eng.submit(r)
            tokens, done, error = _collect(r)
            assert error is None, error
            assert done is not None
            outs.append(tokens)
        return outs, eng.stats()
    finally:
        if engine is None:
            eng.shutdown()


# --- unit tier: pool, cache tiers, durable store --------------------------


def test_host_pool_alloc_release_balance():
    cfg = get_config("tiny-llama")
    pool = HostKVPool(cfg, capacity_pages=4, page_size=8,
                      dtype=np.float32, quantized=False)
    pages = [pool.alloc() for _ in range(4)]
    assert pool.used == 4 and pool.num_free == 0
    with pytest.raises(AllocationError):
        pool.alloc()
    for p in pages:
        pool.release(p)
    assert pool.used == 0 and pool.num_free == 4


def test_cache_tier_moves_and_probe_weighting():
    cfg = get_config("tiny-llama")
    alloc = BlockAllocator(32)
    host = HostKVPool(cfg, capacity_pages=8, page_size=4,
                      dtype=np.float32, quantized=False)
    cache = PrefixCache(alloc, page_size=4, capacity_pages=16,
                        host_pool=host)
    ids = np.arange(13, dtype=np.int32)          # 3 full pages
    pages = alloc.alloc(4)
    cache.insert(ids, pages)
    alloc.release_all(pages)                     # slot done; cache holds
    assert cache.device_entries() == 3
    assert cache.probe_tiered(ids) == (12, 0)

    # Spill the LRU page to host: probe stays warm but tier-split.
    (key, page), = cache.spill_candidates(1)
    hp = host.alloc()
    cache.mark_host(key, hp)
    assert cache.device_entries() == 2 and cache.host_entries() == 1
    # The spilled page was the chain HEAD (LRU == oldest == page 0 of
    # the prefix), so device matching stops there and host picks up.
    assert cache.probe_tiered(ids) == (0, 4) or \
        cache.probe_tiered(ids)[1] == 4

    # lookup_chain reports the host hit as a fault at its position.
    chain, faults = cache.lookup_chain(ids)
    assert len(chain) == 3 and len(faults) == 1
    assert chain[faults[0]][1] == TIER_HOST
    cache.release_chain(chain)

    # detach → reinsert (the engine's fault cycle), page accounting even.
    hp2 = cache.detach_host(key)
    assert hp2 == hp and cache.host_entries() == 0
    new_page = alloc.alloc(1)[0]
    host.release(hp2)
    assert cache.reinsert_device(key, new_page)
    alloc.release(new_page)                      # slot's own ref drops
    assert cache.device_entries() == 3
    chain, faults = cache.lookup_chain(ids)
    assert not faults and [t for _, t, _ in chain] == [TIER_DEVICE] * 3
    cache.release_chain(chain)


def test_cache_host_lru_pressure_drops_oldest():
    cfg = get_config("tiny-llama")
    alloc = BlockAllocator(64)
    host = HostKVPool(cfg, capacity_pages=2, page_size=4,
                      dtype=np.float32, quantized=False)
    cache = PrefixCache(alloc, page_size=4, capacity_pages=32,
                        host_pool=host)
    keys = []
    for seed in range(4):
        ids = np.full((5,), seed, dtype=np.int32)
        pages = alloc.alloc(1)
        cache.insert(ids, pages)
        alloc.release_all(pages)
    for key, _page in cache.spill_candidates(4):
        try:
            hp = host.alloc()
        except AllocationError:
            assert cache.pop_lru_host() is not None
            hp = host.alloc()
        cache.mark_host(key, hp)
        keys.append(key)
    # Cap 2: the two oldest host entries were LRU-dropped to admit the
    # two newest; pool exactly full, nothing leaked.
    assert cache.host_entries() == 2
    assert host.used == 2
    cache.clear()
    assert host.used == 0
    assert alloc.num_free == 63


def test_resident_floor_must_fit_device_pool():
    """A floor the pool can never satisfy would turn every retire into
    a full-cache spill — rejected at construction."""
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, host_kv_resident_pages=23).validate()
    dataclasses.replace(CFG, host_kv_resident_pages=22).validate()


def test_evict_for_never_sacrifices_host_entries():
    """Pressure eviction drops only DEVICE-tier entries: dropping a
    host entry frees no device page, so an unsatisfiable demand must
    not wipe the warm host tier for nothing."""
    cfg = get_config("tiny-llama")
    alloc = BlockAllocator(32)
    host = HostKVPool(cfg, capacity_pages=4, page_size=4,
                      dtype=np.float32, quantized=False)
    cache = PrefixCache(alloc, page_size=4, capacity_pages=32,
                        host_pool=host)
    for seed in range(3):
        ids = np.full((5,), seed, dtype=np.int32)
        pages = alloc.alloc(1)
        cache.insert(ids, pages)
        alloc.release_all(pages)
    (key, _page), = cache.spill_candidates(1)
    cache.mark_host(key, host.alloc())
    assert cache.device_entries() == 2 and cache.host_entries() == 1
    cache.evict_for(10_000)                      # unsatisfiable demand
    assert cache.device_entries() == 0
    assert cache.host_entries() == 1, "warm host tier was wiped"


def test_disagg_config_env_ships_host_kv_knobs():
    """A programmatically-configured disagg pool must spawn workers
    with the host tier ON — the spawn-time env channel carries the
    four new knobs and they round-trip through from_env."""
    from polykey_tpu.engine.disagg_pool import _config_env

    cfg = dataclasses.replace(CFG, kv_state_dir="/tmp/hostkv-env-test")
    env = _config_env(cfg)
    assert env["POLYKEY_HOST_KV_BYTES"] == str(cfg.host_kv_bytes)
    assert env["POLYKEY_KV_RESIDENT_PAGES"] == "12"
    assert env["POLYKEY_KV_RESTORE_SLOTS"] == "2"
    assert env["POLYKEY_KV_STATE_DIR"] == "/tmp/hostkv-env-test"
    saved = dict(os.environ)
    try:
        os.environ.update(env)
        rt = EngineConfig.from_env()
        assert rt.host_kv_bytes == cfg.host_kv_bytes
        assert rt.host_kv_resident_pages == cfg.host_kv_resident_pages
        assert rt.host_kv_restore_slots == cfg.host_kv_restore_slots
        assert rt.kv_state_dir == cfg.kv_state_dir
        assert rt.prefix_cache
    finally:
        os.environ.clear()
        os.environ.update(saved)


def test_state_store_roundtrip_and_params_gate(tmp_path):
    cfg = get_config("tiny-llama")
    shape = (cfg.num_layers, 2, 8, cfg.num_kv_heads, cfg.head_dim)
    k = np.random.default_rng(0).standard_normal(shape).astype(np.float32)
    v = np.random.default_rng(1).standard_normal(shape).astype(np.float32)
    keys = [b"\x01" * 16, b"\x02" * 16]
    store = PrefixStateStore(str(tmp_path), "tiny-llama", 8,
                             params_key="abc", quantized=False)
    store.save_batch(keys, k, v, None, None)

    alloc = BlockAllocator(16)
    host = HostKVPool(cfg, capacity_pages=4, page_size=8,
                      dtype=np.float32, quantized=False)
    cache = PrefixCache(alloc, page_size=8, capacity_pages=16,
                        host_pool=host)
    expect = (cfg.num_layers, 0, 8, cfg.num_kv_heads, cfg.head_dim)
    adopted = store.load_into(cache, host, expect)
    assert adopted == 2 and cache.host_entries() == 2
    # Contents round-tripped bit-exactly into the host pool.
    for i, key in enumerate(keys):
        page = cache._map[key][0]
        assert cache._map[key][1] == TIER_HOST
        assert np.array_equal(host.k[:, page], k[:, i])
        assert np.array_equal(host.v[:, page], v[:, i])

    # A store written under DIFFERENT weights must not warm this cache.
    other = PrefixStateStore(str(tmp_path), "tiny-llama", 8,
                             params_key="different", quantized=False)
    cache2 = PrefixCache(alloc, page_size=8, capacity_pages=16,
                         host_pool=host)
    assert other.load_into(cache2, host, expect) == 0


# --- engine tier: bit-identity with the tier forced on vs off -------------


def test_state_store_restart_does_not_clobber(tmp_path):
    """A supervisor restart builds a new store in the SAME process with
    its batch counter back at 0 — its writes must not overwrite the
    previous incarnation's batches (the state a SECOND crash needs)."""
    cfg = get_config("tiny-llama")
    shape = (cfg.num_layers, 1, 8, cfg.num_kv_heads, cfg.head_dim)
    k = np.ones(shape, np.float32)
    v = np.ones(shape, np.float32)
    store1 = PrefixStateStore(str(tmp_path), "tiny-llama", 8,
                              params_key="abc", quantized=False)
    store1.save_batch([b"\x01" * 16], k, v, None, None)
    store2 = PrefixStateStore(str(tmp_path), "tiny-llama", 8,
                              params_key="abc", quantized=False)
    store2.save_batch([b"\x02" * 16], 2 * k, 2 * v, None, None)
    blobs = [n for n in os.listdir(tmp_path) if n.endswith(".pkkv")]
    assert len(blobs) == 2, "second incarnation clobbered the first"

    alloc = BlockAllocator(16)
    host = HostKVPool(cfg, capacity_pages=4, page_size=8,
                      dtype=np.float32, quantized=False)
    cache = PrefixCache(alloc, page_size=8, capacity_pages=16,
                        host_pool=host)
    expect = (cfg.num_layers, 0, 8, cfg.num_kv_heads, cfg.head_dim)
    assert store1.load_into(cache, host, expect) == 2


def test_sticky_sessions_bit_identical_fp32():
    ref, _ = _serve(REF_CFG, STICKY_MIX)
    out, stats = _serve(CFG, STICKY_MIX)
    assert out == ref
    assert stats["kv_pages_evicted"] > 0, "tier never spilled"
    assert stats["kv_pages_restored"] > 0, "tier never faulted back"
    assert (stats["kv_page_faults_prefix"]
            + stats["kv_page_faults_ctx"]) > 0
    assert stats["host_kv"] is True


def test_sticky_sessions_bit_identical_int8_kv():
    cfg_q = dataclasses.replace(CFG, kv_dtype="int8")
    ref, _ = _serve(dataclasses.replace(REF_CFG, kv_dtype="int8"),
                    STICKY_MIX)
    out, stats = _serve(cfg_q, STICKY_MIX)
    assert out == ref
    assert stats["kv_pages_restored"] > 0


def test_tiny_host_pool_pressure_never_kills_engine():
    """Host tier smaller than one session's chain: admission-pressure
    spills into a FULL host pool LRU-drop other entries — never a page
    an in-flight lookup chain depends on (the chain's host pages detach
    to the request before the allocation that can trigger the spill).
    Regression: this used to KeyError in `_admit` and kill the loop."""
    from polykey_tpu.engine.kv_cache import host_kv_page_bytes

    page_b = host_kv_page_bytes(get_config("tiny-llama"), 8, np.float32)
    cfg = dataclasses.replace(CFG, host_kv_bytes=3 * page_b)
    mix = STICKY_MIX * 3                 # heavy revisits under churn
    ref, _ = _serve(REF_CFG, mix)
    out, stats = _serve(cfg, mix)
    assert out == ref
    assert stats["kv_host_capacity"] == 3


def test_spec_engine_with_host_tier_greedy_exact():
    """Speculative engines + host tier: restores refill only the TARGET
    pool (the draft's prefix KV is lost with the device pages), which
    by rejection-sampling construction costs acceptance, never
    correctness — greedy streams still equal the plain engine's."""
    spec_cfg = dataclasses.replace(CFG, draft_model="tiny-llama",
                                   spec_gamma=2)
    ref, _ = _serve(REF_CFG, STICKY_MIX)      # plain, all-device
    out, stats = _serve(spec_cfg, STICKY_MIX)
    assert out == ref
    assert stats["kv_pages_evicted"] > 0


def test_tier_disabled_allocates_nothing():
    eng = InferenceEngine(REF_CFG)
    try:
        assert eng._host_kv is None
        stats = eng.stats()
        assert stats["host_kv"] is False
        assert stats["kv_host_pages"] == 0
        assert stats["kv_host_capacity"] == 0
    finally:
        eng.shutdown()


def test_pages_balance_after_idle_with_tier():
    """Every device page is free, cache-held, or reserved after the
    engine drains — spills/restores must not leak allocator refs; host
    pages are exactly the cache's host entries."""
    eng = InferenceEngine(CFG)
    try:
        outs, _ = _serve(CFG, STICKY_MIX, engine=eng)
        assert all(len(t) >= 1 for t in outs)
        deadline = time.monotonic() + 10
        while eng.busy and time.monotonic() < deadline:
            time.sleep(0.05)
        stats = eng.stats()
        assert (
            stats["pages_free"] + stats["prefix_cache_pages"]
            == CFG.num_pages - 1
        )
        assert stats["kv_host_pages"] == stats["prefix_host_pages"]
    finally:
        eng.shutdown()


# --- page-aware scheduling: resident lanes never wait on faulting ones ----


def test_resident_lane_dispatches_while_faulting_lane_waits():
    """Submit spilled-session revisits (faulting) together with a fresh
    prompt (resident): the resident admission's activating prefill must
    land on the timeline BEFORE any fault's restore — the faulting
    lanes wait on the restore frontier, never the other way around."""
    cfg = dataclasses.replace(CFG, host_kv_restore_slots=1)
    eng = InferenceEngine(cfg)
    try:
        # Warm + spill: serve the sessions, then let retire-floor
        # eviction push their prefixes to host.
        _serve(cfg, SESSION_PROMPTS, engine=eng)
        deadline = time.monotonic() + 10
        while eng.busy and time.monotonic() < deadline:
            time.sleep(0.05)
        assert eng.stats()["prefix_host_pages"] > 0, "nothing spilled"

        requests = []
        for p in (SESSION_PROMPTS[0], SESSION_PROMPTS[1],
                  "totally fresh resident prompt here yy"):
            r = GenRequest(prompt=p, max_new_tokens=6)
            requests.append(r)
        for r in requests:
            eng.submit(r)
        for r in requests:
            _, done, error = _collect(r)
            assert error is None, error
            assert done is not None

        events = eng.timeline.events()
        restore_idx = [i for i, e in enumerate(events)
                       if e["kind"] == "note"
                       and e["note_kind"] == "kv_restore"]
        final_prefill_idx = [i for i, e in enumerate(events)
                             if e["kind"] == "prefill" and e["final"]]
        assert restore_idx, "revisits never faulted"
        # The burst's restores come after at least one activating
        # prefill that preceded them (the resident lane's): faulting
        # admissions register-and-wait, resident ones dispatch inline.
        burst_restores = [i for i in restore_idx
                          if i > final_prefill_idx[0]]
        resident_before = [i for i in final_prefill_idx
                           if i < burst_restores[0]]
        assert resident_before, (
            "no prefill dispatched ahead of the first restore — a "
            "faulting lane stalled the resident one"
        )
    finally:
        eng.shutdown()


# --- restart durability ----------------------------------------------------


def test_durable_reload_recovers_warm_streams(tmp_path):
    cfg = dataclasses.replace(CFG, kv_state_dir=str(tmp_path))
    first, _ = _serve(cfg, SESSION_PROMPTS)
    assert any(n.endswith(".pkkv") for n in os.listdir(tmp_path)), \
        "no durable spill batches were written"

    fresh = InferenceEngine(cfg)
    try:
        assert fresh._kv_reloaded_pages > 0
        second, stats = _serve(cfg, SESSION_PROMPTS, engine=fresh)
        assert second == first
        assert stats["kv_pages_restored"] > 0, \
            "reloaded pages never served a fault"
    finally:
        fresh.shutdown()


def test_corrupt_state_file_rejected_cleanly(tmp_path):
    cfg = dataclasses.replace(CFG, kv_state_dir=str(tmp_path))
    first, _ = _serve(cfg, SESSION_PROMPTS)
    blobs = sorted(n for n in os.listdir(tmp_path) if n.endswith(".pkkv"))
    assert blobs
    path = os.path.join(tmp_path, blobs[0])
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF                 # flip one payload bit
    open(path, "wb").write(bytes(data))

    fresh = InferenceEngine(cfg)                 # must not raise
    try:
        # The corrupt batch is rejected (and discarded); others load.
        assert not os.path.exists(path)
        second, _ = _serve(cfg, SESSION_PROMPTS, engine=fresh)
        assert second == first                   # correctness unharmed
    finally:
        fresh.shutdown()


def test_supervised_restart_reloads_durable_prefix(tmp_path):
    """The ROADMAP item 3 story end-to-end: a supervisor-driven restart
    rebuilds the engine from the factory, which reloads the durable
    store — the fresh engine serves the old sessions warm (faults >0)
    and bit-identically."""
    from polykey_tpu.engine.supervisor import EngineSupervisor

    cfg = dataclasses.replace(CFG, kv_state_dir=str(tmp_path))
    engine = InferenceEngine(cfg, seed=0)
    sup = EngineSupervisor(
        engine, lambda: InferenceEngine(cfg, seed=0),
        max_restarts=2, check_interval_s=0.05,
    ).start()
    try:
        first, _ = _serve(cfg, SESSION_PROMPTS, engine=sup.engine)
        old = sup.engine
        old.dead = "test: injected crash"
        deadline = time.monotonic() + 120
        while sup.engine is old:
            assert time.monotonic() < deadline, "supervisor never restarted"
            time.sleep(0.05)
        fresh = sup.engine
        assert fresh._kv_reloaded_pages > 0
        second, stats = _serve(cfg, SESSION_PROMPTS, engine=fresh)
        assert second == first
        assert stats["kv_pages_restored"] > 0
        # The restart note carries the reload evidence.
        notes = [e for e in fresh.timeline.events()
                 if e["kind"] == "note"
                 and e["note_kind"] == "engine_restart"]
        assert notes and notes[0]["attrs"]["kv_reloaded"] > 0
    finally:
        sup.stop()
        sup.engine.shutdown()


def test_worker_wires_state_dir_and_advertises_host_kv(tmp_path):
    """Disagg workers (ISSUE 13) with the tier on: the per-worker KV
    state dir derives from the worker state dir, and ping advertises
    host-tier warmth alongside warm_sessions."""
    from polykey_tpu.engine.worker import WorkerConn, WorkerServer

    cfg = dataclasses.replace(CFG, supervise=False)
    server = WorkerServer(
        cfg, tier="prefill", replica=0, exit_mode="simulate",
        state_dir=str(tmp_path),
    ).start()
    try:
        assert server.engine.config.kv_state_dir.endswith("kv-prefill-0")
        assert server.engine._kv_state is not None
        with WorkerConn(("127.0.0.1", server.port)) as conn:
            reply, _ = conn.request({"op": "ping"})
        assert reply["ok"]
        assert "kv_host_pages" in reply
        assert "kv_reloaded_pages" in reply
    finally:
        server.stop()

    # An EXPLICIT kv_state_dir is still worker-scoped: a shared dir
    # would let each worker's durable gc delete the others' batches.
    explicit = dataclasses.replace(
        cfg, kv_state_dir=str(tmp_path / "shared")
    )
    server2 = WorkerServer(
        explicit, tier="decode", replica=1, exit_mode="simulate",
    ).start()
    try:
        assert server2.engine.config.kv_state_dir == os.path.join(
            str(tmp_path / "shared"), "kv-decode-1"
        )
    finally:
        server2.stop()


# --- warmth advertisement --------------------------------------------------


def test_prefix_warmth_is_tier_aware():
    """A spilled-but-warm prefix must probe above cold (the PR 7/13
    routers would otherwise treat the session as cold) but below an
    equally-long device-resident one."""
    eng = InferenceEngine(CFG)
    try:
        _serve(CFG, SESSION_PROMPTS, engine=eng)
        deadline = time.monotonic() + 10
        while eng.busy and time.monotonic() < deadline:
            time.sleep(0.05)
        assert eng.stats()["prefix_host_pages"] > 0
        warmths = []
        for p in SESSION_PROMPTS:
            ids = eng.tokenizer.encode(p)
            dev, host = eng._prefix.probe_tiered(
                np.asarray(ids, np.int32))
            warmth = eng.prefix_warmth(ids)
            warmths.append((dev, host, warmth, len(ids)))
        spilled = [w for w in warmths if w[1] > 0]
        assert spilled, "no probed session was host-resident"
        for dev, host, warmth, n in spilled:
            assert warmth > 0.0                       # not cold
            assert warmth < (dev + host) / n or dev + host == 0
            assert abs(warmth - (dev + 0.5 * host) / n) < 1e-9
    finally:
        eng.shutdown()
