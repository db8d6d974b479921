"""Disaggregated prefill/decode tiers (ISSUE 13; engine/worker.py +
engine/disagg_pool.py), on CPU with in-process worker servers over real
localhost sockets (``exit_mode="simulate"`` makes worker-exit sever the
control plane instead of the test process — indistinguishable from
death to the coordinator).

Pinned contracts:
- greedy streams through the pool are BIT-identical to a single-process
  engine (same params/seed) — the acceptance criterion;
- worker death at any phase (mid-handoff, mid-decode) re-routes with
  zero lost tokens and the delivered prefix suppressed;
- a decode-side death re-ships the RETAINED blob without re-running
  prefill (the two-phase hand-over's payoff);
- a corrupt/truncated blob re-routes cleanly, never corrupting a pool;
- session-sticky prefill routing and the NetKV decode scoring are
  deterministic;
- POLYKEY_DISAGG unset builds no pool (config guards);
- the exposition renders tier-labeled engine families + the handoff
  families.
"""

import threading
import time

import numpy as np
import pytest

from polykey_tpu import faults
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.disagg_pool import DECODE, PREFILL, DisaggPool
from polykey_tpu.engine.replica_pool import DEAD, SERVING
from polykey_tpu.engine.worker import WorkerServer, session_key


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _config(**overrides) -> EngineConfig:
    base = dict(
        model="tiny-llama", dtype="float32", max_decode_slots=4,
        page_size=8, num_pages=128, max_seq_len=64,
        prefill_buckets=(16, 32), decode_block_steps=2,
        adaptive_block=False, max_new_tokens_cap=12,
        default_max_new_tokens=12, supervise=False,
        disagg_heartbeat_s=0.1, disagg_recovery_wait_s=10.0,
    )
    base.update(overrides)
    return EngineConfig(**base)


def _run(sub, prompt: str, n: int = 10, **kw):
    """Submit + drain one request; returns (tokens, error, request)."""
    request = GenRequest(prompt=prompt, max_new_tokens=n, **kw)
    sub.submit(request)
    tokens = []
    while True:
        kind, value = request.out.get(timeout=60)
        if kind == "token":
            tokens.append(value)
        elif kind == "done":
            return tokens, None, request
        else:
            return tokens, value, request


def _worker(cfg, tier, replica=0, seed=7, **kw) -> WorkerServer:
    return WorkerServer(cfg, tier=tier, replica=replica, seed=seed,
                        exit_mode="simulate", **kw).start()


def _pool(cfg, workers, **kw) -> DisaggPool:
    return DisaggPool.create(
        cfg,
        workers=[(w.tier, ("127.0.0.1", w.port)) for w in workers],
        **kw,
    )


class _Stack:
    """One prefill + N decode workers + pool + a reference engine, torn
    down together."""

    def __init__(self, cfg, decode_workers=1, prefill_workers=1, **pool_kw):
        self.cfg = cfg
        self.workers = []
        for i in range(prefill_workers):
            self.workers.append(_worker(cfg, PREFILL, replica=i))
        for i in range(decode_workers):
            self.workers.append(_worker(cfg, DECODE, replica=i))
        self.pool = _pool(cfg, self.workers, **pool_kw)

    def close(self):
        self.pool.shutdown()
        for worker in self.workers:
            worker.stop()


@pytest.fixture()
def stacks():
    opened = []

    def make(cfg=None, **kw) -> _Stack:
        stack = _Stack(cfg or _config(), **kw)
        opened.append(stack)
        return stack

    yield make
    for stack in opened:
        stack.close()


@pytest.fixture(scope="module")
def reference_tokens():
    """Greedy token streams from a single-process engine at the shared
    fixture config/seed — the bit-identity baseline."""
    engine = InferenceEngine(_config(), seed=7)
    streams = {}
    for prompt in ("hello disagg world", "kill test prompt",
                   "sampled stream prompt"):
        toks, err, _ = _run(engine, prompt)
        assert err is None
        streams[prompt] = toks
    sampled, err, _ = _run(engine, "sampled stream prompt",
                           temperature=0.9, seed=1234)
    assert err is None
    streams["__sampled__"] = sampled
    engine.shutdown()
    return streams


# -- end-to-end identity ------------------------------------------------------


def test_greedy_stream_bit_identical_to_single_process(
        stacks, reference_tokens):
    stack = stacks()
    toks, err, req = _run(stack.pool, "hello disagg world")
    assert err is None
    assert toks == reference_tokens["hello disagg world"]
    # Routing breadcrumbs for the gateway trailers.
    assert req.replica == 0
    assert req.tier == "prefill=0,decode=0"
    stats = stack.pool.stats()
    assert stats["handoffs"]["ok"] == 1
    assert stats["handoff_bytes"] > 0
    assert stats["tiers"][PREFILL]["serving"] == 1
    assert stats["tiers"][DECODE]["serving"] == 1


def test_sampled_stream_identical_with_seed(stacks, reference_tokens):
    # Position-keyed draws + the same seed ⇒ the handed-off decode
    # replays the exact sampled stream a single process produces.
    stack = stacks()
    toks, err, _ = _run(stack.pool, "sampled stream prompt",
                        temperature=0.9, seed=1234)
    assert err is None
    assert toks == reference_tokens["__sampled__"]


def test_int8_kv_handoff_bit_identical():
    cfg = _config(kv_dtype="int8")
    engine = InferenceEngine(cfg, seed=7)
    ref, err, _ = _run(engine, "int8 handoff prompt")
    engine.shutdown()
    assert err is None
    stack = _Stack(cfg)
    try:
        toks, err, _ = _run(stack.pool, "int8 handoff prompt")
        assert err is None
        assert toks == ref
    finally:
        stack.close()


def test_concurrent_burst_all_complete(stacks):
    stack = stacks(decode_workers=2)
    results = []

    def one(i):
        results.append(_run(stack.pool, f"burst prompt {i}", 6))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(results) == 10
    assert all(err is None and len(toks) == 6 for toks, err, _ in results)


# -- crash safety -------------------------------------------------------------


def test_decode_worker_death_mid_stream_resumes_bit_identical(
        stacks, reference_tokens):
    stack = stacks(decode_workers=2)
    faults.install("worker-exit=3@1:tier=decode:replica=0")
    toks, err, req = _run(stack.pool, "kill test prompt")
    assert err is None
    assert toks == reference_tokens["kill test prompt"]
    assert req.restarted is True
    stats = stack.pool.stats()
    assert stats["streams_resumed"] == 1
    assert stats["handoffs"]["retried"] == 1
    assert stats["tier_states"]["decode/0"] == DEAD    # no restart path
    assert stats["tier_states"]["decode/1"] == SERVING


def test_decode_death_reships_retained_blob_without_reprefill(stacks):
    """The two-phase hand-over's payoff: after a decode-side death the
    coordinator re-ships the blob it already fetched — the prefill tier
    admits exactly ONE request for the stream."""
    stack = stacks(decode_workers=2)
    prefill_worker = stack.workers[0]
    faults.install("worker-exit=2@1:tier=decode:replica=0")
    toks, err, _ = _run(stack.pool, "reship prompt")
    assert err is None and len(toks) == 10
    assert prefill_worker.engine.stats()["requests_admitted"] == 1


def test_prefill_worker_death_mid_handoff_reroutes(
        stacks, reference_tokens):
    stack = stacks(prefill_workers=2)
    # Value 1 selects the FETCH site: prefill completed, blob retained,
    # the worker dies mid-handoff — the blob never ships.
    faults.install("worker-exit=1@1:tier=prefill")
    toks, err, _ = _run(stack.pool, "kill test prompt")
    assert err is None
    assert toks == reference_tokens["kill test prompt"]
    states = stack.pool.stats()["tier_states"]
    assert sorted(
        states[f"{PREFILL}/{i}"] for i in range(2)
    ) == [DEAD, SERVING]


def test_prefill_worker_death_at_intake_reroutes(
        stacks, reference_tokens):
    stack = stacks(prefill_workers=2)
    # Value 0 selects the intake site: death while the request is
    # queued, before any prefill work.
    faults.install("worker-exit=0@1:tier=prefill")
    toks, err, _ = _run(stack.pool, "kill test prompt")
    assert err is None
    assert toks == reference_tokens["kill test prompt"]


def test_corrupt_handoff_blob_reroutes_cleanly(stacks, reference_tokens):
    # kv-handoff-drop truncates the shipped blob to half (a partial
    # write); validation catches it and the prefill re-runs — the
    # worker itself stays SERVING (a torn transfer is a link event).
    stack = stacks()
    faults.install("kv-handoff-drop=1@1:tier=prefill")
    toks, err, _ = _run(stack.pool, "kill test prompt")
    assert err is None
    assert toks == reference_tokens["kill test prompt"]
    stats = stack.pool.stats()
    assert stats["handoffs"]["retried"] == 1
    assert stats["tier_states"]["prefill/0"] == SERVING


def test_handoff_delay_fault_slows_but_completes(stacks):
    stack = stacks()
    faults.install("handoff-delay=0.3@1:tier=prefill")
    t0 = time.monotonic()
    toks, err, _ = _run(stack.pool, "slow handoff prompt", 4)
    assert err is None and len(toks) == 4
    assert time.monotonic() - t0 >= 0.3


def test_reroute_budget_bounds_failures(stacks):
    # Every decode attempt dies instantly; the budget (max_reroutes)
    # bounds the retries and the request fails UNAVAILABLE-shaped
    # ("engine..." prefix → retryable/resumable at the gateway).
    cfg = _config(max_reroutes=1, disagg_recovery_wait_s=0.5)
    stack = stacks(cfg)
    faults.install("worker-exit=0:tier=decode")     # unlimited budget
    toks, err, _ = _run(stack.pool, "doomed prompt")
    assert err is not None and err.startswith("engine")
    stats = stack.pool.stats()
    assert stats["handoffs"]["aborted"] == 1


def test_worker_restart_via_cb_rejoins_serving(stacks):
    """Supervised rejoin: the heartbeat detects death, the restart hook
    brings a replacement up, and the tier returns to SERVING — with the
    sticky sessions pointing at the same tier slot (warm rejoin)."""
    cfg = _config()
    replacement: dict = {}

    def restart_cb(worker):
        server = _worker(cfg, worker.tier, replica=worker.index)
        replacement["server"] = server
        return ("127.0.0.1", server.port)

    prefill = _worker(cfg, PREFILL)
    decode = _worker(cfg, DECODE)
    pool = DisaggPool.create(
        cfg,
        workers=[(PREFILL, ("127.0.0.1", prefill.port)),
                 (DECODE, ("127.0.0.1", decode.port))],
        restart_cb=restart_cb,
    )
    try:
        toks, err, _ = _run(pool, "restart test prompt", 4)
        assert err is None and len(toks) == 4
        decode.simulate_death()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            states = {w.name: w.state for w in pool.workers}
            if states["decode/0"] == SERVING and "server" in replacement:
                break
            time.sleep(0.05)
        assert {w.name: w.state for w in pool.workers}["decode/0"] == SERVING
        toks, err, _ = _run(pool, "restart test prompt", 4)
        assert err is None and len(toks) == 4
    finally:
        pool.shutdown()
        prefill.stop()
        replacement.get("server", decode).stop()


# -- routing ------------------------------------------------------------------


def test_session_sticky_prefill_routing(stacks):
    stack = stacks(prefill_workers=2)
    pool = stack.pool
    # Two turns of one "conversation" (shared page-aligned head) must
    # land on the same prefill worker; a different session may not.
    head = "conversation head shared across turns "
    _run(pool, head + "turn one", 4)
    ids = np.asarray(pool.tokenizer.encode(head + "turn one"), np.int32)
    key = session_key(ids, pool.config.page_size)
    first = pool._sticky[PREFILL][key]
    _run(pool, head + "turn two follows", 4)
    assert pool._sticky[PREFILL][key] == first
    admitted = [w.engine.stats()["requests_admitted"]
                for w in stack.workers if w.tier == PREFILL]
    # Both turns prefilled on one worker (the other may have 0 or
    # unrelated work, but the sticky worker holds both).
    assert max(admitted) >= 2


def test_netkv_decode_scoring_prefers_fast_low_delay_worker():
    pool = DisaggPool.__new__(DisaggPool)
    pool._lock = threading.Lock()
    pool._sticky = {PREFILL: {}, DECODE: {}}
    from polykey_tpu.engine.disagg_pool import _Worker

    slow = _Worker(tier=DECODE, index=0)
    slow.bw_ewma = 1e6                       # 1 MB/s: expensive transfer
    slow.ping = {"queue_delay_s": 0.0, "load": 0.0}
    fast = _Worker(tier=DECODE, index=1)
    fast.bw_ewma = 1e9
    fast.ping = {"queue_delay_s": 0.0, "load": 0.0}
    chosen = pool._score(DECODE, [slow, fast], "s1", payload_bytes=1 << 20)
    assert chosen is fast                    # transfer cost dominates
    # Queue delay flips the choice when transfer is equal.
    fast2 = _Worker(tier=DECODE, index=2)
    fast2.bw_ewma = 1e9
    fast2.ping = {"queue_delay_s": 2.0, "load": 0.0}
    chosen = pool._score(DECODE, [fast2, fast], "s2", payload_bytes=1024)
    assert chosen is fast
    # Deterministic tie-break: lowest index.
    twin = _Worker(tier=DECODE, index=3)
    twin.bw_ewma = 1e9
    twin.ping = {"queue_delay_s": 0.0, "load": 0.0}
    chosen = pool._score(DECODE, [twin, fast], "s3", payload_bytes=0)
    assert chosen is fast                    # index 1 < index 3


# -- config guards ------------------------------------------------------------


def test_disagg_spec_parsing():
    assert EngineConfig(disagg="2x3").disagg_tiers() == (2, 3)
    assert EngineConfig(
        disagg="decode=4,prefill=1"
    ).disagg_tiers() == (1, 4)
    assert EngineConfig().disagg_tiers() is None
    with pytest.raises(ValueError, match="malformed POLYKEY_DISAGG"):
        EngineConfig(disagg="2x").validate()
    with pytest.raises(ValueError, match="malformed POLYKEY_DISAGG"):
        EngineConfig(disagg="prefill=2").validate()
    with pytest.raises(ValueError, match=">= 1 worker"):
        EngineConfig(disagg="0x2").validate()


def test_disagg_excludes_replicas_and_draft():
    with pytest.raises(ValueError, match="mutually exclusive"):
        EngineConfig(disagg="1x1", replicas=2).validate()
    with pytest.raises(ValueError, match="speculative"):
        EngineConfig(disagg="1x1", draft_model="tiny-llama").validate()


def test_unset_disagg_builds_no_pool(monkeypatch):
    # POLYKEY_DISAGG unset → from_env carries "" and the service
    # builder's disagg branch is unreachable (single-process paths
    # byte-identical — the chaos/pool suites pin behavior).
    monkeypatch.delenv("POLYKEY_DISAGG", raising=False)
    assert EngineConfig.from_env().disagg == ""


# -- gateway + observability --------------------------------------------------


def test_tpu_service_passthrough_and_trailers(stacks):
    from polykey_tpu.gateway import errors
    from polykey_tpu.gateway.tpu_service import TpuService

    stack = stacks()
    service = TpuService.create(stack.pool)
    assert service.watchdog is None          # pool supervises itself
    assert service.supervisor is None
    response = service.execute_tool(
        "llm_generate",
        _params({"prompt": "gateway disagg prompt", "max_tokens": 4}),
        None, None,
    )
    # Random-init ids may detokenize to empty text on the hermetic byte
    # tokenizer; the RPC outcome + routing trailers are the contract.
    assert response.status.code == 200
    trailers = dict(errors.pop_rpc_trailers())
    assert trailers[errors.REPLICA_KEY] == "0"
    assert trailers[errors.TIER_KEY] == "prefill=0,decode=0"


def _params(values: dict):
    from google.protobuf import struct_pb2

    params = struct_pb2.Struct()
    params.update(values)
    return params


def test_exposition_renders_tier_labels_and_handoff_families(stacks):
    from polykey_tpu.obs import engine_collector

    stack = stacks()
    _run(stack.pool, "exposition prompt", 4)
    page = "\n".join(engine_collector(stack.pool)())
    # render_sample sorts label names alphabetically.
    assert 'polykey_requests_completed_total{replica="0",tier="prefill"}' \
        in page
    assert 'polykey_requests_completed_total{replica="0",tier="decode"}' \
        in page
    assert ('polykey_replica_state{replica="0",state="SERVING",'
            'tier="decode"} 1') in page
    assert 'polykey_replicas_serving{tier="prefill"} 1' in page
    assert 'polykey_handoffs_total{outcome="ok"} 1' in page
    assert "polykey_handoff_bytes_total" in page
    assert 'polykey_handoff_ms_bucket{le="+Inf"} 1' in page
    assert 'polykey_ttft_ms_count{replica="0",tier="decode"}' in page


def test_timeline_records_handoff_lifecycle(stacks):
    from polykey_tpu.obs.timeline import engine_timelines, to_perfetto

    stack = stacks()
    _run(stack.pool, "timeline prompt", 4)
    kinds = [e.get("note_kind") for e in stack.pool.timeline.events()
             if e["kind"] == "note"]
    assert "handoff_start" in kinds
    assert "handoff_ack" in kinds
    trace = to_perfetto(engine_timelines(stack.pool))
    names = {e.get("name") for e in trace["traceEvents"]}
    assert "handoff_start" in names and "handoff_ack" in names
    # Abort events appear on failure.
    faults.install("kv-handoff-drop=1@1:tier=prefill")
    _run(stack.pool, "timeline prompt two", 4)
    kinds = [e.get("note_kind") for e in stack.pool.timeline.events()
             if e["kind"] == "note"]
    assert "handoff_abort" in kinds


def test_stats_aggregates_additive_counters(stacks):
    stack = stacks()
    _run(stack.pool, "stats prompt", 4)
    stats = stack.pool.stats()
    per = {f"{s['tier']}/{s['replica']}": s for s in stats["per_worker"]}
    assert stats["requests_completed"] == (
        per["prefill/0"]["requests_completed"]
        + per["decode/0"]["requests_completed"]
    )
    assert stats["workers_total"] == 2
    assert stats["handoff_ms_p50"] >= 0


def test_flightwatch_renders_tier_column(stacks):
    """The operator console's REPLICAS section derives rows from the
    replica_state gauge, so a disagg pool's tier-labeled workers render
    with their tier — no /debug/slo needed in the coordinator."""
    import importlib.util
    import os as _os

    from polykey_tpu.obs import engine_collector

    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "flightwatch", _os.path.join(repo, "scripts", "flightwatch.py")
    )
    flightwatch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flightwatch)

    stack = stacks()
    _run(stack.pool, "flightwatch prompt", 4)
    page = "\n".join(engine_collector(stack.pool)())
    families = flightwatch.parse_metrics(page)
    frame = flightwatch.render(families, None, "12:00:00Z", "test:0")
    assert "REPLICAS" in frame and "tier" in frame
    assert "prefill" in frame and "decode" in frame
    assert "SERVING" in frame


def test_worker_shed_is_flow_control_not_failover(stacks):
    """A worker-side shed (bounded engine queue) retries after the
    worker's retry-after hint WITHOUT burning the re-route budget or
    counting as a failover — the review-pinned contract that a briefly
    saturated tier must not fail RPCs with 'handoff failed after N
    re-routes (shed)'."""
    cfg = _config(max_queue_depth=1, max_reroutes=1)
    stack = stacks(cfg)
    results = []

    def one(i):
        results.append(_run(stack.pool, f"shed probe {i}", 4))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(5)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(results) == 5
    assert all(err is None and len(toks) == 4 for toks, err, _ in results)
    stats = stack.pool.stats()
    # Sheds (if any fired under this burst) never register as failovers.
    assert stats["requests_rerouted"] == 0
    assert stats["handoffs"]["retried"] == 0
    assert stats["handoffs"]["aborted"] == 0
