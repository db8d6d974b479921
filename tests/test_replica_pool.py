"""Replica-tier failover tests (ISSUE 9): routing determinism, lossless
drain/re-route, bit-identical greedy mid-stream resume, per-replica
give-up with aggregate health, pool-of-1 degeneracy, fault targeting,
and the gateway's replica/restarted/resume trailer contract.

All fault timings are test-scaled (watchdog 0.3 s, check intervals
50 ms); engines compile-warm at construction so a cold XLA compile can
never read as a stall inside those windows.
"""

import dataclasses
import io
import queue
import time

import grpc
import pytest

from polykey_tpu import faults
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.replica_pool import (
    DEAD,
    DRAINING,
    SERVING,
    ReplicaPool,
)
from polykey_tpu.gateway import server as gateway_server
from polykey_tpu.gateway.health import NOT_SERVING, SERVING as H_SERVING, HealthService
from polykey_tpu.gateway.jsonlog import Logger
from polykey_tpu.gateway.tpu_service import TpuService
from polykey_tpu.obs import Observability
from polykey_tpu.proto import polykey_v2_pb2 as pk
from polykey_tpu.proto.polykey_v2_grpc import PolykeyServiceStub

POOL_CONFIG = EngineConfig(
    model="tiny-llama",
    tokenizer="byte",
    dtype="float32",
    max_decode_slots=2,
    page_size=8,
    num_pages=64,
    max_seq_len=64,
    prefill_buckets=(16, 32),
    max_new_tokens_cap=32,
    default_max_new_tokens=8,
    decode_block_steps=1,          # per-token dispatch: fine-grained pacing
    adaptive_block=False,
    lookahead_blocks=1,
    # Engines pre-compile at construction so the first dispatch is never
    # a multi-second XLA compile that the test-scaled watchdog window
    # would misread as a device hang.
    compile_warmup=True,
    warm_sampled_variants=False,
    watchdog_timeout_s=0.3,
    max_queue_depth=0,             # drills queue deliberately; never shed
    replicas=2,
)


@pytest.fixture(autouse=True)
def _clean_injector(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.clear()
    yield
    faults.clear()


def _pool(config=POOL_CONFIG, **kwargs):
    kwargs.setdefault("watchdog_interval_s", 0.05)
    kwargs.setdefault("supervisor_interval_s", 0.05)
    return ReplicaPool.create(config, **kwargs)


def _drain(request: GenRequest, timeout=60.0):
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


def _await(predicate, timeout=20.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def _arm_live(pool, index: int, spec: str) -> None:
    """Arm a fault spec on a LIVE replica engine. Engines cache the
    module injector at construction (the env-var path arms before
    boot), so mid-run chaos hands the fresh injector to the target
    engine; a supervisor-restarted engine re-reads the shared one, so
    spent @N budgets stay spent across the restart."""
    pool.replicas[index].engine._faults = faults.install(spec)


# -- fault targeting grammar --------------------------------------------------


def test_fault_replica_targeting_grammar():
    injector = faults.FaultInjector("step-stall=1.5@2:replica=1,slow-step=0.01")
    # Targeted: only the matching replica consumes.
    assert injector._take("step-stall", replica=0) is None
    assert injector._take("step-stall", replica=None) is None
    assert injector._take("step-stall", replica=1) == 1.5
    assert injector._take("step-stall", replica=1) == 1.5
    assert injector._take("step-stall", replica=1) is None      # @2 spent
    # Untargeted points fire for every replica (and for None callers).
    assert injector._take("slow-step", replica=0) == 0.01
    assert injector._take("slow-step", replica=7) == 0.01
    assert injector._take("slow-step") == 0.01


def test_fault_targeting_rejects_unknown_qualifier():
    with pytest.raises(ValueError, match="qualifier"):
        faults.FaultInjector("step-stall=1.0:shard=2")


def test_same_point_targeted_at_two_replicas_coexists():
    # Two entries for ONE point must not overwrite each other: a chaos
    # spec killing two replicas has to fire on both.
    injector = faults.FaultInjector(
        "prefill-error@1:replica=0,prefill-error@1:replica=1"
    )
    assert injector._take("prefill-error", replica=0) is not None
    assert injector._take("prefill-error", replica=0) is None   # @1 spent
    assert injector._take("prefill-error", replica=1) is not None
    assert injector._take("prefill-error", replica=1) is None
    assert injector.fired("prefill-error") == 2


def test_engine_consumes_only_its_replica_faults():
    faults.install("tokenizer-error@1:replica=1")
    config = dataclasses.replace(POOL_CONFIG, replicas=1, compile_warmup=False)
    engine = InferenceEngine(config)      # replica 0
    try:
        request = GenRequest(prompt="untargeted", max_new_tokens=4)
        engine.submit(request)
        tokens, done, error = _drain(request)
        assert error is None and done is not None and tokens
        assert engine._faults.fired("tokenizer-error") == 0
    finally:
        engine.shutdown()


# -- routing -----------------------------------------------------------------


def test_routing_deterministic_tie_breaks_to_lowest_index():
    pool = _pool()
    try:
        request = GenRequest(prompt="tie", max_new_tokens=2)
        picks = [pool._route(request, set())[0].index for _ in range(5)]
        assert picks == [0] * 5
        pool.submit(request)
        assert request.replica == 0
        _drain(request)
    finally:
        pool.shutdown()


def test_routing_least_delay_and_headroom(monkeypatch):
    pool = _pool()
    try:
        monkeypatch.setattr(
            pool.replicas[0].engine, "queue_delay_estimate_s", lambda: 0.8
        )
        monkeypatch.setattr(
            pool.replicas[1].engine, "queue_delay_estimate_s", lambda: 0.0
        )
        request = GenRequest(prompt="delayed", max_new_tokens=2)
        replica, reason = pool._route(request, set())
        assert replica.index == 1 and reason == "least-delay"
        # Headroom: replica 0's estimated delay blows the deadline, so
        # the feasibility filter (not just the score) removed it.
        request = GenRequest(prompt="deadline", max_new_tokens=2,
                             deadline=time.monotonic() + 0.2)
        replica, reason = pool._route(request, set())
        assert replica.index == 1 and reason == "headroom"
    finally:
        pool.shutdown()


def test_routing_prefers_prefix_warm_replica():
    config = dataclasses.replace(POOL_CONFIG, prefix_cache=True)
    pool = _pool(config)
    try:
        # 17+ byte-tokens => at least one full page (page_size 8) of
        # cacheable page-aligned prefix after the first completion.
        prompt = "shared system prompt!"
        first = GenRequest(prompt=prompt, max_new_tokens=4)
        pool.submit(first)
        assert first.replica == 0
        _, done, error = _drain(first)
        assert error is None and done is not None
        warm = pool.replicas[0].engine.prefix_warmth(
            pool.tokenizer.encode(prompt)
        )
        assert warm > 0.0
        # Load the cold replica LESS attractive on delay to prove warmth
        # dominates the epsilon load term: same prompt routes back to 0.
        again = GenRequest(prompt=prompt, max_new_tokens=4)
        replica, reason = pool._route(again, set())
        assert replica.index == 0 and reason == "prefix-hit"
    finally:
        pool.shutdown()


# -- failover: lossless drain + bit-identical resume -------------------------


def test_drain_requeues_losslessly(monkeypatch):
    pool = _pool()
    try:
        # Pin routing to replica 0 for the setup so its slots (2) fill
        # and two more requests sit QUEUED there when it dies.
        real_route = pool._route
        monkeypatch.setattr(
            pool, "_route",
            lambda request, exclude: real_route(request, exclude | {1}),
        )
        _arm_live(pool, 0, "slow-step=0.05:replica=0,step-stall=1.0@1:replica=0")
        requests = [
            GenRequest(prompt=f"victim {i}", max_new_tokens=6)
            for i in range(4)
        ]
        for request in requests:
            pool.submit(request)
            assert request.replica == 0
        monkeypatch.setattr(pool, "_route", real_route)
        outcomes = [_drain(r) for r in requests]
        for tokens, done, error in outcomes:
            assert error is None, f"failover leaked an error: {error}"
            assert done is not None
            assert len(tokens) == 6      # token-complete despite the kill
        stats = pool.stats()
        assert stats["requests_rerouted"] >= 1
        assert all(
            r.replica == 1 for r in requests
        ), "every victim should finish on the healthy replica"
        # Replica 0 recovers (supervised restart) while nothing failed.
        assert _await(
            lambda: pool.stats()["replica_states"]["0"] == SERVING,
            timeout=30.0,
        )
        # Engine-level requests_failed counts the dead replica's failed
        # ATTEMPTS (honest per-replica accounting); the client-visible
        # outcome — zero errors, token-complete streams — is what the
        # loop above asserted, and every failed attempt is covered by a
        # reroute.
        assert stats["requests_failed"] <= stats["requests_rerouted"]
    finally:
        pool.shutdown()


def test_midstream_resume_is_bit_identical_greedy():
    pool = _pool()
    try:
        prompt = "failover determinism probe"
        baseline = GenRequest(prompt=prompt, max_new_tokens=12)
        pool.submit(baseline)
        base_tokens, base_done, base_error = _drain(baseline)
        assert base_error is None and base_done is not None
        assert len(base_tokens) == 12

        # Same prompt again; replica 0 now stalls mid-stream (slow-step
        # paces it so tokens are flowing when the stall lands).
        _arm_live(pool, 0, "slow-step=0.05:replica=0,step-stall=1.0@1:replica=0")
        victim = GenRequest(prompt=prompt, max_new_tokens=12)
        pool.submit(victim)
        assert victim.replica == 0
        tokens, done, error = _drain(victim)
        assert error is None and done is not None
        assert tokens == base_tokens, (
            "resumed greedy stream must be bit-identical to the "
            "uninterrupted run"
        )
        assert getattr(victim, "restarted", False)
        assert victim.replica == 1
        stats = pool.stats()
        assert stats["streams_resumed"] >= 1
        assert done.completion_tokens == 12
    finally:
        pool.shutdown()


# -- health aggregation -------------------------------------------------------


def test_per_replica_giveup_keeps_health_serving():
    # Restart budget 0: the first trip exhausts it and the supervisor
    # gives up — on ONE replica. Health must stay SERVING on the other.
    config = dataclasses.replace(POOL_CONFIG, max_engine_restarts=0)
    health = HealthService()
    health.set_serving_status("", H_SERVING)
    pool = _pool(config, health=health)
    try:
        _arm_live(pool, 0, "slow-step=0.05:replica=0,step-stall=1.0@1:replica=0")
        victim = GenRequest(prompt="giveup victim", max_new_tokens=8)
        pool.submit(victim)
        assert victim.replica == 0
        tokens, done, error = _drain(victim)
        # The request itself still completes (rerouted to replica 1).
        assert error is None and done is not None and len(tokens) == 8
        assert _await(
            lambda: pool.stats()["replica_states"]["0"] == DEAD, timeout=30.0
        )
        assert health._statuses.get("") == H_SERVING
        assert pool.dead is None
        assert pool.stats()["replicas_serving"] == 1
        # The pool still takes traffic on the survivor.
        after = GenRequest(prompt="after giveup", max_new_tokens=4)
        pool.submit(after)
        assert after.replica == 1
        _, done, error = _drain(after)
        assert error is None and done is not None
    finally:
        pool.shutdown()


def test_all_replicas_dead_flips_health_and_submit():
    config = dataclasses.replace(
        POOL_CONFIG, replicas=1, max_engine_restarts=0
    )
    health = HealthService()
    health.set_serving_status("", H_SERVING)
    pool = _pool(config, health=health)
    try:
        _arm_live(pool, 0, "step-stall=1.0@1:replica=0")
        victim = GenRequest(prompt="sole victim", max_new_tokens=8)
        pool.submit(victim)
        _, done, error = _drain(victim)
        # Pool of 1, no reroute target: single-engine failure semantics.
        assert done is None
        assert error is not None and error.startswith("engine")
        assert _await(lambda: pool.dead is not None, timeout=30.0)
        assert health._statuses.get("") == NOT_SERVING
        from polykey_tpu.engine.engine import EngineDeadError

        with pytest.raises(EngineDeadError):
            pool.submit(GenRequest(prompt="too late", max_new_tokens=2))
    finally:
        pool.shutdown()


def test_pool_of_one_recovers_like_single_supervisor():
    # Pool of 1 = today's supervisor semantics: fault → in-flight fails
    # UNAVAILABLE-style, health dips NOT_SERVING, restart brings both
    # back (the chaos suite pins the same story without a pool).
    config = dataclasses.replace(POOL_CONFIG, replicas=1)
    health = HealthService()
    health.set_serving_status("", H_SERVING)
    pool = _pool(config, health=health)
    try:
        _arm_live(pool, 0, "step-stall=1.0@1:replica=0")
        victim = GenRequest(prompt="restart victim", max_new_tokens=8)
        pool.submit(victim)
        _, done, error = _drain(victim)
        assert done is None and error is not None and error.startswith("engine")
        assert _await(
            lambda: pool.stats()["replica_states"]["0"] == SERVING
            and health._statuses.get("") == H_SERVING,
            timeout=30.0,
        )
        after = GenRequest(prompt="after restart", max_new_tokens=4)
        pool.submit(after)
        tokens, done, error = _drain(after)
        assert error is None and done is not None and tokens
        assert pool.stats()["engine_restarts"] == 1
    finally:
        pool.shutdown()


# -- pool stats / state machine ----------------------------------------------


def test_stats_aggregate_across_replicas():
    pool = _pool()
    try:
        requests = [
            GenRequest(prompt=f"stats {i}", max_new_tokens=4)
            for i in range(3)
        ]
        for request in requests:
            pool.submit(request)
        for request in requests:
            _, done, error = _drain(request)
            assert error is None and done is not None
        stats = pool.stats()
        assert stats["replicas_total"] == 2
        per = stats["per_replica"]
        assert len(per) == 2
        assert stats["requests_completed"] == sum(
            s["requests_completed"] for s in per
        ) == 3
        assert set(stats["replica_states"]) == {"0", "1"}
        assert per[0]["replica"] == 0 and per[1]["replica"] == 1
        assert sum(stats["router_decisions"].values()) >= 3
        # Occupancy denominator is PER-REPLICA slots: avg_lanes is
        # bounded by one replica's slot count, so dividing by the
        # pool-summed slots_total would understate a saturated pool.
        if "occupancy" in stats:
            assert stats["occupancy"] == round(
                stats["avg_lanes"] / POOL_CONFIG.max_decode_slots, 4
            )
    finally:
        pool.shutdown()


def test_draining_replica_gets_no_admissions():
    pool = _pool()
    try:
        pool._transition(0, DRAINING)
        for i in range(3):
            request = GenRequest(prompt=f"avoid drain {i}", max_new_tokens=2)
            pool.submit(request)
            assert request.replica == 1
            _drain(request)
        pool._transition(0, SERVING)
    finally:
        pool.shutdown()


# -- gateway integration: trailers + received_tokens -------------------------


def test_grpc_pool_stream_carries_replica_and_restarted_trailers():
    logger = Logger(stream=io.StringIO())
    obs = Observability()
    pool = _pool()
    service = TpuService.create(pool, logger=logger, obs=obs)
    server, _, port = gateway_server.build_server(
        service, logger, address="127.0.0.1:0", obs=obs
    )
    server.start()
    try:
        with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
            grpc.channel_ready_future(channel).result(timeout=10)
            stub = PolykeyServiceStub(channel)

            request = pk.ExecuteToolRequest(tool_name="llm_generate")
            request.parameters.update({"prompt": "trailer run", "max_tokens": 6})
            call = stub.ExecuteToolStream(request, timeout=60)
            chunks = list(call)
            assert chunks[-1].final
            trailers = dict(call.trailing_metadata() or ())
            assert trailers.get("replica") == "0"
            assert "restarted" not in trailers

            # Kill replica 0 mid-stream: the pool resumes on replica 1
            # and the SAME RPC completes, flagged restarted.
            _arm_live(
                pool, 0, "slow-step=0.05:replica=0,step-stall=1.0@1:replica=0"
            )
            request2 = pk.ExecuteToolRequest(tool_name="llm_generate")
            request2.parameters.update(
                {"prompt": "trailer run", "max_tokens": 12}
            )
            call2 = stub.ExecuteToolStream(request2, timeout=120)
            chunks2 = list(call2)
            assert chunks2[-1].final
            text2 = "".join(c.delta for c in chunks2)
            trailers2 = dict(call2.trailing_metadata() or ())
            assert trailers2.get("replica") == "1"
            assert trailers2.get("restarted") == "1"
            assert text2            # stream delivered despite the kill

            # engine_stats over gRPC shows the pool view.
            stats = dict(
                stub.ExecuteTool(
                    pk.ExecuteToolRequest(tool_name="engine_stats"),
                    timeout=30,
                ).struct_output
            )
            assert stats["replicas_total"] == 2
            assert stats["streams_resumed"] >= 1
    finally:
        server.stop(grace=None)
        service.close()


def test_failover_keeps_trace_id_and_records_resume_span():
    """Trace-id continuity across failover (ISSUE 10): a re-routed,
    resumed stream keeps its ORIGINAL x-trace-id on the new replica —
    echoed in the trailers of the same RPC — and the recorded span tree
    carries an explicit `resume` child under the root naming both
    replicas, so the failover is readable from the flight recorder."""
    logger = Logger(stream=io.StringIO())
    obs = Observability()
    pool = _pool()
    service = TpuService.create(pool, logger=logger, obs=obs)
    server, _, port = gateway_server.build_server(
        service, logger, address="127.0.0.1:0", obs=obs
    )
    server.start()
    trace_id = "failover-trace-0001"
    try:
        with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
            grpc.channel_ready_future(channel).result(timeout=10)
            stub = PolykeyServiceStub(channel)

            _arm_live(
                pool, 0, "slow-step=0.05:replica=0,step-stall=1.0@1:replica=0"
            )
            request = pk.ExecuteToolRequest(tool_name="llm_generate")
            request.parameters.update(
                {"prompt": "trace continuity run", "max_tokens": 12}
            )
            call = stub.ExecuteToolStream(
                request, timeout=120, metadata=(("x-trace-id", trace_id),)
            )
            chunks = list(call)
            assert chunks[-1].final
            trailers = dict(call.trailing_metadata() or ())
            assert trailers.get("restarted") == "1", trailers
            assert trailers.get("replica") == "1"
            # The client's trace id survived the replica move.
            assert trailers.get("x-trace-id") == trace_id

            recorded = [
                t for t in obs.recorder.traces()
                if t.get("trace_id") == trace_id
            ]
            assert recorded, "resumed stream's span tree was not recorded"
            tree = recorded[-1]
            children = {c["name"]: c for c in tree.get("children", ())}
            assert "resume" in children, sorted(children)
            resume = children["resume"]
            assert resume["trace_id"] == trace_id
            assert resume["attrs"]["from_replica"] == 0
            assert resume["attrs"]["to_replica"] == 1
            # Decode work continued under the SAME root after the move.
            assert "decode" in children
            # Attribution followed the stream across replicas: the root
            # carries accumulated device_ms spanning both attempts.
            assert tree.get("attrs", {}).get("device_ms", 0) > 0
    finally:
        server.stop(grace=None)
        service.close()


def test_received_tokens_suppresses_prefix():
    # Server-side resume contract: received_tokens=k replays the greedy
    # generation and emits only the suffix — the client-resume path
    # (client.py) depends on this being exact.
    config = dataclasses.replace(
        POOL_CONFIG, replicas=1, compile_warmup=False, supervise=False
    )
    engine = InferenceEngine(config)
    logger = Logger(stream=io.StringIO())
    service = TpuService.create(engine, logger=logger)
    try:
        params = {"prompt": "resume suffix probe", "max_tokens": 10}
        full = service.execute_tool(
            "llm_generate", _struct(params), None, None
        ).string_output
        resumed = service.execute_tool(
            "llm_generate", _struct({**params, "received_tokens": 4}),
            None, None,
        ).string_output
        assert resumed and resumed != full
        assert full.endswith(resumed)
        whole = service.execute_tool(
            "llm_generate", _struct({**params, "received_tokens": 0}),
            None, None,
        ).string_output
        assert whole == full
        with pytest.raises(ValueError):
            service.execute_tool(
                "llm_generate", _struct({**params, "received_tokens": -1}),
                None, None,
            )
    finally:
        service.close()


def test_stream_error_flushes_stop_hold_buffer():
    # With stop sequences armed, _text_events holds back up to
    # len(stop)-1 trailing chars; an engine failure must flush that
    # tail BEFORE raising, or resume-tokens would claim tokens whose
    # text the client never received — a client resume would then
    # suppress them and permanently lose the held text.
    import types as _types

    from polykey_tpu.engine.tokenizer import ByteTokenizer
    from polykey_tpu.gateway import errors as gw_errors

    tokenizer = ByteTokenizer()
    engine = _types.SimpleNamespace(
        tokenizer=tokenizer,
        config=_types.SimpleNamespace(request_timeout_s=5.0),
    )
    service = TpuService(engine)
    request = GenRequest(prompt="x")
    token_ids = tokenizer.encode("abc")
    for tid in token_ids:
        request.out.put(("token", tid))
    request.out.put(("error", "engine restarting: test"))
    deltas = []
    with pytest.raises(gw_errors.UnavailableError) as err:
        for kind, value in service._text_events(request, stops=["ZZ"]):
            if kind == "delta":
                deltas.append(value)
    assert "".join(deltas) == "abc"          # held tail flushed
    trailers = dict(err.value.trailing_metadata())
    assert trailers[gw_errors.RESUME_SUPPORTED_KEY] == "1"
    assert trailers[gw_errors.RESUME_TOKENS_KEY] == str(len(token_ids))


def _stream_service():
    import types as _types

    from polykey_tpu.engine.tokenizer import ByteTokenizer

    tokenizer = ByteTokenizer()
    engine = _types.SimpleNamespace(
        tokenizer=tokenizer,
        config=_types.SimpleNamespace(request_timeout_s=5.0),
    )
    return TpuService(engine), tokenizer


@pytest.mark.parametrize("stops", [[], ["ZZ"]], ids=["plain", "stop-armed"])
def test_stream_sends_the_queued_tokens_as_one_delta(stops):
    # A decode block hands a stream its tokens at one instant: what is
    # queued goes out as ONE delta, then the terminal event; the text is
    # the tokens' own, in order.
    service, tokenizer = _stream_service()
    request = GenRequest(prompt="x")
    for tid in tokenizer.encode("abcdefgh"):
        request.out.put(("token", tid))
    request.out.put(("done", None))
    events = list(service._queued_as_one(
        request, service._text_events(request, stops)))
    assert events == [("delta", "abcdefgh"), ("done", None)]


def test_stream_sends_a_token_at_once_when_nothing_else_is_queued():
    # Text waits for nothing: with the queue empty behind it a token is
    # its own delta (a first token; a stream decoded a token a step).
    service, tokenizer = _stream_service()
    request = GenRequest(prompt="x")
    a, b, c = tokenizer.encode("abc")[-3:]
    request.out.put(("token", a))
    stream = service._queued_as_one(request, service._text_events(request, []))
    assert next(stream) == ("delta", "a")
    request.out.put(("token", b))
    request.out.put(("token", c))
    request.out.put(("done", None))
    assert list(stream) == [("delta", "bc"), ("done", None)]


def test_stream_error_sends_the_joined_text_before_it_raises():
    # The resume trailer counts every consumed token as delivered, so the
    # text joined so far goes out before the engine's error does.
    from polykey_tpu.gateway import errors as gw_errors

    service, tokenizer = _stream_service()
    request = GenRequest(prompt="x")
    ids = tokenizer.encode("abc")
    for tid in ids:
        request.out.put(("token", tid))
    request.out.put(("error", "engine restarting: test"))
    deltas = []
    with pytest.raises(gw_errors.UnavailableError) as err:
        for kind, value in service._queued_as_one(
                request, service._text_events(request, [])):
            deltas.append(value)
    assert deltas == ["abc"]
    trailers = dict(err.value.trailing_metadata())
    assert trailers[gw_errors.RESUME_TOKENS_KEY] == str(len(ids))


def _struct(values: dict):
    from google.protobuf import struct_pb2

    s = struct_pb2.Struct()
    s.update(values)
    return s
