"""Engine tests: continuous batching, streaming, cancellation, stats, and the
full gRPC stack with the TPU service mounted (tiny model, CPU device).

This is the concurrency-stress tier SURVEY.md §4 prescribes in place of Go's
race detector: many concurrent clients hammering the batcher with assertion
checks on every response.
"""

import queue
import threading
import time

import grpc
import numpy as np
import pytest

from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.gateway import server as gateway_server
from polykey_tpu.gateway.jsonlog import Logger
from polykey_tpu.gateway.tpu_service import TpuService
from polykey_tpu.proto import polykey_v2_pb2 as pk
from polykey_tpu.proto.polykey_v2_grpc import PolykeyServiceStub

import io

TEST_CONFIG = EngineConfig(
    model="tiny-llama",
    tokenizer="byte",
    dtype="float32",
    max_decode_slots=4,
    page_size=8,
    num_pages=64,
    max_seq_len=64,
    prefill_buckets=(16, 32),
    max_new_tokens_cap=32,
    default_max_new_tokens=8,
)


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(TEST_CONFIG)
    yield eng
    eng.shutdown()


def _collect(request: GenRequest, timeout=30.0):
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


def test_single_request(engine):
    request = GenRequest(prompt="hello", max_new_tokens=5)
    engine.submit(request)
    tokens, done, error = _collect(request)
    assert error is None
    assert done is not None
    assert len(tokens) == done.completion_tokens <= 5
    assert done.prompt_tokens == len(engine.tokenizer.encode("hello"))
    assert done.ttft_ms > 0


def test_greedy_reproducible(engine):
    outs = []
    for _ in range(2):
        request = GenRequest(prompt="abc", max_new_tokens=6, temperature=0.0)
        engine.submit(request)
        tokens, done, error = _collect(request)
        assert error is None
        outs.append(tokens)
    assert outs[0] == outs[1]


def test_concurrent_requests_batched(engine):
    """More requests than slots: all must complete, slots recycled."""
    requests = [
        GenRequest(prompt=f"prompt {i}", max_new_tokens=6, temperature=0.5)
        for i in range(10)
    ]
    for request in requests:
        engine.submit(request)
    results = [_collect(request) for request in requests]
    for tokens, done, error in results:
        assert error is None
        assert done is not None
        assert len(tokens) >= 1
    # All pages back in the pool afterwards.
    assert engine.allocator.num_free == TEST_CONFIG.num_pages - 1
    assert not engine.busy


def test_batched_greedy_matches_solo(engine):
    """Continuous batching must not change greedy output: run a probe alone,
    then again while 3 other requests occupy the batch."""
    probe_prompt = "determinism probe"
    solo = GenRequest(prompt=probe_prompt, max_new_tokens=6)
    engine.submit(solo)
    solo_tokens, _, _ = _collect(solo)

    noise = [
        GenRequest(prompt=f"noise {i}", max_new_tokens=12, temperature=1.0)
        for i in range(3)
    ]
    probe = GenRequest(prompt=probe_prompt, max_new_tokens=6)
    for request in noise:
        engine.submit(request)
    engine.submit(probe)
    probe_tokens, _, probe_err = _collect(probe)
    for request in noise:
        _collect(request)
    assert probe_err is None
    assert probe_tokens == solo_tokens


def test_burst_admission_matches_solo(engine):
    """A probe admitted inside a same-bucket burst (batched prefill group)
    must produce the same greedy stream as when admitted alone."""
    probe_prompt = "burst determinism"
    solo = GenRequest(prompt=probe_prompt, max_new_tokens=6)
    engine.submit(solo)
    solo_tokens, _, _ = _collect(solo)

    burst = [GenRequest(prompt=f"burst noise {i}", max_new_tokens=6)
             for i in range(3)]
    probe = GenRequest(prompt=probe_prompt, max_new_tokens=6)
    for r in burst + [probe]:
        engine.submit(r)
    probe_tokens, _, probe_err = _collect(probe)
    for r in burst:
        _collect(r)
    assert probe_err is None
    assert probe_tokens == solo_tokens


def test_decode_block_steps_equivalence():
    """Blocked decode (K steps per dispatch, device-side EOS/budget stop)
    must be a pure batching of the K=1 step loop: identical greedy tokens,
    including for requests whose budget is not a multiple of K."""
    import dataclasses

    outs = {}
    for k in (1, 8):
        eng = InferenceEngine(
            dataclasses.replace(TEST_CONFIG, decode_block_steps=k)
        )
        try:
            reqs = [
                GenRequest(prompt=p, max_new_tokens=n)
                for p, n in (("block probe", 11), ("x", 3), ("longer one", 8))
            ]
            for r in reqs:
                eng.submit(r)
            outs[k] = [_collect(r) for r in reqs]
        finally:
            eng.shutdown()
    for (t1, d1, e1), (t8, d8, e8) in zip(outs[1], outs[8]):
        assert e1 is None and e8 is None
        assert t1 == t8
        assert d1.completion_tokens == d8.completion_tokens


def test_compile_warmup_engine_serves_identically():
    """compile_warmup pre-runs the jitted shapes against the garbage page
    in __init__; the warmed engine must serve the same greedy streams."""
    import dataclasses

    ref_eng = InferenceEngine(TEST_CONFIG)
    try:
        r = GenRequest(prompt="warmup probe", max_new_tokens=6)
        ref_eng.submit(r)
        ref, _, _ = _collect(r)
    finally:
        ref_eng.shutdown()

    warm_eng = InferenceEngine(
        dataclasses.replace(TEST_CONFIG, compile_warmup=True)
    )
    try:
        r = GenRequest(prompt="warmup probe", max_new_tokens=6)
        warm_eng.submit(r)
        out, done, error = _collect(r)
        assert error is None
        assert out == ref
    finally:
        warm_eng.shutdown()


def test_stale_block_tokens_never_reach_new_occupant():
    """Lookahead safety net: a block dispatched while request A held slot 0
    must deliver nothing once the slot belongs to request B — the
    per-block request-identity snapshot (engine._snapshot_requests) is the
    only guard on this path, since B can be active with A's block still
    unprocessed only through host-side transitions (cancel + re-admit).
    White-box: the engine loop is stopped and _process_step driven
    directly with a forged stale block."""
    import numpy as np

    from polykey_tpu.engine.engine import _Slot

    eng = InferenceEngine(TEST_CONFIG)
    eng.shutdown()  # stop the loop; we drive internals directly

    req_a = GenRequest(prompt="A")          # the evicted occupant
    req_b = GenRequest(prompt="B")          # the new occupant
    slot_b = _Slot(request=req_b, pages=[], position_cap=10)
    slot_b.generated = 1
    eng._slots[0] = slot_b
    eng._active[0] = True
    eng._seq_lens[0] = 3

    B, K = TEST_CONFIG.max_decode_slots, TEST_CONFIG.decode_block_steps
    packed = np.full((K, B), 7, dtype=np.int32)   # every lane "emitted"
    reqs = [req_a] + [None] * (B - 1)       # snapshot from A's dispatch
    eng._process_step(("plain", packed, reqs))

    assert req_b.out.empty()                # B got nothing from A's block
    assert req_a.out.empty()                # A is gone; tokens are dropped
    assert slot_b.generated == 1            # no bookkeeping drift either


def test_lookahead_depth_greedy_equality():
    """The lookahead pipeline is a scheduling change only: greedy output at
    depth 4 (and at a block size that straddles request boundaries) must
    equal depth-1 token-at-a-time output, across overlapping admissions."""
    import dataclasses

    prompts = [f"pipeline prompt {i}" for i in range(6)]

    def run(depth, block):
        cfg = dataclasses.replace(
            TEST_CONFIG, lookahead_blocks=depth, decode_block_steps=block
        )
        eng = InferenceEngine(cfg)
        try:
            reqs = [GenRequest(prompt=p, max_new_tokens=7) for p in prompts]
            for r in reqs:
                eng.submit(r)
            outs = []
            for r in reqs:
                tokens, done, error = _collect(r)
                assert error is None and done is not None
                outs.append(tokens)
            return outs
        finally:
            eng.shutdown()

    assert run(4, 3) == run(1, 1)


def test_stop_sequences(monkeypatch):
    """`stop` cuts generation BEFORE the earliest match, never emits the
    stop text (even when it spans delta boundaries — every byte-tokenizer
    delta is one char, so any multi-char stop spans), and cancels the
    engine request. Unary and streaming agree.

    Uses an ASCII-vocab model variant (vocab 96 → every generated id
    renders one byte) so greedy output is dense text; tiny-llama's 512
    vocab mostly lands outside the byte tokenizer's range."""
    import dataclasses

    from google.protobuf import struct_pb2

    from polykey_tpu.gateway.tpu_service import TpuService
    from polykey_tpu.models.config import MODEL_REGISTRY, TINY_LLAMA

    # monkeypatch (not setdefault) so the registry entry is removed on
    # teardown — registry contents must not depend on test order.
    monkeypatch.setitem(
        MODEL_REGISTRY,
        "tiny-llama-ascii",
        dataclasses.replace(TINY_LLAMA, name="tiny-llama-ascii", vocab_size=96),
    )
    eng = InferenceEngine(
        dataclasses.replace(TEST_CONFIG, model="tiny-llama-ascii")
    )
    service = TpuService(eng)
    try:
        def run(stop=None, stream=False):
            params = struct_pb2.Struct()
            d = {"prompt": "stop test prompt", "max_tokens": 24}
            if stop is not None:
                d["stop"] = stop
            params.update(d)
            if stream:
                chunks = list(
                    service.execute_tool_stream(
                        "llm_generate", params, None, None
                    )
                )
                return "".join(c.delta for c in chunks)
            return service.execute_tool(
                "llm_generate", params, None, None
            ).string_output

        full = run()
        assert len(full) >= 6, repr(full)
        stop = full[3:6]            # guaranteed mid-stream match
        cut = run(stop=stop)
        assert cut == full[: full.index(stop)]
        assert stop not in cut
        assert run(stop=stop, stream=True) == cut
        # List form; a never-matching stop leaves the output unchanged.
        assert run(stop=["@@never@@", stop]) == cut
        assert run(stop="@@never@@") == full
        # Invalid stop types are rejected.
        import pytest as _pytest

        with _pytest.raises(Exception):
            run(stop=[""])
    finally:
        eng.shutdown()


def test_seeded_sampling_batch_independent():
    """A seeded sampled request must produce an identical stream no matter
    what else is in the batch, which engine geometry serves it, or how
    scheduling interleaves — every draw is keyed by (request seed, token
    position), not by a shared RNG chain. Different seeds must diverge."""
    import dataclasses

    def run(cfg, companions):
        eng = InferenceEngine(cfg)
        try:
            target = GenRequest(prompt="seeded stream", max_new_tokens=10,
                                temperature=1.0, top_p=0.9, seed=42)
            others = [
                GenRequest(prompt=f"noise {i}", max_new_tokens=8,
                           temperature=0.7, seed=100 + i)
                for i in range(companions)
            ]
            for r in [*others[:companions // 2], target,
                      *others[companions // 2:]]:
                eng.submit(r)
            result = None
            for r in [target, *others]:
                tokens, done, error = _collect(r)
                assert error is None and done is not None
                if r is target:
                    result = tokens
            return result
        finally:
            eng.shutdown()

    alone = run(TEST_CONFIG, 0)
    crowded = run(TEST_CONFIG, 3)
    other_geometry = run(
        dataclasses.replace(
            TEST_CONFIG, max_decode_slots=2, decode_block_steps=2,
            lookahead_blocks=3,
        ),
        1,
    )
    assert alone == crowded == other_geometry
    assert len(alone) > 1

    different_seed = None
    eng = InferenceEngine(TEST_CONFIG)
    try:
        r = GenRequest(prompt="seeded stream", max_new_tokens=10,
                       temperature=1.0, top_p=0.9, seed=43)
        eng.submit(r)
        different_seed, done, error = _collect(r)
        assert error is None
    finally:
        eng.shutdown()
    assert different_seed != alone


def test_cancellation_frees_slot(engine):
    request = GenRequest(prompt="cancel me", max_new_tokens=32, temperature=1.0)
    engine.submit(request)
    request.out.get(timeout=30)  # wait for the first token
    request.cancelled.set()
    deadline = time.monotonic() + 10
    while engine.busy and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not engine.busy
    assert engine.allocator.num_free == TEST_CONFIG.num_pages - 1


def test_pool_exhaustion_backpressure():
    """A pool that fits one request at a time still completes all requests."""
    config = EngineConfig(
        model="tiny-llama", tokenizer="byte", dtype="float32",
        max_decode_slots=2, page_size=8, num_pages=4, max_seq_len=32,
        prefill_buckets=(16,), max_new_tokens_cap=8, default_max_new_tokens=4,
    )
    eng = InferenceEngine(config)
    try:
        requests = [GenRequest(prompt=f"req {i}", max_new_tokens=4) for i in range(4)]
        for request in requests:
            eng.submit(request)
        for request in requests:
            tokens, done, error = _collect(request)
            assert error is None, error
            assert done is not None
        assert eng.allocator.num_free == config.num_pages - 1
    finally:
        eng.shutdown()


def test_stats_shape(engine):
    stats = engine.stats()
    for key in ("requests_admitted", "tokens_generated", "slots_busy",
                "pages_free", "model", "tokens_per_sec"):
        assert key in stats
    assert stats["model"] == "tiny-llama"


# -- full-stack gRPC tests --------------------------------------------------


@pytest.fixture(scope="module")
def grpc_stack(engine):
    logger = Logger(stream=io.StringIO(), level="debug")
    service = TpuService(engine)
    server, health, port = gateway_server.build_server(
        service, logger, address="127.0.0.1:0"
    )
    server.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    yield PolykeyServiceStub(channel)
    channel.close()
    server.stop(grace=None)


def _llm_request(prompt="hi there", **params):
    request = pk.ExecuteToolRequest(tool_name="llm_generate")
    request.parameters.update({"prompt": prompt, "max_tokens": 6, **params})
    return request


def test_grpc_llm_generate_unary(grpc_stack):
    resp = grpc_stack.ExecuteTool(_llm_request(), timeout=60)
    assert resp.status.code == 200
    assert resp.WhichOneof("output") == "string_output"


def test_grpc_llm_generate_stream(grpc_stack):
    chunks = list(grpc_stack.ExecuteToolStream(_llm_request(), timeout=60))
    assert chunks[-1].final
    assert chunks[-1].status.code == 200
    usage = chunks[-1].usage
    assert usage.completion_tokens >= 1
    assert usage.ttft_ms > 0
    assert usage.prompt_tokens == len("hi there".encode()) + 1  # bytes + BOS


def test_grpc_mock_tools_still_work(grpc_stack):
    resp = grpc_stack.ExecuteTool(
        pk.ExecuteToolRequest(tool_name="example_tool"), timeout=30
    )
    assert resp.status.code == 200
    assert resp.string_output.startswith("Mock execution of example_tool")
    resp = grpc_stack.ExecuteTool(
        pk.ExecuteToolRequest(tool_name="nope"), timeout=30
    )
    assert resp.string_output == "Unknown tool: nope"


def test_grpc_engine_stats_tool(grpc_stack):
    resp = grpc_stack.ExecuteTool(
        pk.ExecuteToolRequest(tool_name="engine_stats"), timeout=30
    )
    assert resp.WhichOneof("output") == "struct_output"
    assert dict(resp.struct_output)["model"] == "tiny-llama"


def test_grpc_missing_prompt_errors(grpc_stack):
    request = pk.ExecuteToolRequest(tool_name="llm_generate")
    request.parameters.update({"max_tokens": 4})
    with pytest.raises(grpc.RpcError) as err:
        grpc_stack.ExecuteTool(request, timeout=30)
    assert "prompt" in err.value.details()


def test_grpc_concurrent_streams(grpc_stack):
    """Concurrent streaming clients — the race-detector analog."""
    errors: list = []

    def worker(i):
        try:
            chunks = list(
                grpc_stack.ExecuteToolStream(
                    _llm_request(prompt=f"client {i}", temperature=0.8),
                    timeout=120,
                )
            )
            assert chunks[-1].final
            assert chunks[-1].usage.completion_tokens >= 1
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors


def test_traced_request_span_tree(engine):
    """End-to-end tracing acceptance: a streaming generation through an
    obs-wired stack leaves a span tree in the flight recorder with the
    queue/prefill/decode/detokenize phases, every span nested inside its
    parent, retrievable via the engine_stats tool."""
    from polykey_tpu.obs import Observability

    obs = Observability()
    # The recorder files durations only; keep the spans themselves to
    # check where each starts and ends.
    recorded, record = [], obs.recorder.record
    obs.recorder.record = lambda span: (recorded.append(span), record(span))
    service = TpuService(engine, obs=obs)
    logger = Logger(stream=io.StringIO(), level="debug")
    server, _, port = gateway_server.build_server(
        service, logger, address="127.0.0.1:0", obs=obs
    )
    server.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        stub = PolykeyServiceStub(channel)
        request = pk.ExecuteToolRequest(tool_name="llm_generate")
        request.parameters.update({"prompt": "trace this", "max_tokens": 8})
        chunks = list(stub.ExecuteToolStream(request, timeout=120))
        assert chunks[-1].final

        resp = stub.ExecuteTool(
            pk.ExecuteToolRequest(tool_name="engine_stats"), timeout=30
        )
        stats = dict(resp.struct_output)
        assert "last_trace" in stats
        trace = dict(stats["last_trace"])
        assert trace["attrs"]["tool"] == "llm_generate"
        children = {c["name"]: dict(c) for c in trace["children"]}
        for phase in ("queue_wait", "prefill_wait", "prefill", "decode",
                      "detokenize"):
            assert phase in children, f"missing {phase} span"
        # decode carries per-block children with token counts.
        blocks = children["decode"].get("children", [])
        assert blocks and sum(
            int(b["attrs"]["tokens"]) for b in blocks
        ) >= chunks[-1].usage.completion_tokens - 1
        # Every span lies inside its parent (one monotonic clock), and
        # the engine phases follow one another in order.
        root = next(
            s for s in recorded if s.attrs.get("tool") == "llm_generate"
        )

        def assert_nested(parent):
            for child in parent.children:
                assert child.end is not None, child.name
                assert parent.start <= child.start <= child.end <= parent.end, (
                    parent.name, child.name)
                assert_nested(child)

        assert root.end is not None
        assert_nested(root)
        spans = {c.name: c for c in root.children}
        order = ("queue_wait", "prefill_wait", "prefill", "decode")
        for before, after in zip(order, order[1:]):
            assert spans[before].end <= spans[after].start, (before, after)

        # TTFT/ITL percentiles (histogram-backed) surface in the stats.
        assert stats["ttft_ms_p50"] > 0
        assert stats["ttft_ms_p99"] >= stats["ttft_ms_p50"]

        # metrics_text view renders the Prometheus page over gRPC.
        request = pk.ExecuteToolRequest(tool_name="engine_stats")
        request.parameters.update({"view": "metrics_text"})
        resp = stub.ExecuteTool(request, timeout=30)
        page = resp.string_output
        for family in ("polykey_ttft_ms_bucket", "polykey_decode_tokens_total",
                       "polykey_active_requests", "polykey_engine_up",
                       "polykey_watchdog_stalls_total"):
            assert family in page, f"missing {family} in exposition"

        # trace view dumps the recorder.
        request = pk.ExecuteToolRequest(tool_name="engine_stats")
        request.parameters.update({"view": "trace"})
        resp = stub.ExecuteTool(request, timeout=30)
        dump = dict(resp.struct_output)
        assert any(
            dict(dict(t).get("attrs") or {}).get("tool") == "llm_generate"
            for t in dump["traces"]
        )
    finally:
        channel.close()
        server.stop(grace=None)


def test_quantized_engine_serves():
    """POLYKEY_QUANTIZE path: int8 weight-only engine generates end to end
    and stays deterministic (greedy)."""
    import dataclasses

    eng = InferenceEngine(dataclasses.replace(TEST_CONFIG, quantize=True))
    try:
        r1 = GenRequest(prompt="hello", max_new_tokens=8, temperature=0.0)
        r2 = GenRequest(prompt="hello", max_new_tokens=8, temperature=0.0)
        eng.submit(r1)
        t1, d1, e1 = _collect(r1)
        eng.submit(r2)
        t2, d2, e2 = _collect(r2)
        assert e1 is None and e2 is None
        assert d1 is not None and d2 is not None
        assert t1 == t2 and len(t1) == 8
    finally:
        eng.shutdown()


def test_parse_seed_rejects_nonfinite_and_unsafe_floats():
    """JSON Struct numbers are doubles: NaN/Infinity and integers beyond
    2**53 must all raise the same descriptive ValueError (not
    OverflowError), and safe integer-valued floats must parse."""
    import pytest

    from polykey_tpu.gateway.tpu_service import TpuService

    parse = TpuService._parse_seed
    assert parse({}) is None
    assert parse({"seed": 42}) == 42
    assert parse({"seed": 42.0}) == 42
    for bad in (float("nan"), float("inf"), float("-inf"),
                1.5, float(2 ** 53 + 2)):
        with pytest.raises(ValueError, match="seed"):
            parse({"seed": bad})


def test_compile_warmup_covers_sampled_variants():
    """greedy is a batch-keyed static argname on both prefill and the
    decode block, so warmup must pre-compile the greedy=False variants
    too — the first sampled request must not trigger any new compile."""
    import dataclasses

    # Unique shape key (slots/buckets used by no other test): jax.jit
    # caches are shared across engine instances with equal jit params, so
    # a shared shape would let earlier sampled-request tests pre-populate
    # the entries and this test would pass even with warmup broken.
    eng = InferenceEngine(
        dataclasses.replace(
            TEST_CONFIG, compile_warmup=True,
            max_decode_slots=5, prefill_buckets=(24,),
        )
    )
    try:
        n_prefill = eng._jit_prefill._cache_size()
        n_decode = eng._jit_decode._cache_size()
        r = GenRequest(
            prompt="sampled warm probe", max_new_tokens=8,
            temperature=0.9, top_p=0.8, seed=11,
        )
        eng.submit(r)
        tokens, done, error = _collect(r)
        assert error is None and done is not None and tokens
        assert eng._jit_prefill._cache_size() == n_prefill
        assert eng._jit_decode._cache_size() == n_decode
    finally:
        eng.shutdown()


def test_compile_warmup_greedy_only_mode():
    """warm_sampled_variants=False (the greedy-only benchmark mode) must
    still fully pre-compile the greedy path: a greedy request triggers no
    new compile. (No cross-engine cache-size comparison here — jax.jit
    wrappers over the same function with equal jit params SHARE the
    underlying cache across engine instances, so only same-engine deltas
    are meaningful.)"""
    import dataclasses

    eng = InferenceEngine(
        dataclasses.replace(
            TEST_CONFIG, compile_warmup=True, warm_sampled_variants=False,
            # Unique shape key — see test_compile_warmup_covers_sampled_variants.
            max_decode_slots=6, prefill_buckets=(40,),
        )
    )
    try:
        n_prefill = eng._jit_prefill._cache_size()
        n_decode = eng._jit_decode._cache_size()
        r = GenRequest(prompt="greedy only probe", max_new_tokens=8)
        eng.submit(r)
        tokens, done, error = _collect(r)
        assert error is None and done is not None and tokens
        assert eng._jit_prefill._cache_size() == n_prefill
        assert eng._jit_decode._cache_size() == n_decode
    finally:
        eng.shutdown()


def test_adaptive_block_solo_vs_loaded():
    """Load-adaptive blocking: a lone stream dispatches the small solo
    block (max(1, K//8)); concurrent streams dispatch the full K. Output
    is identical to the static-block engine either way."""
    import dataclasses

    cfg = dataclasses.replace(TEST_CONFIG, decode_block_steps=8)
    static_cfg = dataclasses.replace(cfg, adaptive_block=False)

    def run_solo(config):
        eng = InferenceEngine(config)
        try:
            r = GenRequest(prompt="adaptive probe", max_new_tokens=12)
            eng.submit(r)
            tokens, done, error = _collect(r)
            assert error is None and done is not None
            # The always-on accumulator (the deepest in-flight target
            # any dispatch ran with) and the timeline ring's event order.
            return (tokens, eng.timeline.events(),
                    eng.metrics.depth_target_max)
        finally:
            eng.shutdown()

    def dispatch_steps(events, loaded):
        return {e["steps"] for e in events
                if e["kind"] == "dispatch" and (e["lanes"] > 1) == loaded}

    def assert_tail_capped(events, steps, need=11):
        """In-flight work never exceeds what the stream still needs, in
        ring order: dispatch i runs with a depth target of at most the
        blocks the host still counted as needed (`need` decode tokens
        less `steps` per block it had processed), and the loop drains to
        target - 1 queued blocks before it dispatches again."""
        dispatched = processed = 0
        allowed = None
        for e in events:
            if e["kind"] == "process":
                processed += 1
            elif e["kind"] == "dispatch":
                if allowed is not None:
                    assert dispatched - processed <= allowed, (
                        dispatched, processed, allowed)
                dispatched += 1
                needed = -(-max(0, need - steps * processed) // steps)
                allowed = max(0, needed - 1)

    solo_tokens, solo_events, solo_max = run_solo(cfg)
    static_tokens, static_events, static_max = run_solo(static_cfg)
    assert dispatch_steps(solo_events, loaded=False) == {1}
    assert dispatch_steps(static_events, loaded=False) == {8}
    assert solo_tokens == static_tokens
    # Constant LOOKAHEAD steps MID-STREAM: shrinking K deepens the
    # pipeline so the queued-ahead work keeps covering the roundtrip —
    # 1 + (depth-1) x (K/steps), i.e. 1+8=9 at K=1; only the lookahead
    # portion scales, so depth 1 stays exactly synchronous (the
    # escape-hatch contract test_dispatch_pipeline pins). Bounded by the
    # stream's remaining budget (12 new tokens -> ~12 blocks at K=1).
    assert solo_max >= 1 + (cfg.lookahead_blocks - 1) * 8, solo_max
    assert solo_max <= 1 + (cfg.lookahead_blocks - 1) * 8
    # Tail cap: in-flight work never exceeds what active streams still
    # need, so stream tails don't leave ~lookahead x K steps of dead
    # full-batch work queued in front of the next arrival's prefill.
    assert_tail_capped(solo_events, steps=1)
    assert_tail_capped(static_events, steps=8)
    assert static_max <= cfg.lookahead_blocks

    # Under load (>1 active stream) the adaptive engine uses the full K.
    eng = InferenceEngine(cfg)
    try:
        reqs = [GenRequest(prompt=f"load {i}", max_new_tokens=12)
                for i in range(3)]
        for r in reqs:
            eng.submit(r)
        outs = [_collect(r) for r in reqs]
        assert all(e is None for _, _, e in outs)
        # Every dispatch that carried more than one live stream ran the
        # full block; whatever ran with one stream left ran the solo one.
        events = eng.timeline.events()
        assert dispatch_steps(events, loaded=True) == {8}
        assert dispatch_steps(events, loaded=False) <= {1}
    finally:
        eng.shutdown()


def test_int4_engine_serves():
    """POLYKEY_QUANTIZE=int4 path: group-wise int4 weight-only engine
    generates end to end and stays deterministic (greedy)."""
    import dataclasses

    eng = InferenceEngine(
        dataclasses.replace(TEST_CONFIG, quantize=True, quantize_bits=4)
    )
    try:
        r1 = GenRequest(prompt="hello", max_new_tokens=8, temperature=0.0)
        r2 = GenRequest(prompt="hello", max_new_tokens=8, temperature=0.0)
        eng.submit(r1)
        t1, d1, e1 = _collect(r1)
        eng.submit(r2)
        t2, d2, e2 = _collect(r2)
        assert e1 is None and e2 is None
        assert d1 is not None and d2 is not None
        assert t1 == t2 and len(t1) == 8
    finally:
        eng.shutdown()


def test_top_k_one_is_greedy_end_to_end():
    """top_k=1 at temperature 1.0 must reproduce the greedy stream
    exactly — the sampler's rank mask leaves only the argmax."""
    import dataclasses

    eng = InferenceEngine(TEST_CONFIG)
    try:
        g = GenRequest(prompt="topk greedy probe", max_new_tokens=8)
        eng.submit(g)
        greedy_tokens, _, _ = _collect(g)

        r = GenRequest(prompt="topk greedy probe", max_new_tokens=8,
                       temperature=1.0, top_k=1, seed=9)
        eng.submit(r)
        tokens, done, error = _collect(r)
        assert error is None and done is not None
        assert tokens == greedy_tokens
    finally:
        eng.shutdown()


def test_top_k_seeded_reproducible():
    """Same (prompt, seed, top_k) → same stream, and a different top_k
    changes the distribution's support (k=1 vs unrestricted differ for
    this seed)."""
    eng = InferenceEngine(TEST_CONFIG)
    try:
        def run(top_k):
            r = GenRequest(prompt="topk seed probe", max_new_tokens=10,
                           temperature=1.2, top_k=top_k, seed=123)
            eng.submit(r)
            tokens, done, error = _collect(r)
            assert error is None and done is not None
            return tokens
        a, b = run(4), run(4)
        assert a == b
        assert run(1) != a or run(0) != a
    finally:
        eng.shutdown()


def test_parse_top_k_validation():
    from polykey_tpu.gateway.tpu_service import TpuService

    parse = TpuService._parse_top_k
    assert parse({}) == 0
    assert parse({"top_k": 5}) == 5
    assert parse({"top_k": 5.0}) == 5
    for bad in (-1, 1.5, float("nan"), float("inf")):
        with pytest.raises(ValueError, match="top_k"):
            parse({"top_k": bad})


def test_top_k_clamps_to_candidate_width():
    """With the top-k prefilter on (top_p_candidates=C), a wider top_k
    clamps to C at admission — the sampled paths only ever see the top-C
    logits, and the clamp makes that contract explicit instead of a
    silent sampler property."""
    import dataclasses

    eng = InferenceEngine(
        dataclasses.replace(TEST_CONFIG, top_p_candidates=8)
    )
    try:
        r = GenRequest(prompt="x", top_k=100)
        assert eng._eff_top_k(r) == 8
        assert eng._eff_top_k(GenRequest(prompt="x", top_k=3)) == 3
        assert eng._eff_top_k(GenRequest(prompt="x", top_k=0)) == 0
        # And the clamped request still serves.
        req = GenRequest(prompt="clamped topk", max_new_tokens=6,
                         temperature=1.0, top_k=100, seed=2)
        eng.submit(req)
        tokens, done, error = _collect(req)
        assert error is None and done is not None and tokens
    finally:
        eng.shutdown()


def test_prequantized_moe_engine_serves():
    """Bench phase E's exact path: a PRE-quantized int8 Mixtral-family
    tree handed to the engine (quantize=False — params arrive quantized,
    like the 8B/9B bench phases) serves greedily and matches the engine
    that quantizes the same weights itself."""
    import dataclasses

    import jax

    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.quant import quantize_params
    from polykey_tpu.models.transformer import init_params

    cfg = dataclasses.replace(TEST_CONFIG, model="tiny-mixtral")
    mc = get_config("tiny-mixtral")
    fp = init_params(jax.random.PRNGKey(3), mc, "float32")
    pre = quantize_params(fp, mc, bits=8)

    def serve(config, params):
        eng = InferenceEngine(config, params=params)
        try:
            r = GenRequest(prompt="hello moe", max_new_tokens=8,
                           temperature=0.0)
            eng.submit(r)
            toks, done, err = _collect(r)
            assert err is None and done is not None
            return toks
        finally:
            eng.shutdown()

    got = serve(cfg, pre)
    want = serve(dataclasses.replace(cfg, quantize=True), fp)
    assert got == want and len(got) == 8


def test_admission_keeps_slots_occupied():
    """Occupancy regression gate for the admission policy: under a
    saturated closed loop (client queue deeper than the slot count) the
    average live-lane count per dispatched block must approach the slot
    count. The old one-admission-per-iteration policy equilibrated at
    ~max_new/decode_block_steps lanes (measured 5/32 on hardware —
    PERF.md r03); this pins the fix."""
    import threading

    cfg = EngineConfig(
        model="tiny-llama",
        tokenizer="byte",
        dtype="float32",
        max_decode_slots=8,
        page_size=8,
        num_pages=512,
        max_seq_len=128,
        prefill_buckets=(32,),
        max_new_tokens_cap=64,
        decode_block_steps=8,
        lookahead_blocks=2,
    )
    engine = InferenceEngine(cfg)
    try:
        sem = threading.Semaphore(cfg.max_decode_slots * 2)
        done = threading.Semaphore(0)

        def drain(r):
            try:
                while r.out.get(timeout=120.0)[0] == "token":
                    pass
            finally:
                sem.release()
                done.release()

        n_req = 48
        for _ in range(n_req):
            sem.acquire()
            r = GenRequest(prompt="occupancy", max_new_tokens=64)
            engine.submit(r)
            threading.Thread(target=drain, args=(r,), daemon=True).start()
        for _ in range(n_req):
            assert done.acquire(timeout=120.0)

        # The always-on occupancy accumulators (block-weighted).
        blocks = engine.metrics.blocks_dispatched
        assert blocks > 0
        avg_lanes = engine.metrics.lanes_dispatched / blocks
        # Ramp/tail blocks drag the average below the slot count; 60% is
        # comfortably above the broken policy's ~max_new/K = 8... which
        # equals the slot count here, so ALSO bound total blocks: the
        # broken policy needs ~n_req extra admission-starved blocks.
        assert avg_lanes >= cfg.max_decode_slots * 0.6, avg_lanes
        ideal = n_req * 64 / cfg.max_decode_slots / cfg.decode_block_steps
        assert blocks <= ideal * 2.5, (blocks, ideal)
    finally:
        engine.shutdown()


def test_int8_kv_engine_serves():
    """EngineConfig.kv_dtype='int8': quantized KV pools (+ bf16 scale
    pools) through admission, batched prefill, blocked decode, and
    retirement — all requests complete with the full token budget."""
    cfg = EngineConfig(
        model="tiny-llama",
        tokenizer="byte",
        dtype="float32",
        kv_dtype="int8",
        max_decode_slots=4,
        page_size=8,
        num_pages=128,
        max_seq_len=64,
        prefill_buckets=(16, 32),
        max_new_tokens_cap=32,
    )
    import jax.numpy as jnp

    engine = InferenceEngine(cfg)
    try:
        assert engine.paged.quantized
        assert engine.paged.kv.dtype == jnp.int8
        assert engine.paged.ks.dtype == jnp.bfloat16
        reqs = [GenRequest(prompt=f"int8 kv {i}", max_new_tokens=12)
                for i in range(6)]
        for r in reqs:
            engine.submit(r)
        for r in reqs:
            tokens = []
            while True:
                kind, v = r.out.get(timeout=120.0)
                if kind == "token":
                    tokens.append(v)
                elif kind == "done":
                    break
                else:
                    raise AssertionError(f"request failed: {v}")
            assert len(tokens) == 12
    finally:
        engine.shutdown()


# -- the prefill cover (ISSUE 41): fewest rows the compiled windows allow ------


@pytest.mark.parametrize("buckets", [
    (128, 512), (16, 32), (16, 64), (16, 128), (32,), (64, 256, 1024),
], ids=lambda b: "-".join(map(str, b)))
def test_prefill_cover_is_the_fewest_rows(buckets):
    """Every span from 1 to twice the widest bucket, at a page-aligned
    start, for every group-size set a slot count gives: the cover holds
    exactly the span, never dispatches more rows than one bucket (or
    whole chunks) did, keeps every start page-aligned and its split
    windows inside one group."""
    from polykey_tpu.engine.engine import prefill_cover, prefill_group_sizes

    page, widest = 8, max(buckets)

    def before(n):
        if n > widest:
            return -(-n // widest) * widest
        return next(b for b in buckets if n <= b)

    def pad(k, groups):
        return next(g for g in groups if g >= k)

    for slots in (1, 2, 4, 16):
        groups = prefill_group_sizes(slots)
        for start in (0, 5 * page):
            for n in range(1, 2 * widest + 1):
                windows = prefill_cover(n, start, buckets, groups, page)
                lead = (n - 1) // widest    # chunk-wide windows, then the tail
                tail = windows[lead:]
                assert all(width == widest for width, _ in windows[:lead])
                # Contiguous from `start`, every start page-aligned.
                at = start
                for width, begin in windows:
                    assert begin == at and begin % page == 0
                    assert width in buckets
                    at += width
                # Holds the span, and no window is empty.
                assert at - start >= n > at - start - windows[-1][0]
                # The tail is one dispatch: one width, inside a group.
                assert len({w for w, _ in tail}) == 1
                assert len(tail) <= groups[-1]
                rows = lead * widest + pad(len(tail), groups) * tail[0][0]
                assert rows <= before(n), (n, windows)
                # No narrower or equally wide cover with fewer windows.
                left = n - lead * widest
                for b in buckets:
                    k = -(-left // b)
                    if k == 1 or (b % page == 0 and k <= groups[-1]):
                        assert (pad(len(tail), groups) * tail[0][0], len(tail)) \
                            <= (pad(k, groups) * b, k)
    groups = prefill_group_sizes(16)
    if buckets == (128, 512):
        assert prefill_cover(128, 0, buckets, groups, 16) == [(128, 0)]
        assert prefill_cover(129, 0, buckets, groups, 16) == \
            [(128, 0), (128, 128)]
        assert prefill_cover(256, 32, buckets, groups, 16) == \
            [(128, 32), (128, 160)]
        assert prefill_cover(257, 0, buckets, groups, 16) == [(512, 0)]
        assert prefill_cover(600, 0, buckets, groups, 16) == \
            [(512, 0), (128, 512)]
        # One compiled row: nothing to split over.
        assert prefill_cover(200, 0, buckets, (1,), 16) == [(512, 0)]
        # A bucket that is not whole pages is never split over.
        assert prefill_cover(200, 0, buckets, groups, 48) == [(512, 0)]


def _hand_driven(config):
    """An engine whose loop has ended: driven by hand."""
    eng = InferenceEngine(config)
    eng.shutdown()
    return eng


@pytest.mark.parametrize("small,n,start", [
    (16, 25, 0), (16, 32, 0), (16, 27, 8), (8, 13, 16),
])
def test_two_rows_on_one_table_equal_one_wide_row(small, n, start):
    """`_prefill_fn` with rows at (start, start + small) on ONE page table
    is the prefill of the same tokens in one row of 2 x small: within a
    layer every row's K/V is written before any row's attention gathers,
    so the second row reads the first row's keys. Float32 on the CPU:
    the logits at the last real position, the sampled token and the
    pages written agree to rounding."""
    import jax
    import jax.numpy as jnp

    from polykey_tpu.engine.engine import _prefill_fn
    from polykey_tpu.models.transformer import forward_paged, unembed

    eng = _hand_driven(TEST_CONFIG)     # only its params and pools are used
    cfg, page = eng.model_cfg, TEST_CONFIG.page_size
    pages_each = (start + 2 * small) // page
    rng = np.random.default_rng(n)
    ids = rng.integers(32, 127, size=start + n).astype(np.int32)

    def table(first):
        row = np.zeros((TEST_CONFIG.pages_per_seq,), np.int32)
        row[:pages_each] = first + np.arange(pages_each)
        return row

    def rows(width, first):
        k = -(-n // width)
        tokens = np.zeros((k, width), np.int32)
        tokens.reshape(-1)[:n] = ids[start:]
        starts = (start + np.arange(k) * width).astype(np.int32)
        last_rel = np.full((k,), width - 1, np.int32)
        last_rel[-1] = start + n - 1 - starts[-1]
        return (jnp.asarray(tokens), jnp.asarray(starts),
                jnp.asarray(last_rel), jnp.asarray(np.tile(table(first), (k, 1))))

    def logits_of(paged, tokens, starts, last_rel, tables):
        positions = starts[:, None] + jnp.arange(tokens.shape[1])[None, :]
        hidden, paged = forward_paged(
            eng.params, cfg, tokens, positions, paged, tables
        )
        last = hidden[jnp.arange(len(starts)), last_rel]
        return unembed(eng.params, cfg, last), paged

    def sampled(paged, tokens, starts, last_rel, tables):
        k = len(starts)
        return _prefill_fn(
            eng.params, cfg, paged, tokens, starts, last_rel, tables,
            jnp.zeros((k, 2), jnp.int32), jnp.zeros((k,), jnp.float32),
            jnp.ones((k,), jnp.float32), jnp.zeros((k,), jnp.int32),
            greedy=True,
        )

    paged = eng.paged
    one_first, two_first = 1, 1 + pages_each
    if start:
        # The cached prefix both variants start from, on both tables.
        for first in (one_first, two_first):
            prefix = np.zeros((1, start), np.int32)
            prefix[0] = ids[:start]
            _, paged = logits_of(
                paged, jnp.asarray(prefix), jnp.zeros((1,), jnp.int32),
                jnp.asarray([start - 1], jnp.int32),
                jnp.asarray(table(first))[None],
            )
    one, paged = logits_of(paged, *rows(2 * small, one_first))
    two, paged = logits_of(paged, *rows(small, two_first))
    assert len(two) == -(-n // small)
    np.testing.assert_allclose(one[-1], two[-1], rtol=0, atol=2e-5)
    live = -(-(start + n) // page)
    # K and V of the pages alike: [L, live, 2, page, Hk·D].
    a = np.array(paged.kv[:, one_first:one_first + live])
    b = np.array(paged.kv[:, two_first:two_first + live])
    tail = start + n - (live - 1) * page    # real rows of the last page
    a[:, -1, :, tail:] = b[:, -1, :, tail:] = 0   # padding rows differ
    assert np.abs(a[:, :, 0]).max() > 0 and np.abs(a[:, :, 1]).max() > 0
    np.testing.assert_allclose(a, b, rtol=0, atol=2e-5)
    token_one, paged, _ = sampled(paged, *rows(2 * small, one_first))
    token_two, paged, _ = sampled(paged, *rows(small, two_first))
    assert int(token_one[-1]) == int(token_two[-1]) == int(jax.numpy.argmax(one[-1]))


@pytest.mark.parametrize("budget,admitted", [(None, 3), (32, 1), (33, 2)])
def test_admit_puts_a_prompts_windows_in_one_group(budget, admitted):
    """Two prompts that split and one that does not, waiting together:
    each prompt's windows are consecutive rows of one dispatch, a group
    that cannot take a prompt whole goes out first, the budget is
    charged the cover's rows, and the counters say what was dispatched."""
    import dataclasses

    config = dataclasses.replace(
        TEST_CONFIG, prefill_buckets=(16, 64), max_seq_len=128,
        num_pages=96,
    )
    eng = _hand_driven(config)
    dispatched = []
    dispatch = eng._dispatch_prefill_group

    def recording(bucket, group):
        dispatched.append((bucket, [
            (slot_idx, len(ids), start, last)
            for slot_idx, _, ids, start, last in group
        ]))
        return dispatch(bucket, group)

    eng._dispatch_prefill_group = recording
    prompts = ["a" * 19, "b" * 30, "c" * 9]         # 20, 31, 10 tokens
    for prompt in prompts:
        eng._submit.put(GenRequest(prompt=prompt, max_new_tokens=4))
    worked, spent = eng._admit(budget=budget)
    assert worked
    want = [
        (16, [(0, 16, 0, False), (0, 4, 16, True),
              (1, 16, 0, False), (1, 15, 16, True)]),
        (16, [(2, 10, 0, True)]),
    ]
    if admitted == 1:
        want = [(16, want[0][1][:2])]
    elif admitted == 2:
        want = want[:1]
    assert dispatched == want
    assert spent == 32 * min(admitted, 2) + 16 * (admitted == 3)
    assert eng._submit.qsize() == 3 - admitted
    snap = eng.metrics.snapshot()
    windows = sum(len(rows) for _, rows in want)
    assert snap["prefill_windows_dispatched"] == windows
    assert snap["prefill_prompts_split"] == min(admitted, 2)
    padded = sum(
        16 * next(g for g in (1, 2, 4) if g >= len(rows)) for _, rows in want
    )
    assert snap["prefill_rows_dispatched"] == padded
    assert snap["prefill_rows_useful"] == sum(
        n for _, rows in want for _, n, _, _ in rows
    )
    # Only a prompt's last window activated its lane.
    assert int(eng._active.sum()) == admitted
    assert [int(n) for n in eng._seq_lens[:3]] == \
        [21, 32, 11][:admitted] + [0] * (3 - admitted)


def test_warmed_engine_compiles_nothing_for_a_covered_mix():
    """A prompt over several windows rides shapes the warm-up compiled:
    a mix of split, unsplit and long prompts after warm-up builds no
    executable (same-engine deltas only: jit caches are shared between
    engines with equal jit parameters)."""
    import dataclasses

    eng = InferenceEngine(dataclasses.replace(
        TEST_CONFIG, compile_warmup=True, warm_sampled_variants=False,
        # A shape key no other test uses, as the warm-up tests above.
        max_decode_slots=7, prefill_buckets=(24, 96), max_seq_len=192,
        num_pages=200,
    ))
    try:
        before = eng.stats()
        n_prefill = eng._jit_prefill._cache_size()
        n_decode = eng._jit_decode._cache_size()
        # 10: one 24-window; 30 and 48: two; 60: three would pad to four,
        # so the 96-window; 100: a 96-chunk and a 24 tail; 130: a chunk
        # and a split tail.
        lengths = (10, 30, 48, 60, 96, 100, 130)
        requests = [
            GenRequest(prompt="x" * (n - 1), max_new_tokens=4)
            for n in lengths
        ]
        for r in requests[:4]:
            eng.submit(r)
        for r in requests[:4]:
            _, done, error = _collect(r)
            assert error is None and done is not None
        for r in requests[4:]:
            eng.submit(r)
            _, done, error = _collect(r)
            assert error is None and done is not None
        after = eng.stats()
        assert after["compiles"] == before["compiles"]
        assert eng._jit_prefill._cache_size() == n_prefill
        assert eng._jit_decode._cache_size() == n_decode
        assert after["prefill_prompts_split"] == 3          # 30, 48, 130
        assert after["prefill_windows_dispatched"] == 1 + 2 + 2 + 1 + 1 + 2 + 3
        assert after["prefill_rows_useful"] == sum(lengths)
    finally:
        eng.shutdown()
