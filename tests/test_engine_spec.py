"""Speculative decoding in the serving path (VERDICT r1 #3).

The acceptance bar: a spec-decode engine (draft model mounted, paged
draft/verify rounds — engine/spec_decode.py) emits EXACTLY the same greedy
stream as the plain engine, and the full gRPC streaming path works with a
tiny draft+target pair.
"""

import dataclasses
import io
import queue
import time

import grpc

from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.gateway import server as gateway_server
from polykey_tpu.gateway.jsonlog import Logger
from polykey_tpu.gateway.tpu_service import TpuService
from polykey_tpu.proto import polykey_v2_pb2 as pk
from polykey_tpu.proto.polykey_v2_grpc import PolykeyServiceStub

BASE_CONFIG = EngineConfig(
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
# Draft = same architecture at a different seed (engine inits the draft from
# seed+2): a *wrong* draft model, which is exactly the point — greedy output
# must still be the target's chain no matter how bad the drafts are.
SPEC_CONFIG = dataclasses.replace(BASE_CONFIG, draft_model="tiny-llama",
                                  spec_gamma=3)

PROMPTS = ["hello spec", "draft and verify", "q", "the quick brown fox"]


def _collect(request: GenRequest, timeout=60.0):
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


def _run_prompts(config, temperature=0.0, top_p=1.0, max_new=8):
    eng = InferenceEngine(config)
    try:
        reqs = [
            GenRequest(prompt=p, max_new_tokens=max_new,
                       temperature=temperature, top_p=top_p)
            for p in PROMPTS
        ]
        for r in reqs:
            eng.submit(r)
        outs = []
        for r in reqs:
            tokens, done, error = _collect(r)
            assert error is None, error
            assert done is not None
            outs.append(tokens)
        return outs, eng.metrics.snapshot()
    finally:
        eng.shutdown()


def test_spec_greedy_matches_plain_engine():
    plain, _ = _run_prompts(BASE_CONFIG)
    spec, snap = _run_prompts(SPEC_CONFIG)
    assert spec == plain
    # The rounds really were speculative: proposals were counted, and the
    # batch advanced in multi-token rounds (fewer steps than tokens).
    assert snap["drafts_proposed"] > 0
    assert snap["decode_steps"] < snap["tokens_generated"]


def test_spec_good_draft_accepts():
    # Draft == target weights (same seed: draft inits from seed+2, so seed
    # target at seed+2 ≡ draft) would be ideal; approximate with the real
    # guarantee instead: acceptance is in [0, 1] and counted consistently.
    _, snap = _run_prompts(SPEC_CONFIG)
    assert 0.0 <= snap["spec_acceptance"] <= 1.0
    assert snap["drafts_accepted"] <= snap["drafts_proposed"]


def test_spec_sampled_completes():
    outs, snap = _run_prompts(SPEC_CONFIG, temperature=0.8)
    assert all(len(t) >= 1 for t in outs)
    assert snap["requests_failed"] == 0
    assert snap["drafts_proposed"] > 0


def test_spec_top_p_falls_back_to_plain():
    # Without the top-k prefilter (top_p_candidates=0) top_p<1 rows take
    # the plain step (full-vocab truncation inside the spec round would
    # need per-step sorts); the request still completes. Matching the
    # plain engine's sampled path seed-for-seed is not guaranteed, so
    # assert completion only.
    outs, snap = _run_prompts(SPEC_CONFIG, temperature=0.8, top_p=0.9)
    assert all(len(t) >= 1 for t in outs)
    assert snap["requests_failed"] == 0
    # Every decode step had a top_p<1 batch → zero speculative rounds.
    assert "drafts_proposed" not in snap


def test_spec_top_p_speculates_with_prefilter():
    """With top_p_candidates set, top_p<1 batches stay on the speculative
    path (truncated rejection sampling, sampling.truncated_dist) —
    the batch-wide plain-step fallback and its acceptance collapse are
    gone. Mixed greedy + sampled batches round through spec too."""
    cfg = dataclasses.replace(SPEC_CONFIG, top_p_candidates=32)
    outs, snap = _run_prompts(cfg, temperature=0.8, top_p=0.9)
    assert all(len(t) >= 1 for t in outs)
    assert snap["requests_failed"] == 0
    assert snap.get("drafts_proposed", 0) > 0

    # Mixed batch: one greedy + sampled rows concurrently.
    eng = InferenceEngine(cfg)
    try:
        reqs = [
            GenRequest(prompt="greedy row", max_new_tokens=6),
            GenRequest(prompt="sampled row", max_new_tokens=6,
                       temperature=0.9, top_p=0.8),
        ]
        for r in reqs:
            eng.submit(r)
        for r in reqs:
            tokens, done, error = _collect(r)
            assert error is None and done is not None and tokens
        assert eng.metrics.snapshot().get("drafts_proposed", 0) > 0
    finally:
        eng.shutdown()


def test_spec_top_p_truncated_acceptance_is_exact():
    """Sharp identity check: with draft == target, the truncated
    acceptance ratio p'/q' is exactly 1 for every draft, so a top_p<1
    sampled stream must accept ALL drafts (acceptance 1.0) — any
    asymmetry between the draft-side and verify-side truncation would
    show up as rejections."""
    import jax
    import jax.numpy as jnp

    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.transformer import init_params

    cfg = dataclasses.replace(
        SPEC_CONFIG, top_p_candidates=32, max_decode_slots=2
    )
    params = init_params(
        jax.random.PRNGKey(5), get_config("tiny-llama"), jnp.float32
    )
    eng = InferenceEngine(cfg, params=params, draft_params=params)
    try:
        reqs = [GenRequest(prompt=f"identical {i}", max_new_tokens=12,
                           temperature=1.0, top_p=0.7) for i in range(2)]
        for r in reqs:
            eng.submit(r)
        for r in reqs:
            tokens, done, error = _collect(r)
            assert error is None and done is not None
        snap = eng.metrics.snapshot()
        assert snap["drafts_proposed"] > 0
        assert snap["spec_acceptance"] == 1.0, snap
    finally:
        eng.shutdown()


def test_spec_long_generation_budget_cap():
    # Budget/EOS truncation mid-window: max_new not a multiple of gamma+1
    # forces the final round to truncate on host.
    eng = InferenceEngine(SPEC_CONFIG)
    try:
        r = GenRequest(prompt="truncate me", max_new_tokens=10)
        eng.submit(r)
        tokens, done, error = _collect(r)
        assert error is None
        assert done is not None
        assert len(tokens) <= 10
    finally:
        eng.shutdown()


def test_spec_grpc_streaming_e2e():
    logger = Logger(stream=io.StringIO())
    eng = InferenceEngine(SPEC_CONFIG)
    try:
        service = TpuService(eng)
        server, health, port = gateway_server.build_server(
            service, logger, address="127.0.0.1:0"
        )
        server.start()
        try:
            channel = grpc.insecure_channel(f"127.0.0.1:{port}")
            stub = PolykeyServiceStub(channel)
            request = pk.ExecuteToolRequest(tool_name="llm_generate")
            request.parameters.fields["prompt"].string_value = "stream spec"
            request.parameters.fields["max_new_tokens"].number_value = 8
            chunks = list(stub.ExecuteToolStream(request, timeout=120))
            assert chunks, "no stream chunks"
            final = chunks[-1]
            assert final.status.code == 200
            channel.close()
        finally:
            server.stop(grace=None)
    finally:
        eng.shutdown()


def test_spec_compile_warmup_matches_cold():
    """Spec engines now take compile warmup (spec prefill groups + the
    spec round); warmed output must equal the cold engine's bit-for-bit
    and the merge/prefill caches must cover the first admission."""
    cold, _ = _run_prompts(SPEC_CONFIG)
    warm_cfg = dataclasses.replace(SPEC_CONFIG, compile_warmup=True)
    eng = InferenceEngine(warm_cfg)
    try:
        n_prefill = eng._jit_spec_prefill._cache_size()
        n_merge = eng._jit_merge._cache_size()
        n_round = eng._jit_spec_decode._cache_size()
        reqs = [GenRequest(prompt=p, max_new_tokens=8) for p in PROMPTS]
        for r in reqs:
            eng.submit(r)
        outs = []
        for r in reqs:
            tokens, done, error = _collect(r)
            assert error is None and done is not None
            outs.append(tokens)
        assert outs == cold
        # No new greedy compiles after warmup.
        assert eng._jit_spec_prefill._cache_size() == n_prefill
        assert eng._jit_merge._cache_size() == n_merge
        # The spec ROUND is the heavy compile - it must be warmed too.
        assert eng._jit_spec_decode._cache_size() == n_round
    finally:
        eng.shutdown()


def test_spec_compile_warmup_covers_top_p_candidates():
    """With top_p_candidates>0, the spec round dispatches with BOTH
    candidates=0 (all-greedy batches) and candidates=top_p_candidates
    (any truncated-top-p row) — warmup must pre-compile both variants so
    the first sampled batch never stalls on a serving-time compile."""
    # Unique shape key (slots/buckets used by no other test): jit caches
    # are shared across engine instances, so shared shapes could be
    # pre-populated by earlier tests and mask a warmup regression.
    cfg = dataclasses.replace(
        SPEC_CONFIG, top_p_candidates=32, compile_warmup=True,
        max_decode_slots=7, prefill_buckets=(48,),
    )
    eng = InferenceEngine(cfg)
    try:
        n_round = eng._jit_spec_decode._cache_size()
        n_prefill = eng._jit_spec_prefill._cache_size()
        r = GenRequest(
            prompt="warm top-p probe", max_new_tokens=8,
            temperature=0.9, top_p=0.8, seed=7,
        )
        eng.submit(r)
        tokens, done, error = _collect(r)
        assert error is None and done is not None and tokens
        assert eng._jit_spec_decode._cache_size() == n_round
        assert eng._jit_spec_prefill._cache_size() == n_prefill
    finally:
        eng.shutdown()


def test_spec_compile_warmup_covers_plain_fallback():
    """With top_p_candidates=0 a sampled top_p<1 batch leaves the spec
    path and takes the PLAIN decode block — warmup must pre-compile that
    fallback variant too (greedy=False, candidates=0)."""
    cfg = dataclasses.replace(
        SPEC_CONFIG, compile_warmup=True,
        # Unique shape key — see test_spec_compile_warmup_covers_top_p_candidates.
        max_decode_slots=9, prefill_buckets=(56,),
    )
    assert cfg.top_p_candidates == 0
    eng = InferenceEngine(cfg)
    try:
        n_plain = eng._jit_decode._cache_size()
        r = GenRequest(
            prompt="plain fallback probe", max_new_tokens=8,
            temperature=0.9, top_p=0.8, seed=3,
        )
        eng.submit(r)
        tokens, done, error = _collect(r)
        assert error is None and done is not None and tokens
        assert eng._jit_decode._cache_size() == n_plain
    finally:
        eng.shutdown()


def test_adaptive_gamma_drops_on_bad_draft():
    """The per-lane gamma dial (ISSUE 19, superseding the VERDICT r2 #8
    engine-global ladder): a draft that keeps getting rejected drags the
    lane's acceptance EWMA under the low-water mark and clamps that
    lane's dial — the dispatch width follows the widest ACTIVE lane down
    to the low rung mid-stream, and a drained engine resets optimistic
    (fresh lanes boot at gamma_max). Greedy output stays the target's
    chain regardless (the core spec guarantee)."""
    plain, _ = _run_prompts(BASE_CONFIG, max_new=24)
    cfg = dataclasses.replace(SPEC_CONFIG, spec_gamma=4)
    eng = InferenceEngine(cfg)
    try:
        assert eng._gamma == 4 and eng._gamma_low == 2
        reqs = [GenRequest(prompt=p, max_new_tokens=24) for p in PROMPTS]
        for r in reqs:
            eng.submit(r)
        # Poll the dispatch width and the per-lane stats while tokens
        # stream: with a terrible draft (~zero acceptance) each lane's
        # EWMA falls under GAMMA_ACCEPT_FLOOR within a handful of
        # rounds, so the dial drop MUST be observable mid-flight.
        width_dropped = lane_dropped = False
        outs = []
        for r in reqs:
            tokens = []
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                kind, value = r.out.get(timeout=60.0)
                if kind == "token":
                    tokens.append(value)
                    if eng._gamma == eng._gamma_low:
                        width_dropped = True
                    if eng.stats().get("spec_gamma_min") == eng._gamma_low:
                        lane_dropped = True
                elif kind == "done":
                    break
                else:
                    raise AssertionError(f"request error: {value}")
            outs.append(tokens)
        assert width_dropped, "dispatch width never followed lanes down"
        assert lane_dropped, "no lane dial reached the low rung"
        # Aggregate EWMA (observability mirror of the per-lane blend)
        # agrees the draft is bad.
        assert eng._accept_ewma < 0.35
        assert outs == plain
        # Drained: per-lane state resets optimistic, so the next
        # admission dispatches at full width again. ("done" lands before
        # the round's width recompute — give the loop a beat.)
        deadline = time.monotonic() + 10.0
        while eng._gamma != eng._gamma_max and time.monotonic() < deadline:
            time.sleep(0.05)
        assert eng._gamma == eng._gamma_max == 4
    finally:
        eng.shutdown()


def test_adaptive_gamma_stays_high_with_perfect_draft():
    """draft == target ⇒ acceptance 1.0 ⇒ the dial never leaves the full
    gamma."""
    import jax
    import jax.numpy as jnp

    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.transformer import init_params

    params = init_params(
        jax.random.PRNGKey(5), get_config("tiny-llama"), jnp.float32
    )
    cfg = dataclasses.replace(SPEC_CONFIG, spec_gamma=4)
    eng = InferenceEngine(cfg, params=params, draft_params=params)
    try:
        reqs = [GenRequest(prompt=p, max_new_tokens=12) for p in PROMPTS]
        for r in reqs:
            eng.submit(r)
        for r in reqs:
            tokens, done, error = _collect(r)
            assert error is None and done is not None
        assert eng._gamma == 4
        assert eng.metrics.snapshot()["spec_acceptance"] == 1.0
    finally:
        eng.shutdown()


def test_adaptive_gamma_off_pins_full_gamma():
    """POLYKEY_ADAPTIVE_GAMMA=0 semantics: the ladder collapses to the
    configured gamma and the dial never moves."""
    cfg = dataclasses.replace(
        SPEC_CONFIG, spec_gamma=4, adaptive_gamma=False
    )
    eng = InferenceEngine(cfg)
    try:
        assert eng._gamma_low == eng._gamma_max == 4
        reqs = [GenRequest(prompt=p, max_new_tokens=8) for p in PROMPTS]
        for r in reqs:
            eng.submit(r)
        for r in reqs:
            tokens, done, error = _collect(r)
            assert error is None and done is not None
        assert eng._gamma == 4
    finally:
        eng.shutdown()


def test_spec_heterogeneous_draft_architecture(monkeypatch):
    """A REAL draft is a smaller model of the same family (config 5:
    gemma-2-2b drafting for 9b) — different depth/heads/widths, same
    vocab. The engine's draft pool must size itself from the DRAFT
    config, and greedy output must still equal the plain engine's."""
    from polykey_tpu.models.config import MODEL_REGISTRY, TINY_GEMMA

    monkeypatch.setitem(
        MODEL_REGISTRY, "tiny-gemma-draft",
        dataclasses.replace(
            TINY_GEMMA, name="tiny-gemma-draft",
            num_layers=1, num_heads=2, num_kv_heads=1,
            hidden_size=32, intermediate_size=64,
            query_pre_attn_scalar=16.0,
        ),
    )
    base = dataclasses.replace(BASE_CONFIG, model="tiny-gemma")
    plain, _ = _run_prompts(base)
    spec_cfg = dataclasses.replace(
        base, draft_model="tiny-gemma-draft", spec_gamma=3
    )
    spec, snap = _run_prompts(spec_cfg)
    assert spec == plain
    assert snap["drafts_proposed"] > 0


def test_spec_quantized_engine_greedy_matches_quantized_plain():
    """int8 weight-only target + int8 draft (the phase-C2 serving shape):
    the quantized spec engine's greedy stream must equal the quantized
    PLAIN engine's — quantization changes the logits, so the reference
    is the quantized plain engine, not the fp32 one."""
    plain_q, _ = _run_prompts(
        dataclasses.replace(BASE_CONFIG, quantize=True)
    )
    spec_q, snap = _run_prompts(
        dataclasses.replace(SPEC_CONFIG, quantize=True)
    )
    assert spec_q == plain_q
    assert snap["drafts_proposed"] > 0


def test_spec_top_k_one_is_greedy_end_to_end():
    """top_k=1 on the SPECULATIVE truncated path (top_p_candidates>0):
    draft and verify dists both collapse to the argmax, so the stream
    must equal the plain engine's greedy stream — a sharp check that the
    rank mask is applied identically on both sides of the rejection
    sampler."""
    plain, _ = _run_prompts(BASE_CONFIG)
    cfg = dataclasses.replace(SPEC_CONFIG, top_p_candidates=32)
    eng = InferenceEngine(cfg)
    try:
        outs = []
        for p in PROMPTS:
            r = GenRequest(prompt=p, max_new_tokens=8,
                           temperature=1.0, top_k=1, seed=5)
            eng.submit(r)
            tokens, done, error = _collect(r)
            assert error is None and done is not None
            outs.append(tokens)
        snap = eng.metrics.snapshot()
        assert snap.get("drafts_proposed", 0) > 0   # really speculative
        assert outs == plain
    finally:
        eng.shutdown()
