"""Ragged paged attention tests (ISSUE 12).

Three layers:
- KERNEL: the ragged Pallas kernel (interpret mode on CPU) against the
  per-token gather reference across the feature matrix — mixed
  prefill+decode streams, GQA, soft-capping, sliding windows,
  multi-tile ranges, decode-only and prefill-only streams, the int8-KV
  quantized variant, and the token-tile alignment gate's teeth.
- ENGINE: greedy output streams BIT-IDENTICAL between the ragged and
  bucketed dispatch modes at lookahead depths 1 and 2 (the acceptance
  criterion), sampled streams identical (draws key on (seed, position),
  never on batch shape), mixed-batch edge cases (prefill-only cold
  burst, budget-clipped chunk tail, decode-only steady state), the
  padding-waste accounting, the kill-switch, and config validation.
- CHAOS: PR 3 supervisor restart and PR 7 replica failover/resume
  semantics unchanged with the ragged path enabled.
"""

import dataclasses
import queue
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu import faults
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.ops.paged_attention import quantize_kv_rows
from polykey_tpu.ops.ragged_paged_attention_kernel import (
    ragged_gather_attention,
    ragged_paged_attention,
)

TOL = 2e-5


@pytest.fixture(autouse=True)
def _clean_injector(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.clear()
    yield
    faults.clear()


# -- kernel: interpret-mode parity vs the gather reference --------------------


def _ragged_case(seed, seq_lens, kv_lens, *, N=32, ps=8, Hk=2, Hq=4,
                 D=32, P=8, pad_to=8, dtype=jnp.float32):
    """Build a ragged stream: ascending contiguous ranges (row padding
    at the tail), random pools/tables, plus the per-token view the
    gather reference consumes."""
    rng = np.random.default_rng(seed)
    seq_lens = np.asarray(seq_lens, np.int32)
    kv_lens = np.asarray(kv_lens, np.int32)
    S = len(seq_lens)
    starts = np.concatenate([[0], np.cumsum(seq_lens)[:-1]]).astype(np.int32)
    used = int(seq_lens.sum())
    T = -(-used // pad_to) * pad_to
    # Pools in the stored layout (engine/kv_cache.py): heads folded.
    kp = jnp.asarray(rng.normal(size=(N, ps, Hk * D)), dtype)
    vp = jnp.asarray(rng.normal(size=(N, ps, Hk * D)), dtype)
    tables = rng.integers(1, N, size=(S, P)).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(T, Hq, D)), dtype)
    rows = np.arange(T)
    sid = np.clip(np.searchsorted(starts, rows, side="right") - 1, 0, S - 1)
    in_seq = (rows >= starts[sid]) & (rows < starts[sid] + seq_lens[sid])
    pos = np.where(in_seq, kv_lens[sid] - seq_lens[sid] + rows - starts[sid], 0)
    tok_tables = np.where(in_seq[:, None], tables[sid], 0)
    return dict(
        q=q, kp=kp, vp=vp, tables=jnp.asarray(tables),
        starts=jnp.asarray(starts), lens=jnp.asarray(seq_lens),
        kvs=jnp.asarray(kv_lens), in_seq=in_seq,
        tok_tables=jnp.asarray(tok_tables), pos=jnp.asarray(pos),
    )


def _kernel_vs_gather(case, **kw):
    out_k = ragged_paged_attention(
        case["q"], case["kp"], case["vp"], case["tables"],
        case["starts"], case["lens"], case["kvs"], interpret=True, **kw,
    )
    out_g = ragged_gather_attention(
        case["q"], case["kp"], case["vp"], case["tok_tables"],
        case["pos"], scale=kw["scale"],
        logit_softcap=kw.get("logit_softcap"), window=kw.get("window"),
    )
    err = np.abs(np.asarray(out_k) - np.asarray(out_g))[case["in_seq"]]
    return float(err.max())


@pytest.mark.parametrize("softcap,win", [
    (None, None), (30.0, None), (None, 16), (30.0, 16),
])
def test_ragged_kernel_matches_gather(softcap, win):
    """Mixed stream: decode singles + prefill chunks, across the
    softcap/window matrix."""
    case = _ragged_case(0, seq_lens=[1, 11, 1, 5], kv_lens=[37, 20, 5, 48])
    w = None if win is None else jnp.int32(win)
    assert _kernel_vs_gather(
        case, scale=0.125, logit_softcap=softcap, window=w,
    ) < TOL


def test_ragged_kernel_multi_tile_ranges():
    """A chunk spanning several token tiles, odd page-group divisor
    (P % G != 0 exercises the ceil grid arithmetic)."""
    case = _ragged_case(
        1, seq_lens=[1, 29, 3, 1], kv_lens=[11, 29, 40, 63],
        P=7, N=64,
    )
    assert _kernel_vs_gather(case, scale=0.2, pages_per_block=2) < TOL


def test_ragged_kernel_decode_only_stream():
    """48 decode singles pack ceil(48/8) tiles — the steady-state shape."""
    lens = [1] * 48
    kvs = list(np.random.default_rng(3).integers(1, 60, size=48))
    case = _ragged_case(2, seq_lens=lens, kv_lens=kvs, N=64)
    assert _kernel_vs_gather(case, scale=0.125) < TOL


def test_ragged_kernel_prefill_only_stream():
    """One cold chunk, no decode rows (kv_len == seq_len: pure prefill
    attending over its own freshly-written window)."""
    case = _ragged_case(4, seq_lens=[24], kv_lens=[24])
    assert _kernel_vs_gather(case, scale=0.125) < TOL


def test_ragged_kernel_gqa_no_grouping():
    case = _ragged_case(5, seq_lens=[1, 9], kv_lens=[33, 9], Hk=4, Hq=4)
    assert _kernel_vs_gather(case, scale=0.125) < TOL


def test_ragged_kernel_quantized_matches_gather():
    """int8-KV variant: scale-page DMA + in-kernel dequant must match
    the int8 gather path tightly, and the fp gather loosely (bounded
    quantization error)."""
    case = _ragged_case(6, seq_lens=[1, 11, 4], kv_lens=[37, 20, 30])
    from polykey_tpu.engine.kv_cache import fold_heads, unfold_heads

    k8, ks = quantize_kv_rows(unfold_heads(case["kp"], 32))
    v8, vs = quantize_kv_rows(unfold_heads(case["vp"], 32))
    k8, v8 = fold_heads(k8), fold_heads(v8)
    out_k = ragged_paged_attention(
        case["q"], (k8, ks), (v8, vs), case["tables"],
        case["starts"], case["lens"], case["kvs"],
        scale=0.125, interpret=True,
    )
    out_g = ragged_gather_attention(
        case["q"], (k8, ks), (v8, vs), case["tok_tables"], case["pos"],
        scale=0.125,
    )
    err = np.abs(np.asarray(out_k) - np.asarray(out_g))[case["in_seq"]]
    assert float(err.max()) < TOL
    out_fp = ragged_gather_attention(
        case["q"], case["kp"], case["vp"], case["tok_tables"],
        case["pos"], scale=0.125,
    )
    qerr = np.abs(np.asarray(out_k) - np.asarray(out_fp))[case["in_seq"]]
    assert float(qerr.max()) < 0.05   # quantization error, not a bug


def test_ragged_kernel_tile_alignment_raises():
    case = _ragged_case(7, seq_lens=[1, 4], kv_lens=[9, 4])
    with pytest.raises(ValueError, match="token_tile"):
        ragged_paged_attention(
            case["q"][:5], case["kp"], case["vp"], case["tables"],
            case["starts"], case["lens"], case["kvs"],
            scale=0.125, interpret=True,
        )


# -- engine: ragged vs bucketed bit-identity ----------------------------------


BASE = EngineConfig(
    model="tiny-llama", tokenizer="byte", dtype="float32",
    max_decode_slots=4, page_size=8, num_pages=64, max_seq_len=64,
    prefill_buckets=(16, 32), max_new_tokens_cap=16,
    decode_block_steps=4, lookahead_blocks=2,
    compile_warmup=False, supervise=False, signals_interval_s=0,
)
RAGGED = dataclasses.replace(BASE, ragged_dispatch=True)


def _drain(request, timeout=60.0):
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


def _serve(config, specs, depth=None, seed=0, monkeypatch=None):
    if depth is not None:
        monkeypatch.setenv("POLYKEY_DISPATCH_LOOKAHEAD", str(depth))
    engine = InferenceEngine(config, seed=seed)
    try:
        requests = [GenRequest(**s) for s in specs]
        for r in requests:
            engine.submit(r)
        outs = []
        for r in requests:
            tokens, done, error = _drain(r)
            assert error is None, error
            assert done is not None
            outs.append(tokens)
        stats = engine.stats()
    finally:
        engine.shutdown()
    return outs, stats


@pytest.mark.parametrize("depth", [1, 2])
def test_ragged_greedy_streams_bit_identical(depth, monkeypatch):
    """THE acceptance criterion: greedy output streams are bit-identical
    between the ragged and bucketed paths at lookahead depths 1 and 2 —
    short prompts (admissions), a beyond-bucket prompt (chunk
    advancement), and concurrent decode (mixed dispatches)."""
    specs = [
        dict(prompt="hi", max_new_tokens=8, seed=11),
        dict(prompt="abcdefgh" * 2, max_new_tokens=8, seed=11),
        dict(prompt="abcdefgh" * 6, max_new_tokens=8, seed=11),  # chunked
        dict(prompt="xyz", max_new_tokens=8, seed=11),
    ]
    bucketed, _ = _serve(BASE, specs, depth, monkeypatch=monkeypatch)
    ragged, stats = _serve(RAGGED, specs, depth, monkeypatch=monkeypatch)
    assert ragged == bucketed
    assert stats["ragged"] is True


def test_ragged_sampled_streams_identical():
    """Sampled draws key on fold_in(seed, position) — batch- and
    path-independent, so even sampled streams match across modes."""
    specs = [
        dict(prompt="hello world", max_new_tokens=6, temperature=0.9,
             top_p=0.8, top_k=5, seed=42),
        dict(prompt="abcdefgh" * 3, max_new_tokens=6, temperature=1.0,
             seed=7),
    ]
    bucketed, _ = _serve(BASE, specs)
    ragged, _ = _serve(RAGGED, specs)
    assert ragged == bucketed


def test_ragged_prefill_only_cold_burst():
    """Cold burst filling every slot from idle: more prompt tokens than
    one ragged stream holds, so admission ranges span several
    prefill-only dispatches — all streams complete and match the
    bucketed mode."""
    specs = [
        dict(prompt="abcdefgh" * 3, max_new_tokens=4, seed=3)
        for _ in range(4)
    ]
    bucketed, _ = _serve(BASE, specs)
    ragged, stats = _serve(RAGGED, specs)
    assert ragged == bucketed
    assert stats["tokens_useful"] > 0


def test_ragged_budget_clipped_chunk_tail(monkeypatch):
    """A long prompt whose chunk ranges clip against the stream width /
    budget while another lane decodes: the tail range is partial and
    the stream stays correct."""
    cfg_b = dataclasses.replace(BASE, prefill_budget=16, prefill_chunk=16)
    cfg_r = dataclasses.replace(cfg_b, ragged_dispatch=True)
    specs = [
        dict(prompt="warm", max_new_tokens=12, seed=9),
        dict(prompt="abcdefgh" * 7, max_new_tokens=6, seed=9),  # 56 > W=16
    ]
    bucketed, _ = _serve(cfg_b, specs)
    ragged, stats = _serve(cfg_r, specs)
    assert ragged == bucketed
    # The clipped tail means strictly more than one ragged dispatch
    # carried prefill tokens.
    assert stats["prefill_tokens_total"] >= 56


def test_ragged_decode_only_iterations_keep_block_path():
    """Steady-state decode (no prefill pending) must keep the K-step
    block path: steps_dispatched outgrows blocks_dispatched, which only
    multi-step blocks produce (a ragged dispatch is steps=1; adaptive
    blocking is pinned off so the solo stream doesn't shrink K)."""
    specs = [dict(prompt="abc", max_new_tokens=12, seed=1)]
    _, stats = _serve(
        dataclasses.replace(RAGGED, adaptive_block=False), specs
    )
    assert stats["steps_dispatched"] > stats["blocks_dispatched"]


def test_ragged_padding_waste_accounting():
    _, stats = _serve(RAGGED, [dict(prompt="abcd" * 4, max_new_tokens=4)])
    assert stats["tokens_dispatched"] >= stats["tokens_useful"] > 0
    assert 0.0 < stats["tokens_useful_fraction"] <= 1.0
    _, bstats = _serve(BASE, [dict(prompt="abcd" * 4, max_new_tokens=4)])
    assert bstats["tokens_dispatched"] >= bstats["tokens_useful"] > 0


def test_ragged_kill_switch(monkeypatch):
    monkeypatch.setenv("POLYKEY_DISABLE_RAGGED", "1")
    engine = InferenceEngine(RAGGED, seed=0)
    try:
        assert engine._ragged is False
        r = GenRequest(prompt="still serves", max_new_tokens=4)
        engine.submit(r)
        tokens, done, error = _drain(r)
        assert error is None and done is not None and len(tokens) == 4
    finally:
        engine.shutdown()


def test_ragged_config_validation():
    # Speculative decoding composes with ragged dispatch since ISSUE 19
    # (verify windows ride the flat stream) — the old refusal is gone.
    dataclasses.replace(RAGGED, draft_model="tiny-llama").validate()
    with pytest.raises(ValueError, match="tp-at-most"):
        dataclasses.replace(RAGGED, dp=2).validate()
    with pytest.raises(ValueError, match="tp-at-most"):
        dataclasses.replace(RAGGED, sp=2).validate()


# -- recompile stability (smoke-scale census) ---------------------------------


def test_ragged_engine_recompile_stable():
    """Warmed ragged engine: the serving sweep (admissions, chunked
    prompt, retires, both depths) compiles NOTHING new — the single
    resident ragged executable plus the decode blocks serve every
    shape; the bucketed prefill handle's cache never grows."""
    from polykey_tpu.analysis.graph import drive_engine, recompile_findings

    config = dataclasses.replace(
        RAGGED, compile_warmup=True, warm_sampled_variants=False,
    )
    engine = InferenceEngine(config, seed=0)
    try:
        handles = {
            "_jit_ragged": engine._jit_ragged,
            "_jit_decode": engine._jit_decode,
            "_jit_merge": engine._jit_merge,
            "_jit_retire": engine._jit_retire,
            "_jit_prefill": engine._jit_prefill,   # growth watch only
        }
        prefill_before = engine._jit_prefill._cache_size()
        waves = [
            [GenRequest(prompt="abc", max_new_tokens=4, seed=2),
             GenRequest(prompt="abcdefgh" * 2, max_new_tokens=4, seed=2)],
            [GenRequest(prompt="abcdefgh" * 6, max_new_tokens=4, seed=2)],
        ]

        def sweep():
            configured = engine._depth
            try:
                errors = []
                for depth in (1, 2):
                    engine._depth = depth
                    errors.extend(drive_engine(engine, waves))
                return errors
            finally:
                engine._depth = configured

        findings, sizes = recompile_findings("ragged-smoke", {
            k: v for k, v in handles.items() if k != "_jit_prefill"
        }, sweep)
        assert findings == [], [f.message for f in findings]
        # The bucketed prefill executables are GONE from this engine's
        # serving: nothing compiled them during the sweep.
        assert engine._jit_prefill._cache_size() == prefill_before
    finally:
        engine.shutdown()


# -- chaos: supervisor + failover semantics unchanged -------------------------


CHAOS_RAGGED = dataclasses.replace(
    RAGGED,
    max_decode_slots=1, max_seq_len=128, num_pages=32,
    prefill_buckets=(16,), max_new_tokens_cap=32,
    decode_block_steps=1, adaptive_block=False, lookahead_blocks=1,
    compile_warmup=True, warm_sampled_variants=False,
    watchdog_timeout_s=0.3, max_queue_depth=0, supervise=True,
)


def _await(predicate, timeout=20.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def test_ragged_supervisor_restart():
    """PR 3 semantics with ragged on: an injected step-stall trips the
    watchdog, the supervisor restarts into a FRESH ragged engine, and
    serving resumes — the ragged dispatch path changes nothing about
    trip/restart/recovery."""
    from polykey_tpu.engine.supervisor import EngineSupervisor
    from polykey_tpu.engine.watchdog import Watchdog
    from polykey_tpu.gateway.health import SERVING, HealthService

    faults.install("step-stall=1.0@1")
    engine = InferenceEngine(CHAOS_RAGGED)
    health = HealthService()
    health.set_serving_status("", SERVING)
    watchdog = Watchdog(engine, health=health, check_interval_s=0.05)
    watchdog.start()
    supervisor = EngineSupervisor(
        engine, lambda: InferenceEngine(CHAOS_RAGGED),
        watchdog=watchdog, health=health,
        max_restarts=2, restart_window_s=60.0,
        check_interval_s=0.05, join_timeout_s=5.0,
    ).start()
    try:
        victim = GenRequest(prompt="stall victim", max_new_tokens=8)
        engine.submit(victim)
        assert _await(lambda: watchdog.tripped or supervisor.restarts > 0,
                      timeout=10.0)
        _, done, error = _drain(victim, timeout=15.0)
        assert done is None and error is not None
        assert _await(lambda: supervisor.restarts == 1, timeout=15.0)
        fresh = supervisor.engine
        assert fresh is not engine and fresh._ragged
        ok = GenRequest(prompt="after restart", max_new_tokens=6)
        fresh.submit(ok)
        tokens, done, error = _drain(ok, timeout=15.0)
        assert error is None and done is not None and len(tokens) == 6
    finally:
        supervisor.stop()
        watchdog.stop()
        supervisor.engine.shutdown()


def test_ragged_pool_resume_bit_identical():
    """PR 7 semantics with ragged on: replica death mid-stream resumes
    the greedy stream bit-identically on the surviving replica."""
    from polykey_tpu.engine.replica_pool import ReplicaPool

    config = dataclasses.replace(
        CHAOS_RAGGED, max_decode_slots=2, replicas=2,
    )
    pool = ReplicaPool.create(
        config, watchdog_interval_s=0.05, supervisor_interval_s=0.05,
    )
    try:
        prompt = "ragged failover determinism probe"
        baseline = GenRequest(prompt=prompt, max_new_tokens=12)
        pool.submit(baseline)
        base_tokens, base_done, base_error = _drain(baseline)
        assert base_error is None and base_done is not None
        assert len(base_tokens) == 12

        # In ragged mode the PREFILL rides _dispatch_step (fault sleeps
        # included), so arming step-stall up front would wedge the
        # dispatch BEFORE the first token — a queued requeue, not the
        # mid-stream resume this test pins. Pace the replica, let a few
        # tokens flow, THEN wedge it.
        pool.replicas[0].engine._faults = faults.install(
            "slow-step=0.1:replica=0"
        )
        victim = GenRequest(prompt=prompt, max_new_tokens=12)
        pool.submit(victim)
        assert victim.replica == 0
        head = []
        for _ in range(3):
            kind, value = victim.out.get(timeout=30)
            assert kind == "token", value
            head.append(value)
        pool.replicas[0].engine._faults = faults.install(
            "slow-step=0.1:replica=0,step-stall=1.0@1:replica=0"
        )
        tokens, done, error = _drain(victim)
        assert error is None and done is not None
        assert head + tokens == base_tokens
        assert pool.stats()["streams_resumed"] >= 1
    finally:
        pool.shutdown()


# -- forward_ragged routes to gather under meshed extents ---------------------


def test_forward_ragged_gather_under_mesh(monkeypatch):
    """With any mesh extent > 1 the ragged kernel (un-shard_mapped)
    must NOT be chosen even where the geometry gate passes — the
    GSPMD-partitionable gather path serves instead."""
    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    calls = {"kernel": 0}

    def fake_kernel(*a, **k):
        calls["kernel"] += 1
        raise AssertionError("kernel path must not be taken under mesh")

    monkeypatch.setattr(
        "polykey_tpu.ops.ragged_paged_attention_kernel.use_ragged_kernel",
        lambda *_: True,
    )

    from polykey_tpu.engine.kv_cache import init_paged_kv
    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.transformer import forward_ragged, init_params

    cfg = get_config("tiny-llama")
    mesh = create_mesh(MeshConfig(tp=2), jax.devices()[:2]) \
        if len(jax.devices()) >= 2 else None
    if mesh is None:
        pytest.skip("needs >= 2 devices (conftest forces 8 CPU devices)")
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    paged = init_paged_kv(cfg, 16, 8, jnp.float32)
    T, P = 8, 4
    tokens = jnp.zeros((T,), jnp.int32)
    positions = jnp.zeros((T,), jnp.int32)
    token_tables = jnp.zeros((T, P), jnp.int32)
    starts = jnp.asarray([0, 1], jnp.int32)
    lens = jnp.asarray([1, 1], jnp.int32)
    kvs = jnp.asarray([1, 1], jnp.int32)
    seq_tables = jnp.zeros((2, P), jnp.int32)
    monkeypatch.setattr(
        "polykey_tpu.ops.ragged_paged_attention_kernel._ragged_call",
        fake_kernel,
    )
    hidden, _ = forward_ragged(
        params, cfg, tokens, positions, paged, token_tables,
        starts, lens, kvs, seq_tables, mesh=mesh,
    )
    assert hidden.shape == (T, cfg.hidden_size)
    assert calls["kernel"] == 0


@pytest.mark.parametrize("kv_dtype", [None, jnp.int8])
def test_forward_ragged_gates_on_head_geometry(monkeypatch, kv_dtype):
    """The kernel gate sees (num_kv_heads, head_dim) for fp and int8 pools
    alike: the LAST two axes of the stacked 5-D [L, N, ps, Hk, D] pool,
    not axes 2,3 (page_size, Hk) — gated on those, the fp ragged kernel
    can never be chosen on TPU."""
    from polykey_tpu.engine.kv_cache import init_paged_kv
    from polykey_tpu.models.config import get_config
    from polykey_tpu.models.transformer import forward_ragged, init_params

    seen = []
    monkeypatch.setattr(
        "polykey_tpu.ops.ragged_paged_attention_kernel.use_ragged_kernel",
        lambda Hk, D: seen.append((Hk, D)) or False,
    )
    cfg = get_config("tiny-llama")
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    paged = init_paged_kv(cfg, 16, 8, jnp.float32, kv_dtype=kv_dtype)
    T, P = 8, 4
    zeros = jnp.zeros((T,), jnp.int32)
    ones = jnp.asarray([1, 1], jnp.int32)
    forward_ragged(
        params, cfg, zeros, zeros, paged, jnp.zeros((T, P), jnp.int32),
        jnp.asarray([0, 1], jnp.int32), ones, ones,
        jnp.zeros((2, P), jnp.int32),
    )
    assert cfg.num_kv_heads != 8                 # page_size: tells them apart
    assert seen == [(cfg.num_kv_heads, cfg.head_dim)]
