"""The hybrid stack (models/hybrid.py): a layer pattern of Mamba-2 mixers,
attention and latent expert layers, with per-slot recurrent state beside
the paged pool (engine/kv_cache.py SlotState).

Everything is compared with the plain reference tests/reference_nemotron_h.py
(float32, the token-by-token recurrence, no cache) on seeded weights at toy
size, on LOGITS. Tolerances, and why:

- F32_TOL = 2e-4 on logits of about unit scale: the served path in float32
  differs from the reference only in summation order (the chunked form,
  the paged attention, grouped matmuls); measured differences are 1e-6 to
  1e-5. The same weights in bfloat16 differ by 1e-2 and more, so a path
  that computed in lower precision fails it (test_bfloat16_fails_the_
  float32_tolerance).
- STATE_TOL = 1e-5 on stored state of about 1e-2 scale: same reason.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_nemotron_h as ref
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import InferenceEngine
from polykey_tpu.models.config import MODEL_REGISTRY, get_config
from polykey_tpu.models.hybrid import (
    FROM_PREVIOUS_ROW,
    FROM_SLOT,
    FROM_ZERO,
    ssd_chunks,
)
from polykey_tpu.models.transformer import init_params
from polykey_tpu.ops import hybrid_kernels
from polykey_tpu.ops.moe import moe_latent_held
import grouped_experts
from pattern_stack import SlotBatch, served, text, worst_margin

F32_TOL = 2e-4
STATE_TOL = 1e-5
CFG = get_config("tiny-hybrid")
BATCH = SlotBatch(CFG, ref, F32_TOL)
fresh, prefill, decode, decode_tail = (
    BATCH.fresh, BATCH.prefill, BATCH.decode, BATCH.decode_tail)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG, jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (200,), 3, 130), np.int32)


def test_reference_copy_is_the_benchmarks_file():
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.join(here, "..", "perfbench", "references", "nemotron_h.py")
    with open(bench) as a, open(os.path.join(here, "reference_nemotron_h.py")) as b:
        assert a.read() == b.read()


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_form_equals_the_recurrence(chunk):
    N, T, H, P, G, S = 3, 16, 8, 4, 2, 8
    k = jax.random.split(jax.random.PRNGKey(chunk), 6)
    x = jax.random.normal(k[0], (N, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (N, T, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = jax.random.normal(k[3], (N, T, G, S))
    Cm = jax.random.normal(k[4], (N, T, G, S))
    h0 = jax.random.normal(k[5], (N, H, P, S))
    kind = jnp.asarray([FROM_SLOT, FROM_PREVIOUS_ROW, FROM_ZERO])
    y, end = ssd_chunks(x, dt, A, Bm, Cm, h0, kind, chunk)

    def recurrence(h, n):
        ys = []
        for t in range(T):
            Bh = jnp.repeat(Bm[n, t], H // G, axis=0)
            Ch = jnp.repeat(Cm[n, t], H // G, axis=0)
            h = (jnp.exp(dt[n, t] * A)[:, None, None] * h
                 + (dt[n, t][:, None] * x[n, t])[:, :, None] * Bh[:, None, :])
            ys.append(jnp.einsum("hpn,hn->hp", h, Ch))
        return jnp.stack(ys), h

    y0, e0 = recurrence(h0[0], 0)
    y1, e1 = recurrence(e0, 1)                   # row 1 chains from row 0
    y2, e2 = recurrence(jnp.zeros_like(h0[0]), 2)
    # Summation order only (float32, values of order 10).
    np.testing.assert_allclose(y, jnp.stack([y0, y1, y2]), atol=2e-4)
    np.testing.assert_allclose(end, jnp.stack([e0, e1, e2]), atol=2e-4)


def test_state_update_kernel_matches_jnp():
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    B, H, P, N, G = 3, 8, 16, 16, 2
    args = (jax.random.normal(k[0], (B, H, P, N)),
            jax.random.uniform(k[1], (B, H)),
            jax.random.normal(k[2], (B, H, P)),
            jax.random.normal(k[3], (B, G, N)),
            jax.random.normal(k[4], (B, G, N)))
    want = hybrid_kernels.ssm_state_update_jnp(*args)
    got = hybrid_kernels.ssm_state_update(*args, interpret=True)
    for a, b in zip(got, want):      # the same float32 operations
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("rows", [5, 600])
def test_held_experts_kernel_matches_jnp(rows, monkeypatch):
    monkeypatch.setattr(hybrid_kernels, "MOE_ROW_TILE", 256)
    k = jax.random.split(jax.random.PRNGKey(rows), 4)
    L, E, inner = 32, 4, 256
    v = jax.random.normal(k[0], (rows, L))
    up = jax.random.normal(k[1], (E, L, inner)) * L ** -0.5
    down = jax.random.normal(k[2], (E, inner, L)) * inner ** -0.5
    w = jnp.where(jax.random.uniform(k[3], (rows, E)) > 0.5, 0.3, 0.0)
    want = hybrid_kernels.moe_held_experts_jnp(v, up, down, w)
    got = hybrid_kernels.moe_held_experts(v, up, down, w, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-4)   # summation order


@pytest.mark.parametrize("routing", list(grouped_experts.ROUTINGS))
@pytest.mark.parametrize("rows", grouped_experts.ROWS)
def test_grouped_held_experts_match_jnp(rows, routing):
    """The un-gated instance over rows sorted by expert (ISSUE 48)."""
    grouped_experts.check(rows, routing, gated=False)


@pytest.mark.parametrize("rows, form", grouped_experts.RULE)
def test_the_backend_alone_chooses_the_held_product(
        rows, form, params, monkeypatch):
    """`moe_held` of a latent layer: the jnp form off the chip; on it the
    grouped kernel at every row count, a decode step's 64 included."""
    grouped_experts.check_rule(
        rows, form, params["layers"]["moe"][0], CFG, monkeypatch)


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """The guide's share test: the routed parts of the four chips' shares
    plus the shared expert ONCE = the layer with every expert held, in the
    program and in the reference alike."""
    p = params["layers"]["moe"][0]
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    held, routed = CFG.experts_held // 2, CFG.n_routed_experts
    assert routed % held == 0 and routed // held == 4
    whole = {**p,
             "up": jax.random.normal(k[0], (routed, *p["up"].shape[1:])) * 0.2,
             "down": jax.random.normal(k[1], (routed, *p["down"].shape[1:])) * 0.2}
    no_shared = {"shared_down": jnp.zeros_like(p["shared_down"])}
    x = jax.random.normal(k[2], (2, 6, CFG.hidden_size))
    uncut = dataclasses.replace(CFG, experts_held=routed)
    want = moe_latent_held(whole, x, uncut)
    total = moe_latent_held({**whole, **no_shared}, x, uncut) * 0.0
    ref_total = jnp.zeros_like(x[0])
    for share in range(4):
        cfg = dataclasses.replace(
            CFG, experts_held=held, first_expert=share * held)
        mine = {**whole, **no_shared,
                "up": whole["up"][share * held:(share + 1) * held],
                "down": whole["down"][share * held:(share + 1) * held]}
        total = total + moe_latent_held(mine, x, cfg)
        ref_total = ref_total + ref.expert_layer(x[0], {
            **mine, "norm": jnp.ones_like(p["norm"])}, cfg) - x[0]
    shared_once = moe_latent_held(
        {**whole, "up": whole["up"] * 0.0}, x, uncut)
    np.testing.assert_allclose(total + shared_once, want, atol=F32_TOL)
    # The reference's shares add up to the program's routed sum (its input
    # is normed inside; these rows are fed un-normed, so compare through
    # the same gain-1 norm on both sides).
    normed = x[0] * jax.lax.rsqrt(
        jnp.mean(jnp.square(x[0]), -1, keepdims=True) + CFG.rms_norm_eps)
    routed_only = moe_latent_held({**whole, **no_shared}, normed[None], uncut)
    np.testing.assert_allclose(ref_total, routed_only[0], atol=F32_TOL)


# -- what a slot's state may and may not do (kv_cache.SlotState) ------------


def test_rule_i_a_first_window_starts_from_zero_state(params, tokens):
    ids = tokens[:13]
    want = ref.forward(params, CFG, ids)
    paged, state = fresh()
    dirty = jax.tree.map(lambda x: x + 3.0, state)      # the last occupant's
    got, _, _ = prefill(params, paged, dirty, 1, ids, 0, 16, [FROM_ZERO])
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)
    # The case bites: the same rows read FROM_SLOT see the dirt.
    bad, _, _ = prefill(params, paged, dirty, 1, ids, 0, 16, [FROM_SLOT])
    assert np.max(np.abs(bad - want)) > 100 * F32_TOL


@pytest.mark.parametrize("width", [16, 64])
def test_rule_ii_padding_never_advances_state(params, tokens, width):
    """13 real tokens in a window of 16 or of 64: the stored state is the
    one after the 13th token, whatever the padding; decode goes on from it."""
    ids = tokens[:20]
    want = ref.forward(params, CFG, ids)
    paged, state = fresh()
    got, paged, state = prefill(params, paged, state, 2, ids[:13], 0, width,
                                [FROM_ZERO])
    np.testing.assert_allclose(got, want[:13], atol=F32_TOL, rtol=0)
    exact, _, exact_state = prefill(params, *fresh(), 2, ids[:13], 0, 16,
                                    [FROM_ZERO])
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(exact_state)):
        np.testing.assert_allclose(a[2], b[2], atol=STATE_TOL)
    decode_tail(params, paged, state, 2, ids, 13, want)


def test_rule_iii_an_inactive_lane_is_not_advanced(params, tokens):
    ids = tokens[:12]
    paged, state = fresh()
    _, paged, state = prefill(params, paged, state, 0, ids, 0, 16, [FROM_ZERO])
    _, paged, state = prefill(params, paged, state, 3, ids, 0, 16, [FROM_ZERO])
    _, _, after = decode(params, paged, state, 3, int(ids[-1]), 12)
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(state)):
        np.testing.assert_array_equal(a[0], b[0])       # lane 0: bit for bit
        assert np.max(np.abs(np.asarray(a[3]) - np.asarray(b[3]))) > 0


def test_rule_iv_a_row_starts_where_the_row_above_ended(params, tokens):
    """28 tokens as two 16-rows of ONE dispatch (the cover of PR 41)."""
    ids = tokens[:36]
    want = ref.forward(params, CFG, ids)
    paged, state = fresh()
    got, paged, state = prefill(params, paged, state, 1, ids[:28], 0, 16,
                                [FROM_ZERO, FROM_PREVIOUS_ROW])
    np.testing.assert_allclose(got, want[:28], atol=F32_TOL, rtol=0)
    decode_tail(params, paged, state, 1, ids, 28, want)
    # The case bites: the second row from zero state is another model.
    bad, _, _ = prefill(params, *fresh(), 1, ids[:28], 0, 16,
                        [FROM_ZERO, FROM_ZERO])
    assert np.max(np.abs(bad[16:] - want[16:28])) > 100 * F32_TOL


def test_rule_v_a_chunk_starts_from_the_slots_stored_state(params, tokens):
    """84 tokens: a 64-wide chunk, then the tail's two 16-rows in a second
    dispatch, the first of them from what the slot stores."""
    ids = tokens[:90]
    want = ref.forward(params, CFG, ids)
    paged, state = fresh()
    head, paged, state = prefill(params, paged, state, 2, ids[:64], 0, 64,
                                 [FROM_ZERO])
    tail, paged, state = prefill(params, paged, state, 2, ids[64:84], 64, 16,
                                 [FROM_SLOT, FROM_PREVIOUS_ROW])
    np.testing.assert_allclose(head, want[:64], atol=F32_TOL, rtol=0)
    np.testing.assert_allclose(tail, want[64:84], atol=F32_TOL, rtol=0)
    decode_tail(params, paged, state, 2, ids, 84, want)


def test_bfloat16_fails_the_float32_tolerance(params, tokens):
    ids = tokens[:13]
    want = ref.forward(params, CFG, ids)
    low = jax.tree.map(
        lambda w: w.astype(jnp.bfloat16) if w.ndim > 1 else w, params)
    paged, state = fresh(jnp.bfloat16)
    got, _, _ = prefill(low, paged, state, 0, ids, 0, 16, [FROM_ZERO])
    assert np.max(np.abs(got - want)) > 10 * F32_TOL


# -- through the engine ------------------------------------------------------

ENGINE = EngineConfig(
    model="tiny-hybrid", tokenizer="byte", dtype="float32",
    max_decode_slots=2, page_size=8, num_pages=160, max_seq_len=256,
    prefill_buckets=(16, 64), max_new_tokens_cap=32, decode_block_steps=4,
)


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(ENGINE, seed=5)
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("tokens_in,reset,chained,resumed", [
    (10, 1, 0, 0),       # one window
    (28, 1, 1, 0),       # two 16-rows of one dispatch
    (84, 1, 1, 1),       # a 64-wide chunk, then the tail's two rows
    (150, 1, 1, 2),      # two chunks, then 22 tokens in two rows
])
def test_engine_serves_what_the_reference_computes(
        engine, tokens_in, reset, chained, resumed):
    before = engine.stats()
    prompt = text(tokens_in, tokens_in)
    (ids,) = served(engine, [prompt])
    assert len(ids) == 10
    # A served token is the reference's argmax up to summation order.
    assert worst_margin(ref, engine, prompt, ids) <= F32_TOL
    after = engine.stats()
    moved = {k: after[k] - before[k] for k in (
        "state_slots_reset", "state_windows_chained", "state_chunks_resumed")}
    assert moved == {"state_slots_reset": reset,
                     "state_windows_chained": chained,
                     "state_chunks_resumed": resumed}


@pytest.mark.parametrize("rule, want", [
    # As it answers on the chip (ISSUE 56): yes, at any row count — the
    # two 64-wide chunks a 150-token prompt rides and the two 16-rows in
    # one dispatch after them.
    (lambda rows: True, 160),
    # A rule of rows would still be asked dispatch by dispatch: the
    # chunks counted, the narrow dispatch not.
    (lambda rows: rows >= 64, 128),
    # As it answers off the chip: the jnp form ran every row.
    (None, 0),
], ids=["on-chip", "by-dispatch", "off-chip"])
def test_engine_counts_the_rows_its_expert_layers_ran_grouped(
        engine, rule, want, monkeypatch):
    """`prefill_rows_grouped_experts` (ISSUE 48): host-known, counted by
    the function `moe_held` decides by, per dispatch."""
    from polykey_tpu.engine import engine as engine_mod

    if rule is not None:
        monkeypatch.setattr(engine_mod, "held_experts_grouped", rule)
    before = engine.stats()
    served(engine, [text(150, 150)])
    after = engine.stats()
    grew = {k: after[k] - before[k] for k in (
        "prefill_rows_grouped_experts", "prefill_rows_dispatched")}
    assert grew == {"prefill_rows_grouped_experts": want,
                    "prefill_rows_dispatched": 160}


def test_engine_reuses_slots_and_batches_lanes(engine):
    """Seven requests on two slots, submitted at once: every slot is taken
    again after another request left its state there, lanes decode side
    by side and turn over at different steps."""
    prompts = [text(n, 100 + n) for n in (9, 30, 70, 12, 40, 20, 90)]
    outs = served(engine, prompts, new=9)
    for prompt, ids in zip(prompts, outs):
        assert len(ids) == 9
        assert worst_margin(ref, engine, prompt, ids) <= F32_TOL


def test_engine_stats_name_the_state(engine):
    stats = engine.stats()
    assert stats["state_pool_bytes"] == engine.state.nbytes > 0
    per_slot_layer = (
        CFG.mamba_num_heads * CFG.mamba_head_dim * CFG.ssm_state_size * 4
        + (CFG.conv_kernel - 1) * CFG.conv_dim * 4)      # float32 engine
    assert stats["state_pool_bytes"] == (
        ENGINE.max_decode_slots * CFG.layer_pattern.count("M") * per_slot_layer)


def test_a_model_without_state_allocates_none():
    from polykey_tpu.engine.kv_cache import SlotState

    assert jax.tree.leaves(SlotState()) == []
    assert SlotState().nbytes == 0
    assert not get_config("tiny-llama").stateful


@pytest.mark.parametrize("knob", [
    {"prefix_cache": True},
    {"prefix_cache": True, "host_kv_bytes": 1 << 20},
    {"disagg": "prefill=1,decode=1"},
    {"disagg_tier": "prefill"},
    {"draft_model": "tiny-hybrid"},
    {"tp": 2},
    {"pp": 2},
    {"dp": 2},
    {"quantize": True},
    {"kv_dtype": "int8"},
])
def test_features_that_cannot_carry_state_are_refused(knob):
    with pytest.raises(ValueError, match="per-slot recurrent state"):
        dataclasses.replace(ENGINE, **knob).validate()
    # The same knob on a model without state is not refused HERE.
    other = dataclasses.replace(ENGINE, model="tiny-llama", **knob)
    try:
        other.validate()
    except ValueError as e:
        assert "recurrent state" not in str(e)


def test_a_stateful_model_registered_late_is_still_refused():
    """The harness builds its EngineConfig BEFORE it registers the
    ModelConfig (perfbench/server_child.py): the refusal looks the model up
    when the engine validates, and an unknown name is never waved through."""
    late = dataclasses.replace(ENGINE, model="hybrid-registered-late",
                               prefix_cache=True)
    with pytest.raises(ValueError, match="unknown model"):
        late.validate()
    MODEL_REGISTRY[late.model] = dataclasses.replace(CFG, name=late.model)
    try:
        with pytest.raises(ValueError, match="per-slot recurrent state"):
            InferenceEngine(late)
        dataclasses.replace(late, prefix_cache=False).validate()
    finally:
        del MODEL_REGISTRY[late.model]


def test_heap_release_after_warmup_is_harmless():
    """engine/device.release_compile_heap: a collection and glibc's
    malloc_trim, a no-op where there is none; what it is for is a chip
    finding (PERF.md section 7: the hole in a compiling run's window)."""
    from polykey_tpu.engine import device

    before = np.arange(8)
    device.release_compile_heap()
    device.release_compile_heap()
    np.testing.assert_array_equal(before, np.arange(8))


def test_layer_pattern_must_fit_the_depth():
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(CFG, layer_pattern="ME")
    with pytest.raises(ValueError, match="experts held"):
        dataclasses.replace(CFG, first_expert=12)
    assert "tiny-hybrid" in MODEL_REGISTRY


def test_gateway_pool_admits_a_stream_for_every_slot(engine):
    """A streaming RPC holds a worker for its life: the pool follows the
    engine's slots, and stays at 32 for every engine of 16 or fewer."""
    from types import SimpleNamespace

    from polykey_tpu.gateway.server import rpc_workers

    def service(slots):
        return SimpleNamespace(engine=SimpleNamespace(
            config=SimpleNamespace(max_decode_slots=slots)))

    assert rpc_workers(service(64)) == 128
    assert rpc_workers(service(16)) == 32
    assert rpc_workers(object()) == 32          # the mock backend
    assert rpc_workers(SimpleNamespace(engine=engine)) == 32
