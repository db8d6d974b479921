"""An operator + feed-forward pattern (models/hybrid.py "C", "D", "*" with
q/k norms, "E" gated on the full hidden; preset `tiny-lfm2`): gated
short-convolution operators whose only per-slot state is the conv's last
columns (engine/kv_cache.py SlotState), QK-normed RoPE attention, a dense
part and gated experts all held, one tied matrix.

Everything is compared with the plain reference tests/reference_lfm2_moe.py
(float32, no cache) on seeded weights at toy size, on LOGITS; the gains
(norms, q/k norms) are seeded away from 1 so that a misplaced norm shows.
Tolerances, and why:

- F32_TOL = 2e-4 on logits of about unit scale: the served path in float32
  differs from the reference only in summation order (paged attention,
  the one-pass expert product); measured differences are 1e-6 to 1e-5.
  The same weights in bfloat16 differ by 1e-2 and more
  (test_bfloat16_fails_the_float32_tolerance).
- STATE_TOL = 1e-5 on stored conv columns of about unit scale: same reason.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_lfm2_moe as ref
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import InferenceEngine
from polykey_tpu.engine.kv_cache import init_slot_state
from polykey_tpu.models import hybrid
from polykey_tpu.models.config import MODEL_REGISTRY, get_config
from polykey_tpu.models.hybrid import (
    FROM_PREVIOUS_ROW,
    FROM_SLOT,
    FROM_ZERO,
    PrefillRows,
)
from polykey_tpu.models.transformer import init_params, unembed
from polykey_tpu.ops import hybrid_kernels
from polykey_tpu.ops.moe import held_router_weights, moe_gated_held, moe_held
import grouped_experts
from pattern_stack import SLOTS, SlotBatch, served, text, worst_margin

F32_TOL = 2e-4
STATE_TOL = 1e-5
CFG = get_config("tiny-lfm2")
BATCH = SlotBatch(CFG, ref, F32_TOL)
fresh, prefill, decode, decode_tail = (
    BATCH.fresh, BATCH.prefill, BATCH.decode, BATCH.decode_tail)


def with_seeded_gains(params, key):
    """Every norm gain (layer norms, q/k norms, the final norm) drawn in
    [0.5, 1.5]; the router's bias stays what the init made it."""
    def seeded(path, w):
        name = path[-1].key
        if w.ndim != 1 or name == "router_bias":
            return w
        salt = sum(map(ord, jax.tree_util.keystr(path)))
        return jax.random.uniform(
            jax.random.fold_in(key, salt), w.shape, w.dtype, 0.5, 1.5)

    return jax.tree_util.tree_map_with_path(seeded, params)


@pytest.fixture(scope="module")
def params():
    return with_seeded_gains(
        init_params(jax.random.PRNGKey(0), CFG, jnp.float32),
        jax.random.PRNGKey(9))


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (200,), 3, 130), np.int32)


def test_reference_copy_is_the_benchmarks_file():
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.join(here, "..", "perfbench", "references", "lfm2_moe.py")
    with open(bench) as a, open(os.path.join(here, "reference_lfm2_moe.py")) as b:
        assert a.read() == b.read()


# -- the pieces ---------------------------------------------------------------


def test_pattern_is_operator_and_feed_forward_pairs():
    assert CFG.layer_pattern == "CDCD*ECECECE*ECECECE"
    assert CFG.num_layers == 20 and CFG.kv_layers == 2
    assert CFG.stateful and CFG.state_held == "short-conv columns"
    assert get_config("tiny-hybrid").state_held == "Mamba-2 h and conv columns"
    with pytest.raises(ValueError, match="'M', 'C'"):
        dataclasses.replace(CFG, layer_pattern="CX" * 10)


def test_tied_head_is_one_leaf(params):
    top = {k for k in params if k != "layers"}
    assert top == {"embed", "final_norm"}
    assert params["embed"].shape == (CFG.vocab_size, CFG.hidden_size)
    hidden = jax.random.normal(jax.random.PRNGKey(3), (5, CFG.hidden_size))
    np.testing.assert_allclose(
        unembed(params, CFG, hidden), hidden @ params["embed"].T, atol=1e-5)
    # num_params counts the matrices (the conv's taps among them), each
    # once, and the vocabulary matrix ONCE.
    assert CFG.num_params() == sum(
        x.size for x in jax.tree.leaves(params) if x.ndim > 1)


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated-silu"])
@pytest.mark.parametrize("rows", [5, 600])
def test_held_experts_kernel_matches_jnp(rows, gated, monkeypatch):
    monkeypatch.setattr(hybrid_kernels, "MOE_ROW_TILE", 256)
    k = jax.random.split(jax.random.PRNGKey(rows), 5)
    L, E, inner = 32, 4, 256
    v = jax.random.normal(k[0], (rows, L))
    up = jax.random.normal(k[1], (E, L, inner)) * L ** -0.5
    down = jax.random.normal(k[2], (E, inner, L)) * inner ** -0.5
    w = jnp.where(jax.random.uniform(k[3], (rows, E)) > 0.5, 0.3, 0.0)
    how = {}
    if gated:
        how = {"gate": jax.random.normal(k[4], (E, L, inner)) * L ** -0.5,
               "activation": "silu"}
        act = jax.nn.silu(jnp.einsum("rl,eli->eri", v, how["gate"])) \
            * jnp.einsum("rl,eli->eri", v, up)
    else:
        # Today's un-gated instance, written out: relu(v up)² only.
        act = jnp.square(jax.nn.relu(jnp.einsum("rl,eli->eri", v, up)))
    written_out = jnp.einsum("eri,eil->rl", act * w.T[:, :, None], down)
    want = hybrid_kernels.moe_held_experts_jnp(v, up, down, w, **how)
    got = hybrid_kernels.moe_held_experts(v, up, down, w, interpret=True,
                                          **how)
    np.testing.assert_allclose(want, written_out, atol=1e-4)
    np.testing.assert_allclose(got, want, atol=1e-4)   # summation order


@pytest.mark.parametrize("routing", list(grouped_experts.ROUTINGS))
@pytest.mark.parametrize("rows", grouped_experts.ROWS)
def test_grouped_held_experts_match_jnp(rows, routing):
    """The gated instance over rows sorted by expert (ISSUE 48)."""
    grouped_experts.check(rows, routing, gated=True)


@pytest.mark.parametrize("rows, form", grouped_experts.RULE)
def test_the_backend_alone_chooses_the_held_product(
        rows, form, params, monkeypatch):
    """`moe_held` of gated experts on the full hidden: the jnp form off
    the chip; on it the grouped kernel at every row count, a decode step's
    64 included."""
    grouped_experts.check_rule(
        rows, form, params["layers"]["moe"][0], CFG, monkeypatch)


def test_router_bias_chooses_but_does_not_weigh():
    """A hand-computed case: logits (2, 1, 0, -1), top-2. The scores are
    (0.8808, 0.7311, 0.5, 0.2689); a bias of 0.5 on expert 2 lifts it over
    expert 1 in the CHOICE, and its weight is still its score, 0.5, over
    the chosen scores' sum + 1e-6."""
    cfg = dataclasses.replace(CFG, n_routed_experts=4, experts_held=4,
                              num_experts_per_tok=2)
    router = jnp.zeros((CFG.hidden_size, 4)).at[0].set(
        jnp.asarray([2.0, 1.0, 0.0, -1.0]))
    h = jnp.zeros((1, CFG.hidden_size)).at[0, 0].set(1.0)
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    plain = held_router_weights(
        {"router": router, "router_bias": jnp.zeros((4,))}, h, cfg)
    total = s[0] + s[1] + 1e-6
    np.testing.assert_allclose(
        plain[0], [s[0] / total, s[1] / total, 0.0, 0.0], atol=1e-6)
    biased = held_router_weights(
        {"router": router,
         "router_bias": jnp.asarray([0.0, 0.0, 0.5, 0.0])}, h, cfg)
    total = s[0] + s[2] + 1e-6
    np.testing.assert_allclose(
        biased[0], [s[0] / total, 0.0, s[2] / total, 0.0], atol=1e-6)
    np.testing.assert_allclose(biased[0, 2], 0.5 / total, atol=1e-6)


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """The guide's share test for the gated layer: four chips holding 4 of
    the 16 experts each (`first_expert`), their outputs summed, = the layer
    with every expert held, in the program; and = the uncut REFERENCE's
    layer."""
    p = params["layers"]["moe"][0]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 6, CFG.hidden_size))
    held = CFG.n_routed_experts // 4
    want = moe_gated_held(p, x, CFG)
    total = jnp.zeros_like(want)
    for share in range(4):
        cut = slice(share * held, (share + 1) * held)
        cfg = dataclasses.replace(
            CFG, experts_held=held, first_expert=share * held)
        mine = {**p, "gate": p["gate"][cut], "up": p["up"][cut],
                "down": p["down"][cut]}
        total = total + moe_held(mine, x, cfg)
    np.testing.assert_allclose(total, want, atol=F32_TOL)
    # The reference norms its input; feed it rows whose norm is the
    # identity's (gain 1) by norming them here the same way.
    normed = x[0] * jax.lax.rsqrt(
        jnp.mean(jnp.square(x[0]), -1, keepdims=True) + CFG.rms_norm_eps)
    uncut = ref.expert_layer(
        x[0], {**p, "norm": jnp.ones_like(p["norm"])}, CFG) - x[0]
    np.testing.assert_allclose(
        moe_gated_held(p, normed[None], CFG)[0], uncut, atol=F32_TOL)


def test_an_expert_form_nobody_computes_is_refused(params):
    p = params["layers"]["moe"][0]
    x = jnp.zeros((1, 2, CFG.hidden_size))
    with pytest.raises(ValueError, match="gate on the shared expert"):
        moe_held(p, x, dataclasses.replace(CFG, shared_expert_gate=True))
    with pytest.raises(ValueError, match="router_scoring"):
        moe_held(p, x, dataclasses.replace(CFG, router_scoring="tanh"))


def test_conv_decode_form_equals_its_prefill_form_column_by_column(params):
    """One row of 11 tokens through `conv_prefill` from zero, against the
    same tokens one `conv_decode` step at a time: outputs and the stored
    columns agree after every token; an inactive lane's columns stay."""
    p = params["layers"]["conv"][1]
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 11, CFG.hidden_size))
    conv0 = jnp.zeros((2, CFG.conv_kernel - 1, CFG.hidden_size))
    rows = PrefillRows(*(jnp.asarray([v], jnp.int32)
                         for v in (0, FROM_ZERO, 0, 11)))
    want, stored = hybrid.conv_prefill(p, u, CFG, conv0, rows)
    conv = conv0.at[1].set(7.0)             # lane 1: someone else's columns
    live = jnp.asarray([True, False])
    for t in range(11):
        both = jnp.stack([u[0, t], u[0, t]])
        out, conv = hybrid.conv_decode(p, both, conv, live)
        np.testing.assert_allclose(out[0], want[0, t], atol=1e-5)
    np.testing.assert_allclose(conv[0], stored[0], atol=STATE_TOL)
    np.testing.assert_array_equal(conv[1], jnp.full_like(conv[1], 7.0))


def test_qk_norm_comes_before_rotary(params, tokens, monkeypatch):
    """The program agrees with the reference, and NOT with a reference that
    rotates first and norms after (with gains away from 1 the two differ).
    The swapped reference is traced at a length no other test uses: its
    jitted layers are cached by shape."""
    ids = tokens[:19]
    want = ref.forward(params, CFG, ids[:17])
    got, _, _ = prefill(params, *fresh(), 0, ids, 0, 64, [FROM_ZERO])
    np.testing.assert_allclose(got[:17], want, atol=F32_TOL, rtol=0)
    norm, rotary, gains = ref.head_norm, ref.rotary, []

    def norm_later(x, weight, eps):
        gains.append(weight)
        return x

    monkeypatch.setattr(ref, "head_norm", norm_later)
    monkeypatch.setattr(
        ref, "rotary",
        lambda x, positions, theta: norm(
            rotary(x, positions, theta), gains.pop(0), CFG.rms_norm_eps))
    swapped = ref.forward(params, CFG, ids)
    assert np.max(np.abs(swapped - got)) > 100 * F32_TOL


# -- what a slot's state may and may not do (kv_cache.SlotState) ------------


def test_state_is_conv_columns_only():
    state = init_slot_state(CFG, SLOTS, jnp.float32)
    assert state.ssm == ()
    assert [c.shape for c in state.conv] == [
        (SLOTS, CFG.conv_kernel - 1, CFG.hidden_size)
    ] * CFG.layer_pattern.count("C")
    assert state.nbytes == SLOTS * 8 * 2 * CFG.hidden_size * 4
    # A pattern that holds both: one conv entry a mixer or conv operator,
    # in pattern order, and an `ssm` entry a mixer.
    both = dataclasses.replace(
        get_config("tiny-hybrid"), layer_pattern="MC*EM", conv_kernel=4)
    mixed = init_slot_state(both, 2, jnp.float32)
    assert len(mixed.ssm) == 2
    assert [c.shape[-1] for c in mixed.conv] == [
        both.conv_dim, both.hidden_size, both.conv_dim]


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
    """13 real tokens in a window of 16 or of 64: the stored columns are
    those after the 13th token, whatever the padding; prefill then decode
    through the cache = the reference's full forward."""
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


def test_rule_ii_a_row_shorter_than_the_taps_reaches_back(params, tokens):
    """A ONE-token tail after a chunk: the columns stored are the slot's
    last one and the new one."""
    ids = tokens[:20]
    want = ref.forward(params, CFG, ids)
    paged, state = fresh()
    _, paged, state = prefill(params, paged, state, 0, ids[:16], 0, 16,
                              [FROM_ZERO])
    tail, paged, state = prefill(params, paged, state, 0, ids[16:17], 16, 16,
                                 [FROM_SLOT])
    np.testing.assert_allclose(tail, want[16:17], atol=F32_TOL, rtol=0)
    decode_tail(params, paged, state, 0, ids, 17, want)


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
    model="tiny-lfm2", tokenizer="byte", dtype="float32",
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


def test_engine_reuses_a_slot_after_a_longer_occupant(engine):
    """Five requests on two slots, of unequal lengths: a short prompt takes
    a slot a longer one left its columns in; a lane whose stream ended
    stays inactive beside a live one until the next admission."""
    prompts = [text(n, 200 + n) for n in (70, 9, 30, 12, 40)]
    outs = served(engine, prompts, new=[6, 14, 5, 12, 7])
    for prompt, ids, n in zip(prompts, outs, (6, 14, 5, 12, 7)):
        assert len(ids) == n
        assert worst_margin(ref, engine, prompt, ids) <= F32_TOL


def test_engine_stats_name_the_state(engine):
    stats = engine.stats()
    assert stats["state_pool_bytes"] == engine.state.nbytes > 0
    assert engine.state.ssm == ()           # nothing held for a recurrence
    assert stats["state_pool_bytes"] == (
        ENGINE.max_decode_slots * CFG.layer_pattern.count("C")
        * (CFG.conv_kernel - 1) * CFG.hidden_size * 4)   # float32 engine


@pytest.mark.parametrize("knob", [
    {"prefix_cache": True},
    {"prefix_cache": True, "host_kv_bytes": 1 << 20},
    {"disagg": "prefill=1,decode=1"},
    {"disagg_tier": "prefill"},
    {"draft_model": "tiny-lfm2"},
    {"tp": 2},
    {"pp": 2},
    {"dp": 2},
    {"quantize": True},
    {"kv_dtype": "int8"},
])
def test_features_that_cannot_carry_state_are_refused(knob):
    """Exactly what the sibling stack refuses, each message naming the
    state THIS model holds."""
    with pytest.raises(ValueError, match="per-slot recurrent state") as e:
        dataclasses.replace(ENGINE, **knob).validate()
    assert "short-conv columns" in str(e.value)


def test_a_stateful_model_registered_late_is_still_refused():
    late = dataclasses.replace(ENGINE, model="lfm2-registered-late",
                               prefix_cache=True)
    with pytest.raises(ValueError, match="unknown model"):
        late.validate()
    MODEL_REGISTRY[late.model] = dataclasses.replace(CFG, name=late.model)
    try:
        with pytest.raises(ValueError, match="short-conv columns"):
            InferenceEngine(late)
        dataclasses.replace(late, prefix_cache=False).validate()
    finally:
        del MODEL_REGISTRY[late.model]
