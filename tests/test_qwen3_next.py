"""A gated delta-rule / output-gated attention pattern (models/hybrid.py
"L", "*" with a gate, partial rotary and q/k norms, "E" softmax-routed with
a gated shared expert, zero-centred norms; preset `tiny-qwen3-next`): the
linear-attention layers carry a float32 matrix S per slot and value head
beside the conv's last columns (engine/kv_cache.py SlotState).

Everything is compared with the plain reference tests/reference_qwen3_next.py
(float32, no cache, the delta rule token by token) on seeded weights at toy
size, on LOGITS; the gains (zero-centred w, the gated norm's gain, the q/k
norms) are seeded away from their starting values so that a misplaced norm
or offset shows. Tolerances, and why:

- F32_TOL = 5e-4 on logits of about unit scale: the served path in float32
  differs from the reference only in summation order (the chunked form and
  its triangular inverse, paged attention, the one-pass expert product): a
  layer's output differs by 1e-6 to 2e-6, and this stack carries a
  difference on — every body ends in a norm or a gate, so a layer's output
  is of unit scale whatever its input's, and a perturbation grows ~1.4 x an
  entry (measured on the reference itself: 1e-5 at the embedding is 0.27
  at the logits) — so the logits differ by 1e-5 to 2.2e-4, most at the
  widest window (eight chunks). The same weights in bfloat16 differ by
  1e-1 and more (test_bfloat16_fails_the_float32_tolerance). A top-4
  choice that falls the other way near a tie moves a position by 0.1: the
  token slices below hold none (tokens[:17] at a 64-window has one, at
  position 15).
- STATE_TOL = 5e-5 on stored state of at most unit scale: same reason
  (the deepest layers' conv columns of a 64-window against a 16-window
  differ by 1.7e-5).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_qwen3_next as ref
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
from polykey_tpu.models.layers import rope
from polykey_tpu.models.transformer import init_params
from polykey_tpu.ops import hybrid_kernels
from polykey_tpu.ops.moe import held_router_weights, moe_gated_held, moe_held
import grouped_experts
from pattern_stack import SLOTS, SlotBatch, served, text, worst_margin

F32_TOL = 5e-4
STATE_TOL = 5e-5
CFG = get_config("tiny-qwen3-next")
BATCH = SlotBatch(CFG, ref, F32_TOL)
fresh, prefill, decode, decode_tail = (
    BATCH.fresh, BATCH.prefill, BATCH.decode, BATCH.decode_tail)


def with_seeded_gains(params, key):
    """Every norm gain drawn away from where the init leaves it: the
    zero-centred w (layer norms, q/k norms, the final norm) in [−0.5, 0.5],
    the delta body's plain gated-norm gain in [0.5, 1.5]. A_log, dt_bias
    and the shared expert's gate vector stay what the init made them."""
    def seeded(path, w):
        name = path[-1].key
        if w.ndim != 1 or name in ("A_log", "dt_bias", "shared_score"):
            return w
        salt = sum(map(ord, jax.tree_util.keystr(path)))
        low = 0.5 if name == "gate_norm" else -0.5
        return jax.random.uniform(
            jax.random.fold_in(key, salt), w.shape, w.dtype, low, low + 1.0)

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
    bench = os.path.join(here, "..", "perfbench", "references", "qwen3_next.py")
    with open(bench) as a, open(
            os.path.join(here, "reference_qwen3_next.py")) as b:
        assert a.read() == b.read()


# -- the pieces ---------------------------------------------------------------


def test_pattern_is_three_linear_layers_to_one_attending():
    assert CFG.layer_pattern == "LELELE*E" * 2
    assert CFG.num_layers == 16 and CFG.kv_layers == 2
    assert CFG.stateful and CFG.state_held == "delta-rule S and conv columns"
    assert CFG.delta_conv_dim == 2 * 2 * 8 + 4 * 16 and CFG.rotary_dim == 4
    with pytest.raises(ValueError, match="'M', 'C', 'L'"):
        dataclasses.replace(CFG, layer_pattern="LX" * 8)


def test_num_params_counts_every_matrix_once(params):
    # Every leaf of two dimensions or more, the conv's taps among them,
    # and the shared expert's gate vector (a 1-column projection).
    gates = CFG.layer_pattern.count("E") * CFG.hidden_size
    assert CFG.num_params() == gates + sum(
        x.size for x in jax.tree.leaves(params) if x.ndim > 1)
    # Per token: of the 16 held experts an expert layer runs its top 4.
    idle = 8 * 12 * 3 * CFG.hidden_size * CFG.intermediate_size
    assert CFG.num_active_params() == CFG.num_params() - idle
    assert "router_bias" not in params["layers"]["moe"][0]


def delta_inputs(seed, B, Hk, Hv, Dk, Dv):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (B, Hv, Dk, Dv)),
            jnp.exp(-jax.random.uniform(k[1], (B, Hv))),
            jax.nn.sigmoid(jax.random.normal(k[2], (B, Hv))),
            ref.unit(jax.random.normal(k[3], (B, Hk, Dk))),
            ref.unit(jax.random.normal(k[4], (B, Hk, Dk))) * Dk ** -0.5,
            jax.random.normal(k[5], (B, Hv, Dv)))


def test_delta_state_update_kernel_matches_jnp_and_the_rule():
    """The kernel (interpret mode) = its jax.numpy form = the five lines
    of the rule written out; a lane with decay 1 and β 0 keeps its state
    bit for bit in both."""
    S, decay, beta, k, q, v = delta_inputs(0, 3, 2, 4, 8, 16)
    decay, beta = decay.at[1].set(1.0), beta.at[1].set(0.0)
    want = hybrid_kernels.gated_delta_state_update_jnp(S, decay, beta, k, q, v)
    got = hybrid_kernels.gated_delta_state_update(
        S, decay, beta, k, q, v, interpret=True)
    for a, b in zip(got, want):          # the same float32 operations
        np.testing.assert_allclose(a, b, atol=1e-5)
    for new, _ in (got, want):
        np.testing.assert_array_equal(new[1], S[1])
        assert float(jnp.max(jnp.abs(new[0] - S[0]))) > 0
    kh, qh = jnp.repeat(k, 2, axis=1), jnp.repeat(q, 2, axis=1)
    S1 = decay[..., None, None] * S
    d = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S1, kh))
    S1 = S1 + kh[..., :, None] * d[..., None, :]
    np.testing.assert_allclose(want[0], S1, atol=1e-5)
    np.testing.assert_allclose(
        want[1], jnp.einsum("bhkv,bhk->bhv", S1, qh), atol=1e-5)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_delta_form_equals_the_recurrence(chunk):
    """Three rows of 16 positions — 16, 11 and 5 of them real, the rest
    padded (β = g = 0), so a row's end falls on a chunk's edge, inside a
    chunk, and inside the first — from the slot's state, chained from the
    row above, and from zero: outputs and end states equal the rule run
    token by token over the real positions."""
    N, T, Hk, Hv, Dk, Dv = 3, 16, 2, 4, 8, 16
    key = jax.random.split(jax.random.PRNGKey(chunk), 6)
    lengths = jnp.asarray([16, 11, 5])
    real = (jnp.arange(T)[None] < lengths[:, None])[..., None]
    q = ref.unit(jax.random.normal(key[0], (N, T, Hk, Dk))) * Dk ** -0.5
    k = ref.unit(jax.random.normal(key[1], (N, T, Hk, Dk)))
    v = jax.random.normal(key[2], (N, T, Hv, Dv))
    beta = jnp.where(real, jax.nn.sigmoid(
        jax.random.normal(key[3], (N, T, Hv))), 0.0)
    g = jnp.where(real, -jax.random.uniform(key[4], (N, T, Hv)) * 2.0, 0.0)
    S0 = jax.random.normal(key[5], (N, Hv, Dk, Dv))
    kind = jnp.asarray([FROM_SLOT, FROM_PREVIOUS_ROW, FROM_ZERO])
    o, end = hybrid.delta_chunks(q, k, v, beta, g, S0, kind, chunk)

    def recurrence(S, n):
        outs = []
        for t in range(int(lengths[n])):
            args = (jnp.exp(g[n, t])[None], beta[n, t][None], k[n, t][None],
                    q[n, t][None], v[n, t][None])
            S, out = hybrid_kernels.gated_delta_state_update_jnp(S, *args)
            outs.append(out[0])
        return jnp.stack(outs), S

    o0, e0 = recurrence(S0[:1], 0)
    o1, e1 = recurrence(e0, 1)                   # row 1 chains from row 0
    o2, e2 = recurrence(jnp.zeros_like(S0[:1]), 2)
    # Summation order and the triangular inverse (float32, unit scale).
    for n, want in enumerate((o0, o1, o2)):
        np.testing.assert_allclose(o[n, :len(want)], want, atol=2e-5)
    np.testing.assert_allclose(
        end, jnp.concatenate([e0, e1, e2]), atol=2e-5)
    with pytest.raises(ValueError, match="whole chunks"):
        hybrid.delta_chunks(q, k, v, beta, g, S0, kind, 5)


def test_delta_decode_form_equals_its_prefill_form_token_by_token(params):
    """One row of 11 tokens through `delta_prefill` from zero, against the
    same tokens one `delta_decode` step at a time: outputs, S and the
    stored columns agree; an inactive lane keeps S and its columns."""
    p = params["layers"]["delta"][1]
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 16, CFG.hidden_size))
    state = init_slot_state(CFG, 2, jnp.float32)
    S0, conv0 = state.ssm[0], state.conv[0]
    rows = PrefillRows(*(jnp.asarray([v], jnp.int32)
                         for v in (0, FROM_ZERO, 0, 11)))
    want, S_end, conv_end = hybrid.delta_prefill(p, u, CFG, S0, conv0, rows)
    S, conv = S0.at[1].set(7.0), conv0.at[1].set(7.0)   # lane 1: another's
    live = jnp.asarray([True, False])
    for t in range(11):
        both = jnp.stack([u[0, t], u[0, t]])
        out, S, conv = hybrid.delta_decode(p, both, CFG, S, conv, live)
        np.testing.assert_allclose(out[0], want[0, t], atol=1e-5)
    np.testing.assert_allclose(S[0], S_end[0], atol=STATE_TOL)
    np.testing.assert_allclose(conv[0], conv_end[0], atol=STATE_TOL)
    np.testing.assert_array_equal(S[1], jnp.full_like(S[1], 7.0))
    np.testing.assert_array_equal(conv[1], jnp.full_like(conv[1], 7.0))


def test_softmax_router_weighs_over_all_the_chosen_held_or_not():
    """A hand-computed case: logits (2, 1, 0, −1) over 4 published experts,
    top-2, NO bias. p = softmax = (0.6439, 0.2369, 0.0871, 0.0321); experts
    0 and 1 are chosen and take p_e over p_0 + p_1. A chip that holds
    experts 1–2 alone still weighs expert 1 by that sum (expert 0, chosen
    and not held, is in it), and expert 2 gets nothing."""
    cfg = dataclasses.replace(CFG, n_routed_experts=4, experts_held=2,
                              first_expert=1, num_experts_per_tok=2)
    logits = np.asarray([2.0, 1.0, 0.0, -1.0])
    router = jnp.zeros((CFG.hidden_size, 4)).at[0].set(jnp.asarray(logits))
    h = jnp.zeros((1, CFG.hidden_size)).at[0, 0].set(1.0)
    p = np.exp(logits) / np.exp(logits).sum()
    got = held_router_weights({"router": router}, h, cfg)
    total = p[0] + p[1]
    np.testing.assert_allclose(
        got[0], [p[0] / total, p[1] / total, 0.0, 0.0], atol=1e-6)
    held = got[0, cfg.first_expert:cfg.first_expert + cfg.experts_held]
    np.testing.assert_allclose(held, [p[1] / total, 0.0], atol=1e-6)
    # The sigmoid router of the sibling patterns would weigh otherwise.
    s = 1.0 / (1.0 + np.exp(-logits))
    assert abs(s[0] / (s[0] + s[1]) - p[0] / total) > 0.1


def test_partial_rotary_turns_the_leading_dims_only():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 3, 16))
    positions = jnp.arange(5)[None] + 3
    got = rope(x, positions, 1e7, rotary_dim=4)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    np.testing.assert_allclose(
        got[..., :4], rope(x[..., :4], positions, 1e7), atol=1e-6)
    assert float(jnp.max(jnp.abs(got[..., :4] - x[..., :4]))) > 0.1
    # The whole head, asked for by its width or not at all: one function.
    np.testing.assert_array_equal(
        rope(x, positions, 1e7, rotary_dim=16), rope(x, positions, 1e7))
    want = ref.rotary(x[0], positions[0], 1e7, 4)
    np.testing.assert_allclose(got[0], want, atol=1e-6)


@pytest.mark.parametrize("rows, form", grouped_experts.RULE)
def test_the_backend_alone_chooses_the_held_product(
        rows, form, params, monkeypatch):
    """`moe_held` of softmax-routed gated experts with a shared expert: the
    jnp form off the chip; on it the grouped kernel at every row count, a
    decode step's 64 included — the shared expert beside either."""
    grouped_experts.check_rule(
        rows, form, params["layers"]["moe"][0], CFG, monkeypatch)


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """The guide's share test: the routed parts of four chips holding 4 of
    the 16 experts each (`first_expert`) plus the shared expert ONCE = the
    layer with every expert held, in the program; and = the uncut
    REFERENCE's layer."""
    p = params["layers"]["moe"][0]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 6, CFG.hidden_size))
    held = CFG.n_routed_experts // 4
    want = moe_gated_held(p, x, CFG)
    routed_only = dataclasses.replace(
        CFG, moe_shared_intermediate=0, shared_expert_gate=False)
    total = jnp.zeros_like(want)
    for share in range(4):
        cut = slice(share * held, (share + 1) * held)
        cfg = dataclasses.replace(
            routed_only, experts_held=held, first_expert=share * held)
        mine = {**p, "gate": p["gate"][cut], "up": p["up"][cut],
                "down": p["down"][cut]}
        total = total + moe_held(mine, x, cfg)
    np.testing.assert_allclose(total, moe_held(p, x, routed_only),
                               atol=F32_TOL)
    shared_once = moe_gated_held(
        {**p, "down": jnp.zeros_like(p["down"])}, x, CFG)
    assert float(jnp.max(jnp.abs(shared_once))) > 0.01
    np.testing.assert_allclose(total + shared_once, want, atol=F32_TOL)
    # The reference norms its input; feed it rows whose norm is the
    # identity's (zero-centred gain w = 0) by norming them here the same way.
    normed = x[0] * jax.lax.rsqrt(
        jnp.mean(jnp.square(x[0]), -1, keepdims=True) + CFG.rms_norm_eps)
    uncut = ref.expert_layer(
        x[0], {**p, "norm": jnp.zeros_like(p["norm"])}, CFG) - x[0]
    np.testing.assert_allclose(
        moe_gated_held(p, normed[None], CFG)[0], uncut, atol=F32_TOL)


def test_an_expert_form_nobody_computes_is_refused(params):
    p = params["layers"]["moe"][0]
    x = jnp.zeros((1, 2, CFG.hidden_size))
    with pytest.raises(ValueError, match="gate on the shared expert"):
        moe_held(p, x, dataclasses.replace(CFG, moe_latent_size=8))
    with pytest.raises(ValueError, match="gate on the shared expert"):
        moe_held(p, x, dataclasses.replace(CFG, moe_shared_intermediate=0))
    with pytest.raises(ValueError, match="router_scoring"):
        moe_held(p, x, dataclasses.replace(CFG, router_scoring="top1"))


@pytest.mark.parametrize("fault", [
    {"norm_offset": 0.0},               # plain gains where 1 + w is meant
    {"partial_rotary_factor": 1.0},     # the whole head turned
], ids=["offset-off", "rotary-whole-head"])
def test_offset_norm_and_partial_rotary_are_the_references(
        params, tokens, fault):
    """The program agrees with the reference, and NOT with a reference
    that reads another norm offset or turns the whole head."""
    ids = tokens[:13]
    want = ref.forward(params, CFG, ids)
    got, _, _ = prefill(params, *fresh(), 0, ids, 0, 64, [FROM_ZERO])
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)
    other = ref.forward(params, dataclasses.replace(CFG, **fault), ids)
    assert np.max(np.abs(other - got)) > 100 * F32_TOL


# -- what a slot's state may and may not do (kv_cache.SlotState) ------------


def test_state_is_a_matrix_and_conv_columns_a_linear_layer():
    state = init_slot_state(CFG, SLOTS, jnp.float32)
    linear = CFG.layer_pattern.count("L")
    assert [s.shape for s in state.ssm] == [
        (SLOTS, CFG.delta_value_heads, CFG.delta_key_dim, CFG.delta_value_dim)
    ] * linear
    assert {s.dtype for s in init_slot_state(CFG, 1).ssm} == {
        jnp.dtype(jnp.float32)}
    assert [c.shape for c in state.conv] == [
        (SLOTS, CFG.conv_kernel - 1, CFG.delta_conv_dim)] * linear
    # A pattern that holds both recurrences: one `ssm` entry a mixer or
    # linear-attention layer, in pattern order, each of its own shape.
    hybrid_cfg = get_config("tiny-hybrid")
    both = dataclasses.replace(
        hybrid_cfg, layer_pattern="ML*EL",
        **{f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)
           if f.name.startswith("delta_")})
    mixed = init_slot_state(both, 2, jnp.float32)
    assert [s.shape[1:] for s in mixed.ssm] == [
        (both.mamba_num_heads, both.mamba_head_dim, both.ssm_state_size),
        (4, 8, 16), (4, 8, 16)]
    assert [c.shape[-1] for c in mixed.conv] == [
        both.conv_dim, both.delta_conv_dim, both.delta_conv_dim]


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
    """13 real tokens in a window of 16 or of 64: S and the stored columns
    are those after the 13th token, whatever the padding; prefill then
    decode through the cache = the reference's full forward."""
    ids = tokens[40:60]
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
    model="tiny-qwen3-next", tokenizer="byte", dtype="float32",
    max_decode_slots=2, page_size=8, num_pages=160, max_seq_len=256,
    prefill_buckets=(16, 64), max_new_tokens_cap=32, decode_block_steps=4,
)


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(ENGINE, seed=5)
    # The engine's own seeded init leaves every gain at its start; the
    # served tree gets gains away from it, as the slot-batch tests have.
    eng.params = with_seeded_gains(eng.params, jax.random.PRNGKey(11))
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
    a slot a longer one left its S and columns in; a lane whose stream
    ended stays inactive beside a live one until the next admission."""
    prompts = [text(n, 200 + n) for n in (70, 9, 30, 12, 40)]
    outs = served(engine, prompts, new=[6, 14, 5, 12, 7])
    for prompt, ids, n in zip(prompts, outs, (6, 14, 5, 12, 7)):
        assert len(ids) == n
        assert worst_margin(ref, engine, prompt, ids) <= F32_TOL


def test_engine_stats_name_the_state(engine):
    stats = engine.stats()
    assert stats["state_pool_bytes"] == engine.state.nbytes > 0
    assert len(engine.state.ssm) == len(engine.state.conv) == 6
    per_slot_layer = (
        CFG.delta_value_heads * CFG.delta_key_dim * CFG.delta_value_dim * 4
        + (CFG.conv_kernel - 1) * CFG.delta_conv_dim * 4)  # float32 engine
    assert stats["state_pool_bytes"] == (
        ENGINE.max_decode_slots * CFG.layer_pattern.count("L")
        * per_slot_layer)


@pytest.mark.parametrize("knob", [
    {"prefix_cache": True},
    {"prefix_cache": True, "host_kv_bytes": 1 << 20},
    {"disagg": "prefill=1,decode=1"},
    {"disagg_tier": "prefill"},
    {"draft_model": "tiny-qwen3-next"},
    {"tp": 2},
    {"pp": 2},
    {"dp": 2},
    {"quantize": True},
    {"kv_dtype": "int8"},
])
def test_features_that_cannot_carry_state_are_refused(knob):
    """Exactly what the sibling stacks refuse, each message naming the
    state THIS model holds."""
    with pytest.raises(ValueError, match="per-slot recurrent state") as e:
        dataclasses.replace(ENGINE, **knob).validate()
    assert "delta-rule S and conv columns" in str(e.value)


def test_a_stateful_model_registered_late_is_still_refused():
    late = dataclasses.replace(ENGINE, model="qwen3-next-registered-late",
                               prefix_cache=True)
    with pytest.raises(ValueError, match="unknown model"):
        late.validate()
    MODEL_REGISTRY[late.model] = dataclasses.replace(CFG, name=late.model)
    try:
        with pytest.raises(ValueError, match="delta-rule S"):
            InferenceEngine(late)
        dataclasses.replace(late, prefix_cache=False).validate()
    finally:
        del MODEL_REGISTRY[late.model]
