"""A gated delta-rule / NoPE attention pattern over dense MLPs, every body
post-normed and none pre-normed (models/hybrid.py "L" with β in (0, 2),
"*" without a position embedding under ONE q/k norm over the whole
projection, "D"; preset `tiny-olmo-hybrid`): the linear-attention layers
carry a float32 matrix S per slot and value head beside the conv's last
columns (engine/kv_cache.py SlotState), stored two heads side by side in a
row (192 + 192 = three whole lane tiles; ops/hybrid_kernels.py
`pack_heads`).

Everything is compared with the plain reference tests/reference_olmo_hybrid.py
(float32, no cache, the delta rule token by token, the block order, the
norm's span and β's range written out) on seeded weights at toy size, on
LOGITS; the gains are seeded away from 1 so that a misplaced norm shows.
Tolerances, and why:

- F32_TOL = 5e-4 on logits of about unit scale: the served path in float32
  differs from the reference only in summation order (the chunked form and
  its triangular inverse, paged attention); every body ends in a norm, so
  a layer's output is of unit scale whatever its input's and a difference
  is carried on: the logits differ by 1e-5 to 2e-4 (1.2e-4 over two chained
  16-rows), as the sibling delta-rule toy's do. The same stack with one of
  the model's three facts read otherwise (β in (0, 1), a pre-norm, the q/k
  norm a head at a time), with a position embedding, with S rounded to
  bfloat16 after every token or with bfloat16 weights differs by 1e-2 and
  more (the `fault` cases below).
- STATE_TOL = 5e-5 on ONE layer's stored state of at most unit scale fed
  the same input: summation order alone.
"""

import dataclasses
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_olmo_hybrid as ref
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import InferenceEngine, _decode_fn
from polykey_tpu.engine.kv_cache import (
    init_paged_kv,
    init_slot_state,
    resident_nbytes,
)
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
from pattern_stack import SLOTS, SlotBatch, served, text, worst_margin

F32_TOL = 5e-4
STATE_TOL = 5e-5
CFG = get_config("tiny-olmo-hybrid")
BATCH = SlotBatch(CFG, ref, F32_TOL)
fresh, prefill, decode, decode_tail = (
    BATCH.fresh, BATCH.prefill, BATCH.decode, BATCH.decode_tail)
PER_ROW = CFG.delta_heads_per_row


def with_seeded_gains(params, key):
    """Every norm gain (plain, starts at 1) drawn from [0.5, 1.5]: the
    post-norms, the q/k norms over the projection, the delta body's gated
    norm, the final norm. A_log and dt_bias stay what the init made them."""
    def seeded(path, w):
        if w.ndim != 1 or path[-1].key in ("A_log", "dt_bias"):
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
    bench = os.path.join(here, "..", "perfbench", "references",
                         "olmo_hybrid.py")
    with open(bench) as a, open(
            os.path.join(here, "reference_olmo_hybrid.py")) as b:
        assert a.read() == b.read()


# -- the pieces ---------------------------------------------------------------


def test_pattern_is_three_linear_layers_to_one_attending_over_dense_mlps():
    assert CFG.layer_pattern == "LDLDLD*D" * 2
    assert CFG.num_layers == 16 and CFG.kv_layers == 2
    assert CFG.stateful and CFG.state_held == "delta-rule S and conv columns"
    assert CFG.delta_conv_dim == 2 * 6 * 24 + 6 * 192
    assert CFG.num_heads == CFG.num_kv_heads        # MHA: groups of one
    assert not CFG.use_rope and not CFG.pre_norm and CFG.sandwich_norm
    assert "E" not in CFG.layer_pattern and not CFG.n_routed_experts


def test_num_params_counts_every_matrix_once(params):
    assert CFG.num_params() == sum(
        x.size for x in jax.tree.leaves(params) if x.ndim > 1)
    assert CFG.num_active_params() == CFG.num_params()
    # No entry has a pre-norm's gain, every entry has the post-norm's;
    # the q/k gains are as long as the projections.
    for kind in ("delta", "attention", "dense"):
        for p in params["layers"][kind]:
            assert "norm" not in p and p["post_norm"].shape == (64,)
    attn = params["layers"]["attention"][0]
    assert attn["q_norm"].shape == attn["k_norm"].shape == (4 * 16,)


@pytest.mark.parametrize("fact, message", [
    ({"pre_norm": False, "sandwich_norm": False}, "needs a norm"),
    ({"qk_norm_span": "row"}, "'head' or 'projection'"),
    ({"qk_norm": False}, "needs qk_norm"),
    ({"attn_output_gate": True}, "attn_output_gate"),
    ({"delta_beta_scale": 1.5}, "must be 1"),
])
def test_a_fact_that_cannot_compose_is_refused(fact, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **fact)


@pytest.mark.parametrize("preset", [
    "tiny-hybrid", "tiny-lfm2", "tiny-qwen3-next", "tiny-pangu"])
def test_the_sibling_patterns_keep_todays_facts(preset):
    """The three new facts default to what every pattern had."""
    cfg = get_config(preset)
    assert cfg.pre_norm and cfg.qk_norm_span == "head"
    assert cfg.delta_beta_scale == 1.0 and cfg.delta_heads_per_row == 1


@pytest.mark.parametrize("heads, dim, per_row", [
    (30, 192, 2),       # the published model: rows of 384, three lane tiles
    (6, 192, 2),        # the toy
    (32, 128, 1),       # the sibling: a whole tile a head already
    (4, 16, 1),         # its toy: eight would make a tile, four there are
    (8, 48, 8),
    (3, 192, 1),        # an odd count cannot pair
])
def test_heads_share_a_row_where_that_makes_whole_tiles(heads, dim, per_row):
    cfg = dataclasses.replace(CFG, delta_value_heads=heads,
                              delta_key_heads=heads, delta_value_dim=dim)
    assert cfg.delta_heads_per_row == per_row
    S = jax.random.normal(jax.random.PRNGKey(0), (2, heads, 5, dim))
    packed = hybrid_kernels.pack_heads(S, per_row)
    assert packed.shape == (2, heads // per_row, 5, per_row * dim)
    np.testing.assert_array_equal(
        hybrid_kernels.unpack_heads(packed, per_row), S)
    # Head r·n + j lies in columns j·Dv .. of row r.
    last = heads - 1
    np.testing.assert_array_equal(
        packed[:, last // per_row, :, (last % per_row) * dim:], S[:, last])
    # A pattern without the body holds no heads to lay out.
    assert get_config("tiny-lfm2").delta_heads_per_row == 1


@pytest.mark.parametrize("lanes, columns, limit", [
    (64, 30 * 128, True),       # this model: 15.7 MB of pages in flight
    (64, 2 * 256, False),       # the sibling delta-rule cell
    (64, 8 * 64, False),
    (16, 8 * 128, False),       # the dense cells
])
def test_page_write_raises_its_vmem_limit_only_where_its_pages_need_it(
        lanes, columns, limit):
    """The decode step's page write holds every lane's K and V page in VMEM
    between its two waves: 64 lanes of 30 KV heads pass the compiler's
    16 MB default (refused when compiled for a v5e:
    tests/test_paged_layout.py), every sibling's call stays the call it
    was."""
    from polykey_tpu.ops import paged_write_kernel

    pool = jax.ShapeDtypeStruct((2 * 128, 16, columns), jnp.bfloat16)
    rows = jax.ShapeDtypeStruct((lanes, 2, 1, columns), jnp.bfloat16)
    params = paged_write_kernel._vmem_limit([pool], [rows])
    if not limit:
        assert params is None
        return
    held = lanes * 2 * (16 + 1) * columns * 2
    assert held > 16 * 1024 * 1024 // 2
    assert params.vmem_limit_bytes == 2 * held


def delta_inputs(seed, B, Hk, Hv, Dk, Dv):
    """β in (1, 2) on every lane and head: the negative-eigenvalue side."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (B, Hv, Dk, Dv)),
            jnp.exp(-jax.random.uniform(k[1], (B, Hv))),
            1.0 + jax.nn.sigmoid(jax.random.normal(k[2], (B, Hv))),
            ref.unit(jax.random.normal(k[3], (B, Hk, Dk))),
            ref.unit(jax.random.normal(k[4], (B, Hk, Dk))) * Dk ** -0.5,
            jax.random.normal(k[5], (B, Hv, Dv)))


@pytest.mark.parametrize("shape, per_row", [
    ((2, 30, 30, 96, 192), 2),      # the published heads, side by side
    ((2, 30, 30, 96, 192), 1),      # ... and apart
    ((3, 6, 6, 24, 192), 2),        # the toy
    ((3, 2, 4, 8, 64), 2),          # a key head shared by a row's two heads
    ((3, 2, 8, 8, 32), 4),          # ... and by two of a row's four
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else f"n{v}")
def test_delta_state_update_kernel_matches_jnp_and_the_rule(shape, per_row):
    """The kernel (interpret mode) = its jax.numpy form = the five lines of
    the rule written out on the heads apart, in the stored layout; a lane
    with decay 1 and β 0 keeps its state bit for bit in both."""
    S, decay, beta, k, q, v = delta_inputs(0, *shape)
    decay, beta = decay.at[1].set(1.0), beta.at[1].set(0.0)
    stored = hybrid_kernels.pack_heads(S, per_row)
    want = hybrid_kernels.gated_delta_state_update_jnp(
        stored, decay, beta, k, q, v)
    got = hybrid_kernels.gated_delta_state_update(
        stored, decay, beta, k, q, v, interpret=True)
    for a, b in zip(got, want):          # the same float32 operations
        np.testing.assert_allclose(a, b, atol=2e-5)
    for new, _ in (got, want):
        assert new.shape == stored.shape
        np.testing.assert_array_equal(new[1], stored[1])
        assert float(jnp.max(jnp.abs(new[0] - stored[0]))) > 0
    rep = shape[2] // shape[1]
    kh, qh = jnp.repeat(k, rep, axis=1), jnp.repeat(q, rep, axis=1)
    S1 = decay[..., None, None] * S
    d = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S1, kh))
    S1 = S1 + kh[..., :, None] * d[..., None, :]
    np.testing.assert_allclose(
        hybrid_kernels.unpack_heads(want[0], per_row), S1, atol=2e-5)
    np.testing.assert_allclose(
        want[1], jnp.einsum("bhkv,bhk->bhv", S1, qh), atol=2e-5)


def test_the_siblings_kernel_call_traces_as_it_did():
    """`gated_delta_state_update` at the sibling cell's shape (32 heads of
    128 x 128, one head a row): the traced program is the parent's, byte
    for byte (sha256 of the jaxpr text at PR 59's tree)."""
    import hashlib
    import re

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    args = (S(64, 32, 128, 128), S(64, 32), S(64, 32), S(64, 16, 128),
            S(64, 16, 128), S(64, 32, 128))
    traced = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(
        hybrid_kernels.gated_delta_state_update)(*args)))
    assert hashlib.sha256(traced.encode()).hexdigest()[:16] == \
        "5798323e51858b57"


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_delta_form_equals_the_recurrence_with_beta_above_one(chunk):
    """Three rows of 16 positions — 16, 11 and 5 of them real — from the
    slot's state, chained from the row above, and from zero, EVERY β in
    (1, 2): the transition has a negative eigenvalue at every token, and
    the chunk's nilpotent inverse still gives the rule run token by
    token (outputs and end states)."""
    N, T, H, Dk, Dv = 3, 16, 6, 24, 192
    key = jax.random.split(jax.random.PRNGKey(chunk), 6)
    lengths = jnp.asarray([16, 11, 5])
    real = (jnp.arange(T)[None] < lengths[:, None])[..., None]
    q = ref.unit(jax.random.normal(key[0], (N, T, H, Dk))) * Dk ** -0.5
    k = ref.unit(jax.random.normal(key[1], (N, T, H, Dk)))
    v = jax.random.normal(key[2], (N, T, H, Dv))
    beta = jnp.where(real, 1.0 + jax.nn.sigmoid(
        jax.random.normal(key[3], (N, T, H))), 0.0)
    assert float(jnp.min(jnp.where(real, beta, 2.0))) > 1.0
    g = jnp.where(real, -jax.random.uniform(key[4], (N, T, H)) * 2.0, 0.0)
    S0 = jax.random.normal(key[5], (N, H, Dk, Dv))
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
    # Summation order and the triangular inverse (float32; S of a few
    # units after sixteen corrections of strength up to 2).
    for n, want in enumerate((o0, o1, o2)):
        np.testing.assert_allclose(o[n, :len(want)], want, atol=5e-5)
    np.testing.assert_allclose(
        end, jnp.concatenate([e0, e1, e2]), atol=5e-5)


@pytest.mark.parametrize("scale", [1.0, 2.0])
def test_chunked_form_holds_where_a_chunks_keys_lie_close(scale):
    """One row of 64 positions in ONE chunk of 64, every key within 0.8 of
    a common direction — as a real prompt's keys lie, and as the seeded
    model's do on the chip — β up to `scale`: the chunked form is the rule
    run token by token. The triangular inverse is taken by forward
    substitution; as the product of I + X^{2^i} it was off by 2.4 at
    β < 1 and NaN at β < 2 on such keys (and `_unit_lower_inverse` is held
    to a float64 inverse beside it)."""
    T, H, Dk, Dv = 64, 3, 24, 48
    key = jax.random.split(jax.random.PRNGKey(int(scale)), 6)
    common = ref.unit(jax.random.normal(key[0], (H, Dk)))
    k = ref.unit(0.8 * common + 0.2 * Dk ** -0.5
                 * jax.random.normal(key[1], (1, T, H, Dk)))
    assert float(jnp.min(jnp.einsum("thd,shd->hts", k[0], k[0]))) > 0.7
    q = ref.unit(jax.random.normal(key[2], (1, T, H, Dk))) * Dk ** -0.5
    v = jax.random.normal(key[3], (1, T, H, Dv))
    beta = scale * jax.nn.sigmoid(jax.random.normal(key[4], (1, T, H)))
    g = -jax.random.uniform(key[5], (1, T, H)) * 0.1
    zero = jnp.zeros((1, H, Dk, Dv))
    o, end = hybrid.delta_chunks(q, k, v, beta, g, zero,
                                 jnp.asarray([FROM_ZERO]), 64)
    S, outs = zero, []
    for t in range(T):
        S, out = hybrid_kernels.gated_delta_state_update_jnp(
            S, jnp.exp(g[:, t]), beta[:, t], k[:, t], q[:, t], v[:, t])
        outs.append(out[0])
    np.testing.assert_allclose(o[0], jnp.stack(outs), atol=1e-4)
    np.testing.assert_allclose(end, S, atol=1e-4)
    X = -np.tril(np.asarray(beta[0, :, 0, None] * jnp.einsum(
        "td,sd->ts", k[0, :, 0], k[0, :, 0])), -1)
    got = hybrid._unit_lower_inverse(jnp.asarray(X, jnp.float32))
    np.testing.assert_allclose(
        got, np.linalg.inv(np.eye(T) - X.astype(np.float64)), atol=1e-5)


def test_delta_decode_form_equals_its_prefill_form_token_by_token(params):
    """One row of 11 tokens through `delta_prefill` from zero, against the
    same tokens one `delta_decode` step at a time, both on the STORED
    layout: outputs, S and the stored columns agree; an inactive lane
    keeps S and its columns."""
    p = params["layers"]["delta"][1]
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 16, CFG.hidden_size))
    state = init_slot_state(CFG, 2, jnp.float32)
    S0, conv0 = state.ssm[0], state.conv[0]
    rows = PrefillRows(*(jnp.asarray([v], jnp.int32)
                         for v in (0, FROM_ZERO, 0, 11)))
    want, S_end, conv_end = hybrid.delta_prefill(p, u, CFG, S0, conv0, rows)
    assert S_end.shape == S0.shape
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


def test_beta_reaches_two_and_the_reference_says_so(params):
    """`_delta_factors` at a large b: β is the scale, not 1."""
    p = params["layers"]["delta"][0]
    x = jnp.zeros((1, CFG.delta_conv_dim))
    b = jnp.full((1, 6), 30.0)
    *_, beta, _ = hybrid._delta_factors(
        p, x, b, jnp.zeros((1, 6)), CFG, jnp.asarray([[True]]))
    np.testing.assert_allclose(beta, 2.0, atol=1e-6)
    np.testing.assert_allclose(ref.strength(b), 2.0, atol=1e-6)
    one = dataclasses.replace(CFG, delta_beta_scale=1.0)
    *_, beta, _ = hybrid._delta_factors(
        p, x, b, jnp.zeros((1, 6)), one, jnp.asarray([[True]]))
    np.testing.assert_allclose(beta, 1.0, atol=1e-6)


def faulted_reference(monkeypatch, **stand_ins):
    """The reference with some of its functions replaced, its layers under
    new function objects (`forward` jits by function)."""
    for name, fn in stand_ins.items():
        monkeypatch.setattr(ref, name, fn)
    monkeypatch.setattr(ref, "LAYERS", {
        kind: (lambda fn: lambda x, p, cfg: fn(x, p, cfg))(fn)
        for kind, fn in ref.LAYERS.items()})


def per_head_norm(y, gain, heads, eps):
    T = y.shape[0]
    return ref.rms_norm(y.reshape(T, heads, -1),
                        ref.f32(gain).reshape(heads, -1), eps).reshape(T, -1)


def rotated(x, positions):
    return rope(x[None], positions[None], 500_000.0)[0]


REFERENCE_FAULTS = {
    # What the sibling delta-rule model does: β in (0, 1).
    "beta-unscaled": {"strength": jax.nn.sigmoid},
    # A pre-norm (gain 1) before every body, beside the post-norm.
    "pre-norm": {"read": lambda x, eps: ref.rms_norm(
        x, jnp.ones(x.shape[-1]), eps)},
    # The q/k norm a head at a time, each head with its part of the gain.
    "qk-norm-per-head": {"qk_normed": per_head_norm},
    # A rotary embedding on q and k (the family's other members' theta).
    "rope-on": {"positioned": rotated},
    # S rounded to bfloat16 after every token.
    "bf16-state": {"carried": lambda S: jax.lax.reduce_precision(
        S, exponent_bits=8, mantissa_bits=7)},
}


@pytest.mark.parametrize("fault", sorted(REFERENCE_FAULTS))
def test_the_program_is_this_model_and_not_its_neighbour(
        params, tokens, fault, monkeypatch):
    """The program agrees with the reference on LOGITS, and not with a
    reference that reads one of the model's facts otherwise."""
    ids = tokens[:13]
    got, _, _ = prefill(params, *fresh(), 0, ids, 0, 64, [FROM_ZERO])
    np.testing.assert_allclose(got, ref.forward(params, CFG, ids),
                               atol=F32_TOL, rtol=0)
    faulted_reference(monkeypatch, **REFERENCE_FAULTS[fault])
    other = ref.forward(params, CFG, ids)
    assert np.max(np.abs(other - got)) > 20 * F32_TOL


def flipped(params, fact):
    """(cfg, params) of the PROGRAM with one of the three new facts at the
    default every other pattern has, and the leaves that reading needs."""
    cfg = dataclasses.replace(CFG, **fact)
    layers = {kind: [dict(p) for p in trees]
              for kind, trees in params["layers"].items()}
    if cfg.pre_norm:
        for trees in layers.values():
            for p in trees:
                p["norm"] = jnp.ones_like(p["post_norm"])
    if cfg.qk_norm_span == "head":
        for p in layers["attention"]:
            p["q_norm"] = p["q_norm"][:CFG.head_dim]
            p["k_norm"] = p["k_norm"][:CFG.head_dim]
    return cfg, {**params, "layers": {k: tuple(v) for k, v in layers.items()}}


@pytest.mark.parametrize("fact", [
    {"delta_beta_scale": 1.0}, {"pre_norm": True}, {"qk_norm_span": "head"},
    {"use_rope": True},
], ids=lambda f: next(iter(f)))
def test_each_fact_flipped_in_the_program_fails_the_tolerance(
        params, tokens, fact):
    ids = tokens[:13]
    want = ref.forward(params, CFG, ids)
    cfg, tree = flipped(params, fact)
    got, _, _ = SlotBatch(cfg, ref, F32_TOL).prefill(
        tree, *fresh(), 0, ids, 0, 16, [FROM_ZERO])
    assert np.max(np.abs(got - want)) > 20 * F32_TOL


def test_bfloat16_weights_fail_the_float32_tolerance(params, tokens):
    ids = tokens[:13]
    want = ref.forward(params, CFG, ids)
    low = jax.tree.map(
        lambda w: w.astype(jnp.bfloat16) if w.ndim > 1 else w, params)
    paged, state = fresh(jnp.bfloat16)
    got, _, _ = prefill(low, paged, state, 0, ids, 0, 16, [FROM_ZERO])
    assert np.max(np.abs(got - want)) > 10 * F32_TOL


def test_full_width_norm_and_no_position_in_the_attending_layer(params):
    """`attention_layer` alone against the reference's mix, causal over
    one row: q and k normed over all 64 columns, nothing turned."""
    p = params["layers"]["attention"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 9, CFG.hidden_size))
    positions = jnp.arange(9)[None]

    def attend(idx, q, k, v, pool):
        scores = jnp.einsum("bthd,bshd->bhts", q, k) * CFG.q_scale
        scores = jnp.where(jnp.tril(jnp.ones((9, 9), bool)), scores, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd",
                          jax.nn.softmax(scores, -1), v), pool

    got, _ = hybrid.attention_layer(p, x, positions, CFG, attend, 0, None)
    np.testing.assert_allclose(got[0], ref.attention_mix(x[0], p, CFG),
                               atol=2e-5)


# -- what a slot's state may and may not do (kv_cache.SlotState) ------------


def test_state_is_rows_of_two_heads_and_conv_columns_a_linear_layer():
    state = init_slot_state(CFG, SLOTS, jnp.float32)
    linear = CFG.layer_pattern.count("L")
    assert PER_ROW == 2
    assert [s.shape for s in state.ssm] == [(SLOTS, 3, 24, 384)] * linear
    assert {s.dtype for s in init_slot_state(CFG, 1).ssm} == {
        jnp.dtype(jnp.float32)}
    assert [c.shape for c in state.conv] == [
        (SLOTS, CFG.conv_kernel - 1, CFG.delta_conv_dim)] * linear
    # Off the TPU a layout has no tiles: resident bytes are the nominal.
    assert state.resident_nbytes == state.nbytes == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(state))


@pytest.mark.parametrize("shape, dtype, tiling, order, want", [
    ((64, 30, 96, 192), np.float32, ((8, 128),), (0, 1, 2, 3),
     64 * 30 * 96 * 256 * 4),       # heads apart: a third more
    ((64, 15, 96, 384), np.float32, ((8, 128),), (0, 1, 2, 3),
     64 * 15 * 96 * 384 * 4),       # two a row: what is held
    ((64, 3, 11520), np.dtype("bfloat16"), ((8, 128), (2, 1)), (0, 1, 2),
     64 * 8 * 11520 * 2),
    ((4, 5), np.float32, (), (0, 1), 80),
])
def test_resident_bytes_round_up_to_the_layouts_tiles(
        shape, dtype, tiling, order, want):
    layout = types.SimpleNamespace(tiling=tiling, major_to_minor=order)
    x = types.SimpleNamespace(
        shape=shape, dtype=np.dtype(dtype),
        nbytes=int(np.prod(shape)) * np.dtype(dtype).itemsize,
        format=types.SimpleNamespace(layout=layout))
    assert resident_nbytes(x) == want


RULES = {
    # name: (tokens, the prefill dispatches as (stop, start, width,
    # sources), a faulted twin of the LAST dispatch's sources or None)
    "i-first-window-from-zero": (13, [(13, 0, 16, [FROM_ZERO])], [FROM_SLOT]),
    "ii-padding-16": (13, [(13, 0, 16, [FROM_ZERO])], None),
    "ii-padding-64": (13, [(13, 0, 64, [FROM_ZERO])], None),
    "iv-chained-rows": (
        28, [(28, 0, 16, [FROM_ZERO, FROM_PREVIOUS_ROW])],
        [FROM_ZERO, FROM_ZERO]),
    "v-chunk-from-the-slot": (
        84, [(64, 0, 64, [FROM_ZERO]),
             (84, 64, 16, [FROM_SLOT, FROM_PREVIOUS_ROW])],
        [FROM_ZERO, FROM_PREVIOUS_ROW]),
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_state_rules_prefill_then_decode_equal_the_full_forward(
        params, tokens, rule):
    """The siblings' rules i, ii, iv and v as cases of one test: a prompt
    through its prefill dispatches INTO A SLOT ANOTHER OCCUPANT LEFT DIRTY,
    then six tokens decoded through the cache and the state = the
    reference's full forward on LOGITS; and the faulted twin (the dirt
    read, a row from zero that should chain, a chunk from zero that should
    resume) is another model."""
    n, dispatches, fault = RULES[rule]
    ids = tokens[:n + 6]
    want = ref.forward(params, CFG, ids)
    paged, state = fresh()
    state = jax.tree.map(lambda x: x + 3.0, state)      # the last occupant's
    before = (paged, state)
    for stop, start, width, sources in dispatches:
        before = (paged, state)
        got, paged, state = prefill(
            params, paged, state, 2, ids[start:stop], start, width, sources)
        np.testing.assert_allclose(got, want[start:stop], atol=F32_TOL,
                                   rtol=0)
    decode_tail(params, paged, state, 2, ids, n, want)
    if fault is not None:
        stop, start, width, _ = dispatches[-1]
        bad, _, _ = prefill(params, *before, 2, ids[start:stop], start,
                            width, fault)
        assert np.max(np.abs(bad - want[start:stop])) > 20 * F32_TOL


def test_padding_never_moves_what_is_stored(params, tokens):
    """13 real tokens in a window of 16 or of 64: the same S and columns
    in every layer — to F32_TOL, not STATE_TOL: the deepest layers' state
    is computed from a stream that already differs as the logits do (eight
    chunks against two; 1.3e-4 read on 0.2 % of the last layers' S)."""
    ids = tokens[40:53]
    _, _, wide = prefill(params, *fresh(), 2, ids, 0, 64, [FROM_ZERO])
    _, _, exact = prefill(params, *fresh(), 2, ids, 0, 16, [FROM_ZERO])
    for a, b in zip(jax.tree.leaves(wide), jax.tree.leaves(exact)):
        np.testing.assert_allclose(a[2], b[2], atol=F32_TOL)
        assert float(jnp.max(jnp.abs(a[2]))) > 0


def test_rule_iii_an_inactive_lane_is_not_advanced(params, tokens):
    ids = tokens[:12]
    paged, state = fresh()
    _, paged, state = prefill(params, paged, state, 0, ids, 0, 16, [FROM_ZERO])
    _, paged, state = prefill(params, paged, state, 3, ids, 0, 16, [FROM_ZERO])
    _, _, after = decode(params, paged, state, 3, int(ids[-1]), 12)
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(state)):
        np.testing.assert_array_equal(a[0], b[0])       # lane 0: bit for bit
        assert np.max(np.abs(np.asarray(a[3]) - np.asarray(b[3]))) > 0


def test_decode_block_of_a_stateful_pattern_without_experts(params):
    """The decode program of state WITHOUT held experts: nothing is
    counted, the packed download is [steps, lanes] with no row more, and
    the state rides the block's carry."""
    B, P, steps = SLOTS, 6, 3
    paged = init_paged_kv(CFG, 1 + B * P, 8, jnp.float32)
    state = init_slot_state(CFG, B, jnp.float32)
    tables = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
    active = jnp.asarray([True, False, True, False])
    packed, last, seq, act, paged, after = _decode_fn(
        params, CFG, paged, jnp.full((B,), 5, jnp.int32),
        jnp.full((B,), 1, jnp.int32), tables, active,
        jnp.full((B,), 40, jnp.int32), jnp.zeros((B, 2), jnp.int32),
        jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32), state,
        greedy=True, steps=steps, eos_id=-1)
    assert packed.shape == (steps, B)
    assert np.all(np.asarray(packed)[:, 1] == -1)
    assert np.all(np.asarray(packed)[:, 0] >= 0)
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(state)):
        np.testing.assert_array_equal(a[1], b[1])
        assert float(jnp.max(jnp.abs(a[0] - b[0]))) > 0


# -- through the engine ------------------------------------------------------

ENGINE = EngineConfig(
    model="tiny-olmo-hybrid", tokenizer="byte", dtype="float32",
    max_decode_slots=2, page_size=8, num_pages=160, max_seq_len=256,
    prefill_buckets=(16, 64), max_new_tokens_cap=32, decode_block_steps=4,
)


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(ENGINE, seed=5)
    # The engine's own seeded init leaves every gain at 1; the served tree
    # gets gains away from it, as the slot-batch tests have.
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
    prompts = [text(n, 300 + n) for n in (70, 9, 30, 12, 40)]
    outs = served(engine, prompts, new=[6, 14, 5, 12, 7])
    for prompt, ids, n in zip(prompts, outs, (6, 14, 5, 12, 7)):
        assert len(ids) == n
        assert worst_margin(ref, engine, prompt, ids) <= F32_TOL


def test_engine_stats_name_the_state_and_count_no_experts(engine):
    stats = engine.stats()
    assert stats["state_pool_bytes"] == engine.state.resident_nbytes > 0
    assert len(engine.state.ssm) == len(engine.state.conv) == 6
    per_slot_layer = (
        CFG.delta_value_heads * CFG.delta_key_dim * CFG.delta_value_dim * 4
        + (CFG.conv_kernel - 1) * CFG.delta_conv_dim * 4)  # float32 engine
    assert stats["state_pool_bytes"] == (
        ENGINE.max_decode_slots * CFG.layer_pattern.count("L")
        * per_slot_layer)
    assert "held_expert_calls" not in stats
    # MHA: K and V of every one of the four heads, a token and layer.
    assert stats["kv_token_bytes"] == 2 * 2 * 4 * 16 * 4


@pytest.mark.parametrize("knob", [
    {"prefix_cache": True},
    {"prefix_cache": True, "host_kv_bytes": 1 << 20},
    {"disagg": "prefill=1,decode=1"},
    {"disagg_tier": "prefill"},
    {"draft_model": "tiny-olmo-hybrid"},
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
    late = dataclasses.replace(ENGINE, model="olmo-hybrid-registered-late",
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
