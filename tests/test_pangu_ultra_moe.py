"""A latent-attention (MLA) pattern (models/hybrid.py "A" over a ONE-part
page pool, a post-norm on every body, "D", "E" sigmoid-routed with a plain
shared expert; preset `tiny-pangu`): what a token leaves in the cache is one
row a layer — its normed latent beside the one rotary key all heads share —
and no per-head K or V is ever stored or rebuilt (engine/kv_cache.py).

Everything is compared with the plain reference
tests/reference_pangu_ultra_moe.py (float32, no cache, the EXPANDED form:
every head's key and value built from the latent) on seeded weights at toy
size, on LOGITS; every gain is seeded away from 1 so that a misplaced or
missing norm shows, and the routers' bias is zero (the family states none).
Tolerance, and why:

- F32_TOL = 2e-4 on logits of about unit scale: the served path in float32
  differs from the reference in summation order alone (the absorbed
  products associate (q W_uk) c where the reference has q (W_uk c), paged
  attention, the one-pass expert product); measured 3e-6 to 2e-5. The same
  weights in bfloat16 differ by 5e-2 and more
  (test_bfloat16_fails_the_float32_tolerance). A top-4 choice that falls
  the other way near a tie would move a position by 0.1: the token slices
  below hold none.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import grouped_experts
import reference_pangu_ultra_moe as ref
from pattern_stack import SLOTS, SlotBatch, served, text, worst_margin
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import InferenceEngine
from polykey_tpu.engine.kv_cache import (
    host_kv_page_bytes,
    init_paged_kv,
    kv_pool_bytes,
)
from polykey_tpu.models import hybrid
from polykey_tpu.models.config import MODEL_REGISTRY, get_config
from polykey_tpu.models.hybrid import FROM_PREVIOUS_ROW, FROM_SLOT, FROM_ZERO
from polykey_tpu.models.transformer import init_params
from polykey_tpu.ops.moe import moe_gated_held, moe_held
from polykey_tpu.ops.paged_attention import latent_attention, paged_write
from polykey_tpu.ops.paged_attention_kernel import mla_latent_decode

F32_TOL = 2e-4
CFG = get_config("tiny-pangu")
BATCH = SlotBatch(CFG, ref, F32_TOL)
fresh, prefill, decode, decode_tail = (
    BATCH.fresh, BATCH.prefill, BATCH.decode, BATCH.decode_tail)


def with_seeded_gains(params, key):
    """Every gain drawn from [0.5, 1.5] (the init leaves 1: a post-norm
    read as a pre-norm, or skipped, would not show), the routers' bias
    zero."""
    def seeded(path, w):
        if path[-1].key == "router_bias":
            return jnp.zeros_like(w)
        if w.ndim != 1:
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
                         "pangu_ultra_moe.py")
    with open(bench) as a, open(
            os.path.join(here, "reference_pangu_ultra_moe.py")) as b:
        assert a.read() == b.read()


# -- the pieces ---------------------------------------------------------------


def test_pattern_attends_through_latent_layers_alone():
    assert CFG.layer_pattern == "ADAEAE" and CFG.kv_layers == 3
    assert CFG.latent_kv and not CFG.stateful
    assert (CFG.kv_parts, CFG.latent_width, CFG.kv_row_width) == (1, 40, 128)
    assert CFG.q_scale == (16 + 8) ** -0.5
    # One pool geometry: latent rows or K and V, never both.
    with pytest.raises(ValueError, match="mixes 'A' and '\\*'"):
        dataclasses.replace(CFG, layer_pattern="AD*EAE")
    with pytest.raises(ValueError, match="'M', 'C', 'L', '\\*', 'A'"):
        dataclasses.replace(CFG, layer_pattern="AX" * 3)
    # The published widths: a 576-wide row in five whole tiles.
    full = dataclasses.replace(CFG, kv_lora_rank=512, qk_rope_head_dim=64)
    assert (full.latent_width, full.kv_row_width) == (576, 640)


def test_num_params_counts_every_matrix_once(params):
    assert CFG.num_params() == sum(
        x.size for x in jax.tree.leaves(params) if x.ndim > 1)
    # Per token: of the 8 held experts an expert layer runs its top 4.
    idle = 2 * 4 * 3 * CFG.hidden_size * CFG.intermediate_size
    assert CFG.num_active_params() == CFG.num_params() - idle
    latent = params["layers"]["latent"][0]
    assert latent["w_uk"].shape == (4, 16, 32)
    assert latent["w_uv"].shape == (4, 32, 16)
    for kind in ("latent", "dense", "moe"):
        assert "post_norm" in params["layers"][kind][0]


def test_absorbed_equals_expanded_at_one_position(params):
    """(b) One query against 11 cached rows: q̃ · row and W_uv Σ p c (the
    program's form) against Σ p (W_uv c) with per-head keys [W_uk c | k_r]
    (the published form), written out here from the same leaves."""
    p = params["layers"]["latent"][1]
    key = jax.random.split(jax.random.PRNGKey(3), 4)
    S, H, rank, nope, rot = 11, 4, 32, 16, 8
    c = jax.random.normal(key[0], (S, rank))
    k_r = jax.random.normal(key[1], (S, rot))
    q_nope = jax.random.normal(key[2], (H, nope))
    q_rope = jax.random.normal(key[3], (H, rot))
    scale = (nope + rot) ** -0.5
    k_nope = jnp.einsum("sc,hnc->shn", c, p["w_uk"])
    v = jnp.einsum("sc,hcv->shv", c, p["w_uv"])
    s = (jnp.einsum("hn,shn->hs", q_nope, k_nope)
         + jnp.einsum("hd,sd->hs", q_rope, k_r)) * scale
    want = jnp.einsum("hs,shv->hv", jax.nn.softmax(s, axis=-1), v)
    absorbed = jnp.concatenate(
        [jnp.einsum("hn,hnc->hc", q_nope, p["w_uk"]), q_rope], axis=-1)
    row = jnp.concatenate([c, k_r], axis=-1)
    probs = jax.nn.softmax(absorbed @ row.T * scale, axis=-1)
    got = jnp.einsum("hc,hcv->hv", probs @ row[:, :rank], p["w_uv"])
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("rows, form", grouped_experts.RULE)
def test_the_backend_alone_chooses_the_held_product(
        rows, form, params, monkeypatch):
    """`moe_held` of sigmoid-routed gated experts with a plain shared
    expert: the jnp form off the chip; on it the grouped kernel at every
    row count, a decode step's 64 included."""
    grouped_experts.check_rule(
        rows, form, params["layers"]["moe"][0], CFG, monkeypatch)


def test_the_two_shares_add_up_to_the_uncut_layer(params):
    """(c) The guide's share test: the routed parts of two chips holding 8
    of the 16 experts each (`first_expert`) plus the shared expert ONCE =
    the layer with every expert held, in the program; and = the uncut
    REFERENCE's layer."""
    p = params["layers"]["moe"][0]
    uncut = dataclasses.replace(CFG, experts_held=16)
    other = init_params(jax.random.PRNGKey(4), CFG, jnp.float32)[
        "layers"]["moe"][0]
    whole = {**p, **{name: jnp.concatenate([p[name], other[name]])
                     for name in ("gate", "up", "down")}}
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 6, CFG.hidden_size))
    want = moe_gated_held(whole, x, uncut)
    routed_only = dataclasses.replace(CFG, moe_shared_intermediate=0)
    total = jnp.zeros_like(want)
    for share in range(2):
        cut = slice(share * 8, (share + 1) * 8)
        cfg = dataclasses.replace(routed_only, first_expert=share * 8)
        mine = {**whole, "gate": whole["gate"][cut], "up": whole["up"][cut],
                "down": whole["down"][cut]}
        total = total + moe_held(mine, x, cfg)
    shared_once = moe_gated_held(
        {**whole, "down": jnp.zeros_like(whole["down"])}, x, uncut)
    assert float(jnp.max(jnp.abs(shared_once))) > 0.01
    np.testing.assert_allclose(total + shared_once, want, atol=F32_TOL)
    # The reference norms its input and its output; feed it rows whose
    # pre-norm is the identity's and take the post-norm off.
    normed = x[0] * jax.lax.rsqrt(
        jnp.mean(jnp.square(x[0]), -1, keepdims=True) + CFG.rms_norm_eps)
    plain = dataclasses.replace(uncut, sandwich_norm=False)
    layer = ref.expert_layer(
        x[0], {**whole, "norm": jnp.ones_like(p["norm"])}, plain) - x[0]
    np.testing.assert_allclose(
        moe_gated_held(whole, normed[None], uncut)[0], layer, atol=F32_TOL)


@pytest.mark.parametrize("fault", ["post-norm-dropped", "rope-score-dropped",
                                   "kv-norm-dropped"])
def test_every_piece_of_the_layer_is_the_references(params, tokens, fault,
                                                    monkeypatch):
    """The program agrees with the reference, and NOT with a reference
    that drops the post-norm, the rotary part of the score, or the
    latent's norm."""
    ids = tokens[:13]
    want = ref.forward(params, CFG, ids)
    got, _, _ = prefill(params, *fresh(), 0, ids, 0, 16, [FROM_ZERO])
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)
    cfg, faulty = CFG, params
    if fault == "post-norm-dropped":
        cfg = dataclasses.replace(CFG, sandwich_norm=False)
    elif fault == "rope-score-dropped":
        monkeypatch.setattr(ref, "rope_score", lambda q, k: 0.0)
    else:
        faulty = jax.tree_util.tree_map_with_path(
            lambda path, w: (jnp.ones_like(w) if path[-1].key == "kv_norm"
                             else w), params)
    jax.clear_caches()          # the reference's layers are jitted by name
    other = ref.forward(faulty, cfg, ids)
    jax.clear_caches()
    assert np.max(np.abs(other - got)) > 100 * F32_TOL


# -- the pool: one part a page --------------------------------------------------


def test_pool_is_one_row_a_token_and_layer():
    paged = init_paged_kv(CFG, 9, 8, jnp.float32)
    assert paged.kv.shape == (3, 9, 1, 8, 128)
    assert paged.kv.nbytes == kv_pool_bytes(CFG, 9, 8, jnp.float32)
    assert host_kv_page_bytes(CFG, 8, jnp.float32) == 3 * 8 * 128 * 4
    with pytest.raises(ValueError, match="no int8 form"):
        init_paged_kv(CFG, 9, 8, jnp.float32, kv_dtype=jnp.int8)
    # At the published widths: 7 layers x 640 stored columns x 2 bytes a
    # token (576 published + 64 of padding), 1.17 GB at the cell's geometry.
    full = dataclasses.replace(
        CFG, kv_lora_rank=512, qk_rope_head_dim=64,
        layer_pattern="ADAEAEAEAEAEAE", num_layers=14)
    assert kv_pool_bytes(full, 1, 1) == 7 * 640 * 2 == 8960
    assert kv_pool_bytes(full, 8192, 16) == 8192 * 16 * 8960


@pytest.mark.parametrize("preset", [
    "tiny-llama", "tiny-mixtral", "tiny-gemma", "tiny-hybrid", "tiny-lfm2",
    "tiny-qwen3-next"])
def test_kv_models_pool_is_what_it_was(preset):
    """(e) A K/V model's pool: two parts a page of Hk·D columns, the bytes
    the old formula gave, int8 with its scale pools."""
    cfg = get_config(preset)
    assert cfg.kv_parts == 2 and not cfg.latent_kv
    assert cfg.kv_row_width == cfg.num_kv_heads * cfg.head_dim
    paged = init_paged_kv(cfg, 5, 8, jnp.bfloat16)
    assert paged.kv.shape == (
        cfg.kv_layers, 5, 2, 8, cfg.num_kv_heads * cfg.head_dim)
    assert paged.ks is None
    assert kv_pool_bytes(cfg, 5, 8) == paged.kv.nbytes == (
        2 * cfg.kv_layers * 5 * 8 * cfg.num_kv_heads * cfg.head_dim * 2)
    if not cfg.layer_pattern:
        quantized = init_paged_kv(cfg, 5, 8, kv_dtype=jnp.int8)
        assert kv_pool_bytes(cfg, 5, 8, kv_dtype=jnp.int8) == sum(
            x.nbytes for x in jax.tree.leaves(quantized))


@pytest.mark.parametrize("preset", [
    "tiny-pangu", "tiny-llama", "tiny-hybrid", "tiny-lfm2", "tiny-qwen3-next"])
def test_the_capacity_ledgers_pool_is_the_allocators(preset):
    """memlint's mirror (engine/roofline.py, no jax) reads a page's parts,
    the pool's layers and a row's width from the model, as the allocator
    does: a latent pool is one part, a pattern's pool its attending layers'."""
    from polykey_tpu.engine.roofline import (
        kv_bytes_per_token,
        kv_pool_bytes_spec,
    )

    cfg = get_config(preset)
    assert kv_pool_bytes_spec(cfg, 9, 8, "bfloat16") == kv_pool_bytes(cfg, 9, 8)
    assert kv_bytes_per_token(cfg, "bfloat16") == kv_pool_bytes(cfg, 1, 1)
    if not cfg.layer_pattern:
        assert kv_pool_bytes_spec(cfg, 9, 8, "int8") == kv_pool_bytes(
            cfg, 9, 8, kv_dtype=jnp.int8)


def test_paged_write_of_one_part_rows():
    """Token scatter and page scatter of a one-part pool put row (page p,
    offset o) at entry p; a K/V pool's entries 2p and 2p + 1 as before."""
    rows = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 1, 128))
    tables = jnp.asarray([[3, 1, 0], [2, 4, 0]], jnp.int32)
    pool = jnp.zeros((6, 8, 128))
    aligned = jnp.arange(16)[None] + jnp.zeros((2, 1), jnp.int32)
    got = paged_write(pool, rows, None, tables, aligned)
    np.testing.assert_array_equal(got[3], rows[0, :8, 0])
    np.testing.assert_array_equal(got[1], rows[0, 8:, 0])
    np.testing.assert_array_equal(got[4], rows[1, 8:, 0])
    np.testing.assert_array_equal(got[5], jnp.zeros((8, 128)))
    shifted = paged_write(pool, rows[:, :3], None, tables, aligned[:, :3] + 7)
    np.testing.assert_array_equal(shifted[3, 7], rows[0, 0, 0])
    np.testing.assert_array_equal(shifted[1, :2], rows[0, 1:3, 0])
    one = paged_write(pool, rows[:, :1], None, tables,
                      jnp.asarray([[9], [0]]))
    np.testing.assert_array_equal(one[1, 1], rows[0, 0, 0])
    np.testing.assert_array_equal(one[2, 0], rows[1, 0, 0])


DECODE_CASES = {
    "one-page": [3, 5, 1, 7, 2],
    "page-boundary": [7, 8, 15, 16, 9],
    "full-table": [47, 47, 40, 33, 47],
    "empty-lanes": [0, 12, 0, 30, 0],
}


@pytest.mark.parametrize("case", list(DECODE_CASES))
@pytest.mark.parametrize("pages_per_block", [0, 1, 2])
def test_latent_decode_kernel_matches_the_gather_path(case, pages_per_block):
    """(d) `mla_latent_decode` in interpret mode against `latent_attention`
    over the gathered table: positions inside one page, on both sides of a
    page boundary, at the table's end, and lanes that hold nothing (the
    garbage page, position 0); blocks of one page, of two, and the whole
    table in one."""
    B, Hq, W, V, ps, P = 5, 4, 128, 32, 8, 6
    key = jax.random.split(jax.random.PRNGKey(0), 2)
    rows = jax.random.normal(key[0], (1 + B * P, ps, W), jnp.float32)
    q = jax.random.normal(key[1], (B, 1, Hq, W), jnp.float32)
    pos = np.asarray(DECODE_CASES[case], np.int32)[:, None]
    tables = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
    if case == "empty-lanes":
        tables[pos[:, 0] == 0] = 0
    args = (q, rows, jnp.asarray(tables), jnp.asarray(pos))
    got = mla_latent_decode(*args, scale=0.2, v_width=V, interpret=True,
                            pages_per_block=pages_per_block)
    want = latent_attention(*args, scale=0.2, v_width=V)
    assert got.shape == (B, 1, Hq, V)
    np.testing.assert_allclose(got, want, atol=2e-6)


# -- prefill then decode through the pool = the reference's full forward -----


@pytest.mark.parametrize("width", [16, 64])
def test_one_window_then_decode(params, tokens, width):
    """(a) 13 real tokens in a window of 16 or of 64, then seven decode
    steps: every logit is the reference's."""
    ids = tokens[40:60]
    want = ref.forward(params, CFG, ids)
    got, paged, state = prefill(params, *fresh(), 2, ids[:13], 0, width,
                                [FROM_ZERO])
    np.testing.assert_allclose(got, want[:13], atol=F32_TOL, rtol=0)
    decode_tail(params, paged, state, 2, ids, 13, want)


def test_two_chained_windows_then_decode(params, tokens):
    """(a) 28 tokens as two 16-rows of ONE dispatch (the cover)."""
    ids = tokens[:36]
    want = ref.forward(params, CFG, ids)
    got, paged, state = prefill(params, *fresh(), 1, ids[:28], 0, 16,
                                [FROM_ZERO, FROM_PREVIOUS_ROW])
    np.testing.assert_allclose(got, want[:28], atol=F32_TOL, rtol=0)
    decode_tail(params, paged, state, 1, ids, 28, want)


def test_a_prompt_longer_than_the_largest_bucket(params, tokens):
    """(a) 84 tokens: a 64-wide chunk, then the tail's two 16-rows in a
    second dispatch, reading the first chunk's rows through the table."""
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


def test_a_reused_slot_and_an_idle_lane(params, tokens):
    """(a) Slot 3 served a long prompt; a short one then takes its pages
    (the rows past its end are the last occupant's, masked by position);
    the other lanes idle beside it on the garbage page."""
    long, short = tokens[100:150], tokens[:20]
    paged, state = fresh()
    _, paged, state = prefill(params, paged, state, 3, long, 0, 64,
                              [FROM_ZERO])
    want = ref.forward(params, CFG, short)
    got, paged, state = prefill(params, paged, state, 3, short[:9], 0, 16,
                                [FROM_ZERO])
    np.testing.assert_allclose(got, want[:9], atol=F32_TOL, rtol=0)
    before = np.asarray(paged.kv[:, BATCH.table(0)])
    decode_tail(params, paged, state, 3, short, 9, want)
    # An idle lane's step writes the garbage page alone.
    _, after, _ = decode(params, paged, state, 3, int(short[9]), 9)
    np.testing.assert_array_equal(
        np.asarray(after.kv[:, BATCH.table(0)]), before)


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
    model="tiny-pangu", tokenizer="byte", dtype="float32",
    max_decode_slots=2, page_size=8, num_pages=160, max_seq_len=256,
    prefill_buckets=(16, 64), max_new_tokens_cap=32, decode_block_steps=4,
)


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(ENGINE, seed=5)
    eng.params = with_seeded_gains(eng.params, jax.random.PRNGKey(11))
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("tokens_in", [10, 28, 84])
def test_engine_serves_what_the_reference_computes(engine, tokens_in):
    """One window; two 16-rows of one dispatch; a 64-wide chunk, then the
    tail's two rows."""
    prompt = text(tokens_in, tokens_in)
    (ids,) = served(engine, [prompt])
    assert len(ids) == 10
    assert worst_margin(ref, engine, prompt, ids) <= F32_TOL


def test_engine_reuses_a_slot_after_a_longer_occupant(engine):
    prompts = [text(n, 200 + n) for n in (70, 9, 30, 12, 40)]
    outs = served(engine, prompts, new=[6, 14, 5, 12, 7])
    for prompt, ids, n in zip(prompts, outs, (6, 14, 5, 12, 7)):
        assert len(ids) == n
        assert worst_margin(ref, engine, prompt, ids) <= F32_TOL


def test_engine_stats_name_the_pool(engine):
    stats = engine.stats()
    assert stats["state_pool_bytes"] == 0
    assert stats["kv_token_bytes"] == CFG.kv_layers * CFG.kv_row_width * 4
    assert stats["kv_pool_bytes"] == (
        ENGINE.num_pages * ENGINE.page_size * stats["kv_token_bytes"])
    assert stats["kv_pool_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(engine.paged))


def test_a_kv_models_engine_reads_its_pool_in_bytes_too():
    eng = InferenceEngine(dataclasses.replace(
        ENGINE, model="tiny-llama", max_seq_len=128))
    try:
        cfg = get_config("tiny-llama")
        stats = eng.stats()
        assert stats["kv_token_bytes"] == (
            2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim * 4)
        assert stats["kv_pool_bytes"] == eng.paged.kv.nbytes
    finally:
        eng.shutdown()


def test_prefix_cache_shares_latent_pages():
    """Cached pages are pages, whatever their parts: a second prompt that
    shares 32 tokens with the first prefills its suffix alone, and both
    streams are the reference's."""
    eng = InferenceEngine(dataclasses.replace(ENGINE, prefix_cache=True),
                          seed=5)
    try:
        eng.params = with_seeded_gains(eng.params, jax.random.PRNGKey(11))
        first = text(40, 1)
        second = first[:32] + text(21, 2)
        for prompt in (first, second, first):
            (ids,) = served(eng, [prompt])
            assert worst_margin(ref, eng, prompt, ids) <= F32_TOL
        assert eng.stats()["prefix_hit_tokens"] >= 32
    finally:
        eng.shutdown()


@pytest.mark.parametrize("knob", [
    {"prefix_cache": True, "host_kv_bytes": 1 << 20},
    {"disagg": "prefill=1,decode=1"},
    {"disagg_tier": "prefill"},
    {"draft_model": "tiny-llama"},
    {"tp": 2},
    {"pp": 2},
    {"dp": 2},
    {"sp": 2},
    {"quantize": True},
    {"kv_dtype": "int8"},
])
def test_what_the_latent_pool_does_not_compose_with_is_refused(knob):
    """(f) Each names the latent pool and what it holds."""
    with pytest.raises(ValueError, match="latent pool") as e:
        dataclasses.replace(ENGINE, **knob).validate()
    assert "40-wide row a token and layer" in str(e.value)


def test_a_latent_model_registered_late_is_still_refused():
    late = dataclasses.replace(ENGINE, model="pangu-registered-late",
                               kv_dtype="int8")
    with pytest.raises(ValueError, match="unknown model"):
        late.validate()
    MODEL_REGISTRY[late.model] = dataclasses.replace(CFG, name=late.model)
    try:
        with pytest.raises(ValueError, match="latent pool"):
            InferenceEngine(late)
        dataclasses.replace(late, kv_dtype="").validate()
    finally:
        del MODEL_REGISTRY[late.model]


def test_the_stack_walker_applies_a_post_norm_only_where_stated(params):
    """`sandwich_norm` off: the same leaves without the second norm are
    the reference without it (the six sibling bodies never see one)."""
    plain = dataclasses.replace(CFG, sandwich_norm=False)
    ids = np.arange(3, 12, dtype=np.int32)
    want = ref.forward(params, plain, ids)
    got, _, _ = SlotBatch(plain, ref, F32_TOL).prefill(
        params, *fresh(), 0, ids, 0, 16, [FROM_ZERO])
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)
    assert "post_norm" not in hybrid.init_layer(
        jax.random.PRNGKey(0), "dense", plain, jnp.float32)
    assert SLOTS == 4


# -- (e) the K/V models' kernels trace as they did before the one-part page --

# sha256 of the traced programs (jaxpr text, addresses blanked) at PR 54's
# tree: the paged decode kernel (bf16, int8, the sp-merge state form), the
# XLA write paths, the blockwise prefill kernel and the held experts'
# products at the sibling cells' widths. This PR gave the decode kernel a
# `parts` argument, `paged_write` an optional V, the blockwise kernel a
# `native` flag and the experts' tiles a byte bound: with the defaults every
# one of them must trace to the program it was. ("flash" re-recorded by PR 59,
# 915df01329ca993c -> 7b0269fc2e46ba51: the grid's key axis is a dynamic bound
# and the K/V index maps take the first and last needed key block of each
# query block, all three scalar-prefetched, and a block whose second half no
# query sees is multiplied by its first half alone; the mask, the sums and
# the other eight programs are as they were.)
_TRACED_AT_PR_54 = {
    "decode-bf16": "27a3f8227381dd04", "decode-int8": "091848e56f944cb7",
    "decode-bf16-state": "3d5e950c9b98ff01", "write-T1": "7b8f5182bc3cf86a",
    "write-T32": "8dab4252880f4314", "flash": "7b0269fc2e46ba51",
    "held-masked": "1df9daf567ae6eeb", "held-grouped": "92f78f53fcf7711d",
    "held-latent": "798481ab2ea5b29d",
}


def _traced(name: str) -> str:
    import hashlib
    import re

    from polykey_tpu.ops import flash_attention as fa
    from polykey_tpu.ops import hybrid_kernels as hk
    from polykey_tpu.ops import paged_attention_kernel as pk

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    if name.startswith("decode"):
        B, Hq, D, Hk, ps, N, P = 16, 32, 128, 8, 16, 512, 64
        quantized = "int8" in name
        kv = S((2 * N, ps, Hk * D), jnp.int8 if quantized else jnp.bfloat16)
        pool = (kv, S((N, ps, Hk)), S((N, ps, Hk))) if quantized else kv
        args = (S((B, Hq, D)), pool, S((B, P), jnp.int32), S((B,), jnp.int32))

        def fn(q, pool, tables, positions):
            return pk._decode_call(
                q, pool, tables, positions, jnp.zeros((1,), jnp.int32),
                jnp.array([0, P], jnp.int32), scale=0.1, logit_softcap=None,
                interpret=False, state=name.endswith("state"))
    elif name.startswith("write"):
        T = int(name.split("T")[1])
        fn = paged_write
        args = (S((1024, 16, 1024)), S((4, T, 8, 128)), S((4, T, 8, 128)),
                S((4, 64), jnp.int32), S((4, T), jnp.int32))
    elif name == "flash":
        def fn(q, k, v, positions):
            return fa.flash_attention(q, k, v, positions, scale=0.1,
                                      force_kernel=True)
        args = (S((2, 512, 32, 128)), S((2, 1024, 8, 128)),
                S((2, 1024, 8, 128)), S((2, 512), jnp.int32))
    elif name == "held-latent":
        def fn(v, up, down, w):
            return hk.moe_held_experts(v, up, down, w, activation="relu2")
        args = (S((64, 1024)), S((128, 1024, 2688)), S((128, 2688, 1024)),
                S((64, 128), jnp.float32))
    else:
        rows = 1024 if name == "held-grouped" else 64

        def fn(v, up, down, w, gate):
            if name == "held-grouped":
                return hk.moe_held_experts_grouped(
                    v, up, down, w, chosen=4, gate=gate, activation="silu")
            return hk.moe_held_experts(v, up, down, w, gate=gate,
                                       activation="silu")
        args = (S((rows, 2048)), S((64, 2048, 1536)), S((64, 1536, 2048)),
                S((rows, 64), jnp.float32), S((64, 2048, 1536)))
    text = re.sub(r"0x[0-9a-f]+", "ADDR", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", list(_TRACED_AT_PR_54))
def test_kv_models_kernels_trace_as_they_did(name):
    assert _traced(name) == _TRACED_AT_PR_54[name]
