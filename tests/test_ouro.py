"""A looped stack (ModelConfig.loop_steps > 1; preset `tiny-ouro`): the SAME
sandwich-normed multi-head layers run `loop_steps` times a token, the final
norm closing every pass, every pass on cache layers of its own
(u · num_layers + l of ONE pool), an exit gate on every pass's output and
the exit rule choosing the hidden state the head reads.

Everything is compared with the plain reference tests/reference_ouro.py
(float32, no cache, the passes, the norm between them, the gate and the
rule written out) on seeded weights at toy size, on LOGITS; the gains are
seeded away from 1 so that a misplaced norm shows, and the gate's w is
spread so that positions leave at different passes.

F32_TOL = 2e-4 on logits of about unit scale: the served path in float32
differs from the reference in summation order alone (paged attention, the
gate as a row sum); readings are 2e-6 to 5e-6. A pass fewer, the norm
between the passes left out or a cache shared by the passes differs by
1e-1 and more (the `fault` cases).
"""

import dataclasses
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_ouro as ref
from pattern_stack import SLOTS, SlotBatch, served, text, worst_margin
from polykey_tpu.engine import roofline
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import (
    InferenceEngine,
    _decode_fn,
    _prefill_fn,
)
from polykey_tpu.engine.kv_cache import init_paged_kv, kv_pool_bytes
from polykey_tpu.models.config import MODEL_REGISTRY, ModelConfig, get_config
from polykey_tpu.models.transformer import (
    forward,
    forward_slots_counted,
    init_params,
    unembed,
)
from polykey_tpu.models.quant import QuantizedTensor, dequantize
from polykey_tpu.obs.exposition import engine_collector

F32_TOL = 2e-4
CFG = get_config("tiny-ouro")
GAINS = ("ln1", "ln2", "post_ln1", "post_ln2", "final_norm")


def looped(passes: int, threshold: float = 1.0) -> ModelConfig:
    return dataclasses.replace(
        CFG, loop_steps=passes, early_exit_threshold=threshold)


def seeded(cfg, key=0, spread=8.0):
    """The package's init with every gain drawn from [0.5, 1.5] and the
    gate's w `spread` times its fan-in scale (λ then covers (0, 1))."""
    params = init_params(jax.random.PRNGKey(key), cfg, jnp.float32)

    def gains(path, w):
        if path[-1].key not in GAINS:
            return w
        salt = sum(map(ord, jax.tree_util.keystr(path)))
        return jax.random.uniform(
            jax.random.fold_in(jax.random.PRNGKey(9), salt), w.shape,
            w.dtype, 0.5, 1.5)

    params = jax.tree_util.tree_map_with_path(gains, params)
    gate = params["exit_gate"]
    return {**params, "exit_gate": {"w": gate["w"] * spread,
                                    "b": gate["b"] + 0.25}}


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (96,), 3, 130), np.int32)


def test_reference_copy_is_the_benchmarks_file():
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.join(here, "..", "perfbench", "references", "ouro.py")
    with open(bench) as a, open(os.path.join(here, "reference_ouro.py")) as b:
        assert a.read() == b.read()


# -- the ModelConfig's two facts -----------------------------------------------

def test_a_pass_owns_its_cache_layers_and_the_weights_count_once():
    assert CFG.loop_steps == 2 and CFG.early_exit_threshold == 1.0
    assert CFG.kv_layers == 2 * CFG.num_layers == 6
    assert looped(4).kv_layers == 12
    once = dataclasses.replace(CFG, loop_steps=1)
    assert once.kv_layers == once.num_layers
    # The gate's w and b, and nothing else, beside the one-pass model's.
    assert CFG.num_params() == once.num_params() + CFG.hidden_size + 1
    assert looped(4).num_params() == CFG.num_params()
    params = init_params(jax.random.PRNGKey(0), CFG, jnp.float32)
    assert CFG.num_params() == sum(x.size for x in jax.tree.leaves(params))
    assert "exit_gate" not in init_params(
        jax.random.PRNGKey(0), once, jnp.float32)


@pytest.mark.parametrize("knob,match", [
    ({"loop_steps": 0}, "must be >= 1"),
    ({"loop_steps": 2, "layer_pattern": "*D*", "dense_intermediate_size": 8},
     "unrolled walk"),
    ({"loop_steps": 2, "sliding_window": 16}, "sliding_window"),
])
def test_model_config_refuses(knob, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **knob)


def test_published_model_counts_what_the_issue_counts():
    """Ouro-2.6B's own sizes: 2,667,974,657 parameters, 192 cache layers,
    1.5 MiB of K and V a token, four reads of the layers a step."""
    full = ModelConfig(
        name="ouro-probe", vocab_size=49152, hidden_size=2048,
        intermediate_size=5632, num_layers=48, num_heads=16, num_kv_heads=16,
        head_dim=128, use_post_norms=True, loop_steps=4)
    assert full.num_params() == 2_667_974_657
    assert full.kv_layers == 192
    assert roofline.kv_bytes_per_token(full, "bfloat16") == 1_572_864
    assert kv_pool_bytes(full, 340, 16) == 340 * 16 * 1_572_864
    assert roofline.kv_pool_bytes_split(full, 340, 16, "bfloat16") == (
        340 * 16 * 1_572_864, 0.0)
    once = dataclasses.replace(full, loop_steps=1)
    layers = 48 * (4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048)
    read = roofline.weight_read_bytes(full, "bfloat16", False, 8)
    # Three more reads of the layers and the final norm, four of the gate.
    assert read - roofline.weight_read_bytes(once, "bfloat16", False, 8) == (
        3 * 2 * (layers + 2048) + 4 * 2 * 2049)
    # Held once, whatever the passes (the gate's 4 KB are not counted).
    assert roofline.weight_resident_bytes(full, "bfloat16", False, 8) == (
        roofline.weight_resident_bytes(once, "bfloat16", False, 8))
    assert roofline.decode_flops_per_token(full, 100.0) > 3.5 * (
        roofline.decode_flops_per_token(once, 100.0) - 4 * 49152 * 2048)


# -- the stack against the reference --------------------------------------------

@pytest.mark.parametrize("passes", [2, 4])
def test_no_cache_forward_is_the_reference(tokens, passes):
    cfg = looped(passes, 0.5)
    params = seeded(cfg)
    ids = tokens[:40]
    hidden, _ = forward(params, cfg, jnp.asarray(ids)[None],
                        jnp.arange(40)[None])
    np.testing.assert_allclose(
        np.asarray(unembed(params, cfg, hidden[0])),
        ref.forward(params, cfg, ids), atol=F32_TOL, rtol=0)


@pytest.mark.parametrize("passes,threshold", [
    (2, 1.0), (4, 1.0), (2, 0.5), (4, 0.5)])
@pytest.mark.parametrize("how", ["whole", "chunked"])
def test_prefill_then_paged_decode_is_the_reference(
        tokens, passes, threshold, how):
    """The prompt as one window or as a 16-chunk and its tail, then decode
    a token at a time, all through the paged pool: every position's logits
    are the reference's full forward's, from the pass its rule chose."""
    cfg = looped(passes, threshold)
    params = seeded(cfg)
    batch = SlotBatch(cfg, ref, F32_TOL)
    ids, n = tokens[:40], 28
    want = ref.forward(params, cfg, ids)
    paged, state = batch.fresh()
    if how == "whole":
        got, paged, state = batch.prefill(
            params, paged, state, 1, ids[:n], 0, 32, [0])
    else:
        first, paged, state = batch.prefill(
            params, paged, state, 1, ids[:16], 0, 16, [0])
        rest, paged, state = batch.prefill(
            params, paged, state, 1, ids[16:n], 16, 16, [0])
        got = np.concatenate([first, rest])
    np.testing.assert_allclose(got, want[:n], atol=F32_TOL, rtol=0)
    batch.decode_tail(params, paged, state, 1, ids, n, want)


def test_lanes_of_one_batch_leave_at_different_passes(tokens):
    """Threshold 0.5 over four passes: the lanes of ONE decode step leave
    at different passes, each lane's logits are the reference's for ITS
    pass, and the step reports the pass a lane."""
    cfg = looped(4, 0.5)
    params = seeded(cfg)
    P = 4
    paged = init_paged_kv(cfg, 1 + SLOTS * P, 8, jnp.float32)
    tables = jnp.arange(1, 1 + SLOTS * P, dtype=jnp.int32).reshape(SLOTS, P)
    rows = np.stack([tokens[i * 20:i * 20 + 20] for i in range(SLOTS)])
    n = 12
    hidden, paged, _, _, exits = forward_slots_counted(
        params, cfg, jnp.asarray(rows[:, :n]),
        jnp.tile(jnp.arange(n)[None], (SLOTS, 1)), paged, tables, None)
    wants = [ref.forward_passes(params, cfg, row) for row in rows]
    np.testing.assert_array_equal(
        np.asarray(exits), np.stack([w[2][:n] for w in wants]))
    seen = set()
    for i in range(n, 20):
        hidden, paged, _, _, exits = forward_slots_counted(
            params, cfg, jnp.asarray(rows[:, i:i + 1]),
            jnp.full((SLOTS, 1), i), paged, tables, None,
            active=jnp.ones(SLOTS, bool))
        logits = np.asarray(unembed(params, cfg, hidden[:, 0]))
        for lane, (by_pass, _, left) in enumerate(wants):
            assert int(exits[lane, 0]) == left[i]
            np.testing.assert_allclose(
                logits[lane], by_pass[left[i], i], atol=F32_TOL, rtol=0)
        seen.add(tuple(int(e) for e in exits[:, 0]))
    assert any(len(set(step)) > 1 for step in seen)      # in ONE step


@pytest.mark.parametrize("fault", ["a_pass_fewer", "no_norm_between",
                                   "shared_cache", "no_post_norms"])
def test_reference_sees_a_fault_of_the_loop(tokens, monkeypatch, fault):
    """The reference's own teeth: each way of running the loop wrongly
    moves its logits by a thousand tolerances."""
    cfg = looped(4)
    params = seeded(cfg)
    ids = tokens[:24]
    want = ref.forward(params, cfg, ids)
    held = {}

    def shared(u, layer, k, v):
        if u == 0:
            held[layer] = (k, v)
        return held[layer]

    patch = {
        "a_pass_fewer": ("passes", lambda cfg: cfg.loop_steps - 1),
        "no_norm_between": (
            "closing_norm",
            lambda x, w, eps, u, last:
            ref.rms_norm(x, w, eps) if u == last else x),
        "shared_cache": ("own_cache", shared),
        "no_post_norms": ("post_norm", lambda y, w, eps: y),
    }[fault]
    monkeypatch.setattr(ref, *patch)
    jax.clear_caches()
    try:
        got = ref.forward(params, cfg, ids)
    finally:
        jax.clear_caches()
    assert np.max(np.abs(got - want)) > 1000 * F32_TOL


def test_a_later_pass_leaves_the_earlier_passes_pages_alone(tokens):
    """Pass u's pages are cache layer u · L + l: after a decode step the
    rows pass 1 wrote lie in layers L .. 2L − 1, and the rows of the pool
    that held pass 0's K and V before the step are byte-identical after
    it but for the step's own position."""
    cfg, L = CFG, CFG.num_layers
    params = seeded(cfg)
    batch = SlotBatch(cfg, ref, F32_TOL)
    paged, state = batch.fresh()
    _, paged, state = batch.prefill(
        params, paged, state, 0, tokens[:12], 0, 16, [0])
    before = np.asarray(paged.kv)
    _, after, _ = batch.decode(params, paged, state, 0, tokens[12], 12)
    after = np.asarray(after.kv)
    page, row = batch.table(0)[12 // 8], 12 % 8
    changed = np.argwhere(np.any(before != after, axis=(2, 4)))
    # Exactly one row of one page a cache layer, in all 2 L of them (the
    # idle lanes write to every cache layer's garbage page 0).
    assert sorted(tuple(map(int, c)) for c in changed if c[1] != 0) == [
        (layer, int(page), row) for layer in range(2 * L)]
    # The two passes wrote DIFFERENT rows (the second read the first's
    # normed output): a shared cache would hold one of them twice.
    assert not np.allclose(after[:L, page, :, row], after[L:, page, :, row])


# -- the engine's two programs ---------------------------------------------------

def _decode_args(cfg, steps=3, B=SLOTS, P=6):
    paged = init_paged_kv(cfg, 1 + B * P, 8, jnp.float32)
    tables = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
    return (paged, jnp.full((B,), 5, jnp.int32), jnp.full((B,), 1, jnp.int32),
            tables, jnp.asarray([True, False, True, True][:B]),
            jnp.full((B,), 40, jnp.int32), jnp.zeros((B, 2), jnp.int32),
            jnp.zeros((B,)), jnp.ones((B,)), jnp.zeros((B,), jnp.int32))


@pytest.mark.parametrize("threshold,spread", [(1.0, 1.0), (0.5, 8.0)])
def test_decode_block_brings_the_exits_home(threshold, spread):
    """`loop_steps` rows after the tokens': the block's live lane-steps by
    the pass the rule chose. At the published threshold and fan-in-scale
    gates every one is in the last bin."""
    cfg = looped(4, threshold)
    params = seeded(cfg, spread=spread)
    steps = 3
    packed, *_ = _decode_fn(
        params, cfg, *_decode_args(cfg), greedy=True, steps=steps, eos_id=-1)
    packed = np.asarray(packed)
    assert packed.shape == (steps + 4, SLOTS)
    exits = packed[steps:]
    assert np.all(exits == exits[:, :1])                 # one count a row
    assert exits[:, 0].sum() == 3 * steps                # three live lanes
    if threshold == 1.0:
        assert exits[:, 0].tolist() == [0, 0, 0, 3 * steps]
    else:
        assert np.count_nonzero(exits[:, 0]) > 1
    assert np.all(packed[:steps, 1] == -1) and np.all(packed[:steps, 0] >= 0)


# Digests of the PARENT's jaxprs (commit 8cb75a3, this installation's JAX),
# made by the same calls as `_program_jaxprs` below.
PARENT_JAXPRS = {
    "jax": "0.9.0",
    "tiny-llama": ("095df9dd91105e95", "3aef56dbfc40b947"),
    "tiny-mixtral": ("4e9bf12aa89b8088", "e50070fe411b5b3d"),
}


def _program_jaxprs(cfg):
    """The text of the engine's prefill ([2, 16]) and decode (4 lanes, 3
    steps) programs over `cfg` at toy geometry."""
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.float32))
    paged, last, seq, tables, active, caps, seeds, temp, top_p, top_k = (
        _decode_args(cfg))
    decode = jax.make_jaxpr(
        lambda params, paged: _decode_fn(
            params, cfg, paged, last, seq, tables, active, caps, seeds, temp,
            top_p, top_k, greedy=True, steps=3, eos_id=-1))(params, paged)
    prefill = jax.make_jaxpr(
        lambda params, paged: _prefill_fn(
            params, cfg, paged, jnp.zeros((2, 16), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.full((2,), 9, jnp.int32),
            tables[:2], seeds[:2], temp[:2], top_p[:2], top_k[:2],
            greedy=True))(params, paged)
    return str(prefill), str(decode)


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-mixtral"])
def test_one_pass_builds_the_parents_programs(model):
    """`loop_steps` 1 builds no outer loop, no gate and no extra output:
    the dense prefill and decode jaxprs are the parent's, letter for
    letter (digests), and hold one scan less than the looped ones."""
    cfg = get_config(model)
    prefill, decode = _program_jaxprs(cfg)
    if jax.__version__ == PARENT_JAXPRS["jax"]:
        digests = tuple(
            hashlib.sha256(t.encode()).hexdigest()[:16]
            for t in (prefill, decode))
        assert digests == PARENT_JAXPRS[model]
    if model == "tiny-llama":
        twice = dataclasses.replace(
            cfg, num_kv_heads=cfg.num_heads, use_post_norms=True,
            loop_steps=2)
        for once, again in zip((prefill, decode), _program_jaxprs(twice)):
            assert again.count("scan[") == once.count("scan[") + 1
            # The gate's sigmoid beside the MLP's silu.
            assert again.count("logistic") == once.count("logistic") + 1


# -- through the engine ------------------------------------------------------------

ENGINE = EngineConfig(
    model="tiny-ouro", tokenizer="byte", dtype="float32",
    max_decode_slots=2, page_size=8, num_pages=160, max_seq_len=256,
    prefill_buckets=(16, 64), max_new_tokens_cap=32, decode_block_steps=4,
)


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(ENGINE, seed=5)
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("tokens_in", [10, 28, 84])
def test_engine_serves_what_the_reference_computes(engine, tokens_in):
    """One window, two 16-rows of one dispatch, a 64-wide chunk and its
    tail: a served token is the reference's argmax up to summation order."""
    prompt = text(tokens_in, tokens_in)
    (ids,) = served(engine, [prompt])
    assert len(ids) == 10
    assert worst_margin(ref, engine, prompt, ids) <= F32_TOL


def test_engine_stats_name_the_loop_and_count_its_passes(engine):
    before = engine.stats()
    served(engine, [text(20, 1), text(33, 2)], new=[9, 12])
    stats = engine.stats()
    once = dataclasses.replace(CFG, loop_steps=1)
    assert stats["loop"] == {
        "steps": 2, "kv_layers": 6,
        "kv_bytes_per_token": 2 * (2 * 4 * 16 * 4) * CFG.num_layers}
    assert stats["kv_token_bytes"] == stats["loop"]["kv_bytes_per_token"]
    assert stats["kv_pool_bytes"] == 2 * kv_pool_bytes(
        once, ENGINE.num_pages, ENGINE.page_size, jnp.float32)
    assert stats["kv_pool_bytes"] == sum(roofline.kv_pool_bytes_split(
        CFG, ENGINE.num_pages, ENGINE.page_size, "float32"))
    steps = stats["steps_dispatched"] - before.get("steps_dispatched", 0)
    assert steps > 0
    assert (stats["loop_layer_passes"] - before.get("loop_layer_passes", 0)
            == steps * 2 * CFG.num_layers)
    exits = np.asarray(stats["loop_exits_by_step"]) - np.asarray(
        before.get("loop_exits_by_step", [0, 0]))
    # Threshold 1: every decoded token (all but each stream's first, the
    # prefill's) counts in the last bin.
    assert exits.tolist() == [0, (9 - 1) + (12 - 1)]


def test_exporter_names_the_loop_counters(engine):
    served(engine, [text(12, 3)], new=5)
    stats = engine.stats()
    body = "\n".join(engine_collector(engine)())
    assert "# TYPE polykey_loop_layer_passes_total counter" in body
    assert (f"polykey_loop_layer_passes_total {stats['loop_layer_passes']}"
            in body)
    assert "# TYPE polykey_loop_exits_total counter" in body
    for step, count in enumerate(stats["loop_exits_by_step"], start=1):
        assert f'polykey_loop_exits_total{{step="{step}"}} {count}' in body


def test_a_dense_engine_reports_no_loop():
    eng = InferenceEngine(dataclasses.replace(ENGINE, model="tiny-llama"),
                          seed=5)
    try:
        served(eng, [text(12, 3)], new=5)
        stats = eng.stats()
    finally:
        eng.shutdown()
    for key in ("loop", "loop_layer_passes", "loop_exits_by_step"):
        assert key not in stats


@pytest.mark.parametrize("knob,names", [
    ({"pp": 2}, "pp > 1"),
    ({"prefix_cache": True, "host_kv_bytes": 1 << 20}, "host_kv_bytes"),
    ({"disagg": "prefill=1,decode=1"}, "disagg / disagg_tier"),
    ({"disagg_tier": "prefill"}, "disagg / disagg_tier"),
    ({"draft_model": "tiny-llama"}, "draft_model"),
])
def test_features_that_count_one_pass_are_refused(knob, names):
    with pytest.raises(ValueError, match="6 cache layers") as e:
        dataclasses.replace(ENGINE, **knob).validate()
    assert names in str(e.value) and "loop_steps" in str(e.value)


def test_a_looped_model_registered_late_is_still_refused():
    late = dataclasses.replace(ENGINE, model="ouro-registered-late",
                               draft_model="tiny-llama")
    MODEL_REGISTRY[late.model] = dataclasses.replace(CFG, name=late.model)
    try:
        with pytest.raises(ValueError, match="draft_model"):
            InferenceEngine(late)
        dataclasses.replace(late, draft_model=None).validate()
    finally:
        del MODEL_REGISTRY[late.model]


@pytest.mark.parametrize("knob", [
    {"prefix_cache": True},
    {"kv_dtype": "int8"},
    {"quantize": True},
    {"tp": 2},
], ids=["prefix_cache", "int8_kv", "int8_weights", "tp2"])
def test_features_that_follow_kv_layers_serve_the_loop(knob):
    """Not refused, so held to the reference: the prefix cache (the second
    request resumes from cached pages of every pass), int8 K/V and int8
    weights (against the reference over the same rounded tree: a margin of
    rounding, not of a wrong pass), tp = 2."""
    eng = InferenceEngine(dataclasses.replace(ENGINE, **knob), seed=5)
    try:
        shared = text(40, 7)
        prompts = [shared + text(9, 8)[:8], shared + text(9, 9)[:8]]
        outs = [served(eng, [p])[0] for p in prompts]
        if knob.get("prefix_cache"):
            assert eng.stats()["prefix_hit_tokens"] >= 40
        tol = 0.05 if "kv_dtype" in knob else F32_TOL
        # The reference reads an int8 leaf dequantized.
        tree = jax.tree.map(
            lambda w: dequantize(w, jnp.float32)
            if isinstance(w, QuantizedTensor) else w,
            eng.params, is_leaf=lambda w: isinstance(w, QuantizedTensor))
        if knob.get("quantize"):
            tol = 5e-3
        for prompt, ids in zip(prompts, outs):
            assert len(ids) == 10
            prompt_ids = eng.tokenizer.encode(prompt)
            logits = ref.forward(tree, eng.model_cfg, np.asarray(
                prompt_ids + ids[:-1], np.int32))
            rows = logits[len(prompt_ids) - 1:]
            worst = max(float(np.max(r) - r[t]) for r, t in zip(rows, ids))
            assert worst <= tol
    finally:
        eng.shutdown()
