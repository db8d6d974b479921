"""The held experts a live lane chose (ISSUE 53): counted inside the decode
program of a layer pattern with expert layers (ops/moe.py
`held_experts_hit`, models/hybrid.py `run_stack`), carried home as one more
row of the block's one packed download (engine `_decode_fn`), and kept as
two monotone counters, `held_experts_hit` and `held_expert_calls`
(engine/metrics.py). Every count here is compared with numpy's count of the
same combine weights over the same live lanes; a model without an expert
layer keeps its program's shapes and has neither counter. Since ISSUE 56
the count is also what the product READS: `run_stack` zeroes an idle
lane's combine weights, the grouped kernel gives an expert nobody chose
no tile, and a live lane's tokens are what they were."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polykey_tpu.engine import engine as engine_mod
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import InferenceEngine
from polykey_tpu.engine.kv_cache import SlotState
from polykey_tpu.engine.metrics import EngineMetrics
from polykey_tpu.models import hybrid
from polykey_tpu.models.config import get_config
from polykey_tpu.models.hybrid import FROM_ZERO
from polykey_tpu.models.transformer import (
    forward_slots,
    forward_slots_counted,
    init_params,
)
from polykey_tpu.obs.exposition import engine_collector
from polykey_tpu.ops import hybrid_kernels, moe
from polykey_tpu.ops.moe import held_experts_hit
import grouped_experts
from pattern_stack import PAGES_PER_SEQ, SLOTS, SlotBatch, served, text

PATTERNS = ["tiny-hybrid", "tiny-lfm2", "tiny-qwen3-next", "tiny-pangu"]
STEPS = 4


def counted_by_numpy(weights, live) -> int:
    weights, live = np.asarray(weights), np.asarray(live)
    return int(np.count_nonzero((weights != 0)[live].any(axis=0)))


@pytest.mark.parametrize("live, want", [
    ([True, True, True], 3),       # experts 0, 1 and 3: an expert, not a pair
    ([True, False, False], 2),     # lane 0 alone chose 0 and 1
    ([False, True, False], 1),     # lane 1 chose 1, as lane 0 did
    ([True, True, False], 2),      # what the idle lane 2 chose (3) is left out
    ([False, False, False], 0),    # a step nobody is live in hits nothing
])
def test_an_expert_counts_once_and_only_for_a_live_row(live, want):
    weights = jnp.asarray([[0.5, 0.5, 0.0, 0.0],
                           [0.0, 1.0, 0.0, 0.0],
                           [0.0, 0.0, 0.0, 1.0]], jnp.float32)
    got = held_experts_hit(weights, jnp.asarray(live))
    assert got.dtype == jnp.int32 and got.shape == ()
    assert int(got) == want == counted_by_numpy(weights, live)


class Spy:
    """Stands in for models/hybrid.py's `held_experts_hit`: keeps the
    concrete (combine weights, live lanes) of every call."""

    def __init__(self):
        self.calls = []

    def __call__(self, weights, live):
        self.calls.append((np.asarray(weights), np.asarray(live)))
        return held_experts_hit(weights, live)


@functools.lru_cache(maxsize=None)
def lanes_after_prefill(name):
    """Slots 0, 1 and 3 of a toy slot batch hold prompts of other lengths
    (slot 2 was never used): (cfg, params, paged, state, last tokens,
    sequence lengths, page tables). Made once a model: nothing here
    donates or writes them."""
    cfg = get_config(name)
    batch = SlotBatch(cfg, None, None)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    paged, state = batch.fresh()
    last = np.zeros((SLOTS,), np.int32)
    seq = np.ones((SLOTS,), np.int32)
    tables = np.zeros((SLOTS, PAGES_PER_SEQ), np.int32)
    for slot, n in ((0, 9), (1, 14), (3, 5)):
        ids = np.asarray(jax.random.randint(
            jax.random.PRNGKey(slot), (n,), 3, 130), np.int32)
        logits, paged, state = batch.prefill(
            params, paged, state, slot, ids, 0, 16, [FROM_ZERO])
        last[slot], seq[slot] = int(np.argmax(logits[-1])), n + 1
        tables[slot] = batch.table(slot)
    return cfg, params, paged, state, last, seq, tables


@pytest.mark.parametrize("name", PATTERNS)
def test_a_decode_step_counts_its_live_lanes_choices(name, monkeypatch):
    """One step through `forward_slots_counted`, lane 1 idle (it routes
    its token all the same): the count is numpy's over the live lanes of
    every expert layer, and `forward_slots` is the same step without it."""
    cfg, params, paged, state, last, seq, tables = lanes_after_prefill(name)
    active = jnp.asarray([True, False, False, True])
    spy, routed = Spy(), []
    monkeypatch.setattr(hybrid, "held_experts_hit", spy)

    def as_routed(p, tokens, cfg):
        routed.append(np.asarray(moe.held_weights(p, tokens, cfg)))
        return routed[-1]

    monkeypatch.setattr(hybrid, "held_weights", as_routed)
    step = functools.partial(
        forward_slots_counted, params, cfg, jnp.asarray(last)[:, None],
        jnp.asarray(seq - 1)[:, None], paged, jnp.asarray(tables), state,
        active=active)
    hidden, _, _, hits, _ = step()
    assert len(spy.calls) == len(routed) == cfg.layer_pattern.count("E")
    assert int(hits) == sum(counted_by_numpy(w, live) for w, live in spy.calls)
    assert all((live == np.asarray(active)).all() for _, live in spy.calls)
    # What is counted is what the product is handed (ISSUE 56): the
    # router's weights on the live lanes, zeros on the idle ones.
    for (w, live), raw in zip(spy.calls, routed):
        np.testing.assert_array_equal(w, np.where(live[:, None], raw, 0.0))
    # The case bites: the idle lanes chose held experts no live lane chose.
    everyone = np.ones((SLOTS,), bool)
    assert int(hits) < sum(counted_by_numpy(w, everyone) for w in routed)
    # Bounded by what a layer holds and by what the live lanes can choose.
    assert 0 < int(hits) <= len(spy.calls) * min(
        cfg.experts_held, 2 * cfg.num_experts_per_tok)
    thin = forward_slots(
        params, cfg, jnp.asarray(last)[:, None], jnp.asarray(seq - 1)[:, None],
        paged, jnp.asarray(tables), state, active=active)
    assert len(thin) == 3
    np.testing.assert_array_equal(thin[0], hidden)


def test_a_prefill_counts_nothing(monkeypatch):
    cfg = get_config("tiny-hybrid")
    spy = Spy()
    monkeypatch.setattr(hybrid, "held_experts_hit", spy)
    batch = SlotBatch(cfg, None, None)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    batch.prefill(params, *batch.fresh(), 1, np.arange(3, 12, dtype=np.int32),
                  0, 16, [FROM_ZERO])
    assert spy.calls == []


def decode_block(cfg, last, seq, tables, active, caps):
    """`_decode_fn` of one greedy block of STEPS steps, as a function of
    (params, paged, state)."""
    zeros = jnp.zeros((SLOTS,), jnp.float32)

    def block(params, paged, state):
        return engine_mod._decode_fn(
            params, cfg, paged, jnp.asarray(last), jnp.asarray(seq),
            jnp.asarray(tables), jnp.asarray(active),
            jnp.asarray(caps, jnp.int32), jnp.zeros((SLOTS, 2), jnp.uint32),
            zeros, zeros + 1.0, jnp.zeros((SLOTS,), jnp.int32), state,
            greedy=True, steps=STEPS, eos_id=-1)

    return block


@pytest.mark.parametrize("name", PATTERNS)
def test_a_block_sends_its_count_home_as_one_more_row(name, monkeypatch):
    """`_decode_fn` run step by step: lane 0 reaches its cap after two
    steps, lane 3 runs all four, lanes 1 and 2 are idle. The block's sum,
    over steps and expert layers, is the one extra row, in every column;
    each call saw the lanes live AT ITS STEP."""
    cfg, params, paged, state, last, seq, tables = lanes_after_prefill(name)
    active = np.asarray([True, False, False, True])
    caps = seq + np.asarray([2, 0, 0, 100])
    spy = Spy()
    monkeypatch.setattr(hybrid, "held_experts_hit", spy)
    with jax.disable_jit():
        packed = np.asarray(decode_block(
            cfg, last, seq, tables, active, caps)(params, paged, state)[0])
    assert packed.shape == (STEPS + 1, SLOTS)
    tokens, row = packed[:-1], packed[-1]
    assert ((tokens >= 0) == [[True, False, False, True]] * 2
            + [[False, False, False, True]] * 2).all()
    layers = cfg.layer_pattern.count("E")
    assert len(spy.calls) == STEPS * layers
    for k in range(STEPS):
        for _, live in spy.calls[k * layers:(k + 1) * layers]:
            assert (live == (tokens[k] >= 0)).all()
    want = sum(counted_by_numpy(w, live) for w, live in spy.calls)
    assert want > 0 and (row == want).all()


def as_the_parent_routed(p, h, cfg, weights=None):
    """`moe_held` as `run_stack` called it before ISSUE 56: every lane,
    idle or not, with the weights its own token routed to."""
    del weights
    return moe.moe_held(p, h, cfg)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
@pytest.mark.parametrize("name", PATTERNS)
def test_live_lanes_stream_what_they_did_beside_idle_ones(
        name, form, monkeypatch):
    """A greedy block of `_decode_fn` with lanes 1 and 2 idle and lane 0
    ending after two steps: every live lane's tokens, step by step, are
    those of the block whose expert layers see the idle lanes' routing too
    (the parent's `run_stack`) — off the chip, and with the grouped kernel
    (interpret mode) as the chip runs it."""
    cfg, params, paged, state, last, seq, tables = lanes_after_prefill(name)
    active = np.asarray([True, False, False, True])
    caps = seq + np.asarray([2, 0, 0, 100])
    if form == "kernel":
        monkeypatch.setattr(moe, "held_experts_grouped", lambda rows: True)
        monkeypatch.setattr(
            hybrid_kernels, "moe_held_experts_grouped", functools.partial(
                hybrid_kernels.moe_held_experts_grouped, interpret=True))
    block = decode_block(cfg, last, seq, tables, active, caps)
    with jax.disable_jit():
        got = np.asarray(block(params, paged, state)[0])
        monkeypatch.setattr(hybrid, "moe_held", as_the_parent_routed)
        want = np.asarray(block(params, paged, state)[0])
    live = want[:-1] >= 0
    assert live.sum() == 2 + STEPS
    np.testing.assert_array_equal(got[:-1] >= 0, live)
    np.testing.assert_array_equal(got[:-1][live], want[:-1][live])
    # The count was always the live lanes': it is the same number.
    np.testing.assert_array_equal(got[-1], want[-1])


@pytest.mark.parametrize("live", list(grouped_experts.LIVE))
@pytest.mark.parametrize("form", list(grouped_experts.DECODE_FORMS))
def test_grouped_kernel_on_a_decode_call_matches_jnp(form, live):
    """64 rows, the idle lanes' weights zeroed: two idle, ONE live, NONE."""
    grouped_experts.check_decode_call(form, live)


@pytest.mark.parametrize("live", list(grouped_experts.LIVE))
@pytest.mark.parametrize("form", list(grouped_experts.DECODE_FORMS))
def test_the_kernels_read_is_the_counters_count(form, live):
    grouped_experts.check_read_is_the_count(form, live)


@pytest.mark.parametrize("name, rows", [
    ("tiny-llama", STEPS), ("tiny-mixtral", STEPS), ("tiny-hybrid", STEPS + 1),
])
def test_only_a_pattern_with_expert_layers_downloads_the_row(name, rows):
    """A model without an "E" layer (Mixtral's `moe_mlp` is no held-experts
    product) downloads [steps, B], as it did."""
    cfg = get_config(name)
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.float32))
    batch = SlotBatch(cfg, None, None)
    paged, state = jax.eval_shape(batch.fresh)
    if not cfg.stateful:
        state = SlotState()
    ints = np.ones((SLOTS,), np.int32)
    block = decode_block(
        cfg, ints, ints, np.zeros((SLOTS, PAGES_PER_SEQ), np.int32), ints > 0,
        ints + 8)
    packed = jax.eval_shape(block, params, paged, state)[0]
    assert packed.shape == (rows, SLOTS) and packed.dtype == jnp.int32


# -- through the engine ------------------------------------------------------

HYBRID = EngineConfig(
    model="tiny-hybrid", tokenizer="byte", dtype="float32",
    max_decode_slots=2, page_size=8, num_pages=160, max_seq_len=256,
    prefill_buckets=(16, 64), max_new_tokens_cap=32, decode_block_steps=4,
)
DENSE = EngineConfig(
    model="tiny-llama", tokenizer="byte", dtype="float32",
    max_decode_slots=2, page_size=8, num_pages=32, max_seq_len=64,
    prefill_buckets=(16, 32), max_new_tokens_cap=16,
)
KEYS = ("held_experts_hit", "held_expert_calls")


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(HYBRID, seed=5)
    yield eng
    eng.shutdown()


def test_engine_counts_calls_by_live_steps_and_hits_within_bounds(engine):
    """One stream on two slots: every decode step with a live lane is one
    call an expert layer (the steps a block ran past the stream's end are
    not), the other lane is idle throughout, and one live lane cannot hit
    more held experts than it chooses."""
    cfg = engine.model_cfg
    first = engine.stats()
    assert all(key not in first for key in KEYS)     # nothing decoded yet
    (ids,) = served(engine, [text(20, 20)], new=10)
    after = engine.stats()
    layers = cfg.layer_pattern.count("E")
    # The first token is the prefill's; each of the other nine is a step.
    assert after["held_expert_calls"] == layers * (len(ids) - 1)
    assert 0 < after["held_experts_hit"] <= after["held_expert_calls"] * min(
        cfg.experts_held, cfg.num_experts_per_tok)


def test_engine_counters_only_grow_and_keep_their_bound(engine):
    before = engine.stats()
    served(engine, [text(n, n) for n in (9, 30, 12, 40, 25)],
           new=[12, 5, 9, 7, 10])
    after = engine.stats()
    calls, hit = (after[key] - before.get(key, 0) for key in reversed(KEYS))
    layers = engine.model_cfg.layer_pattern.count("E")
    # Two lanes decode side by side: fewer live steps than tokens, never
    # fewer than the longest stream's.
    assert layers * 11 <= calls <= layers * (11 + 4 + 8 + 6 + 9)
    assert calls % layers == 0
    assert 0 < hit <= calls * engine.model_cfg.experts_held


def test_engine_exports_both_counters(engine):
    served(engine, [text(9, 1)], new=3)
    stats = engine.stats()
    body = "\n".join(engine_collector(engine)())
    for key, name in zip(KEYS, ("polykey_held_experts_hit_total",
                                "polykey_held_expert_calls_total")):
        assert f"# TYPE {name} counter" in body
        assert f"{name} {stats[key]}" in body


def test_a_dense_engine_has_neither_counter():
    eng = InferenceEngine(DENSE)
    try:
        served(eng, ["no expert layer here"], new=6)
        stats = eng.stats()
        assert stats["blocks_processed"] > 0
        assert all(key not in stats for key in KEYS)
        assert "polykey_held_expert" not in "\n".join(engine_collector(eng)())
    finally:
        eng.shutdown()


def test_the_hook_adds_to_both_counters_under_one_lock():
    metrics = EngineMetrics()
    assert all(key not in metrics.snapshot() for key in KEYS)
    metrics.on_held_experts(16, 37)
    metrics.on_held_experts(8, 11)
    snap = metrics.snapshot()
    assert (snap["held_expert_calls"], snap["held_experts_hit"]) == (24, 48)
