"""Engine phases, request phases and lane-step outcomes (ISSUE 26): the
one span primitive and the always-on accumulators it feeds. Counts,
identities and orderings only — never a duration (tiny model, CPU)."""

import dataclasses
import os
import time

import pytest

from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.metrics import EngineMetrics, RequestTimings
from polykey_tpu.obs.exposition import engine_collector
from polykey_tpu.obs.timeline import (
    EVENT_FIELDS,
    LOOP_PHASES,
    PHASES,
    phase,
)

from test_engine import _collect

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K = 8
CONFIG = EngineConfig(
    model="tiny-llama",
    tokenizer="byte",
    dtype="float32",
    max_decode_slots=4,
    page_size=8,
    num_pages=96,
    max_seq_len=96,
    prefill_buckets=(16,),
    max_new_tokens_cap=40,
    decode_block_steps=K,
    lookahead_blocks=2,
)
SHORT = "hello phase"                    # 12 tokens: the 16 bucket
LONG = "a prompt longer than any bucket, so it prefills in chunks"


def stopped_engine(config=CONFIG) -> InferenceEngine:
    """An engine whose loop has ended, to be driven by hand: every step
    of the schedule is then the test's, not the thread's."""
    eng = InferenceEngine(config)
    eng.shutdown()
    return eng


def wait_drained(eng: InferenceEngine) -> None:
    deadline = time.monotonic() + 60.0
    while eng.busy or eng._inflight_q:
        assert time.monotonic() < deadline, "engine did not drain"
        time.sleep(0.01)


@pytest.fixture(scope="module")
def live():
    eng = InferenceEngine(CONFIG)
    yield eng
    eng.shutdown()


# -- the table and the primitive ---------------------------------------------


def test_accumulators_hold_exactly_the_table():
    metrics = EngineMetrics()
    assert set(metrics.phase_seconds) == set(PHASES)
    assert set(metrics.phase_count) == set(PHASES)
    assert set(LOOP_PHASES) == {n for n, (level, _) in PHASES.items()
                                if level == "loop"}
    assert {level for level, _ in PHASES.values()} == {"loop", "nested"}
    snap = metrics.snapshot()
    assert set(snap["phase_seconds"]) == set(PHASES)
    assert snap["phase_count"] == dict.fromkeys(PHASES, 0)


@pytest.mark.parametrize("name", sorted(PHASES))
def test_phase_counts_once_and_is_documented(name):
    metrics = EngineMetrics()
    with phase(metrics, name, seq=3, lanes=2):
        pass
    assert metrics.phase_count[name] == 1
    assert sum(metrics.phase_count.values()) == 1
    assert metrics.phase_seconds[name] >= 0.0
    with open(os.path.join(ROOT, "COMPONENTS.md")) as f:
        assert f"`{name}`" in f.read()


@pytest.mark.parametrize("kind", sorted(EVENT_FIELDS))
def test_timeline_event_kinds_are_documented(kind):
    with open(os.path.join(ROOT, "COMPONENTS.md")) as f:
        assert f"| `{kind}` |" in f.read()


def test_a_phase_outside_the_table_is_refused():
    metrics = EngineMetrics()
    with pytest.raises(KeyError):
        with phase(metrics, "not_a_phase"):
            pass
    assert sum(metrics.phase_count.values()) == 0


def test_perfbench_reader_sums_the_loop_phases_that_work(monkeypatch):
    import importlib

    monkeypatch.syspath_prepend(os.path.join(ROOT, "perfbench"))
    reader = importlib.import_module("phases")
    assert set(reader.LOOP_WORK_PHASES) == set(LOOP_PHASES) - {"idle_wait"}


# -- request phases ------------------------------------------------------------


def test_ttft_phases_partition_without_stamps():
    timings = RequestTimings(enqueued=1.0)
    timings.first_token = 3.0
    assert timings.ttft_phases_s() == (0.0, 0.0, 2.0)
    timings.prefill_start = 1.5
    assert timings.ttft_phases_s() == (0.5, 0.0, 1.5)
    timings.prefill_dispatched = 2.0
    assert timings.ttft_phases_s() == (0.5, 0.5, 1.0)
    assert sum(timings.ttft_phases_s()) * 1e3 == timings.ttft_ms


@pytest.mark.parametrize("prompts", [
    [SHORT], [SHORT + " a", SHORT + " b", SHORT + " c"], [LONG],
], ids=["bucketed", "grouped", "chunked"])
def test_ttft_phases_sum_to_ttft(prompts):
    eng = stopped_engine()
    requests = [GenRequest(prompt=p, max_new_tokens=4) for p in prompts]
    for r in requests:
        eng._submit.put(r)
    eng._admit()
    while any(s is not None and s.pending is not None for s in eng._slots):
        eng._advance_chunked_prefills(None)
    eng._resolve_prefills(block=True)
    if len(prompts) > 1:
        # One group dispatch: one stamp for all its members.
        assert len({r.timings.prefill_dispatched for r in requests}) == 1
    total_ms = 0.0
    for r in requests:
        t = r.timings
        assert t.enqueued <= t.prefill_start <= t.prefill_dispatched \
            <= t.first_token
        phases_ms = sum(t.ttft_phases_s()) * 1e3
        assert phases_ms == pytest.approx(t.ttft_ms, abs=1e-6)
        total_ms += t.ttft_ms
    snap = eng.metrics.snapshot()
    assert snap["ttft_phase_count"] == len(requests)
    assert sum(snap["ttft_phase_seconds"].values()) * 1e3 == \
        pytest.approx(total_ms, abs=1e-2)
    chunks = -(-len(eng.tokenizer.encode(LONG)) // 16)
    assert snap["phase_count"]["prefill"] == (
        chunks if prompts == [LONG] else 1)
    assert snap["phase_count"]["chunk"] == (
        chunks if prompts == [LONG] else 0)


def test_traced_request_gets_prefill_wait_and_prefill_spans():
    from polykey_tpu.obs.trace import Span

    eng = stopped_engine()
    root = Span("rpc")
    request = GenRequest(prompt=SHORT, max_new_tokens=4, trace=root)
    eng._submit.put(request)
    eng._admit()
    eng._resolve_prefills(block=True)
    spans = {c.name: c for c in root.children}
    assert {"queue_wait", "prefill_wait", "prefill", "decode"} <= set(spans)
    t = request.timings
    assert spans["queue_wait"].end == spans["prefill_wait"].start \
        == t.prefill_start
    assert spans["prefill_wait"].end == spans["prefill"].start \
        == t.prefill_dispatched
    assert spans["prefill"].end == spans["decode"].start == t.first_token
    assert spans["prefill"].attrs["prompt_tokens"] == t.prompt_tokens


# -- admission deferrals ------------------------------------------------------


@pytest.mark.parametrize("reason", ["no_slot", "budget", "no_pages"])
def test_admit_deferred_counts_the_reason(reason):
    config = CONFIG
    if reason == "no_pages":
        # 7 usable pages: one 12 + 40 token request takes all of them.
        config = dataclasses.replace(CONFIG, num_pages=8)
    eng = stopped_engine(config)
    n = {"no_slot": 6, "budget": 3, "no_pages": 2}[reason]
    for i in range(n):
        eng._submit.put(GenRequest(prompt=f"{SHORT} {i}", max_new_tokens=40))
    admitted, spent = eng._admit(budget=16 if reason == "budget" else None)
    assert admitted
    taken = {"no_slot": 4, "budget": 1, "no_pages": 1}[reason]
    assert sum(s is not None for s in eng._slots) == taken
    assert eng._submit.qsize() == n - taken
    want = dict.fromkeys(("no_slot", "no_pages", "budget"), 0)
    want[reason] = 1
    assert eng.metrics.snapshot()["admit_deferred"] == want
    if reason == "budget":
        assert spent == 16


def test_an_empty_queue_defers_nothing():
    eng = stopped_engine()
    assert eng._admit() == (False, 0)
    assert sum(eng.metrics.admit_deferred.values()) == 0


# -- lane-step outcomes -----------------------------------------------------


def lane_steps(eng) -> tuple:
    m = eng.metrics
    return (m.decode_lane_steps_delivered, m.decode_lane_steps_overshoot,
            m.decode_lane_steps_dead)


def test_lane_step_identity_by_hand():
    """Three streams on four slots, three blocks dispatched ahead: one
    stream ends exactly on the first block's edge, one mid-way through
    the second, one is cancelled before the second is processed, and the
    third block is dead by the time it is reached."""
    eng = stopped_engine()
    edge = GenRequest(prompt=SHORT + " e", max_new_tokens=1 + K)
    mid = GenRequest(prompt=SHORT + " m", max_new_tokens=1 + K + 3)
    gone = GenRequest(prompt=SHORT + " g", max_new_tokens=40)
    for r in (edge, mid, gone):
        eng._submit.put(r)
    eng._admit()
    eng._resolve_prefills(block=True)
    blocks = [eng._dispatch_step() for _ in range(3)]
    assert [b.steps for b in blocks] == [K, K, K]
    assert [len(b.live) for b in blocks] == [3, 3, 3]

    eng._process_step(blocks[0])
    first = lane_steps(eng)
    assert sum(first) == 4 * K and first[2] == K
    gone.cancelled.set()
    eng._process_step(blocks[1])
    assert all(s is None for s in eng._slots)
    before_dead = lane_steps(eng)
    eng._process_step(blocks[2])            # dead: skipped unread
    assert eng.metrics.blocks_synced == 2
    after = lane_steps(eng)
    assert after[0] == before_dead[0]
    assert after[1] - before_dead[1] == 3 * K
    assert after[2] == 3 * K
    assert sum(after) == 3 * 4 * K

    streamed = 0
    for r in (edge, mid, gone):
        tokens, _done, _error = _collect(r, timeout=5.0)
        streamed += len(tokens) - 1         # the first comes from prefill
    assert after[0] == streamed == eng.metrics.tokens_generated
    # Live lanes that delivered nothing: at least the cancelled lane's
    # second block and the whole dead block.
    assert after[1] >= K + 3 * K


def test_a_legacy_block_without_steps_is_not_counted():
    import numpy as np

    eng = stopped_engine()
    packed = np.full((K, 4), -1, dtype=np.int32)
    eng._process_step(("plain", packed, [None] * 4))
    assert lane_steps(eng) == (0, 0, 0)


def test_lane_step_identity_on_the_running_loop(live):
    before = lane_steps(live)
    requests = [GenRequest(prompt=f"{SHORT} {i}", max_new_tokens=n)
                for i, n in enumerate((1 + K, 1 + K + 3, 2, 30, 30, 17))]
    for r in requests:
        live.submit(r)
    requests[3].cancelled.set()
    streamed = firsts = 0
    for r in requests:
        tokens, _done, _error = _collect(r)
        streamed += len(tokens)
        firsts += bool(tokens)
    wait_drained(live)
    delivered, overshoot, dead = (
        a - b for a, b in zip(lane_steps(live), before))
    assert delivered == streamed - firsts
    steps_of = {e["seq"]: e["steps"] for e in live.timeline.events()
                if e["kind"] == "dispatch"}
    processed = [e["seq"] for e in live.timeline.events()
                 if e["kind"] == "process"]
    assert len(processed) == live.metrics.blocks_processed
    assert delivered + overshoot + dead == sum(
        CONFIG.max_decode_slots * steps_of[seq] for seq in processed)
    assert overshoot >= 0 and dead >= 0


def test_phase_counts_match_the_dispatch_counters(live):
    calls = {"prefill": 0}
    jit_prefill = live._jit_prefill

    def counting(*args, **kwargs):
        calls["prefill"] += 1
        return jit_prefill(*args, **kwargs)

    wait_drained(live)
    before = live.metrics.snapshot()
    synced_before = live.metrics.blocks_synced
    live._jit_prefill = counting
    try:
        for prompt in (SHORT, LONG, SHORT + " again"):
            r = GenRequest(prompt=prompt, max_new_tokens=12)
            live.submit(r)
            _tokens, done, error = _collect(r)
            assert error is None and done is not None
        wait_drained(live)
    finally:
        live._jit_prefill = jit_prefill
    after = live.metrics.snapshot()

    def grew(key, name=None):
        if name is None:
            return after[key] - before[key]
        return after[key][name] - before[key][name]

    blocks = grew("blocks_dispatched")
    assert blocks > 0 and calls["prefill"] >= 3
    # Every decode block and every prefill dispatch is one span.
    assert grew("phase_count", "dispatch") == blocks
    assert grew("phase_count", "decode") == blocks
    assert grew("phase_count", "prefill") == calls["prefill"]
    assert grew("phase_count", "dispatch") + grew("phase_count", "prefill") \
        == blocks + calls["prefill"]
    assert grew("phase_count", "process") == grew("blocks_processed")
    assert grew("phase_count", "readback_wait") == \
        live.metrics.blocks_synced - synced_before
    assert grew("phase_count", "admit") >= 3
    assert grew("phase_count", "resolve") >= 1
    assert grew("prefill_rows_dispatched") == 16 * calls["prefill"]
    assert grew("prefill_rows_useful") == sum(
        len(live.tokenizer.encode(p))
        for p in (SHORT, LONG, SHORT + " again"))


def test_exporter_renders_one_sample_per_phase_and_counter(live):
    page = "\n".join(engine_collector(live)())
    for name in PHASES:
        assert f'polykey_engine_phase_seconds_total{{phase="{name}"}}' in page
        assert f'polykey_engine_phase_entries_total{{phase="{name}"}}' in page
    for name in ("queue", "prefill_wait", "first_token"):
        assert f'polykey_ttft_phase_seconds_total{{phase="{name}"}}' in page
    for reason in ("no_slot", "no_pages", "budget"):
        assert f'polykey_admit_deferred_total{{reason="{reason}"}}' in page
    for family in ("polykey_decode_lane_steps_delivered_total",
                   "polykey_decode_lane_steps_overshoot_total",
                   "polykey_decode_lane_steps_dead_total",
                   "polykey_prefill_rows_dispatched_total",
                   "polykey_prefill_rows_useful_total",
                   "polykey_ttft_phase_requests_total"):
        assert f"\n{family} " in page
    stats = live.stats()
    for key in ("phase_seconds", "phase_count", "ttft_phase_seconds",
                "ttft_phase_count", "admit_deferred",
                "decode_lane_steps_delivered", "prefill_rows_useful"):
        assert key in stats
