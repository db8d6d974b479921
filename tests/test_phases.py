"""Engine phases, request phases and lane-step outcomes (ISSUE 26): the
one span primitive and the always-on accumulators it feeds. Counts,
identities and orderings only — never a duration (tiny model, CPU)."""

import dataclasses
import os
import time

import pytest

from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.metrics import (
    QUEUE_CAUSES,
    EngineMetrics,
    RequestTimings,
)
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
    assert {level for level, _ in PHASES.values()} == {
        "loop", "nested", "startup"}
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
    assert not eng._admit_visits


# -- queue time by cause (ISSUE 45) ---------------------------------------------


def serve_queue(eng, budget=None, free=None) -> None:
    """Visit `_admit` until the queue is empty, reading every first token
    after each visit; `free(eng)` makes room between visits."""
    while not eng._submit.empty():
        eng._admit(budget=budget)
        eng._resolve_prefills(block=True)
        if free is not None:
            free(eng)


def finish_all(eng) -> None:
    for i, slot in enumerate(eng._slots):
        if slot is not None:
            eng._finish(i)


@pytest.mark.parametrize("cause", ["loop", "budget", "no_slot", "no_pages"])
def test_queue_causes_partition_the_queue_phase(cause):
    """Four forced situations: nothing deferred, the iteration's budget
    spent, no free slot, AllocationError. Every request's causes add up
    to its queue phase; only `loop` and the forced cause hold time."""
    config, budget, free = CONFIG, None, None
    if cause == "budget":
        budget = 16                          # one 16-row window a visit
    elif cause == "no_slot":
        free = finish_all
    elif cause == "no_pages":
        # 7 usable pages: one 12 + 40 token request takes all of them.
        config, free = dataclasses.replace(CONFIG, num_pages=8), finish_all
    eng = stopped_engine(config)
    n = {"loop": 3, "budget": 3, "no_slot": 6, "no_pages": 3}[cause]
    requests = [GenRequest(prompt=f"{SHORT} {i}", max_new_tokens=40)
                for i in range(n)]
    for r in requests:
        eng._submit.put(r)
    serve_queue(eng, budget=budget, free=free)

    waited = [r for r in requests if r.timings.queue_deferred]
    for r in requests:
        t = r.timings
        assert t.first_token > 0.0
        assert set(t.queue_deferred) <= {cause}
        queue = t.ttft_phases_s()[0]
        assert 0.0 <= sum(t.queue_deferred.values()) <= queue
    # The first visit admits what it can; who it left waits behind it.
    assert len(waited) == {"loop": 0, "budget": 2, "no_slot": 2,
                           "no_pages": 2}[cause]
    if cause != "loop":
        last = requests[-1].timings
        visits = 2 if cause == "no_slot" else 3
        assert eng.metrics.admit_deferred[cause] == visits - 1
        # Behind every deferring visit up to the start of its own.
        assert 0.0 < last.queue_deferred[cause] < last.ttft_phases_s()[0]

    by_cause = eng.metrics.ttft_queue_seconds
    assert set(by_cause) == set(QUEUE_CAUSES)
    assert sum(by_cause.values()) == pytest.approx(
        eng.metrics.ttft_phase_seconds["queue"], abs=1e-6)
    assert by_cause["loop"] > 0.0
    for name in set(QUEUE_CAUSES) - {"loop", cause}:
        assert by_cause[name] == 0.0
    assert by_cause[cause] > 0.0
    snap = eng.metrics.snapshot()           # rounded to the microsecond
    assert sum(snap["ttft_queue_seconds"].values()) == pytest.approx(
        snap["ttft_phase_seconds"]["queue"], abs=4e-6)


def test_queue_cause_stretches_are_cut_at_the_visits():
    """By hand: visits that ended at 2 (budget), 3 (budget) and 5
    (no_slot) left a request enqueued at 1 waiting; the admitting visit
    began at 6. A visit that ended before it arrived is not its."""
    eng = stopped_engine()
    eng._admit_visits.extend(
        [(0.5, "no_pages"), (2.0, "budget"), (3.0, "budget"),
         (5.0, "no_slot")])
    eng._admit_began = 6.0
    timings = RequestTimings(enqueued=1.0)
    eng._stamp_admitted(timings)
    assert timings.queue_deferred == {"budget": 3.0, "no_slot": 1.0}
    assert [end for end, _ in eng._admit_visits] == [2.0, 3.0, 5.0]
    timings.prefill_start = 6.5
    timings.first_token = 7.0
    eng.metrics.on_first_token(timings)
    assert eng.metrics.ttft_queue_seconds == {
        "loop": 1.5, "budget": 3.0, "no_slot": 1.0, "no_pages": 0.0}
    # A request that arrived after the last of them waited on the loop.
    late = RequestTimings(enqueued=5.5)
    eng._stamp_admitted(late)
    assert late.queue_deferred == {} and not eng._admit_visits


# -- a finished first token waiting for the host (ISSUE 45) --------------------


def test_first_token_phase_once_per_resolved_dispatch():
    """Two group dispatches (cap 2 a visit by budget) and a chunked
    prompt: `first_token` is entered once per dispatch whose tokens are
    read, inside `resolve`, oldest dispatch first; the poll-gap pair
    moves only then."""
    eng = stopped_engine()
    prompts = [SHORT + " a", SHORT + " b", SHORT + " c", LONG]
    requests = [GenRequest(prompt=p, max_new_tokens=4) for p in prompts]
    for r in requests[:3]:
        eng._submit.put(r)
    eng._admit(budget=32)                   # a and b: one dispatch of two
    eng._admit(budget=32)                   # c: a dispatch of its own
    eng._submit.put(requests[3])
    eng._admit()                            # registered for chunks
    while any(s is not None and s.pending is not None for s in eng._slots):
        eng._advance_chunked_prefills(None)
    m = eng.metrics
    chunks = -(-len(eng.tokenizer.encode(LONG)) // 16)
    assert m.phase_count["prefill"] == 2 + chunks
    # Only a dispatch that completes a prompt leaves tokens to read.
    assert [len(rec.members) for rec in eng._first_tokens] == [2, 1, 1]
    assert m.phase_count["first_token"] == 0
    assert m.first_token_poll_gap_count == 0

    order = []
    resolve_slot = eng._resolve_slot

    def recording(slot_idx, slot):
        # Inside `first_token`, which is inside the caller's `resolve`:
        # neither has been counted yet.
        order.append((slot.request, m.phase_count["first_token"],
                      m.phase_count["resolve"]))
        resolve_slot(slot_idx, slot)

    eng._resolve_slot = recording
    with eng._phase("resolve"):
        eng._resolve_prefills(block=True)
    assert [r for r, _, _ in order] == requests
    assert [n for _, n, _ in order] == [0, 0, 1, 2]
    assert {n for _, _, n in order} == {0}
    assert m.phase_count["first_token"] == 3 == m.first_token_poll_gap_count
    assert m.phase_count["resolve"] == 1
    assert m.phase_seconds["first_token"] <= m.phase_seconds["resolve"]
    assert m.first_token_poll_gap_seconds > 0.0
    assert not eng._has_unresolved()
    # Nothing to read: neither the phase nor the pair moves.
    gap = m.first_token_poll_gap_seconds
    eng._resolve_prefills(block=True)
    assert m.phase_count["first_token"] == 3
    assert m.first_token_poll_gap_seconds == gap


def test_poll_gap_runs_from_the_last_unfinished_look():
    """A dispatch found unfinished is stamped and kept; a block's emit
    loop reads a dispatch at the turn of the first of its lanes, inside
    one `first_token` phase, and the rest at their own turns; a dispatch
    whose every member finished unread is dropped without a phase."""

    class Unfinished:
        def is_ready(self):
            return False

    eng = stopped_engine()
    pair = [GenRequest(prompt=f"{SHORT} {i}", max_new_tokens=4)
            for i in range(2)]
    late = GenRequest(prompt=SHORT + " z", max_new_tokens=4)
    for r in pair:
        eng._submit.put(r)
    eng._admit()                            # one dispatch of two rows
    block = eng._dispatch_step()            # both lanes ride this block
    eng._submit.put(late)
    eng._admit()                            # dispatched after the block
    records = list(eng._first_tokens)
    assert [len(rec.members) for rec in records] == [2, 1]
    # Records are told apart by identity (list.remove): `==` on their
    # token arrays, [2] against [1], is not a truth value.
    assert records[0] != records[1] and records[1] == records[1]
    landed = records[1].toks_dev
    for rec in records:
        rec.toks_dev = Unfinished()
    stamps = [rec.polled for rec in records]
    eng._resolve_prefills()
    assert eng._first_tokens == records
    assert all(rec.polled > was for rec, was in zip(records, stamps))
    assert eng.metrics.first_token_poll_gap_count == 0

    inside = []
    resolve_slot = eng._resolve_slot

    def recording(slot_idx, slot):
        # Entered but not yet counted: the read is inside the phase.
        inside.append(eng.metrics.first_token_poll_gap_count
                      - eng.metrics.phase_count["first_token"])
        resolve_slot(slot_idx, slot)

    eng._resolve_slot = recording
    eng._process_step(block)
    # The pair's first lane opened the dispatch's phase; its second was
    # read at its own turn in the loop, outside it.
    assert inside == [1, 0]
    assert eng._first_tokens == records[1:]
    assert all(r.timings.first_token > 0.0 for r in pair)
    assert not late.timings.first_token
    assert eng.metrics.phase_count["first_token"] == 1
    assert eng.metrics.first_token_poll_gap_count == 1
    assert eng.metrics.ttft_phase_count == 2
    # `late` is cancelled and finished before its token was read.
    records[1].toks_dev = landed
    eng._finish(records[1].members[0][0], error="cancelled")
    eng._resolve_prefills(block=True)
    assert not eng._first_tokens
    assert eng.metrics.phase_count["first_token"] == 1
    assert eng.metrics.first_token_poll_gap_count == 1


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


def test_a_model_without_expert_layers_counts_no_grouped_rows(
        live, monkeypatch):
    """`prefill_rows_grouped_experts` follows the rule `moe_held` decides
    by only where the model has an "E" layer: with the rule answering yes
    for every dispatch, as it does on the chip, a plain decoder still
    counts none."""
    from polykey_tpu.engine import engine as engine_mod

    monkeypatch.setattr(engine_mod, "held_experts_grouped", lambda rows: True)
    before = live.metrics.snapshot()
    r = GenRequest(prompt=SHORT + " once more", max_new_tokens=4)
    live.submit(r)
    _tokens, done, error = _collect(r)
    assert error is None and done is not None
    wait_drained(live)
    after = live.metrics.snapshot()
    assert after["prefill_rows_dispatched"] > before["prefill_rows_dispatched"]
    assert after["prefill_rows_grouped_experts"] == 0


def test_exporter_renders_one_sample_per_phase_and_counter(live):
    page = "\n".join(engine_collector(live)())
    for name in PHASES:
        assert f'polykey_engine_phase_seconds_total{{phase="{name}"}}' in page
        assert f'polykey_engine_phase_entries_total{{phase="{name}"}}' in page
    for name in ("queue", "prefill_wait", "first_token"):
        assert f'polykey_ttft_phase_seconds_total{{phase="{name}"}}' in page
    for reason in ("no_slot", "no_pages", "budget"):
        assert f'polykey_admit_deferred_total{{reason="{reason}"}}' in page
    for cause in QUEUE_CAUSES:
        assert f'polykey_ttft_queue_seconds_total{{cause="{cause}"}}' in page
    for family in ("polykey_decode_lane_steps_delivered_total",
                   "polykey_decode_lane_steps_overshoot_total",
                   "polykey_decode_lane_steps_dead_total",
                   "polykey_prefill_rows_dispatched_total",
                   "polykey_prefill_rows_useful_total",
                   "polykey_prefill_rows_grouped_experts_total",
                   "polykey_prefill_keys_read_total",
                   "polykey_prefill_keys_table_total",
                   "polykey_ttft_phase_requests_total",
                   "polykey_first_token_poll_gap_seconds_total",
                   "polykey_first_token_reads_total"):
        assert f"\n{family} " in page
    stats = live.stats()
    for key in ("phase_seconds", "phase_count", "ttft_phase_seconds",
                "ttft_phase_count", "ttft_queue_seconds", "admit_deferred",
                "decode_lane_steps_delivered", "prefill_rows_useful",
                "prefill_rows_grouped_experts",
                "prefill_keys_read_total", "prefill_keys_table_total",
                "first_token_poll_gap_seconds",
                "first_token_poll_gap_count"):
        assert key in stats


def test_a_supervised_restart_hands_the_wait_accumulators_over():
    """The queue causes, the `first_token` phase and the poll-gap pair
    live in EngineMetrics, so the engine a supervisor swaps in goes on
    from the dead one's readings (as phase_seconds does)."""
    from polykey_tpu.engine.supervisor import EngineSupervisor

    old = stopped_engine()
    for i in range(2):
        old._submit.put(GenRequest(prompt=f"{SHORT} {i}", max_new_tokens=4))
    serve_queue(old, budget=16)
    before = old.metrics.snapshot()
    assert before["ttft_queue_seconds"]["budget"] > 0.0
    assert before["first_token_poll_gap_count"] == 2
    old.dead = "killed by the test"
    supervisor = EngineSupervisor(old, lambda: InferenceEngine(CONFIG))
    supervisor._restart(old)
    fresh = supervisor.engine
    try:
        assert fresh is not old and fresh.metrics is old.metrics
        assert not fresh._first_tokens and not fresh._admit_visits
        request = GenRequest(prompt=SHORT, max_new_tokens=4)
        fresh.submit(request)
        _tokens, done, error = _collect(request)
        assert error is None and done is not None
        wait_drained(fresh)
        after = fresh.metrics.snapshot()
        assert after["ttft_phase_count"] == 3
        assert after["phase_count"]["first_token"] == 3
        # Both constructions' start-up phases (ISSUE 62).
        assert after["phase_count"]["init"] == 2
        assert after["phase_seconds"]["init"] > before["phase_seconds"]["init"]
        assert after["first_token_poll_gap_count"] == 3
        assert after["first_token_poll_gap_seconds"] > \
            before["first_token_poll_gap_seconds"]
        assert after["ttft_queue_seconds"]["budget"] == \
            before["ttft_queue_seconds"]["budget"]
        assert after["ttft_queue_seconds"]["loop"] > \
            before["ttft_queue_seconds"]["loop"]
    finally:
        fresh.shutdown()
