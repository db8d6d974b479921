"""SLO signal plane tests (ISSUE 11): delta-histogram math, windowed
aggregation over the snapshot ring, burn-rate/budget property tests on
synthetic deltas with known quantiles, breach/recovery state machine,
the /debug/slo surface and its gate, restart adoption (windows survive
the supervisor's metrics handoff), the signals-off overhead gate, and
alert-rule emission from the same policy.
"""

import importlib.util
import json
import os
import time
from dataclasses import replace

import pytest

from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import GenRequest, InferenceEngine
from polykey_tpu.engine.metrics import EngineMetrics
from polykey_tpu.obs import DebugSurface, FlightRecorder, TimelineRecorder
from polykey_tpu.obs.histogram import (
    Histogram,
    estimate_quantile,
    fraction_le,
)
from polykey_tpu.obs.signals import (
    SignalPlane,
    SloObjective,
    SloPolicy,
    alert_rules_yaml,
    merge_deltas,
    signals_snapshot,
    summarize_deltas,
    window_label,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG = EngineConfig(
    model="tiny-llama",
    tokenizer="byte",
    dtype="float32",
    max_decode_slots=4,
    page_size=8,
    num_pages=64,
    max_seq_len=64,
    prefill_buckets=(16,),
    max_new_tokens_cap=32,
    default_max_new_tokens=8,
    decode_block_steps=4,
    signals_interval_s=0.05,
)


def _load_script(name: str):
    path = os.path.join(REPO, "scripts", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _drain(request: GenRequest, timeout: float = 120.0):
    tokens = []
    deadline = time.monotonic() + timeout
    while True:
        kind, value = request.out.get(timeout=deadline - time.monotonic())
        if kind == "token":
            tokens.append(value)
        elif kind == "done":
            return tokens, None
        else:
            return tokens, value


def _run_burst(engine, n=3, max_new=8, prefix="signals"):
    requests = [
        GenRequest(prompt=f"{prefix} {i}", max_new_tokens=max_new)
        for i in range(n)
    ]
    for request in requests:
        engine.submit(request)
    for request in requests:
        _tokens, error = _drain(request)
        assert error is None, error
    return requests


# -- delta-histogram math (property tests on known quantiles) -----------------


BOUNDS = (1.0, 2.0, 4.0, 8.0)


def test_estimate_quantile_known_values():
    counts = (0, 10, 0, 0, 0)          # all mass in (1, 2]
    assert estimate_quantile(BOUNDS, counts, 10, 50) == pytest.approx(1.5)
    assert estimate_quantile(BOUNDS, counts, 10, 100) == pytest.approx(2.0)
    assert estimate_quantile(BOUNDS, counts, 10, 10) == pytest.approx(1.1)
    # Split mass: 5 in (0,1], 5 in (4,8] — p50 lands at the first
    # bucket's edge, p75 halfway into the second populated one.
    counts = (5, 0, 0, 5, 0)
    assert estimate_quantile(BOUNDS, counts, 10, 50) == pytest.approx(1.0)
    assert estimate_quantile(BOUNDS, counts, 10, 75) == pytest.approx(6.0)
    # +Inf mass clamps to the largest finite bound; empty returns 0.
    assert estimate_quantile(BOUNDS, (0, 0, 0, 0, 9), 9, 99) == 8.0
    assert estimate_quantile(BOUNDS, (0, 0, 0, 0, 0), 0, 50) == 0.0


def test_fraction_le_interpolates():
    counts = (0, 10, 0, 0, 0)          # uniform inside (1, 2]
    assert fraction_le(BOUNDS, counts, 1.5) == pytest.approx(0.5)
    assert fraction_le(BOUNDS, counts, 2.0) == pytest.approx(1.0)
    assert fraction_le(BOUNDS, counts, 1.0) == pytest.approx(0.0)
    assert fraction_le(BOUNDS, counts, 100.0) == pytest.approx(1.0)
    # Everything in +Inf is above ANY threshold; empty has no verdict.
    assert fraction_le(BOUNDS, (0, 0, 0, 0, 5), 100.0) == pytest.approx(0.0)
    assert fraction_le(BOUNDS, (0, 0, 0, 0, 0), 1.0) is None


def test_histogram_counts_snapshot_matches_percentiles():
    hist = Histogram(bounds=BOUNDS)
    for value in (1.5, 1.5, 3.0, 9.0):
        hist.observe(value)
    counts, total_sum = hist.counts_snapshot()
    assert sum(counts) == 4 and total_sum == pytest.approx(15.0)
    assert estimate_quantile(BOUNDS, counts, 4, 50) == pytest.approx(
        hist.percentile(50)
    )


def test_window_label():
    assert window_label(60) == "1m"
    assert window_label(300) == "5m"
    assert window_label(3600) == "1h"
    assert window_label(7200) == "2h"
    assert window_label(90) == "90s"
    assert window_label(2.5) == "2.5s"


# -- policy parsing -----------------------------------------------------------


def test_policy_from_json_and_validation():
    policy = SloPolicy.from_json({
        "objectives": [
            {"name": "ttft", "kind": "latency", "signal": "ttft_ms",
             "threshold_ms": 500, "target": 0.95},
            {"name": "avail", "kind": "availability", "target": 0.999},
            {"name": "busy", "kind": "floor",
             "signal": "device_busy_fraction", "target": 0.5},
        ]
    })
    assert len(policy.objectives) == 3
    assert policy.objectives[0].error_budget == pytest.approx(0.05)
    with pytest.raises(ValueError, match="unknown objective kind"):
        SloPolicy.from_json([{"name": "x", "kind": "nope"}])
    with pytest.raises(ValueError, match="needs signal"):
        SloPolicy.from_json(
            [{"name": "x", "kind": "latency", "signal": "bogus",
              "threshold_ms": 1}]
        )
    with pytest.raises(ValueError, match="duplicate"):
        SloPolicy.from_json([
            {"name": "x", "kind": "availability"},
            {"name": "x", "kind": "availability"},
        ])
    with pytest.raises(ValueError, match="unknown objective fields"):
        SloPolicy.from_json([{"name": "x", "kind": "availability",
                              "typo_field": 1}])


def test_windows_from_spec_fail_fast():
    from polykey_tpu.obs.signals import DEFAULT_WINDOWS, windows_from_spec

    assert windows_from_spec("") == DEFAULT_WINDOWS
    assert windows_from_spec("300,60") == (60.0, 300.0)
    with pytest.raises(ValueError, match="bad signals windows"):
        windows_from_spec("60;300")        # typo must not silently
    with pytest.raises(ValueError, match="all > 0"):
        windows_from_spec("0,300")         # fall back to defaults
    with pytest.raises(ValueError, match="at least one"):
        windows_from_spec(",")


def test_policy_from_env(monkeypatch, tmp_path):
    monkeypatch.delenv("POLYKEY_SLO", raising=False)
    assert SloPolicy.from_env() is None
    monkeypatch.setenv("POLYKEY_SLO", "default")
    assert len(SloPolicy.from_env().objectives) >= 3
    path = tmp_path / "slo.json"
    path.write_text(json.dumps([
        {"name": "only", "kind": "availability", "target": 0.9}
    ]))
    monkeypatch.setenv("POLYKEY_SLO", f"@{path}")
    policy = SloPolicy.from_env()
    assert [o.name for o in policy.objectives] == ["only"]


# -- windowed aggregation over synthetic time ---------------------------------


def _plane(windows=(1.0, 10.0), interval=0.5, **kwargs):
    metrics = EngineMetrics()
    plane = SignalPlane(metrics, windows=windows, interval_s=interval,
                        **kwargs)
    return metrics, plane


def test_counters_become_windowed_rates():
    metrics, plane = _plane()
    t0 = 1000.0
    assert plane.maybe_sample(now=t0)
    assert not plane.maybe_sample(now=t0 + 0.1)   # interval gate
    metrics.on_step(100)                          # 100 tokens
    metrics.on_admit()
    assert plane.maybe_sample(now=t0 + 10.0)
    summary = plane.window_summary(10.0)
    assert summary["covered_s"] == pytest.approx(10.0)
    assert summary["tokens_per_sec"] == pytest.approx(10.0)


def test_delta_quantiles_ignore_stale_history():
    """The staleness fix itself: a histogram poisoned by an old slow
    era reports CURRENT-window quantiles from the delta, while the
    cumulative percentile stays stuck in the past."""
    metrics, plane = _plane(windows=(5.0, 50.0), interval=1.0)
    t0 = 2000.0
    for _ in range(100):
        metrics.ttft_hist.observe(5000.0)         # the bad old days
    plane.maybe_sample(now=t0)
    for _ in range(100):
        metrics.ttft_hist.observe(10.0)           # now: healthy
    plane.maybe_sample(now=t0 + 4.0)
    windowed = plane.window_summary(5.0)
    assert windowed["ttft_ms_count"] == 100
    assert windowed["ttft_ms_p95"] < 50.0
    # Lifetime view is still dominated by the stale half.
    assert metrics.ttft_hist.percentile(95) > 1000.0


def test_latency_burn_breach_and_recovery_events():
    timeline = TimelineRecorder(capacity=64)
    recorder = FlightRecorder(capacity=8)
    metrics, plane = _plane(windows=(1.0, 10.0), interval=0.5,
                            timeline=timeline, recorder=recorder)
    plane.set_policy(SloPolicy(objectives=(
        SloObjective(name="ttft", kind="latency", signal="ttft_ms",
                     threshold_ms=100.0, target=0.9),
    )))
    t0 = 3000.0
    plane.maybe_sample(now=t0)
    # 8 good + 2 bad: bad fraction 0.2 against a 0.1 budget -> burn 2.
    for _ in range(8):
        metrics.ttft_hist.observe(10.0)
    for _ in range(2):
        metrics.ttft_hist.observe(5000.0)
    plane.maybe_sample(now=t0 + 10.0)
    state = plane.slo_state()["ttft"]
    assert state["burn_rate"]["1s"] == pytest.approx(2.0, rel=1e-3)
    assert state["breached"] and state["breaches"] == 1
    # Budget over the long window: 0.2/0.1 -> fully exhausted (clamp 0).
    assert state["budget_remaining"] == 0.0
    kinds = [e["kind"] for e in timeline.events()]
    assert "note" in kinds
    notes = [e for e in timeline.events() if e["kind"] == "note"]
    assert notes[-1]["note_kind"] == "slo_breach"
    assert notes[-1]["attrs"]["objective"] == "ttft"
    assert any(e["kind"] == "slo_breach" for e in recorder.events())

    # Recovery: a clean window drops the burn under threshold; breached
    # clears, the counter does NOT move, and the recovery is recorded.
    for _ in range(100):
        metrics.ttft_hist.observe(10.0)
    plane.maybe_sample(now=t0 + 20.0)
    state = plane.slo_state()["ttft"]
    assert not state["breached"] and state["breaches"] == 1
    assert state["burn_rate"]["1s"] == pytest.approx(0.0)
    assert state["budget_remaining"] == 1.0
    notes = [e for e in timeline.events() if e["kind"] == "note"]
    assert notes[-1]["note_kind"] == "slo_recovered"


def test_availability_burn_counts_expiries_once():
    """Engine semantics: a deadline expiry increments BOTH
    requests_failed (on_finish(failed=True)) and the phase counter —
    availability must count it once (bad = failed + shed), or every
    expiry would burn the budget twice."""
    metrics, plane = _plane(windows=(1.0, 10.0), interval=0.5)
    plane.set_policy(SloPolicy(objectives=(
        SloObjective(name="avail", kind="availability", target=0.9),
    )))
    t0 = 4000.0
    plane.maybe_sample(now=t0)
    for _ in range(6):
        metrics.requests_completed += 1
    metrics.requests_shed += 1
    # 3 failures, ONE of which is a deadline expiry (mirroring
    # engine._expire: failed++ AND deadline_expired["queued"]++).
    metrics.requests_failed += 3
    metrics.deadline_expired["queued"] += 1
    plane.maybe_sample(now=t0 + 10.0)
    state = plane.slo_state()["avail"]
    # bad = 3 failed + 1 shed = 4 of 10 total -> 0.4 / 0.1 budget = 4
    # (double-counting the expiry would report 5).
    assert state["burn_rate"]["1s"] == pytest.approx(4.0)
    assert state["breached"]


def test_floor_objective_time_budget():
    metrics, plane = _plane(windows=(1.0, 10.0), interval=0.5)
    plane.set_policy(SloPolicy(objectives=(
        SloObjective(name="busy", kind="floor",
                     signal="device_busy_fraction", target=0.9,
                     time_budget=0.25),
    )))
    t0 = 5000.0
    plane.maybe_sample(now=t0)
    # Window busy/gap = 0.5 < floor 0.9 -> violated -> burn 1/0.25 = 4.
    metrics.dispatch_gap_ms_total += 1000.0
    metrics.device_busy_ms_total += 500.0
    plane.maybe_sample(now=t0 + 2.0)
    state = plane.slo_state()["busy"]
    assert state["burn_rate"]["1s"] == pytest.approx(4.0)
    assert state["breached"]
    # Healthy windows accumulate ok history; the time-budget accounting
    # trends the budget back up as violation time ages out.
    for i in range(1, 6):
        metrics.dispatch_gap_ms_total += 1000.0
        metrics.device_busy_ms_total += 990.0
        plane.maybe_sample(now=t0 + 2.0 + 2.0 * i)
    state = plane.slo_state()["busy"]
    assert not state["breached"]
    assert state["burn_rate"]["1s"] == pytest.approx(0.0)
    # Budget integrates time-in-violation over the BUDGET WINDOW, not
    # the observed span: 2 s violated of a 10 s window against a 0.25
    # time budget -> 1 - (0.2 / 0.25) = 0.2 remaining. (Dividing by
    # the observed span would have read a brief warm-up dip as a fully
    # exhausted budget.)
    assert state["budget_remaining"] == pytest.approx(0.2, abs=0.01)


def test_no_evidence_no_verdict():
    """Empty windows must not breach, burn, or consume budget — a cold
    or idle engine is not a violating engine."""
    _metrics, plane = _plane()
    plane.set_policy(SloPolicy(objectives=(
        SloObjective(name="ttft", kind="latency", signal="ttft_ms",
                     threshold_ms=100.0, target=0.9),
    )))
    plane.maybe_sample(now=6000.0)
    plane.maybe_sample(now=6010.0)
    state = plane.slo_state()["ttft"]
    assert state["burn_rate"]["1s"] is None
    assert not state["breached"] and state["breaches"] == 0
    assert state["budget_remaining"] == 1.0


def test_merge_deltas_sums_counters_and_buckets():
    a = {"covered_s": 5.0,
         "counters": {"tokens_generated": 50, "requests_completed": 2},
         "hists": {"ttft_ms": ((1, 2, 0), 30.0)}}
    b = {"covered_s": 4.0,
         "counters": {"tokens_generated": 30, "requests_completed": 1},
         "hists": {"ttft_ms": ((0, 1, 3), 70.0)}}
    merged = merge_deltas([a, b, None])
    assert merged["covered_s"] == 5.0
    assert merged["counters"]["tokens_generated"] == 80
    assert merged["hists"]["ttft_ms"] == ((1, 3, 3), 100.0)
    assert merge_deltas([None, None]) is None


def test_summarize_handles_empty_window():
    deltas = {"covered_s": 5.0, "counters": {}, "hists": {}}
    summary = summarize_deltas(deltas, {})
    assert summary["availability"] is None
    assert summary["avg_lanes"] is None


def test_plane_ring_is_bounded():
    metrics, plane = _plane(windows=(1.0,), interval=0.5)
    assert plane.capacity == 4           # 1.0/0.5 + 2
    for i in range(50):
        plane.maybe_sample(now=7000.0 + i)
    assert plane.samples() == 4


# -- engine integration -------------------------------------------------------


@pytest.fixture(scope="module")
def signals_engine():
    engine = InferenceEngine(CONFIG)
    # Test-scale windows (the env default is 1m/5m/1h); swapping the
    # plane before traffic is the supported harness hook.
    engine.metrics.signals = SignalPlane(
        engine.metrics, windows=(1.0, 3.0, 300.0), interval_s=0.05,
        timeline=engine.timeline,
    )
    _run_burst(engine, n=4, max_new=8)
    yield engine
    engine.shutdown()


def test_engine_stats_windowed_keys(signals_engine):
    """The *_5m satellite: windowed TTFT quantiles ride engine_stats
    alongside the lifetime ones (suffix = label of the window nearest
    300 s)."""
    signals_engine.metrics.signals.sample_now()
    stats = signals_engine.stats()
    assert "ttft_ms_p95_5m" in stats
    assert stats["ttft_ms_p95_5m"] > 0
    assert "itl_ms_p95_5m" in stats
    assert "ttft_ms_p95" in stats        # lifetime keys unchanged


def test_signals_snapshot_shape(signals_engine):
    snap = signals_snapshot(signals_engine)
    replica = snap["replicas"]["0"]
    assert replica["enabled"]
    assert set(replica["windows"]) == {"1s", "3s", "5m"}
    window = replica["windows"]["5m"]
    assert window["ttft_ms_count"] >= 4
    assert 0.0 <= window["device_busy_fraction"] <= 1.0
    assert replica["now"]["load_fraction"] >= 0.0
    assert snap["aggregate"]["5m"]["ttft_ms_count"] >= 4


def test_slo_families_exported(signals_engine):
    from polykey_tpu.obs.exposition import engine_collector

    plane = signals_engine.metrics.signals
    plane.set_policy(SloPolicy(objectives=(
        SloObjective(name="ttft", kind="latency", signal="ttft_ms",
                     threshold_ms=60_000.0, target=0.5),
    )))
    try:
        plane.sample_now()
        page = "\n".join(engine_collector(signals_engine)())
        assert "# TYPE polykey_slo_budget_remaining_ratio gauge" in page
        assert 'polykey_slo_budget_remaining_ratio{objective="ttft"}' in page
        assert ('polykey_slo_burn_rate{objective="ttft",window="1s"}'
                in page)
        assert 'polykey_slo_breaches_total{objective="ttft"} 0' in page
    finally:
        plane.set_policy(None)


def test_debug_slo_gated_and_serving(monkeypatch, signals_engine):
    surface = DebugSurface(engine_provider=lambda: signals_engine)
    monkeypatch.delenv("POLYKEY_DEBUG_ENDPOINTS", raising=False)
    status, _, _ = surface.handle("/debug/slo", "")
    assert status == 404
    monkeypatch.setenv("POLYKEY_DEBUG_ENDPOINTS", "1")
    status, ctype, body = surface.handle("/debug/slo", "")
    assert status == 200 and ctype == "application/json"
    snap = json.loads(body)
    assert snap["replicas"]["0"]["enabled"]
    monkeypatch.setenv("POLYKEY_DEBUG_ENDPOINTS", "0")
    status, _, _ = surface.handle("/debug/slo", "")
    assert status == 404


def test_config_threads_windows_and_policy(monkeypatch):
    """Windows and policy ride EngineConfig (config-first, env
    fallback): a programmatic construction controls them without
    touching os.environ, and EngineConfig.from_env captures the boot
    env so restart factories replay the same spec."""
    monkeypatch.delenv("POLYKEY_SIGNALS_WINDOWS", raising=False)
    monkeypatch.delenv("POLYKEY_SLO", raising=False)
    policy_json = json.dumps([
        {"name": "cfg_avail", "kind": "availability", "target": 0.95}
    ])
    engine = InferenceEngine(replace(
        CONFIG, signals_windows="2,6", slo_policy=policy_json,
    ))
    try:
        plane = engine.metrics.signals
        assert plane.windows == (2.0, 6.0)
        assert [o.name for o in plane.policy.objectives] == ["cfg_avail"]
    finally:
        engine.shutdown()
    monkeypatch.setenv("POLYKEY_SIGNALS_WINDOWS", "30,90")
    monkeypatch.setenv("POLYKEY_SLO", "default")
    config = EngineConfig.from_env()
    assert config.signals_windows == "30,90"
    assert config.slo_policy == "default"


def test_closed_loop_fault_breach_recovery():
    """The ISSUE 11 acceptance demo at test scale: a mid-run slow-step
    fault drives TTFT burn > 1, increments the breach counter, lands
    slo_breach on the timeline, and the burn STOPS once the fault
    clears — recovery recorded, counter frozen."""
    from polykey_tpu import faults

    engine = InferenceEngine(replace(CONFIG, max_new_tokens_cap=16))
    plane = SignalPlane(
        engine.metrics, windows=(1.5, 4.0, 12.0), interval_s=0.05,
        timeline=engine.timeline,
        policy=SloPolicy(objectives=(
            SloObjective(name="ttft", kind="latency", signal="ttft_ms",
                         threshold_ms=400.0, target=0.7),
        )),
    )
    engine.metrics.signals = plane
    try:
        _run_burst(engine, n=3, max_new=8, prefix="clean")
        time.sleep(0.2)
        plane.sample_now()
        assert not plane.slo_state()["ttft"]["breached"], (
            "clean traffic must not breach"
        )
        breaches0 = plane.slo_state()["ttft"]["breaches"]

        engine._faults = faults.install("slow-step=0.6@8")
        try:
            _run_burst(engine, n=2, max_new=8, prefix="faulted")
            plane.sample_now()
            state = plane.slo_state()["ttft"]
            burn = state["burn_rate"]["1.5s"]
            assert burn is not None and burn > 1.0, state
            assert state["breached"]
            assert state["breaches"] == breaches0 + 1
            notes = [e for e in engine.timeline.events()
                     if e["kind"] == "note"
                     and e["note_kind"] == "slo_breach"]
            assert notes and notes[-1]["attrs"]["objective"] == "ttft"
        finally:
            faults.clear()
            engine._faults = None

        # Recovery: clean traffic ages the faulted TTFTs out of the
        # short window; budget burn stops (counter frozen, flag clear).
        deadline = time.monotonic() + 30
        recovered = False
        while time.monotonic() < deadline:
            _run_burst(engine, n=1, max_new=8, prefix="recover")
            time.sleep(0.2)
            plane.sample_now()
            state = plane.slo_state()["ttft"]
            if not state["breached"]:
                recovered = True
                break
        assert recovered, plane.slo_state()
        assert plane.slo_state()["ttft"]["breaches"] == breaches0 + 1
        assert any(
            e["kind"] == "note" and e["note_kind"] == "slo_recovered"
            for e in engine.timeline.events()
        )
    finally:
        faults.clear()
        engine.shutdown()


def test_windows_survive_supervised_restart():
    """The adoption satellite: the supervisor hands the old engine's
    metrics (and therefore the signal plane, its ring, and its breach
    state) to the fresh engine — windows must NOT zero across a
    restart, and the plane's timeline binding must follow to the fresh
    ring so later breaches stay visible."""
    from polykey_tpu.engine.supervisor import EngineSupervisor

    config = replace(CONFIG, supervise=True)
    engine = InferenceEngine(config)
    plane = SignalPlane(
        engine.metrics, windows=(1.0, 3.0, 300.0), interval_s=0.05,
        timeline=engine.timeline,
    )
    engine.metrics.signals = plane
    supervisor = EngineSupervisor(
        engine, lambda: InferenceEngine(config), check_interval_s=0.05,
    )
    supervisor.start()
    try:
        _run_burst(engine, n=2, max_new=8)
        plane.sample_now()
        samples_before = plane.samples()
        assert samples_before >= 2
        ttft_before = engine.metrics.ttft_hist.count

        engine.dead = "signals adoption test kill"
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if supervisor.engine is not engine \
                    and supervisor.engine.dead is None:
                break
            time.sleep(0.05)
        fresh = supervisor.engine
        assert fresh is not engine, "supervisor never restarted"

        # Same plane object, ring intact, counters continuous.
        assert fresh.metrics.signals is plane
        assert plane.samples() >= samples_before
        assert fresh.metrics.ttft_hist.count == ttft_before
        # Timeline rebound to the FRESH engine's ring.
        assert plane.timeline is fresh.timeline
        _run_burst(fresh, n=1, max_new=8)
        plane.sample_now()
        assert "ttft_ms_p95_5m" in fresh.stats()
    finally:
        supervisor.stop()
        supervisor.engine.shutdown()


def test_signals_disabled_zero_alloc_and_identical_streams():
    """The overhead gate: signals_interval_s=0 allocates NO plane, and
    the engine's behavior is bit-identical with the plane on vs off —
    same greedy streams, same dispatched lane accounting (PR 8
    discipline: observability must not perturb the schedule)."""
    on = InferenceEngine(CONFIG)
    off = InferenceEngine(replace(CONFIG, signals_interval_s=0))
    try:
        assert on.metrics.signals is not None
        assert off.metrics.signals is None

        def streams(engine):
            out = []
            for i in range(3):
                request = GenRequest(prompt=f"overhead {i}",
                                     max_new_tokens=8, seed=1234 + i)
                engine.submit(request)
                tokens, error = _drain(request)
                assert error is None, error
                out.append(tokens)
            return out

        assert streams(on) == streams(off)
        # Sequential single requests: deterministic lane accounting —
        # avg_lanes must be EXACTLY equal across the two engines.
        assert on.metrics.snapshot().get("avg_lanes") == \
            off.metrics.snapshot().get("avg_lanes")
        assert "ttft_ms_p95_5m" not in off.stats()
    finally:
        on.shutdown()
        off.shutdown()


# -- alert-rule emission ------------------------------------------------------


def test_alert_rules_from_policy():
    policy = SloPolicy(objectives=(
        SloObjective(name="interactive_ttft", kind="latency",
                     signal="ttft_ms", threshold_ms=2000.0, target=0.95,
                     fast_burn=10.0),
    ))
    yaml_text = alert_rules_yaml(policy, windows=(60.0, 300.0, 3600.0))
    assert "groups:" in yaml_text
    assert "alert: PolykeySloFastBurnInteractiveTtft" in yaml_text
    assert "alert: PolykeySloSlowBurnInteractiveTtft" in yaml_text
    assert "alert: PolykeySloBudgetLowInteractiveTtft" in yaml_text
    assert ('polykey_slo_burn_rate{objective="interactive_ttft",'
            'window="5m"} > 10') in yaml_text
    assert ('polykey_slo_burn_rate{objective="interactive_ttft",'
            'window="1h"} > 1') in yaml_text
    assert ('polykey_slo_budget_remaining_ratio'
            '{objective="interactive_ttft"} < 0.1') in yaml_text


def test_alert_rules_cli(capsys):
    from polykey_tpu.obs import signals as signals_mod

    rc = signals_mod.main([
        "--emit-alert-rules",
        "--policy",
        json.dumps([{"name": "cli_avail", "kind": "availability",
                     "target": 0.99}]),
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "PolykeySloFastBurnCliAvail" in out
    os.environ.pop("POLYKEY_SLO", None)   # main() writes it for from_env


# -- flightwatch --------------------------------------------------------------


def test_flightwatch_parse_and_render():
    flightwatch = _load_script("flightwatch")
    page = "\n".join([
        "# HELP polykey_tokens_per_sec x",
        "# TYPE polykey_tokens_per_sec gauge",
        "polykey_tokens_per_sec 123.4",
        "polykey_decode_slots 8",
        "polykey_live_lanes 6.5",
        "polykey_queue_depth 3",
        "polykey_active_requests 6",
        "polykey_requests_shed_total 0",
        "polykey_device_busy_fraction 0.987",
        "polykey_dispatch_inflight 1",
        "polykey_dispatch_lookahead_depth 2",
        'polykey_replica_state{replica="0",state="SERVING"} 1',
        'polykey_slo_breaches_total{objective="ttft"} 2',
    ])
    families = flightwatch.parse_metrics(page)
    assert flightwatch.metric(families, "polykey_tokens_per_sec") == 123.4
    assert flightwatch.metric(
        families, "polykey_replica_state", replica="0", state="SERVING"
    ) == 1
    slo = {
        "replicas": {"0": {
            "slo": {"ttft": {"budget_remaining": 0.25,
                             "burn_rate": {"1m": 2.5, "5m": 1.1},
                             "breaches": 2, "breached": True}},
            "now": {"queue_delay_s": 0.05, "load_fraction": 0.75},
        }},
        "aggregate": {"1m": {"ttft_ms_p50": 120.0, "ttft_ms_p95": 900.0,
                             "itl_ms_p95": 12.0, "tokens_per_sec": 123.4,
                             "availability": 1.0,
                             "device_busy_fraction": 0.987}},
        # Disagg coordinator windows (ISSUE 16): the HANDOFF section.
        "pool": {"1m": {
            "covered_s": 60.0,
            "handoffs": {"ok": 41, "rerouted": 2, "failed": 0},
            "handoff_bytes": 123_000_000,
            "wire_bandwidth_bytes_per_s": 2_050_000.0,
            "handoff_ms_count": 43, "handoff_ms_p50": 3.1,
            "handoff_ms_p95": 9.7,
            "tier_faults": {"prefill": 1, "decode": 0},
            "tier_restores": {"prefill": 1, "decode": 0},
            "fault_rate_per_min": 1.0,
        }},
        "pool_now": {"wire_bw_ewma_bytes_per_s": {"decode-0": 2_400_000.0}},
    }
    frame = flightwatch.render(families, slo, "12:00:00Z", "test:0")
    assert "ENGINE" in frame and "123.4" in frame
    assert "WINDOWS" in frame and "900.0" in frame
    assert "SLO" in frame and "BREACHED" in frame
    assert "REPLICAS" in frame and "SERVING" in frame
    assert "HANDOFF" in frame and "41/2/0" in frame
    assert "3.1/9.7" in frame and "2.05" in frame
    assert "decode-0 2.40 MB/s" in frame
    # Degrades without /debug/slo: still renders the engine section.
    frame = flightwatch.render(families, None, "12:00:00Z", "test:0")
    assert "ENGINE" in frame and "WINDOWS" not in frame
