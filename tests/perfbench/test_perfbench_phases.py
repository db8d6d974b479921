"""The phase readers (ISSUE 26): arithmetic on synthetic engine_stats, the
recorded trace with synthetic `polykey/` host spans, and None — never an
exception — wherever the source is missing."""

import gzip
import json
import os

import pytest

from perfbench_paths import DATA, ROOT

import phases
import run
import trace_reduce

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
NEW = ("ttft_queue_ms_mean", "ttft_prefill_wait_ms_mean",
       "ttft_first_token_ms_mean", "prefill_device_queue_ms_p50",
       "decode_overshoot_share", "prefill_padding_share",
       "host_ms_per_decode_block", "idle_gap_named_share")
META = {"workload": "recorded.cell", "seed": 7, "trace": 1}


def context(stats_open=None, stats_close=None, trace=None):
    return run.Context(
        stats_open=stats_open or {}, stats_close=stats_close or {},
        trace=trace, samples={"meta": dict(META)})


def recorded() -> dict:
    with gzip.open(os.path.join(DATA, "recorded_trace.json.gz"), "rt") as f:
        return json.load(f)


def stats(scale: float) -> dict:
    """engine_stats as the engine of this PR returns them, every counter
    `scale` times a base reading."""
    seconds = {"admit": 0.5, "restore": 0.0, "chunk": 0.25, "dispatch": 1.0,
               "resolve": 0.25, "process": 10.0, "idle_wait": 3.0,
               "prefill": 0.4, "decode": 0.9, "ragged": 0.0,
               "ragged_spec": 0.0, "spec_decode": 0.0, "readback_wait": 9.0}
    return {
        "phase_seconds": {k: v * scale for k, v in seconds.items()},
        "blocks_dispatched": 100 * scale,
        "ttft_phase_seconds": {"queue": 30.0 * scale,
                               "prefill_wait": 0.5 * scale,
                               "first_token": 12.0 * scale},
        "ttft_phase_count": 20 * scale,
        "decode_lane_steps_delivered": 900 * scale,
        "decode_lane_steps_overshoot": 100 * scale,
        "decode_lane_steps_dead": 50 * scale,
        "prefill_rows_dispatched": 4096 * scale,
        "prefill_rows_useful": 3072 * scale,
    }


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_in_the_manifest_with_a_reader(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry in MANIFEST["per_layer"][-len(NEW):]
    assert entry["source"] in ("program_span", "program_counter")
    assert os.path.exists(
        os.path.join(ROOT, "perfbench", "metrics", name + ".py"))


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_without_its_source(name):
    """A server without the phase keys (the parent commit), and no trace."""
    old_server = {"blocks_dispatched": 5.0, "tokens_useful": 3.0}
    assert run.read_metric(name, context()) is None
    assert run.read_metric(name, context(old_server, old_server)) is None
    # A trace whose events file is not there.
    assert run.read_metric(
        name, context(old_server, old_server, trace={"busy_s": 1.0})) is None


@pytest.mark.parametrize("name, want", [
    ("ttft_queue_ms_mean", 1500.0),
    ("ttft_prefill_wait_ms_mean", 25.0),
    ("ttft_first_token_ms_mean", 600.0),
    ("decode_overshoot_share", 10.0),
    ("prefill_padding_share", 25.0),
    # admit .5 + chunk .25 + dispatch 1 + resolve .25 + process 10
    # - readback_wait 9 = 3 s of host work over 100 blocks.
    ("host_ms_per_decode_block", 30.0),
])
def test_counter_readers_on_synthetic_stats(name, want):
    ctx = context(stats(1.0), stats(2.0))
    assert run.read_metric(name, ctx) == pytest.approx(want)
    # Nothing happened in the window: no value, no division by zero.
    assert run.read_metric(name, context(stats(1.0), stats(1.0))) is None


@pytest.mark.parametrize("host, device, want", [
    ([10, 20, 30], [15, 26, 37], [5, 6, 7]),
    # The capture opened between a dispatch and its execution.
    ([20, 30], [15, 26, 37], [6, 7]),
    # ... and closed before the last execution.
    ([10, 20, 30], [15, 26], [5, 6]),
    ([10], [], []),
    ([], [5], []),
], ids=["aligned", "leading_execution", "trailing_dispatch", "no_execution",
        "no_dispatch"])
def test_join_in_order_skips_executions_it_saw_no_dispatch_for(
        host, device, want):
    assert phases.join_in_order(host, device) == want


def test_overlap_of_gaps_with_merged_spans():
    cover = phases.merged([(0, 5), (3, 8), (20, 30)])
    assert cover == [[0, 8], [20, 30]]
    assert phases.overlap([(6, 10), (12, 14), (25, 40)], cover) == 2 + 0 + 5


def write_events(tmp_path, monkeypatch, extracted: dict) -> dict:
    """Put `extracted` where a traced run leaves its events file."""
    monkeypatch.setattr(phases, "HERE", str(tmp_path))
    folder = tmp_path / "out" / META["workload"]
    folder.mkdir(parents=True)
    path = folder / "seed7.trace1.trace.events.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump(extracted, f)
    return trace_reduce.reduce(extracted)


def test_prefill_device_queue_on_the_recorded_trace(tmp_path, monkeypatch):
    extracted = recorded()
    starts = phases.program_starts(extracted, "jit__prefill_fn")
    assert starts == [62000000]
    # Two synthetic host spans: the dispatch of the recorded prefill,
    # 22 ms before it ran, and a later one the capture saw no execution of.
    extracted["annotations"] += [
        ["polykey/prefill", starts[0] - 22000000, 900000],
        ["polykey/prefill", starts[0] + 50000000, 900000],
    ]
    trace = write_events(tmp_path, monkeypatch, extracted)
    value = run.read_metric("prefill_device_queue_ms_p50",
                            context(trace=trace))
    assert value == pytest.approx(22.0)


def test_idle_gap_named_share_on_the_recorded_trace(tmp_path, monkeypatch):
    extracted = recorded()
    ops = next(line["events"] for line in extracted["planes"][0]["lines"]
               if line["name"] == trace_reduce.OPS_LINE)
    gaps = sorted(trace_reduce.gaps((e[1], e[1] + e[2]) for e in ops),
                  key=lambda g: g[0] - g[1])
    idle = sum(end - start for start, end in gaps)
    assert len(gaps) > 2 and idle > 0
    # The recorded `polykey/decode` span is left out; two synthetic spans:
    # one over the whole longest gap, one over the first half of the next.
    (a0, a1), (b0, b1) = gaps[0], gaps[1]
    half = (b1 - b0) // 2
    extracted["annotations"] = [
        ["polykey/process", a0, a1 - a0],
        ["polykey/idle_wait", b0, half],
    ]
    # ... and two instants at the edges of the trace, so that the stretch
    # the host spans are counted over is the whole of it.
    first = min(e[1] for e in ops)
    last = max(e[1] + e[2] for e in ops)
    extracted["annotations"] += [["polykey/admit", first, 0],
                                 ["polykey/admit", last, 0]]
    trace = write_events(tmp_path, monkeypatch, extracted)
    value = run.read_metric("idle_gap_named_share", context(trace=trace))
    assert value == pytest.approx(100.0 * ((a1 - a0) + half) / idle)
    assert 0.0 < value < 100.0
    # Without them only the idle time between the first span's start and
    # the last one's end counts: a span open at an edge is not in a capture.
    extracted["annotations"] = extracted["annotations"][:2]
    lo, hi = min(a0, b0), max(a1, b0 + half)
    inside = sum(min(e, hi) - max(s, lo) for s, e in gaps
                 if min(e, hi) > max(s, lo))
    write_events(tmp_path / "clipped", monkeypatch, extracted)
    clipped = run.read_metric("idle_gap_named_share", context(trace=trace))
    assert clipped == pytest.approx(100.0 * ((a1 - a0) + half) / inside)
    assert clipped > value
    # No host span at all: nothing is named.
    extracted["annotations"] = []
    write_events(tmp_path / "none", monkeypatch, extracted)
    assert run.read_metric("idle_gap_named_share",
                           context(trace=trace)) == 0.0


@pytest.mark.parametrize("name", ["prefill_device_queue_ms_p50",
                                  "idle_gap_named_share"])
@pytest.mark.parametrize("damage", ["not_gzip", "cut_short", "other_form"])
def test_trace_reader_reports_nothing_from_events_it_cannot_read(
        tmp_path, monkeypatch, capsys, name, damage):
    """The readers also run over a program and a capture they were not
    written for: the run keeps its result line, the reason goes to stderr."""
    trace = write_events(tmp_path, monkeypatch, recorded())
    path = tmp_path / "out" / META["workload"] / "seed7.trace1.trace.events.json.gz"
    if damage == "not_gzip":
        path.write_bytes(b"{}")
    elif damage == "cut_short":
        path.write_bytes(path.read_bytes()[:200])
    else:
        with gzip.open(path, "wt") as f:
            json.dump({"planes": [{"lines": [{"name": trace_reduce.OPS_LINE}]}],
                       "annotations": [["polykey/prefill", 5]]}, f)
    assert run.read_metric(name, context(trace=trace)) is None
    assert "Traceback" in capsys.readouterr().err


@pytest.mark.parametrize("value", [None, "12", [1], {"a": 1}, True])
def test_delta_reads_numbers_only(value):
    ctx = context({"k": 1.0, "d": {"e": 1.0}}, {"k": value, "d": {"e": value}})
    assert phases.delta(ctx, "k") is None
    assert phases.delta(ctx, "d", "e") is None
    assert phases.delta(ctx, "k", "e") is None
