"""The readers of a first token's two waits (ISSUE 45): the queue by cause
and the poll gap on synthetic engine_stats, the lag between a prefill's
end on the device and the host's read on a small synthetic events file,
and None — never an exception — wherever the source is missing."""

import copy
import gzip
import json
import os

import pytest

from perfbench_paths import DATA, ROOT

import phases
import run
import waits

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
COUNTER_READERS = ("ttft_queue_loop_ms_mean", "ttft_queue_budget_ms_mean",
                   "ttft_queue_capacity_ms_mean",
                   "first_token_poll_gap_ms_mean")
SPAN_READERS = ("first_token_host_lag_ms_mean", "first_token_host_lag_ms_p90",
                "first_token_lag_in_readback_ms_mean")
META = {"workload": "synthetic.cell", "seed": 7, "trace": 1}
MS = 1_000_000


def context(stats_open=None, stats_close=None, trace=None):
    return run.Context(
        stats_open=stats_open or {}, stats_close=stats_close or {},
        trace=trace, samples={"meta": dict(META)})


def stats(scale: float) -> dict:
    """engine_stats as the engine of this PR returns them, every counter
    `scale` times a base reading: 20 requests, 8 dispatches read."""
    return {
        "ttft_phase_seconds": {"queue": 5.0 * scale,
                               "prefill_wait": 0.2 * scale,
                               "first_token": 7.0 * scale},
        "ttft_phase_count": 20 * scale,
        "ttft_queue_seconds": {"loop": 1.8 * scale, "budget": 3.0 * scale,
                               "no_slot": 0.15 * scale,
                               "no_pages": 0.05 * scale},
        "first_token_poll_gap_seconds": 1.2 * scale,
        "first_token_poll_gap_count": 8 * scale,
    }


def synthetic() -> dict:
    """Three cycles of a 90 ms decode block and a 92 ms prefill: the
    prefills end at 182, 364 and 546 ms, their first tokens are read at
    275, 460 and 640 ms, and the engine thread sat in `readback_wait`
    over 200..272 and 390..454 ms."""
    with open(os.path.join(DATA, "first_token_lag.events.json")) as f:
        return json.load(f)


def place(tmp_path, monkeypatch, extracted: dict) -> dict:
    """Put `extracted` where a traced run leaves its events file; the
    reduced trace a reader is handed beside it."""
    monkeypatch.setattr(phases, "HERE", str(tmp_path))
    folder = tmp_path / "out" / META["workload"]
    folder.mkdir(parents=True, exist_ok=True)
    with gzip.open(folder / "seed7.trace1.trace.events.json.gz", "wt") as f:
        json.dump(extracted, f)
    return {"busy_s": 1.0}


def modules(extracted: dict) -> list:
    return next(line["events"] for line in extracted["planes"][0]["lines"]
                if line["name"] == "XLA Modules")


@pytest.mark.parametrize("name", COUNTER_READERS + SPAN_READERS)
def test_reader_is_in_the_manifest_beside_its_file(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    counter = name in COUNTER_READERS
    assert entry["source"] == (
        "program_counter" if counter else "program_span")
    assert entry["layer"] == ("Admission / scheduler"
                              if name.startswith("ttft_queue")
                              else "Dispatch pipeline")
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["moves"] == "ttft_ms_mean"
    assert entry["workloads"] == ["mistral-7b.tool-turns"]
    assert os.path.exists(
        os.path.join(ROOT, "perfbench", "metrics", name + ".py"))


@pytest.mark.parametrize("name", COUNTER_READERS + SPAN_READERS)
def test_reader_returns_none_without_its_source(name):
    """A server without the keys (the parent commit), and no trace."""
    old_server = {"blocks_dispatched": 5.0, "ttft_phase_count": 3.0,
                  "ttft_phase_seconds": {"queue": 1.0}}
    newer = dict(old_server, ttft_phase_count=9.0)
    assert run.read_metric(name, context()) is None
    assert run.read_metric(name, context(old_server, newer)) is None
    # A trace whose events file is not there.
    assert run.read_metric(
        name, context(old_server, newer, trace={"busy_s": 1.0})) is None


@pytest.mark.parametrize("name, want", [
    ("ttft_queue_loop_ms_mean", 90.0),          # 1.8 s over 20 requests
    ("ttft_queue_budget_ms_mean", 150.0),
    ("ttft_queue_capacity_ms_mean", 10.0),      # no_slot .15 + no_pages .05
    ("first_token_poll_gap_ms_mean", 150.0),    # 1.2 s over 8 dispatches
])
def test_counter_readers_on_synthetic_stats(name, want):
    ctx = context(stats(1.0), stats(2.0))
    assert run.read_metric(name, ctx) == pytest.approx(want)
    # Nothing happened in the window: no value, no division by zero.
    assert run.read_metric(name, context(stats(1.0), stats(1.0))) is None


def test_queue_causes_add_up_to_the_queue_phase():
    ctx = context(stats(1.0), stats(3.0))
    causes = sum(run.read_metric(name, ctx) for name in COUNTER_READERS[:3])
    assert causes == pytest.approx(run.read_metric("ttft_queue_ms_mean", ctx))


def lag_readings(tmp_path, monkeypatch, extracted: dict) -> list:
    trace = place(tmp_path, monkeypatch, extracted)
    return [run.read_metric(name, context(trace=trace))
            for name in SPAN_READERS]


def test_lag_and_its_covered_part_by_hand(tmp_path, monkeypatch):
    extracted = synthetic()
    assert waits.program_ends(extracted, "jit__prefill_fn") == [
        182 * MS, 364 * MS, 546 * MS]
    assert waits.lag_intervals(extracted) == [
        (182 * MS, 275 * MS), (364 * MS, 460 * MS), (546 * MS, 640 * MS)]
    mean, p90, covered = lag_readings(tmp_path, monkeypatch, extracted)
    # Lags 93, 96 and 94 ms; the 90th percentile between 94 and 96.
    assert mean == pytest.approx((93 + 96 + 94) / 3)
    assert p90 == pytest.approx(94 + 0.8 * (96 - 94))
    # Under `readback_wait`: 200..272 of 182..275, 390..454 of 364..460,
    # nothing of 546..640.
    assert covered == pytest.approx((72 + 64 + 0) / 3)
    assert covered <= mean


def edge(case: str) -> dict:
    extracted = copy.deepcopy(synthetic())
    if case == "opened_before_a_read":
        # The capture opened between an execution's end and its read.
        extracted["annotations"].insert(
            0, ["polykey/first_token", 100 * MS, 1 * MS])
    elif case == "closed_before_a_read":
        # ... and closed between them: the last execution waits unread.
        modules(extracted).append(
            ["jit__prefill_fn(22)", 636 * MS, 92 * MS, {}])
    elif case == "execution_nobody_reads":
        # A fourth execution, before the last read, with no span of its
        # own (a chunk that does not complete its prompt).
        modules(extracted).insert(
            2, ["jit__prefill_fn(22)", 182 * MS, 20 * MS, {}])
    elif case == "read_before_its_execution":
        extracted["annotations"][3][1] = 300 * MS   # second read, 64 ms early
    elif case == "no_first_token_span":
        extracted["annotations"] = [
            a for a in extracted["annotations"]
            if a[0] != "polykey/first_token"]
    elif case == "no_prefill_execution":
        modules(extracted)[:] = [
            e for e in modules(extracted) if "prefill" not in e[0]]
    return extracted


@pytest.mark.parametrize("case", ["opened_before_a_read",
                                  "closed_before_a_read"])
def test_lag_join_skips_what_a_captures_edge_cut(
        tmp_path, monkeypatch, case):
    assert lag_readings(tmp_path, monkeypatch, edge(case)) == \
        lag_readings(tmp_path / "whole", monkeypatch, synthetic())


@pytest.mark.parametrize("case", [
    "execution_nobody_reads", "read_before_its_execution",
    "no_first_token_span", "no_prefill_execution"])
def test_lag_join_gives_no_number_where_the_two_do_not_pair_off(
        tmp_path, monkeypatch, case):
    assert waits.lag_intervals(edge(case)) is None
    assert lag_readings(tmp_path, monkeypatch, edge(case)) == [None] * 3


@pytest.mark.parametrize("name", SPAN_READERS)
@pytest.mark.parametrize("damage", ["not_gzip", "other_form"])
def test_span_reader_reports_nothing_from_events_it_cannot_read(
        tmp_path, monkeypatch, capsys, name, damage):
    trace = place(tmp_path, monkeypatch, synthetic())
    path = tmp_path / "out" / META["workload"] / \
        "seed7.trace1.trace.events.json.gz"
    if damage == "not_gzip":
        path.write_bytes(b"{}")
    else:
        with gzip.open(path, "wt") as f:
            json.dump({"planes": [{"lines": [{"name": "XLA Modules"}]}],
                       "annotations": [["polykey/first_token", 5]]}, f)
    assert run.read_metric(name, context(trace=trace)) is None
    assert "Traceback" in capsys.readouterr().err
