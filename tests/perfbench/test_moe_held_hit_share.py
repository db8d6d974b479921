"""`moe_held_experts_hit_share` (ISSUE 53): of the held experts of the decode
program's expert layers, the share a live lane chose, from the two
engine_stats counters read beside the profiler's start and stop; nothing —
never an exception — where the program has no such counters; one number with
what `moe_held_experts_roofline` counts as needed; in the manifest for the
cells whose configuration has an expert ("E") layer and for no other."""

import json
import os

import pytest

from perfbench_paths import ROOT

import kernel_costs
import run
from test_moe_grouped_share import hybrid_cells
from test_perfbench_capture import HYBRID as HELD, held_context, spec_of

NAME = "moe_held_experts_hit_share"


def context(name, stats_start, stats_stop):
    """A traced run of any cell with nothing in its capture but the two
    readings of engine_stats."""
    traced = {"start": 100.0, "stop": 104.0, "stop_call_s": 60.0,
              "stats_start": stats_start, "stats_stop": stats_stop}
    return run.Context(samples={"meta": {"traced": traced}, "requests": []},
                       trace=None, spec=spec_of(name))


def counted(calls, hit=None):
    stats = {"blocks_dispatched": calls / 8}
    if hit is not None:
        stats.update(held_expert_calls=calls, held_experts_hit=hit)
    return stats


@pytest.mark.parametrize("name", sorted(HELD))
@pytest.mark.parametrize("share", [0.31, 0.72, 1.0])
def test_share_of_the_held_experts_hit_over_the_capture(name, share):
    """2,000 expert-layer calls between the two readings."""
    first = counted(100.0, 5000.0)
    last = counted(2100.0, 5000.0 + share * 2000 * HELD[name])
    assert run.read_metric(NAME, context(name, first, last)) == \
        pytest.approx(100.0 * share)


@pytest.mark.parametrize("first, last", [
    # The parent's program: no such counter.
    (counted(100.0), counted(2100.0)),
    # The counters appear with the first decode block that lands: one
    # reading without them is no reading.
    (counted(0.0), counted(2100.0, 9000.0)),
    # No decode step between the readings.
    (counted(100.0, 5000.0), counted(100.0, 5000.0)),
    # A capture that never started (the tools' call failed).
    (None, None),
    ({}, {}),
], ids=["no-counter", "one-sided", "no-step", "no-readings", "empty"])
def test_nothing_where_there_is_nothing_to_divide(first, last):
    for name in HELD:
        assert run.read_metric(NAME, context(name, first, last)) is None


@pytest.mark.parametrize("name", ["mistral-7b", "mixtral-8x7b-tp4"])
def test_a_dense_cell_reads_nothing(name):
    """Its server has no such counters, and its costs no held experts."""
    stats = {"blocks_dispatched": 5.0}
    assert run.read_metric(NAME, context(name, stats, stats)) is None


@pytest.mark.parametrize("name", sorted(HELD))
def test_the_roofline_counts_the_share_this_metric_reads(name):
    """A kernel at 90 % of the roofline every held expert's bytes give
    (`held_context`) reads 90 % x (the bytes of the share hit / all of
    them): the two metrics of one line describe one capture."""
    ctx = held_context(name, counted(100.0, 5000.0),
                       counted(2100.0, 5000.0 + 0.4 * 2000 * HELD[name]))
    share = run.read_metric(NAME, ctx)
    assert share == pytest.approx(40.0)
    costs = kernel_costs.for_spec(ctx.spec)
    whole = costs.moe_held_experts(ctx.spec, 64)["bytes"]
    hit = costs.moe_held_experts(ctx.spec, 64, hit_share=share / 100.0)["bytes"]
    assert run.read_metric("moe_held_experts_roofline", ctx) == \
        pytest.approx(90.0 * hit / whole)


def test_manifest_entry_lists_the_hybrid_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    hybrid = hybrid_cells(manifest)
    assert len(hybrid) >= 3
    entries = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entries == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Kernels",
        "moves": "tpot_ms_mean", "workloads": hybrid,
    }]
    assert os.path.exists(
        os.path.join(ROOT, "perfbench", "metrics", NAME + ".py"))
    for cell in manifest["workloads"]:
        names = [m["name"] for m in
                 run.metrics_for(manifest, cell["name"], "per_layer")]
        assert (NAME in names) == (cell["name"] in hybrid)
