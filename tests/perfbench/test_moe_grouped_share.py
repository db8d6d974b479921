"""`moe_grouped_prefill_rows_share` (ISSUE 48): the share of the prefill
rows whose expert layers ran the grouped product, from two engine_stats
counters; nothing — never an exception — on a program without the
counter; in the manifest for the two hybrid cells and no other."""

import json
import os

import pytest

from perfbench_paths import ROOT

import run

NAME = "moe_grouped_prefill_rows_share"
HYBRID = ["nemotron-3-super-ep4.decode-wide", "lfm2-24b-a2b-pp4.decode-wide"]


def context(stats_open, stats_close):
    return run.Context(stats_open=stats_open, stats_close=stats_close,
                       trace=None, samples={"meta": {}})


def counters(dispatched, grouped=None):
    stats = {"prefill_rows_dispatched": dispatched, "blocks_dispatched": 5.0}
    if grouped is not None:
        stats["prefill_rows_grouped_experts"] = grouped
    return stats


@pytest.mark.parametrize("opened, closed, want", [
    # 144,000 rows in the window, 120,000 of them in wide dispatches.
    (counters(2048.0, 1024.0), counters(146048.0, 121024.0), 100 / 1.2),
    # Narrow dispatches only: a share of 0, not nothing.
    (counters(2048.0, 1024.0), counters(4096.0, 1024.0), 0.0),
    (counters(0.0, 0.0), counters(512.0, 512.0), 100.0),
    # The parent's program: no such counter.
    (counters(2048.0), counters(146048.0), None),
    # No prefill in the window.
    (counters(2048.0, 1024.0), counters(2048.0, 1024.0), None),
    ({}, {}, None),
], ids=["mixed", "all-narrow", "all-wide", "no-counter", "no-prefill",
        "no-stats"])
def test_share_of_rows_grouped(opened, closed, want):
    got = run.read_metric(NAME, context(opened, closed))
    assert got == (None if want is None else pytest.approx(want))


def test_manifest_entry_is_last_and_lists_the_hybrid_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Kernels",
        "moves": "output_tok_s", "workloads": HYBRID,
    }
    assert os.path.exists(
        os.path.join(ROOT, "perfbench", "metrics", NAME + ".py"))
    for cell in manifest["workloads"]:
        names = [m["name"] for m in
                 run.metrics_for(manifest, cell["name"], "per_layer")]
        assert (NAME in names) == (cell["name"] in HYBRID)
