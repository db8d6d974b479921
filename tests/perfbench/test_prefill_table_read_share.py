"""`prefill_table_read_share` (ISSUE 59): of the positions the prefill
dispatches' page tables span, the share a layer's attention gathered and
streamed, from two engine_stats counters read beside the profiler's start
and stop; nothing — never an exception — on a program without the counters
(the parent) or with no prefill in the capture; in the manifest last, for
every cell."""

import json
import os

import pytest

from perfbench_paths import ROOT

import run

NAME = "prefill_table_read_share"


def context(stats_start, stats_stop):
    traced = {"start": 100.0, "stop": 104.0, "stop_call_s": 60.0,
              "stats_start": stats_start, "stats_stop": stats_stop}
    return run.Context(samples={"meta": {"traced": traced}, "requests": []},
                       trace=None)


def counted(table=None, read=None):
    stats = {"prefill_rows_dispatched": 1024.0}
    if table is not None:
        stats.update(prefill_keys_table_total=table,
                     prefill_keys_read_total=read)
    return stats


@pytest.mark.parametrize("first, last, want", [
    # 44 rows of a 4,096-position table, 512 keys read of each.
    (counted(8192.0, 1024.0), counted(188416.0, 23552.0), 12.5),
    # A long prompt's last chunks: the whole table.
    (counted(0.0, 0.0), counted(8192.0, 8192.0), 100.0),
    (counted(4096.0, 512.0), counted(12288.0, 3584.0), 37.5),
    # The parent's program: no such counters.
    (counted(), counted(), None),
    # No prefill between the readings.
    (counted(8192.0, 1024.0), counted(8192.0, 1024.0), None),
    (None, None, None), ({}, {}, None),
], ids=["tool-turns", "table-end", "mixed", "no-counter", "no-prefill",
        "no-readings", "empty-readings"])
def test_share_of_the_tables_read(first, last, want):
    got = run.read_metric(NAME, context(first, last))
    assert got == (None if want is None else pytest.approx(want))


def test_an_untraced_run_reads_nothing():
    ctx = run.Context(samples={"meta": {"traced": {
        "start": None, "stop": None}}, "requests": []}, trace=None)
    assert run.read_metric(NAME, ctx) is None


def test_manifest_entry_is_last_and_lists_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Kernels",
        "moves": "tpot_ms_mean",
        "workloads": [cell["name"] for cell in manifest["workloads"]],
    }
    assert os.path.exists(
        os.path.join(ROOT, "perfbench", "metrics", NAME + ".py"))
    for cell in manifest["workloads"]:
        names = [m["name"] for m in
                 run.metrics_for(manifest, cell["name"], "per_layer")]
        assert NAME in names
