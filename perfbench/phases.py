"""What the phase readers share: deltas of the engine's phase accumulators
(engine_stats `phase_seconds`, `ttft_phase_seconds`, the lane-step and
prefill-row counters) and the extracted events of a traced run, the
`.events.json.gz` that trace_reduce.py writes beside the reduced trace:
the host's `polykey/` spans and the device planes' lines on the
profiler's one clock. Everything here returns None where its source is
missing (a server without the keys, a run without a device trace): the
readers also run over a program that predates them, and a reader that
raises there costs the run its result line."""

from __future__ import annotations

import gzip
import json
import os
import traceback

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
# The engine's loop phases that do work (obs/timeline.py PHASES, level
# "loop", less idle_wait): never nested in one another, so their seconds
# add up to the engine thread's busy time.
LOOP_WORK_PHASES = ("admit", "restore", "chunk", "dispatch", "resolve",
                    "process")


def delta(ctx, key: str, entry: str | None = None):
    """Counter `key` (or entry `entry` of the dict `key`), window close
    minus window open; None if either reading lacks it."""
    values = []
    for stats in (ctx.stats_open, ctx.stats_close):
        value = stats.get(key)
        if entry is not None:
            value = value.get(entry) if isinstance(value, dict) else None
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            return None
        values.append(float(value))
    return values[1] - values[0]


def ttft_phase_ms_mean(ctx, phase: str):
    """Mean milliseconds of one TTFT phase over the requests whose first
    token resolved inside the window."""
    seconds = delta(ctx, "ttft_phase_seconds", phase)
    count = delta(ctx, "ttft_phase_count")
    if seconds is None or not count:
        return None
    return 1000.0 * seconds / count


def share(part, whole):
    if part is None or not whole:
        return None
    return 100.0 * part / whole


def events(ctx):
    """The traced run's extracted events, or None without a trace."""
    if getattr(ctx, "trace", None) is None:
        return None
    meta = ctx.samples["meta"]
    path = os.path.join(
        HERE, "out", meta["workload"],
        f"seed{meta['seed']}.trace{meta['trace']}.trace.events.json.gz")
    if not os.path.exists(path):
        return None
    try:
        with gzip.open(path, "rt") as f:
            return json.load(f)
    except (OSError, EOFError, ValueError):
        traceback.print_exc()       # cut short or not gzip: nothing to read
        return None


def from_events(ctx, compute):
    """`compute(extracted)` on the traced run's events; None without a
    trace, and where the events are not in the form this file expects
    (another trace_reduce.py, a capture cut short): the traceback goes to
    stderr and the run keeps its result line."""
    extracted = events(ctx)
    if extracted is None:
        return None
    try:
        return compute(extracted)
    except Exception:
        traceback.print_exc()
        return None


def spans(extracted: dict, name: str) -> list:
    """(start, end) of the host spans named `polykey/<name>`, by start."""
    full = trace_reduce.ANNOTATION_PREFIX + name
    return sorted((start, start + dur)
                  for got, start, dur in extracted["annotations"]
                  if got == full)


def program_starts(extracted: dict, program: str) -> list:
    """Start times of one jitted program's executions on the first
    device plane ('XLA Modules' line), in order."""
    for plane in extracted["planes"][:1]:
        for line in plane["lines"]:
            if line["name"] == trace_reduce.MODULES_LINE:
                return sorted(
                    e[1] for e in line["events"]
                    if trace_reduce.program_of(e[0]) == program)
    return []


def join_in_order(host_starts: list, device_starts: list) -> list:
    """Device start minus host start, the n-th dispatch with the n-th
    execution. A capture can open between a dispatch and its execution,
    so leading executions whose dispatch it did not see are skipped: the
    smallest shift at which no execution precedes its own dispatch."""
    for shift in range(len(device_starts) + 1):
        pairs = list(zip(host_starts, device_starts[shift:]))
        if all(device >= host for host, device in pairs):
            return [device - host for host, device in pairs]
    return []


def merged(intervals) -> list:
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def overlap(gaps: list, cover: list) -> float:
    """Total length of `gaps` (sorted, disjoint) that the merged
    intervals `cover` overlap."""
    total, i = 0.0, 0
    for start, end in gaps:
        while i < len(cover) and cover[i][1] <= start:
            i += 1
        j = i
        while j < len(cover) and cover[j][0] < end:
            total += min(end, cover[j][1]) - max(start, cover[j][0])
            j += 1
    return total


def idle_gap_named_share(extracted: dict):
    """Share of the device's idle time (the gaps between operations, every
    device plane) that some `polykey/` host span overlaps. Counted from
    the first recorded span's start to the last one's end: a span that
    was open when the capture started or stopped is not in it, so
    outside that stretch the capture cannot say what the host was doing."""
    cover = merged((start, start + dur)
                   for _name, start, dur in extracted["annotations"])
    if not cover:
        return 0.0
    seen = [(cover[0][0], cover[-1][1])]
    idle = named = 0.0
    for plane in extracted["planes"]:
        for line in plane["lines"]:
            if line["name"] != trace_reduce.OPS_LINE:
                continue
            gaps = trace_reduce.gaps(
                (e[1], e[1] + e[2]) for e in line["events"])
            idle += overlap(gaps, seen)
            named += overlap(gaps, cover)
    return share(named, idle)
