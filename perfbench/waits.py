"""What the readers of a first token's two waits share (ISSUE 45).

The queue, by cause: deltas of the engine's `ttft_queue_seconds{loop,
budget, no_slot, no_pages}` over `ttft_phase_count` (engine_stats, window
close minus open): the same requests `ttft_queue_ms_mean` reads, and the
four add up to it.

A finished first token waiting for the host: in a traced run, from the end
of a prefill program's execution on the device ('XLA Modules' line) to the
start of the `polykey/first_token` host span that reads its tokens; with
no capture, the engine's own bound, `first_token_poll_gap_seconds` over
`first_token_poll_gap_count`.

Everything returns None where its source is missing (a server that
predates the keys, a run without a device trace): a reader that raises
costs the run its result line (phases.py)."""

from __future__ import annotations

import statistics

import estimators
import phases
import trace_reduce

PREFILL_PROGRAM = "jit__prefill_fn"


def queue_cause_ms_mean(ctx, *causes: str):
    """Mean milliseconds of queue time charged to `causes`, over the
    requests whose first token resolved inside the window."""
    count = phases.delta(ctx, "ttft_phase_count")
    seconds = [phases.delta(ctx, "ttft_queue_seconds", cause)
               for cause in causes]
    if not count or None in seconds:
        return None
    return 1000.0 * sum(seconds) / count


def poll_gap_ms_mean(ctx):
    """Mean milliseconds, per prefill dispatch whose first tokens were
    read inside the window, since the engine last found it unfinished."""
    seconds = phases.delta(ctx, "first_token_poll_gap_seconds")
    count = phases.delta(ctx, "first_token_poll_gap_count")
    if seconds is None or not count:
        return None
    return 1000.0 * seconds / count


def program_ends(extracted: dict, program: str) -> list:
    """End times of one jitted program's executions on the first device
    plane ('XLA Modules' line), in order."""
    for plane in extracted["planes"][:1]:
        for line in plane["lines"]:
            if line["name"] == trace_reduce.MODULES_LINE:
                return sorted(
                    e[1] + e[2] for e in line["events"]
                    if trace_reduce.program_of(e[0]) == program)
    return []


def lag_intervals(extracted: dict):
    """(device end, host start) of each first-token read in the capture:
    the n-th execution of the prefill program with the n-th
    `polykey/first_token` span. The engine enters that span once per
    prefill dispatch, at the first read of its tokens, and reads
    dispatches in the order the device ran them (two that ride the same
    decode block are read in that block's slot order, milliseconds
    apart: the mean is the same either way), so where every dispatch
    completes a prompt the two correspond one to one.

    The edges, as phases.join_in_order has them for dispatch -> start: a
    capture can open between an execution and its read, so a span that
    starts before the first recorded execution ended is some earlier
    execution's and is skipped; it can close between them, so an
    execution that ended after the last span started is still waiting
    and is skipped. What is left must pair off, each read after its own
    execution's end. Otherwise None, and no number: an execution whose
    tokens nobody reads (a chunked prompt's chunk before the last, a
    dispatch whose every request was cancelled) shifts every later pair
    by one, and a lag joined that way is wrong without looking wrong."""
    ends = program_ends(extracted, PREFILL_PROGRAM)
    starts = [start for start, _ in phases.spans(extracted, "first_token")]
    if not ends or not starts:
        return None
    starts = [start for start in starts if start >= ends[0]]
    if not starts:
        return None
    ends = [end for end in ends if end <= starts[-1]]
    if len(ends) != len(starts):
        return None
    if any(start < end for end, start in zip(ends, starts)):
        return None
    return list(zip(ends, starts))


def lags_ms(extracted: dict):
    intervals = lag_intervals(extracted)
    if intervals is None:
        return None
    return [(start - end) / 1e6 for end, start in intervals]


def lag_ms_mean(extracted: dict):
    lags = lags_ms(extracted)
    return None if lags is None else statistics.fmean(lags)


def lag_ms_p90(extracted: dict):
    lags = lags_ms(extracted)
    return None if lags is None else estimators.percentile(lags, 90)


def lag_in_readback_ms_mean(extracted: dict):
    """Mean milliseconds of a lag interval that `polykey/readback_wait`
    spans cover: the engine thread was blocked on a decode block's
    tokens while the first token lay finished."""
    intervals = lag_intervals(extracted)
    if intervals is None:
        return None
    cover = phases.merged(phases.spans(extracted, "readback_wait"))
    return statistics.fmean(
        phases.overlap([interval], cover) for interval in intervals) / 1e6
