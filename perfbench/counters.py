"""Helpers the counter-based readers share: deltas of engine_stats."""

from __future__ import annotations

import statistics


def delta(ctx, key: str) -> float:
    """Counter `key`, window close minus window open."""
    return float(ctx.stats_close[key]) - float(ctx.stats_open[key])


def traced_delta(ctx, key: str):
    """Counter `key` over the traced part of the window."""
    traced = ctx.samples["meta"]["traced"]
    if not traced.get("stats_start") or not traced.get("stats_stop"):
        return None
    return float(traced["stats_stop"][key]) - float(traced["stats_start"][key])


def live_tokens(ctx, points: int = 40):
    """Tokens whose K/V a decode step must read, averaged over the traced
    part of the window: for every stream live at an instant, its prompt
    plus the tokens the client had received by then. (Pages in use would
    overstate it: the engine reserves a request's pages ahead. The client
    lags the device by the dispatch lookahead, a few percent of a
    context, so this understates rather than overstates.)"""
    traced = ctx.samples["meta"]["traced"]
    if traced["start"] is None or traced["stop"] is None:
        return None
    totals = []
    for i in range(points):
        t = traced["start"] + (traced["stop"] - traced["start"]) * (i + 0.5) / points
        live = 0
        for r in ctx.samples["requests"]:
            if not r["times"] or r["times"][0] > t:
                continue
            ended = r["final"] if r["final"] is not None else r["times"][-1]
            if ended < t:
                continue
            got = sum(n for at, n in zip(r["times"], r["counts"]) if at <= t)
            live += r["prompt_tokens"] + got
        totals.append(live)
    return statistics.fmean(totals)


def decode_step_device_ms(ctx):
    """Median device time of a decode block in the trace over its steps."""
    decode = ctx.trace["modules"].get("jit__decode_fn")
    if not decode or not decode["durations_s"]:
        return None
    steps = ctx.spec["engine"]["decode_block_steps"]
    return 1000.0 * statistics.median(decode["durations_s"]) / steps
