"""From a run's raw samples to numbers: the end-to-end definitions and the
candidates the noise study compares. Pure Python over the sample file.

A sample file (run.py writes one per run) is
    {"meta": {"t_open": s, "t_close": s, ...},
     "requests": [{"client", "index", "prompt_tokens", "asked", "send",
                   "times": [...], "counts": [...], "final", "status",
                   "error", "usage"}, ...]}
with every time in seconds on the load generator's monotonic clock. A chunk
of n characters is n tokens arriving at one instant.
"""

from __future__ import annotations

import statistics


def percentile(values, q: float):
    """Linear interpolation between order statistics (q in 0..100)."""
    if not values:
        return None
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def trimmed_mean(values, share: float = 0.1):
    """Mean without the lowest and the highest `share` of the samples."""
    if not values:
        return None
    ordered = sorted(values)
    cut = int(len(ordered) * share)
    kept = ordered[cut:len(ordered) - cut] or ordered
    return statistics.fmean(kept)


def window(samples: dict, seconds: float | None = None):
    """(open, close); `seconds` cuts a shorter window from the same run."""
    meta = samples["meta"]
    t0 = meta["t_open"]
    t1 = meta["t_close"] if seconds is None else min(
        meta["t_close"], t0 + seconds)
    return t0, t1


def token_events(samples: dict, t0: float, t1: float):
    """Every chunk arrival inside [t0, t1], in time order: (time, tokens)."""
    events = []
    for r in samples["requests"]:
        for t, n in zip(r["times"], r["counts"]):
            if t0 <= t <= t1:
                events.append((t, n))
    events.sort()
    return events


BURST_GAP_S = 0.010


def bursts(events):
    """Arrival instants: chunks closer than BURST_GAP_S are one burst (a
    decode block's tokens reach all clients within 20-30 ms; blocks are
    hundreds of ms apart). Returns [(start time, tokens), ...]."""
    out = []
    last = None
    for t, n in events:
        if last is not None and t - last <= BURST_GAP_S:
            out[-1][1] += n
        else:
            out.append([t, n])
        last = t
    return out


def rate_edge_aligned(events, t_end=None):
    """Tokens per second between the first and the last arrival instant:
    the tokens after the first instant over the time between the starts of
    the two, an instant being a burst of chunks (see `bursts`). A burst on
    the window's edge neither adds nor drops tokens: the first burst only
    starts the clock, and a last burst that the window's end may have cut
    (its last chunk within BURST_GAP_S of `t_end`) is left out. Streams
    that arrive as fewer than three bursts fall back to single chunks."""
    if len(events) < 2:
        return None
    groups = bursts(events)
    if t_end is not None and t_end - events[-1][0] <= BURST_GAP_S:
        groups = groups[:-1]
    if len(groups) < 3:
        groups = [[t, n] for t, n in events]
    span = groups[-1][0] - groups[0][0]
    if span <= 0:
        return None
    return sum(n for _, n in groups[1:]) / span


def output_tok_s(samples: dict, seconds: float | None = None):
    t0, t1 = window(samples, seconds)
    return rate_edge_aligned(token_events(samples, t0, t1), t1)


def output_tok_s_fixed_wall(samples: dict, seconds: float | None = None):
    """The candidate the issue argues against: tokens in the window over
    the window's wall time."""
    t0, t1 = window(samples, seconds)
    events = token_events(samples, t0, t1)
    return sum(n for _, n in events) / (t1 - t0) if t1 > t0 else None


def output_tok_s_subwindow_median(samples: dict, seconds: float | None = None,
                                  parts: int = 5):
    """Median of the edge-aligned rates of `parts` equal sub-windows."""
    t0, t1 = window(samples, seconds)
    step = (t1 - t0) / parts
    rates = [rate_edge_aligned(token_events(
        samples, t0 + i * step, t0 + (i + 1) * step), t0 + (i + 1) * step)
        for i in range(parts)]
    rates = [r for r in rates if r is not None]
    return statistics.median(rates) if rates else None


def stream_tpots_ms(samples: dict, seconds: float | None = None,
                    min_tokens: int = 32):
    """Per stream: (last - first token time) / (tokens - 1) over the part
    of the stream inside the window, streams with >= min_tokens inside."""
    t0, t1 = window(samples, seconds)
    out = []
    for r in samples["requests"]:
        inside = [(t, n) for t, n in zip(r["times"], r["counts"])
                  if t0 <= t <= t1]
        tokens = sum(n for _, n in inside)
        if tokens >= min_tokens and inside[-1][0] > inside[0][0]:
            out.append(1000.0 * (inside[-1][0] - inside[0][0]) / (tokens - 1))
    return out


def tpot_ms_p50(samples: dict, seconds: float | None = None):
    tpots = stream_tpots_ms(samples, seconds)
    return statistics.median(tpots) if tpots else None


def tpot_ms_stream_mean(samples: dict, seconds: float | None = None):
    """Candidate: the plain mean of the per-stream values."""
    tpots = stream_tpots_ms(samples, seconds)
    return statistics.fmean(tpots) if tpots else None


def tpot_ms_mean(samples: dict, seconds: float | None = None,
                 min_tokens: int = 32):
    """Mean time per output token over all tokens: the sum over streams of
    (last - first token time) over the sum of (tokens - 1), for the part of
    each stream inside the window, streams with >= min_tokens inside. Each
    stream weighs as many intervals as it has, so a stream the window's
    edge cut short does not count like a whole one."""
    t0, t1 = window(samples, seconds)
    span = intervals = 0.0
    for r in samples["requests"]:
        inside = [(t, n) for t, n in zip(r["times"], r["counts"])
                  if t0 <= t <= t1]
        tokens = sum(n for _, n in inside)
        if tokens >= min_tokens and inside[-1][0] > inside[0][0]:
            span += inside[-1][0] - inside[0][0]
            intervals += tokens - 1
    return 1000.0 * span / intervals if intervals else None


def ttfts_ms(samples: dict, seconds: float | None = None):
    """Send -> first token, every request sent inside the window that
    answered."""
    t0, t1 = window(samples, seconds)
    return [1000.0 * (r["times"][0] - r["send"])
            for r in samples["requests"]
            if r["send"] is not None and t0 <= r["send"] <= t1 and r["times"]]


def ttft_ms_mean(samples: dict, seconds: float | None = None):
    values = ttfts_ms(samples, seconds)
    return statistics.fmean(values) if values else None


def ttft_ms_trimmed(samples: dict, seconds: float | None = None):
    return trimmed_mean(ttfts_ms(samples, seconds))


def ttft_ms_p50(samples: dict, seconds: float | None = None):
    return percentile(ttfts_ms(samples, seconds), 50)


def ttft_ms_p90(samples: dict, seconds: float | None = None):
    return percentile(ttfts_ms(samples, seconds), 90)


def requests_in_window(samples: dict, seconds: float | None = None):
    return float(len(ttfts_ms(samples, seconds)))


def gateway_overheads_ms(samples: dict):
    """Client TTFT minus the engine's own (Usage.ttft_ms), per request
    that completed with Usage filled."""
    t0, t1 = window(samples)
    out = []
    for r in samples["requests"]:
        if (r["usage"] and r["times"] and r["send"] is not None
                and t0 <= r["send"] <= t1):
            out.append(1000.0 * (r["times"][0] - r["send"])
                       - r["usage"]["ttft_ms"])
    return out


def spread(values):
    """The contract's spread: inter-quartile distance of
    statistics.quantiles(values, n=4) as a share of the median."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else None


# The candidates of the noise study, by the name its table prints.
CANDIDATES = {
    "output_tok_s": output_tok_s,
    "output_tok_s.fixed_wall": output_tok_s_fixed_wall,
    "output_tok_s.subwindow_median": output_tok_s_subwindow_median,
    "tpot_ms_p50": tpot_ms_p50,
    "tpot_ms_mean": tpot_ms_mean,
    "tpot_ms_stream_mean": tpot_ms_stream_mean,
    "ttft_ms_mean": ttft_ms_mean,
    "ttft_ms_trimmed10": ttft_ms_trimmed,
    "ttft_ms_p50": ttft_ms_p50,
    "ttft_ms_p90": ttft_ms_p90,
    "requests_in_window": requests_in_window,
}
