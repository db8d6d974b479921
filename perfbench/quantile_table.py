"""Writes the length tables of the traffic files from stated distributions.

    python3 perfbench/quantile_table.py          # prints the three tables

A traffic file carries its table explicitly, so nothing reads this module
at run time; it is kept so that a later traffic file can state its table
the same way: lengths are evenly spaced quantiles of a distribution, not
draws, and each client owns one row from every band of the sorted table,
so every client carries about the same work whatever order a seed puts
its rows in.
"""

from __future__ import annotations

import json
import math
from statistics import NormalDist


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int, multiple: int = 1) -> list[int]:
    """n evenly spaced quantiles ((i + 0.5) / n) of a log-normal, clipped
    to [lo, hi] and rounded to a multiple."""
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        value = median * math.exp(sigma * z)
        value = multiple * round(value / multiple)
        out.append(int(min(hi, max(lo, value))))
    return out


def uniform_quantiles(n: int, lo: int, hi: int) -> list[int]:
    return [round(lo + (hi - lo) * (i + 0.5) / n) for i in range(n)]


def deal(sorted_values: list[int], clients: int) -> list[list[int]]:
    """Client i takes one value from each band of `clients` consecutive
    quantiles, walking the bands alternately up and down (antithetic), so
    the per-client sums are close."""
    bands = len(sorted_values) // clients
    rows = []
    for i in range(clients):
        mine = []
        for b in range(bands):
            j = i if b % 2 == 0 else clients - 1 - i
            mine.append(sorted_values[b * clients + j])
        rows.append(mine)
    return rows


def scattered(values: list[int], rows: int) -> list[int]:
    """`rows` values taken from `values` in a fixed scattered order, so a
    prompt length is not tied to an output length."""
    return [values[(13 * j + 5) % len(values)] for j in range(rows)]


def tables() -> dict:
    prompts = lognormal_quantiles(32, 200, 0.5, 32, 480)
    sat_out = deal(lognormal_quantiles(32, 384, 0.32, 256, 768, 8), 16)
    chat_out = deal(lognormal_quantiles(96, 256, 0.32, 128, 512, 8), 16)
    turn = deal(uniform_quantiles(224, 64, 128), 14)
    tool_out = [[(24, 32, 40)[(i + k) % 3] for k in range(16)]
                for i in range(14)]

    def pairs(prompt_rows, out_rows):
        flat = []
        for i, outs in enumerate(out_rows):
            flat.append([[prompt_rows[i][k], o] for k, o in enumerate(outs)])
        return flat

    sat_prompts = scattered(prompts, 32)
    chat_prompts = scattered(prompts, 96)
    return {
        "decode-saturated": pairs(
            [sat_prompts[2 * i:2 * i + 2] for i in range(16)], sat_out),
        "chat-closed": pairs(
            [chat_prompts[6 * i:6 * i + 6] for i in range(16)], chat_out),
        "tool-turns": pairs([[384 + t for t in row] for row in turn],
                            tool_out),
    }


if __name__ == "__main__":
    for name, table in tables().items():
        print(name)
        for row in table:
            print("  " + json.dumps(row))
