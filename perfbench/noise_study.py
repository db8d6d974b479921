"""The noise study: candidate estimators compared on the same recorded runs.

    python3 perfbench/noise_study.py <set dir> [<set dir> ...]

A set directory holds the sample files of one set of runs
(`<dir>/<cell>/seed*.trace0.samples.json.gz`, as run.py writes them under
perfbench/out/). For every cell, estimator and window length (cut from
the same recordings) this prints the median over the set's runs and the
spread the contract uses: the inter-quartile distance of
statistics.quantiles(values, n=4) as a share of the median. No chip time:
everything is computed from the recorded timestamps.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import estimators  # noqa: E402

WINDOWS = (30.0, 40.0, None)     # None: the whole recorded window


def load_set(set_dir: str) -> dict:
    """{cell: [samples, ...]} in the order the runs were made."""
    cells: dict = {}
    pattern = os.path.join(set_dir, "*", "seed*.trace0.samples.json.gz")
    for path in sorted(glob.glob(pattern)):
        with gzip.open(path, "rt") as f:
            cells.setdefault(os.path.basename(os.path.dirname(path)),
                             []).append(json.load(f))
    for runs in cells.values():
        # One machine's monotonic clock: process start times give the order.
        runs.sort(key=lambda run: run["meta"]["t_start"])
    return cells


def table(set_dirs: list) -> list:
    """Rows (cell, estimator, window, [(median, spread, n) per set])."""
    sets = [load_set(d) for d in set_dirs]
    rows = []
    for cell in sorted({c for s in sets for c in s}):
        for name, fn in estimators.CANDIDATES.items():
            for seconds in WINDOWS:
                cols = []
                for s in sets:
                    values = [fn(run, seconds) for run in s.get(cell, [])]
                    values = [v for v in values if v is not None]
                    if len(values) < 2:
                        cols.append(None)
                        continue
                    cols.append((statistics.median(values),
                                 estimators.spread(values) or 0.0,
                                 len(values)))
                if any(cols):
                    rows.append((cell, name, seconds, cols))
        setups = [[run["meta"]["setup_s"] for run in s.get(cell, [])]
                  for s in sets]
        rows.append((cell, "setup_s (first run of the set left out)", None, [
            (statistics.median(v[1:]), estimators.spread(v[1:]) or 0.0,
             len(v) - 1)
            if len(v) > 2 else None for v in setups]))
        rows.append((cell, "setup_s of the set's first run", None, [
            (v[0], 0.0, 1) if v else None for v in setups]))
    return rows


def render(rows: list, set_dirs: list) -> str:
    head = ["cell", "estimator", "window"] + [
        f"{os.path.basename(os.path.normpath(d))}: median (spread %, n)"
        for d in set_dirs]
    out = ["| " + " | ".join(head) + " |", "|" + "---|" * len(head)]
    for cell, name, seconds, cols in rows:
        cells = [cell, name, "whole" if seconds is None else f"{seconds:.0f} s"]
        for col in cols:
            cells.append("-" if col is None else
                         f"{col[0]:.4g} ({100 * col[1]:.2f}%, {col[2]})")
        out.append("| " + " | ".join(cells) + " |")
    return "\n".join(out)


if __name__ == "__main__":
    dirs = sys.argv[1:]
    if not dirs:
        sys.exit(__doc__)
    print(render(table(dirs), dirs))
