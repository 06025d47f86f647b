#!/usr/bin/env python3
"""How widely the runs of one tree spread, reckoned as the driver's
check reckons it, and a table of it for a directory of result lines.

A spread is the distance between the first and the third quartile of a
set of runs (``statistics.quantiles(values, n=4)``; numpy's quartiles
lie closer together), with the run farthest from the set's median left
out where that narrows it, so that one disturbed run in a set does no
harm and two do. As a share it is taken of the whole set's median.
A bound in ``BENCHMARK.json`` is ``bound_for`` of the widest share any
set of any cell read: five times it, to the nearest half per cent,
inside the contract's 1% and 10%. (The driver's check refuses a bound
under twice and over eight times the spread its own runs read.)

    python benchmark/spread.py <directory> [<directory> ...]

reads every file of each directory whose last line is a result line of
``benchmark/run.py`` (one file a run, one directory a set) and prints,
per metric, the values, their median, spread, range and the spread over
the metric's bound.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BOUND_STEP = 0.005       # bounds are whole numbers of half per cents
BOUND_FACTOR = 5.0
BOUND_MIN, BOUND_MAX = 0.01, 0.1


def quartile_distance(values) -> float:
    """Third quartile less first; 0 of fewer than two values."""
    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def spread(values) -> float:
    """The quartile distance, without the run farthest from the median
    where leaving it out narrows it (a set of three or more)."""
    values = list(values)
    whole = quartile_distance(values)
    if len(values) < 3:
        return whole
    median = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - median))
    return min(whole, quartile_distance(values[:far] + values[far + 1:]))


def spread_share(values) -> float:
    """``spread`` as a share of the whole set's median; of a median
    of 0 (a count that read 0 in most runs), 0 where nothing spreads
    and infinite where something does."""
    width, median = spread(values), abs(statistics.median(values))
    if not median:
        return math.inf if width else 0.0
    return width / median


def bound_for(widest_share: float) -> float:
    """The bound the widest spread of a metric asks for."""
    steps = math.floor(BOUND_FACTOR * widest_share / BOUND_STEP + 0.5)
    return min(BOUND_MAX, max(BOUND_MIN, steps * BOUND_STEP))


def result_lines(directory: str) -> list[dict]:
    """The result line of every run kept in ``directory``, by file
    name; a file whose last line is no result line is passed over."""
    out = []
    for name in sorted(os.listdir(directory)):
        path = os.path.join(directory, name)
        if not os.path.isfile(path):
            continue
        with open(path, errors="replace") as f:
            lines = f.read().strip().splitlines()
        try:
            line = json.loads(lines[-1]) if lines else None
        except ValueError:
            continue
        if isinstance(line, dict) and "metrics" in line:
            out.append(line)
    return out


def table(runs: list[dict], bounds: dict[str, float]) -> list[dict]:
    """One row a metric over the result lines ``runs``."""
    rows = []
    for name in sorted({m for r in runs for m in r["metrics"]}):
        values = [r["metrics"][name]["value"] for r in runs
                  if name in r["metrics"]]
        share = spread_share(values)
        bound = bounds.get(name)
        rows.append({
            "metric": name, "n": len(values), "values": values,
            "median": statistics.median(values), "spread": spread(values),
            "spread_share": share, "range": [min(values), max(values)],
            "bound": bound,
            "spread_over_bound": share / bound if bound else None})
    return rows


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print("usage: python benchmark/spread.py <directory> "
              "[<directory> ...]", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bounds = {m["name"]: m["bound"]
                  for m in json.load(f)["end_to_end"]}
    for directory in argv:
        runs = result_lines(directory)
        wrong = sum(not r["correct"] or r["failed"] > 0 for r in runs)
        print(f"{directory}: {len(runs)} runs, {wrong} not correct or "
              f"with failed requests")
        for row in table(runs, bounds):
            over = ("" if row["spread_over_bound"] is None else
                    f"  {row['spread_over_bound']:.2f} of the bound "
                    f"{100 * row['bound']:g}%")
            print(f"  {row['metric']}: n {row['n']}  median "
                  f"{row['median']:.6g}  spread {row['spread']:.4g} "
                  f"({100 * row['spread_share']:.3f}%)  range "
                  f"{row['range'][0]:.6g}-{row['range'][1]:.6g}{over}")
            print("    " + " ".join(f"{v:.6g}" for v in row["values"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
