"""The arithmetic from recorded times and counts to metric values.

A runner hands the harness one flat ``record``: named scalars and named
lists, all taken on the benchmark's own clock or read from the
program's public state. Every metric that is not read from the device
trace is one ``reduce(record, args)`` over it, with ``args`` from the
metric's own JSON file, so a new percentile or ratio is a data file.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks, as numpy's default; ``None`` of nothing."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_ms(due_s: float, first_token_s: float) -> float:
    """Time to first token from the instant the request was DUE."""
    return (first_token_s - due_s) * 1e3


def tpot_ms(token_times_s) -> float | None:
    """One request's gap between tokens: (last - first) / (n - 1)."""
    n = len(token_times_s)
    if n < 2:
        return None
    return (token_times_s[-1] - token_times_s[0]) / (n - 1) * 1e3


def whole_step_rate(step_ends_s, step_counts, open_s: float,
                    close_s: float) -> tuple[float, float]:
    """``(count, seconds)`` over whole steps: the window runs from the
    first step boundary at or after ``open_s`` to the first at or after
    ``close_s``, and a step's count belongs to the boundary that
    released it."""
    start = next((t for t in step_ends_s if t >= open_s), None)
    end = next((t for t in step_ends_s if t >= close_s), None)
    if start is None:
        return 0.0, 0.0
    if end is None:
        end = step_ends_s[-1]
    count = sum(c for t, c in zip(step_ends_s, step_counts)
                if start < t <= end)
    return float(count), end - start


def quarter_means(series) -> tuple[float, float]:
    """Means over the first and over the last quarter of ``series``. Of
    the requests left waiting after each step of a window they say
    whether the queue grew: the knee of an open loop is the highest
    rate at which the last quarter's is no more than half a request
    over the first's (bursts come and go, so single steps say
    nothing)."""
    quarter = max(1, len(series) // 4)
    return (sum(series[:quarter]) / quarter,
            sum(series[-quarter:]) / quarter)


def _product(record: dict, names) -> float | None:
    if isinstance(names, str):
        names = [names]
    out = 1.0
    for name in names:
        v = record.get(name)
        if v is None:
            return None
        out *= v
    return out


def reduce(record: dict, args: dict) -> float | None:
    """One number from ``record``; ``None`` where it has nothing to
    read (the harness then leaves the metric out of the line).

    ``stat`` is ``percentile`` (of ``series``, at ``q``), ``mean``,
    ``max`` or ``sum`` (of ``series``), ``value`` (the scalar
    ``series``), or ``ratio``: the product of the scalars named in
    ``num`` over the product of those in ``den``. ``scale`` multiplies
    the result (100 for a share in %)."""
    stat = args["stat"]
    if stat == "ratio":
        num, den = _product(record, args["num"]), _product(record,
                                                           args["den"])
        out = None if num is None or not den else num / den
    elif stat == "value":
        out = record.get(args["series"])
    else:
        values = record.get(args["series"])
        if not values:
            return None
        if stat == "percentile":
            out = percentile(values, args["q"])
        elif stat == "mean":
            out = sum(values) / len(values)
        elif stat == "max":
            out = max(values)
        elif stat == "sum":
            out = sum(values)
        else:
            raise ValueError(f"unknown stat {stat!r}")
    if out is None:
        return None
    return float(out) * args.get("scale", 1.0)
