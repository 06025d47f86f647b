"""Metrics that weigh what the program's spans count against the time
the device took, read from the run's xplane as
``benchmark/readers/program_trace.py`` reads it (its ``load``, its
slice, its labels).

``what`` is

- ``scope_union_ms_per_span``: the time on chip 0 during which ANY
  operation whose scope contains one of ``match`` ran (the union of
  their intervals: a loop's ``while`` and the operations of its body
  carry the same scope and lie inside one another, so their sum would
  count the time twice), for the operations that start inside a span
  named ``per`` in the slice, per such span;
- ``counts_ratio``: the sum over the spans named ``num.span`` of the
  product of their stats ``num.stats``, over the sum of the stat
  ``den.stat`` over the spans named ``den.span`` that start inside one
  of those (the ``num`` spans that lie in the slice);
- ``bytes_roofline``: ``100 * bytes a step / (peak bytes/s * seconds a
  step)``. Bytes a step: the record's ``fixed`` (a key, may be absent)
  plus the record's ``per_row`` times the mean of the stat ``rows`` over
  the spans named ``span``. Seconds a step: the device time of the
  compiled program ``program`` per run, or, with ``match``, of the
  operations whose label contains one of ``match`` per run of
  ``program``. The peak is the record's ``peak_hbm_bytes_per_s``.

Each returns ``None`` where the harness has no trace summary, the trace
no device plane, no such span, stat, scope, program or kernel (a program
from before they existed), or the record lacks a key.
"""

from __future__ import annotations

from benchmark import trace_reduce
from benchmark.readers import program_trace


def scope_union_ms_per_span(spans, ops, match, per: str) -> float | None:
    inside = program_trace.in_slice(spans, ops, per)
    scoped = [(e[0], e[1], e[2]) for e in ops
              if any(m in e[3] for m in match)
              and any(s[1] <= e[1] < s[1] + s[2] for s in inside)]
    if not inside or not scoped:
        return None
    union = trace_reduce.busy_union(scoped)
    return sum(b - a for a, b in union) / len(inside) * 1e3


def _stat_sum(spans, name: str, stats) -> float | None:
    """Σ over the spans named ``name`` of the product of ``stats``;
    ``None`` where no such span carries them all."""
    total, seen = 0.0, False
    for s in spans:
        if s[0] != name or not all(
                isinstance(s[3].get(k), (int, float)) for k in stats):
            continue
        product = 1.0
        for k in stats:
            product *= s[3][k]
        total, seen = total + product, True
    return total if seen else None


def counts_ratio(spans, ops, num: dict, den: dict) -> float | None:
    """Both sums over the same work: the ``num`` spans that lie inside
    the slice, and the ``den`` spans that start inside one of those (a
    ``num`` span cut by the slice's edge is left out with what it
    holds)."""
    above = program_trace.in_slice(spans, ops, num["span"])
    below = [s for s in spans if s[0] == den["span"]
             and any(a[1] <= s[1] < a[1] + a[2] for a in above)]
    top = _stat_sum(above, num["span"], num["stats"])
    bottom = _stat_sum(below, den["span"], [den["stat"]])
    if top is None or not bottom:
        return None
    return top / bottom


def bytes_roofline(spans, ops, record: dict, trace: dict,
                   args: dict) -> float | None:
    inside = [s for s in program_trace.in_slice(spans, ops, args["span"])
              if isinstance(s[3].get(args["rows"]), (int, float))]
    runs, seconds = (trace.get("programs") or {}).get(args["program"],
                                                      (0, 0.0))
    per_row = record.get(args["per_row"])
    peak = record.get("peak_hbm_bytes_per_s")
    fixed = record.get(args["fixed"]) if args.get("fixed") else 0.0
    if not inside or not runs or not peak or per_row is None \
            or fixed is None:
        return None
    if args.get("match"):
        seconds = sum(e[2] for e in ops if any(
            m in program_trace.op_label(e[0]) for m in args["match"]))
    if not seconds:
        return None
    rows = sum(s[3][args["rows"]] for s in inside) / len(inside)
    return 100.0 * (fixed + per_row * rows) / (peak * seconds / runs)


def read(args: dict, record: dict, trace: dict | None) -> float | None:
    path = (program_trace.newest_xplane(program_trace.TRACE_DIR)
            if trace else None)
    if path is None:
        return None
    parsed = program_trace.load(path)
    if not parsed["ops"]:
        return None
    spans = parsed["spans"]
    ops = parsed["ops"][min(parsed["ops"])]
    what = args["what"]
    if what == "scope_union_ms_per_span":
        return scope_union_ms_per_span(spans, ops, args["match"],
                                       args["per"])
    if what == "counts_ratio":
        return counts_ratio(spans, ops, args["num"], args["den"])
    if what == "bytes_roofline":
        return bytes_roofline(spans, ops, record, trace, args)
    raise ValueError(f"unknown span_work metric {what!r}")
