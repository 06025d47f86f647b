"""Metrics of a layer of sparse experts and of latent attention: what the
program's spans count of its routing, and the bytes and operations those
counts stand for against the time the device took. Read from the run's
xplane as ``benchmark/readers/program_trace.py`` reads it (its ``load``,
its slice, its labels).

``what`` is

- ``stat_ratio``: ``scale`` x the sum of the stat ``num`` over the sum of
  the product of the stats ``den`` (a name or a list), both over the spans
  named ``span`` that lie in the slice and carry them all;
- ``roofline``: ``100 x least seconds / seconds`` of one run. The work
  of a run is a sum of terms, each the record's ``per`` times the mean
  of the stat ``stat`` over the spans named ``span`` (a term without
  ``stat`` counts once): ``bytes`` over the record's
  ``peak_hbm_bytes_per_s``, and, where given, ``flops`` over one chip's
  ``peak_flops``; the least seconds are the larger of the two. Seconds a
  run: with ``match``, the device time of the operations whose label
  contains one of ``match`` and that start inside such a span, per span;
  else the device time of the compiled program ``program`` per run.

Each returns ``None`` where the harness has no trace summary, the trace
no device plane, no such span, stat, program or kernel (a program from
before they existed), or the record lacks a key.
"""

from __future__ import annotations

from benchmark.readers import program_trace


def _numbers(span, names) -> list | None:
    values = [span[3].get(n) for n in names]
    return values if all(isinstance(v, (int, float)) for v in values) \
        else None


def stat_ratio(spans, ops, span: str, num: str, den,
               scale: float = 1.0) -> float | None:
    den = [den] if isinstance(den, str) else list(den)
    top = bottom = 0.0
    for s in program_trace.in_slice(spans, ops, span):
        values = _numbers(s, [num] + den)
        if values is None:
            continue
        product = 1.0
        for v in values[1:]:
            product *= v
        top, bottom = top + values[0], bottom + product
    return scale * top / bottom if bottom else None


def _work(terms, inside, record: dict) -> float | None:
    total = 0.0
    for term in terms:
        per = record.get(term["per"])
        if per is None:
            return None
        if "stat" in term:
            counted = [s[3][term["stat"]] for s in inside]
            per *= sum(counted) / len(counted)
        total += per
    return total


def roofline(spans, ops, record: dict, trace: dict,
             args: dict) -> float | None:
    stats = [t["stat"] for t in args["bytes"] + args.get("flops", [])
             if "stat" in t]
    inside = [s for s in program_trace.in_slice(spans, ops, args["span"])
              if _numbers(s, stats) is not None]
    peak_bytes = record.get("peak_hbm_bytes_per_s")
    if not inside or not peak_bytes:
        return None
    moved = _work(args["bytes"], inside, record)
    if moved is None:
        return None
    least = moved / peak_bytes
    if args.get("flops"):
        peak, chips = record.get("peak_flops"), trace.get("chips")
        done = _work(args["flops"], inside, record)
        if not peak or not chips or done is None:
            return None
        least = max(least, done / (peak / chips))
    if args.get("match"):
        seconds = sum(
            e[2] for e in ops
            if any(m in program_trace.op_label(e[0]) for m in args["match"])
            and any(s[1] <= e[1] < s[1] + s[2] for s in inside)
        ) / len(inside)
    else:
        runs, total = (trace.get("programs") or {}).get(args["program"],
                                                        (0, 0.0))
        seconds = total / runs if runs else 0.0
    return 100.0 * least / seconds if seconds else None


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
    if what == "stat_ratio":
        return stat_ratio(spans, ops, args["span"], args["num"],
                          args["den"], args.get("scale", 1.0))
    if what == "roofline":
        return roofline(spans, ops, record, trace, args)
    raise ValueError(f"unknown expert_work metric {what!r}")
