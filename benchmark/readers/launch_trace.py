"""The device's program runs paired with the engine's launches, and what
the pairing reads: how long an admission's own run took, how long a
launched program waited for the chip, how long a finished run waited
for the host's read, and whether the chip waited for a launch.

The engine numbers every program it dispatches. The span that dispatches
one (``serve.prefill.launch``, ``serve.decode.launch``,
``kv.copy_on_write``) carries ``program`` (the compiled program's name
less ``jit_``) and ``launch`` (the number), an admission's launch also
``since_admit_s`` (seconds since the scheduler made the sequence); the
span that banks a launch's result (``serve.decode.commit``,
``serve.prefill.commit``, ``serve.drain``) carries ``read``, the number
it banks, and ``serve.prefill.commit`` the engine's own ``ttft_s``. Chip
0's ``XLA Modules`` line (``trace_reduce.PROGRAMS_LINE``) holds the runs,
``jit_<program>``, each with a ``run_id``; the runtime's host events
``DoEnqueueProgram`` and ``CompleteCallbacks`` name the same ``run_id``.

**Two clocks.** The profiler puts the chip's times some way off the
host's (about -1.5 ms in a v5e's trace, where a run would seem to start
before the span that launched it). A run is enqueued before it starts
and its completion handled after it ends, so the offset lies in a
bracket (``clock_bracket``); the spans are moved onto the chip's clock by
its lower end (``clock_offset``) before anything is paired. That end is
late by the quickest completion the runtime handled, and the upper end
is tight only where the chip was idle at an enqueue, so every instant
set against the other clock carries up to the bracket's width of error.
Only ``device_wait_ms`` is read across the clocks; the other three are
read on one clock each.

**Pairing**, per program: its launch spans in ``launch`` order and its
runs in start order, launch ``i`` with run ``i + d``, for the one offset
``d`` at which every such pair has its run start no earlier than the
launch span starts and end no later than the launch can have been read:
the end of the ``serve.decode.wait`` before the commit that names it, or
the end of the ``serve.drain`` that names it or holds that commit (a
drain reads inside itself). A launch whose read lies outside the trace
is held to the first bound alone. Where no offset fits, or more than one
does, the program is not paired and nothing is read from it.

``what``, over the runs paired with the launch spans named ``launch``:

- ``run_ms``: the ``q``-th percentile of their device time (chip clock);
- ``device_wait_ms``: the ``q``-th percentile of ``max(0, run start -
  end of its launch span)``: how long a launched program waited for the
  chip (across the clocks: high by up to the bracket's width);
- ``read_lag_ms``: the ``q``-th percentile of ``end of the span that
  reads the launch - the runtime's handling of the run's completion``:
  how long a finished result waited for the host to bank it (host clock;
  the run's end, across the clocks, where the trace holds no completion);
- ``launch_bound_share``: the share (%) of them before whose first
  operation chip 0 sat idle more than ``trace_reduce.MIN_GAP_S`` since
  its previous operation ended (chip clock): with a launch ahead, a
  program that is not held back by one before it starts as it arrives,
  so such a gap is the chip waiting for the launch.

Each returns ``None`` where the harness has no trace summary, the trace
no device plane or no program run, no span named ``launch`` carries a
launch number (a program from before it numbered them), or a program
launched by such a span could not be paired.

As a command, ``python -m benchmark.readers.launch_trace <trace dir>``
prints the table PERF.md section 5 is written from: the clocks' bracket,
per program the launches, runs, pairs and offset with the p50 and p90 of
device wait, run and read lag; per admission its time to first token in
parts beside the engine's own.
"""

from __future__ import annotations

import bisect
import functools
import itertools
import sys
from collections import defaultdict
from typing import NamedTuple

from benchmark import metric_math, trace_reduce
from benchmark.readers import program_trace as pt

#: the spans that dispatch a program and number it
LAUNCH_SPANS = ("serve.prefill.launch", "serve.decode.launch",
                "kv.copy_on_write")
#: the runtime's host events that name a run by its ``run_id`` (found on
#: the v5e): its enqueue, and the handling of its completion
RUNTIME_EVENTS = {"DoEnqueueProgram": "enqueued",
                  "CompleteCallbacks": "completed"}
PREFIX = "jit_"


class Pair(NamedTuple):
    launch: tuple        # the launch span (name, start, dur, stats, thread)
    run: tuple           # the run (program, start, dur, stats, done)
    read: tuple | None   # the span that banks it; None outside the trace


def program_of(event_name: str) -> str:
    """``jit_decode(12345)`` -> ``decode``: the name a launch span gives."""
    name = trace_reduce._numbered(event_name.split("(")[0])
    return name[len(PREFIX):] if name.startswith(PREFIX) else name


def parse(data: bytes) -> dict:
    """``{"runs": {chip: [(program, start_s, dur_s, stats)]}, "enqueued":
    {(chip, run_id): host_s}, "completed": {...}}``: the events of the
    program line of every device plane, in the file's order, and the
    host times of the runtime's own events that name a run by its
    ``run_id`` (``RUNTIME_EVENTS``)."""
    out: dict = {"runs": {}, "enqueued": {}, "completed": {}}
    for field, plane in pt._fields(memoryview(data)):
        if field != 1:
            continue
        plane_name, lines, stat_names, metadata = "", [], {}, {}
        for f, value in pt._fields(plane):
            if f == 2:
                plane_name = pt._text(value)
            elif f == 3:
                lines.append(value)
            elif f in (4, 5):
                entry = dict(pt._fields(value))
                (metadata if f == 4 else stat_names)[entry.get(1, 0)] = (
                    entry.get(2, b""))
        chip = trace_reduce.DEVICE_PLANE.match(plane_name)
        if not chip and not plane_name.startswith("/host:"):
            continue
        stat_names = {k: pt._text(dict(pt._fields(v)).get(2, b""))
                      for k, v in stat_names.items()}
        names = {k: pt._text(dict(pt._fields(v)).get(2, b""))
                 for k, v in metadata.items()}
        if not chip:
            names = {k: n for k, n in names.items() if n in RUNTIME_EVENTS}
        for line in lines:
            head = dict((f, v) for f, v in pt._fields(line) if f == 2)
            if chip and (pt._text(head.get(2, b""))
                         != trace_reduce.PROGRAMS_LINE):
                continue
            events = [(names[meta], start, dur,
                       dict(pt._stat(st, stat_names) for st in stats))
                      for meta, start, dur, stats in pt._events(line, names)]
            if chip:
                out["runs"][int(chip[1])] = [
                    (program_of(name), start, dur, stats)
                    for name, start, dur, stats in events]
                continue
            for name, start, _, stats in events:
                key = (stats.get("device_ordinal", 0), stats.get("run_id"))
                out[RUNTIME_EVENTS[name]][key] = start
    return out


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """``parse`` of one xplane file."""
    with open(path, "rb") as f:
        return parse(f.read())


def clock_bracket(runs, chip: int, enqueued: dict,
                  completed: dict) -> tuple[float, float]:
    """``(lo, hi)``: seconds between which ``chip``'s times in the trace
    run ahead of the host's (negative: behind). The runtime handles a
    run's completion after the run ends and enqueues it before it starts,
    both on the host's clock, so the offset is at least every ``end -
    completed`` and at most every ``start - enqueued``. ``lo`` is tight
    wherever one completion was handled at once; ``hi`` only where the
    chip was idle when a run was enqueued (a busy chip queues each run
    behind the one in flight)."""
    lo, hi = float("-inf"), float("inf")
    for _, start, dur, stats in runs:
        key = (chip, stats.get("run_id"))
        if key in completed:
            lo = max(lo, start + dur - completed[key])
        if key in enqueued:
            hi = min(hi, start - enqueued[key])
    return lo, hi


def clock_offset(runs, chip: int, enqueued: dict, completed: dict) -> float:
    """``clock_bracket``'s lower end, 0 where no run's completion is in
    the trace: later than the truth by the quickest completion the
    runtime handled, so a run paired by it never seems to end after its
    read or start before its launch."""
    lo, _ = clock_bracket(runs, chip, enqueued, completed)
    return lo if lo > float("-inf") else 0.0


def on_chip_clock(spans, path: str,
                  chip: int) -> tuple[list, list, tuple[float, float]]:
    """``(spans, runs, (lo, hi))``: the spans moved onto ``chip``'s clock
    by ``clock_offset``; the chip's runs, each with ``done``, the instant
    the runtime handled its completion, moved the same way (its end where
    the trace holds none); and the clocks' bracket."""
    parsed = load(path)
    runs = parsed["runs"].get(chip, [])
    times = (runs, chip, parsed["enqueued"], parsed["completed"])
    offset = clock_offset(*times)
    done = {key: t + offset for key, t in parsed["completed"].items()}
    return ([(s[0], s[1] + offset) + s[2:] for s in spans],
            [r + (done.get((chip, r[3].get("run_id")), r[1] + r[2]),)
             for r in runs],
            clock_bracket(*times))


def _number(stats: dict, key: str) -> int | None:
    value = stats.get(key)
    return value if isinstance(value, int) else None


def reads(spans) -> dict[int, tuple]:
    """``{launch: (the span that reads it, the latest instant its run
    can have ended)}``."""
    out = {}
    wait_end: dict = {}      # thread -> end of its last serve.decode.wait
    drain: dict = {}         # thread -> (start, end) of its last drain
    for span in sorted(spans, key=lambda s: (s[1], -s[2])):
        name, start, dur, stats, thread = span
        if name == "serve.decode.wait":
            wait_end[thread] = start + dur
        elif name == "serve.drain":
            drain[thread] = (start, start + dur)
        n = _number(stats, "read")
        if n is None:
            continue
        lo, hi = drain.get(thread, (0.0, float("-inf")))
        out[n] = (span, hi if lo <= start < hi else wait_end.get(thread))
    return out


def offset(launched, ran, bounds: dict) -> int | None:
    """The one ``d`` at which launch ``i`` pairs with run ``i + d`` under
    the module docstring's two bounds; ``None`` where none or several
    fit."""
    fits = []
    for d in range(1 - len(launched), len(ran)):
        pairs = range(max(0, -d), min(len(launched), len(ran) - d))
        for i in pairs:
            span, (_, start, dur, *_) = launched[i], ran[i + d]
            bound = bounds.get(span[3]["launch"])
            if start < span[1] or (bound is not None and start + dur > bound):
                break
        else:
            if pairs:
                fits.append(d)
    return fits[0] if len(fits) == 1 else None


def pairing(spans, runs) -> dict[str, tuple]:
    """``{program: (offset or None, launch spans, runs, pairs)}`` for
    every program a numbered launch span names."""
    read = reads(spans)
    bounds = {n: bound for n, (_, bound) in read.items()
              if bound is not None}
    launched = defaultdict(list)
    for span in spans:
        if (span[0] in LAUNCH_SPANS and _number(span[3], "launch")
                is not None and isinstance(span[3].get("program"), str)):
            launched[span[3]["program"]].append(span)
    out = {}
    for program, spans_of in launched.items():
        spans_of.sort(key=lambda s: s[3]["launch"])
        ran = sorted((r for r in runs if r[0] == program),
                     key=lambda r: r[1])
        d = offset(spans_of, ran, bounds)
        pairs = [] if d is None else [
            Pair(s, ran[i + d], read.get(s[3]["launch"], (None,))[0])
            for i, s in enumerate(spans_of) if 0 <= i + d < len(ran)]
        out[program] = (d, spans_of, ran, pairs)
    return out


def select(paired: dict, launch: str) -> list[Pair] | None:
    """The pairs whose launch span is named ``launch``; ``None`` where
    there are none, or a program such a span launched was not paired."""
    out = []
    for d, spans_of, _, pairs in paired.values():
        if not any(s[0] == launch for s in spans_of):
            continue
        if d is None:
            return None
        out += [p for p in pairs if p.launch[0] == launch]
    return out or None


def _end(event) -> float:
    return event[1] + event[2]


def device_waits(pairs) -> list[float]:
    return [max(0.0, p.run[1] - _end(p.launch)) for p in pairs]


def read_lags(pairs) -> list[float]:
    return [_end(p.read) - p.run[4] for p in pairs if p.read is not None]


def launch_bound_share(pairs, ops) -> float | None:
    """Runs with no operation before or in them are left out."""
    ops = sorted((e[1], _end(e)) for e in ops)
    starts = [start for start, _ in ops]
    reach = list(itertools.accumulate((end for _, end in ops), max))
    counted = bound = 0
    for p in pairs:
        k = bisect.bisect_left(starts, p.run[1])    # the run's first
        if not 0 < k < len(ops):
            continue
        counted += 1
        bound += starts[k] - reach[k - 1] > trace_reduce.MIN_GAP_S
    return 100.0 * bound / counted if counted else None


def _ms(values, q: float) -> float | None:
    p = metric_math.percentile(values, q)
    return None if p is None else p * 1e3


def value(what: str, pairs, ops, q: float = 50) -> float | None:
    if what == "run_ms":
        return _ms([p.run[2] for p in pairs], q)
    if what == "device_wait_ms":
        return _ms(device_waits(pairs), q)
    if what == "read_lag_ms":
        return _ms(read_lags(pairs), q)
    if what == "launch_bound_share":
        return launch_bound_share(pairs, ops)
    raise ValueError(f"unknown launch_trace metric {what!r}")


def read(args: dict, record: dict, trace: dict | None) -> float | None:
    path = pt.newest_xplane(pt.TRACE_DIR) if trace else None
    if path is None:
        return None
    parsed = pt.load(path)
    if not parsed["ops"]:
        return None
    chip = min(parsed["ops"])
    spans, runs, _ = on_chip_clock(parsed["spans"], path, chip)
    pairs = select(pairing(spans, runs), args["launch"]) if runs else None
    if pairs is None:
        return None
    return value(args["what"], pairs, parsed["ops"][chip], args.get("q", 50))


# -- the table, for a human --------------------------------------------------

#: an admission's time to first token in parts, each from where the one
#: before ends
PARTS = ("queue", "host", "wait", "run", "done", "lag")


def admissions(spans, pairs) -> list[dict]:
    """Each paired admission's time to first token in parts (seconds):
    ``queue`` (its ``serve.prefill``'s ``queue_wait_s``: due to made by
    the scheduler), ``host`` (its launch's ``since_admit_s``: made to
    dispatched), ``wait`` (run start - launch end, signed, so that the
    parts add up), ``run``, ``done`` (run end to the runtime's handling of
    its completion), ``lag`` (that to the end of its commit); their
    ``sum`` and the engine's own ``ttft_s``. ``wait`` and ``done`` are
    set across the clocks, in opposite directions, and their sum is not;
    launches from before ``since_admit_s`` give no row."""
    by_launch = {p.launch[3]["launch"]: p for p in pairs}
    prefill: dict = {}       # thread -> its last serve.prefill
    out = []
    for span in sorted(spans, key=lambda s: (s[1], -s[2])):
        name, _, _, stats, thread = span
        if name == "serve.prefill":
            prefill[thread] = span
            continue
        p = by_launch.get(_number(stats, "launch"))
        parent = prefill.get(thread)
        if (name != "serve.prefill.launch" or p is None or p.read is None
                or parent is None or "since_admit_s" not in stats):
            continue
        row = {"id": parent[3].get("id"), "program": stats["program"],
               "queue": parent[3].get("queue_wait_s") or 0.0,
               "host": stats["since_admit_s"],
               "wait": p.run[1] - _end(span), "run": p.run[2],
               "done": p.run[4] - _end(p.run),
               "lag": _end(p.read) - p.run[4],
               "ttft": p.read[3].get("ttft_s")}
        row["sum"] = sum(row[k] for k in PARTS)
        out.append(row)
    return out


def table(path: str) -> None:
    parsed = pt.load(path)
    print(f"trace {path}")
    if not parsed["ops"]:
        print("no device plane")
        return
    chip = min(parsed["ops"])
    spans, runs, (lo, hi) = on_chip_clock(parsed["spans"], path, chip)
    paired = pairing(spans, runs)
    print(f"\nchip {chip}'s clock runs {lo * 1e3:+.3f} to {hi * 1e3:+.3f} ms "
          f"ahead of the host's; the spans are moved by the first, so device "
          f"wait is high by up to {(hi - lo) * 1e3:.3f} ms; programs, their "
          f"launches, runs and pairs at the offset found; device wait, run "
          f"and read lag in ms, p50 / p90")
    for program, (d, spans_of, ran, pairs) in sorted(paired.items()):
        cols = [f"{program:10s} {len(spans_of):6d} {len(ran):6d} "
                f"{len(pairs):6d} offset {'none' if d is None else d:>4}"]
        for label, values in (("wait", device_waits(pairs)),
                              ("run", [p.run[2] for p in pairs]),
                              ("lag", read_lags(pairs))):
            p50, p90 = _ms(values, 50), _ms(values, 90)
            cols.append(f"{label} " + (" -" if p50 is None else
                                       f"{p50:.3f} / {p90:.3f}"))
        print("  " + "  ".join(cols))
    for launch in ("serve.decode.launch", "serve.prefill.launch"):
        pairs = select(paired, launch)
        if pairs:
            share = launch_bound_share(pairs, parsed["ops"][chip])
            print(f"  {launch}: launch-bound runs "
                  f"{'-' if share is None else f'{share:.2f}%'}")
    rows = admissions(spans, select(paired, "serve.prefill.launch") or [])
    if not rows:
        return
    print("\nadmissions (ms): queue wait + host (made to dispatched) + "
          "device wait + run + completion + read lag = sum, beside ttft_s "
          "(ttft_s - sum)")
    agree = 0
    for r in rows:
        ttft = r["ttft"]
        off = None if ttft is None else ttft - r["sum"]
        agree += off is not None and abs(off) <= 1e-3
        print(f"  {str(r['id']):14s} {r['program']:8s} "
              + " ".join(f"{r[k] * 1e3:9.3f}" for k in PARTS + ("sum",))
              + (" ttft -" if ttft is None else
                 f" ttft {ttft * 1e3:9.3f} ({off * 1e3:+.3f})"))
    print(f"  {agree} of {len(rows)} sums within 1 ms of ttft_s")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m benchmark.readers.launch_trace <trace dir>",
              file=sys.stderr)
        return 2
    path = pt.newest_xplane(argv[0])
    if path is None:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    table(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
