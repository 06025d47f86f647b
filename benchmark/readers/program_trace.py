"""Metrics read from the program's own spans and names in the profiler's
trace: ``telemetry.span`` is a trace annotation, so the spans lie in the
xplane's host planes on the clock of the device planes, with their
counts as stats; the kernels carry the ``name=`` of their
``pallas_call`` and every operation the ``jax.named_scope`` it was
traced under.

The summary a reader is handed keeps only the benchmark's own host spans
and no path, so this reader opens the run's xplane itself: the newest
``*.xplane.pb`` under ``.cache/bench_trace/`` of the checkout, and only
where the harness has a summary of its own (a traced run that wrote no
xplane reads nothing, not another cell's stale file). It maps the file
onto plain tuples (``load``, once per process) and the arithmetic works
on those, with ``trace_reduce``'s busy union, gap attribution and
operation labels.

``what`` is

- ``span_ms``: the ``q``-th percentile of the durations of the spans
  named ``span`` that lie inside the traced slice (from the first to the
  last operation on chip 0);
- ``gap_ms_per_span``: idle time on chip 0 (gaps of the busy union over
  ``trace_reduce.MIN_GAP_S``) whose middle lies inside such a span, per
  such span;
- ``scope_ms_per_span``: device time on chip 0 of the operations whose
  scope contains one of ``match`` and that start inside a span named
  ``per`` in the slice, per such span (the same spans above and below
  the line); where ``per`` is absent, of all such operations per run of
  the main program;
- ``kernel_roofline``: ``100 * flops_per_step_per_chip / (peak per chip *
  seconds per step of the kernels whose label contains one of match)``.

Every one returns ``None`` where the harness has no trace summary, where
the trace has no such span, scope or kernel (a program from before they
were named); all but ``span_ms`` where it has no device plane; the last
two where the main program never ran.

As a command, ``python -m benchmark.readers.program_trace <trace dir>``
prints the table PERF.md section 5 is written from.
"""

from __future__ import annotations

import functools
import os
import re
import struct
import sys
from collections import defaultdict

from benchmark import metric_math, trace_reduce

#: where the harness leaves a traced run's xplane, ``<cell>/`` below it
TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "bench_trace")
#: a span of ``telemetry.span``: a dotted lower-case name; the
#: benchmark's own ``bench.*`` annotations are not the program's
PROGRAM_SPAN = re.compile(r"^(?!bench\.)[a-z_]+(\.[a-z_]+)+$")
#: the stat of a device event's METADATA that holds the scope its
#: operation was traced under, ``jit(decode)/kv.gather/gather:`` (found
#: on the v5e, PR 26; the compiler's own copies carry none)
SCOPE_STAT = "tf_op"
NO_SCOPE = "_none_"


# -- from the file to tuples -------------------------------------------------
# ``jax.profiler.ProfileData`` shows an event's own stats and not those
# of its metadata, where the TPU keeps the scope, and names an event
# without its metadata's id, so two instructions with one text (in two
# programs) could not be told apart. So the file is walked once, here,
# in the protobuf wire format of ``xplane.proto``:
# ``XSpace.planes`` (1); ``XPlane.name`` (2), ``.lines`` (3),
# ``.event_metadata`` (4) and ``.stat_metadata`` (5), both maps of id
# (1) to message (2); ``XLine.id`` (1), ``.name`` (2), ``.timestamp_ns``
# (3), ``.events`` (4); ``XEvent.metadata_id`` (1), ``.offset_ps`` (2),
# ``.duration_ps`` (3), ``.stats`` (4); ``XEventMetadata.name`` (2),
# ``.stats`` (5); ``XStatMetadata.name`` (2); ``XStat.metadata_id`` (1)
# and its value: double (2), uint64 (3), int64 (4), string (5), bytes
# (6), or ``ref_value`` (7), the id of a stat metadata whose name is
# the value. A field left out holds 0.

def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: a varint as an
    ``int``, any other field as a slice of ``buf``."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _stat(buf, stat_names: dict) -> tuple[str, object]:
    """``(name, value)`` of one ``XStat``."""
    name, value = "", 0
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, "")
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif f in (5, 6):
            value = _text(v)
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _events(line, wanted: dict):
    """``(metadata id, start_s, dur_s, stats)`` of the events of one
    ``XLine`` whose metadata id is in ``wanted``, the stats undecoded."""
    t0_ns, events = 0, []
    for f, v in _fields(line):
        if f == 3:
            t0_ns = v
        elif f == 4:
            events.append(v)
    for event in events:
        meta = offset_ps = dur_ps = 0
        stats = []
        for f, v in _fields(event):
            if f == 1:
                meta = v
            elif f == 2:
                offset_ps = v
            elif f == 3:
                dur_ps = v
            elif f == 4:
                stats.append(v)
        if meta in wanted:
            yield (meta, (t0_ns + offset_ps * 1e-3) * 1e-9, dur_ps * 1e-12,
                   stats)


def parse(data: bytes) -> dict:
    """``{"spans": [(name, start_s, dur_s, stats, thread)], "ops":
    {chip: [(name, start_s, dur_s, scope)]}}`` from the bytes of an
    ``XSpace``: the program's spans of every host plane, a thread being
    a line's id, and the operations of every device plane, each with
    the scope of its own metadata."""
    spans: list[tuple] = []
    ops: dict[int, list] = {}
    for field, plane in _fields(memoryview(data)):
        if field != 1:
            continue
        plane_name, lines, stat_names, metadata = "", [], {}, {}
        for f, value in _fields(plane):
            if f == 2:
                plane_name = _text(value)
            elif f == 3:
                lines.append(value)
            elif f in (4, 5):
                entry = dict(_fields(value))
                (metadata if f == 4 else stat_names)[entry.get(1, 0)] = (
                    entry.get(2, b""))
        stat_names = {k: _text(dict(_fields(v)).get(2, b""))
                      for k, v in stat_names.items()}
        chip = trace_reduce.DEVICE_PLANE.match(plane_name)
        if not chip and not plane_name.startswith("/host:"):
            continue
        named = {}          # metadata id -> (event name, scope)
        for key, meta in metadata.items():
            name, scope = "", ""
            for f, v in _fields(meta):
                if f == 2:
                    name = _text(v)
                elif f == 5 and chip:
                    stat, value = _stat(v, stat_names)
                    if stat == SCOPE_STAT:
                        scope = str(value).rstrip(":")
            if chip or PROGRAM_SPAN.match(name):
                named[key] = (name, scope)
        for line in lines:
            head = dict((f, v) for f, v in _fields(line) if f in (1, 2))
            if chip:
                if _text(head.get(2, b"")) == trace_reduce.OPS_LINE:
                    ops[int(chip[1])] = [
                        (named[meta][0], start, dur, named[meta][1])
                        for meta, start, dur, _ in _events(line, named)]
            else:
                spans.extend(
                    (named[meta][0], start, dur,
                     dict(_stat(st, stat_names) for st in stats),
                     head.get(1, 0))
                    for meta, start, dur, stats in _events(line, named))
    return {"spans": sorted(spans, key=lambda s: s[1]), "ops": ops}


def newest_xplane(directory: str) -> str | None:
    found = [os.path.join(base, name)
             for base, _, files in os.walk(directory)
             for name in files if name.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime, default=None)


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """``parse`` of one xplane file."""
    with open(path, "rb") as f:
        return parse(f.read())


# -- arithmetic on tuples ----------------------------------------------------

def _plain(events) -> list[tuple]:
    return [(e[0], e[1], e[2]) for e in events]


def in_slice(spans, ops, name: str | None = None) -> list[tuple]:
    """The spans (named ``name``) that lie inside the traced slice: from
    the first operation's start to the last one's end, and the whole
    trace where no operation ran on a device."""
    lo = min((e[1] for e in ops), default=float("-inf"))
    hi = max((e[1] + e[2] for e in ops), default=float("inf"))
    return [s for s in spans if (name is None or s[0] == name)
            and lo <= s[1] and s[1] + s[2] <= hi]


def span_ms(spans, ops, name: str, q: float) -> float | None:
    p = metric_math.percentile(
        [s[2] for s in in_slice(spans, ops, name)], q)
    return None if p is None else p * 1e3


def gap_ms_per_span(spans, ops, name: str) -> float | None:
    named = in_slice(spans, ops, name)
    if not named or not ops:
        return None
    gaps = trace_reduce.gap_attribution(_plain(ops), _plain(named))
    return gaps.get(name, 0.0) / len(named) * 1e3


def scope_ms_per_span(spans, ops, match, per: str | None,
                      main_runs: int) -> float | None:
    """The first ``per`` span of a trace opens before the chip's first
    operation and the last closes after its last, so neither lies in
    the slice: their operations are left out with them."""
    scoped = [e for e in ops if any(m in e[3] for m in match)]
    n = main_runs
    if per:
        inside = in_slice(spans, ops, per)
        n = len(inside)
        scoped = [e for e in scoped
                  if any(s[1] <= e[1] < s[1] + s[2] for s in inside)]
    seconds = sum(e[2] for e in scoped)
    if not n or not seconds:        # a program without that scope
        return None
    return seconds / n * 1e3


#: an event's name is its whole HLO instruction and a trace repeats a
#: few thousand of them some hundred thousand times
op_label = functools.lru_cache(maxsize=None)(trace_reduce.op_name)


def kernel_roofline(ops, match, flops_per_step: float,
                    peak_flops_per_chip: float,
                    main_runs: int) -> float | None:
    seconds = sum(e[2] for e in ops
                  if any(m in op_label(e[0]) for m in match))
    if not main_runs or not seconds or not peak_flops_per_chip:
        return None
    return 100.0 * flops_per_step / (peak_flops_per_chip
                                     * seconds / main_runs)


def self_times(spans) -> dict[str, list]:
    """``{name: [count, total_s, self_s, counts]}``: a span's self time
    is its duration minus the part its children cover, a child being a
    span that starts and ends inside it on its thread. ``counts`` sums
    each numeric stat and says how often each value of a label (a stat
    that is a string: ``program:extend``) came; ``step``, ``id`` and
    ``span_id`` say which span it is and are left out."""
    out: dict[str, list] = {}

    def close(top):
        _, name, dur, covered, stats = top
        row = out.setdefault(name, [0, 0.0, 0.0, defaultdict(float)])
        row[0] += 1
        row[1] += dur
        row[2] += dur - covered
        for k, v in stats.items():
            if k in ("step", "id", "span_id"):
                continue
            if isinstance(v, str):
                row[3][f"{k}:{v}"] += 1
            elif isinstance(v, (int, float)):
                row[3][k] += v

    stacks: dict = defaultdict(list)    # thread -> open spans
    for name, start, dur, stats, thread in sorted(
            spans, key=lambda s: (s[1], -s[2])):
        stack = stacks[thread]
        while stack and start >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][3] += dur
        stack.append([start + dur, name, dur, 0.0, stats])
    for stack in stacks.values():
        while stack:
            close(stack.pop())
    return {k: [v[0], v[1], v[2], dict(v[3])] for k, v in out.items()}


_NAMED = re.compile(r"[A-Za-z_][\w.]*")


def scope_label(scope: str) -> str:
    """The innermost part of a scope path that somebody named:
    ``jit(decode)/attn/bhqk,bhkd->bhqd/dot_general`` -> ``attn``,
    ``jit(step)/jvp(TransformerLM)/layer_0/attn/rotary/mul`` ->
    ``rotary``. The last part is the primitive; a transformation's
    wrapper ``f(...)``, an einsum's own subscripts and an argument's
    path are not names. Where nothing is named, the first part (the
    program, or the argument), and ``_none_`` for no scope at all."""
    parts = [p for p in scope.split("/") if p]
    for part in reversed(parts[:-1]):
        if _NAMED.fullmatch(part):
            return part
    return parts[0] if parts else NO_SCOPE


def scope_totals(ops) -> dict[str, float]:
    """Device seconds by ``scope_label``."""
    totals: dict[str, float] = defaultdict(float)
    for _, _, dur, scope in ops:
        totals[scope_label(scope)] += dur
    return dict(totals)


# -- the reader --------------------------------------------------------------

def read(args: dict, record: dict, trace: dict | None) -> float | None:
    path = newest_xplane(TRACE_DIR) if trace else None
    if path is None:
        return None
    parsed = load(path)
    spans = parsed["spans"]
    ops = parsed["ops"][min(parsed["ops"])] if parsed["ops"] else []
    what = args["what"]
    if what == "span_ms":
        return span_ms(spans, ops, args["span"], args["q"])
    if not ops:
        return None
    main_runs = len(trace.get("main_program_s") or [])
    if what == "gap_ms_per_span":
        return gap_ms_per_span(spans, ops, args["span"])
    if what == "scope_ms_per_span":
        return scope_ms_per_span(spans, ops, args["match"],
                                 args.get("per"), main_runs)
    if what == "kernel_roofline":
        peak = record.get("peak_flops")
        return kernel_roofline(
            ops, args["match"], args["flops_per_step_per_chip"],
            peak / trace["chips"] if peak else 0.0, main_runs)
    raise ValueError(f"unknown program_trace metric {what!r}")


# -- the table, for a human --------------------------------------------------

def table(path: str) -> None:
    parsed = load(path)
    spans = parsed["spans"]
    print(f"trace {path}")
    print("\nprogram spans (count, total ms, self ms, counts summed and "
          "labels by value)")
    for name, (n, total, own, counts) in sorted(
            self_times(spans).items(), key=lambda kv: -kv[1][1]):
        shown = " ".join(f"{k}={v:g}" for k, v in sorted(counts.items()))
        print(f"  {name:26s} {n:6d} {total * 1e3:11.3f} {own * 1e3:11.3f}"
              f"  {shown}")
    if not parsed["ops"]:
        print("\nno device plane")
        return
    ops = parsed["ops"][min(parsed["ops"])]
    busy, window = trace_reduce.busy_and_window(_plain(ops))
    print(f"\nchip {min(parsed['ops'])}: busy {busy:.4f} s of "
          f"{window:.4f} s, idle {100 * (1 - busy / window):.2f}%; idle "
          f"gaps over {trace_reduce.MIN_GAP_S * 1e6:.0f} us by innermost "
          f"program span (ms: each gap split over the spans it crosses, "
          f"and whole to the span at its middle)")
    split = trace_reduce.gap_shares(_plain(ops), spans)
    whole = trace_reduce.gap_attribution(_plain(ops), _plain(spans))
    for name, s in trace_reduce.top(split, 20):
        print(f"  {name:26s} {s * 1e3:11.3f} "
              f"{whole.get(name, 0.0) * 1e3:11.3f}")
    print("\ndevice time by scope (ms, share of busy)")
    for name, s in trace_reduce.top(scope_totals(ops), 25):
        print(f"  {name:40s} {s * 1e3:11.3f} {100 * s / busy:6.2f}%")
    print("\ndevice time by kernel or operation, and its scope (ms, "
          "share of busy)")
    by_op: dict[str, float] = defaultdict(float)
    for name, _, dur, scope in ops:
        by_op[f"{op_label(name):40s} "
              f"{scope_label(scope)}"] += dur
    for name, s in trace_reduce.top(by_op, 30):
        print(f"  {name:64s} {s * 1e3:11.3f} {100 * s / busy:6.2f}%")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m benchmark.readers.program_trace "
              "<trace dir>", file=sys.stderr)
        return 2
    path = newest_xplane(argv[0])
    if path is None:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    table(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
