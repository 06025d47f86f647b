"""From the profiler's xplane file to numbers: the busy union and idle
share of each chip, the device time of each operation, the runs of each
compiled program, and each long idle gap split over the host spans that
cover it: the program's own (``telemetry.span``: ``serve.decode.wait``)
and the benchmark's (``bench.wait_request``).

The arithmetic works on plain ``(name, start_s, duration_s)`` tuples so
that it can be checked on a hand-written event list; ``reduce_xplane``
only maps the file onto them (``jax.profiler.ProfileData``, nothing but
JAX).
"""

from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
#: a span somebody named: dotted lower case, which is what the program's
#: ``telemetry.span`` names and the benchmark's ``bench.*`` annotations
#: are and the runtime's own host events are not
HOST_SPAN = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
NO_SPAN = "_none_"
OTHER = "_other_"        # what a list cut to its longest entries left out
MIN_GAP_S = 20e-6        # shorter gaps are launch latency, not waiting


def busy_union(events) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals in which any event ran."""
    merged: list[list[float]] = []
    for start, end in sorted((s, s + d) for _, s, d in events):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_and_window(events) -> tuple[float, float]:
    """``(busy_s, window_s)``: the union's length, and the span from the
    first event's start to the last event's end."""
    union = busy_union(events)
    if not union:
        return 0.0, 0.0
    return (sum(b - a for a, b in union), union[-1][1] - union[0][0])


_INSTRUCTION = re.compile(
    r"^%(?P<name>\S+) = (?P<shape>.*?) (?P<opcode>[a-z][\w\-]*)\(")
_ARRAY = re.compile(r"([a-z]+\d+)\[([\d,]*)\]")
_KIND = re.compile(r"kind=(\w+)")
MOSAIC = "mosaic:"


def _numbered(name: str) -> str:
    """``fusion.123`` -> ``fusion``: XLA's instance number off."""
    return re.sub(r"[._]\d+$", "", name)


def op_name(event_name: str) -> str:
    """A short label under which the same work in every layer adds up.
    On the TPU an event is named by its whole HLO instruction:
    ``%fusion.329 = (f32[1024,8192]{...}, ...) fusion(...), kind=kOutput``
    becomes ``fusion:kOutput_f32_1024_8192`` (opcode, fusion kind, first
    output); a Pallas kernel (``custom_call_target="tpu_custom_call"``)
    becomes ``mosaic:`` and its instruction's name, which is all the
    program gives its kernels today. Any other name only loses its
    instance number."""
    m = _INSTRUCTION.match(event_name)
    if not m:
        return _numbered(event_name)
    if 'custom_call_target="tpu_custom_call"' in event_name:
        return MOSAIC + _numbered(m["name"])
    label = m["opcode"]
    kind = _KIND.search(event_name) if label == "fusion" else None
    if kind:
        label += ":" + kind[1]
    array = _ARRAY.search(m["shape"])
    if array:
        label += "_" + array[1] + "".join(
            "_" + d for d in array[2].split(",") if d)
    return label


def op_totals(events) -> dict[str, float]:
    totals: dict[str, float] = defaultdict(float)
    for name, _, dur in events:
        totals[op_name(name)] += dur
    return dict(totals)


def gap_attribution(events, host_spans, min_gap_s: float = MIN_GAP_S
                    ) -> dict[str, float]:
    """Idle seconds between busy intervals, by the host span that
    covers the middle of each gap (the innermost, where spans nest);
    ``_none_`` where the benchmark had no span open."""
    union = busy_union(events)
    out: dict[str, float] = defaultdict(float)
    for (_, gap_start), (gap_end, _) in zip(union, union[1:]):
        gap = gap_end - gap_start
        if gap < min_gap_s:
            continue
        mid = gap_start + gap / 2
        covering = [(d, n) for n, s, d in host_spans if s <= mid <= s + d]
        out[min(covering)[1] if covering else NO_SPAN] += gap
    return dict(out)


def gap_shares(events, host_spans, min_gap_s: float = MIN_GAP_S
               ) -> dict[str, float]:
    """Idle seconds between busy intervals, each gap cut where a span
    starts or ends and every piece given to the innermost span that
    covers it (``gap_attribution`` gives a whole gap to the span at its
    middle, which is what a metric per span wants; this says what the
    host was doing all through it, and sums to the same seconds). Of a
    span only its first three fields are read: name, start, seconds."""
    union = busy_union(events)
    out: dict[str, float] = defaultdict(float)
    for (_, lo), (hi, _) in zip(union, union[1:]):
        if hi - lo < min_gap_s:
            continue
        near = [s for s in host_spans if s[1] < hi and s[1] + s[2] > lo]
        cuts = sorted({lo, hi} | {t for s in near
                                  for t in (s[1], s[1] + s[2])
                                  if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            over = [(s[2], s[0]) for s in near
                    if s[1] <= mid <= s[1] + s[2]]
            out[min(over)[1] if over else NO_SPAN] += b - a
    return dict(out)


def top(totals: dict[str, float], n: int = 10,
        rest: str | None = None) -> list[list]:
    """The ``n`` largest entries; with ``rest``, where there are more,
    the ``n - 1`` largest and the sum of the others under that name, so
    that the list still sums to the whole."""
    ranked = [[k, v] for k, v in sorted(totals.items(),
                                        key=lambda kv: -kv[1])]
    if rest is None or len(ranked) <= n:
        return ranked[:n]
    return ranked[:n - 1] + [[rest, sum(v for _, v in ranked[n - 1:])]]


def summarize(ops_by_chip: dict[int, list], programs: list,
              host_spans: list) -> dict | None:
    """The trace summary the readers and the last line take their
    numbers from. ``ops_by_chip`` maps a chip to its operation events,
    ``programs`` are the first chip's compiled-program runs."""
    ops_by_chip = {c: ev for c, ev in ops_by_chip.items() if ev}
    if not ops_by_chip:
        return None
    pairs = [busy_and_window(ev) for ev in ops_by_chip.values()]
    first = ops_by_chip[min(ops_by_chip)]
    by_program: dict[str, list[float]] = defaultdict(list)
    for name, _, dur in programs:
        by_program[_numbered(name.split("(")[0])].append(dur)
    main = max(by_program.values(), key=sum, default=[])
    totals = op_totals(first)
    return {
        "chips": len(pairs),
        "busy_s": sum(b for b, _ in pairs) / len(pairs),
        "window_s": sum(w for _, w in pairs) / len(pairs),
        "op_totals": totals,
        "main_program_s": main,
        "programs": {k: [len(v), sum(v)] for k, v in by_program.items()},
        "device_ops": top(totals),
        "idle_gaps": top(gap_shares(first, host_spans), rest=OTHER),
    }


def reduce_xplane(path: str) -> dict | None:
    """``summarize`` over an ``.xplane.pb`` file; ``None`` where no
    operation ran on a device."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops_by_chip: dict[int, list] = {}
    programs_by_chip: dict[int, list] = {}
    host_spans: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops_by_chip[chip] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
                elif line.name == PROGRAMS_LINE:
                    programs_by_chip[chip] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events
                    if HOST_SPAN.match(e.name))
    first = min(programs_by_chip, default=None)
    return summarize(ops_by_chip, programs_by_chip.get(first, []),
                     host_spans)
