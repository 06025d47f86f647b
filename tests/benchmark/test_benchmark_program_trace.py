"""``benchmark/readers/program_trace.py``: its four ``what``s on
hand-written event lists, its table, and the data-driven claim once
more: a ``program_span`` metric added as files alone is read in the
rehearsal's traced CPU run."""

import io
import json
import os
import struct
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_benchmark_rehearsal as rehearsal  # noqa: E402
from benchmark import harness, trace_reduce  # noqa: E402
from benchmark.readers import program_trace as pt  # noqa: E402

MS = 1e-3
FLASH = ('%flash_fwd.3 = bf16[128,1024,64]{2,1,0} custom-call(%a, %b), '
         'custom_call_target="tpu_custom_call"')
FUSION = ('%fusion.7 = bf16[8,1024]{1,0} fusion(%p), kind=kLoop, '
          'metadata={op_name="jit(decode)/kv.gather/gather"}')


def span(name, start_ms, dur_ms, thread="main", **stats):
    return (name, start_ms * MS, dur_ms * MS, stats, thread)


def op(start_ms, dur_ms, scope="", name="%copy.1 = bf16[4]{0} copy(%x)"):
    return (name, start_ms * MS, dur_ms * MS, scope)


# Two steps of 10 ms on one thread. The chip is busy 0.3-4 and 6-9 in the
# first, 12-14 and 19.5-21 in the second: gaps of 2 ms (middle 5, inside
# step 1), 3 ms (middle 10.5: it straddles the edge between the steps
# and its middle lies in step 2) and 5.5 ms (middle 16.75, in step 2's
# decode.wait).
SPANS = [
    span("serve.step", 0.5, 9.5, step=0, admitted=1, id="r1",
         span_id="req/r1"),
    span("serve.schedule", 0.6, 0.4, admitted=1, cached_tokens=16),
    span("serve.decode", 4.5, 5.0, live=2, program="decode"),
    span("serve.decode.wait", 5.0, 4.0),
    span("serve.step", 10.0, 10.5, step=1, admitted=0),
    span("serve.decode", 11.0, 9.0, live=2, program="decode"),
    span("serve.decode.wait", 14.5, 5.0),
    span("serve.step", 20.6, 3.0, step=2),      # ends after the last op
    span("checkpoint.save", 2.0, 30.0, thread="writer"),
]
OPS = [
    op(0.3, 3.7, "jit(decode)/kv.write/scatter"),
    op(6.0, 3.0, "jit(decode)/kv.gather/gather"),
    op(12.0, 2.0, "jit(decode)/jit(main)/kv.gather/convert_element_type"),
    op(19.5, 1.5),                               # no scope at all
]


@pytest.mark.parametrize("what,args,expected", [
    # nested spans: the two whole steps, not their children, not the
    # third step that outlives the slice
    ("span_ms", {"span": "serve.step", "q": 50}, 10.0),
    ("span_ms", {"span": "serve.decode.wait", "q": 100}, 5.0),
    ("span_ms", {"span": "serve.prefill", "q": 50}, None),
    # the straddling gap goes to the step its middle lies in: 2 ms in
    # step 1, 3 + 5.5 in step 2, over two steps
    ("gap_ms_per_span", {"span": "serve.step"}, (2.0 + 3.0 + 5.5) / 2),
    ("gap_ms_per_span", {"span": "serve.decode.wait"}, (2.0 + 5.5) / 2),
    ("gap_ms_per_span", {"span": "serve.schedule"}, 0.0),
    # the operation with no scope counts under no match
    ("scope_ms_per_span", {"match": ["kv.gather"], "per": "serve.step"},
     (3.0 + 2.0) / 2),
    ("scope_ms_per_span", {"match": ["kv.write", "kv.gather"]},
     (3.7 + 3.0 + 2.0) / 4),                    # per main-program run
    ("scope_ms_per_span", {"match": ["rotary"], "per": "serve.step"}, None),
    ("scope_ms_per_span", {"match": ["kv."], "per": "serve.prefill"}, None),
])
def test_whats_on_a_hand_written_trace(what, args, expected):
    if what == "span_ms":
        got = pt.span_ms(SPANS, OPS, args["span"], args["q"])
    elif what == "gap_ms_per_span":
        got = pt.gap_ms_per_span(SPANS, OPS, args["span"])
    else:
        got = pt.scope_ms_per_span(SPANS, OPS, args["match"],
                                   args.get("per"), main_runs=4)
    assert got == (None if expected is None else pytest.approx(expected))


def test_scope_time_and_its_divisor_cover_the_same_steps():
    """Four steps of 10 ms with 4 ms of gather each. As in every real
    trace the first step opens before the chip's first operation and
    the last closes after its last one, so both lie outside the slice;
    their operations are left out with them, and so is one that starts
    between two steps: 4 ms a step, not 16 ms over two steps."""
    spans = [span("serve.step", 10 * i, 9.5, step=i) for i in range(4)]
    ops = [op(10 * i + 1, 4.0, "jit(decode)/kv.gather/gather")
           for i in range(4)]
    assert len(pt.in_slice(spans, ops, "serve.step")) == 2
    for extra in ([], [op(19.6, 0.3, "jit(prefill)/kv.gather/gather")]):
        assert pt.scope_ms_per_span(
            spans, ops + extra, ["kv.gather"], "serve.step",
            main_runs=0) == pytest.approx(4.0)
    # per run of the main program the whole line is divided: its runs
    # are counted over the same line
    assert pt.scope_ms_per_span(spans, ops, ["kv.gather"], None,
                                main_runs=4) == pytest.approx(4.0)


@pytest.mark.parametrize("chips,peak,expected", [
    # 2 kernels of 1.5 ms on chip 0 in 2 steps: 3e9 flop in 1.5 ms of a
    # 4e12 flop/s chip is half its peak; the same kernels on a second
    # chip change nothing: chip 0's time against one chip's peak
    (1, 4e12, 50.0), (2, 8e12, 50.0)])
def test_kernel_roofline_reads_chip_0_against_one_chips_peak(
        chips, peak, expected, monkeypatch):
    kernels = [op(1.0, 1.5, name=FLASH), op(5.0, 1.5, name=FLASH),
               op(7.0, 9.0, name=FUSION)]
    ops = {chip: kernels for chip in range(chips)}
    monkeypatch.setattr(pt, "newest_xplane", lambda directory: "hand.pb")
    monkeypatch.setattr(pt, "load",
                        lambda path: {"spans": [], "ops": ops})
    trace = {"window_s": 1.0, "chips": chips, "main_program_s": [0.1, 0.1]}
    args = {"what": "kernel_roofline", "match": ["flash_"],
            "flops_per_step_per_chip": 3e9}
    assert pt.read(args, {"peak_flops": peak}, trace) \
        == pytest.approx(expected)
    # nothing by that name, no run of the main program, no device plane
    assert pt.read(dict(args, match=["fused_ce_"]),
                   {"peak_flops": peak}, trace) is None
    assert pt.read(args, {"peak_flops": peak},
                   dict(trace, main_program_s=[])) is None
    with pytest.raises(ValueError):
        pt.read({"what": "nonsense"}, {}, trace)
    monkeypatch.setattr(pt, "load", lambda path: {"spans": [], "ops": {}})
    assert pt.read(args, {"peak_flops": peak}, trace) is None
    # no summary from the harness (the run wrote no xplane): nothing is
    # read, a span's durations neither, whatever file lies about
    monkeypatch.setattr(pt, "load", lambda path: {"spans": SPANS,
                                                  "ops": {0: OPS}})
    step = {"what": "span_ms", "span": "serve.step", "q": 50}
    assert pt.read(step, {}, trace) == pytest.approx(10.0)
    assert pt.read(step, {}, None) is None


def _message(*fields) -> bytes:
    """A protobuf message from ``(number, int | float | bytes | str)``
    pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value % (1 << 64))
        elif isinstance(value, float):
            out += varint(number << 3 | 1) + struct.pack("<d", value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_the_files_bytes_become_spans_and_operations_with_their_scope():
    """An XSpace by hand. The device plane's first event metadata
    carries ``tf_op`` as a string, the second as a reference to a stat
    metadata's name, the third no scope, and the fourth is the first's
    instruction again in another program, under another scope: the
    scope is the metadata's, found by its id and not by the event's
    name. 300 is a two-byte varint; an id or offset of 0 is left out.
    The host plane has one program span with a stat of every kind and
    one event that is not the program's."""
    def stat_meta(key, name):
        return (5, _message((1, key), (2, _message((1, key), (2, name)))))

    def event_meta(key, name, *stats):
        return (4, _message((1, key), (2, _message(
            (1, key), (2, name), *[(5, st) for st in stats]))))

    def event(key, offset_ps, dur_ps, *stats):
        return (4, _message((1, key), (2, offset_ps), (3, dur_ps),
                            *[(4, st) for st in stats]))

    mul = "%mul.2 = f32[4]{0} multiply(%a, %b)"
    copy = "%copy.256 = bf16[4]{0} copy(%pool)"
    device = _message(
        (1, 7), (2, "/device:TPU:0"),
        (3, _message((1, 1), (2, "XLA Ops"), (3, 1000),
                     event(300, 5_000_000, 2_000_000),
                     event(4, 8_000_000, 1_000_000),
                     event(5, 9_000_000, 500_000),
                     event(6, 0, 3_000_000))),
        (3, _message((1, 2), (2, "XLA Modules"), (3, 1000),
                     event(300, 0, 9_000_000))),
        stat_meta(300, "tf_op"), stat_meta(2, "flops"),
        stat_meta(9, "jit(decode)/rotary/mul:"),
        event_meta(300, FUSION, _message((1, 2), (3, 17)),
                   _message((1, 300), (5, "jit(decode)/kv.gather/gather:"))),
        event_meta(4, mul, _message((1, 300), (7, 9))),
        event_meta(5, copy, _message((1, 2), (3, 0))),
        event_meta(6, FUSION,
                   _message((1, 300), (5, "jit(prefill)/attn/gather:"))))
    host = _message(
        (2, "/host:CPU"),
        (3, _message((1, 41), (2, "python3"), (3, 1000),
                     event(1, 1_000_000, 4_000_000,
                           _message((1, 1), (4, 3)),
                           _message((1, 2), (4, -2)),
                           _message((1, 3), (2, 0.25)),
                           _message((1, 4), (5, "extend")),
                           _message((1, 5), (7, 6)),
                           _message((1, 7), (3, 2**63 + 1))),
                     event(2, 0, 9_000_000))),
        stat_meta(1, "admitted"), stat_meta(2, "delta"),
        stat_meta(3, "queue_wait_s"), stat_meta(4, "program"),
        stat_meta(5, "cause"), stat_meta(6, "blocks"), stat_meta(7, "id"),
        event_meta(1, "serve.prefill"), event_meta(2, "PjitFunction(decode)"))
    space = _message((1, device), (1, host), (4, "a hostname"),
                     (1, _message((2, "/host:metadata"))))
    us = 1e-6
    parsed = pt.parse(space)
    assert parsed["ops"] == {0: [
        (FUSION, pytest.approx(6 * us), pytest.approx(2 * us),
         "jit(decode)/kv.gather/gather"),
        (mul, pytest.approx(9 * us), pytest.approx(1 * us),
         "jit(decode)/rotary/mul"),
        (copy, pytest.approx(10 * us), pytest.approx(0.5 * us), ""),
        (FUSION, pytest.approx(1 * us), pytest.approx(3 * us),
         "jit(prefill)/attn/gather")]}
    assert parsed["spans"] == [
        ("serve.prefill", pytest.approx(2 * us), pytest.approx(4 * us),
         {"admitted": 3, "delta": -2, "queue_wait_s": 0.25,
          "program": "extend", "cause": "blocks", "id": 2**63 + 1}, 41)]
    assert pt.parse(b"") == {"spans": [], "ops": {}}
    assert trace_reduce.op_name(FLASH) == "mosaic:flash_fwd"
    for scope, label in [
            ("jit(decode)/attn/bhqk,bhkd->bhqd/dot_general", "attn"),
            ("jit(step)/jit(main)/jvp(TransformerLM)/layer_0/attn/rotary/mul",
             "rotary"),
            ("jit(step)/transpose(jvp(loss))/pallas_call", "jit(step)"),
            ("jit(prefill)/convert_element_type", "jit(prefill)"),
            ("pool['k']", "pool['k']"), ("", pt.NO_SCOPE)]:
        assert pt.scope_label(scope) == label
    assert pt.scope_totals(OPS) == pytest.approx({
        "kv.write": 3.7 * MS, "kv.gather": 5 * MS, pt.NO_SCOPE: 1.5 * MS})


def test_self_time_is_duration_minus_children_on_the_same_thread():
    rows = pt.self_times(SPANS)
    n, total, own, counts = rows["serve.step"]
    assert (n, counts) == (3, {"admitted": 1})
    assert total == pytest.approx(23.0 * MS)
    # step 1: 9.5 - schedule 0.4 - decode 5.0; step 2: 10.5 - 9.0; the
    # writer thread's span covers them all and is nobody's parent
    assert own == pytest.approx((4.1 + 1.5 + 3.0) * MS)
    assert rows["serve.decode"][2] == pytest.approx((1.0 + 4.0) * MS)
    # a label is counted by value, an identifier is left out
    assert rows["serve.decode"][3] == {"live": 4, "program:decode": 2}
    assert rows["checkpoint.save"][2] == pytest.approx(30.0 * MS)
    assert rows["serve.schedule"][3] == {"admitted": 1, "cached_tokens": 16}


def test_a_gap_is_split_over_the_spans_it_crosses():
    """The gap from 4 to 6 is step 1's own time to 4.5, its decode to 5
    and decode.wait after; the one from 9 to 12 crosses decode (to 9.5),
    step 1 (to 10), step 2's own time (to 11) and its decode; the one
    from 14 to 19.5 is half a millisecond of decode and 5 of its wait."""
    ops = [o[:3] for o in OPS]
    assert trace_reduce.gap_shares(ops, SPANS[:-1]) == pytest.approx({
        "serve.step": (0.5 + 0.5 + 1.0) * MS,
        "serve.decode": (0.5 + 0.5 + 1.0 + 0.5) * MS,
        "serve.decode.wait": (1.0 + 5.0) * MS})
    assert sum(trace_reduce.gap_shares(ops, SPANS).values()) == (
        pytest.approx(sum(trace_reduce.gap_attribution(
            ops, [s[:3] for s in SPANS]).values())))


def test_program_span_names(tmp_path):
    for name in ("serve.step", "kv.copy_on_write", "serve.decode.wait"):
        assert pt.PROGRAM_SPAN.match(name)
    for name in ("bench.engine_step", "PjitFunction(decode)", "copy.1",
                 "zero_sized_hlo_elimination", "$profiler.py:91 trace"):
        assert not pt.PROGRAM_SPAN.match(name)
    assert pt.newest_xplane(str(tmp_path)) is None
    assert pt.main([str(tmp_path)]) == 1


# ---------------------------------------------------------------------------
# a program_span metric as files alone, read in the traced CPU run
# ---------------------------------------------------------------------------

def test_rehearsal_reads_a_program_span_metric_made_of_files(
        tmp_path, monkeypatch, capsys):
    root = str(tmp_path)
    rehearsal._write(f"{root}/b/configs/tiny_serve.json",
                     rehearsal.CONFIGS["tiny_serve"])
    rehearsal._write(f"{root}/b/traffic/tiny_sessions.json",
                     rehearsal.TRAFFIC["tiny_sessions"])
    rehearsal._write(f"{root}/b/layer_metrics/prefill_span_ms_p50.json",
                     {"reader": "program_trace", "args": {
                         "what": "span_ms", "span": "serve.prefill",
                         "q": 50}})
    rehearsal._write(f"{root}/b/layer_metrics/host_gap_ms_per_step.json",
                     {"reader": "program_trace", "args": {
                         "what": "gap_ms_per_span", "span": "serve.step"}})
    cell = "tiny_serve.tiny_sessions"
    rehearsal._write(f"{root}/BENCHMARK.json", {
        "paths": ["b"],
        "configs": [{"name": "tiny_serve",
                     "file": "b/configs/tiny_serve.json"}],
        "workloads": [{"name": cell, "config": "tiny_serve",
                       "traffic": "tiny_sessions", "chips": 1,
                       "why": "rehearsal"}],
        "end_to_end": [],
        "per_layer": [
            {"name": "prefill_span_ms_p50", "unit": "ms"},
            {"name": "host_gap_ms_per_step", "unit": "ms"}]})
    # the reader looks under its own checkout alone, and only where the
    # harness has a summary, which a CPU trace (no device plane) does
    # not give: point it at this root, and hand the harness the summary
    # of one operation on one chip
    monkeypatch.setattr(pt, "TRACE_DIR",
                        os.path.join(root, ".cache", "bench_trace"))
    monkeypatch.setattr(
        trace_reduce, "reduce_xplane", lambda path: trace_reduce.summarize(
            {0: [("%copy.1 = bf16[4]{0} copy(%x)", 0.0, MS)]},
            [("jit_decode(1)", 0.0, MS)], []))
    pt.load.cache_clear()
    out = io.StringIO()
    rc = harness.run(root, cell, seed=2**31 + 7, seconds=0.6, trace=True,
                     process_start=time.monotonic(), require_chip=False,
                     out=out)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    # the span's durations need no device plane in the file; the gap
    # metric does, finds none on the CPU, returns nothing and is left out
    assert set(line["metrics"]) == {"prefill_span_ms_p50"}
    assert line["metrics"]["prefill_span_ms_p50"]["value"] > 0
    # the walk over the file's bytes gives what jax's own reader gives
    from jax.profiler import ProfileData
    path = pt.newest_xplane(pt.TRACE_DIR)
    theirs = sorted(
        (e.start_ns * 1e-9, e.name, e.duration_ns * 1e-9, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for ln in plane.lines for e in ln.events
        if pt.PROGRAM_SPAN.match(e.name))
    ours = pt.load(path)["spans"]
    assert len(ours) == len(theirs) > 10
    for (name, start, dur, stats, _), other in zip(ours, theirs):
        assert (pytest.approx(start), name, pytest.approx(dur), stats) \
            == other
    # and the table for a human, from the same trace
    assert pt.main([pt.TRACE_DIR]) == 0
    table = capsys.readouterr().out
    for name in ("serve.step", "serve.schedule", "serve.prefill.launch",
                 "serve.decode.wait", "no device plane"):
        assert name in table
