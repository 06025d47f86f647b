"""``benchmark/readers/launch_trace.py`` on a hand-written xplane: the
device's program runs paired with the engine's numbered launches, the
six metrics that read the pairing, and ``None`` wherever the pairing is
not one offset."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark_program_trace import _message  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark.readers import launch_trace as lt  # noqa: E402
from benchmark.readers import program_trace as pt  # noqa: E402

MS = 1e-3
OP = "%fusion.1 = bf16[4]{0} fusion(%x), kind=kLoop"


def span(name, start_ms, end_ms, **stats):
    return (name, start_ms, end_ms, stats)


def run(program, start_ms, end_ms):
    return (program, start_ms, end_ms)


# One thread, times in ms. Decode launch 10 was made before the trace: its
# run opens the device line and step 1's commit reads it. Step 1 launches
# an extend (waits 0.5 ms behind that run) and decode 12; step 2 a cold
# prefill (waits 0.1 ms) and decode 14; each queued run starts as the one
# before it ends. In step 3 the host is slow and the chip waits 0.5 ms for
# decode 15; decode 16 is read by a drain; decode 17 is still in flight
# when the trace ends, with no run in it.
SPANS = [
    span("serve.schedule", 0.05, 0.1, admitted=1),
    span("serve.prefill", 0.12, 0.55, id="ask", program="extend",
         queue_wait_s=0.002),
    span("serve.prefill.launch", 0.2, 0.5, program="extend", launch=11,
         since_admit_s=0.4 * MS),
    span("serve.decode.launch", 0.6, 0.8, program="decode", launch=12),
    span("serve.decode.wait", 0.9, 5.0),
    span("serve.decode.commit", 5.0, 5.3, tokens=1, read=10),
    span("serve.prefill.commit", 5.1, 5.2, id="ask", read=11,
         ttft_s=(2.0 + 0.4 + 5.2 - 0.5) * MS),
    span("serve.schedule", 5.35, 5.4, admitted=1),
    span("serve.prefill", 5.45, 5.85, id="doc", program="prefill",
         queue_wait_s=0.001),
    span("serve.prefill.launch", 5.5, 5.8, program="prefill", launch=13,
         since_admit_s=0.7 * MS),
    span("serve.decode.launch", 5.9, 6.1, program="decode", launch=14),
    span("serve.decode.wait", 6.2, 7.0),
    span("serve.decode.commit", 7.0, 7.2, tokens=1, read=12),
    # a sum 0.3 ms short of the engine's own TTFT: still within 1 ms
    span("serve.prefill.commit", 7.05, 7.1, id="doc", read=13,
         ttft_s=(1.0 + 0.7 + 7.1 - 5.8 + 0.3) * MS),
    span("serve.decode.launch", 8.1, 8.4, program="decode", launch=15),
    span("serve.decode.wait", 8.5, 8.55),
    span("serve.decode.commit", 8.55, 8.6, tokens=2, read=14),
    span("serve.decode.launch", 8.7, 8.9, program="decode", launch=16),
    span("serve.decode.wait", 9.0, 9.4),
    span("serve.decode.commit", 9.4, 9.5, tokens=2, read=15),
    span("serve.drain", 9.6, 10.5, reason="idle", tokens=2, read=16),
    span("serve.decode.launch", 10.6, 10.8, program="decode", launch=17),
]
RUNS = [
    run("decode", 0.1, 1.0),            # launch 10's, before the trace
    run("extend", 1.0, 4.9),
    run("decode", 4.9, 5.9),
    run("prefill", 5.9, 6.9),
    run("decode", 6.9, 7.9),
    run("decode", 8.4, 9.3),
    run("decode", 9.3, 10.3),
]


#: chip 0's clock in the hand-written trace runs this far ahead of the
#: host's (a v5e's read about 1.45 ms behind; the reader takes either)
SKEW_MS = 1.4
#: the runtime enqueues a run this long before it starts (host clock);
#: it handles each completion the instant the run ends, the extend's
#: (run 1) this much later
ENQUEUE_MS = 0.05
LATE_MS = {1: 0.1}


def xplane(spans, runs, runtime=True) -> bytes:
    """An XSpace: a host plane with ``spans`` and their stats on one
    line, and chip 0 with one operation a run on its ``XLA Ops`` line and
    the runs on its ``XLA Modules`` line, each with a ``run_id``. With
    ``runtime`` the chip's times are ``SKEW_MS`` off the host's, and the
    host plane also holds the runtime's enqueue and completion of each
    run, named by its ``run_id``, on a second line."""
    skew = SKEW_MS if runtime else 0.0
    events = [(s[0], s[1], s[2], s[3], 1) for s in spans]
    if runtime:
        for i, (_, start, end) in enumerate(runs):
            late = LATE_MS.get(i, 0.0)
            for name, at in (("DoEnqueueProgram", start - ENQUEUE_MS),
                             ("CompleteCallbacks", end + late)):
                events.append((name, at, at + 0.01,
                               {"run_id": 100 + i, "device_ordinal": 0}, 2))
    names = sorted({e[0] for e in events})
    stat_ids = {k: i + 1 for i, k in enumerate(sorted(
        {k for e in events for k in e[3]} | {"run_id"}))}

    def meta(field, key, name):
        return (field, _message((1, key), (2, _message((1, key),
                                                        (2, name)))))

    def stat(key, value):
        kind = 5 if isinstance(value, str) else (
            2 if isinstance(value, float) else 4)
        return _message((1, stat_ids[key]), (kind, value))

    def event(key, start_ms, end_ms, stats=()):
        return (4, _message((1, key), (2, round(start_ms * 1e9)),
                            (3, round((end_ms - start_ms) * 1e9)),
                            *[(4, st) for st in stats]))

    host = _message(
        (2, "/host:CPU"),
        *[(3, _message((1, line), (2, f"thread {line}"), (3, 0), *[
            event(names.index(e[0]) + 1, e[1], e[2],
                  [stat(k, v) for k, v in e[3].items()])
            for e in events if e[4] == line])) for line in (1, 2)],
        *[meta(5, i, k) for k, i in stat_ids.items()],
        *[meta(4, i + 1, n) for i, n in enumerate(names)])
    programs = sorted({r[0] for r in runs})
    device = _message(
        (1, 7), (2, "/device:TPU:0"),
        (3, _message((1, 1), (2, "XLA Ops"), (3, 0), *[
            event(100, r[1] + skew, r[2] + skew) for r in runs])),
        (3, _message((1, 2), (2, "XLA Modules"), (3, 0), *[
            event(programs.index(r[0]) + 1, r[1] + skew, r[2] + skew,
                  [stat("run_id", 100 + i)]) for i, r in enumerate(runs)])),
        meta(4, 100, OP), meta(5, stat_ids["run_id"], "run_id"),
        *[meta(4, i + 1, f"jit_{p}({40 + i})")
          for i, p in enumerate(programs)])
    return _message((1, device), (1, host))


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Point the readers at a file of ``xplane(...)``'s bytes; returns a
    function that writes one and the trace summary a harness would hand
    them."""
    monkeypatch.setattr(pt, "TRACE_DIR", str(tmp_path))

    def write(spans=SPANS, runs=RUNS, runtime=True):
        (tmp_path / "t.xplane.pb").write_bytes(xplane(spans, runs, runtime))
        pt.load.cache_clear()
        lt.load.cache_clear()
        return {"chips": 1, "busy_s": 1.0, "window_s": 1.0}
    yield write
    pt.load.cache_clear()
    lt.load.cache_clear()


def _parsed():
    """The trace's spans on chip 0's clock, and its runs."""
    path = pt.newest_xplane(pt.TRACE_DIR)
    return lt.on_chip_clock(pt.load(path)["spans"], path, 0)[:2]


def test_runs_pair_with_their_launches_at_the_one_offset(traced):
    traced()
    path = pt.newest_xplane(pt.TRACE_DIR)
    parsed = lt.load(path)
    runs = parsed["runs"][0]
    assert [r[0] for r in runs] == [r[0] for r in RUNS]
    assert runs[1][1:3] == (pytest.approx((1.0 + SKEW_MS) * MS),
                            pytest.approx(3.9 * MS))
    assert [r[3]["run_id"] for r in runs] == list(range(100, 107))
    # the runtime's events bracket the offset between the clocks: every
    # run enqueued before it starts, completed after it ends (host clock);
    # the completions, handled at once, give it
    assert parsed["enqueued"][(0, 101)] == pytest.approx(0.95 * MS)
    times = (runs, 0, parsed["enqueued"], parsed["completed"])
    assert lt.clock_bracket(*times) == (
        pytest.approx(SKEW_MS * MS), pytest.approx((SKEW_MS + ENQUEUE_MS)
                                                   * MS))
    assert lt.clock_offset(*times) == pytest.approx(SKEW_MS * MS)
    assert lt.clock_offset(runs, 0, {}, {}) == 0.0
    spans, _ = _parsed()
    paired = lt.pairing(spans, runs)
    # decode: the first run is launch 10's, from before the trace, so
    # launch 12 takes the second; launch 17's run is not in the trace
    found = {program: (d, [(p.launch[3]["launch"],
                            round(p.run[1] / MS - SKEW_MS, 3),
                            p.read and p.read[0]) for p in pairs])
             for program, (d, _, _, pairs) in paired.items()}
    assert found == {
        "decode": (1, [(12, 4.9, "serve.decode.commit"),
                       (14, 6.9, "serve.decode.commit"),
                       (15, 8.4, "serve.decode.commit"),
                       (16, 9.3, "serve.drain")]),
        "extend": (0, [(11, 1.0, "serve.prefill.commit")]),
        "prefill": (0, [(13, 5.9, "serve.prefill.commit")])}
    # on the trace's own clocks no offset fits: launch 12's run would end
    # after the wait before its read
    assert lt.pairing(pt.load(path)["spans"], runs)["decode"][0] is None
    # the read of each launch, and the latest instant its run could end:
    # the wait before the commit, or the drain's end
    bounds = {n: round(b / MS - SKEW_MS, 3)
              for n, (_, b) in lt.reads(spans).items()}
    assert bounds == {10: 5.0, 11: 5.0, 12: 7.0, 13: 7.0, 14: 8.55,
                      15: 9.4, 16: 10.5}


def test_the_runtimes_run_ids_agree_with_the_pairing(traced):
    """The cross-check by ids the rule does not read: each paired run was
    enqueued by the runtime after its launch span began and its completion
    handled before the span that reads it ended, all on the host's
    clock."""
    traced()
    path = pt.newest_xplane(pt.TRACE_DIR)
    spans, runs, (lo, _) = lt.on_chip_clock(pt.load(path)["spans"], path, 0)
    parsed = lt.load(path)
    checked = 0
    for _, _, _, pairs in lt.pairing(spans, runs).values():
        for p in pairs:
            key = (0, p.run[3]["run_id"])
            assert parsed["enqueued"][key] >= p.launch[1] - lo
            if p.read is not None:
                assert parsed["completed"][key] <= lt._end(p.read) - lo
            checked += 1
    assert checked == 6


@pytest.mark.parametrize("name,expected", [
    # an admission's own run: the extend's 3.9 ms and the prefill's 1.0
    ("admit_run_ms_p50.ttft", 2.45), ("admit_run_ms_p50.tput", 2.45),
    # launch end to run start: 0.5 behind launch 10's decode, 0.1
    ("admit_device_wait_ms_p50.ttft", 0.3),
    # the runtime's handling of the run's completion to the end of the
    # commit that banks its token: 0.2 (the extend's, handled 0.1 ms after
    # its run ended 0.3 before the commit did), 0.2
    ("first_token_lag_ms_p50.ttft", 0.2),
    # of four paired decode runs the chip sat idle before launch 15's
    # alone (7.9 to 8.4): the others started as the run before them ended
    ("decode_launch_bound_share.tpot", 25.0),
    ("decode_launch_bound_share.tput", 25.0)])
def test_the_six_metrics_read_the_pairing(traced, name, expected):
    trace = traced()
    manifest = harness._load(os.path.join(REPO, "BENCHMARK.json"))
    (metric,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert metric["source"] == "program_span"
    got = harness.read_metrics(os.path.join(REPO, "benchmark"),
                               "per_layer", [metric], {}, trace)
    assert got[name]["value"] == pytest.approx(expected)
    # no summary from the harness: nothing is read
    assert harness.read_metrics(os.path.join(REPO, "benchmark"),
                                "per_layer", [metric], {}, None) == {}


def test_decode_reads_lag_behind_their_runs(traced):
    traced()
    pairs = lt.select(lt.pairing(*_parsed()), "serve.decode.launch")
    assert [round(x / MS, 3) for x in lt.read_lags(pairs)] == [
        1.3, 0.7, 0.2, 0.2]
    assert lt.value("read_lag_ms", pairs, [], q=100) == pytest.approx(1.3)
    with pytest.raises(ValueError):
        lt.value("nonsense", pairs, [])


def test_an_admissions_parts_add_up_to_its_ttft(traced, capsys):
    traced()
    spans, runs = _parsed()
    rows = lt.admissions(spans, lt.select(lt.pairing(spans, runs),
                                          "serve.prefill.launch"))
    assert [r["id"] for r in rows] == ["ask", "doc"]
    ask, doc = rows
    assert [round(ask[k] / MS, 3) for k in lt.PARTS] == [
        2.0, 0.4, 0.5, 3.9, 0.1, 0.2]
    assert ask["sum"] == pytest.approx(ask["ttft"])
    assert doc["ttft"] - doc["sum"] == pytest.approx(0.3 * MS)
    assert lt.main([pt.TRACE_DIR]) == 0
    out = capsys.readouterr().out
    assert "2 of 2 sums within 1 ms of ttft_s" in out
    assert "runs +1.400 to +1.450 ms ahead" in out
    assert "high by up to 0.050 ms" in out
    assert "launch-bound runs 25.00%" in out
    assert lt.main([]) == 2


def test_two_offsets_that_fit_read_nothing(traced):
    """Two prefills launched before any of three runs, none read in the
    trace: launch i fits run i and run i + 1 alike."""
    trace = traced([span("serve.prefill.launch", 0.0, 0.1,
                         program="prefill", launch=1),
                    span("serve.prefill.launch", 0.2, 0.3,
                         program="prefill", launch=2)],
                   [run("prefill", 1.0, 1.5), run("prefill", 2.0, 2.5),
                    run("prefill", 3.0, 3.5)], runtime=False)
    spans, runs = _parsed()
    assert lt.pairing(spans, runs)["prefill"][0] is None
    args = {"what": "run_ms", "launch": "serve.prefill.launch", "q": 50}
    assert lt.read(args, {}, trace) is None


def test_a_run_that_would_start_before_its_launch_reads_nothing(traced):
    """The second run starts before the second launch does, and the first
    launch's read (the wait before its commit ends at 1.7) comes before
    the second run ends: no offset fits."""
    trace = traced([span("serve.prefill.launch", 1.0, 1.1,
                         program="prefill", launch=1),
                    span("serve.decode.wait", 1.2, 1.7),
                    span("serve.decode.commit", 1.7, 1.75, read=1),
                    span("serve.prefill.launch", 2.0, 2.1,
                         program="prefill", launch=2)],
                   [run("prefill", 1.5, 1.6), run("prefill", 1.8, 1.9)],
                   runtime=False)
    spans, runs = _parsed()
    assert lt.pairing(spans, runs)["prefill"][0] is None
    args = {"what": "device_wait_ms", "launch": "serve.prefill.launch",
            "q": 50}
    assert lt.read(args, {}, trace) is None


def test_a_trace_without_launch_numbers_reads_nothing(traced):
    """A program from before the engine numbered its launches: its spans
    carry no ``launch`` and ``read``, so every metric is left out."""
    plain = [(n, a, b, {k: v for k, v in s.items()
                        if k not in ("launch", "read", "ttft_s")})
             for n, a, b, s in SPANS]
    trace = traced(plain)
    for name in ("admit_run_ms_p50.ttft", "decode_launch_bound_share.tpot"):
        spec = json.load(open(os.path.join(
            REPO, "benchmark", "layer_metrics", name + ".json")))
        assert lt.read(spec["args"], {}, trace) is None
    assert lt.pairing(*_parsed()) == {}
