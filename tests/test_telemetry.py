"""Unified telemetry subsystem tests (ISSUE 4).

Single-process: registry thread-safety + typed instruments, event-log
JSONL round-trip + monotonic ordering + torn-tail/corruption semantics,
rollup merge math, stall detector (fires on an injected ``dispatch.wait``
chaos delay naming the delayed worker; silent on a clean run),
``tools/obs_report.py`` rendering and ``--check``.

Multi-process (the acceptance scenario): ≥2 workers produce per-worker
JSONL event logs, publish registry snapshots through the coordination
KV (on this container's jaxlib vintage that exercises the legacy
string-get fallback), the coordinator merges a fleet rollup into
TensorBoard event files, and ``obs_report`` renders step-time p50/p95,
infeed-wait fraction, and retry counts from the run directory.
"""

import io
import json
import os
import threading
import time

import pytest

from distributed_tensorflow_tpu import telemetry
from distributed_tensorflow_tpu.cluster import coordination
from distributed_tensorflow_tpu.coordinator import remote_dispatch as rd
from distributed_tensorflow_tpu.resilience import faults
from distributed_tensorflow_tpu.resilience.faults import (
    FaultRule, FaultSchedule)
from distributed_tensorflow_tpu.testing import multi_process_runner as mpr


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_concurrent_increments_observed_exactly():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("x/hits")
    n_threads, per_thread = 8, 2000

    def spam():
        for _ in range(per_thread):
            c.increment()

    ts = [threading.Thread(target=spam) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per_thread
    assert reg.snapshot()["x/hits"]["value"] == n_threads * per_thread


def test_histogram_and_timer_concurrent_records():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("h", window=64)
    t = reg.timer("t")

    def spam(base):
        for i in range(500):
            h.record(base + i)
            t.record(0.001)

    ts = [threading.Thread(target=spam, args=(k,)) for k in range(4)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert h.count == 2000
    snap = reg.snapshot()
    assert snap["h"]["count"] == 2000
    assert snap["t"]["count"] == 2000
    assert abs(snap["t"]["sum"] - 2.0) < 1e-6
    assert snap["h"]["p50"] is not None


def test_get_or_create_idempotent_and_typed():
    reg = telemetry.MetricsRegistry()
    a = reg.counter("n")
    assert reg.counter("n") is a
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("n")


def test_snapshot_delta_reports_only_changes():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("a")
    g = reg.gauge("b")
    c.increment()
    g.set(1)
    snap = reg.snapshot()
    assert reg.delta(snap) == {}
    c.increment()
    d = reg.delta(snap)
    assert list(d) == ["a"] and d["a"]["value"] == 2
    assert reg.delta(None) == reg.snapshot()


def test_collector_merged_into_snapshot():
    reg = telemetry.MetricsRegistry()
    reg.register_collector("ext", lambda: {"stage/elements": 7})
    assert reg.snapshot()["ext/stage/elements"]["value"] == 7
    # a broken collector must not take down export
    reg.register_collector("boom", lambda: 1 / 0)
    assert "ext/stage/elements" in reg.snapshot()


def test_pipeline_stage_stats_exported_through_registry():
    """input/dataset.py stage counters ride the profiler collector."""
    from distributed_tensorflow_tpu.input.dataset import Dataset
    ds = Dataset.range(32).map(lambda x: x + 1, num_parallel_calls=2,
                               name="tlm").prefetch(2, name="tlm")
    assert [int(x) for x in ds] == list(range(1, 33))
    snap = telemetry.get_registry().snapshot()
    keys = [k for k in snap if k.startswith("input/pipeline/map:tlm")]
    assert any(k.endswith("/elements") for k in keys), sorted(snap)[:40]


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------

def test_event_log_roundtrip_and_monotonic_ordering(tmp_path):
    log = telemetry.EventLog(str(tmp_path / "events-0.jsonl"),
                             process_id=3)
    for i in range(50):
        log.event("train.step", step=i, dur_s=0.001 * i)
    with log.span("checkpoint.save", path="/ck") as sp:
        sp["bytes"] = 123
    log.close()
    evs = telemetry.read_events(str(tmp_path / "events-0.jsonl"))
    assert len(evs) == 51
    assert all(e["pid"] == 3 for e in evs)
    steps = [e for e in evs if e["ev"] == "train.step"]
    assert [e["step"] for e in steps] == list(range(50))
    ts = [e["t"] for e in evs]
    assert ts == sorted(ts), "monotonic timestamps violated"
    span = evs[-1]
    assert span["ev"] == "checkpoint.save"
    assert span["dur_s"] >= 0 and span["bytes"] == 123


def test_span_records_error_and_reraises(tmp_path):
    log = telemetry.EventLog(str(tmp_path / "e.jsonl"))
    with pytest.raises(ValueError):
        with log.span("checkpoint.save"):
            raise ValueError("disk full")
    log.close()
    (ev,) = telemetry.read_events(str(tmp_path / "e.jsonl"))
    assert "disk full" in ev["error"]


def test_torn_tail_tolerated_midfile_corruption_rejected(tmp_path):
    path = str(tmp_path / "events-0.jsonl")
    good = {"ev": "a", "t": 0.1, "wall": 1.0, "pid": 0}
    with open(path, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write(json.dumps(good) + "\n")
        f.write('{"ev": "torn-tai')             # crashed writer
    assert len(telemetry.read_events(path)) == 2
    with pytest.raises(telemetry.EventLogCorruptError):
        telemetry.read_events(path, tolerate_torn_tail=False)

    with open(path, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write("not json at all\n")            # mid-file damage
        f.write(json.dumps(good) + "\n")
    with pytest.raises(telemetry.EventLogCorruptError, match=":2"):
        telemetry.read_events(path)


def test_module_level_api_off_by_default_then_configured(tmp_path):
    telemetry.shutdown()
    assert not telemetry.enabled()
    assert telemetry.event("ignored") is None       # no-op, no crash
    with telemetry.span("also.ignored"):
        pass
    try:
        telemetry.configure(str(tmp_path), process_id=5)
        assert telemetry.enabled()
        telemetry.event("hello", x=1)
    finally:
        telemetry.shutdown()
    evs = telemetry.read_events(str(tmp_path / "events-5.jsonl"))
    assert evs[-1]["ev"] == "hello" and evs[-1]["x"] == 1
    assert not telemetry.enabled()


def test_event_log_rotation_chains_segments(tmp_path):
    """Size-capped rotation (ISSUE 10): a long-lived writer rolls
    events.jsonl -> .1 -> .2 ... at line boundaries; the reader chains
    the segments back transparently, in order, so trace/obs consumers
    are unchanged."""
    path = str(tmp_path / "events-0.jsonl")
    log = telemetry.EventLog(path, process_id=0, max_bytes=400)
    n = 60
    for i in range(n):
        log.event("serve.step", step=i)
    log.close()
    import glob
    segs = sorted(glob.glob(path + ".*"))
    assert len(segs) >= 2, "cap never triggered rotation"
    assert os.path.getsize(path) <= 400
    for seg in segs:
        assert os.path.getsize(seg) <= 400 + 120    # one line overshoot
    evs = telemetry.read_events(path)
    assert [e["step"] for e in evs] == list(range(n))
    ts = [e["t"] for e in evs]
    assert ts == sorted(ts), "monotonic t broken across segments"
    # per-file read still works (no rotated siblings consulted; the
    # live file may be freshly rotated and empty)
    live_only = telemetry.read_events(path, include_rotated=False)
    assert len(live_only) < n
    # run-level reader sees the full chained history too
    run = telemetry.read_run(str(tmp_path))
    assert len(run[0]) == n


def test_event_log_rotation_torn_live_tail_tolerated(tmp_path):
    path = str(tmp_path / "events-0.jsonl")
    log = telemetry.EventLog(path, process_id=0, max_bytes=300)
    for i in range(30):
        log.event("train.step", step=i)
    log.close()
    with open(path, "a") as f:
        f.write('{"ev": "torn-tai')          # SIGKILL mid-write
    evs = telemetry.read_events(path)
    assert len(evs) == 30
    # ... but corruption inside a ROTATED segment is never tolerated
    seg = telemetry.events.rotated_segments(path)[0]
    with open(seg, "r+") as f:
        lines = f.readlines()
        lines[0] = "damaged\n"
        f.seek(0)
        f.writelines(lines)
        f.truncate()
    with pytest.raises(telemetry.EventLogCorruptError):
        telemetry.read_events(path)


def test_stall_event_names_accruing_badput_bucket(tmp_path):
    """Satellite (ISSUE 10): stall.suspected carries the badput bucket
    the blocked time is accruing to — the live ledger's current bucket,
    'idle' when no ledger is active."""
    from distributed_tensorflow_tpu.telemetry import goodput

    def fire_and_read(subdir):
        d = tmp_path / subdir
        telemetry.configure(str(d), process_id=0)
        try:
            det = telemetry.StallDetector(warmup_timeout_s=300.0,
                                          output=io.StringIO())
            try:
                det._triggered()
            finally:
                det.stop()
        finally:
            telemetry.shutdown()
        (ev,) = telemetry.read_events(str(d / "events-0.jsonl"))
        assert ev["ev"] == "stall.suspected"
        return ev

    assert fire_and_read("no_ledger")["badput_bucket"] == "idle"
    led = goodput.GoodputLedger(register=False)
    prev = goodput.activate(led)
    try:
        led.step_completed(0.001)
        led.enter("ckpt_block")
        assert fire_and_read("ckpt")["badput_bucket"] == "ckpt_block"
    finally:
        goodput.activate(prev)


# ---------------------------------------------------------------------------
# rollup merge (math on synthetic snapshots; the KV transport is covered
# by the multi-process test below)
# ---------------------------------------------------------------------------

def _snap(pid, counter, hist_count, p50, p95):
    return {"pid": pid, "seq": 1, "wall": float(pid),
            "metrics": {
                "training/steps_completed":
                    {"type": "counter", "value": counter},
                "training/step_time":
                    {"type": "histogram", "count": hist_count,
                     "sum": hist_count * p50, "min": 0.0, "max": p95,
                     "p50": p50, "p95": p95}}}


def test_merge_rollup_sum_max_p50_p95():
    r = telemetry.merge_rollup({0: _snap(0, 10, 100, 0.01, 0.02),
                                1: _snap(1, 4, 300, 0.03, 0.05)})
    m = r["metrics"]
    assert m["training/steps_completed"]["sum"] == 14
    assert m["training/steps_completed"]["max"] == 10
    assert m["training/step_time"]["count"] == 400
    assert m["training/step_time"]["p95"] == 0.05     # max of worker p95s
    assert m["training/step_time"]["p50"] == 0.03     # count-weighted
    scalars = telemetry.rollup_scalars(r)
    assert scalars["fleet/training/steps_completed/sum"] == 14.0


# ---------------------------------------------------------------------------
# stall detector (+ chaos delay at dispatch.wait)
# ---------------------------------------------------------------------------

@pytest.fixture()
def fresh_service():
    """Isolated local KV service + fresh generation (the
    test_remote_dispatch idiom)."""
    old = coordination._LOCAL
    coordination._LOCAL = coordination._LocalService()
    rd._reset_generation_for_tests()
    agent = coordination.CoordinationServiceAgent()
    yield agent
    rd._reset_generation_for_tests()
    coordination._LOCAL = old


def _noop(x):
    return x


def _drive_dispatch_steps(agent, tmp_path, n_steps, schedule=None,
                          factor=3.0, min_timeout_s=0.4):
    """Drive a 2-worker remote-dispatch step loop with telemetry on;
    returns (stall events, detector). One 'step' = one closure on each
    worker lane."""
    services = []
    for wid in (1, 2):
        svc = rd.RemoteWorkerService(worker_id=wid, agent=agent)
        threading.Thread(target=svc.run, kwargs={"poll_s": 0.05},
                         daemon=True).start()
        services.append(svc)
    lanes = [rd.RemoteLane(w, agent=agent, staleness_s=30.0)
             for w in (1, 2)]
    telemetry.configure(str(tmp_path), process_id=0)
    detector = telemetry.StallDetector(
        factor=factor, min_steps=3, min_timeout_s=min_timeout_s,
        output=io.StringIO())
    try:
        ctx = (faults.inject(schedule) if schedule is not None
               else _null_ctx())
        with ctx:
            for i in range(n_steps):
                seqs = [lane.submit(_noop, (i,), {}) for lane in lanes]
                for lane, seq in zip(lanes, seqs):
                    assert lane.wait(seq, timeout_s=60) == i
                time.sleep(0.02)        # steady cadence
                detector.step_completed(i)
    finally:
        detector.stop()
        rd.shutdown_workers(agent, worker_ids=[1, 2], timeout_s=10)
        telemetry.shutdown()
    events = telemetry.read_events(str(tmp_path / "events-0.jsonl"))
    return [e for e in events if e["ev"] == "stall.suspected"], detector


class _null_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


@pytest.mark.chaos
def test_stall_detector_fires_on_injected_dispatch_delay(
        fresh_service, tmp_path):
    """A chaos ``delay`` at dispatch.wait for worker 2 must produce a
    ``stall.suspected`` event NAMING worker 2 (waiting-lane gauge
    attribution), and training must complete regardless (non-fatal)."""
    schedule = FaultSchedule(seed=7, rules=(
        FaultRule(site="dispatch.wait", tag="2", action="delay",
                  delay_s=2.5, hits=(9,)),))
    stalls, det = _drive_dispatch_steps(fresh_service, tmp_path,
                                        n_steps=10, schedule=schedule)
    assert det.triggered_count >= 1
    assert stalls, "no stall.suspected event emitted"
    assert any(str(s.get("suspect_worker")) == "2" for s in stalls), stalls


@pytest.mark.chaos
def test_stall_detector_silent_on_clean_run(fresh_service, tmp_path):
    stalls, det = _drive_dispatch_steps(fresh_service, tmp_path,
                                        n_steps=10, schedule=None)
    assert det.triggered_count == 0
    assert stalls == []


# ---------------------------------------------------------------------------
# obs_report
# ---------------------------------------------------------------------------

def _write_run(tmp_path):
    log = telemetry.EventLog(str(tmp_path / "events-0.jsonl"),
                             process_id=0)
    for i in range(40):
        log.event("train.step", step=i, dur_s=0.010 + 0.0001 * i,
                  infeed_wait_s=0.001)
    log.event("dispatch.retry", worker=1, error="x")
    log.event("fault.fired", site="coord.kv_get", tag="k", hit=1,
              action="raise")
    log.event("checkpoint.save", dur_s=0.2, path="/ck")
    log.close()


def test_obs_report_renders_percentiles_and_retries(tmp_path, capsys):
    import tools.obs_report as obs
    _write_run(tmp_path)
    assert obs.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "p50" in out and "p95" in out
    assert "worker 1: 1" in out
    assert "coord.kv_get: 1" in out
    assert "checkpoint.save" in out
    assert obs.main([str(tmp_path), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)["report"]
    assert rep["step_time"]["count"] == 40
    assert rep["retries"] == {"worker 1": 1}
    assert 0.05 < rep["infeed_wait_fraction"] < 0.15


def test_obs_report_check_gate(tmp_path, capsys):
    import tools.obs_report as obs
    _write_run(tmp_path)
    # torn tail: tolerated
    with open(tmp_path / "events-0.jsonl", "a") as f:
        f.write('{"ev": "torn')
    assert obs.main([str(tmp_path), "--check"]) == 0
    assert "torn tail" in capsys.readouterr().out
    # mid-file corruption: rejected
    path = tmp_path / "events-0.jsonl"
    lines = path.read_text().split("\n")
    lines[5] = "{definitely not json"
    path.write_text("\n".join(lines))
    assert obs.main([str(tmp_path), "--check"]) == 1
    # empty dir: distinct non-zero
    empty = tmp_path / "empty"
    empty.mkdir()
    assert obs.main([str(empty), "--check"]) == 2


# ---------------------------------------------------------------------------
# multi-process: per-worker JSONL + KV snapshot publish + fleet rollup
# in TensorBoard event files + obs_report over the run dir
# ---------------------------------------------------------------------------

def _fleet_worker(tmpdir):
    import os
    import time

    from distributed_tensorflow_tpu import telemetry
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.cluster.coordination import (
        coordination_service)
    from distributed_tensorflow_tpu.utils.summary import SummaryWriter

    runtime = bootstrap.initialize()
    agent = coordination_service()
    pid = runtime.process_id
    run_dir = os.path.join(tmpdir, "run")
    telemetry.configure(run_dir, process_id=pid)
    reg = telemetry.get_registry()
    steps = reg.counter("training/steps_completed")
    hist = reg.histogram("training/step_time")

    publisher = telemetry.MetricsPublisher(agent=agent, interval_s=0.2,
                                           process_id=pid)
    n_steps = 15 + 5 * pid             # unequal so sum/max are telling
    for i in range(n_steps):
        t0 = time.monotonic()
        time.sleep(0.005)
        dur = time.monotonic() - t0
        steps.increment()
        hist.record(dur)
        telemetry.event("train.step", step=i, dur_s=round(dur, 6),
                        infeed_wait_s=0.0005)
    if pid == 1:
        telemetry.event("dispatch.retry", worker=1, error="synthetic")
    publisher.stop()                   # final snapshot published
    agent.barrier("telemetry-published", timeout_s=60)

    rollup = None
    if pid == 0:
        aggregator = telemetry.FleetAggregator(
            worker_ids=range(runtime.num_processes), agent=agent,
            interval_s=0.5,
            summary_writer=SummaryWriter(run_dir))
        rollup = aggregator.collect_once()
        aggregator.stop()
        aggregator.writer.close()
    agent.barrier("telemetry-rolled-up", timeout_s=60)
    telemetry.shutdown()
    bootstrap.shutdown()
    if rollup is None:
        return None
    m = rollup["metrics"]
    return {"sum": m["training/steps_completed"]["sum"],
            "max": m["training/steps_completed"]["max"],
            "hist_count": m["training/step_time"]["count"],
            "p95": m["training/step_time"]["p95"]}


@pytest.mark.multiprocess
def test_fleet_rollup_across_processes(tmp_path):
    """Acceptance: 2 workers -> per-worker JSONL, KV snapshot publish
    (legacy string-get path on this jaxlib), coordinator rollup with
    correct sum/max/count, fleet/* scalars in a TensorBoard event file,
    and obs_report rendering p50/p95 + retry counts from the run dir."""
    result = mpr.run(_fleet_worker, num_workers=2,
                     args=(str(tmp_path),), timeout=180)
    rollups = [r for r in result.return_values if r is not None]
    assert len(rollups) == 1
    (rollup,) = rollups
    assert rollup["sum"] == 15 + 20
    assert rollup["max"] == 20
    assert rollup["hist_count"] == 35
    assert rollup["p95"] is not None and rollup["p95"] >= 0.005

    run_dir = tmp_path / "run"
    # per-worker JSONL event logs
    for pid in (0, 1):
        evs = telemetry.read_events(str(run_dir / f"events-{pid}.jsonl"))
        assert sum(e["ev"] == "train.step" for e in evs) == 15 + 5 * pid

    # fleet rollup landed in a TensorBoard event file
    from distributed_tensorflow_tpu.utils.summary import read_scalars
    import glob
    event_files = glob.glob(str(run_dir / "events.out.tfevents.*"))
    assert event_files
    scalars = {}
    for f in event_files:
        for tag, step, value in read_scalars(f):
            scalars[tag] = value
    assert scalars["fleet/training/steps_completed/sum"] == 35.0
    assert scalars["fleet/training/steps_completed/max"] == 20.0
    assert "fleet/training/step_time/p95" in scalars

    # obs_report renders the whole run dir
    import tools.obs_report as obs
    assert obs.main([str(run_dir), "--json"]) == 0
    assert obs.main([str(run_dir), "--check"]) == 0


# ---------------------------------------------------------------------------
# end-to-end smoke: examples/train_mnist.py with telemetry on (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_mnist_telemetry_smoke(tmp_path):
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run_dir = tmp_path / "mnist_run"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "train_mnist.py"),
         "--steps", "30", "--telemetry-dir", str(run_dir)],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    evs = telemetry.read_events(str(run_dir / "events-0.jsonl"))
    steps = [e for e in evs if e["ev"] == "train.step"]
    assert len(steps) == 30
    assert any(e.get("loss") is not None for e in steps)

    check = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "obs_report.py"),
         str(run_dir), "--json"],
        capture_output=True, text=True, timeout=120, env=env, cwd=repo)
    assert check.returncode == 0, check.stderr[-2000:]
    rep = json.loads(check.stdout)["report"]
    assert rep["step_time"]["count"] == 30
    assert rep["step_time"]["p50"] > 0
    check2 = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "obs_report.py"),
         str(run_dir), "--check"],
        capture_output=True, text=True, timeout=120, env=env, cwd=repo)
    assert check2.returncode == 0
