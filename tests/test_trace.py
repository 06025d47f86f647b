"""Cross-host trace timeline & step-time attribution (ISSUE 8).

Covers: clock-offset recovery under injected per-host skew (<10ms
alignment), torn-tail JSONL merge, span-causality round-trip over the
real dispatch machinery (dispatch.send -> worker.execute ->
dispatch.result linked by one span_id), overlap-efficiency parity
against a hand-computed 2-bucket schedule, the bottleneck classifier on
synthetic input-bound/comm-bound runs, obs_report's phase table +
bottleneck CI gates, and trace_report's CLI + completeness check.
"""

import json
import threading
import time

import pytest

from distributed_tensorflow_tpu import telemetry
from distributed_tensorflow_tpu.cluster import coordination
from distributed_tensorflow_tpu.coordinator import remote_dispatch as rd
from distributed_tensorflow_tpu.parallel import collectives
from distributed_tensorflow_tpu.telemetry import trace as tv_trace


# ---------------------------------------------------------------------------
# clock-offset estimation / trace assembly
# ---------------------------------------------------------------------------

def _synthetic_worker(pid, skew_s, *, gen=0, n_sync=3):
    """One worker's event list: clock.sync at shared barrier instants
    (the i-th crossing of 'ckpt' happens at true wall 1000+10*i) plus a
    train.step span, all read through a clock running ``skew_s`` fast."""
    evs = []
    for i in range(n_sync):
        evs.append({"ev": "clock.sync", "t": 10.0 * i,
                    "wall": 1000.0 + 10.0 * i + skew_s, "pid": pid,
                    "barrier": "ckpt_shards/ckpt", **(
                        {"gen": gen} if gen else {})})
    evs.append({"ev": "train.step", "t": 15.0,
                "wall": 1015.0 + skew_s, "pid": pid, "dur_s": 0.5,
                "step": 3})
    return evs


def test_clock_skew_recovered_under_10ms():
    """Injected per-host offsets (+5s, -2.3s) recover from the barrier
    sync points; matching events align to well under 10ms."""
    ebp = {0: _synthetic_worker(0, 0.0),
           1: _synthetic_worker(1, +5.0),
           2: _synthetic_worker(2, -2.3)}
    offs = tv_trace.estimate_clock_offsets(ebp)
    assert offs["__unaligned__"] == []
    assert abs(offs[0]) < 0.010
    assert abs(offs[1] - 5.0) < 0.010
    assert abs(offs[2] + 2.3) < 0.010
    trace = tv_trace.assemble_trace(ebp, offsets=offs)
    ts = sorted(e["ts"] for e in trace["traceEvents"]
                if e.get("name") == "train.step")
    assert ts[-1] - ts[0] < 10_000          # us: <10ms post-alignment
    json.dumps(trace)                       # valid Chrome-trace JSON


def test_supervisor_aligned_via_heartbeat_pairs():
    """A supervisor with no barrier in common aligns through clock.hb
    (worker wall vs heartbeat mtime in the supervisor's domain)."""
    sup_skew = 7.0
    ebp = {0: _synthetic_worker(0, 0.0),
           "supervisor": [
               {"ev": "clock.hb", "t": 1.0, "wall": 2000.0 + sup_skew,
                "pid": "supervisor", "worker": 0, "step": 5,
                "worker_wall": 1010.0, "mtime": 1010.0 + sup_skew}]}
    offs = tv_trace.estimate_clock_offsets(ebp)
    assert abs(offs["supervisor"] - sup_skew) < 0.010
    assert offs["__unaligned__"] == []


def test_unsynced_process_flagged_not_guessed():
    ebp = {0: _synthetic_worker(0, 0.0),
           7: [{"ev": "train.step", "t": 1.0, "wall": 999.0, "pid": 7,
                "dur_s": 0.1}]}
    offs = tv_trace.estimate_clock_offsets(ebp)
    assert offs[7] == 0.0
    assert offs["__unaligned__"] == [7]
    meta = tv_trace.assemble_trace(ebp, offsets=offs)["otherData"]
    assert meta["clock_unaligned"] == ["7"]


def test_barrier_emits_clock_sync_event(tmp_path):
    """The coordination-service barrier records the sync point the
    offset estimator feeds on (single-process local service path)."""
    telemetry.configure(str(tmp_path), process_id=0)
    try:
        coordination.CoordinationServiceAgent().barrier("unit_sync")
    finally:
        telemetry.shutdown()
    events = telemetry.read_events(
        telemetry.event_log_path(str(tmp_path), 0))
    syncs = [e for e in events if e["ev"] == "clock.sync"]
    assert len(syncs) == 1 and syncs[0]["barrier"] == "unit_sync"


def test_torn_tail_merges_and_completeness(tmp_path):
    """A SIGKILL'd writer's torn final line must not break assembly or
    count a generation as missing."""
    with open(tmp_path / "events-0.jsonl", "w") as f:
        for ev in _synthetic_worker(0, 0.0):
            f.write(json.dumps(ev) + "\n")
    with open(tmp_path / "events-1.jsonl", "w") as f:
        for ev in _synthetic_worker(1, 0.0, gen=1):
            f.write(json.dumps(ev) + "\n")
        f.write('{"ev": "train.step", "t": 99, "wa')    # torn tail
    ebp = telemetry.read_run(str(tmp_path))
    assert len(ebp[1]) == 4                 # torn line dropped
    comp = tv_trace.trace_completeness(ebp)
    assert comp["complete"], comp
    assert set(comp["generations"]) == {0, 1}
    out = tv_trace.write_trace(str(tmp_path))
    with open(out) as f:
        assert json.load(f)["traceEvents"]


def test_completeness_flags_generation_hole():
    """A supervisor timeline naming gen 1 with no worker events for it
    is an incomplete (unmergeable) run."""
    ebp = {0: _synthetic_worker(0, 0.0),    # gen-0 events only
           "supervisor": [
               {"ev": "recovery.generation_start", "t": 0.1,
                "wall": 1000.0, "pid": "supervisor", "generation": 0},
               {"ev": "recovery.generation_start", "t": 9.0,
                "wall": 1009.0, "pid": "supervisor", "generation": 1}]}
    comp = tv_trace.trace_completeness(ebp)
    assert not comp["complete"]
    assert comp["missing"] == [1]


# ---------------------------------------------------------------------------
# span causality: dispatch -> execute -> result
# ---------------------------------------------------------------------------

@pytest.fixture()
def fresh_service():
    old = coordination._LOCAL
    coordination._LOCAL = coordination._LocalService()
    rd._reset_generation_for_tests()
    agent = coordination.CoordinationServiceAgent()
    yield agent
    rd._reset_generation_for_tests()
    coordination._LOCAL = old


def test_dispatch_span_causality_roundtrip(fresh_service, tmp_path):
    """One closure through the real dispatch machinery: the
    coordinator's dispatch.send/dispatch.result and the worker's
    worker.execute span share a span_id, and the assembled trace links
    them with flow arrows in causal order."""
    agent = fresh_service
    telemetry.configure(str(tmp_path), process_id=0)
    try:
        svc = rd.RemoteWorkerService(worker_id=1, agent=agent)
        t = threading.Thread(target=svc.run, kwargs={"poll_s": 0.05},
                             daemon=True)
        t.start()
        lane = rd.RemoteLane(1, agent=agent, staleness_s=5.0)
        assert lane.execute(_triple, (7,), {}, timeout_s=30) == 21
    finally:
        telemetry.shutdown()
    events = telemetry.read_events(
        telemetry.event_log_path(str(tmp_path), 0))
    by_name = {e["ev"]: e for e in events
               if e["ev"] in ("dispatch.send", "worker.execute",
                              "dispatch.result")}
    assert set(by_name) == {"dispatch.send", "worker.execute",
                            "dispatch.result"}
    span_ids = {e["span_id"] for e in by_name.values()}
    assert len(span_ids) == 1               # one causal chain
    assert by_name["worker.execute"]["dur_s"] >= 0
    # assembled trace: the chain renders as s -> t -> f flow arrows
    trace = tv_trace.assemble_trace({0: events})
    flows = [e for e in trace["traceEvents"] if e.get("cat") == "flow"]
    assert [f["ph"] for f in flows] == ["s", "t", "f"]
    assert len({f["id"] for f in flows}) == 1


def test_checkpoint_tier_commits_share_span_id(tmp_path):
    """A pipelined local->durable save's save span and both tier
    commits carry one span_id (the capture->commit ladder chain)."""
    import numpy as np
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        Checkpoint)
    telemetry.configure(str(tmp_path / "tv"), process_id=0)
    try:
        ck = Checkpoint(x=np.arange(8.0))
        ck.write(str(tmp_path / "local" / "ck-1"),
                 tier="local",
                 pipeline_to=str(tmp_path / "durable" / "ck-1"))
        ck.sync()
    finally:
        telemetry.shutdown()
    events = telemetry.read_events(
        telemetry.event_log_path(str(tmp_path / "tv"), 0))
    saves = [e for e in events if e["ev"] == "checkpoint.save"]
    commits = [e for e in events if e["ev"] == "checkpoint.commit"]
    assert len(saves) == 1 and len(commits) == 2
    assert {c["tier"] for c in commits} == {"local", "durable"}
    ids = {e["span_id"] for e in saves + commits}
    assert ids == {"ckpt/ck-1"}


def _triple(x):
    return 3 * x


# ---------------------------------------------------------------------------
# overlap efficiency
# ---------------------------------------------------------------------------

def test_overlap_parity_vs_hand_computed_two_bucket_schedule():
    """Hand-computed 2-bucket schedule: backward runs [0, 1.0]s; bucket
    A (last layers) is ready at 0.5 and reduces for 0.3 -> finishes at
    0.8, fully hidden; bucket B is ready at 1.0 (backward end) and
    reduces for 0.4 -> entirely exposed. Serial cost 0.7, exposed 0.4,
    overlap_eff = 1 - 0.4/0.7 = 3/7."""
    r = collectives.simulate_overlap([0.5, 1.0], [0.3, 0.4],
                                     backward_end_s=1.0)
    assert r["serial_s"] == pytest.approx(0.7)
    assert r["finish_s"] == [pytest.approx(0.8), pytest.approx(1.4)]
    assert r["exposed_s"] == pytest.approx(0.4)
    assert r["overlap_eff"] == pytest.approx(3.0 / 7.0)
    # channel serialization: a bucket cannot start before the previous
    # one finished even if its grads are ready earlier
    r2 = collectives.simulate_overlap([0.0, 0.0], [0.6, 0.2],
                                      backward_end_s=1.0)
    assert r2["finish_s"] == [pytest.approx(0.6), pytest.approx(0.8)]
    assert r2["exposed_s"] == 0.0 and r2["overlap_eff"] == 1.0
    # degenerate: nothing to reduce
    assert collectives.simulate_overlap([], [])["overlap_eff"] is None
    assert tv_trace.overlap_efficiency(0.0, 0.0) is None
    assert tv_trace.overlap_efficiency(1.0, 0.25) == pytest.approx(0.75)


def test_bucketer_plan_summary_matches_plan():
    import jax.numpy as jnp
    b = collectives.GradientBucketer(("dp",), bytes_per_pack=48,
                                     reverse=True)
    leaves = [jnp.zeros(8, jnp.float32), jnp.zeros(8, jnp.float32),
              jnp.zeros(4, jnp.float32)]
    summary = b.plan_summary(leaves)
    # reverse leaf order: the 16B leaf + one 32B leaf hit the 48B
    # boundary and close the bucket; the remaining 32B leaf is its own
    assert [(s["leaves"], s["bytes"]) for s in summary] == [
        (2, 48), (1, 32)]
    assert all(s["dtype"] == "float32" for s in summary)


# ---------------------------------------------------------------------------
# bottleneck classifier
# ---------------------------------------------------------------------------

def test_classifier_synthetic_input_and_comm_bound():
    b = tv_trace.classify_run({"infeed": 0.4})
    assert b["class"] == "input-bound" and b["trigger"] == "infeed"
    b = tv_trace.classify_run({"collective": 0.5})
    assert b["class"] == "comm-bound"
    b = tv_trace.classify_run({"infeed": 0.02, "collective": 0.1})
    assert b["class"] == "compute-bound" and b["reasons"] == []
    b = tv_trace.classify_run({"checkpoint": 0.3})
    assert b["class"] == "checkpoint-bound"
    b = tv_trace.classify_run({"recovery": 0.5})
    assert b["class"] == "recovery-bound"
    # several tripped: the largest measured/threshold ratio wins
    b = tv_trace.classify_run({"infeed": 0.16, "collective": 0.9})
    assert b["class"] == "comm-bound" and len(b["reasons"]) == 2


def _write_phase_run(tmp_path, *, infeed_s=0.0, collective_s=0.0,
                     n=20, dur_s=0.1):
    with open(tmp_path / "events-0.jsonl", "w") as f:
        for i in range(n):
            f.write(json.dumps({
                "ev": "train.step", "t": i * dur_s,
                "wall": 1000 + i * dur_s, "pid": 0, "step": i,
                "dur_s": dur_s,
                "compute_s": dur_s - infeed_s - collective_s,
                "collective_s": collective_s,
                "infeed_wait_s": infeed_s}) + "\n")


def test_obs_report_phase_table_and_bottleneck_gate(tmp_path, capsys):
    import tools.obs_report as obs
    _write_phase_run(tmp_path, infeed_s=0.04, dur_s=0.1)   # 40% infeed
    assert obs.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "phase attribution" in out
    assert "per-step phases" in out
    assert "bottleneck: input-bound" in out
    # JSON report carries the classification + fractions
    assert obs.main([str(tmp_path), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)["report"]
    assert rep["bottleneck"]["class"] == "input-bound"
    assert rep["phases"]["fractions"]["infeed_wait"] == pytest.approx(
        0.4, abs=0.01)
    # CI gates: expected class passes, a forbidden class fails
    assert obs.main([str(tmp_path), "--check",
                     "--expect-bottleneck", "input-bound"]) == 0
    capsys.readouterr()
    assert obs.main([str(tmp_path), "--check",
                     "--forbid-bottleneck", "input-bound"]) == 1
    capsys.readouterr()
    assert obs.main([str(tmp_path), "--check",
                     "--expect-bottleneck", "comm-bound"]) == 1
    capsys.readouterr()


def test_obs_report_comm_bound_from_collective_phase(tmp_path, capsys):
    import tools.obs_report as obs
    _write_phase_run(tmp_path, collective_s=0.05, dur_s=0.1)
    assert obs.main([str(tmp_path), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)["report"]
    assert rep["bottleneck"]["class"] == "comm-bound"
    assert rep["phases"]["fractions"]["collective"] == pytest.approx(
        0.5, abs=0.01)


# ---------------------------------------------------------------------------
# StepTelemetry phase wiring
# ---------------------------------------------------------------------------

def test_step_telemetry_phases_into_event_and_registry(tmp_path):
    from distributed_tensorflow_tpu.training.loops import StepTelemetry
    reg = telemetry.MetricsRegistry()
    telemetry.configure(str(tmp_path), process_id=0)
    try:
        st = StepTelemetry(reg=reg)
        st.step_completed(0, loss=1.5, dur_s=0.2,
                          phases={"compute": 0.15, "collective": 0.04,
                                  "ckpt_block": 0.01},
                          overlap_eff=0.8)
    finally:
        telemetry.shutdown()
    [ev] = [e for e in telemetry.read_events(
        telemetry.event_log_path(str(tmp_path), 0))
        if e["ev"] == "train.step"]
    assert ev["compute_s"] == pytest.approx(0.15)
    assert ev["collective_s"] == pytest.approx(0.04)
    assert ev["ckpt_block_s"] == pytest.approx(0.01)
    assert ev["overlap_eff"] == pytest.approx(0.8)
    snap = reg.snapshot()
    assert snap["training/overlap_eff"]["value"] == pytest.approx(0.8)
    assert snap["training/phase/compute_frac"]["count"] == 1


# ---------------------------------------------------------------------------
# trace_report CLI
# ---------------------------------------------------------------------------

def test_trace_report_cli_roundtrip(tmp_path, capsys):
    import tools.trace_report as tr
    for pid, skew in ((0, 0.0), (1, 4.0)):
        with open(tmp_path / f"events-{pid}.jsonl", "w") as f:
            for ev in _synthetic_worker(pid, skew):
                f.write(json.dumps(ev) + "\n")
    assert tr.main([str(tmp_path), "--check"]) == 0
    out = capsys.readouterr().out
    assert "trace written" in out and "trace check ok" in out
    with open(tmp_path / "trace.json") as f:
        trace = json.load(f)
    names = {e.get("name") for e in trace["traceEvents"]}
    assert "train.step" in names and "process_name" in names
    # injected 4s skew recovered in the written offsets
    offs = trace["otherData"]["clock_offsets_s"]
    assert abs(offs["1"] - 4.0) < 0.010


def test_trace_report_check_fails_on_generation_hole(tmp_path, capsys):
    import tools.trace_report as tr
    with open(tmp_path / "events-0.jsonl", "w") as f:
        for ev in _synthetic_worker(0, 0.0):
            f.write(json.dumps(ev) + "\n")
    with open(tmp_path / "events-supervisor.jsonl", "w") as f:
        for g in (0, 1):
            f.write(json.dumps(
                {"ev": "recovery.generation_start", "t": float(g),
                 "wall": 1000.0 + g, "pid": "supervisor",
                 "generation": g}) + "\n")
    assert tr.main([str(tmp_path), "--check"]) == 1
    assert "INCOMPLETE" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# profiler <-> telemetry step correlation (satellite)
# ---------------------------------------------------------------------------

def test_step_marker_shares_step_numbering_with_telemetry(tmp_path):
    """profiler.step_marker(step) stamps the SAME step integer into the
    telemetry stream that StepTelemetry's train.step events carry, so
    XPlane traces and the framework timeline correlate by step."""
    from distributed_tensorflow_tpu.training.loops import StepTelemetry
    from distributed_tensorflow_tpu.utils import profiler
    telemetry.configure(str(tmp_path), process_id=0)
    try:
        st = StepTelemetry(reg=telemetry.MetricsRegistry())
        for step in range(3):
            with profiler.step_marker(step):
                time.sleep(0.001)
            st.step_completed(step, dur_s=0.001)
    finally:
        telemetry.shutdown()
    events = telemetry.read_events(
        telemetry.event_log_path(str(tmp_path), 0))
    markers = [e["step"] for e in events
               if e["ev"] == "profiler.step_marker"]
    steps = [e["step"] for e in events if e["ev"] == "train.step"]
    assert markers == steps == [0, 1, 2]


def test_fleet_phase_summary_from_rollup():
    """aggregate.phase_summary surfaces the fleet's phase fractions and
    overlap efficiency from published registry snapshots — no event
    files needed."""
    from distributed_tensorflow_tpu.telemetry import aggregate
    from distributed_tensorflow_tpu.training.loops import StepTelemetry

    def worker_payload(pid, collective_frac, overlap):
        reg = telemetry.MetricsRegistry()
        st = StepTelemetry(reg=reg)
        for i in range(10):
            st.step_completed(i, dur_s=0.1,
                              phases={"compute": 0.1 * (
                                  1 - collective_frac),
                                  "collective": 0.1 * collective_frac},
                              overlap_eff=overlap)
        return {"pid": pid, "seq": 1, "wall": 0.0,
                "metrics": reg.snapshot()}

    rollup = aggregate.merge_rollup({0: worker_payload(0, 0.3, 0.9),
                                     1: worker_payload(1, 0.5, 0.7)})
    summary = aggregate.phase_summary(rollup)
    assert summary["phases"]["collective"]["count"] == 20
    assert 0.3 <= summary["phases"]["collective"]["p50"] <= 0.5
    assert summary["phases"]["collective"]["p95"] == pytest.approx(
        0.5, abs=0.01)                      # worst worker's tail
    assert summary["overlap_eff"]["mean"] == pytest.approx(0.8)
    assert summary["overlap_eff"]["min"] == pytest.approx(0.7)
