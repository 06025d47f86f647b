"""Online streaming trainer (models/online_dlrm.py) + freshness SLO
(telemetry/slo.py) + the supervised end-to-end topology."""

import os

import numpy as np
import pytest

from distributed_tensorflow_tpu.input import stream as st
from distributed_tensorflow_tpu.models import online_dlrm as od
from distributed_tensorflow_tpu.telemetry import slo as tv_slo


def _log(tmp_path, cfg, n, seed=0):
    path = str(tmp_path / "s.log")
    w = st.StreamWriter.open(path)
    while w.next_offset < n:
        k = min(64, n - w.next_offset)
        st.append_chunk(w, st.seeded_events(
            seed, w.next_offset, k, n_users=cfg.n_users,
            n_items=cfg.n_items, n_dense=cfg.n_dense))
    w.close()
    return path


def test_online_trainer_end_to_end(tmp_path):
    cfg = od.OnlineConfig.tiny(batch_size=8)
    path = _log(tmp_path, cfg, 160)
    t = od.OnlineTrainer(cfg, path, str(tmp_path / "ck"),
                         commit_every=4)
    assert t.restore() == 0
    s = t.run(160, idle_timeout_s=2.0)
    assert s["offset"] == 160 and s["events_applied"] == 160
    assert s["commits"] == 5
    assert np.isfinite(s["loss_last"])
    assert s["tables"]["user"]["admissions"] > 0


def test_online_trainer_learns(tmp_path):
    """The loss trends down over the stream — tables are actually
    training through the dynamic membership."""
    cfg = od.OnlineConfig.tiny(batch_size=16)
    path = _log(tmp_path, cfg, 640)
    t = od.OnlineTrainer(cfg, path, str(tmp_path / "ck"),
                         commit_every=10)
    t.restore()
    losses = []
    t.run(640, idle_timeout_s=2.0,
          on_batch=lambda tr: losses.append(None))
    # compare the eval snapshot against an untrained model
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        Checkpoint, latest_checkpoint)
    tmpl = Checkpoint(single_writer=True,
                      online=od.checkpoint_template(cfg))
    flat = tmpl.restore(latest_checkpoint(str(tmp_path / "ck"),
                                          "online"))
    trained = od.eval_snapshot(cfg, od.unpack_restored(flat))
    fresh = od.OnlineTrainer(cfg, path, str(tmp_path / "ck2"))
    untrained = od.eval_snapshot(cfg, fresh._state_nested())
    assert trained < untrained


def test_eval_snapshot_uses_membership(tmp_path):
    cfg = od.OnlineConfig.tiny(batch_size=8)
    path = _log(tmp_path, cfg, 80)
    t = od.OnlineTrainer(cfg, path, str(tmp_path / "ck"),
                         commit_every=5)
    t.restore()
    t.run(80, idle_timeout_s=2.0)
    loss = od.eval_snapshot(cfg, t._state_nested())
    assert np.isfinite(loss)


# ---------------------------------------------------------------------------
# Freshness SLO
# ---------------------------------------------------------------------------

def test_freshness_metric_validation():
    s = tv_slo.SLO("f", "freshness", objective=0.9, threshold_s=2.0)
    assert s.is_bad({"freshness_s": 3.0})
    assert not s.is_bad({"freshness_s": 1.0})
    with pytest.raises(ValueError, match="threshold_s"):
        tv_slo.SLO("f", "freshness", objective=0.9)
    with pytest.raises(ValueError, match="metric"):
        tv_slo.SLO("f", "staleness", objective=0.9, threshold_s=1.0)


def test_default_online_slos_burn_and_records():
    events = {0: [
        {"ev": "stream.snapshot_published", "wall": 10.0 + i,
         "freshness_s": 0.5 if i < 2 else 9.0, "lag_events": 0,
         "offset": i} for i in range(10)]}
    records = tv_slo.freshness_records_from_events(events)
    assert len(records) == 10
    slos = tv_slo.default_online_slos(
        freshness_s=2.0, windows=tv_slo.windows_for_span(10.0))
    report = tv_slo.evaluate_records(records, slos)
    fres = report["freshness_p90"]
    assert fres["bad"] == 8
    assert fres["budget_consumed"] == pytest.approx(8.0)
    # a mostly-stale run burns both windows of the page pair
    assert fres["firing"]
    # a healthy tail re-clears the short window
    healthy = [dict(r, freshness_s=0.1) for r in records]
    report2 = tv_slo.evaluate_records(healthy, slos)
    assert not report2["freshness_p90"]["firing"]


def test_health_report_renders_online_section(tmp_path):
    import json
    import subprocess
    import sys
    run = tmp_path / "run"
    run.mkdir()
    with open(run / "events-0.jsonl", "w") as f:
        for i in range(4):
            f.write(json.dumps({
                "ev": "stream.snapshot_published", "t": float(i),
                "wall": 100.0 + i, "pid": 0, "offset": 16 * (i + 1),
                "freshness_s": 0.2, "lag_events": 0}) + "\n")
            f.write(json.dumps({
                "ev": "train.step", "t": float(i) + 0.5,
                "wall": 100.5 + i, "pid": 0, "step": i,
                "dur_s": 0.4}) + "\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools",
                                      "health_report.py"), str(run)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    text = out.stdout.decode()
    assert out.returncode == 0, text
    assert "freshness_p90" in text
    assert "online: 4 snapshot(s) served" in text


def test_obs_report_renders_online_section(tmp_path):
    import json
    import subprocess
    import sys
    run = tmp_path / "run"
    run.mkdir()
    with open(run / "events-0.jsonl", "w") as f:
        f.write(json.dumps({"ev": "stream.produced", "t": 0.0,
                            "wall": 100.0, "pid": 0,
                            "offset": 64}) + "\n")
        f.write(json.dumps({"ev": "stream.batch_applied", "t": 0.5,
                            "wall": 100.5, "pid": 0, "lo": 0,
                            "hi": 16, "n": 16, "step": 1}) + "\n")
        f.write(json.dumps({"ev": "stream.batch_applied", "t": 0.9,
                            "wall": 100.9, "pid": 0, "lo": 16,
                            "hi": 32, "n": 16, "step": 2}) + "\n")
        f.write(json.dumps({"ev": "stream.commit", "t": 1.0,
                            "wall": 101.0, "pid": 0,
                            "offset": 32, "step": 2}) + "\n")
        f.write(json.dumps({"ev": "embed.update", "t": 1.1,
                            "wall": 101.1, "pid": 0, "table": "user",
                            "capacity": 64, "mapped": 9,
                            "admissions": 9, "evictions": 1,
                            "grows": 0}) + "\n")
        f.write(json.dumps({"ev": "stream.snapshot_published",
                            "t": 1.2, "wall": 101.2, "pid": 0,
                            "offset": 32, "freshness_s": 0.2,
                            "lag_events": 32}) + "\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "obs_report.py"),
         str(run)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    text = out.stdout.decode()
    assert out.returncode == 0, text
    assert "online: 32 event(s) applied" in text
    assert "lag (produced - applied): 32 event(s)" in text
    assert "table user: 9/64 rows mapped" in text


# ---------------------------------------------------------------------------
# The supervised end-to-end topology (heavy: spawns 4 processes) —
# chaos_sweep --online runs the seeded-kill version of this.
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.multiprocess
@pytest.mark.chaos
def test_supervised_online_survives_seeded_kill(tmp_path):
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run_dir = str(tmp_path / "run")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    seed = int(os.environ.get("DTX_CHAOS_SEED", "1"))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "examples", "train_online.py"),
         "--supervised", "--events", "240", "--kill-seed", str(seed),
         "--stream-dir", str(tmp_path / "stream"),
         "--ckpt-dir", str(tmp_path / "ck"),
         "--telemetry-dir", run_dir],
        cwd=repo, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, timeout=280)
    tail = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, tail[-2000:]
    sys.path.insert(0, os.path.join(repo, "tools"))
    from chaos_sweep import _freshness_gate, _stream_accounting_gate
    assert _stream_accounting_gate(run_dir, 240) == []
    assert _freshness_gate(run_dir, 240, 30.0) == []
