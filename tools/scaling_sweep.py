#!/usr/bin/env python
"""Device-count scaling sweep + CI gate (ISSUE 6).

Runs ``bench.py --scaling`` in a subprocess pinned to a virtual-device
CPU mesh (``JAX_PLATFORMS=cpu`` +
``XLA_FLAGS=--xla_force_host_platform_device_count=N``), then gates:

1. every row carries an ``efficiency_pct`` (or pipeline ``vs_gpipe``)
   column and the dp transformer curve exists at {1,2,4,8} devices;
2. efficiency-curve monotonicity sanity vs the PREVIOUS round's
   ``SCALING_r*.json`` when one exists — no (workload, devices[,
   schedule, technique]) row may regress more than
   ``--regression-frac`` (10% default) in throughput (same
   ``timing_era`` only — rounds captured on a different-speed host
   don't gate each other's raw throughput; memfrontier param floors
   are host-invariant and always gate);
3. telemetry wiring: one ``scaling.row`` event per row must land in the
   run's event log (``DTX_TELEMETRY_DIR`` is set for the child;
   bench.py emits through ``telemetry.event``);
4. memory frontier (ISSUE 18): the ``memfrontier`` rows must show
   ZeRO-2 + activation offload training >= 2x the replicated
   baseline's max trainable params at the same device count, with the
   frontier config proven to step and a per-technique
   ``step_time_mult`` tax column (floor-gated in bench_trend, not
   throughput-gated — these rows carry no throughput);
5. interleaved 1F1B: on the ``transformer-pp-il`` rows the
   interleaved-v2 measured AND analytic bubble fractions must undercut
   plain 1F1B's at pp=4 (same-run pp=1 baseline only).

    python tools/scaling_sweep.py --out SCALING_r07.json

Exit code 0 = all gates green. Writes the curve JSON to ``--out``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def previous_round_file(out_path: str) -> str | None:
    rounds = sorted(glob.glob(os.path.join(REPO, "SCALING_r*.json")))
    rounds = [p for p in rounds
              if os.path.abspath(p) != os.path.abspath(out_path)]
    return rounds[-1] if rounds else None


def row_key(row: dict) -> tuple:
    return (row.get("workload"), row.get("metric"), row.get("devices"),
            row.get("schedule"), row.get("technique"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "SCALING_run.json"),
                    help="where to write the curve JSON "
                         "(check in as SCALING_r<NN>.json)")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU device count for the sweep")
    ap.add_argument("--regression-frac", type=float, default=0.10,
                    help="max allowed per-row throughput regression vs "
                         "the previous round's file")
    ap.add_argument("--keep-telemetry", action="store_true",
                    help="print the telemetry dir instead of using a "
                         "temp dir")
    args = ap.parse_args()

    tdir = (os.path.join(REPO, ".cache", "scaling_telemetry")
            if args.keep_telemetry else
            tempfile.mkdtemp(prefix="dtx_scaling_telemetry_"))
    os.makedirs(tdir, exist_ok=True)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + f" --xla_force_host_platform_device_count="
                     f"{args.devices}"),
        DTX_TELEMETRY_DIR=tdir,
    )
    cmd = [sys.executable, os.path.join(REPO, "bench.py"), "--scaling",
           "--out", args.out, "--max-devices", str(args.devices)]
    print("scaling_sweep:", " ".join(cmd), flush=True)
    rc = subprocess.run(cmd, env=env, check=False).returncode
    if rc != 0:
        print(f"scaling_sweep: FAIL — bench exited {rc}")
        return 1
    with open(args.out) as f:
        result = json.load(f)
    rows = result["rows"]

    failures = []

    # gate 1: curve shape
    dp_rows = [r for r in rows if r["workload"] in ("transformer",)
               and r.get("metric") == "tokens_per_sec"]
    dp_counts = sorted(r["devices"] for r in dp_rows)
    want = [c for c in (1, 2, 4, 8) if c <= args.devices]
    if dp_counts != want:
        failures.append(f"transformer dp curve has device counts "
                        f"{dp_counts}, expected {want}")
    # every row must carry SOME efficiency-ish column: dp curves use
    # efficiency_pct, pipeline rows vs_gpipe / vs_1f1b, memory-frontier
    # rows the per-technique step_time_mult tax
    eff_cols = ("efficiency_pct", "vs_gpipe", "vs_1f1b", "step_time_mult")
    for r in rows:
        if not any(c in r for c in eff_cols):
            failures.append(f"row missing efficiency column: {row_key(r)}")

    # gate 2: monotonicity sanity vs the previous round
    prev_path = previous_round_file(args.out)
    if prev_path:
        with open(prev_path) as f:
            prev_data = json.load(f)
        prev = {row_key(r): r for r in prev_data["rows"]}
        same_era = (prev_data.get("timing_era")
                    == result.get("timing_era"))
        if not same_era:
            print(f"scaling_sweep: host era changed "
                  f"({prev_data.get('timing_era')!r} -> "
                  f"{result.get('timing_era')!r}) — absolute-"
                  f"throughput regression vs "
                  f"{os.path.basename(prev_path)} skipped (PR 14 "
                  f"rule); floors and ratios still gate")
        for r in rows:
            p = prev.get(row_key(r))
            if p is None:
                continue
            # throughput rows regress on throughput (same host era
            # only); memory-frontier rows carry no throughput — their
            # floor is the max trainable param count, host-invariant
            field = ("throughput" if "throughput" in r
                     else "max_trainable_params")
            if field == "throughput" and not same_era:
                continue
            if field not in r or field not in p:
                continue
            floor = p[field] * (1.0 - args.regression_frac)
            if r[field] < floor:
                failures.append(
                    f"{row_key(r)}: {field} {r[field]} "
                    f"regressed >{args.regression_frac:.0%} vs "
                    f"{p[field]} in {os.path.basename(prev_path)}")
        print(f"scaling_sweep: compared {len(rows)} rows against "
              f"{os.path.basename(prev_path)}")
    else:
        print("scaling_sweep: no previous SCALING_r*.json — "
              "regression gate skipped")

    # gate 1b: phase breakdown + overlap (ISSUE 8) — multi-device
    # transformer rows must carry measured attribution, not just
    # throughput, with sane ranges
    for r in dp_rows:
        if r["devices"] == 1:
            continue
        for field in ("compute_frac", "collective_frac",
                      "infeed_wait_frac", "overlap_eff"):
            if field not in r:
                failures.append(f"{row_key(r)}: missing phase field "
                                f"{field!r}")
        eff = r.get("overlap_eff")
        if eff is not None and not (0.0 <= eff <= 1.0):
            failures.append(f"{row_key(r)}: overlap_eff {eff} outside "
                            f"[0, 1]")
        cf, xf = r.get("compute_frac"), r.get("collective_frac")
        if isinstance(cf, (int, float)) and isinstance(xf, (int, float)) \
                and cf + xf > 1.02:
            failures.append(f"{row_key(r)}: compute_frac {cf} + "
                            f"collective_frac {xf} > 1")

    # gate 4: memory frontier (ISSUE 18) — ZeRO-2 + activation offload
    # must train >= 2x the replicated baseline's params at the same
    # device count, every frontier row must have actually stepped, and
    # each technique reports its step-time tax
    mf_rows = {r.get("technique"): r for r in rows
               if r.get("workload") == "memfrontier"}
    if mf_rows:
        for tech, r in mf_rows.items():
            if not r.get("steps_ok"):
                failures.append(f"memfrontier {tech}: frontier config "
                                f"did not step")
            if "step_time_mult" not in r:
                failures.append(f"memfrontier {tech}: missing "
                                f"step_time_mult tax column")
        rep = mf_rows.get("replicated")
        top = mf_rows.get("zero2+offload")
        if rep is None or top is None:
            failures.append("memfrontier rows missing replicated or "
                            "zero2+offload technique")
        elif rep["devices"] != top["devices"]:
            failures.append("memfrontier replicated vs zero2+offload "
                            "compared at different device counts")
        elif top["max_trainable_params"] < 2 * rep["max_trainable_params"]:
            failures.append(
                f"memfrontier: zero2+offload trains "
                f"{top['max_trainable_params']} params vs replicated "
                f"{rep['max_trainable_params']} — below the 2x bar")

    # gate 5: interleaved 1F1B (ISSUE 18) — at pp=4 the measured bubble
    # of interleaved-v2 must undercut plain 1F1B's, and each row's
    # analytic fraction must be present for the README table
    il_rows = {r.get("schedule"): r for r in rows
               if r.get("workload") == "transformer-pp-il"}
    if il_rows:
        plain = il_rows.get("1f1b")
        il = il_rows.get("interleaved-v2")
        if plain is None or il is None:
            failures.append("transformer-pp-il rows missing 1f1b or "
                            "interleaved-v2 schedule")
        else:
            for r in (plain, il):
                if "bubble_analytic" not in r or "measured_bubble" not in r:
                    failures.append(f"transformer-pp-il {r['schedule']}: "
                                    f"missing bubble columns")
            if (il.get("measured_bubble", 1.0)
                    >= plain.get("measured_bubble", 0.0)):
                failures.append(
                    f"interleaved-v2 measured bubble "
                    f"{il.get('measured_bubble')} not below plain 1F1B's "
                    f"{plain.get('measured_bubble')}")
            if (il.get("bubble_analytic", 1.0)
                    >= plain.get("bubble_analytic", 0.0)):
                failures.append("interleaved-v2 analytic bubble not "
                                "below plain 1F1B's")

    # gate 3: scaling.* telemetry wiring
    os.environ["JAX_PLATFORMS"] = "cpu"   # import-safe off-TPU
    from distributed_tensorflow_tpu import telemetry
    ev_path = telemetry.event_log_path(tdir, 0)
    try:
        events = telemetry.read_events(ev_path)
    except OSError:
        events = []
    scaling_events = [e for e in events if e.get("ev") == "scaling.row"]
    if len(scaling_events) != len(rows):
        failures.append(f"expected {len(rows)} scaling.row telemetry "
                        f"events, found {len(scaling_events)} in "
                        f"{ev_path}")

    if failures:
        for msg in failures:
            print(f"scaling_sweep: FAIL — {msg}")
        return 1
    eff8 = next((r["efficiency_pct"] for r in dp_rows
                 if r["devices"] == max(dp_counts)), None)
    print(f"scaling_sweep: OK — {len(rows)} rows, "
          f"{len(scaling_events)} telemetry events, "
          f"{max(dp_counts)}-device transformer efficiency {eff8}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
