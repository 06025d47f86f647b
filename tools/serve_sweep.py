#!/usr/bin/env python
"""Serving-bench runner + row-shape gate (SERVING_r*.json).

Runs ``bench.py --serving`` in a subprocess (CPU-pinned unless the env
says otherwise), validates the emitted row against the serving-row
contract, and optionally persists the checked shape as the round's
``SERVING_r<NN>.json`` — the file ``tools/bench_trend.py`` trends and
gates. ``--check FILE`` instead validates an existing file (CI mode:
the checked-in round must still parse and satisfy the contract).

Row contract (what downstream tooling depends on):

- ``metric`` == ``serving_tokens_per_sec``, ``value`` > 0;
- ``extra`` carries ``p50_latency_ms`` <= ``p99_latency_ms`` (both
  > 0), ``qps_target`` > 0, ``qps_achieved`` > 0,
  ``tokens_generated`` > 0, ``n_requests`` > 0, ``seed``;
- every benched request completed: ``qps_achieved`` spans exactly
  ``n_requests`` completions (the bench loop cannot exit otherwise,
  so this is implied by the row existing — the gate checks the fields
  that would expose a silent truncation);
- serving-speed fields (ISSUE 14), when present: ``cache_hit_rate``
  and ``accepted_draft_rate`` in [0, 1]; a row carrying the same-run
  caching-off baseline (``baseline_nocache``) must show the WIN — more
  tokens/s and lower p99 than the baseline — and byte-identical
  outputs (``outputs_match_nocache``); an int8 row's measured
  ``kv_quant_max_logit_err`` must be a finite non-negative number.
- disaggregated rows (ISSUE 16, ``extra.disagg`` true): must carry the
  same-run monolithic baseline (``baseline_monolithic``) with
  byte-identical outputs (``outputs_match_monolithic``), and the gate
  is INVERTED vs the usual more-is-better — decode TBT p99
  (``decode_p99_ms``) must be strictly LOWER than the monolithic
  baseline's at equal chip budget; the migration latency series
  (``migrations`` > 0, finite positive ``migrate_p99_ms``) must be
  present.

Usage::

    python tools/serve_sweep.py                       # run + gate
    python tools/serve_sweep.py --out SERVING_r01.json
    python tools/serve_sweep.py --check SERVING_r01.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REQUIRED_EXTRA = ("p50_latency_ms", "p99_latency_ms", "qps_target",
                  "qps_achieved", "tokens_generated", "n_requests",
                  "seed")


def validate_row(row: dict) -> list[str]:
    """Violation messages for one serving row (empty = ok)."""
    bad = []
    if row.get("metric") != "serving_tokens_per_sec":
        bad.append(f"metric={row.get('metric')!r} != "
                   f"'serving_tokens_per_sec'")
    v = row.get("value")
    if not isinstance(v, (int, float)) or v <= 0:
        bad.append(f"value={v!r} not a positive number")
    extra = row.get("extra")
    if not isinstance(extra, dict):
        return bad + ["extra missing"]
    for k in REQUIRED_EXTRA:
        if k not in extra:
            bad.append(f"extra.{k} missing")
    for k in ("p50_latency_ms", "p99_latency_ms", "qps_target",
              "qps_achieved", "tokens_generated", "n_requests"):
        x = extra.get(k)
        if k in extra and (not isinstance(x, (int, float)) or x <= 0):
            bad.append(f"extra.{k}={x!r} not positive")
    p50, p99 = extra.get("p50_latency_ms"), extra.get("p99_latency_ms")
    if isinstance(p50, (int, float)) and isinstance(p99, (int, float)) \
            and p50 > p99:
        bad.append(f"p50 {p50} > p99 {p99}")
    for k in ("cache_hit_rate", "accepted_draft_rate"):
        x = extra.get(k)
        if x is not None and not (isinstance(x, (int, float))
                                  and 0.0 <= x <= 1.0):
            bad.append(f"extra.{k}={x!r} not in [0, 1]")
    base = extra.get("baseline_nocache")
    if base is not None:
        # the acceptance gate: caching must WIN against its same-run
        # caching-off baseline, and outputs must be byte-identical
        if extra.get("outputs_match_nocache") is not True:
            bad.append("outputs_match_nocache is not true — caching "
                       "changed greedy outputs")
        bt = base.get("tokens_per_sec")
        if isinstance(bt, (int, float)) and isinstance(v, (int, float)) \
                and v <= bt:
            bad.append(f"cache-on tokens/s {v} <= caching-off "
                       f"baseline {bt}")
        bp = base.get("p99_latency_ms")
        if isinstance(bp, (int, float)) and isinstance(p99, (int, float)) \
                and p99 >= bp:
            bad.append(f"cache-on p99 {p99}ms >= caching-off "
                       f"baseline {bp}ms")
    err = extra.get("kv_quant_max_logit_err")
    if err is not None and not (isinstance(err, (int, float))
                                and 0.0 <= err < float("inf")):
        bad.append(f"extra.kv_quant_max_logit_err={err!r} not a "
                   f"finite non-negative number")
    if extra.get("disagg"):
        mono = extra.get("baseline_monolithic")
        if not isinstance(mono, dict):
            bad.append("disagg row missing baseline_monolithic "
                       "(the same-run equal-chip-budget baseline)")
        else:
            if extra.get("outputs_match_monolithic") is not True:
                bad.append("outputs_match_monolithic is not true — "
                           "disaggregation changed greedy outputs")
            dp = extra.get("decode_p99_ms")
            mp = mono.get("decode_p99_ms")
            if not isinstance(dp, (int, float)) or dp <= 0:
                bad.append(f"extra.decode_p99_ms={dp!r} not positive")
            # the INVERTED gate: under the prefill burst the disagg
            # decode tail must beat the monolithic one
            elif isinstance(mp, (int, float)) and dp >= mp:
                bad.append(f"disagg decode p99 {dp}ms >= monolithic "
                           f"baseline {mp}ms — disaggregation did "
                           f"not protect the decode tail")
        n_mig = extra.get("migrations")
        if not isinstance(n_mig, int) or n_mig <= 0:
            bad.append(f"extra.migrations={n_mig!r} not positive — "
                       f"a disagg row without migrations measured "
                       f"nothing")
        mig99 = extra.get("migrate_p99_ms")
        if not (isinstance(mig99, (int, float))
                and 0.0 < mig99 < float("inf")):
            bad.append(f"extra.migrate_p99_ms={mig99!r} not a finite "
                       f"positive number")
    return bad


def validate_file(path: str) -> list[str]:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable ({e})"]
    if data.get("bench") != "serving":
        return [f"{path}: bench={data.get('bench')!r} != 'serving'"]
    rows = data.get("rows")
    if not rows:
        return [f"{path}: no rows"]
    bad = []
    for i, row in enumerate(rows):
        bad += [f"row {i}: {m}" for m in validate_row(row)]
    return bad


def run_bench(out_path: str, qps, requests, seed, telemetry_dir, *,
              prefix_reuse=None, kv_dtype=None, speculative=None,
              disagg=False) -> int:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["DTX_TELEMETRY_DIR"] = telemetry_dir
    cmd = [sys.executable, os.path.join(REPO, "bench.py"), "--serving",
           "--out", out_path, "--seed", str(seed)]
    if disagg:
        cmd += ["--disagg"]
    if qps is not None:
        cmd += ["--qps", str(qps)]
    if requests is not None:
        cmd += ["--requests", str(requests)]
    if prefix_reuse:
        cmd += ["--prefix-reuse", str(prefix_reuse)]
    if kv_dtype:
        cmd += ["--kv-dtype", kv_dtype]
    if speculative:
        cmd += ["--speculative", str(speculative)]
    proc = subprocess.run(cmd, cwd=REPO, env=env,
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT)
    sys.stdout.write(proc.stdout.decode(errors="replace"))
    return proc.returncode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", metavar="FILE", default=None,
                    help="validate an existing SERVING_r*.json instead "
                         "of running the bench")
    ap.add_argument("--out", default=None,
                    help="persist the gated result (e.g. "
                         "SERVING_r01.json)")
    ap.add_argument("--qps", type=float, default=None)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefix-reuse", type=float, default=None,
                    help="forward to bench.py --serving: shared-prefix "
                         "workload fraction (enables prefix caching + "
                         "the same-run caching-off baseline gate)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("f32", "bf16", "int8"))
    ap.add_argument("--speculative", type=int, default=None,
                    metavar="K")
    ap.add_argument("--disagg", action="store_true",
                    help="forward to bench.py --serving: the "
                         "disaggregated prefill/decode burst bench "
                         "(inverted decode-p99 gate vs the same-run "
                         "monolithic baseline)")
    args = ap.parse_args(argv)

    if args.check:
        bad = validate_file(args.check)
        if bad:
            for m in bad:
                print(f"serve_sweep: GATE FAILED — {m}", file=sys.stderr)
            return 1
        print(f"serve_sweep: OK — {args.check} satisfies the "
              f"serving-row contract")
        return 0

    tmp = tempfile.mkdtemp(prefix="dtx_serve_sweep_")
    out_path = args.out or os.path.join(tmp, "serving.json")
    rc = run_bench(out_path, args.qps, args.requests, args.seed, tmp,
                   prefix_reuse=args.prefix_reuse,
                   kv_dtype=args.kv_dtype,
                   speculative=args.speculative,
                   disagg=args.disagg)
    if rc != 0:
        print(f"serve_sweep: bench.py --serving failed (rc={rc})",
              file=sys.stderr)
        return 1
    bad = validate_file(out_path)
    # the bench must also have emitted its serving.row telemetry event
    # (the obs pipeline's hook) into the run dir we configured
    sys.path.insert(0, REPO)
    from distributed_tensorflow_tpu.telemetry.events import read_run
    rows_seen = sum(
        1 for events in read_run(tmp).values()
        for ev in events if ev.get("ev") == "serving.row")
    if rows_seen == 0:
        bad.append("no serving.row telemetry event recorded")
    if bad:
        for m in bad:
            print(f"serve_sweep: GATE FAILED — {m}", file=sys.stderr)
        return 1
    print(f"serve_sweep: OK — row gated"
          + (f", persisted to {args.out}" if args.out else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
