#!/usr/bin/env python
"""Attribute tier-1 suite time: per-test/per-file durations + true-cold
compile cost (ISSUE 3 CI satellite).

Two modes:

1. ``--log`` parses a pytest ``--durations=N`` report (the tier-1
   command with ``--durations=60`` appended) and aggregates by file —
   the cheap way to find WARM hotspots from a log the driver already
   produced::

       python tools/suite_profile.py --log /tmp/_t1.log

2. ``--cold FILE [FILE ...]`` times the named test files against a
   FRESH compilation cache (scratch ``JAX_COMPILATION_CACHE_DIR``),
   i.e. the cost a cache-wiped driver round actually pays.
   Compile-bound files show a large cold/warm gap; IO/sleep-bound
   files do not::

       python tools/suite_profile.py --cold tests/test_transformer.py

Measured on this box (2026-08, 1-core CPU CI, jax 0.4.37): cold cost is
SPREAD — ~60s/file across the kernel-heavy files (sequence_parallel,
chaos, transformer), reference_parity ~35s, while the conformance
matrix is only ~6s cold (the r5 "conformance 26×N dominates cold"
attribution no longer holds here). Tiering therefore targets
parametrized DUPLICATES (e.g. the causal=False sequence-parallel
variants) rather than whole files, and the repo-local persistent cache
(tests/conftest.py) remains the main cold-round defense.
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import subprocess
import sys
import tempfile
import time

_DURATION_RE = re.compile(
    r"^\s*(\d+\.\d+)s\s+(call|setup|teardown)\s+(\S+?)::(\S+)")


def parse_durations(log_path: str):
    """(seconds, phase, file, test) rows from a --durations report."""
    rows = []
    with open(log_path, errors="replace") as f:
        for line in f:
            m = _DURATION_RE.match(line)
            if m:
                rows.append((float(m.group(1)), m.group(2),
                             m.group(3), m.group(4)))
    return rows


def report_log(log_path: str, top: int, tier_threshold: float) -> int:
    rows = parse_durations(log_path)
    if not rows:
        print(f"no '--durations' rows found in {log_path}; rerun tier-1 "
              f"with --durations=60 appended")
        return 1
    by_file: dict = collections.defaultdict(float)
    for sec, _phase, fname, _test in rows:
        by_file[fname] += sec
    print(f"== per-file total (top {top}; only tests the durations "
          f"report listed) ==")
    for fname, sec in sorted(by_file.items(), key=lambda kv: -kv[1])[:top]:
        print(f"{sec:8.1f}s  {fname}")
    print(f"\n== tier candidates (single test >= {tier_threshold:.0f}s; "
          f"mark @pytest.mark.slow or split) ==")
    hits = [(sec, f"{fname}::{test} [{phase}]")
            for sec, phase, fname, test in rows if sec >= tier_threshold]
    for sec, name in sorted(hits, reverse=True):
        print(f"{sec:8.1f}s  {name}")
    if not hits:
        print("(none)")
    return 0


def time_cold(files, timeout_s: int) -> int:
    """Run each file twice — fresh cache, then the same (now-warm)
    cache — and print cold/warm/compile-share."""
    print(f"{'file':<42} {'cold':>8} {'warm':>8} {'compile':>9}")
    for path in files:
        with tempfile.TemporaryDirectory(prefix="dtx_cold_") as cache:
            env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
            times = []
            for _ in range(2):
                t0 = time.monotonic()
                proc = subprocess.run(
                    [sys.executable, "-m", "pytest", path, "-q",
                     "-m", "not slow", "-p", "no:cacheprovider",
                     "-p", "no:randomly"],
                    env=env, capture_output=True, timeout=timeout_s)
                times.append(time.monotonic() - t0)
                if proc.returncode not in (0, 1):   # 1 = test failures
                    print(f"{path:<42} pytest rc={proc.returncode}")
                    break
            else:
                cold, warm = times
                share = (cold - warm) / cold if cold > 0 else 0.0
                print(f"{path:<42} {cold:7.1f}s {warm:7.1f}s "
                      f"{share:8.0%}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--log", help="pytest log containing a "
                                  "--durations report")
    ap.add_argument("--cold", nargs="+", metavar="FILE",
                    help="test files to time cold vs warm")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--tier-threshold", type=float, default=10.0,
                    help="per-test seconds above which to propose "
                         "tiering (default 10)")
    ap.add_argument("--timeout", type=int, default=870,
                    help="per-pytest-run timeout for --cold")
    args = ap.parse_args()
    if not args.log and not args.cold:
        ap.error("need --log and/or --cold")
    rc = 0
    if args.log:
        rc = report_log(args.log, args.top, args.tier_threshold)
    if args.cold:
        rc = time_cold(args.cold, args.timeout) or rc
    return rc


if __name__ == "__main__":
    sys.exit(main())
