"""The dryrun contract must hold WITHOUT conftest's CPU forcing.

A parent process with no XLA_FLAGS / JAX_PLATFORMS set calls
dryrun_multichip(8), which must succeed via its CPU-pinned child — and
a child that fails must fail the dryrun, with no in-process second try.
"""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.multiprocess
def test_dryrun_multichip_without_env_forcing():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "DTX_DRYRUN_IN_SUBPROCESS")}
    # What this test guards is the ENV layer (the child pins its own
    # platform and device count), not per-program coverage — the CPU-mesh
    # suite compiles every parallelism form already and the driver's own
    # dryrun runs all 7 programs. Two programs (plain + the hybrid
    # dcn/shard_map one) keep the runtime bounded on the 1-core CI box.
    env["DTX_DRYRUN_PROGRAMS"] = "base,hybrid"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"),
         "--dryrun", "8"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    oks = re.findall(r"dryrun_multichip\(8\): .+ ok", proc.stdout)
    assert len(oks) == 2, proc.stdout


def test_child_failure_is_the_failure(monkeypatch):
    """A non-zero child (including the deliberate 1 on the involuntary-
    rematerialization warning) raises; nothing re-runs the programs
    in-process where that warning is not scanned for."""
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as g
    finally:
        sys.path.remove(REPO)
    monkeypatch.delenv("DTX_DRYRUN_IN_SUBPROCESS", raising=False)
    monkeypatch.setattr(g, "_dryrun_in_subprocess", lambda n: 1)

    def no_retry(*a, **k):
        raise AssertionError("in-process retry after a failed child")
    monkeypatch.setattr(g, "_dryrun_programs", no_retry)
    with pytest.raises(RuntimeError, match="child failed"):
        g.dryrun_multichip(8)
