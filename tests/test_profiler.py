"""Profiler/tracing subsystem: trace collection produces XPlane output."""

import glob
import os

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.utils import profiler


def test_trace_produces_xplane(tmp_path):
    logdir = str(tmp_path / "profile")
    with profiler.profile(logdir):
        with profiler.Trace("annotated_matmul", step=1):
            x = jnp.ones((64, 64))
            jax.block_until_ready(x @ x)
    produced = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                         recursive=True)
    assert produced, f"no xplane output under {logdir}"


def test_step_marker_and_decorator(tmp_path):
    logdir = str(tmp_path / "profile2")

    @profiler.annotate_function
    def work():
        return jax.block_until_ready(jnp.ones((32, 32)) * 2)

    with profiler.profile(logdir):
        for i in range(2):
            with profiler.step_marker(i):
                work()
    assert glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)


def test_options_accepted():
    opts = profiler.ProfilerOptions(host_tracer_level=3)
    assert opts.host_tracer_level == 3


def test_local_trace_collection(tmp_path, devices):
    """trace(target='local') runs an on-host session and writes a trace
    (the remote form dispatches the same closure over remote_dispatch)."""
    import os
    from distributed_tensorflow_tpu.utils import profiler
    profiler.trace("local", str(tmp_path), duration_ms=50)
    found = []
    for root, _dirs, files in os.walk(tmp_path):
        found.extend(files)
    assert found, "no trace files written"


def test_trace_rejects_address_targets():
    import pytest
    from distributed_tensorflow_tpu.utils import profiler
    with pytest.raises(TypeError, match="grpc ProfilerService"):
        profiler.trace("host:6009", "/tmp/x")


