"""Profiling and tracing (≙ tf.profiler surface, SURVEY.md §5.1).

Maps the reference's profiler API onto jax.profiler, which shares the
same XPlane/TraceMe backend (both sit on tsl/profiler):

- ``start(logdir)`` / ``stop()``           ≙ tf.profiler.experimental.start/stop
  (reference: tensorflow/python/profiler/profiler_v2.py:81/:130)
- ``Trace("name")`` scoped annotation      ≙ tf.profiler.experimental.Trace
  (reference trace.py:28; native TraceMe)
- ``start_server(port)`` on each worker +
  ``trace(service_addr, logdir)`` from a
  client                                   ≙ remote/pod profiling
  (reference profiler_v2.py:169 + profiler_client.py) — the multi-host
  TPU profiling shape is kept identical.
- ``annotate_function``                    decorator form of Trace.

Output is XPlane protos under ``<logdir>/plugins/profile/<run>``, viewable
with tensorboard_plugin_profile or xprof — the same toolchain the
reference's traces feed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
import weakref

import jax


@dataclasses.dataclass(frozen=True)
class ProfilerOptions:
    """≙ tf.profiler.experimental.ProfilerOptions (profiler_v2.py:46).

    XLA/JAX's profiler always records host + device + python trace
    levels; the fields are accepted for API parity and the meaningful
    one (``python_tracer_level``) toggles jax's python tracer.
    """
    host_tracer_level: int = 2
    python_tracer_level: int = 1
    device_tracer_level: int = 1
    delay_ms: int = 0


_state = threading.local()


def start(logdir: str, options: ProfilerOptions | None = None) -> None:
    """Start collecting a trace on this host (device + host + python)."""
    options = options or ProfilerOptions()
    create_perfetto = False
    jax.profiler.start_trace(
        logdir,
        create_perfetto_link=create_perfetto,
        create_perfetto_trace=create_perfetto)
    _state.active_logdir = logdir


def stop() -> None:
    """Stop tracing and write the XPlane output."""
    jax.profiler.stop_trace()
    _state.active_logdir = None


@contextlib.contextmanager
def profile(logdir: str, options: ProfilerOptions | None = None):
    start(logdir, options)
    try:
        yield
    finally:
        stop()


class Trace(jax.profiler.TraceAnnotation):
    """Scoped trace annotation visible in the trace viewer.

    ≙ tf.profiler.experimental.Trace (trace.py:28). Usage:

        with Trace("train_step", step_num=i):
            state, metrics = step(state, batch)
    """

    def __init__(self, name: str, **kwargs):
        if kwargs:
            name = name + " " + " ".join(
                f"{k}={v}" for k, v in sorted(kwargs.items()))
        super().__init__(name)


def annotate_function(fn=None, *, name: str | None = None):
    """Decorator: annotate every call of ``fn`` in the profile."""
    if fn is None:
        return functools.partial(annotate_function, name=name)
    label = name or getattr(fn, "__name__", "fn")

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with Trace(label):
            return fn(*args, **kwargs)
    return wrapper


def start_server(port: int):
    """Start the on-demand profiling server on this worker (every host of
    a pod job calls this; a client then requests traces remotely).
    ≙ tf.profiler.experimental.server.start (profiler_v2.py:169)."""
    return jax.profiler.start_server(port)


def stop_server():
    jax.profiler.stop_server()


def _profile_here(logdir: str, duration_ms: int) -> str:
    """Run an on-host profiling session in THIS process (executed on the
    target via remote dispatch)."""
    import time as _time
    import jax as _jax
    with _jax.profiler.trace(logdir):
        _time.sleep(duration_ms / 1000.0)
    return logdir


def trace(target, logdir: str, duration_ms: int = 2000,
          host_tracer_level: int = 2, num_tracing_attempts: int = 1):
    """Collect ``duration_ms`` of profile from ``target`` into ``logdir``.

    ≙ tf.profiler.experimental.client.trace (profiler_client.py), with a
    TPU-native transport: instead of the reference's grpc ProfilerService
    client (a TensorFlow runtime dependency this framework does not
    take), remote collection rides the framework's own control plane —
    the profiling closure is dispatched to the target PROCESS over the
    coordination service (coordinator/remote_dispatch.py; the target must
    run ``remote_dispatch.run_worker_loop``). Traces land in ``logdir``
    (shared filesystem), viewable in TensorBoard/XProf like the
    reference's.

    ``target``: "local"/None = this process; an int = remote process id.
    ``host_tracer_level`` is accepted for reference-API parity (the jax
    session traces host activity at its standard level);
    ``num_tracing_attempts`` retries transient failures.
    """
    del host_tracer_level           # parity knob; jax session default
    last_err = None
    for _ in range(max(1, num_tracing_attempts)):
        try:
            if target in (None, "local"):
                return _profile_here(logdir, duration_ms)
            if isinstance(target, int):
                from distributed_tensorflow_tpu.coordinator \
                    .remote_dispatch import RemoteLane
                return RemoteLane(target).execute(
                    _profile_here, (logdir, duration_ms), {},
                    timeout_s=duration_ms / 1000.0 + 60.0)
            break
        except (RuntimeError, TimeoutError) as e:
            last_err = e
    if last_err is not None:
        raise last_err
    raise TypeError(
        f"target must be 'local' or a process id, got {target!r}; "
        f"address-based collection would need a grpc ProfilerService "
        f"client, which the TPU-native runtime deliberately does not "
        f"depend on")


@contextlib.contextmanager
def step_marker(step: int):
    """Mark a training step boundary (StepMarker shows step time in the
    trace viewer's overview page).

    **Step-number correlation contract:** the ``step_num`` recorded here
    (and by ``Trace("...", step_num=i)`` annotations) is the SAME
    integer the telemetry layer carries — ``StepTelemetry.
    step_completed(step)`` / the ``step`` field of ``train.step`` JSONL
    events. When telemetry is on, the marker additionally emits a
    ``profiler.step_marker`` event stamped with that step, so an XPlane
    trace (this module's output) and the framework timeline
    (``tools/trace_report.py``'s output) can be lined up step-by-step
    even though they come from different clocks. Regression-tested in
    tests/test_profiler.py.
    """
    from distributed_tensorflow_tpu import telemetry as _telemetry
    _telemetry.event("profiler.step_marker", step=int(step))
    with jax.profiler.StepTraceAnnotation("train", step_num=step):
        yield


# ---------------------------------------------------------------------------
# Host input-pipeline telemetry (≙ tf.data's iterator/autotune stats,
# TF/python/data/experimental/ops/stats_ops.py): every concurrent pipeline
# stage (parallel map/interleave, prefetch, infeed) owns a StageStats and
# registers it here, so the bottleneck stage is attributable from counters
# instead of guessed. The four wait channels answer the only question that
# matters — WHO is blocking WHOM:
#
# - ``busy_s``          time the stage spent doing its own work (map fn,
#                       decode, upstream next() for prefetch)
# - ``producer_wait_s`` stage blocked pulling from upstream (upstream is
#                       the bottleneck)
# - ``blocked_put_s``   stage blocked handing off downstream (downstream
#                       is the bottleneck; bounded queue full)
# - ``consumer_wait_s`` the CONSUMER blocked on this stage (THIS stage is
#                       the bottleneck)
# ---------------------------------------------------------------------------

_stage_registry: "list[weakref.ref]" = []
_stage_lock = threading.Lock()


class StageStats:
    """Thread-safe counters for one concurrent pipeline stage."""

    def __init__(self, name: str, *, workers: int | None = None,
                 register: bool = True):
        self.name = name
        self.workers = workers
        self._lock = threading.Lock()
        self._elements = 0
        self._busy_s = 0.0
        self._producer_wait_s = 0.0
        self._blocked_put_s = 0.0
        self._consumer_wait_s = 0.0
        self._queue_depth_sum = 0
        self._queue_samples = 0
        self._first_t: float | None = None
        self._last_t: float | None = None
        if register:
            register_stage(self)

    def record(self, *, elements: int = 0, busy_s: float = 0.0,
               producer_wait_s: float = 0.0, blocked_put_s: float = 0.0,
               consumer_wait_s: float = 0.0,
               queue_depth: int | None = None) -> None:
        now = time.monotonic()
        with self._lock:
            if self._first_t is None:
                self._first_t = now
            self._last_t = now
            self._elements += elements
            self._busy_s += busy_s
            self._producer_wait_s += producer_wait_s
            self._blocked_put_s += blocked_put_s
            self._consumer_wait_s += consumer_wait_s
            if queue_depth is not None:
                self._queue_depth_sum += queue_depth
                self._queue_samples += 1

    def snapshot(self) -> dict:
        with self._lock:
            wall = ((self._last_t - self._first_t)
                    if self._first_t is not None else 0.0)
            return {
                "name": self.name,
                "workers": self.workers,
                "elements": self._elements,
                "busy_s": round(self._busy_s, 6),
                "producer_wait_s": round(self._producer_wait_s, 6),
                "blocked_put_s": round(self._blocked_put_s, 6),
                "consumer_wait_s": round(self._consumer_wait_s, 6),
                "mean_queue_depth": (
                    round(self._queue_depth_sum / self._queue_samples, 3)
                    if self._queue_samples else None),
                "elements_per_sec": (
                    round(self._elements / wall, 2) if wall > 0 else None),
            }


def register_stage(stats: StageStats) -> None:
    """Add a stage to the process-wide telemetry registry (weakly held —
    an abandoned pipeline's stages disappear with it)."""
    with _stage_lock:
        _stage_registry.append(weakref.ref(stats))


def pipeline_stats(prefix: str | None = None) -> "list[dict]":
    """Snapshots of every live registered stage, registration order.
    ``prefix`` filters on the stage name (e.g. ``"map"``)."""
    out = []
    with _stage_lock:
        live = []
        for ref in _stage_registry:
            s = ref()
            if s is not None:
                live.append(ref)
                if prefix is None or s.name.startswith(prefix):
                    out.append(s.snapshot())
        _stage_registry[:] = live
    return out


def clear_pipeline_stats() -> None:
    """Drop all registered stages (test isolation)."""
    with _stage_lock:
        _stage_registry.clear()


def bottleneck_stage() -> dict | None:
    """The stage its consumer waited on the longest — the pipeline's
    measured bottleneck (None when nothing is registered)."""
    snaps = pipeline_stats()
    if not snaps:
        return None
    return max(snaps, key=lambda s: s["consumer_wait_s"])


# -- telemetry bridge -------------------------------------------------------
# Every live pipeline stage (input/dataset.py map/interleave/prefetch,
# training/loops.py infeed) exports through the unified MetricsRegistry:
# registry snapshots — and therefore cross-host fleet rollups and
# tools/obs_report.py — carry the input pipeline's counters without the
# stages giving up their own (weakly-registered) storage.

def _pipeline_collector() -> dict:
    out = {}
    for snap in pipeline_stats():
        stage = snap.get("name", "?")
        for k, v in snap.items():
            if k in ("name", "workers") or v is None:
                continue
            out[f"{stage}/{k}"] = v
    return out


def _register_telemetry_collector():
    from distributed_tensorflow_tpu.telemetry import registry as _treg
    _treg.get_registry().register_collector("input/pipeline",
                                            _pipeline_collector)


_register_telemetry_collector()
