"""Structured run events: append-only JSONL spans/events.

The run-level "what happened" record: every process of a job writes an
append-only JSONL file of structured events with monotonic timestamps —
step completions, dispatch retries, checkpoint spans, fault firings.
``tools/obs_report.py`` renders a finished run's files into a
human-readable report; the stall detector and cross-host aggregation
consume the same stream live.

Record format (one JSON object per line)::

    {"ev": "train.step", "t": 12.034561, "wall": 1755312000.2,
     "pid": 0, "dur_s": 0.0312, "step": 7, "loss": 2.31}

- ``ev``    event name, dotted namespace (``train.step``,
  ``dispatch.retry``, ``checkpoint.save``, ``stall.suspected``)
- ``t``     monotonic seconds since this process's log was opened —
  strictly ordered within a file regardless of wall-clock steps
- ``wall``  wall time (cross-host correlation, human display)
- ``pid``   the process id in the cluster (jax.process_index vintage)
- ``dur_s`` present for span-end events: the span's duration

API::

    telemetry.configure(logdir)          # or env DTX_TELEMETRY_DIR
    telemetry.event("dispatch.retry", worker=3)
    with telemetry.span("checkpoint.save", path=p):
        ...                              # emits dur_s on exit

With no log configured — the production default — ``event`` is a single
module-global None check (same contract as resilience/faults.fire).
``span`` is, besides, the program's one trace annotation: it always
enters a ``jax.profiler.TraceAnnotation``, which is one flag test in
C++ while no profiler session runs and, while one does, puts the span
into the profiler's trace on the clock of the device planes
(:func:`span`).

Reading back: :func:`read_events` parses a file, tolerating a torn
final line (a crashed process mid-write) but refusing mid-file
corruption — the distinction ``obs_report --check`` enforces.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation


class EventLogCorruptError(ValueError):
    """A JSONL event file is corrupt before its final line (torn tails
    are expected from crashed writers; mid-file damage is not)."""


#: Env var: rotate a process's events.jsonl once it exceeds this many
#: bytes (``events.jsonl`` -> ``events.jsonl.1``, older segments shift
#: up). Unset/0 = never rotate (the pre-rotation behavior).
ENV_ROTATE_BYTES = "DTX_TELEMETRY_ROTATE_BYTES"


class EventLog:
    """Append-only JSONL event writer for one process.

    One file handle per process, all writes serialized under a lock and
    written as complete lines (a reader can never observe a half
    record except the final line of a crashed writer).

    **Rotation:** with ``max_bytes`` set (arg, or the
    ``DTX_TELEMETRY_ROTATE_BYTES`` env var spawned children inherit),
    the file rotates to ``<path>.1`` when a write pushes it past the
    cap (``.1`` -> ``.2`` and so on shift up first), so a long-lived
    serving replica's log stays size-capped per segment.
    :func:`read_events` transparently chains the rotated segments back
    in chronological order — trace/obs reports are unchanged. Rotation
    happens at a line boundary, so rotated segments are always whole.
    """

    def __init__(self, path: str, process_id: "int | str | None" = None,
                 run_id: str | None = None,
                 max_bytes: "int | None" = None):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.path = path
        self.process_id = process_id if process_id is not None else 0
        if max_bytes is None:
            try:
                max_bytes = int(os.environ.get(ENV_ROTATE_BYTES, "0"))
            except ValueError:
                max_bytes = 0
        self.max_bytes = max_bytes or 0
        try:
            self._size = os.path.getsize(path)
        except OSError:
            self._size = 0
        self._lock = threading.Lock()
        # line-buffered: every complete event line reaches the OS as it
        # is written, so a process that dies hard (SIGKILL, os._exit —
        # exactly the processes whose last events matter most) loses at
        # most the line being written, never a buffer of whole events
        self._f: io.TextIOBase | None = open(path, "a", buffering=1,
                                             encoding="utf-8")
        self._t0 = time.monotonic()
        self._last_t = 0.0
        # Elastic-cluster generation stamp: a reformed cluster's restarted
        # process appends to the SAME events-<pid>.jsonl, so the trace
        # assembler needs each record to say which incarnation wrote it.
        # Generation 0 (non-elastic default) stays unstamped — byte-
        # identical records to before.
        try:
            from distributed_tensorflow_tpu.cluster import elastic
            self._gen = elastic.generation()
        except Exception:
            self._gen = 0
        if run_id:
            self.event("run.start", run_id=run_id)

    # -- write ------------------------------------------------------------
    def event(self, name: str, **fields):
        """Append one structured event; returns the record written."""
        rec = {"ev": name}
        with self._lock:
            if self._f is None:
                return None
            # monotonic within the file even if time.monotonic were to
            # be adjusted (it can't go backwards, but clamp anyway so
            # the file-level invariant is unconditional)
            t = time.monotonic() - self._t0
            if t < self._last_t:
                t = self._last_t
            self._last_t = t
            rec["t"] = round(t, 6)
            rec["wall"] = round(time.time(), 6)
            rec["pid"] = self.process_id
            if self._gen:
                rec["gen"] = self._gen
            rec.update(fields)
            line = json.dumps(rec) + "\n"
            self._f.write(line)
            self._size += len(line)
            if self.max_bytes and self._size > self.max_bytes:
                self._rotate_locked()
        return rec

    def _rotate_locked(self):
        """Shift rotated segments up and start a fresh file (caller
        holds the lock; the write that crossed the cap is complete, so
        every segment ends at a line boundary)."""
        self._f.flush()
        self._f.close()
        n = 1
        while os.path.exists(f"{self.path}.{n}"):
            n += 1
        for i in range(n, 1, -1):
            os.replace(f"{self.path}.{i - 1}", f"{self.path}.{i}")
        os.replace(self.path, f"{self.path}.1")
        self._f = open(self.path, "a", buffering=1, encoding="utf-8")
        self._size = 0

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        """Scoped span: emits ``<name>`` at exit with ``dur_s`` (and
        ``error`` when the body raised). Yields a dict the body may add
        result fields to (e.g. ``sp["bytes"] = n``)."""
        extra: dict = {}
        t0 = time.perf_counter()
        try:
            yield extra
        except BaseException as e:
            extra["error"] = f"{type(e).__name__}: {e}"
            raise
        finally:
            merged = {"dur_s": round(time.perf_counter() - t0, 6)}
            merged.update(fields)
            merged.update(extra)        # body-added fields win; never a
            self.event(name, **merged)  # duplicate-kwarg TypeError here

    def flush(self):
        with self._lock:
            if self._f is not None:
                self._f.flush()

    def close(self):
        with self._lock:
            if self._f is not None:
                self._f.flush()
                self._f.close()
                self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ---------------------------------------------------------------------------
# Process-wide log (the faults.py activation pattern: a single global,
# None = disabled = zero overhead).
# ---------------------------------------------------------------------------

_LOG: EventLog | None = None
_LOG_LOCK = threading.Lock()

#: Env var children of multi_process_runner inherit: a directory to
#: write per-process event logs into (file name carries the process id).
ENV_TELEMETRY_DIR = "DTX_TELEMETRY_DIR"


def _default_process_id() -> int:
    # jax.process_index() without forcing backend init in processes that
    # never initialize jax.distributed (single-host tools, tests).
    try:
        import jax
        if jax._src.distributed.global_state.client is not None:
            return jax.process_index()
    except Exception:
        pass
    # multi_process_runner children: the task index is injected before
    # jax.distributed comes up, so env-activated logs in a freshly
    # spawned cluster task land in per-task files instead of all
    # colliding on events-0.jsonl
    for var in ("DTX_TASK_ID", "DTX_MPR_TASK_INDEX"):
        try:
            return int(os.environ[var])
        except (KeyError, ValueError):
            continue
    return 0


def event_log_path(logdir: str, process_id: int) -> str:
    return os.path.join(logdir, f"events-{process_id}.jsonl")


def configure(logdir: str, process_id: int | None = None,
              run_id: str | None = None) -> EventLog:
    """Open (or replace) the process-wide event log under ``logdir``.
    Each process writes its own ``events-<pid>.jsonl``."""
    global _LOG
    pid = process_id if process_id is not None else _default_process_id()
    with _LOG_LOCK:
        if _LOG is not None:
            _LOG.close()
        _LOG = EventLog(event_log_path(logdir, pid), process_id=pid,
                        run_id=run_id)
        return _LOG


def shutdown():
    """Close and detach the process-wide log (back to zero-overhead)."""
    global _LOG
    with _LOG_LOCK:
        if _LOG is not None:
            _LOG.close()
        _LOG = None


def get_event_log() -> EventLog | None:
    return _LOG


def enabled() -> bool:
    """True when a process-wide event log is configured. Call sites with
    non-trivial field construction guard on this; plain sites just call
    :func:`event` (a no-op without a log)."""
    return _LOG is not None


def recording() -> bool:
    """True when a span's fields go anywhere: a profiler session is
    active or an event log is configured. Span sites compute a field
    that costs more than a ``len()`` only under this."""
    return _LOG is not None or TraceAnnotation.is_enabled()


def event(name: str, **fields):
    """Module-level event against the process-wide log; no-op (one
    None check) when telemetry is off."""
    log = _LOG
    if log is None:
        return None
    return log.event(name, **fields)


def _stats(fields: dict) -> dict:
    """``fields`` as a trace annotation carries them: ``None`` left out
    (the annotation would write the word), everything but a number as
    its ``str``."""
    return {k: v if isinstance(v, (int, float)) else str(v)
            for k, v in fields.items() if v is not None}


@contextlib.contextmanager
def span(name: str, **fields):
    """The one way the program opens a span. Yields a dict the body may
    add result fields to (``sp["bytes"] = n``).

    The span is a ``jax.profiler.TraceAnnotation``: while a profiler
    session is active it lands in the trace's host plane with ``fields``
    and the body's additions as its stats, on the clock the device
    planes use, so idle gaps of the chip can be given to it; with no
    session it is one flag test and the fields are never formatted.
    With an event log configured it also writes the JSONL span record
    of :meth:`EventLog.span`.

    A span names no parent. Parentage is nesting in time on one thread:
    a span that starts and ends inside another on the same thread is
    its child (every span inside an engine step nests in that step's
    ``serve.step``, which carries ``step=``). Spans of one request share
    ``id=<request id>`` and ``span_id=request_span_id(id)``."""
    log = _LOG
    traced = TraceAnnotation.is_enabled()
    with TraceAnnotation(name, **(_stats(fields) if traced else {})) as ann:
        extra: dict = {}
        try:
            if log is None:
                yield extra
            else:
                with log.span(name, **fields) as extra:
                    yield extra
        finally:
            if traced and extra:
                ann.set_metadata(**_stats(extra))


# Env activation (≙ faults.DTX_FAULT_SCHEDULE): spawned multi-process
# children inherit the telemetry directory for free.
_env = os.environ.get(ENV_TELEMETRY_DIR)
if _env:
    configure(_env)
del _env


# ---------------------------------------------------------------------------
# Reading back
# ---------------------------------------------------------------------------

def rotated_segments(path: str) -> list[str]:
    """Rotated siblings of an event file in CHRONOLOGICAL order
    (``path.N`` is older than ``path.N-1``; the live ``path`` itself is
    newest and not included)."""
    import glob
    import re
    segs = []
    for p in glob.glob(glob.escape(path) + ".*"):
        m = re.match(re.escape(path) + r"\.(\d+)$", p)
        if m:
            segs.append((int(m.group(1)), p))
    return [p for _, p in sorted(segs, reverse=True)]


def _read_one(path: str, *, tolerate_torn_tail: bool) -> list[dict]:
    out: list[dict] = []
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        lines = f.read().split("\n")
    if lines and lines[-1] == "":
        lines.pop()                     # trailing newline artifact
    for i, line in enumerate(lines):
        try:
            rec = json.loads(line)
            if not isinstance(rec, dict):
                raise ValueError("event record is not an object")
        except ValueError as e:
            if i == len(lines) - 1 and tolerate_torn_tail:
                break                   # torn tail: crashed mid-write
            raise EventLogCorruptError(
                f"{path}:{i + 1}: malformed event line: {e}") from e
        out.append(rec)
    return out


def read_events(path: str, *, tolerate_torn_tail: bool = True,
                include_rotated: bool = True) -> list[dict]:
    """Parse one JSONL event file (chaining any rotated segments).

    A torn FINAL line (crashed writer) is dropped when
    ``tolerate_torn_tail`` (the default); malformed content anywhere
    before the final line raises :class:`EventLogCorruptError` —
    mid-file corruption means the file cannot be trusted at all.

    When the writer rotated (``<path>.N`` siblings exist), the rotated
    segments are read first in chronological order — transparently, so
    every consumer of the base file sees the full history. Rotation
    happens at line boundaries, so only the LIVE file may have a torn
    tail; a malformed line inside a rotated segment is corruption.
    """
    out: list[dict] = []
    if include_rotated:
        for seg in rotated_segments(path):
            out.extend(_read_one(seg, tolerate_torn_tail=False))
    out.extend(_read_one(path, tolerate_torn_tail=tolerate_torn_tail))
    return out


def read_run(logdir: str, *, tolerate_torn_tail: bool = True) -> dict:
    """All per-process event files under ``logdir``:
    ``{process_id: [events...]}`` keyed by the id in the file name
    (numeric ids as ints; a recovery supervisor's file keys as the
    string ``"supervisor"``)."""
    import glob
    import re
    out: dict = {}
    for path in sorted(glob.glob(os.path.join(logdir, "events-*.jsonl"))):
        m = re.search(r"events-([A-Za-z0-9_]+)\.jsonl$", path)
        suffix = m.group(1) if m else str(len(out))
        pid = int(suffix) if suffix.isdigit() else suffix
        out[pid] = read_events(path, tolerate_torn_tail=tolerate_torn_tail)
    return out
