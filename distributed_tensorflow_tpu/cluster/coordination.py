"""Coordination-service surface: KV store, barriers, liveness.

TPU-native equivalent of the reference's coordination service
(reference: third_party/xla/.../tsl/distributed_runtime/coordination/
coordination_service.h — task liveness via heartbeats, a distributed KV
store, barriers, and error propagation; SURVEY.md §2.7). The reference
exposes it to Python only indirectly (context.configure_coordination_service);
here it is a first-class API because the rest of the framework builds on
it: multi-host checkpoint commit barriers (checkpoint/checkpoint.py),
preemption agreement (checkpoint/failure_handling.py), and the remote
coordinator's closure/result channel (coordinator/remote_dispatch.py).

Single-process: every operation is served by an in-process fallback with
identical semantics (same code runs under 1 or N processes).
Multi-process: operations delegate to the TSL coordination service that
``jax.distributed.initialize`` connected us to (bootstrap.initialize).
"""

from __future__ import annotations

import collections
import re
import threading
import time

from distributed_tensorflow_tpu.cluster import elastic
from distributed_tensorflow_tpu.resilience import faults


class CoordinationError(RuntimeError):
    """A coordination-service operation failed (timeout, peer error)."""


def _parse_task_id(node) -> "int | None":
    """Task id from a live-nodes entry.

    Formats seen from TSL: int, ``"/job:jax_worker/task:3"``, ``"3"``.
    The task number is parsed from the trailing ``task:<n>`` field (NOT
    by collecting digits — a job name containing a digit, e.g.
    ``jax_worker_2``, must not mangle the id). Unrecognized formats
    return None.
    """
    if isinstance(node, int):
        return node
    s = str(node)
    m = re.search(r"task:(\d+)\s*$", s)
    if m:
        return int(m.group(1))
    if s.strip().isdigit():
        return int(s.strip())
    return None


class BarrierTimeoutError(CoordinationError):
    """``barrier`` timed out waiting for peers — likely a hung or dead
    task (≙ the reference's BarrierError / DeadlineExceeded status)."""


class _LocalService:
    """In-process KV/barrier service with TSL-equivalent semantics.

    Also the backend of the simulated-fleet harness
    (testing/fleet_sim.py), where hundreds of worker THREADS share one
    instance — which is why blocked readers wait on **per-key**
    conditions: the original single shared condition made every ``set``
    wake every blocked reader of every key (O(writers × waiters)
    spurious wakeups per round — at N=1000 simulated workers the reform
    storm, where every worker blocks on the new generation's config key
    while heartbeats keep streaming in, was the worst scaling offender
    the harness exposed). ``stats["waiters_woken"]`` counts real
    wakeups so the fix is testable.
    """

    def __init__(self):
        self._kv: dict[str, bytes] = {}
        self._lock = threading.Lock()
        # key -> [Condition, waiter_count]; entries exist only while a
        # reader is blocked on that key
        self._waiters: dict[str, list] = {}
        self._barriers: dict[str, dict] = {}
        #: coarse service-side counters (ops, wakeups); reads/updates
        #: are lock-protected where it matters for tests
        self.stats = collections.Counter()

    def _notify_key(self, key: str):
        """Wake only the readers blocked on ``key`` (caller holds
        ``_lock``)."""
        w = self._waiters.get(key)
        if w is not None:
            self.stats["waiters_woken"] += w[1]
            w[0].notify_all()

    def set(self, key: str, value: bytes, *, allow_overwrite: bool = True):
        with self._lock:
            if not allow_overwrite and key in self._kv:
                raise CoordinationError(f"key {key!r} already exists")
            self._kv[key] = value
            self.stats["set"] += 1
            self._notify_key(key)

    def get(self, key: str, timeout_s: float) -> bytes:
        deadline = time.monotonic() + timeout_s
        with self._lock:
            self.stats["get"] += 1
            v = self._kv.get(key)
            if v is not None:               # fast path: no condition
                return v
            w = self._waiters.get(key)
            if w is None:
                w = self._waiters[key] = [
                    threading.Condition(self._lock), 0]
            w[1] += 1
            try:
                while key not in self._kv:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0 or not w[0].wait(remaining):
                        raise CoordinationError(
                            f"timed out waiting for key {key!r}")
                return self._kv[key]
            finally:
                w[1] -= 1
                if w[1] <= 0 and self._waiters.get(key) is w:
                    del self._waiters[key]

    def try_get(self, key: str) -> bytes | None:
        with self._lock:
            self.stats["try_get"] += 1
            return self._kv.get(key)

    def dir_get(self, prefix: str) -> list[tuple[str, bytes]]:
        with self._lock:
            self.stats["dir_get"] += 1
            return sorted((k, v) for k, v in self._kv.items()
                          if k.startswith(prefix))

    def delete(self, key: str):
        """Delete ``key`` and (directory-style, matching TSL) any keys
        under ``key/``."""
        with self._lock:
            self.stats["delete"] += 1
            self._kv.pop(key, None)
            for k in [k for k in self._kv if k.startswith(key + "/")]:
                del self._kv[k]

    def increment(self, key: str, amount: int) -> int:
        with self._lock:
            self.stats["increment"] += 1
            cur = int(self._kv.get(key, b"0"))
            cur += amount
            self._kv[key] = str(cur).encode()
            self._notify_key(key)
            return cur

    def num_keys(self) -> int:
        """Live key count — the KV-size observable the lifecycle-GC
        tests bound across reforms (cluster/kv_gc.py)."""
        with self._lock:
            return len(self._kv)

    def barrier(self, name: str, timeout_s: float, n: int,
                participant: int = 0):
        """Block until ``n`` distinct participants reach ``name``.

        ``n <= 1`` passes trivially (the single-process fallback of the
        production agent). A timed-out barrier raises
        :class:`BarrierTimeoutError` NAMING the missing participant ids
        — the supervisor-facing detail the TSL barrier cannot give you,
        and the first thing an operator of an N-worker fleet needs. A
        released barrier name stays released (one-shot, matching TSL);
        use per-round names for repeated synchronization.
        """
        with self._lock:
            st = self._barriers.get(name)
            if st is None:
                st = self._barriers[name] = {
                    "cv": threading.Condition(self._lock),
                    "arrived": set(), "n": n, "done": n <= 1}
            if st["done"]:
                st["arrived"].add(participant)
                return
            st["arrived"].add(participant)
            if len(st["arrived"]) >= st["n"]:
                st["done"] = True
                st["cv"].notify_all()
                return
            deadline = time.monotonic() + timeout_s
            while not st["done"]:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not st["cv"].wait(remaining):
                    if st["done"]:      # released while timing out
                        return
                    missing = sorted(set(range(st["n"])) - st["arrived"])
                    shown = ", ".join(map(str, missing[:8]))
                    if len(missing) > 8:
                        shown += f", ... ({len(missing)} total)"
                    raise BarrierTimeoutError(
                        f"barrier {name!r} timed out after {timeout_s}s: "
                        f"{len(st['arrived'])}/{st['n']} arrived; "
                        f"missing participant(s): [{shown}]")


_LOCAL = _LocalService()


class CoordinationServiceAgent:
    """Client handle to the coordination service.

    ≙ tsl::CoordinationServiceAgent (coordination_service_agent.h). Use
    ``coordination_service()`` to get the process-wide instance; all
    methods are safe to call in single-process mode.
    """

    def __init__(self):
        self._local = _LOCAL
        self._legacy: bool | None = None
        self._inc_hint: dict[str, int] = {}
        #: per-agent KV/barrier op counts ({op_name: n}) — the raw
        #: material of the fleet-scale control-plane cost curves
        #: (testing/fleet_sim.py). Incremented without a lock: each agent
        #: belongs to one worker (exact there); the process-wide
        #: singleton's counts are approximate under thread races, which
        #: is fine for a cost profile.
        self.op_counts = collections.Counter()

    # -- legacy-client compatibility --------------------------------------
    # jaxlib builds whose DistributedRuntimeClient lacks
    # ``key_value_try_get_bytes`` (jax < 0.5) also have a fatal read bug:
    # the bytes/dir get APIs can SEGFAULT the service-hosting process
    # when the key being read was written by the reading process itself
    # (or has been overwritten/deleted) — the binding hands out a view
    # into the in-process store. The string API copies and is safe in
    # every direction. On such clients every point read is routed
    # through string-get first, falling back to bytes-get only for
    # binary values — which in this framework are always written by a
    # PEER process (pickled closures/results), the safe direction.

    def _is_legacy(self, c) -> bool:
        if self._legacy is None:
            self._legacy = not hasattr(c, "key_value_try_get_bytes")
        return self._legacy

    @staticmethod
    def _legacy_get_once(c, key: str, wait_ms: int) -> "bytes | None":
        """One bounded point read on a legacy client; None when absent."""
        try:
            return c.blocking_key_value_get(key, wait_ms).encode()
        except UnicodeDecodeError:
            # present but binary: peer-written here, so bytes-get is safe
            try:
                return c.blocking_key_value_get_bytes(key, wait_ms)
            except Exception:
                return None
        except Exception:
            return None

    # -- identity ---------------------------------------------------------
    @property
    def _client(self):
        import jax
        return jax._src.distributed.global_state.client

    @property
    def is_distributed(self) -> bool:
        return self._client is not None

    @property
    def process_id(self) -> int:
        import jax
        return jax.process_index()

    @property
    def num_processes(self) -> int:
        import jax
        return jax.process_count()

    @property
    def is_chief(self) -> bool:
        return self.process_id == 0

    # -- KV store ---------------------------------------------------------
    # Every key and barrier name is namespaced with the elastic cluster
    # generation (cluster/elastic.py): a supervisor-reformed cluster gets
    # disjoint coordination state from every dead incarnation's, so a
    # straggler's half-written keys / half-met barriers can never leak
    # into the new generation. Generation 0 (the non-elastic default) is
    # unprefixed. Chaos sites fire on the RAW names — fault schedules
    # target logical keys, not incarnation-specific ones.

    def key_value_set(self, key: str, value: bytes | str, *,
                      allow_overwrite: bool = True):
        self.op_counts["set"] += 1
        key = elastic.namespace(key)
        data = value.encode() if isinstance(value, str) else bytes(value)
        c = self._client
        if c is None:
            self._local.set(key, data, allow_overwrite=allow_overwrite)
        else:
            c.key_value_set_bytes(key, data, allow_overwrite=allow_overwrite)

    def key_value_get(self, key: str, timeout_s: float = 60.0) -> bytes:
        """Blocking get: waits until some process sets ``key``."""
        self.op_counts["get"] += 1
        faults.fire("coord.kv_get", tag=key, exc=CoordinationError,
                    msg=f"injected fault: key_value_get({key!r})")
        key = elastic.namespace(key)
        c = self._client
        if c is None:
            return self._local.get(key, timeout_s)
        if self._is_legacy(c):
            deadline = time.monotonic() + timeout_s
            while True:
                v = self._legacy_get_once(c, key, 100)
                if v is not None:
                    return v
                if time.monotonic() >= deadline:
                    raise CoordinationError(
                        f"key_value_get({key!r}) timed out "
                        f"after {timeout_s}s")
        try:
            return c.blocking_key_value_get_bytes(key, int(timeout_s * 1000))
        except Exception as e:                      # XlaRuntimeError
            raise CoordinationError(
                f"key_value_get({key!r}) failed: {e}") from e

    def key_value_try_get(self, key: str) -> bytes | None:
        self.op_counts["try_get"] += 1
        key = elastic.namespace(key)
        c = self._client
        if c is None:
            return self._local.try_get(key)
        if self._is_legacy(c):
            # No non-blocking get on this vintage: a short blocking
            # string-get is semantically identical (None when absent).
            # Without this the bare `except: return None` below would
            # swallow the AttributeError and EVERY try_get-based poller
            # (preemption signal, heartbeats, shutdown acks) would
            # silently see nothing — the failure paths would never fire.
            return self._legacy_get_once(c, key, 50)
        try:
            return c.key_value_try_get_bytes(key)
        except Exception:
            return None

    def key_value_dir_get(self, prefix: str) -> list[tuple[str, bytes]]:
        self.op_counts["dir_get"] += 1
        prefix = elastic.namespace(prefix)
        c = self._client
        if c is None:
            return self._local.dir_get(prefix)
        try:
            return sorted(c.key_value_dir_get_bytes(prefix))
        except Exception:
            return []

    def key_value_delete(self, key: str):
        self.op_counts["delete"] += 1
        key = elastic.namespace(key)
        c = self._client
        if c is None:
            self._local.delete(key)
        else:
            c.key_value_delete(key)

    def key_value_increment(self, key: str, amount: int = 1) -> int:
        """Atomic fetch-add; returns the post-increment value."""
        self.op_counts["increment"] += 1
        key = elastic.namespace(key)
        c = self._client
        if c is None:
            return self._local.increment(key, amount)
        if hasattr(c, "key_value_increment"):
            return c.key_value_increment(key, amount)
        # Older TSL clients: emulate with dense slot claims.
        # InsertKeyValue with allow_overwrite=False IS atomic on the
        # service and each slot key is written exactly once (no
        # mutation, no directory reads — both are landmines on this
        # vintage). Probing forward from a per-process hint costs one
        # fast RPC per taken slot; coordination counters (generations,
        # incarnations) stay tiny. The final value is also published
        # under ``key`` for plain readers; slot keys live under
        # ``key/`` so a directory delete of ``key`` GCs them.
        i = self._inc_hint.get(key, 0)
        if i == 0:
            # Cold start: seed the probe hint from the published value
            # key (one safe string read). Without this, the p-th
            # process to ever increment probed ~p already-taken slots —
            # N processes touching one counter cost O(N^2) RPCs total,
            # the worst per-op scaling offender the fleet harness's
            # cost curves flagged. Seeded, each process pays one read
            # plus O(amount) probes: O(N) total. The hint may lag the
            # true tail (the value key is best-effort); probing forward
            # absorbs the slack.
            v = self._legacy_get_once(c, key, 50)
            if v is not None:
                try:
                    i = max(i, int(v))
                except ValueError:
                    pass
        claimed = 0
        limit = i + 100_000
        while claimed < amount:
            i += 1
            if i > limit:
                raise CoordinationError(
                    f"key_value_increment({key!r}) fallback exhausted "
                    f"{limit} slots")
            try:
                c.key_value_set_bytes(f"{key}/__c__/{i}", b"1",
                                      allow_overwrite=False)
                claimed += 1
            except Exception as e:
                if "ALREADY_EXISTS" not in str(e):
                    raise CoordinationError(
                        f"key_value_increment({key!r}) failed: {e}") from e
        self._inc_hint[key] = i
        # Value key for plain readers (write-direction: safe). A naive
        # publish is racy: a slower peer's SMALLER value can land after
        # ours (lost update — observed as a full-suite flake in the
        # 2-process barrier/increment test, where a reader past an
        # "everyone incremented" barrier still saw a stale total). The
        # slot keys are the ground truth, so close the race with them:
        # after publishing, probe forward for slots claimed by peers
        # and republish the larger tail until a probe issued AFTER our
        # latest publish finds nothing. Each writer's RPCs are ordered,
        # so any claim our final probe missed belongs to a peer whose
        # own (larger) publish necessarily lands after ours.
        try:
            pub = i
            c.key_value_set_bytes(key, str(pub).encode(),
                                  allow_overwrite=True)
            tail = i
            while tail < limit:
                if self._legacy_get_once(
                        c, f"{key}/__c__/{tail + 1}", 50) is not None:
                    tail += 1
                    continue
                if tail == pub:
                    break
                c.key_value_set_bytes(key, str(tail).encode(),
                                      allow_overwrite=True)
                pub = tail
            self._inc_hint[key] = max(self._inc_hint[key], tail)
        except Exception:
            pass
        return i

    # -- barriers ---------------------------------------------------------
    def barrier(self, name: str, timeout_s: float = 120.0):
        """Block until every process reaches the barrier ``name``.

        Raises :class:`BarrierTimeoutError` on timeout — the failing-fast
        behavior the reference's check_health/barrier path has
        (collective_all_reduce_strategy.py:990) rather than hanging.

        When telemetry is on, a successful barrier emits a
        ``clock.sync`` event: the release is a shared instant every
        participant observes within the release latency, so the trace
        assembler (telemetry/trace.py) uses the per-process walls
        recorded here to estimate per-host clock offsets.
        """
        self.op_counts["barrier"] += 1
        faults.fire("coord.barrier", tag=name, exc=BarrierTimeoutError,
                    msg=f"injected barrier timeout at {name!r}")
        raw_name = name
        name = elastic.namespace(name)
        c = self._client
        if c is None:
            # n/participant come from the agent's identity: 1 for the
            # production single-process fallback (trivially passes,
            # byte-identical behavior), N for the simulated-fleet
            # agents that share one _LocalService across threads.
            self._local.barrier(name, timeout_s, self.num_processes,
                                participant=self.process_id)
        else:
            try:
                c.wait_at_barrier(name, int(timeout_s * 1000))
            except Exception as e:
                raise BarrierTimeoutError(
                    f"barrier {name!r} timed out after {timeout_s}s "
                    f"(a peer process is hung or dead): {e}") from e
        self._emit_clock_sync(raw_name)

    @staticmethod
    def _emit_clock_sync(barrier_name: str):
        """One ``clock.sync`` record per barrier release (no-op with
        telemetry off — a single None check inside events.event)."""
        from distributed_tensorflow_tpu.telemetry import events as _tv
        if _tv.enabled():
            _tv.event("clock.sync", barrier=barrier_name)

    # -- liveness ---------------------------------------------------------
    def live_processes(self) -> list[int]:
        """Process ids the coordination service believes are alive.

        ≙ coordination_service.h task-state polling, the organic failure
        signal behind WorkerPreemptionHandler (SURVEY.md §5.3).
        """
        c = self._client
        if c is None:
            return [0]
        try:
            nodes = c.get_live_nodes([])
            out = []
            for n in nodes:
                tid = _parse_task_id(n)
                if tid is not None:
                    out.append(tid)
            return sorted(set(out))
        except Exception:
            # service variant without get_live_nodes: assume all alive
            import logging
            logging.getLogger(__name__).warning(
                "coordination service has no usable get_live_nodes; "
                "assuming all %d processes alive (organic failure "
                "detection degraded to heartbeats only)",
                self.num_processes)
            return list(range(self.num_processes))


_AGENT: CoordinationServiceAgent | None = None
_AGENT_LOCK = threading.Lock()


def coordination_service() -> CoordinationServiceAgent:
    """Process-wide CoordinationServiceAgent (≙ context's coordination
    service agent singleton)."""
    global _AGENT
    with _AGENT_LOCK:
        if _AGENT is None:
            _AGENT = CoordinationServiceAgent()
        return _AGENT
