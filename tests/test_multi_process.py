"""Real multi-process distributed tests.

≙ the reference's multi_process_runner-based test suites (SURVEY.md §4:
multi_process_runner.py:107, multi_worker_test_base.py:123,
coordinator/fault_tolerance_test.py): every test here spawns actual OS
processes, each with its own JAX runtime, connected through the TSL
coordination service — the paths single-process virtual-device tests
cannot exercise (bootstrap.initialize, cross-process collectives,
multi-host checkpoint commit, preemption agreement, process death).
"""

import os
import time

import numpy as np
import pytest

from distributed_tensorflow_tpu.testing import multi_process_runner as mpr

pytestmark = pytest.mark.multiprocess


# ---------------------------------------------------------------------------
# worker fns (module-level: spawn pickles them by reference)
# ---------------------------------------------------------------------------

def _psum_worker():
    from distributed_tensorflow_tpu.cluster import bootstrap
    runtime = bootstrap.initialize()          # reads TF_CONFIG
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    assert jax.process_count() == runtime.num_processes
    # global cross-process reduction over the CPU "DCN": each process
    # contributes (process_id + 1); sum must be N(N+1)/2.
    x = jnp.ones((4,)) * (runtime.process_id + 1)
    gathered = multihost_utils.process_allgather(x)
    total = float(gathered.sum() / 4)
    bootstrap.shutdown()
    return runtime.process_id, runtime.num_processes, total


def _kv_barrier_worker():
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.cluster.coordination import (
        coordination_service)
    runtime = bootstrap.initialize()
    agent = coordination_service()
    agent.key_value_set(f"greeting/{runtime.process_id}",
                        f"hello-{runtime.process_id}")
    agent.barrier("all-wrote", timeout_s=60)
    peer = (runtime.process_id + 1) % runtime.num_processes
    got = agent.key_value_get(f"greeting/{peer}", timeout_s=30).decode()
    n = agent.key_value_increment("counter", 1)
    agent.barrier("all-read", timeout_s=60)
    final = int(agent.key_value_get("counter", timeout_s=30))
    bootstrap.shutdown()
    return got, n, final


def _ckpt_worker(tmpdir):
    """Sharded multi-host checkpoint: each process owns half of a global
    array; save must barrier so the index lands only after ALL shards."""
    from distributed_tensorflow_tpu.cluster import bootstrap
    runtime = bootstrap.initialize()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from distributed_tensorflow_tpu.parallel.values import DistributedVariable
    from distributed_tensorflow_tpu.checkpoint.checkpoint import Checkpoint

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    nproc = runtime.num_processes
    rows = 4 * nproc
    global_data = np.arange(rows * 3, dtype=np.float32).reshape(rows, 3)
    arr = jax.make_array_from_callback(
        (rows, 3), sharding, lambda idx: global_data[idx])
    var = DistributedVariable(arr, name="table")

    ckpt = Checkpoint(table=var, step=jnp.asarray(7, jnp.int32))
    path = os.path.join(tmpdir, "ckpt-1")
    ckpt.write(path)
    # after write returns (exit barrier), the index must exist everywhere
    assert os.path.exists(os.path.join(path, "checkpoint.index.json"))

    # wipe local state, restore, verify global content
    var.assign(jnp.zeros((rows, 3), jnp.float32))
    restored = Checkpoint(table=var, step=jnp.asarray(0, jnp.int32)) \
        .restore(path)
    local = np.concatenate(
        [np.asarray(s.data) for s in
         sorted(var.read_value().addressable_shards,
                key=lambda s: s.index[0].start or 0)], axis=0)
    expect = global_data[runtime.process_id * 4:(runtime.process_id + 1) * 4]
    ok = np.array_equal(local, expect) and int(restored["step"]) == 7
    bootstrap.shutdown()
    return bool(ok)


def _barrier_timeout_worker():
    """Worker 1 never reaches the barrier; worker 0 must fail fast with
    BarrierTimeoutError instead of hanging (≙ the reference's
    check_health timeout, collective_all_reduce_strategy.py:990)."""
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.cluster.coordination import (
        coordination_service, BarrierTimeoutError)
    runtime = bootstrap.initialize()
    agent = coordination_service()
    outcome = "unknown"
    if runtime.process_id == 0:
        try:
            agent.barrier("never-met", timeout_s=3)
            outcome = "passed"
        except BarrierTimeoutError:
            outcome = "timeout"
    else:
        time.sleep(6)       # deliberately skip the barrier
        outcome = "skipped"
    bootstrap.shutdown()
    return outcome


def _preemption_worker(tmpdir):
    """Cross-process preemption agreement: only process 0 receives the
    signal; BOTH processes must checkpoint at the agreed step."""
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.cluster.coordination import (
        coordination_service)
    runtime = bootstrap.initialize()
    agent = coordination_service()
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        Checkpoint, CheckpointManager)
    from distributed_tensorflow_tpu.checkpoint.failure_handling import (
        PreemptionCheckpointHandler, TerminationConfig)

    state = {"w": jnp.zeros(())}

    def train_step():
        state["w"] = state["w"] + 1.0

    ckpt = Checkpoint(w=state["w"])
    mgr = CheckpointManager(ckpt, tmpdir, checkpoint_name="pre")
    handler = PreemptionCheckpointHandler(
        mgr, TerminationConfig(exit_fn=lambda: None))
    saved_at = None
    for i in range(100):
        # per-step barrier stands in for the SPMD step's collectives:
        # real training is in lockstep because every step psums
        agent.barrier(f"step/{i}", timeout_s=60)
        ckpt._objects["w"] = state["w"]
        handler.run(train_step)
        if runtime.process_id == 0 and i == 4:
            handler.watch_preemption()      # signal arrives on proc 0 only
        if handler._exited:
            saved_at = handler.total_run_calls
            break
        time.sleep(0.05)   # realistic step time >> the signal poll period
    bootstrap.shutdown()
    return runtime.process_id, saved_at


def _killed_worker_detection(tmpdir):
    """Workers 0/1 proceed; worker 2 hangs and is SIGKILLed by the
    parent. Survivors must observe the death as a barrier timeout —
    the organic failure signal (≙ coordination-service task states,
    SURVEY.md §5.3)."""
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.cluster.coordination import (
        coordination_service, CoordinationError)
    runtime = bootstrap.initialize()
    agent = coordination_service()
    if runtime.process_id == 2:
        # tell the parent it is safe to kill us (initialize() done — the
        # rendezvous completed, peers are not blocked on our connect)
        with open(os.path.join(tmpdir, "w2_ready"), "w") as f:
            f.write("1")
        time.sleep(120)                     # killed long before this ends
        return "should-not-survive"
    agent.key_value_set(f"alive/{runtime.process_id}", "1")
    # wait until the parent confirms the kill happened
    while not os.path.exists(os.path.join(tmpdir, "w2_killed")):
        time.sleep(0.2)
    try:
        agent.barrier("post-kill", timeout_s=8)
        outcome = "passed"
    except CoordinationError:
        outcome = "peer-death-detected"
    # Exit ordering: process 0 hosts the coordination service, so it must
    # exit LAST — service teardown hard-aborts any peer with a live
    # client (its PollForError thread calls LOG(FATAL)). Non-hosts report
    # and leave immediately; the host waits for their reports + grace.
    try:
        agent.key_value_set(f"detected/{runtime.process_id}", outcome)
        if runtime.process_id == 0:
            deadline = time.monotonic() + 20
            while (agent.key_value_try_get("detected/1") is None
                   and time.monotonic() < deadline):
                time.sleep(0.1)
            time.sleep(1.0)       # let the peer finish reporting and exit
    except Exception:
        pass
    # NOTE: no clean shutdown — the coordination service may already
    # consider the job unhealthy; survivors just exit.
    return runtime.process_id, outcome


def _remote_square(x):
    return x * x


def _remote_slow_identity(x):
    time.sleep(0.4)
    return x


def _remote_dispatch_worker(tmpdir, slow):
    """proc 0 = coordinator; procs 1..N-1 = remote worker services."""
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.coordinator import remote_dispatch
    from distributed_tensorflow_tpu.coordinator.cluster_coordinator import (
        ClusterCoordinator)
    runtime = bootstrap.initialize()
    if runtime.process_id != 0:
        if slow and runtime.process_id == 2:
            # mark readiness so the parent knows when to kill us
            with open(os.path.join(tmpdir, "victim_ready"), "w") as f:
                f.write("1")
        remote_dispatch.run_worker_loop()
        return ("worker-done", runtime.process_id)

    coord = ClusterCoordinator(
        remote_worker_ids=list(range(1, runtime.num_processes)))
    fn = _remote_slow_identity if slow else _remote_square
    if slow:
        # give the victim worker time to pick up a closure, then have the
        # parent kill it mid-flight
        while not os.path.exists(os.path.join(tmpdir, "victim_ready")):
            time.sleep(0.1)
    results = [coord.schedule(fn, args=(i,)) for i in range(10)]
    if slow:
        with open(os.path.join(tmpdir, "kill_now"), "w") as f:
            f.write("1")
    coord.join(timeout=120)
    values = sorted(coord.fetch(results))
    coord.shutdown()
    expect = sorted(i * i for i in range(10)) if not slow \
        else list(range(10))
    return ("coordinator", values == expect, values)


def _remote_failover_worker(tmpdir):
    return _remote_dispatch_worker(tmpdir, slow=True)


def _range_dataset():
    return iter(range(100, 1000, 100))


def _consume_next(it):
    return next(it)


def _per_worker_dataset_worker():
    """Worker-side datasets: the iterator LIVES on the worker process;
    closures consume it through an opaque handle (≙ per-worker datasets,
    cluster_coordinator.py:1604)."""
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.coordinator import remote_dispatch
    from distributed_tensorflow_tpu.coordinator.cluster_coordinator import (
        ClusterCoordinator, PerWorkerValues)
    runtime = bootstrap.initialize()
    if runtime.process_id != 0:
        remote_dispatch.run_worker_loop()
        return ("worker-done", runtime.process_id)

    coord = ClusterCoordinator(
        remote_worker_ids=list(range(1, runtime.num_processes)))
    per_worker_it = coord.create_per_worker_dataset(_range_dataset)
    assert isinstance(per_worker_it, PerWorkerValues)
    # schedule 4 closures: each consumes the NEXT element of whichever
    # worker's iterator it lands on — worker-side state advances
    rvs = [coord.schedule(_consume_next, args=(per_worker_it,))
           for _ in range(4)]
    coord.join(timeout=120)
    values = sorted(coord.fetch(rvs))
    coord.shutdown()
    # 2 workers × first two elements each (whatever the dispatch split,
    # values come from {100, 200, 300, 400} with per-worker monotonicity)
    ok = all(v in (100, 200, 300, 400) for v in values) and \
        values[0] == 100
    return ("coordinator", ok, values)


def _remote_basic_worker(tmpdir):
    return _remote_dispatch_worker(tmpdir, slow=False)


def _resume_training_worker(tmpdir, preempt_at, total_steps):
    """One generation of a preemptible training job: restore if a
    checkpoint exists, train, optionally get preempted mid-run (signal
    lands on process 0 only), checkpoint-and-stop."""
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.cluster.coordination import (
        coordination_service)
    runtime = bootstrap.initialize()
    agent = coordination_service()
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        Checkpoint, CheckpointManager)
    from distributed_tensorflow_tpu.checkpoint.failure_handling import (
        PreemptionCheckpointHandler, TerminationConfig)

    # "model": w_{t+1} = w_t * 1.5 + t  (order-sensitive: any lost or
    # repeated step changes the final value)
    state = {"w": jnp.asarray(1.0), "t": 0}

    def train_step():
        state["w"] = state["w"] * 1.5 + state["t"]
        state["t"] += 1

    ckpt = Checkpoint(w=state["w"], t=jnp.asarray(0))
    mgr = CheckpointManager(ckpt, tmpdir, checkpoint_name="resume")
    handler = PreemptionCheckpointHandler(
        mgr, TerminationConfig(exit_fn=lambda: None))
    # restore training position from the checkpoint contents
    if mgr.latest_checkpoint:
        restored = Checkpoint(w=state["w"], t=jnp.asarray(0)).restore(
            mgr.latest_checkpoint)
        state["w"] = jnp.asarray(restored["w"])
        state["t"] = int(restored["t"])

    for i in range(1000):
        if state["t"] >= total_steps:
            break
        agent.barrier(f"gen-step/{state['t']}", timeout_s=60)
        ckpt._objects["w"] = state["w"]
        ckpt._objects["t"] = jnp.asarray(state["t"])
        handler.run(train_step)
        if (preempt_at is not None and runtime.process_id == 0
                and state["t"] == preempt_at):
            handler.watch_preemption()
        if handler._exited:
            break
        time.sleep(0.03)
    bootstrap.shutdown()
    return runtime.process_id, state["t"], float(state["w"])


# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------
# Tests that never kill a task run on module-scoped POOLS (persistent
# processes, fresh cluster ports per run — ≙ the reference's
# MultiProcessPoolRunner, multi_process_runner.py:902) to amortize the
# spawn + jax-import cost that dominates this suite's wall-clock.
# Fault-injection tests keep the spawn-per-task MultiProcessRunner.

@pytest.fixture(scope="module")
def pool2():
    pool = mpr.MultiProcessPoolRunner(num_workers=2)
    yield pool
    pool.shutdown()


@pytest.fixture(scope="module")
def pool3():
    pool = mpr.MultiProcessPoolRunner(num_workers=3)
    yield pool
    pool.shutdown()


def test_cross_process_collective(pool2):
    result = pool2.run(_psum_worker, timeout=180)
    vals = sorted(result.return_values)
    assert vals == [(0, 2, 3.0), (1, 2, 3.0)]


def test_kv_store_barrier_increment(pool2):
    result = pool2.run(_kv_barrier_worker, timeout=180)
    assert len(result.return_values) == 2
    gots = sorted(v[0] for v in result.return_values)
    assert gots == ["hello-0", "hello-1"]
    # increments are atomic: post-increment values are a permutation of
    # {1, 2} and everyone converges on 2
    assert sorted(v[1] for v in result.return_values) == [1, 2]
    assert all(v[2] == 2 for v in result.return_values)


def test_multi_host_sharded_checkpoint(tmp_path, pool2):
    result = pool2.run(_ckpt_worker, args=(str(tmp_path),), timeout=240)
    assert result.return_values == [True, True]


def test_barrier_timeout_fails_fast(pool2):
    result = pool2.run(_barrier_timeout_worker, timeout=180)
    outcomes = sorted(result.return_values)
    assert outcomes == ["skipped", "timeout"]


def _finalize_laggard_worker(tmpdir):
    """Unequal-length loops + late preemption signal (ADVICE r2 medium):
    proc 0's data ends at step 5, proc 1's at step 8, and the signal
    lands on proc 1 near its end — the agreed run-to step is beyond
    BOTH loops. finalize() must still commit ONE checkpoint containing
    both hosts' shards (the laggard may not silently drop out)."""
    from distributed_tensorflow_tpu.cluster import bootstrap
    runtime = bootstrap.initialize()
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        Checkpoint, CheckpointManager)
    from distributed_tensorflow_tpu.checkpoint.failure_handling import (
        PreemptionCheckpointHandler, TerminationConfig)

    state = {"w": jnp.zeros(())}

    def train_step():
        state["w"] = state["w"] + 1.0

    ckpt = Checkpoint(w=state["w"])
    mgr = CheckpointManager(ckpt, tmpdir, checkpoint_name="fin")
    handler = PreemptionCheckpointHandler(
        mgr, TerminationConfig(exit_fn=lambda: None))
    n_steps = 5 if runtime.process_id == 0 else 8
    for i in range(n_steps):
        ckpt._objects["w"] = state["w"]
        handler.run(train_step)
        if runtime.process_id == 1 and i == n_steps - 2:
            handler.watch_preemption()   # signal near proc 1's end only
        if handler._exited:
            break
        time.sleep(0.05)
    ckpt._objects["w"] = state["w"]
    if runtime.process_id == 0:
        # deterministically let the peer's (late) signal land before
        # finalizing — in production the 600s agreement timeouts cover
        # this race; the test shouldn't wait that long
        from distributed_tensorflow_tpu.cluster.coordination import (
            coordination_service)
        agent = coordination_service()
        deadline = time.monotonic() + 60
        while (agent.key_value_try_get(handler._SIGNAL_KEY) is None
               and time.monotonic() < deadline):
            time.sleep(0.05)
    handler.finalize()                   # must not hang or skip a host
    saved = mgr.latest_checkpoint
    bootstrap.shutdown()
    return runtime.process_id, saved is not None


def test_preemption_agreement_across_processes(tmp_path, pool2):
    result = pool2.run(_preemption_worker, args=(str(tmp_path),),
                       timeout=240)
    assert len(result.return_values) == 2
    by_proc = dict(result.return_values)
    # both processes checkpointed (at the agreed step); save steps match
    assert by_proc[0] is not None and by_proc[1] is not None
    assert by_proc[0] == by_proc[1]
    # exactly one complete checkpoint exists with both hosts' shards
    cks = [d for d in os.listdir(tmp_path) if d.startswith("pre-")
           and os.path.isdir(tmp_path / d)]
    assert len(cks) == 1
    files = os.listdir(tmp_path / cks[0])
    assert "checkpoint.index.json" in files
    assert "shard_0.npz" in files and "shard_1.npz" in files


def test_remote_coordinator_dispatch(tmp_path, pool3):
    """Closures scheduled on the coordinator run in remote worker
    PROCESSES (≙ cluster_coordinator.py:1027 grpc dispatch)."""
    result = pool3.run(_remote_basic_worker, args=(str(tmp_path),),
                       timeout=240)
    coord = [v for v in result.return_values if v[0] == "coordinator"][0]
    assert coord[1], f"wrong results: {coord[2]}"
    workers = [v for v in result.return_values if v[0] == "worker-done"]
    assert len(workers) == 2     # both worker loops exited via shutdown


def test_per_worker_datasets_on_remote_workers(pool3):
    """create_per_worker_dataset places iterators ON worker processes;
    scheduled closures consume them via resource handles."""
    result = pool3.run(_per_worker_dataset_worker, timeout=240)
    coord = [v for v in result.return_values if v[0] == "coordinator"][0]
    assert coord[1], f"unexpected values: {coord[2]}"


def test_remote_dispatch_failover_on_worker_kill(tmp_path):
    """A killed worker's in-flight closure is transparently re-run on a
    surviving worker (≙ WorkerPreemptionHandler.wait_on_failure :879 —
    the organic producer of WorkerPreemptionError)."""
    spec = mpr.create_cluster_spec(num_workers=3)
    runner = mpr.MultiProcessRunner(
        _remote_failover_worker, spec, args=(str(tmp_path),), timeout=240)
    runner.start()
    deadline = time.monotonic() + 120
    while not (tmp_path / "kill_now").exists():
        assert time.monotonic() < deadline, "coordinator never signalled"
        time.sleep(0.1)
    time.sleep(0.2)               # let worker 2 take a closure in flight
    runner.terminate("worker", 2)
    result = runner.join(timeout=180, raise_on_error=False)
    coord = [t for t in result.tasks.values()
             if t.error is None and t.exitcode == 0
             and t.value and t.value[0] == "coordinator"]
    assert coord, {k: (t.exitcode, t.error and t.error[-500:])
                   for k, t in result.tasks.items()}
    assert coord[0].value[1], f"wrong results: {coord[0].value[2]}"
    assert result.tasks[("worker", 2)].exitcode != 0   # really killed


def test_preemption_restart_resume_training(tmp_path, pool2):
    """The full fault-tolerance story across PROCESS GENERATIONS:
    generation 1 trains, gets preempted (signal on one process),
    checkpoints at the agreed step and stops; generation 2 (fresh
    processes, fresh coordination service) restores and finishes. The
    final state must equal uninterrupted training — the order-sensitive
    recurrence catches any lost, repeated, or torn step."""
    total = 12
    r1 = pool2.run(_resume_training_worker,
                   args=(str(tmp_path), 4, total), timeout=300)
    assert len(r1.return_values) == 2
    for _pid, t, _w in r1.return_values:
        assert t < total, "generation 1 should have been preempted"
    # a complete checkpoint exists
    cks = [d for d in os.listdir(tmp_path) if d.startswith("resume-")]
    assert cks, os.listdir(tmp_path)

    r2 = pool2.run(_resume_training_worker,
                   args=(str(tmp_path), None, total), timeout=300)
    expect = 1.0
    for t in range(total):
        expect = expect * 1.5 + t
    for _pid, t, w in r2.return_values:
        assert t == total
        assert abs(w - expect) < 1e-3 * abs(expect), (w, expect)


def test_killed_process_detected(tmp_path):
    spec = mpr.create_cluster_spec(num_workers=3)
    runner = mpr.MultiProcessRunner(
        _killed_worker_detection, spec, args=(str(tmp_path),), timeout=120)
    runner.start()
    deadline = time.monotonic() + 90
    while not (tmp_path / "w2_ready").exists():
        assert time.monotonic() < deadline, "worker 2 never became ready"
        time.sleep(0.2)
    runner.terminate("worker", 2)
    (tmp_path / "w2_killed").write_text("1")
    result = runner.join(timeout=90, raise_on_error=False)
    survivors = {t.task_id: t for t in result.tasks.values()
                 if t.exitcode == 0 and t.error is None}
    assert set(survivors) == {0, 1}
    for t in survivors.values():
        assert t.value[1] == "peer-death-detected", t.value
    # the killed task died by SIGKILL
    assert result.tasks[("worker", 2)].exitcode != 0


@pytest.mark.multiprocess
def test_finalize_commits_full_checkpoint_on_unequal_stops(tmp_path, pool2):
    result = pool2.run(_finalize_laggard_worker, args=(str(tmp_path),),
                       timeout=240)
    by_proc = dict(result.return_values)
    assert by_proc[0] and by_proc[1]
    cks = [d for d in os.listdir(tmp_path) if d.startswith("fin-")
           and os.path.isdir(tmp_path / d)]
    assert len(cks) >= 1
    # the newest checkpoint has BOTH hosts' shards + a committed index
    newest = sorted(cks)[-1]
    files = os.listdir(tmp_path / newest)
    assert "checkpoint.index.json" in files
    assert "shard_0.npz" in files and "shard_1.npz" in files





def _dlrm_ps_worker(tmpdir):
    """Config #4 composed end-to-end: DLRM through the embedding API,
    trained async via remote dispatch across worker PROCESSES, surviving
    one worker kill mid-run (≙ parameter_server_strategy_v2.py:77 +
    tpu_embedding_v2.py:76 used together — BASELINE.md config #4)."""
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.coordinator import remote_dispatch
    from distributed_tensorflow_tpu.coordinator.cluster_coordinator import (
        ClusterCoordinator)
    from distributed_tensorflow_tpu.models import wide_deep as wd
    runtime = bootstrap.initialize()
    if runtime.process_id != 0:
        if runtime.process_id == 2:
            with open(os.path.join(tmpdir, "victim_ready"), "w") as f:
                f.write("1")
        remote_dispatch.run_worker_loop()
        return ("worker-done", runtime.process_id)

    cfg = wd.WideDeepConfig.tiny(learning_rate=0.05)
    coord = ClusterCoordinator(
        remote_worker_ids=list(range(1, runtime.num_processes)))
    while not os.path.exists(os.path.join(tmpdir, "victim_ready")):
        time.sleep(0.1)

    def on_step(n):
        if n == 10:      # mid-run: datasets live, closures in flight
            with open(os.path.join(tmpdir, "kill_now"), "w") as f:
                f.write("1")           # parent kills worker 2 now
            # block until the kill really happened so the remaining 50
            # steps all run WITHOUT worker 2
            deadline = time.monotonic() + 60
            while not os.path.exists(os.path.join(tmpdir, "killed")):
                assert time.monotonic() < deadline, "kill never confirmed"
                time.sleep(0.05)

    state, losses = wd.train_dlrm_async_ps(cfg, coord, steps=60,
                                           batch_size=32,
                                           max_in_flight=4,
                                           on_step=on_step)
    coord.shutdown()
    first = sum(losses[:10]) / 10
    last = sum(losses[-10:]) / 10
    return ("coordinator", len(losses), first, last)


@pytest.mark.multiprocess
def test_dlrm_async_ps_end_to_end(tmp_path):
    spec = mpr.create_cluster_spec(num_workers=3)
    runner = mpr.MultiProcessRunner(
        _dlrm_ps_worker, spec, args=(str(tmp_path),), timeout=300)
    runner.start()
    deadline = time.monotonic() + 180
    while not (tmp_path / "kill_now").exists():
        assert time.monotonic() < deadline, "coordinator never started"
        time.sleep(0.1)
    runner.terminate("worker", 2)
    (tmp_path / "killed").write_text("1")
    result = runner.join(timeout=300, raise_on_error=False)
    coord = [t for t in result.tasks.values()
             if t.error is None and t.exitcode == 0
             and t.value and t.value[0] == "coordinator"]
    assert coord, {k: (t.exitcode, t.error and t.error[-500:])
                   for k, t in result.tasks.items()}
    _, n_losses, first, last = coord[0].value
    assert n_losses == 60          # every scheduled step completed
    assert last < first, (first, last)     # loss still converging
    assert result.tasks[("worker", 2)].exitcode != 0   # really killed


def _train_and_evaluate_task(tmpdir):
    """Role-dispatched train_and_evaluate: chief+worker train and write
    rotating checkpoints; the evaluator task (OUTSIDE the SPMD world)
    evaluates each one and writes TB summaries
    (≙ distribute_coordinator.py:627 evaluator orchestration)."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        Checkpoint, CheckpointManager)
    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.coordinator.evaluator import (
        SidecarEvaluator, train_and_evaluate)

    FINAL = 3                               # checkpoints 1..3

    def train_fn(ctx):
        # both trainers run lockstep SPMD-style steps; the chief saves
        runtime = bootstrap.runtime()
        from distributed_tensorflow_tpu.cluster.coordination import (
            coordination_service)
        agent = coordination_service()
        w = jnp.zeros(())
        ckpt = Checkpoint(w=w)
        mgr = CheckpointManager(ckpt, tmpdir, checkpoint_name="tne",
                                max_to_keep=2)
        for step in range(1, FINAL + 1):
            w = w + 1.0
            ckpt._objects["w"] = w
            agent.barrier(f"tne_step/{step}", timeout_s=120)
            mgr.save(checkpoint_number=step)
            time.sleep(0.3)       # give the evaluator a rotation window
        bootstrap.shutdown()
        return ("trainer", runtime.process_id)

    def eval_fn(ctx):
        assert ctx.task_type == "evaluator"
        ckpt = Checkpoint(w=jnp.zeros(()))
        ev = SidecarEvaluator(
            ckpt, tmpdir,
            lambda c, step: {"w": float(np.asarray(c._objects["w"]))},
            checkpoint_name="tne",
            summary_dir=os.path.join(tmpdir, "eval_logs"),
            poll_interval_s=0.1, final_step=FINAL, idle_timeout_s=90)
        evaluated = ev.run()
        return ("evaluator", evaluated)

    return train_and_evaluate(train_fn, eval_fn, strategy=None)


@pytest.mark.multiprocess
def test_train_and_evaluate_with_evaluator_task(tmp_path):
    result = mpr.run(_train_and_evaluate_task, num_workers=2,
                     has_evaluator=True, args=(str(tmp_path),),
                     timeout=300)
    values = result.return_values
    trainers = [v for v in values if v[0] == "trainer"]
    evals = [v for v in values if v[0] == "evaluator"]
    assert len(trainers) == 2 and len(evals) == 1, values
    evaluated = evals[0][1]
    steps = [s for s, _ in evaluated]
    # the evaluator saw checkpoints as they rotated and STOPPED at the
    # final one; metrics came from the restored state (w == step)
    assert steps[-1] == 3, evaluated
    for s, m in evaluated:
        assert m["w"] == float(s), evaluated
    # TB event file with eval scalars exists
    logs = os.listdir(tmp_path / "eval_logs")
    assert any("events.out.tfevents" in f for f in logs), logs


# ---------------------------------------------------------------------------
# pool-runner semantics
# ---------------------------------------------------------------------------

def _own_pid():
    import os as _os
    return _os.getpid()


def _raise_worker():
    raise ValueError("intentional")


def test_pool_reuses_processes_across_runs(pool2):
    """The whole point of the pool: consecutive runs land on the SAME
    OS processes (no spawn / jax re-import), and a fresh distributed
    cluster still comes up correctly on every run."""
    pids1 = sorted(pool2.run(_own_pid, timeout=60).return_values)
    pids2 = sorted(pool2.run(_own_pid, timeout=60).return_values)
    assert pids1 == pids2 and len(pids1) == 2
    # distributed runs work on the same pooled processes before/after
    r = pool2.run(_psum_worker, timeout=180)
    assert sorted(r.return_values) == [(0, 2, 3.0), (1, 2, 3.0)]
    pids3 = sorted(pool2.run(_own_pid, timeout=60).return_values)
    assert pids3 == pids1


def test_pool_task_error_does_not_break_pool(pool2):
    """A raising closure reports SubprocessError; the pool stays usable
    (≙ MultiProcessPoolRunner surviving test failures)."""
    pids_before = sorted(pool2.run(_own_pid, timeout=60).return_values)
    with pytest.raises(mpr.SubprocessError, match="intentional"):
        pool2.run(_raise_worker, timeout=60)
    pids_after = sorted(pool2.run(_own_pid, timeout=60).return_values)
    assert pids_after == pids_before


def test_pool_restarts_after_idle_child_death(pool2):
    """A pool child that dies while idle must not strand the fixture:
    the next run detects the dead task and restarts the pool."""
    pids = sorted(pool2.run(_own_pid, timeout=60).return_values)
    pool2._procs[("worker", 0)].kill()
    pool2._procs[("worker", 0)].join(10)
    pids2 = sorted(pool2.run(_own_pid, timeout=120).return_values)
    assert len(pids2) == 2 and pids2 != pids
    # and distributed runs still work on the restarted pool
    r = pool2.run(_psum_worker, timeout=180)
    assert sorted(r.return_values) == [(0, 2, 3.0), (1, 2, 3.0)]
