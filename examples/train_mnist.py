#!/usr/bin/env python
"""Config #1: MNIST CNN under MirroredStrategy semantics (BASELINE.md).

Single-host synchronous data parallelism — the TPU-native counterpart of
the reference's `MirroredStrategy` Keras script, on the NATIVE path
(SURVEY §3.4): distribute dataset -> replicate state -> one compiled
SPMD step via `strategy.compile_step`. For the TF-parity
scope()/run()/merge_call surface, see tests/test_strategy.py and the
conformance suite (testing/strategy_conformance.py); for the Keras-style
`Model.fit` layer, see distributed_tensorflow_tpu/training.

``--elastic`` instead runs the job as an N-worker cluster under the
recovery supervisor (resilience/supervisor.py): worker processes train
data-parallel with periodic checkpoints; if one dies (try
``--kill-seed``) the supervisor kills the stragglers, reforms the
cluster under a fresh generation, and the job resumes from the last
intact checkpoint. Render the run with ``tools/obs_report.py
<telemetry-dir>`` to see the recovery timeline.

``--data-service`` runs the DISAGGREGATED-INPUT topology (ISSUE 12):
task 0 trains and dispatches FILE splits, tasks 1..M are input
workers executing the registered pipeline under heartbeat-backed
leases over the coordination KV; ``--kill-seed`` SIGKILLs input
workers mid-epoch and the epoch's exactly-once split delivery must
survive (gated by ``tools/chaos_sweep.py --data``).
"""

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

#: deterministic synthetic sample pool shared by every worker/generation
_POOL = 512


# ---------------------------------------------------------------------------
# Disaggregated data service (ISSUE 12): task 0 = trainer + dispatcher,
# tasks 1..M = input workers, all under one recovery supervisor
# ---------------------------------------------------------------------------

def _npz_reader(path):
    """Per-file reader of the split files ``write_mnist_split_files``
    lays down — module-level so the split pipeline factory pickles by
    reference into input-worker processes."""
    import numpy as np
    with np.load(path) as z:
        images, labels = z["image"], z["label"]
    for i in range(len(labels)):
        yield {"image": images[i], "label": labels[i]}


def mnist_split_pipeline(files):
    """The registered per-split pipeline (SplitProvider.from_factory):
    unbatched examples; the trainer batches (batch composition follows
    split-completion order, the element MULTISET is deterministic)."""
    from distributed_tensorflow_tpu.input.dataset import Dataset
    return Dataset.from_files(list(files), _npz_reader)


def write_mnist_split_files(data_dir, num_files, pool=_POOL):
    """Shard the deterministic synthetic pool into FILE splits."""
    import numpy as np

    from distributed_tensorflow_tpu.models.mnist_cnn import synthetic_data
    data = synthetic_data(pool)
    per = pool // num_files
    os.makedirs(data_dir, exist_ok=True)
    files = []
    for i in range(num_files):
        path = os.path.join(data_dir, f"mnist-{i:03d}.npz")
        sl = slice(i * per, (i + 1) * per)
        np.savez(path, image=data["image"][sl], label=data["label"][sl])
        files.append(path)
    return files


def seeded_input_kill_plan(seed, input_workers, *, kills=1,
                           step_range=(1, 3)):
    """Seed-derived SIGKILLs of INPUT-WORKER tasks (cluster task ids
    1..M; task 0 is the trainer): fire once the victim's heartbeat
    reports >= after_step splits processed — mid-epoch by
    construction."""
    import random as _random

    from distributed_tensorflow_tpu.resilience import KillSpec
    rng = _random.Random(f"dtx-data-kill:{seed}")
    victims = rng.sample(range(input_workers),
                         k=min(kills, input_workers))
    return [KillSpec(worker=1 + v, after_step=rng.randrange(*step_range))
            for v in victims]


def data_service_worker(data_dir, ckpt_dir, epochs, global_batch, lr,
                        input_workers):
    """One generation of one data-service cluster task. Task 0 is the
    trainer (plus the split dispatcher); tasks 1..M are input workers
    executing the registered pipeline over leased FILE splits. All KV
    traffic is generation-namespaced, so a supervisor reform fences
    every straggler of the dead incarnation."""
    import glob as _glob

    from distributed_tensorflow_tpu.cluster import bootstrap, elastic
    from distributed_tensorflow_tpu.cluster.coordination import (
        CoordinationError, coordination_service)
    from distributed_tensorflow_tpu.input import data_service as dsvc
    from distributed_tensorflow_tpu.input.split_provider import (
        SplitProvider)
    from distributed_tensorflow_tpu.telemetry import events as tv_events

    runtime = bootstrap.initialize()
    if runtime.num_processes > 1:
        # The CPU test backend's gloo client creation is COLLECTIVE:
        # every process of the distributed runtime must initialize its
        # backend or the ones that do (the trainer's first jit) block
        # forever in make_cpu_client waiting for the rest. Input
        # workers never run a jax computation, so touch the backend
        # explicitly.
        import jax
        jax.local_devices()
    tdir = os.environ.get(tv_events.ENV_TELEMETRY_DIR)
    if tdir:
        tv_events.configure(tdir, process_id=runtime.process_id)
    agent = coordination_service()
    files = sorted(_glob.glob(os.path.join(data_dir, "*.npz")))
    provider = SplitProvider.from_factory(files, mnist_split_pipeline,
                                          seed=0)
    cfg = dsvc.DataServiceConfig(job="mnist", lease_timeout_s=1.0,
                                 fetch_timeout_s=60.0)
    if runtime.process_id == 0:
        return _data_service_trainer(
            runtime, agent, provider, cfg, ckpt_dir, epochs,
            global_batch, lr, input_workers)
    wid = runtime.process_id - 1
    worker = dsvc.DataInputWorker(
        agent, provider, cfg, worker_id=wid,
        num_workers=input_workers, epochs=epochs,
        heartbeat_fn=elastic.heartbeat)
    try:
        worker.run()
    except CoordinationError:
        pass          # coordinator torn down at job end: released
    bootstrap.shutdown()
    return ("input_worker", wid, worker.splits_processed)


def _data_service_trainer(runtime, agent, provider, cfg, ckpt_dir,
                          epochs, global_batch, lr, input_workers):
    import time as _time

    import jax
    import numpy as np
    import optax

    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        Checkpoint, CheckpointManager)
    from distributed_tensorflow_tpu.cluster import bootstrap, elastic
    from distributed_tensorflow_tpu.input import data_service as dsvc
    from distributed_tensorflow_tpu.models.mnist_cnn import (
        create_train_state)
    from distributed_tensorflow_tpu.telemetry import events as tv_events
    from distributed_tensorflow_tpu.telemetry import goodput

    ledger = goodput.GoodputLedger()
    goodput.activate(ledger)
    dispatcher = dsvc.DataServiceDispatcher(
        agent, provider, cfg, num_workers=input_workers, epochs=epochs)
    dispatcher.start()
    client = dsvc.DataServiceClient(
        agent, cfg, heartbeat_fn=lambda _s: elastic.heartbeat())

    state, model, tx = create_train_state(jax.random.PRNGKey(0),
                                          learning_rate=lr)
    params, opt_state = state["params"], state["opt_state"]

    def loss_fn(p, images, labels):
        logits = model.apply({"params": p}, images)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    @jax.jit
    def apply_fn(p, o, grads):
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o

    leaves, treedef = jax.tree_util.tree_flatten((params, opt_state))
    # single_writer: the trainer alone owns the model state — the
    # input workers are cluster members but never checkpoint, so the
    # SPMD commit barrier would block for its full timeout every save
    ckpt = Checkpoint(single_writer=True, leaves=list(leaves))
    mgr = CheckpointManager(ckpt, ckpt_dir, checkpoint_name="dsvc")
    start_epoch = 0
    res = mgr.restore_latest()
    if res is not None:
        tier, start_epoch, restored = res
        params, opt_state = jax.tree_util.tree_unflatten(
            treedef, [restored[f"leaves/{i}"]
                      for i in range(len(leaves))])
        print(f"[gen {runtime.generation}] trainer resumed at epoch "
              f"{start_epoch} from the {tier} tier")

    loss = float("nan")
    step = 0
    last_wait = client.total_wait_s
    for epoch in range(start_epoch, epochs):
        batch_buf = []
        for el in client.epoch(epoch):
            batch_buf.append(el)
            if len(batch_buf) < global_batch:
                continue
            t0 = _time.perf_counter()
            images = np.stack([b["image"] for b in batch_buf])
            labels = np.stack([b["label"] for b in batch_buf])
            batch_buf = []
            loss, grads = grad_fn(params, images, labels)
            loss = float(loss)
            params, opt_state = apply_fn(params, opt_state, grads)
            jax.block_until_ready(params)
            dur_s = _time.perf_counter() - t0
            # fetch-wait accrued since the previous step prices into
            # the infeed_wait badput bucket (event-walk AND live paths)
            wait_s = client.total_wait_s - last_wait
            last_wait = client.total_wait_s
            elastic.heartbeat(step)
            tv_events.event("train.step", step=step, loss=loss,
                            dur_s=round(dur_s + wait_s, 6),
                            infeed_wait_s=round(wait_s, 6))
            ledger.step_completed(dur_s + wait_s, infeed_s=wait_s)
            step += 1
        refresh = jax.tree_util.tree_flatten((params, opt_state))[0]
        ckpt._objects["leaves"] = list(refresh)
        ledger.enter("ckpt_block")
        mgr.save(checkpoint_number=epoch + 1)
        ledger.enter("idle")
        print(f"[gen {runtime.generation}] epoch {epoch} done: "
              f"loss={loss:.4f} fetch_wait={client.total_wait_s:.2f}s "
              f"reassigned={dispatcher.splits_reassigned}")
    dsvc.signal_shutdown(agent, cfg)
    dsvc.await_shutdown_acks(agent, cfg, input_workers)
    dispatcher.stop()
    ckpt.sync()
    bootstrap.shutdown()
    return (0, start_epoch, loss)


def elastic_worker(ckpt_dir, total_steps, save_every, global_batch, lr,
                   local_dir=None, snapshot_every=None, snapshot_keep=2,
                   step_delay_s=0.0):
    """One generation of one elastic worker: bootstrap from TF_CONFIG,
    restore down the recovery ladder (own host snapshot > peer replica
    > local disk > durable disk), train data-parallel (grads
    allgather-averaged across processes), checkpoint every
    ``save_every`` steps with host snapshots every ``snapshot_every``
    in between, heartbeat every step. The per-worker batch is derived
    from the CURRENT process count (``global_batch // nproc``), so the
    same worker fn runs at any topology the supervisor reforms to.
    Module-level so the supervisor's spawn machinery can pickle it by
    reference."""
    from distributed_tensorflow_tpu.cluster import bootstrap, elastic
    runtime = bootstrap.initialize()
    import jax
    if runtime.num_processes <= 1:
        # a cluster scaled down to ONE trainer (autoscaler donation —
        # examples/shared_fleet.py) never joins a distributed world,
        # but the spawn harness pre-configures gloo collectives, which
        # this jaxlib rejects without a distributed client: reset
        # before the first computation (the serving_replica discipline)
        import contextlib
        with contextlib.suppress(Exception):
            jax.config.update("jax_cpu_collectives_implementation",
                              "none")
    import numpy as np
    import optax
    from jax.experimental import multihost_utils

    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        Checkpoint, CheckpointManager)
    from distributed_tensorflow_tpu.checkpoint.peer_snapshot import (
        SnapshotStore)
    from distributed_tensorflow_tpu.models.mnist_cnn import (
        create_train_state, synthetic_data)
    from distributed_tensorflow_tpu.telemetry import events as tv_events

    tdir = os.environ.get(tv_events.ENV_TELEMETRY_DIR)
    if tdir:
        tv_events.configure(tdir, process_id=runtime.process_id)
    # live goodput ledger: per-step feeding below prices infeed/ckpt
    # blocking; enter("ckpt_block") names the bucket a stall during a
    # blocking save would accrue to
    from distributed_tensorflow_tpu.telemetry import goodput
    ledger = goodput.GoodputLedger()
    goodput.activate(ledger)

    state, model, tx = create_train_state(jax.random.PRNGKey(0),
                                          learning_rate=lr)
    params, opt_state = state["params"], state["opt_state"]
    data = synthetic_data(_POOL)

    def loss_fn(p, images, labels):
        logits = model.apply({"params": p}, images)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    @jax.jit
    def apply_fn(p, o, grads):
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o

    leaves, treedef = jax.tree_util.tree_flatten((params, opt_state))
    ckpt = Checkpoint(leaves=list(leaves))
    # snapshot_every == 0 disables the host/peer memory tiers entirely
    memdir = elastic.peer_memdir()
    store = (SnapshotStore(memdir, keep=snapshot_keep)
             if memdir and snapshot_every != 0 else None)
    mgr = CheckpointManager(ckpt, ckpt_dir, checkpoint_name="elastic",
                            local_dir=local_dir, snapshot_store=store)
    start_step = 0
    res = mgr.restore_latest()
    if res is not None:
        tier, start_step, restored = res
        params, opt_state = jax.tree_util.tree_unflatten(
            treedef, [restored[f"leaves/{i}"] for i in range(len(leaves))])
        print(f"[gen {runtime.generation} p{runtime.process_id}] resumed "
              f"at step {start_step} from the {tier} tier")

    nproc, pid = runtime.num_processes, runtime.process_id
    per_batch = max(1, global_batch // nproc)
    loss = float("nan")
    import time as _time

    def refresh_tracked():
        ckpt._objects["leaves"] = list(
            jax.tree_util.tree_flatten((params, opt_state))[0])

    for step in range(start_step, total_steps):
        elastic.heartbeat(step)
        if step_delay_s:
            # pacing for shared-fleet runs (examples/shared_fleet.py):
            # a trainer sharing the host with serving replicas models a
            # device-bound step so the 1-core container's CPU contention
            # doesn't drown the serving latency signal
            _time.sleep(step_delay_s)
        # Per-step phase attribution (the obs_report/trace_report phase
        # table): compute = local fwd/bwd + optimizer apply, collective
        # = the cross-process gradient allgather (host-driven here, so
        # it is ENTIRELY exposed — overlap_eff 0 by construction),
        # ckpt_block = step-loop time blocked on
        # checkpoint capture/commit/snapshot.
        t0 = _time.perf_counter()
        start = (step * global_batch + pid * per_batch) % _POOL
        idx = (np.arange(per_batch) + start) % _POOL
        loss, grads = grad_fn(params, data["image"][idx],
                              data["label"][idx])
        loss = float(loss)               # block: fwd/bwd complete
        t1 = _time.perf_counter()
        if nproc > 1:
            grads = jax.tree_util.tree_map(
                lambda g: np.asarray(
                    multihost_utils.process_allgather(g)).mean(0), grads)
        t2 = _time.perf_counter()
        params, opt_state = apply_fn(params, opt_state, grads)
        jax.block_until_ready(params)
        t3 = _time.perf_counter()
        ckpt_s = 0.0
        if (step + 1) % save_every == 0:
            refresh_tracked()
            ledger.enter("ckpt_block")
            mgr.save(checkpoint_number=step + 1)
            ledger.enter("idle")
            ckpt_s = _time.perf_counter() - t3
        elif (store is not None and snapshot_every
              and (step + 1) % snapshot_every == 0):
            refresh_tracked()
            ledger.enter("ckpt_block")
            mgr.snapshot(step + 1)   # memory-only: the cheap hot tier
            ledger.enter("idle")
            ckpt_s = _time.perf_counter() - t3
        dur_s = _time.perf_counter() - t0
        tv_events.event(
            "train.step", step=step, loss=loss,
            dur_s=round(dur_s, 6),
            compute_s=round((t1 - t0) + (t3 - t2), 6),
            collective_s=round(t2 - t1, 6),
            ckpt_block_s=round(ckpt_s, 6))
        ledger.step_completed(dur_s, ckpt_s=ckpt_s)
        if step % 10 == 0 and pid == 0:
            print(f"[gen {runtime.generation}] step {step}: "
                  f"loss={float(loss):.4f}")
    ckpt.sync()
    bootstrap.shutdown()
    return runtime.process_id, start_step, float(loss)


def run_elastic(args):
    import tempfile

    from distributed_tensorflow_tpu.resilience import (
        RecoverySupervisor, seeded_kill_plan, seeded_shrink_plan)

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="mnist_elastic_")
    local_dir = args.local_ckpt_dir
    if local_dir is None and not args.no_local_tier:
        local_dir = ckpt_dir.rstrip("/") + ".local"
    snapshot_every = args.snapshot_every
    if snapshot_every is None:
        snapshot_every = max(1, args.save_every // 2)
    kill_plan = ()
    if args.kill_seed is not None:
        step_range = (2, max(3, args.steps - 4))
        if args.permanent_kill:
            kill_plan = seeded_shrink_plan(args.kill_seed, args.workers,
                                           step_range=step_range)
        else:
            kill_plan = seeded_kill_plan(args.kill_seed, args.workers,
                                         kills=args.kills,
                                         step_range=step_range)
        print(f"chaos kill plan (seed {args.kill_seed}): {kill_plan}")
    sup = RecoverySupervisor(
        elastic_worker, num_workers=args.workers,
        args=(ckpt_dir, args.steps, args.save_every, args.global_batch,
              args.lr),
        kwargs={"local_dir": local_dir,
                "snapshot_every": 0 if args.no_snapshots
                else snapshot_every},
        max_restarts=args.restart_budget, kill_plan=kill_plan,
        shrink_after=args.shrink_after, min_workers=args.min_workers,
        generation_timeout_s=args.generation_timeout,
        telemetry_dir=args.telemetry_dir)
    result = sup.run()
    for pid, start_step, loss in sorted(result.return_values):
        print(f"worker {pid}: resumed@{start_step} final loss={loss:.4f}")
    print(f"done: {sup.restarts_used} restart(s), "
          f"{sup.failures_total} recorded failure(s), "
          f"final generation {sup.generation}, "
          f"final cluster size {sup.num_workers}")
    if args.telemetry_dir:
        print(f"recovery timeline: python tools/obs_report.py "
              f"{args.telemetry_dir}")


def run_data_service(args):
    import tempfile

    from distributed_tensorflow_tpu.resilience import RecoverySupervisor

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="mnist_dsvc_")
    data_dir = os.path.join(ckpt_dir, "splits")
    files = write_mnist_split_files(data_dir, args.split_files)
    kill_plan = ()
    if args.kill_seed is not None:
        kill_plan = seeded_input_kill_plan(
            args.kill_seed, args.input_workers, kills=args.kills)
        print(f"input-worker kill plan (seed {args.kill_seed}): "
              f"{kill_plan}")
    sup = RecoverySupervisor(
        data_service_worker,
        num_workers=1 + args.input_workers,
        args=(data_dir, ckpt_dir, args.epochs, args.global_batch,
              args.lr, args.input_workers),
        max_restarts=args.restart_budget, kill_plan=kill_plan,
        generation_timeout_s=args.generation_timeout,
        telemetry_dir=args.telemetry_dir)
    result = sup.run()
    for value in sorted(result.return_values, key=str):
        print(f"task result: {value}")
    print(f"done: {len(files)} splits x {args.epochs} epochs over "
          f"{args.input_workers} input worker(s), "
          f"{sup.restarts_used} restart(s), "
          f"final generation {sup.generation}")
    if args.telemetry_dir:
        print(f"recovery timeline: python tools/obs_report.py "
              f"{args.telemetry_dir}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--telemetry-dir", default=None,
                    help="enable telemetry: per-step train.step events "
                         "(JSONL) land here; render with "
                         "tools/obs_report.py")
    ap.add_argument("--elastic", action="store_true",
                    help="run as a multi-worker job under the recovery "
                         "supervisor (worker death -> reform -> resume)")
    ap.add_argument("--workers", type=int, default=2,
                    help="elastic: number of worker processes")
    ap.add_argument("--save-every", type=int, default=10,
                    help="elastic: checkpoint every N steps")
    ap.add_argument("--restart-budget", type=int, default=3,
                    help="elastic: max cluster reforms before "
                         "RecoveryFailedError")
    ap.add_argument("--ckpt-dir", default=None,
                    help="elastic: checkpoint directory (default: tmp)")
    ap.add_argument("--kill-seed", type=int, default=None,
                    help="elastic chaos: SIGKILL workers on a schedule "
                         "derived from this seed")
    ap.add_argument("--kills", type=int, default=1,
                    help="elastic chaos: number of scheduled kills")
    ap.add_argument("--permanent-kill", action="store_true",
                    help="elastic chaos: the seed-chosen worker's "
                         "machine dies for good (kill re-fires every "
                         "generation; pair with --shrink-after)")
    ap.add_argument("--shrink-after", type=int, default=None,
                    help="elastic: after N failed restarts of the same "
                         "task, reform at one fewer worker "
                         "(topology-elastic resharded restore)")
    ap.add_argument("--min-workers", type=int, default=1,
                    help="elastic: never shrink below this many workers")
    ap.add_argument("--local-ckpt-dir", default=None,
                    help="elastic: node-local fast checkpoint tier "
                         "(default: <ckpt-dir>.local)")
    ap.add_argument("--no-local-tier", action="store_true",
                    help="elastic: disable the local disk tier")
    ap.add_argument("--snapshot-every", type=int, default=None,
                    help="elastic: host-snapshot cadence between disk "
                         "saves (default: save-every // 2)")
    ap.add_argument("--no-snapshots", action="store_true",
                    help="elastic: disable host/peer snapshot tiers")
    ap.add_argument("--generation-timeout", type=float, default=600.0,
                    help="elastic: per-generation wall budget (s)")
    ap.add_argument("--data-service", action="store_true",
                    help="run with a disaggregated input service under "
                         "the recovery supervisor: task 0 trains (and "
                         "dispatches FILE splits), tasks 1..M execute "
                         "the input pipeline under heartbeat-backed "
                         "leases (--kill-seed SIGKILLs input workers)")
    ap.add_argument("--input-workers", type=int, default=2,
                    help="data-service: input-worker tasks")
    ap.add_argument("--epochs", type=int, default=2,
                    help="data-service: epochs (each = one exactly-once "
                         "pass over every FILE split)")
    ap.add_argument("--split-files", type=int, default=8,
                    help="data-service: FILE splits the sample pool is "
                         "sharded into")
    args = ap.parse_args()

    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()      # exported: spawned workers share it

    if args.data_service:
        run_data_service(args)
        return
    if args.elastic:
        run_elastic(args)
        return

    import jax

    from distributed_tensorflow_tpu import telemetry
    from distributed_tensorflow_tpu.input.dataset import Dataset
    from distributed_tensorflow_tpu.models.mnist_cnn import (
        create_train_state, make_train_step, synthetic_data)
    from distributed_tensorflow_tpu.parallel.mirrored import MirroredStrategy

    exporter = None
    if args.telemetry_dir:
        telemetry.configure(args.telemetry_dir)
        # live scrape: metrics-live.prom in the run dir (plus /metrics
        # when DTX_METRICS_PORT is set)
        exporter = telemetry.MetricsExporter(dir=args.telemetry_dir)

    strategy = MirroredStrategy()
    print(f"devices: {strategy.num_replicas_in_sync} replicas on "
          f"{jax.default_backend()}")

    data = synthetic_data(4096)
    ds = Dataset.from_tensor_slices(data).shuffle(4096).batch(
        args.global_batch).repeat()
    dist_ds = strategy.experimental_distribute_dataset(ds)

    state, model, tx = create_train_state(jax.random.PRNGKey(0),
                                          learning_rate=args.lr)
    # native path (SURVEY §3.4): replicated state + ONE compiled SPMD
    # step; the distributed dataset lands batches sharded over the mesh
    state = strategy.replicate(state)
    step_fn = strategy.compile_step(make_train_step(model, tx))

    from distributed_tensorflow_tpu.training.loops import StepTelemetry
    steps_telemetry = StepTelemetry()
    it = iter(dist_ds)
    for step in range(args.steps):
        state, metrics = step_fn(state, next(it))
        log_step = step % 20 == 0 or step == args.steps - 1
        steps_telemetry.step_completed(
            step, loss=metrics["loss"] if log_step else None)
        if log_step:
            print(f"step {step}: loss={float(metrics['loss']):.4f} "
                  f"acc={float(metrics['accuracy']):.3f}")
    print("done")
    if exporter is not None:
        exporter.stop()
    telemetry.shutdown()


if __name__ == "__main__":
    main()
