#!/usr/bin/env python
"""Serve the flagship transformer: continuous batching + KV-cache decode.

Default mode runs ONE in-process engine against a seeded request load
and prints the latency/throughput summary. ``--elastic`` instead runs N *serving replicas* under the
recovery supervisor (resilience/supervisor.py) — each replica statically
owns a shard of the workload, heartbeats per engine step, and appends
completed requests to ``served-<task>.jsonl``. Kill one mid-load (try
``--kill-seed``) and the supervisor reforms the cluster; the restarted
replica re-queues its unfinished requests from the completion log and
serves them to the SAME tokens (greedy decode over fixed weights is
deterministic). Render the run with ``tools/obs_report.py
<telemetry-dir>`` — serving request latency and the recovery timeline
share one report.

``--elastic --disagg`` splits the replica fleet into one prefill
replica (task 0: owns admission, migrates each prefilled sequence's KV
blocks to a decode task over the write-once chunked blob transport) and
N-1 decode replicas. Greedy outputs stay byte-identical to the
monolithic fleet; chaos kills exercise prefill death mid-migration and
decode death while holding adopted blocks.

With ``--ckpt-dir`` the replicas restore weights down the checkpoint
recovery ladder (CheckpointManager.restore_latest — host snapshot >
peer replica > local disk > durable disk); ``--write-ckpt`` first
writes a seed-deterministic checkpoint there so the restore path is
exercised end-to-end.
"""

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def run_local(args):
    import time

    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu import telemetry
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM)
    from distributed_tensorflow_tpu.serving import InferenceEngine
    from distributed_tensorflow_tpu.serving.replica import seeded_requests

    if args.telemetry_dir:
        telemetry.configure(args.telemetry_dir)
    cfg = TransformerConfig.tiny(max_seq_len=64)
    speed_kw = dict(prefix_caching=args.prefix_cache,
                    speculative_k=args.speculative,
                    kv_dtype=args.kv_dtype)
    if args.ckpt_dir:
        engine = InferenceEngine.from_checkpoint(
            cfg, args.ckpt_dir, num_blocks=64, block_size=8,
            max_slots=4, max_prompt_len=16,
            queue_capacity=args.requests + 1, **speed_kw)
    else:
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        engine = InferenceEngine(cfg, params, num_blocks=64, block_size=8,
                                 max_slots=4, max_prompt_len=16,
                                 queue_capacity=args.requests + 1,
                                 **speed_kw)
    reqs = seeded_requests(args.seed, args.requests, cfg.vocab_size)
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    done = engine.run_until_idle()
    span = time.perf_counter() - t0
    lats = sorted(r["latency_s"] for r in done.values())
    toks = sum(len(r["tokens"]) for r in done.values())
    p = lambda q: lats[min(len(lats) - 1, int(q * (len(lats) - 1)))]  # noqa: E731
    print(f"served {len(done)}/{args.requests} requests in {span:.2f}s "
          f"— {toks / span:.1f} tokens/s, latency p50 "
          f"{p(0.5) * 1e3:.1f}ms p99 {p(0.99) * 1e3:.1f}ms")
    print(f"engine stats: {engine.stats()}")
    if args.telemetry_dir:
        telemetry.shutdown()
        print(f"report: python tools/obs_report.py {args.telemetry_dir}")


def write_checkpoint(ckpt_dir: str):
    """Seed-deterministic serving checkpoint (what a trainer would have
    produced) so --ckpt-dir restores real weights."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        Checkpoint, CheckpointManager)
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM)

    cfg = TransformerConfig.tiny(max_seq_len=64)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    params = (params.unfreeze() if hasattr(params, "unfreeze")
              else dict(params))
    mgr = CheckpointManager(Checkpoint(params=params), ckpt_dir)
    mgr.save(checkpoint_number=1)
    print(f"wrote serving checkpoint to {ckpt_dir}")


def disagg_kill_plan(seed: int, num_workers: int, kills: int,
                     step_range):
    """Disaggregation-aware chaos schedule: alternate kills between the
    prefill replica (task 0 — dies mid-migration, since it exports KV
    blobs every step) and a seed-chosen decode replica (dies holding
    adopted blocks). Same seeding discipline as seeded_kill_plan."""
    import random

    from distributed_tensorflow_tpu.resilience import KillSpec

    rng = random.Random(f"dtx-kill-disagg:{seed}")
    plan = []
    for i in range(kills):
        worker = 0 if i % 2 == 0 else rng.randrange(1, num_workers)
        plan.append(KillSpec(worker=worker,
                             after_step=rng.randrange(*step_range)))
    return plan


def run_elastic(args):
    from distributed_tensorflow_tpu.resilience import (
        RecoverySupervisor, seeded_kill_plan)
    from distributed_tensorflow_tpu.serving.replica import serving_replica

    if args.disagg and args.workers < 2:
        raise SystemExit("--disagg needs --workers >= 2 "
                         "(one prefill + at least one decode replica)")
    run_dir = args.run_dir or args.telemetry_dir
    if not run_dir:
        import tempfile
        run_dir = tempfile.mkdtemp(prefix="serve_elastic_")
    os.makedirs(run_dir, exist_ok=True)
    kill_plan = ()
    if args.kill_seed is not None:
        # kill step range sized to the per-replica workload so the
        # SIGKILL lands while requests are genuinely in flight
        per_replica = max(1, args.requests // args.workers)
        step_range = (3, max(6, per_replica))
        if args.disagg:
            kill_plan = disagg_kill_plan(
                args.kill_seed, args.workers, args.kills, step_range)
        else:
            kill_plan = seeded_kill_plan(
                args.kill_seed, args.workers, kills=args.kills,
                step_range=step_range)
        print(f"chaos kill plan (seed {args.kill_seed}): {kill_plan}")
    sup = RecoverySupervisor(
        serving_replica, num_workers=args.workers,
        args=(run_dir, args.requests, args.seed),
        kwargs={"ckpt_dir": args.ckpt_dir,
                "step_delay_s": args.step_delay,
                "prefix_caching": args.prefix_cache,
                "speculative_k": args.speculative,
                "kv_dtype": args.kv_dtype,
                "disagg": args.disagg},
        max_restarts=args.restart_budget, kill_plan=kill_plan,
        generation_timeout_s=args.generation_timeout,
        telemetry_dir=args.telemetry_dir)
    result = sup.run()
    for task, served, total in sorted(result.return_values):
        print(f"replica {task}: served {served} this generation "
              f"({total} total on its shard)")
    print(f"done: {sup.restarts_used} restart(s), "
          f"{sup.failures_total} recorded failure(s), "
          f"final generation {sup.generation}")
    print(f"completion logs: {run_dir}/served-*.jsonl")
    if args.telemetry_dir:
        print(f"report: python tools/obs_report.py {args.telemetry_dir}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24,
                    help="seeded workload size")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload seed (replayable)")
    ap.add_argument("--telemetry-dir", default=None,
                    help="enable telemetry (serve.step/serve.request "
                         "events + recovery timeline)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore serving weights down the recovery "
                         "ladder from this CheckpointManager directory")
    ap.add_argument("--write-ckpt", action="store_true",
                    help="first write a seed-deterministic checkpoint "
                         "to --ckpt-dir (exercises the restore path)")
    ap.add_argument("--elastic", action="store_true",
                    help="run N supervised serving replicas (worker "
                         "death -> reform -> re-queue in-flight)")
    ap.add_argument("--workers", type=int, default=1,
                    help="elastic: number of serving replicas")
    ap.add_argument("--run-dir", default=None,
                    help="elastic: completion-log directory "
                         "(default: the telemetry dir)")
    ap.add_argument("--kill-seed", type=int, default=None,
                    help="elastic chaos: SIGKILL replicas on a schedule "
                         "derived from this seed")
    ap.add_argument("--kills", type=int, default=1)
    ap.add_argument("--restart-budget", type=int, default=3)
    ap.add_argument("--generation-timeout", type=float, default=600.0)
    ap.add_argument("--step-delay", type=float, default=0.05,
                    help="elastic: per-step pacing seconds (gives "
                         "step-targeted chaos kills a window to land)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable copy-on-write prefix caching "
                         "(cross-request KV reuse; outputs invariant, "
                         "restarted replicas rebuild the cache cold)")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="speculative decoding: K draft tokens per "
                         "slot per step (greedy outputs exactly equal "
                         "non-speculative)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("f32", "bf16", "int8"),
                    help="KV-pool storage dtype (int8: quantized, "
                         "2x+ slots per chip)")
    ap.add_argument("--disagg", action="store_true",
                    help="elastic: disaggregated prefill/decode — task "
                         "0 prefills and migrates KV blocks to decode "
                         "tasks 1..N-1 over the chunked blob transport "
                         "(needs --workers >= 2)")
    args = ap.parse_args()

    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()      # exported: spawned workers share it

    if args.write_ckpt:
        if not args.ckpt_dir:
            ap.error("--write-ckpt requires --ckpt-dir")
        write_checkpoint(args.ckpt_dir)
    if args.elastic:
        run_elastic(args)
    else:
        run_local(args)


if __name__ == "__main__":
    main()
