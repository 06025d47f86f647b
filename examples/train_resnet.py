#!/usr/bin/env python
"""ResNet-50 sync data-parallel training, single- or multi-worker.

≙ the reference's config #2 (BASELINE.md): ResNet-50 ImageNet under
`MultiWorkerMirroredStrategy` with NCCL allreduce (reference:
tensorflow/python/distribute/collective_all_reduce_strategy.py:57).
TPU-native shape: every process holds a slice of one global `jax.Array`
batch; ONE compiled SPMD step runs on the global mesh and GSPMD inserts
the gradient allreduce over ICI/DCN — no per-tensor RPC, no collective
executor.

Input: either synthetic device-resident batches (the perf-isolated
default) or REAL on-disk JPEGs through the parallel host pipeline
(input/image_ops.py + Dataset.map(num_parallel_calls=AUTOTUNE) +
prefetch + InfeedLoop double-buffered device_put), with per-step
infeed-wait reported so host-boundedness is a number, not a guess:

    # single process, all local devices, synthetic batches
    python examples/train_resnet.py --steps 30

    # REAL JPEG path: generate 512 JPEGs on disk, then train from them
    python examples/train_resnet.py --steps 30 --gen-jpegs 512

    # ... or from an existing directory (img_*_cls<label>.jpg layout)
    python examples/train_resnet.py --steps 30 --data-dir /data/jpegs

    # real multi-process sync DP on one box (3 workers, CPU backend),
    # TF_CONFIG injected per process exactly like a cluster launch;
    # JPEG files are FILE-auto-sharded across the workers:
    python examples/train_resnet.py --spawn 3 --steps 10 --gen-jpegs 512
"""

import argparse
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _jpeg_infeed(data_dir: str, runtime, mesh, per_process_batch: int,
                 image_size: int, num_classes: int):
    """files -> FILE-sharded parallel decode pipeline -> InfeedLoop
    staging global jax.Arrays (the host data plane of this example)."""
    import glob
    import os

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu.input.dataset import AUTOTUNE
    from distributed_tensorflow_tpu.input.image_ops import jpeg_pipeline
    from distributed_tensorflow_tpu.training.loops import InfeedLoop

    files = sorted(glob.glob(os.path.join(data_dir, "*.jpg")))
    if len(files) < runtime.num_processes:
        raise SystemExit(
            f"{data_dir} has {len(files)} JPEGs; FILE sharding needs at "
            f"least one per process ({runtime.num_processes})")
    ds = jpeg_pipeline(
        files, batch_size=per_process_batch, image_size=image_size,
        num_parallel_calls=AUTOTUNE, prefetch_depth=4,
        num_shards=runtime.num_processes,
        shard_index=runtime.process_id)

    sharding = NamedSharding(mesh, P("dp"))

    def place(batch):
        if int(batch["label"].max()) >= num_classes:
            raise ValueError(
                f"label {int(batch['label'].max())} >= num_classes "
                f"{num_classes}; generate the data with matching classes")
        return {
            "image": jax.make_array_from_process_local_data(
                sharding, batch["image"]),
            "label": jax.make_array_from_process_local_data(
                sharding, batch["label"]),
        }

    return InfeedLoop(iter(ds), place_fn=place, buffer_size=3), ds


def worker_main(steps: int, global_batch: int, image_size: int,
                data_dir: str | None = None):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu.cluster import bootstrap
    from distributed_tensorflow_tpu.cluster.topology import make_mesh
    from distributed_tensorflow_tpu.models import resnet

    runtime = bootstrap.initialize()           # reads TF_CONFIG if present
    mesh = make_mesh({"dp": -1})               # all global devices
    if global_batch < runtime.num_processes:
        raise SystemExit(
            f"--global-batch {global_batch} is smaller than the process "
            f"count {runtime.num_processes}; every process needs >= 1 "
            f"sample")
    if global_batch % runtime.num_processes:
        adjusted = (global_batch // runtime.num_processes
                    * runtime.num_processes)
        print(f"global batch {global_batch} not divisible by "
              f"{runtime.num_processes} processes; using {adjusted}",
              flush=True)
        global_batch = adjusted
    cfg = resnet.ResNetConfig.resnet50() if image_size >= 128 \
        else resnet.ResNetConfig.tiny()
    state, step_fn = resnet.make_sharded_train_step(
        cfg, mesh, global_batch, image_size=image_size)

    # Per-host input feeding (≙ dataset auto-sharding, input_lib.py:729):
    # each process materializes ONLY its slice of the global batch and
    # assembles the global jax.Array from process-local shards.
    sharding = NamedSharding(mesh, P("dp"))
    per_process = global_batch // runtime.num_processes

    infeed = None
    if data_dir is not None:
        infeed, _ds = _jpeg_infeed(data_dir, runtime, mesh, per_process,
                                   image_size, cfg.num_classes)
        next_batch = infeed.next
    else:
        local = resnet.synthetic_images(
            per_process, image_size, cfg.num_classes,
            seed=runtime.process_id)
        static = {
            "image": jax.make_array_from_process_local_data(
                sharding, local["image"]),
            "label": jax.make_array_from_process_local_data(
                sharding, local["label"]),
        }
        next_batch = lambda: static

    t0, imgs = None, 0
    for i in range(steps):
        batch = next_batch()
        state, metrics = step_fn(state, batch)
        if i == 0:                      # skip compile in the rate
            jax.block_until_ready(metrics["loss"])
            if infeed is not None:      # spin-up wait is not steady state
                infeed.total_wait_s = 0.0
                infeed.batches = 0
            t0 = time.time()
        else:
            imgs += global_batch
        if i % 10 == 0 or i == steps - 1:
            print(f"[p{runtime.process_id}] step {i}: "
                  f"loss={float(metrics['loss']):.4f} "
                  f"acc={float(metrics['accuracy']):.3f}", flush=True)
    jax.block_until_ready(state["step"])
    dt = time.time() - t0
    if runtime.is_chief and imgs:
        print(f"throughput: {imgs / dt:,.1f} images/sec "
              f"({runtime.num_processes} processes, "
              f"{len(jax.devices())} devices)", flush=True)
        if infeed is not None:
            frac = infeed.wait_fraction(dt)
            print(f"infeed wait: {infeed.total_wait_s * 1e3:.1f} ms over "
                  f"{infeed.batches} steps = {frac:.1%} of wall time "
                  f"({'host-bound' if frac >= 0.05 else 'device-bound'})",
                  flush=True)
    final_loss = float(metrics["loss"])
    if infeed is not None:
        infeed.stop()
    bootstrap.shutdown()
    return final_loss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=32,
                    help="32 = tiny config for CPU demo; 224 = ResNet-50")
    ap.add_argument("--data-dir", default=None,
                    help="directory of img_*_cls<label>.jpg files; train "
                         "on REAL decoded JPEGs through the parallel "
                         "host pipeline")
    ap.add_argument("--gen-jpegs", type=int, default=0,
                    help="generate N JPEGs on disk first and train from "
                         "them (implies the real-data path)")
    ap.add_argument("--spawn", type=int, default=0,
                    help="spawn N local worker processes with TF_CONFIG "
                         "(multi-worker demo on one box)")
    args = ap.parse_args()

    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()      # exported, so --spawn workers share it

    data_dir = args.data_dir
    if args.gen_jpegs:
        import tempfile

        from distributed_tensorflow_tpu.input.image_ops import (
            generate_jpeg_directory)
        num_classes = 1000 if args.image_size >= 128 else 10
        data_dir = tempfile.mkdtemp(prefix="dtx_jpegs_")
        # sources ~25% larger than the train crop (RandomCrop headroom)
        generate_jpeg_directory(data_dir, args.gen_jpegs,
                                image_size=args.image_size * 5 // 4,
                                num_classes=num_classes)
        print(f"generated {args.gen_jpegs} JPEGs in {data_dir}",
              flush=True)

    if args.spawn > 1:
        from distributed_tensorflow_tpu.testing import multi_process_runner
        result = multi_process_runner.run(
            worker_main, num_workers=args.spawn,
            args=(args.steps, args.global_batch, args.image_size,
                  data_dir),
            timeout=900)
        losses = result.return_values
        print(f"all {len(losses)} workers done; final losses {losses}")
        assert len(set(round(x, 5) for x in losses)) == 1, \
            "sync DP must keep workers bit-identical"
    else:
        worker_main(args.steps, args.global_batch, args.image_size,
                    data_dir)


if __name__ == "__main__":
    main()
