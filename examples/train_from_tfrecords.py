#!/usr/bin/env python
"""Train Wide&Deep from TFRecord files of tf.Example protos.

The migration path a reference user actually takes: their click logs are
TFRecord shards of ``tf.train.Example`` (written by the reference's
tf.data pipelines). This script

1. writes synthetic click data as sharded tf.Example TFRecords
   (stand-in for an existing dataset — delete this step for real data),
2. builds the host pipeline with the framework's own parser:
   ``Dataset.from_files(shards, example_reader(spec)).map.shuffle.batch``,
   FILE auto-sharded across processes with ``auto_shard_dataset``
   (≙ input_ops.py:28 FILE policy — the transform chain replays on each
   process's shard of the file list), each process assembling its local
   slice into the global batch,
3. trains the Wide&Deep model with one jit SPMD step over a dp mesh.

    python examples/train_from_tfrecords.py --steps 60
"""

import argparse
import os
import sys
import tempfile

import jax
import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.cluster.topology import make_mesh
from distributed_tensorflow_tpu.input import (
    Dataset, FixedLenFeature, encode_example, example_reader)
from distributed_tensorflow_tpu.input.native_loader import write_tfrecords
from distributed_tensorflow_tpu.models import wide_deep as wd
from distributed_tensorflow_tpu.utils.compile_cache import (
    enable_compile_cache)


def write_click_shards(cfg, out_dir: str, n_shards: int = 4,
                       per_shard: int = 512) -> list:
    """Synthetic click logs as tf.Example TFRecord shards."""
    data = wd.synthetic_clicks(cfg, n_shards * per_shard)
    paths = []
    for s in range(n_shards):
        lo = s * per_shard
        payloads = [
            encode_example({
                "dense": np.asarray(data["dense"][i]),
                "categorical": np.asarray(data["categorical"][i],
                                          np.int64),
                "label": np.asarray([int(data["label"][i])], np.int64),
            })
            for i in range(lo, lo + per_shard)
        ]
        path = os.path.join(out_dir, f"clicks-{s:05d}-of-{n_shards:05d}")
        write_tfrecords(path, payloads)
        paths.append(path)
    return paths


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--global-batch", type=int, default=128)
    ap.add_argument("--data-dir", default=None,
                    help="existing TFRecord dir (default: write synthetic)")
    args = ap.parse_args()

    enable_compile_cache()
    bootstrap.initialize()
    cfg = wd.WideDeepConfig.tiny()

    if args.data_dir:
        files = sorted(os.path.join(args.data_dir, f)
                       for f in os.listdir(args.data_dir))
    else:
        tmp = tempfile.mkdtemp(prefix="clicks_")
        files = write_click_shards(cfg, tmp)
        print(f"wrote {len(files)} synthetic TFRecord shards to {tmp}")

    spec = {
        "dense": FixedLenFeature((cfg.num_dense_features,), np.float32),
        "categorical": FixedLenFeature((len(cfg.vocab_sizes),), np.int64),
        "label": FixedLenFeature((1,), np.int64),
    }

    def to_batch(ex):
        return {"dense": ex["dense"],
                "categorical": ex["categorical"].astype(np.int32),
                "label": ex["label"][0].astype(np.int32)}

    runtime = bootstrap.runtime()
    per_process = args.global_batch // runtime.num_processes
    # repeat BEFORE shuffle: a fresh shuffle pass per epoch (the
    # reshuffle_each_iteration=True behavior reference pipelines expect).
    ds = (Dataset.from_files(files, example_reader(spec))
          .map(to_batch)
          .repeat()
          .shuffle(1024, seed=runtime.process_id)
          .batch(per_process, drop_remainder=True)
          .prefetch(2))
    from distributed_tensorflow_tpu.input.dataset import (
        AutoShardPolicy, auto_shard_dataset)
    # FILE policy: each process re-reads ONLY its slice of the shard
    # list; the map/shuffle/batch chain replays on top.
    ds = auto_shard_dataset(ds, runtime.num_processes,
                            runtime.process_id, AutoShardPolicy.AUTO)

    mesh = make_mesh({"dp": -1})
    state, step_fn = wd.make_sharded_train_step(
        cfg, mesh, args.global_batch)

    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P("dp"))
    it = iter(ds)
    losses = []
    for i in range(args.steps):
        host = next(it)          # this process's per_process-sized slice
        batch = {k: jax.make_array_from_process_local_data(sharding, v)
                 for k, v in host.items()}
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i}: loss={losses[-1]:.4f}", flush=True)
    first = sum(losses[:10]) / min(10, len(losses))
    last = sum(losses[-10:]) / min(10, len(losses))
    print(f"loss first-10 {first:.4f} -> last-10 {last:.4f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")
    bootstrap.shutdown()


if __name__ == "__main__":
    main()
