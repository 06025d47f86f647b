#!/usr/bin/env python
"""BERT MLM pretraining on synthetic corpus (BASELINE.md config #3).

≙ the reference's BERT-base CollectiveAllReduceStrategy workload: here
the encoder is the flagship transformer in bidirectional mode with
on-device dynamic 80/10/10 masking, sharded over whatever mesh axes you
pick (dp / fsdp / tp), with GSPMD inserting the gradient allreduce.

    python examples/train_bert.py --axes dp=-1 --steps 20
    python examples/train_bert.py --axes dp=2,tp=2 --seq 512
"""

import argparse
import os
import sys
import time

import jax

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.cluster.topology import make_mesh
from distributed_tensorflow_tpu.models import bert
from distributed_tensorflow_tpu.utils.compile_cache import (
    enable_compile_cache)


def parse_axes(spec: str) -> dict:
    return {k: int(v) for k, v in
            (kv.split("=") for kv in spec.split(","))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--axes", default="dp=-1")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized model (without it: BERT-base, "
                         "whatever the backend)")
    args = ap.parse_args()

    enable_compile_cache()
    bootstrap.initialize()
    mesh = make_mesh(parse_axes(args.axes))
    cfg = (bert.tiny_bert_config(max_seq_len=args.seq)
           if args.tiny else bert.bert_config(max_seq_len=args.seq))

    state, step_fn = bert.make_sharded_train_step(
        cfg, mesh, args.global_batch)
    batch = bert.synthetic_corpus(args.global_batch, cfg.max_seq_len,
                                  cfg.vocab_size)

    t0 = None
    for i in range(args.steps):
        state, metrics = step_fn(state, batch)
        if i == 0:
            jax.block_until_ready(metrics["loss"])
            t0 = time.time()
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: mlm_loss={float(metrics['loss']):.4f}",
                  flush=True)
    jax.block_until_ready(state["step"])
    if args.steps > 1:
        rate = (args.steps - 1) * args.global_batch / (time.time() - t0)
        print(f"throughput: {rate:,.1f} samples/sec on {mesh.shape}")
    bootstrap.shutdown()


if __name__ == "__main__":
    main()
