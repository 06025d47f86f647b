#!/usr/bin/env python
"""Flagship transformer pretraining across every parallelism axis.

≙ the reference's BERT/Transformer-big multi-worker scripts
(BASELINE.md configs #3/#5), driven through the native SPMD path:
pick a mesh shape, get ONE compiled train step, feed global batches.
The model is transformer-big (d_model 1024) unless ``--tiny`` is given;
on the virtual CPU mesh pass ``--tiny``.

    # pure data parallel over all local devices
    python examples/train_transformer.py --axes dp=-1

    # fsdp + tensor parallel
    python examples/train_transformer.py --axes dp=2,fsdp=2,tp=2

    # GPipe pipeline over dp×pp
    python examples/train_transformer.py --axes dp=4,pp=2 --microbatches 4

    # MoE experts over dp×ep
    python examples/train_transformer.py --axes dp=2,ep=4 --moe-experts 4

    # causal sequence parallelism (ring / striped)
    python examples/train_transformer.py --axes dp=4,sp=2 --sp-impl ring
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
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    make_pipelined_train_step,
    make_sharded_train_step,
    synthetic_tokens,
)
from distributed_tensorflow_tpu.utils.compile_cache import (
    enable_compile_cache)


def parse_axes(spec: str) -> dict:
    out = {}
    for kv in spec.split(","):
        k, v = kv.split("=")
        out[k] = int(v)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--axes", default="dp=-1",
                    help="mesh axes, e.g. dp=2,fsdp=2,tp=2")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized model (d_model 64); without it the "
                         "run is transformer-big on whatever backend "
                         "jax found")
    ap.add_argument("--microbatches", type=int, default=2,
                    help="pipeline microbatches when the mesh has pp")
    ap.add_argument("--schedule", default="gpipe",
                    choices=["gpipe", "1f1b"],
                    help="pipeline schedule (1f1b = interleaved "
                         "one-forward-one-backward, O(stages) "
                         "activation memory)")
    ap.add_argument("--grad-sync", default="auto",
                    choices=["auto", "bucketed", "gspmd"],
                    help="gradient sync on non-pp meshes (auto = "
                         "reverse-order bucketed collectives on >1 "
                         "device pure-dp meshes)")
    ap.add_argument("--moe-experts", type=int, default=0)
    ap.add_argument("--sp-impl", default="ring",
                    choices=["ring", "ulysses", "striped"])
    args = ap.parse_args()

    enable_compile_cache()
    bootstrap.initialize()                 # no-op single-process
    mesh = make_mesh(parse_axes(args.axes))
    print(f"mesh: {dict(mesh.shape)} on {jax.default_backend()}")

    kw = {}
    if args.seq_len:
        kw["max_seq_len"] = args.seq_len
    if args.moe_experts:
        kw["moe_experts"] = args.moe_experts
    if "sp" in mesh.shape and mesh.shape["sp"] > 1:
        kw["sp_impl"] = args.sp_impl
        if args.tiny and args.sp_impl == "striped":
            kw["sp_attn_impl"] = "interpret"
    cfg = (TransformerConfig.tiny(**kw) if args.tiny
           else TransformerConfig.transformer_big(**kw))

    if mesh.shape.get("pp", 1) > 1:
        state, step = make_pipelined_train_step(
            cfg, mesh, args.global_batch,
            num_microbatches=args.microbatches,
            schedule=args.schedule)
    else:
        state, step = make_sharded_train_step(cfg, mesh,
                                              args.global_batch,
                                              grad_sync=args.grad_sync)

    tokens = synthetic_tokens(args.global_batch, cfg.max_seq_len,
                              cfg.vocab_size)
    t0 = time.perf_counter()
    for i in range(args.steps):
        state, metrics = step(state, {"tokens": tokens})
        if i % 5 == 0 or i == args.steps - 1:
            loss = float(metrics["loss"])
            print(f"step {i}: loss={loss:.4f}")
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    tok = args.steps * args.global_batch * cfg.max_seq_len
    print(f"{tok / dt:,.0f} tokens/s over {args.steps} steps")


if __name__ == "__main__":
    main()
