"""Runner "train": builds the mesh and the step through the dotted path
in the configuration file, feeds a new batch every step, keeps the
device two steps deep, and checks the first loss against the plain
reference.

The record it returns (``metric_math.reduce`` reads it):
``tokens``, ``steps``, ``elapsed_s`` (window opening to the last step's
``block_until_ready``), ``input_wait_s`` (host time taking and placing
the next batch), ``model_flops`` (``flops.train_step_flops`` x steps),
``losses``.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import flops, reference
from benchmark.runners.common import fold_seed, model_config, resolve
from distributed_tensorflow_tpu.cluster.topology import data_axes, make_mesh

STEPS_AHEAD = 2          # steps dispatched before the host waits for one


class Runner:
    def __init__(self, config: dict, traffic: dict, generator, seed: int,
                 devices):
        self.config = config
        self.devices = devices
        self.model = model_config(config)
        self.global_batch = config["global_batch"]
        self.batches = generator.make(
            traffic, seed, global_batch=self.global_batch,
            seq_len=self.model.max_seq_len,
            vocab_size=self.model.vocab_size)
        self.seed = fold_seed(seed)
        self.failures: list[str] = []

    def build(self) -> None:
        mesh = make_mesh(self.config["mesh"], devices=self.devices)
        self.state, self.step = resolve(self.config["builder"])(
            self.model, mesh, global_batch=self.global_batch,
            seed=self.seed)
        self.sharding = NamedSharding(mesh, P(data_axes(mesh) or None))
        n_params = sum(x.size for x in
                       jax.tree_util.tree_leaves(self.state["params"]))
        self.flops_per_step = flops.train_step_flops(
            self.config["model"], self.global_batch, n_params)

    def _put(self, tokens: np.ndarray):
        return {"tokens": jax.device_put(tokens, self.sharding)}

    def reference_check(self) -> dict:
        """Before the first step donates the weights: the reference's
        loss on the first batch, against the program's first loss."""
        self.first_batch = next(self.batches)
        check = self.config["check"]
        self.ref_loss = reference.chunked_loss(
            self.state["params"], self.first_batch,
            chunk=check["reference_chunk"])
        return {"reference_loss": self.ref_loss}

    def warm(self) -> dict:
        """The one shape this cell uses: the step, three times (the
        first compiles or loads; donated buffers settle by the third)."""
        losses = []
        batch = self._put(self.first_batch)
        for _ in range(3):
            self.state, metrics = self.step(self.state, batch)
            losses.append(float(metrics["loss"]))
            batch = self._put(next(self.batches))
        tol = self.config["check"]["loss_abs_tol"]
        if not abs(losses[0] - self.ref_loss) <= tol:
            self.failures.append(
                f"first loss {losses[0]:.5f} differs from the reference's "
                f"{self.ref_loss:.5f} by more than {tol}")
        self.next_batch = batch
        return {"first_loss": losses[0],
                "first_loss_minus_reference": losses[0] - self.ref_loss}

    def measure(self, seconds: float, tracer) -> dict:
        step_tokens = self.global_batch * self.model.max_seq_len
        batch, losses, input_wait = self.next_batch, [], 0.0
        jax.block_until_ready(self.state)
        gc.collect()
        t0 = time.monotonic()
        tracer.window(t0, t0 + seconds)
        while True:
            i = len(losses)
            tracer.tick(time.monotonic())
            with jax.profiler.StepTraceAnnotation("bench.train_step",
                                                  step_num=i):
                self.state, metrics = self.step(self.state, batch)
            losses.append(metrics["loss"])
            w0 = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.feed"):
                batch = self._put(next(self.batches))
            input_wait += time.monotonic() - w0
            if i >= STEPS_AHEAD:
                with jax.profiler.TraceAnnotation("bench.wait_step"):
                    losses[i - STEPS_AHEAD].block_until_ready()
            if time.monotonic() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("bench.wait_step"):
            losses[-1].block_until_ready()
        elapsed = time.monotonic() - t0
        tracer.close()
        return {"window_open_s": t0, "attempted": len(losses),
                "steps": len(losses),
                "tokens": len(losses) * step_tokens, "elapsed_s": elapsed,
                "input_wait_s": input_wait,
                "model_flops": self.flops_per_step * len(losses),
                "losses": losses}

    def verify(self, record: dict) -> dict:
        """After the window: every loss finite, and the last ten below
        the first ten (fresh batches every step, so the loss falls only
        if the model learns the distribution)."""
        losses = record["losses"] = [float(x) for x in record["losses"]]
        record["failed"] = sum(not math.isfinite(x) for x in losses)
        if record["failed"]:
            self.failures.append(f"{record['failed']} losses are not "
                                 f"finite")
        elif len(losses) >= 20 and not (
                sum(losses[-10:]) < sum(losses[:10])):
            self.failures.append(
                f"the last ten losses (mean {sum(losses[-10:]) / 10:.4f}) "
                f"are not below the first ten "
                f"({sum(losses[:10]) / 10:.4f})")
        return {"last_loss": losses[-1]}
