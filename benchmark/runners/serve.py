"""Runner "serve": builds the engine named by the configuration file's
dotted path, drives ``step()`` from one thread, and keeps the token
clock: a token's time is the return of the ``step()`` that released it,
a request's first-token time is counted from the instant it was DUE.

``drive`` takes any source with ``poll(t)``, ``finished(id)``,
``next_due_s()``, ``ramp_s`` and ``sample`` (see
``benchmark/generators``), so open and closed loops run through the
same code. The record it returns (``metric_math.reduce`` reads it):

per measured request ``ttft_ms``, ``tpot_ms``, ``queue_wait_ms``,
``submit_late_ms``; per step in the window ``decode_batch`` (sequences
that got a token), ``waiting`` (requests left in the queue; its means
over the window's first and last quarter are the scalars
``waiting_first_quarter`` and ``waiting_last_quarter``, by which
``sweep.py`` says whether the queue grew), ``plain_step_ms`` (steps
that admitted nothing); scalars ``tokens`` and ``elapsed_s`` over whole
steps, ``cached_tokens`` and ``prompt_tokens`` summed per admission,
``blocks_used_peak_share``.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp

from benchmark import metric_math, reference
from benchmark.runners.common import fold_seed, model_config, resolve
from distributed_tensorflow_tpu.models.transformer import TransformerLM
from distributed_tensorflow_tpu.serving import Request
from distributed_tensorflow_tpu.serving.scheduler import QueueOverflowError

DRAIN_S = 15.0           # measured requests unfinished this long after
                         # the window closes have failed
GC_LEAD_S = 0.5          # the one collection comes this long before
                         # the window, or before the clock starts


class Runner:
    def __init__(self, config: dict, traffic: dict, generator, seed: int,
                 devices):
        self.config = config
        self.traffic = traffic
        self.generator = generator
        self.raw_seed = seed
        self.seed = fold_seed(seed)
        self.device = devices[0]
        self.model = model_config(config)
        self.failures: list[str] = []

    def source(self, **kw):
        return self.generator.make(self.traffic, self.raw_seed,
                                   self.model.vocab_size, **kw)

    def build(self) -> None:
        model = TransformerLM(self.model)
        with jax.default_device(self.device):
            params = jax.jit(lambda rng: model.init(
                rng, jnp.zeros((1, 8), jnp.int32))["params"])(
                    jax.random.PRNGKey(self.seed))
            self.engine = resolve(self.config["builder"])(
                self.model, params, **self.config["engine"])

    def reference_check(self) -> dict:
        return {}               # after the window, on what was served

    def warm(self) -> dict:
        for wave in self.source(tag="w").warmup():
            for req in wave:
                self._submit(req)
            self.engine.run_until_idle()
        return {}

    def _submit(self, req: dict) -> bool:
        try:
            self.engine.submit(Request(
                id=req["id"], tokens=req["tokens"],
                max_new_tokens=req["max_new_tokens"]))
        except QueueOverflowError:
            return False
        return True

    def measure(self, seconds: float, tracer) -> dict:
        return self.drive(self.source(), seconds, tracer)

    def drive(self, source, seconds: float, tracer) -> dict:
        """Offer ``source``'s load from now: ``ramp_s`` of it is set-up,
        the next ``seconds`` are the window, and the load goes on after
        it until every measured request has finished."""
        engine, sched = self.engine, self.engine.scheduler
        blocks_total = engine.cache_cfg.usable_blocks
        reqs: dict[str, dict] = {}
        # per step: (end_s, ms, admitted, decoded, tokens, left waiting)
        steps: list[tuple] = []
        min_free = blocks_total
        # a full collection walks every object the process holds: tens
        # of milliseconds here, a second in a test worker that has run
        # a few hundred tests. A ramp too short to hold it must not
        # lose its window to it
        collected = source.ramp_s <= GC_LEAD_S
        if collected:
            gc.collect()
        epoch = time.monotonic()
        open_s = epoch + source.ramp_s
        close_s = open_s + seconds
        tracer.window(open_s, close_s)
        while True:
            now = time.monotonic()
            if not collected and now >= open_s - GC_LEAD_S:
                gc.collect()                # once, before the window
                collected = True
            tracer.tick(now)
            for req in source.poll(now - epoch):
                due = (now if req["due_s"] is None
                       else epoch + req["due_s"])
                with jax.profiler.TraceAnnotation("bench.submit"):
                    ok = self._submit(req)
                reqs[req["id"]] = {
                    "due": due, "late_ms": (now - due) * 1e3,
                    "n_prompt": len(req["tokens"]), "prompt": req["tokens"],
                    "max_new": req["max_new_tokens"], "rejected": not ok,
                    "times": [], "admitted": None, "cached": 0,
                    "done": None, "tokens": None}
            if now >= close_s and self._settled(source, reqs, open_s,
                                                close_s, now):
                break
            if sched.idle:
                nxt = source.next_due_s()
                if nxt is None:
                    break
                with jax.profiler.TraceAnnotation("bench.wait_request"):
                    time.sleep(max(0.0, min(epoch + nxt - now, 0.05)))
                continue
            s0 = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                finished = engine.step()
            s1 = time.monotonic()
            admitted = decoded = released = 0
            for seq in sched.running.values():
                r = reqs.get(seq.request.id)
                if r is None:
                    continue
                if r["admitted"] is None:
                    r["admitted"] = seq.admitted_s
                    r["cached"] = seq.cached_tokens
                    admitted += 1
                new = len(seq.generated) - len(r["times"])
                if new > 0:
                    r["times"].extend([s1] * new)
                    decoded += 1
                    released += new
            for rec in finished:
                r = reqs.get(rec["id"])
                if r is not None:
                    r["done"], r["tokens"] = s1, rec["tokens"]
                    source.finished(rec["id"])
            min_free = min(min_free, sched.allocator.num_free)
            steps.append((s1, (s1 - s0) * 1e3, admitted, decoded, released,
                          len(sched.queue)))
        tracer.close()
        return self._record(source, reqs, steps, open_s, close_s,
                            1.0 - min_free / blocks_total)

    @staticmethod
    def _measured(source, r: dict, open_s: float, close_s: float) -> bool:
        if source.sample == "due_in_window":
            return open_s <= r["due"] < close_s
        return r["rejected"] or (r["done"] is not None
                                 and open_s <= r["done"] < close_s)

    def _settled(self, source, reqs, open_s, close_s, now) -> bool:
        """After the window: nothing more to wait for in a closed loop;
        in an open loop, every request due inside it has finished or
        the drain limit has passed."""
        if source.sample != "due_in_window" or now >= close_s + DRAIN_S:
            return True
        return all(r["done"] is not None or r["rejected"]
                   for r in reqs.values()
                   if open_s <= r["due"] < close_s)

    def _record(self, source, reqs, steps, open_s, close_s,
                used_share) -> dict:
        measured = [r for r in reqs.values()
                    if self._measured(source, r, open_s, close_s)]
        good = [r for r in measured if r["done"] is not None
                and len(r["tokens"]) == r["max_new"]
                and all(0 <= t < self.model.vocab_size
                        for t in r["tokens"])]
        ends = [s[0] for s in steps]
        tokens, elapsed = metric_math.whole_step_rate(
            ends, [s[4] for s in steps], open_s, close_s)
        inside = [s for s in steps if open_s <= s[0] < close_s]
        plain = [s[1] for s in inside if s[2] == 0 and s[3] > 0]
        waiting = [s[5] for s in inside]
        began, ended = metric_math.quarter_means(waiting)
        return {
            "window_open_s": open_s,
            "attempted": len(measured),
            "failed": len(measured) - len(good),
            "rejected": sum(r["rejected"] for r in measured),
            "served": good,
            "ttft_ms": [metric_math.ttft_ms(r["due"], r["times"][0])
                        for r in good],
            "tpot_ms": [metric_math.tpot_ms(r["times"]) for r in good
                        if len(r["times"]) > 1],
            "queue_wait_ms": [(r["admitted"] - r["due"]) * 1e3
                              for r in good],
            "submit_late_ms": [r["late_ms"] for r in good],
            "tokens": tokens, "elapsed_s": elapsed,
            "decode_batch": [s[3] for s in inside if s[3] > 0],
            "waiting": waiting,
            "waiting_first_quarter": began, "waiting_last_quarter": ended,
            "plain_step_ms": plain,
            "cached_tokens": float(sum(r["cached"] for r in good)),
            "prompt_tokens": float(sum(r["n_prompt"] for r in good)),
            "blocks_used_peak_share": used_share,
        }

    def verify(self, record: dict) -> dict:
        """After the window, the plain reference over what was served: at every generated
        position of the first requests, the reference's logit of the
        engine's token lies within ``margin`` of its largest (greedy
        tokens flip between the cold and the cache-hit path in bf16, so
        equality cannot be the gate); and the pool's blocks are all
        accounted for."""
        check = self.config["check"]
        served = record.pop("served")
        worst = 0.0
        for r in served[:check["requests"]]:
            gap = reference.greedy_gap(
                self.engine.params, list(r["prompt"]) + list(r["tokens"]),
                r["n_prompt"], self.model.max_seq_len)
            worst = max(worst, float(gap.max()))
        if not worst <= check["logit_margin"]:
            self.failures.append(
                f"an engine token's reference logit is {worst:.4f} below "
                f"the reference's largest; the margin is "
                f"{check['logit_margin']}")
        if not served:
            self.failures.append("no measured request was served")
        acct = self.engine.block_accounting()
        if not acct["conserved"] or acct["leaked_refs"]:
            self.failures.append(f"KV block accounting broken: {acct}")
        return {"reference_worst_gap": worst}
