#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the two things users pay chip time for, once each, through the
entry points they call, at the full width of transformer-big, in ONE
process (a chip belongs to one process: nothing here starts a child):

1. train, one chip — ``bootstrap.initialize`` → ``make_mesh`` →
   ``make_sharded_train_step`` → a handful of steps on one repeated
   ``synthetic_tokens`` batch, in the configuration of the benchmark's
   ``tbig_train`` (``benchmark/configs/tbig_train.json``);
2. serve, same chip — ``InferenceEngine`` over ``tbig_serve``'s model,
   ``submit`` → ``run_until_idle`` over seeded requests, then a
   shared-prefix batch so the extend program and the copy-on-write pool
   copy run too, then the same requests again through the warm engine;
3. train, four chips (only when JAX reports at least four) — the same
   configuration, seed and batch on ``dp=4`` (the bucketed shard_map
   step) and on ``fsdp=2,tp=2`` (GSPMD with the Pallas kernels under
   shard_map), compared with the one-chip first loss.

This is a smoke, not a measurement: the seconds it prints are plain
information. Any exception or failed check, in any phase, ends the run
with a traceback and a non-zero exit. It exits non-zero at once when
JAX's platform is not ``tpu``; it sets no platform itself. The last
line of a passing run is one JSON object naming the device.

    python chip_smoke.py
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import random
import re
import sys
import time

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.cluster.topology import make_mesh
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    make_sharded_train_step,
    synthetic_tokens,
)
from distributed_tensorflow_tpu.serving import InferenceEngine, Request
from distributed_tensorflow_tpu.serving.replica import seeded_requests
from distributed_tensorflow_tpu.utils.compile_cache import (
    enable_compile_cache)

GLOBAL_BATCH = 8
MOSAIC_CALL = "tpu_custom_call"


class SmokeFailure(AssertionError):
    """A check in a phase did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def train_config() -> TransformerConfig:
    """The model keys of ``benchmark/configs/tbig_train.json``."""
    return TransformerConfig.transformer_big(
        max_seq_len=1024, remat=False, scan_layers=False,
        loss_impl="kernel", loss_chunks=8,
        attn_block_q=1024, attn_block_k=1024,
        adam_mu_dtype=jnp.bfloat16)


def serve_config() -> TransformerConfig:
    """The model keys of ``benchmark/configs/tbig_serve.json``."""
    return TransformerConfig.transformer_big(max_seq_len=1024,
                                             scan_layers=False)


def looped_config(n_layers: int = 3) -> TransformerConfig:
    """The looped model of ``benchmark/configs/ouro_serve.json`` at its
    published widths and passes and a small depth: ``n_layers`` layers
    run four times, a KV cache per pass, ``head_dim`` 128, bfloat16
    weights. (Three layers and not two: ``program_check`` goes by element
    counts, and at two a projection's stacked weights, 2 x 2048 x 2048,
    count what a cache layer of 4096 rows counts; for the same reason the
    smoke run's pool has 192 blocks: 12 cache layers of 4096 rows count
    what the 49152-row head counts.)"""
    return TransformerConfig(
        vocab_size=49152, d_model=2048, n_layers=n_layers, n_heads=16,
        d_ff=5632, max_seq_len=512, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, passes=4, post_norms=True,
        tie_embeddings=False, exit_gate=True, rope_base=1e6,
        scan_layers=True, remat=False)


@dataclasses.dataclass(frozen=True)
class ServeShapes:
    """Engine and workload shapes of the smoke run's serve phase."""
    num_blocks: int = 1024
    block_size: int = 16
    max_slots: int = 16
    max_prompt_len: int = 128
    prompt_range: tuple = (16, 128)
    new_range: tuple = (16, 64)
    shared_len: int = 96
    suffix_range: tuple = (8, 32)
    n_requests: int = 12


class CompileClock:
    """Seconds JAX spent compiling or loading from the persistent cache
    (``backend_compile_duration`` covers both), and the cache's hits
    and misses, since the last ``lap()``."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)

    def lap(self) -> str:
        out = (f"compile {self.seconds:.1f}s (persistent cache: "
               f"{self.hits} hit / {self.misses} miss)")
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        return out


def _peak_bytes(device) -> str:
    """``memory_stats()["peak_bytes_in_use"]`` as the device reports it
    (on the v5e it has read close to the live arrays' size, so it may
    leave out the executable's own temporaries)."""
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return ("peak_bytes_in_use not reported" if peak is None
            else f"peak_bytes_in_use {peak / 2**30:.2f} GiB")


def _device_ids(tree) -> set:
    return {s.device.id for leaf in jax.tree_util.tree_leaves(tree)
            for s in leaf.addressable_shards}


def _bytes_on(tree, device_id: int) -> int:
    return sum(s.data.nbytes for leaf in jax.tree_util.tree_leaves(tree)
               for s in leaf.addressable_shards
               if s.device.id == device_id)


def train_phase(cfg: TransformerConfig, axes: dict, devices, *,
                steps: int, clock: CompileClock,
                require_mosaic: bool = True,
                one_chip_first_loss: float | None = None) -> float:
    """``steps`` train steps of ``cfg`` on a mesh of ``axes`` over
    ``devices``; returns the first loss.

    One device: the loss starts within 0.3 of ln(vocab) and ends below
    where it started. Several: the first loss matches
    ``one_chip_first_loss`` within 2e-2 and the state really lives on
    every device of the mesh."""
    name = ",".join(f"{k}={v}" for k, v in axes.items())
    print(f"[train {name}] d_model {cfg.d_model}, {cfg.n_layers} layers, "
          f"seq {cfg.max_seq_len}, vocab {cfg.vocab_size}, batch "
          f"{GLOBAL_BATCH}, devices {[d.id for d in devices]}", flush=True)
    phase_t0 = time.perf_counter()
    mesh = make_mesh(axes, devices=devices)
    state, step = make_sharded_train_step(cfg, mesh,
                                          global_batch=GLOBAL_BATCH)
    batch = {"tokens": synthetic_tokens(GLOBAL_BATCH, cfg.max_seq_len,
                                        cfg.vocab_size)}

    ids = _device_ids(state)
    check(ids == {d.id for d in devices},
          f"state after init lives on devices {sorted(ids)}")
    if len(devices) > 1:
        total = sum(x.nbytes for x in
                    jax.tree_util.tree_leaves(state["params"]))
        per_dev = [_bytes_on(state["params"], d.id) for d in devices]
        print(f"  parameter bytes: total {total}, per device {per_dev}")
        check(all(b > 0 for b in per_dev),
              "every device holds parameter bytes")
        if set(axes) & {"fsdp", "tp"}:
            check(max(per_dev) < total,
                  "sharded parameters: each device holds less than the "
                  "whole")

    # Which kernels the step was built from is read off the lowered
    # program, not trusted to the implementation=None dispatchers.
    t0 = time.perf_counter()
    n_mosaic = jax.jit(step).lower(state, batch).as_text().count(
        MOSAIC_CALL)
    print(f"  lowered step: {n_mosaic} Mosaic custom calls "
          f"({time.perf_counter() - t0:.1f}s to trace and lower)",
          flush=True)
    if require_mosaic:
        check(n_mosaic > 0, "the lowered step contains Mosaic custom "
                            "calls (Pallas attention and fused CE)")

    losses = []
    for i in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        if i == 0:
            print(f"  init through first step "
                  f"{time.perf_counter() - phase_t0:.1f}s, of it "
                  f"{clock.lap()}", flush=True)
            t0 = time.perf_counter()
    print(f"  losses: {' '.join(f'{x:.4f}' for x in losses)}")
    print(f"  steps 2..{steps} ran {time.perf_counter() - t0:.2f}s; "
          f"{_peak_bytes(devices[0])}", flush=True)

    check(all(math.isfinite(x) for x in losses), "every loss is finite")
    if one_chip_first_loss is None:
        target = math.log(cfg.vocab_size)
        check(abs(losses[0] - target) <= 0.3,
              f"first loss {losses[0]:.4f} within 0.3 of ln(vocab) = "
              f"{target:.2f}")
        check(losses[-1] < losses[0],
              f"last loss {losses[-1]:.4f} below first {losses[0]:.4f}")
    else:
        check(abs(losses[0] - one_chip_first_loss) <= 2e-2,
              f"first loss {losses[0]:.4f} within 2e-2 of the one-chip "
              f"{one_chip_first_loss:.4f}")
    return losses[0]


def _serve(engine: InferenceEngine, requests: list, tag: str) -> dict:
    """Submit ``requests`` under ids prefixed ``tag``, run the engine
    dry, and return ``{original id: tokens}`` after checking that each
    request completed with exactly the tokens it asked for."""
    for r in requests:
        engine.submit(dataclasses.replace(r, id=f"{tag}/{r.id}"))
    done = engine.run_until_idle()
    out = {}
    for r in requests:
        rec = done.get(f"{tag}/{r.id}")
        if rec is None:
            raise SmokeFailure(f"request {tag}/{r.id} never completed")
        toks = rec["tokens"]
        if len(toks) != r.max_new_tokens:
            raise SmokeFailure(
                f"request {tag}/{r.id}: {len(toks)} tokens, asked "
                f"{r.max_new_tokens}")
        if not all(0 <= t < engine.cfg.vocab_size for t in toks):
            raise SmokeFailure(f"request {tag}/{r.id}: id out of range")
        out[r.id] = tuple(toks)
    return out


#: opcodes whose output of the pool's size IS the pool, where it lies:
#: the argument, views of it, tuple and loop plumbing, a kernel whose
#: output aliases its operand, an update in place
_IN_PLACE = frozenset({
    "parameter", "bitcast", "get-tuple-element", "tuple", "while",
    "opt-barrier", "custom-call", "dynamic-update-slice", "scatter"})
_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([\w-]+)\(")
_HLO_SHAPE = re.compile(r"\w+\[([\d,]+)\]")
#: a fusion that IS a scatter (XLA wraps one in a kCustom fusion): it
#: updates its operand where it lies, as the bare instruction does. A
#: relayout it needs shows as a ``copy`` beside it, and that counts.
_SCATTER_FUSION = re.compile(r'op_name="[^"]*/scatter"')


def pool_sized_ops(hlo_text: str, sizes) -> list[str]:
    """The instructions of an optimised HLO module that PRODUCE an array
    of one of ``sizes`` elements (the pool's, one pool layer's, every
    slot's whole window), in any type and any order of dimensions:
    copies, gathers, scatters, converts, slices, fusions. What only
    hands the pool on in place (``_IN_PLACE``) does not count."""
    sizes = set(sizes)
    found = []
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m is None or m.group(2) in _IN_PLACE or (
                m.group(2) == "fusion" and _SCATTER_FUSION.search(line)):
            continue
        for dims in _HLO_SHAPE.findall(m.group(1)):
            if math.prod(int(d) for d in dims.split(",")) in sizes:
                found.append(line.strip()[:200])
                break
    return found


def program_check(engine: InferenceEngine) -> None:
    """Which way the engine's programs reach the KV pool; on the paged
    paths (what a TPU takes) a compiled program must hold nothing of the
    pool's size: a whole-pool relayout or a whole-window gather back in
    ``jit_decode`` or ``jit_prefill`` stops a smoke run, not a
    benchmark. Neither by the text of the program (no instruction
    produces an array of the pool's, one cache layer's or every slot's
    window's element count, but in place) nor by its memory (its
    temporaries are smaller than one of the pool's two arrays, and its
    output pool is its input). Asked of decode, of prefill where it
    writes by blocks or the device keeps the pool row-major (XLA's
    scatter writes that layout in place), and of extend where it writes
    by blocks; where its layers still read through the window gather
    (which slices a cache layer out) only the pool's own size counts."""
    print(f"  decode kv_path: {engine.kv_path}", flush=True)
    print(f"  prefill kv_write: {engine.kv_write['prefill']}", flush=True)
    print(f"  extend kv_write: {engine.kv_write['extend']}", flush=True)
    print(f"  extend kv_read: {engine.kv_read}", flush=True)
    if engine.kv_path != "paged":
        return
    cc, slots = engine.cache_cfg, engine.max_slots
    row = cc.n_heads * cc.head_dim
    rows = cc.num_blocks * cc.block_size
    whole, layer = cc.n_layers * rows * row, rows * row
    chosen = jnp.zeros((slots,), jnp.int32)
    pool_bytes = engine.pool["k"].nbytes
    # name: the program the engine launches, what it takes after params
    # and pool (the chosen tokens, the one array the host sends), the
    # element counts no output may have, whether the temporaries are
    # held under one pool array (a 1024-wide forward's own activations
    # are not, beside this file's small pool)
    host = jnp.zeros((slots, 4 + engine.window // cc.block_size), jnp.int32)
    programs = {"decode": (engine._decode_next, (chosen, host),
                           {whole, layer, slots * engine.window * row},
                           True)}
    by_blocks = engine.kv_write["prefill"] == "paged"
    if engine._kv_layout == "rows" or by_blocks:
        # prefill gathers no window (and its K and V stacks of a whole
        # prompt may well count what a few slots' windows count); its
        # own attention matrix is not the pool's either, whatever it
        # counts (at this file's serving shapes, one cache layer)
        programs["prefill"] = (
            engine._prefill_next,
            (chosen, jnp.zeros(2 + 2 * engine.max_seq_len, jnp.int32)),
            {whole, layer} - {cc.n_heads * engine.max_seq_len ** 2},
            not by_blocks)
    if engine.kv_write["extend"] == "paged":
        span = min(64, engine.max_seq_len)
        in_place = engine.kv_read == "paged"
        programs["extend"] = (
            engine._extend_next,
            (chosen, jnp.zeros(2 + 3 * span + engine.window, jnp.int32)),
            {whole, layer} if in_place else {whole}, in_place)
    for name, (program, args, sizes, small) in programs.items():
        compiled = program.lower(engine.served_params, engine.pool,
                                 *args).compile()
        found = pool_sized_ops(compiled.as_text(), sizes)
        for line in found[:8]:
            print(f"    {line}", flush=True)
        check(not found,
              f"the compiled {name} program produces no array of the "
              f"pool's, one layer's or every slot's window's size "
              f"({sorted(sizes)} elements); found {len(found)}")
        memory = compiled.memory_analysis()
        check(memory.alias_size_in_bytes >= 2 * pool_bytes,
              f"the {name} program's pool is updated in place "
              f"({memory.alias_size_in_bytes} B aliased)")
        if small:
            check(memory.temp_size_in_bytes < pool_bytes,
                  f"the {name} program's temporaries "
                  f"({memory.temp_size_in_bytes} B) are smaller than one "
                  f"pool array ({pool_bytes} B)")


def serve_phase(cfg: TransformerConfig, shapes: ServeShapes, device, *,
                clock: CompileClock, seed: int = 0) -> None:
    """Serve seeded requests through one prefix-caching engine on
    ``device``: a cold batch of unrelated prompts, a batch sharing one
    long prefix (full-block hits run the extend program, a prompt that
    ends inside a cached block forces the copy-on-write pool copy),
    then everything twice more through the warm engine. The third pass
    takes exactly the second's path — same programs, same cached
    blocks — so its tokens must be bit-identical."""
    print(f"[serve] d_model {cfg.d_model}, {cfg.n_layers} layers x "
          f"{cfg.passes} passes, "
          f"max_seq_len {cfg.max_seq_len}; {shapes.num_blocks} blocks x "
          f"{shapes.block_size}, {shapes.max_slots} slots, prompts <= "
          f"{shapes.max_prompt_len}", flush=True)
    t0 = time.perf_counter()
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(
        cfg, params, num_blocks=shapes.num_blocks,
        block_size=shapes.block_size, max_slots=shapes.max_slots,
        max_prompt_len=shapes.max_prompt_len, prefix_caching=True)
    for name, tree in (("params", engine.params),
                       ("served params", engine.served_params),
                       ("pool", engine.pool)):
        on = {d for leaf in jax.tree_util.tree_leaves(tree)
              for d in leaf.devices()}
        check(on == {device}, f"engine {name} live on {device}")
    if device.platform == "tpu":
        check(engine.kv_path == "paged",
              "on a TPU the engine's decode program takes the paged path")
        if engine._kv_layout == "lanes":
            check(engine.kv_write["prefill"] == "paged",
                  "on a TPU prefill writes a pool that lies with its rows "
                  "on the lanes by blocks, in place")
    program_check(engine)

    cold = seeded_requests(seed, shapes.n_requests, cfg.vocab_size,
                           prompt_range=shapes.prompt_range,
                           new_tokens_range=shapes.new_range)
    rng = random.Random(f"dtx-chip-smoke:{seed}")
    prefix = tuple(rng.randrange(cfg.vocab_size)
                   for _ in range(shapes.shared_len))
    owner = [Request(id="p-owner", tokens=prefix,
                     max_new_tokens=rng.randrange(*shapes.new_range))]
    sharers = [Request(
        id=f"p{i:02d}",
        tokens=prefix + tuple(rng.randrange(cfg.vocab_size) for _ in
                              range(rng.randrange(*shapes.suffix_range))),
        max_new_tokens=rng.randrange(*shapes.new_range))
        for i in range(shapes.n_requests - 2)]
    # ends inside the prefix's last cached block: a partial-tail hit,
    # which must copy the shared block before writing into it
    inside = shapes.shared_len - shapes.block_size // 2
    cow = [Request(id="p-cow", tokens=prefix[:inside],
                   max_new_tokens=rng.randrange(*shapes.new_range))]
    everything = cold + owner + sharers + cow

    def hit_tokens():
        return engine.stats()["prefix_cache"]["hit_tokens"]

    def one_pass(tag):
        """Returns the tokens, and the prefix-cache tokens the sharers
        and the copy-on-write request hit. (The cache counts a lookup
        again when a deferred request is re-matched, so the sharers'
        figure is a floor; the lone ``cow`` request's is exact.)"""
        out = _serve(engine, cold, tag)
        out.update(_serve(engine, owner, tag))     # registers the prefix
        h0 = hit_tokens()
        out.update(_serve(engine, sharers, tag))
        h1 = hit_tokens()
        out.update(_serve(engine, cow, tag))
        return out, h1 - h0, hit_tokens() - h1

    first, shared_hit, cow_hit = one_pass("cold")
    asked = sum(r.max_new_tokens for r in everything)
    served = sum(len(t) for t in first.values())
    print(f"  cold pass {time.perf_counter() - t0:.1f}s incl. init, of "
          f"it {clock.lap()}; {len(first)} requests, {served}/{asked} "
          f"tokens; prefix-cache tokens hit: sharers {shared_hit}, "
          f"copy-on-write request {cow_hit}", flush=True)
    check(len(first) == len(everything) and served == asked,
          "every request completed with exactly the tokens it asked for, "
          "ids in range")
    full_hit = (shapes.shared_len // shapes.block_size) * shapes.block_size
    check(shared_hit >= len(sharers) * full_hit,
          f"all {len(sharers)} sharers hit the {full_hit}-token prefix "
          f"(the extend program ran)")
    check(cow_hit == inside - 1,
          f"the prompt ending inside a cached block hit {inside - 1} "
          f"tokens, a partial-tail hit (the copy-on-write pool copy ran)")

    t0 = time.perf_counter()
    second = one_pass("warm1")[0]
    third = one_pass("warm2")[0]
    print(f"  two warm passes {time.perf_counter() - t0:.1f}s, of it "
          f"{clock.lap()}", flush=True)
    same = sum(first[k] == second[k] for k in first)
    print(f"  cold pass vs warm pass (prefill path vs cache-hit path, "
          f"{jnp.dtype(cfg.dtype).name}): {same}/{len(first)} requests "
          f"token-identical — information, not a check")
    check(second == third,
          "the same requests through the warm engine twice: tokens "
          "bit-identical, shared-prefix batch included")
    acct = engine.block_accounting()
    check(acct["conserved"] and acct["leaked_refs"] == 0,
          "KV block accounting conserved, no leaked references")
    print(f"  {_peak_bytes(device)}", flush=True)


def main() -> int:
    print(f"jax {jax.__version__}", flush=True)
    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    print(f"platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, found platform "
              f"{dev['platform']!r}", file=sys.stderr)
        return 1

    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    runtime = bootstrap.initialize()
    print(f"bootstrap: {runtime.num_processes} process(es), "
          f"TPU_WORKER_HOSTNAMES="
          f"{os.environ.get('TPU_WORKER_HOSTNAMES')!r}")
    check(runtime.num_processes == 1
          and not runtime.initialized_jax_distributed,
          "bootstrap.initialize() is a no-op on one host")

    # gc between phases: an engine's closures form reference cycles, and
    # the next phase needs the device memory they hold
    cfg = train_config()
    first = train_phase(cfg, {"dp": 1}, devices[:1], steps=6, clock=clock)
    gc.collect()

    serve_phase(serve_config(), ServeShapes(), devices[0], clock=clock)
    gc.collect()
    # the looped shape: head_dim 128 (a row-major pool, the other
    # kernel), 3 layers x 4 passes = 12 cache layers
    serve_phase(looped_config(), ServeShapes(num_blocks=192, max_slots=8),
                devices[0], clock=clock)
    gc.collect()

    if len(devices) >= 4:
        for axes in ({"dp": 4}, {"fsdp": 2, "tp": 2}):
            train_phase(cfg, axes, devices[:4], steps=2, clock=clock,
                        one_chip_first_loss=first)
            gc.collect()
    else:
        print(f"[train four chips] did NOT run: JAX reports "
              f"{len(devices)} device(s), the phase needs 4")

    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
