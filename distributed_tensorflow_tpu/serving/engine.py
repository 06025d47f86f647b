"""The inference engine: sharded model + KV cache + continuous batching.

One :class:`InferenceEngine` is one serving replica's model runtime:

- **Weights** — passed in directly, or restored down the checkpoint
  recovery ladder via :meth:`from_checkpoint`
  (``CheckpointManager.restore_latest``: host snapshot > peer replica >
  local disk > durable disk — a restarted serving replica warm-starts
  from the same tiers a restarted trainer does).
- **Placement** — on a ``dp×tp`` mesh the decode batch's slots shard
  over ``dp`` and heads/mlp/vocab shard over ``tp`` using the SAME
  logical-axis rules as training (serving/decode.param_shardings); the
  KV pool's head axis follows (kv_cache.pool_shardings). Single-device
  when ``mesh=None``.
- **Stepping** — :meth:`step` is one continuous-batching iteration:
  retire finished sequences (free their blocks), admit from the queue
  under the token budget, prefill the newly admitted, decode one token
  for every running sequence. Greedy (argmax) sampling — the decode
  path's output is exactly comparable to full-sequence recompute.
  Launch ahead: the argmax runs inside each program, into a (slots,)
  vector of each slot's last chosen token that stays on the device, so
  a step launches its decode BEFORE it reads the last decode's tokens
  and its admissions' first ones (which the vector that decode is fed
  holds): the host's launch, read-back and bookkeeping overlap the
  device's work. A sequence whose read token ends it had one more
  token-step in flight, which is dropped; where the host needs every
  token (a preemption's replay, a version install, a migration, an
  engine left with nothing running) it drains first (``serve.drain``).
  Speculation stays synchronous: its drafts are built from read tokens.
- **Telemetry** — every step is a ``serve.step`` span; every completed
  request emits a ``serve.request`` event whose ``dur_s`` is the
  queue→completion latency (both render in tools/obs_report.py and as
  spans in tools/trace_report.py). Instruments live under the shared
  ``inference/`` namespace (the one ``Model.predict`` also reports
  into) plus ``serving/`` for engine-specific gauges.
- **Per-request tracing** — every request's lifecycle events
  (``serve.admit`` → ``serve.prefill`` → per-token ``serve.token`` →
  ``serve.request``) share a deterministic ``request_span_id`` derived
  from the request id, so the trace assembler links them with flow
  arrows — ACROSS preemption replays and replica restarts (a restarted
  incarnation re-serving the same id emits the same span id, so one
  request's whole story threads through both generations' tracks).
  Serving steps also feed the live goodput ledger
  (telemetry/goodput.py) when one is active, with replayed tokens
  priced as ``preempt_replay`` badput.
- **Chaos** — each step fires the ``serve.step`` injection site
  (resilience/faults.py) BEFORE mutating any scheduler state, so an
  injected failure is retryable: the replica runtime catches it and
  re-runs the step; no request is lost.

Three serving-speed optimisations stack on the same step loop, each
off by default and each OUTPUT-INVARIANT (greedy tokens are identical
with the feature on or off — the regression contract
tests/test_serving_speed.py pins):

- **Prefix caching** (``prefix_caching=True``) — committed prompt
  prefixes are content-indexed in the scheduler's
  :class:`~distributed_tensorflow_tpu.serving.kv_cache.PrefixCache`;
  a later request whose prompt hash-matches adopts the cached blocks
  (refcounted) and prefill runs ONLY over the unmatched suffix through
  the multi-token ``extend`` program. Shared blocks are copied-on-write
  before any divergent append; eviction is LRU over cached blocks no
  sequence references. Cache hits shrink the serve-step share of the
  goodput ledger automatically (smaller prefill = less serve time for
  the same tokens).
- **Speculative decoding** (``speculative_k=k`` with a small draft
  model, default the target's own first half of layers —
  ``decode.truncated_draft``) — the draft proposes k greedy tokens per
  slot, the target verifies all k+1 positions in ONE cache-aware
  ``extend`` forward, the longest agreeing prefix commits (plus the
  target's own next token), and the first rejection truncates. Greedy
  outputs equal non-speculative decode exactly; ``accepted_draft_rate``
  in :meth:`stats` says how much of the draft's work survived.
- **Quantized KV cache** (``kv_dtype="bf16"``/``"int8"``) — the pool
  stores quantized K/V (int8 with per-(row, head) f32 scales),
  quantize-on-write/dequantize-on-gather inside the compiled programs,
  multiplying servable slots per chip
  (``CacheConfig.bytes_per_token``); greedy parity holds on short
  sequences, with a measured logit-error bound
  (``decode.kv_quantization_probe``) documented in the README.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import os
import threading
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu import telemetry
from distributed_tensorflow_tpu.telemetry import goodput as _goodput
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig, TransformerLM)
from distributed_tensorflow_tpu.ops import paged_attention
from distributed_tensorflow_tpu.resilience import faults
from distributed_tensorflow_tpu.serving import decode as decode_lib
from distributed_tensorflow_tpu.serving.experts import COUNTS
from distributed_tensorflow_tpu.serving.kv_cache import (
    TRASH_BLOCK, CacheConfig, HostTier, init_pool, pool_shardings)
from distributed_tensorflow_tpu.serving.scheduler import (
    AdmissionQueue, ContinuousBatchingScheduler, OutOfBlocksError,
    Request, Sequence)

_pool_epochs = itertools.count()

#: one program for the whole tree (not one a leaf), compiled once a
#: configuration: a hot-swap's new weights run it again
_compute_params = jax.jit(decode_lib.compute_params, static_argnums=(0,),
                          static_argnames=("resident",))


@dataclasses.dataclass
class _Launch:
    """A decode launch whose tokens the host has not read: each
    sequence it advanced with the tokens it launched for it, where those
    lie on the device (``tokens`` (slots, k) for several steps a launch;
    None: in the ``chosen`` vector it returned), the expert counts it
    returned, the array it was handed (``host``: what the span of its
    read counts of the pool is computed from) and its number among the
    engine's dispatches (``launch``), which that span names as its
    ``read``."""

    batch: list
    tokens: object
    picks: list
    host: np.ndarray
    launch: int


def request_span_id(request_id: str) -> str:
    """Deterministic per-request trace span id. Derived from the
    request id alone so every lifecycle event of one request — across
    preemption replays, across replica generations — carries the SAME
    id and the trace assembler threads them with flow arrows."""
    return f"req/{request_id}"


def migrate_span_id(request_id: str) -> str:
    """Span id shared by BOTH halves of one KV migration — the source's
    export and the destination's adopt — so the merged trace renders a
    flow arrow prefill→decode (or victim→survivor for drain/rescue)."""
    return f"kvmig/{request_id}"


def _leaf_checksum(leaf):
    """Two 32-bit sums over a leaf's bits, computed where the leaf lies:
    the plain sum of its words and the sum weighted by position, both
    modulo 2**32 (a leaf of 1-, 2- or 4-byte elements)."""
    bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[leaf.dtype.itemsize]
    words = jax.lax.bitcast_convert_type(leaf, bits).astype(
        jnp.uint32).reshape(-1)
    weight = jax.lax.iota(jnp.uint32, words.size) * jnp.uint32(
        2654435761) + jnp.uint32(1)
    return jnp.stack([jnp.sum(words, dtype=jnp.uint32),
                      jnp.sum(words * weight, dtype=jnp.uint32)])


def params_digest(params) -> str:
    """Content digest of a parameter tree: crc32 over the tree
    structure and every leaf's shape, type and checksum. The checksums
    are computed on the device that holds the leaves and eight bytes a
    leaf come back to the host, so a multi-gigabyte tree costs no host
    round trip. Two engines serving byte-identical weights get the same
    digest regardless of how the weights arrived (fresh init, restore
    tier, hot-swap) — the content half of the ``weights_version``
    identity stamped on every serving event."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    leaves = [jnp.asarray(leaf) for leaf in leaves]
    # a jit of this call's own: its programs are unloaded with it, so a
    # digest leaves nothing on the device and what is allocated next (the
    # engine's pool) lies where it would lie without one
    checksum = jax.jit(lambda leaf: _leaf_checksum(leaf))
    sums = jax.device_get([checksum(leaf) for leaf in leaves])
    crc = zlib.crc32(repr(treedef).encode())
    for leaf, pair in zip(leaves, sums):
        crc = zlib.crc32(
            f"{leaf.shape}{leaf.dtype}{int(pair[0])},{int(pair[1])}"
            .encode(), crc)
    return f"{crc & 0xFFFFFFFF:08x}"


class InferenceEngine:
    """Continuous-batching inference over a sharded transformer.

    ``max_slots`` is the decode batch width (compiled shape; on a mesh
    it must divide the dp shard count), ``max_prompt_len`` the compiled
    prefill width, ``num_blocks``/``block_size`` size the KV pool, and
    ``token_budget`` caps prefill+decode tokens per step (Orca-style
    iteration-level fairness). ``max_seq_len`` bounds prompt+generation
    per sequence (default: the model's ``max_seq_len``).

    Serving-speed knobs (module docstring has the semantics; all
    output-invariant): ``prefix_caching=True`` shares committed prompt
    prefixes across requests; ``speculative_k=k`` drafts k tokens per
    slot and verifies them in one forward (``draft_params``/
    ``draft_cfg`` override the default truncated-target draft);
    ``decode_steps=k`` runs k greedy decode steps in one launch of the
    decode program, each sequence up to its remaining budget, so a
    ``step()`` releases up to k tokens a sequence and the host stands
    between two launches once per k tokens (a sequence that ends inside
    a launch frees its slot at the launch's end, and ``token_budget``
    still counts one decode token a running sequence);
    ``kv_dtype`` in {"f32", "bf16", "int8"} picks the pool's storage
    dtype (``cache_dtype`` remains the raw-dtype spelling)."""

    def __init__(self, cfg: TransformerConfig, params, *, mesh=None,
                 num_blocks: int = 64, block_size: int = 16,
                 max_slots: int = 8, max_prompt_len: int | None = None,
                 token_budget: int | None = None,
                 max_seq_len: int | None = None,
                 queue_capacity: int = 256,
                 queue_policy: str = "reject",
                 cache_dtype=None, kv_dtype: str | None = None,
                 prefix_caching: bool = False,
                 speculative_k: int = 0,
                 draft_params=None, draft_cfg=None,
                 decode_steps: int = 1,
                 role: str = "both",
                 spill_tier: "HostTier | int | None" = None,
                 snapshot_step: int | None = None):
        if cfg.mesh is not None:
            cfg = dataclasses.replace(cfg, mesh=None)
        if role not in ("both", "prefill"):
            raise ValueError(f"role={role!r}; expected 'both' or "
                             f"'prefill'")
        if cfg.latent is not None or cfg.experts is not None:
            # what is not written for a latent pool or an expert layer
            # refuses here, by name, before anything is built
            for given, what in (
                    (mesh is not None, "a mesh (a latent row has no head "
                     "axis to shard; the expert layer's exchange between "
                     "ranks is not written)"),
                    (speculative_k, "speculative decoding (the draft "
                     "and verify programs are not written for it)")):
                if given:
                    raise NotImplementedError(
                        f"latent attention / a layer of sparse experts "
                        f"is served on one device, one token a step: "
                        f"not with {what}")
        self.cfg = cfg
        self.mesh = mesh
        #: "prefill" compiles no decode program: step() admits +
        #: prefills only, and the disaggregated runtime EXPORTS each
        #: prefilled sequence's KV to a decode replica (migrate.py).
        self.role = role
        #: fences host-tier spills and stale drain handoffs: unique per
        #: engine incarnation, never equal across restarts
        self.pool_epoch = f"{os.getpid()}-{next(_pool_epochs)}"
        self.max_slots = max_slots
        self.max_seq_len = min(max_seq_len or cfg.max_seq_len,
                               cfg.max_seq_len)
        self.max_prompt_len = min(max_prompt_len or self.max_seq_len,
                                  self.max_seq_len)
        self.token_budget = token_budget or (max_slots
                                             + self.max_prompt_len)
        cache_cfg = CacheConfig.for_model(cfg, num_blocks=num_blocks,
                                          block_size=block_size,
                                          dtype=cache_dtype,
                                          kv_dtype=kv_dtype)
        max_blocks_per_seq = cache_cfg.blocks_for(self.max_seq_len)
        self.cache_cfg = cache_cfg
        self.window = max_blocks_per_seq * block_size
        self.prefix_caching = bool(prefix_caching)
        self.scheduler = ContinuousBatchingScheduler(
            cache_cfg, max_slots=max_slots,
            max_blocks_per_seq=max_blocks_per_seq,
            token_budget=self.token_budget,
            queue=AdmissionQueue(queue_capacity, queue_policy),
            prefix_caching=self.prefix_caching)

        if speculative_k and not cfg.causal:
            raise ValueError("speculative decoding requires a causal "
                             "model")
        self.spec_k = int(speculative_k)
        self.decode_steps = int(decode_steps)
        if self.decode_steps < 1 or (self.decode_steps > 1 and self.spec_k):
            raise ValueError(
                f"decode_steps={decode_steps} with speculative_k="
                f"{speculative_k}: at least 1, and more than 1 only "
                f"without speculation (both decide how many tokens a "
                f"step releases)")
        self._draft_default = False
        if self.spec_k:
            if draft_params is None:
                # default draft: the target's own first half of layers
                # (free self-speculation; pass an explicit small model
                # for a real distilled draft) — re-derived from the new
                # weights on every hot-swap (install_version)
                self._draft_default = True
                draft_cfg, draft_params = decode_lib.truncated_draft(
                    cfg, params)
            elif draft_cfg is None:
                raise ValueError("draft_params requires draft_cfg")
            self._draft_cfg = draft_cfg
            self._draft_params = self._serving_tree(draft_cfg,
                                                    draft_params)[1]
            self._draft = decode_lib.make_draft_fn(draft_cfg)

        #: the weights as they were given, in the programs' tree: what
        #: the digest, a hot-swap's tree test and a check against the
        #: masters read. ``served_params`` is what the compiled programs
        #: take: the same tree wherever the weights arrive in the
        #: compute type, a copy rounded to it once otherwise
        self.params, self.served_params = self._serving_tree(
            cfg, params, mesh)
        #: model-version identity: snapshot step (0 = direct params, no
        #: checkpoint provenance) + content digest. Stamped on every
        #: serve.prefill/serve.request event and rotated by
        #: install_version — the fence the prefix cache and the rollout
        #: controller both key on.
        self.weights_step = (int(snapshot_step)
                             if snapshot_step is not None else 0)
        self.weights_digest = params_digest(self.params)
        self.swaps = 0
        self.swap_error: BaseException | None = None
        # hot-swap provenance (set by from_checkpoint; load_version
        # needs both to rebuild a pinned CheckpointManager)
        self._version_source: dict | None = None
        self._params_template = None
        self._swap_thread: threading.Thread | None = None
        self._pending_swap = None
        self.pool = init_pool(cache_cfg, mesh)

        # GSPMD cannot partition a pallas_call: a mesh keeps the plain
        # paths (scatter, window); otherwise the builders choose
        admit_impl = "scatter" if mesh is not None else None
        prefill = decode_lib.make_prefill_fn(cfg, cache_cfg,
                                             implementation=admit_impl)
        decode = (decode_lib.make_decode_fn(
            cfg, cache_cfg,
            implementation="window" if mesh is not None else None)
            if cfg.causal and role != "prefill" else None)
        if decode is not None and self.decode_steps > 1:
            decode = decode_lib.make_multi_decode_fn(decode,
                                                     self.decode_steps)
        #: how the decode program reaches the pool ("paged" / "window"):
        #: decides which table _launch_decode hands it
        self.kv_path = decode.kv_path if decode is not None else None
        #: the passes over the stack each program was BUILT to run (the
        #: spans report what the step's program does, not a config file)
        self.prefill_passes = prefill.passes
        self.decode_passes = decode.passes if decode is not None else None
        #: the layout the paged kernel reads the pool in (what a run is)
        self._kv_layout = decode.kv_layout if decode is not None else None
        extend = (decode_lib.make_extend_fn(cfg, cache_cfg,
                                            implementation=admit_impl)
                  if cfg.causal else None)
        #: how each admission program's rows reach the pool ("paged":
        #: by blocks, in place / "scatter"), by ``serve.prefill``'s
        #: ``program``
        self.kv_write = {"prefill": prefill.kv_write,
                         "extend": extend.kv_write if extend else None}
        #: how extend's layers read the keys before the span ("paged":
        #: through the block table, where they lie / "window")
        self.kv_read = extend.kv_read if extend else None
        #: the held experts a program run could touch (every expert
        #: layer's), for the spans' ``experts_held``; None without any
        self._experts_held = (cfg.experts.held * cfg.n_layers
                              if cfg.experts is not None else None)
        copy_fn = decode_lib.make_copy_fn()
        # what the engine launches: the same programs, each choosing its
        # greedy token into the device's ``chosen`` vector (step())
        launch_prefill = decode_lib.launch_prefill(prefill)
        launch_extend = (decode_lib.launch_extend(extend, self.window)
                         if extend is not None else None)
        launch_decode = (decode_lib.launch_decode(decode, self.decode_steps)
                         if decode is not None else None)

        def gather_fn(pool, rows):
            return {n: pool[n][:, rows] for n in pool}

        def insert_fn(pool, rows, vals):
            return {n: pool[n].at[:, rows].set(vals[n]) for n in pool}
        if mesh is not None:
            # jit under the mesh context so GSPMD partitions over it;
            # inputs arrive host-side and get sharded by in_shardings
            from jax.sharding import NamedSharding, PartitionSpec as P
            dp = "dp" if "dp" in mesh.shape else None
            shardings = jax.tree_util.tree_map(lambda a: a.sharding,
                                               self.served_params)
            pool_sh = pool_shardings(mesh, cache_cfg)
            rep = NamedSharding(mesh, P())
            slotv = NamedSharding(mesh, P(dp))
            slotm = NamedSharding(mesh, P(dp, None))
            # speculative verify runs the extend program at (max_slots,
            # k+1), sharded over dp like decode (a suffix prefill's
            # (1, E) launch runs replicated, like prefill)
            self._extend_spec = jax.jit(
                extend,
                in_shardings=(shardings, pool_sh, slotm, slotm, slotv,
                              slotm, slotm),
                out_shardings=(NamedSharding(mesh, P(dp, None, None)),
                               pool_sh),
                donate_argnums=(1,)) \
                if extend is not None else None
            self._copy = jax.jit(
                copy_fn, in_shardings=(pool_sh, rep, rep),
                out_shardings=pool_sh,
                donate_argnums=(0,))
            # migration/spill row movers: gather block rows to a
            # replicated (host-fetchable) array, insert host rows into
            # the sharded pool. No donation on gather — the pool
            # survives an export.
            self._gather = jax.jit(gather_fn,
                                   in_shardings=(pool_sh, rep),
                                   out_shardings=rep)
            self._insert = jax.jit(
                insert_fn, in_shardings=(pool_sh, rep, rep),
                out_shardings=pool_sh,
                donate_argnums=(0,))
            self._prefill_next = jax.jit(
                launch_prefill, in_shardings=(shardings, pool_sh, slotv, rep),
                out_shardings=(slotv, pool_sh, rep), donate_argnums=(1,))
            self._extend_next = jax.jit(
                launch_extend, in_shardings=(shardings, pool_sh, slotv, rep),
                out_shardings=(slotv, pool_sh, rep), donate_argnums=(1,)) \
                if extend is not None else None
            self._decode_next = jax.jit(
                launch_decode,
                in_shardings=(shardings, pool_sh, slotv, slotm),
                out_shardings=(slotv, pool_sh, slotm),
                donate_argnums=(1,)) if decode is not None else None
            chosen = jax.device_put(jnp.zeros(max_slots, jnp.int32), slotv)
        else:
            self._extend_spec = (jax.jit(extend, donate_argnums=(1,))
                                 if extend is not None else None)
            self._copy = jax.jit(copy_fn, donate_argnums=(0,))
            self._gather = jax.jit(gather_fn)
            self._insert = jax.jit(insert_fn, donate_argnums=(0,))
            self._prefill_next = jax.jit(launch_prefill, donate_argnums=(1,))
            self._extend_next = (jax.jit(launch_extend, donate_argnums=(1,))
                                 if extend is not None else None)
            self._decode_next = (jax.jit(launch_decode, donate_argnums=(1,))
                                 if decode is not None else None)
            chosen = jnp.zeros(max_slots, jnp.int32)
        #: each slot's last chosen token, on the device: every launch
        #: takes it and returns it updated (the host reads the copy a
        #: launch was fed, never waiting on that launch)
        self._chosen = chosen
        #: the decode launch whose tokens are not read yet (_Launch)
        self._launched: _Launch | None = None
        #: admitted sequences whose first token is not read yet, each
        #: with its prefill's counts (on the device) and launch number
        self._firsts: list[tuple[Sequence, list, int]] = []
        #: program dispatches so far (_dispatched), counted whether or
        #: not a session records, so the numbers never depend on it
        self._launches = 0
        #: completion records made outside step()'s own retire (a drain
        #: before a preemption), handed out by the step
        self._retired: list[dict] = []
        self.scheduler.drain_hook = self._drain_for_preemption

        # shared inference namespace (Model.predict reports here too)
        reg = telemetry.get_registry()
        self._m_req_latency = reg.histogram(
            "inference/request_latency",
            "admission -> completion seconds per serving request")
        self._m_ttft = reg.histogram(
            "inference/time_to_first_token",
            "submit -> first generated token seconds (queueing included)")
        self._m_completed = reg.counter("inference/requests_completed")
        self._m_tokens = reg.counter("inference/tokens_generated")
        self._m_replayed = reg.counter(
            "inference/tokens_replayed",
            "tokens re-generated after preemption/restart (badput)")
        self._m_step = reg.histogram("serving/step_time",
                                     "one continuous-batching iteration")
        self._m_running = reg.gauge("serving/sequences_running")
        self._m_queued = reg.gauge("serving/requests_queued")
        self._m_blocks_free = reg.gauge("serving/blocks_free")
        self._m_preempt = reg.counter("serving/preemptions")
        self._m_cached_tokens = reg.counter(
            "serving/prefix_cached_tokens",
            "prompt tokens served from the prefix cache (prefill "
            "skipped)")
        self._m_prompt_tokens = reg.counter(
            "serving/prefix_prompt_tokens",
            "prompt tokens submitted to prefix-cache lookup")
        self._m_cache_blocks = reg.gauge("serving/prefix_cache_blocks")
        self._m_spec_proposed = reg.counter(
            "serving/draft_tokens_proposed")
        self._m_spec_accepted = reg.counter(
            "serving/draft_tokens_accepted")
        self._m_model_version = reg.gauge(
            "serving/model_version",
            "snapshot step of the weights currently serving")
        self._m_model_version.set(self.weights_step)
        self._m_swaps = reg.counter(
            "serving/weight_swaps",
            "in-place weight hot-swaps completed")

        self._step_idx = 0
        self._submitted: dict[str, float] = {}      # id -> wall arrival
        self._submit_mono: dict[str, float] = {}    # id -> mono arrival
        # instance-local speculation tallies (the registry counters
        # above are process-wide and shared across engines)
        self._spec_proposed_n = 0
        self._spec_accepted_n = 0
        # instance-local migration tallies
        self.migrations_out = 0
        self.migrations_in = 0
        self.migrated_bytes = 0

        self.spill_tier: HostTier | None = None
        if spill_tier is not None and spill_tier is not False:
            if not self.prefix_caching:
                raise ValueError("spill_tier requires "
                                 "prefix_caching=True (the tier backs "
                                 "prefix-cache eviction)")
            tier = (spill_tier if isinstance(spill_tier, HostTier)
                    else HostTier(int(spill_tier)))
            bs = self.cache_cfg.block_size

            def _extract(block: int) -> dict:
                rows = jnp.arange(block * bs, (block + 1) * bs,
                                  dtype=jnp.int32)
                g = self._gather(self.pool, rows)
                return {n: np.asarray(jax.device_get(a))
                        for n, a in g.items()}

            def _insert_block(block: int, arrays: dict):
                rows = jnp.arange(block * bs, (block + 1) * bs,
                                  dtype=jnp.int32)
                vals = {n: jnp.asarray(a) for n, a in arrays.items()}
                self.pool = self._insert(self.pool, rows, vals)

            self.scheduler.prefix_cache.attach_spill(
                tier, extract=_extract, insert=_insert_block,
                epoch=self._cache_epoch())
            self.spill_tier = tier

    # -- weights -----------------------------------------------------------
    @staticmethod
    def _serving_tree(cfg, params, mesh=None):
        """``(params, served)``, the one way weights reach a program.
        ``params`` is what arrived, on the device in the layout the
        programs index (stacked layers, under ``param_shardings`` on a
        mesh); ``served`` is what the programs are handed: every matrix
        in the compute type (``decode.compute_params``, one program run
        when the weights are taken and not a cast in every run of every
        program), and on one device, where ``decode.wants_resident``,
        the projection kernels as plain matrices
        (``decode.resident_params``: no relayout in a run either; a mesh
        shards the head axis and keeps the model's form). Weights that
        arrive in the compute type are not copied: ``served is params``,
        in the served layout."""
        resident = mesh is None and decode_lib.wants_resident(cfg)
        params = decode_lib.canonical_params(cfg, params)
        want = jax.eval_shape(
            functools.partial(decode_lib.compute_params, cfg), params)
        cast = any(a.dtype != b.dtype for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(want)))
        if resident and not cast:
            params = decode_lib.resident_params(cfg, params)
        if mesh is not None:
            shardings = decode_lib.param_shardings(cfg, mesh)
            params = jax.tree_util.tree_map(
                lambda a, s: jax.device_put(jnp.asarray(a), s),
                dict(params), shardings)
        else:
            params = jax.tree_util.tree_map(jnp.asarray, dict(params))
        if not cast:
            return params, params
        served = _compute_params(cfg, params, resident=resident)
        if mesh is not None:
            served = jax.device_put(served, shardings)
        return params, served

    @property
    def weights_version(self) -> str:
        """``<step>@<digest>`` — the identity stamped on serving
        events; also the weights half of the prefix-cache epoch."""
        return f"{self.weights_step}@{self.weights_digest}"

    def _cache_epoch(self) -> str:
        """Spill/fence epoch = incarnation × weights version. A
        host-tier block survives ONLY while both halves match: a
        restart rotates pool_epoch (PR 16's fence), a hot-swap rotates
        weights_version — either way a stale block is dropped-and-
        counted at re-adoption, never served."""
        return f"{self.pool_epoch}/{self.weights_version}"

    @classmethod
    def from_checkpoint(cls, cfg: TransformerConfig, directory: str, *,
                        checkpoint_name: str = "ckpt",
                        local_dir: str | None = None,
                        snapshot_store=None, seed: int = 0,
                        at_step: int | None = None,
                        **engine_kwargs) -> "InferenceEngine":
        """Restore serving weights down the recovery ladder. The
        checkpoint must have been written as ``Checkpoint(params=...)``
        over a ``TransformerLM(cfg)`` parameter tree; ``local_dir`` /
        ``snapshot_store`` enable the warm tiers exactly as they do for
        trainers (CheckpointManager.restore_latest walks host > peer >
        local > durable and emits ``recovery.restore_tier``). With
        nothing restorable anywhere, falls back to seed-deterministic
        fresh init (cold start). ``at_step`` pin-restores an exact
        snapshot (rollback; raises loudly when that step is torn or
        pruned). The engine remembers its checkpoint source, so
        :meth:`load_version` can later hot-swap to any other step.

        A successful restore emits ``serve.swap`` with
        ``mode="restart"`` — the restart-adoption datapoint the
        update→servable freshness SLO closes on, so the respawn gap
        hot-swap removes is measured, not assumed."""
        from distributed_tensorflow_tpu.checkpoint.checkpoint import (
            Checkpoint, CheckpointManager)
        from distributed_tensorflow_tpu.training.model import (
            _unflatten_like)

        t0 = time.monotonic()
        model = TransformerLM(cfg)
        tokens = jnp.zeros((1, min(8, cfg.max_seq_len)), jnp.int32)
        params = model.init(jax.random.PRNGKey(seed), tokens)["params"]
        params = (params.unfreeze() if hasattr(params, "unfreeze")
                  else dict(params))
        template = params
        ckpt = Checkpoint(params=params)
        mgr = CheckpointManager(ckpt, directory,
                                checkpoint_name=checkpoint_name,
                                local_dir=local_dir,
                                snapshot_store=snapshot_store)
        res = mgr.restore_latest(at_step=at_step)
        step = None
        if res is not None:
            _tier, step, flat = res
            params = _unflatten_like(template, flat, "params")
        eng = cls(cfg, params, snapshot_step=step, **engine_kwargs)
        eng._version_source = dict(directory=directory,
                                   checkpoint_name=checkpoint_name,
                                   local_dir=local_dir,
                                   snapshot_store=snapshot_store)
        eng._params_template = template
        if step is not None:
            telemetry.event(
                "serve.swap", step=step, version=eng.weights_version,
                previous=None, mode="restart", requeued=0,
                dur_s=round(time.monotonic() - t0, 6))
        return eng

    def install_version(self, params, *, step: int | None = None,
                        published_wall: "float | None" = None,
                        mode: str = "swap",
                        started_mono: "float | None" = None) -> dict:
        """Flip the serving weights IN PLACE at a step boundary. The
        parameter tree must match the current one exactly (structure,
        shapes, dtypes) — the compiled programs take params as a plain
        argument, so an identical-shape flip costs zero recompiles.

        The swap rule, in order: (1) every running sequence is
        released and its PRISTINE request re-queued at the front
        (tokens generated under the old weights are discarded, so no
        completed output ever mixes versions — the preemption-replay
        path is sanitized too); (2) the params pointer flips, the tree
        the programs take with it, made as the constructor makes it
        (``_serving_tree``: float32 weights are rounded to the compute
        type again, once), and the default truncated-target draft is
        re-derived when speculative decoding uses it; (3) the prefix
        cache is fenced by the new ``weights_version`` — device entries
        dropped, host-tier spills epoch-fenced; (4) a ``serve.swap`` event is emitted and the
        whole transition is priced into the ``rollout`` badput bucket.
        Zero requests are dropped: the latency clock keys on request
        id and survives the requeue, so SLO burn stays honest."""
        t0 = started_mono if started_mono is not None \
            else time.monotonic()
        raw = params
        params, served = self._serving_tree(self.cfg, params, self.mesh)
        old_l, old_t = jax.tree_util.tree_flatten(self.params)
        new_l, new_t = jax.tree_util.tree_flatten(params)
        if old_t != new_t or any(
                a.shape != b.shape or a.dtype != b.dtype
                for a, b in zip(old_l, new_l)):
            raise ValueError(
                "install_version: parameter tree mismatch — hot-swap "
                "requires the same TransformerConfig (identical "
                "structure/shapes/dtypes); rebuild the engine for an "
                "architecture change")
        previous = self.weights_version
        self._drain("swap")
        requeued = self.scheduler.requeue_running()
        self.params, self.served_params = params, served
        if self.spec_k and self._draft_default:
            self._draft_cfg, draft = decode_lib.truncated_draft(self.cfg,
                                                                raw)
            self._draft_params = self._serving_tree(self._draft_cfg,
                                                    draft)[1]
        self.weights_step = (int(step) if step is not None
                             else self.weights_step + 1)
        self.weights_digest = params_digest(self.params)
        dropped = 0
        if self.scheduler.prefix_cache is not None:
            dropped = self.scheduler.prefix_cache.fence(
                self._cache_epoch())
        self.swaps += 1
        self.swap_error = None
        self._m_swaps.increment()
        self._m_model_version.set(self.weights_step)
        dur = time.monotonic() - t0
        now = time.time()
        freshness = (max(0.0, now - published_wall)
                     if published_wall is not None else None)
        telemetry.event(
            "serve.swap", step=self.weights_step,
            version=self.weights_version, previous=previous,
            mode=mode, requeued=requeued, cache_dropped=dropped,
            dur_s=round(dur, 6),
            freshness_s=(round(freshness, 6)
                         if freshness is not None else None))
        ledger = _goodput.active_ledger()
        if ledger is not None:
            ledger.record("rollout", dur)
        return {"step": self.weights_step,
                "version": self.weights_version,
                "previous": previous, "requeued": requeued,
                "cache_dropped": dropped, "dur_s": dur}

    def _restore_pinned(self, step: int):
        """Fetch snapshot ``step``'s flat state via the restore-tier
        ladder, pinned (torn/pruned ⇒ loud raise, never a silent
        different version)."""
        if self._version_source is None:
            raise RuntimeError(
                "load_version: engine has no checkpoint provenance — "
                "build it with InferenceEngine.from_checkpoint")
        from distributed_tensorflow_tpu.checkpoint.checkpoint import (
            Checkpoint, CheckpointManager)
        src = self._version_source
        ckpt = Checkpoint(params=self._params_template)
        mgr = CheckpointManager(
            ckpt, src["directory"],
            checkpoint_name=src["checkpoint_name"],
            local_dir=src["local_dir"],
            snapshot_store=src["snapshot_store"])
        res = mgr.restore_latest(at_step=int(step))
        if res is None:
            raise FileNotFoundError(
                f"load_version: pinned step {step} not restorable")
        return res[2]

    def load_version(self, step: int, *,
                     published_wall: "float | None" = None) -> dict:
        """Synchronous hot-swap to snapshot ``step``: restore down the
        tier ladder (pinned), rebuild the parameter tree, and
        :meth:`install_version` it. Restore time is part of the priced
        transition. Prefer :meth:`begin_load_version` on a live step
        loop — it keeps the restore off the serving thread."""
        from distributed_tensorflow_tpu.training.model import (
            _unflatten_like)
        t0 = time.monotonic()
        flat = self._restore_pinned(step)
        params = _unflatten_like(self._params_template, flat, "params")
        return self.install_version(params, step=step,
                                    published_wall=published_wall,
                                    started_mono=t0)

    def begin_load_version(self, step: int, *,
                           published_wall: "float | None" = None
                           ) -> bool:
        """Start restoring snapshot ``step`` on a background thread;
        :meth:`step` installs it at the next step boundary once the
        restore lands (the flip itself stays on the serving thread, so
        no request ever sees a half-written tree). Returns False when a
        load is already in flight. A failed restore surfaces as a
        ``serve.swap_error`` event + :attr:`swap_error` — the replica
        keeps serving the current version."""
        if self._swap_thread is not None and \
                self._swap_thread.is_alive():
            return False

        def _work():
            t0 = time.monotonic()
            try:
                flat = self._restore_pinned(step)
            except BaseException as e:       # surfaced at the boundary
                self._pending_swap = ("error", int(step), e)
                return
            self._pending_swap = ("ready", int(step), flat,
                                  published_wall, t0)

        self._pending_swap = None
        self._swap_thread = threading.Thread(
            target=_work, name=f"swap-load-{step}", daemon=True)
        self._swap_thread.start()
        return True

    def _poll_pending_swap(self):
        """Install a background-loaded version at the step boundary."""
        if self._swap_thread is None or self._swap_thread.is_alive():
            return
        self._swap_thread = None
        pending, self._pending_swap = self._pending_swap, None
        if pending is None:
            return
        if pending[0] == "error":
            _kind, step, err = pending
            self.swap_error = err
            telemetry.event("serve.swap_error", step=step,
                            error=repr(err))
            return
        from distributed_tensorflow_tpu.training.model import (
            _unflatten_like)
        _kind, step, flat, published_wall, t0 = pending
        params = _unflatten_like(self._params_template, flat, "params")
        self.install_version(params, step=step,
                             published_wall=published_wall,
                             started_mono=t0)

    # -- request lifecycle -------------------------------------------------
    def submit(self, request: Request, *,
               arrival_wall: "float | None" = None) -> "Request | None":
        """Queue a request; returns the request the queue evicted to
        make room (policy ``evict_oldest``), if any. Raises
        ``QueueOverflowError`` under the ``reject`` policy.

        ``arrival_wall`` backdates the latency clock to the request's
        TRUE arrival (an open-loop timed workload — or a restarted
        replica re-serving backlog whose original arrival predates this
        incarnation): the ``serve.request`` latency then honestly
        includes the queueing the client experienced, so SLO burn can
        not be reset by a restart."""
        if len(request.tokens) > self.max_prompt_len:
            raise ValueError(
                f"request {request.id}: prompt {len(request.tokens)} > "
                f"max_prompt_len {self.max_prompt_len}")
        if not self.cfg.causal and request.max_new_tokens > 0:
            raise ValueError(
                f"request {request.id}: bidirectional (non-causal) "
                f"configs serve scoring requests only "
                f"(max_new_tokens=0)")
        if (len(request.tokens) + request.max_new_tokens
                > self.max_seq_len):
            raise ValueError(
                f"request {request.id}: prompt + max_new_tokens "
                f"exceeds max_seq_len {self.max_seq_len}")
        evicted = self.scheduler.queue.submit(request)
        self._submitted[request.id] = (arrival_wall
                                       if arrival_wall is not None
                                       else time.time())
        self._submit_mono[request.id] = time.monotonic()
        if evicted is not None:
            self._submitted.pop(evicted.id, None)
            self._submit_mono.pop(evicted.id, None)
        self._m_queued.set(len(self.scheduler.queue))
        telemetry.event("serve.admit", id=request.id,
                        span_id=request_span_id(request.id),
                        tenant=request.tenant, pclass=request.pclass,
                        prompt_tokens=len(request.tokens),
                        queued=len(self.scheduler.queue))
        return evicted

    def _apply_copies(self, copies):
        """Execute BlockTable.ensure_writable's copy-on-write
        instructions on the device pool (values AND quantisation
        scales) BEFORE the divergent write they protect."""
        if not copies:
            return
        with telemetry.span("kv.copy_on_write", blocks=len(copies)) as sp:
            src = np.concatenate([np.arange(s, s + n, dtype=np.int32)
                                  for s, _, n in copies])
            dst = np.concatenate([np.arange(d, d + n, dtype=np.int32)
                                  for _, d, n in copies])
            self.pool = self._copy(self.pool, jnp.asarray(src),
                                   jnp.asarray(dst))
            self._dispatched(sp, self._copy)

    def _dispatched(self, sp: dict, program) -> int:
        """Number the dispatch of ``program`` that the span ``sp`` makes
        and return the number; while a session records, the span names
        the program (``program``: its name on the device's program line
        less ``jit_``) and the number (``launch``), which the span that
        banks the run's result names as its ``read``."""
        self._launches += 1
        if telemetry.recording():
            sp["program"] = program.__name__
            sp["launch"] = self._launches
        return self._launches

    # the launches called as the plain programs are, for a check of
    # their logits (the benchmark's latent runner): the compiled programs
    # are the ones the engine serves with, the ``chosen`` they return
    # is dropped
    def _prefill(self, params, pool, tokens, lengths, rows):
        """``(params, pool, tokens (1, S), lengths (1,), rows (1, S))`` →
        ``(last logits (1, V), pool, *counts)``, S the engine's
        ``max_seq_len``."""
        host = np.concatenate([[0], np.asarray(lengths), np.asarray(tokens)[0],
                               np.asarray(rows)[0]]).astype(np.int32)
        _, pool, last, *counts = self._prefill_next(
            params, pool, self._chosen, jnp.asarray(host))
        return (last, pool, *counts)

    def _decode(self, params, pool, tokens, positions, lengths,
                write_rows, table):
        """One decode step over every slot: ``(params, pool, tokens,
        positions, lengths, write_rows, table)`` → ``(logits (slots, V),
        pool, *counts)``; ``positions`` must be ``lengths - 1``, as the
        launch places each slot's token."""
        lengths = np.asarray(lengths)
        if not np.array_equal(np.asarray(positions), lengths - 1):
            raise ValueError("a decode step writes at lengths - 1")
        host = np.column_stack([tokens, lengths, np.ones_like(lengths),
                                write_rows, table]).astype(np.int32)
        _, pool, logits, *counts = self._decode_next(
            params, pool, self._chosen, jnp.asarray(host))
        return (logits, pool, *counts)

    def _prefill_one(self, seq: Sequence):
        """Launch one admitted sequence's prompt through the compiled
        prefill; its greedy first token lands in the device's ``chosen``
        vector and is read by the step's read (:meth:`_read`), after the
        step's decode is launched.

        Cold path: the full prompt through ``prefill`` (fixed
        (1, max_seq_len) shape — wider than max_prompt_len so a
        PREEMPTED sequence's replayed prompt, which includes its
        already-generated tokens, always fits). Prefix-cache hit: only
        the unmatched suffix runs, through the multi-token ``extend``
        program at a power-of-two bucket width (bounded recompiles),
        attending the cached blocks as ``kv_read`` says (in place through
        the block table, or the block-window gather) — the start-offset
        path that turns repeated-prefix traffic into O(suffix) prefill."""
        rid = seq.request.id
        submit_mono = self._submit_mono.get(rid)
        queue_wait = (seq.admitted_s - submit_mono
                      if submit_mono is not None else None)
        C = seq.cached_tokens
        S = seq.prompt_len - C                      # suffix to compute
        bs, W = self.cache_cfg.block_size, self.window
        program = "extend" if C else "prefill"
        E = (min(self.max_seq_len, 1 << max(3, (S - 1).bit_length()))
             if C else self.max_seq_len)            # program's width
        with telemetry.span(
                "serve.prefill", id=rid, span_id=request_span_id(rid),
                model_version=self.weights_version,
                prompt_tokens=seq.prompt_len,
                cached_tokens=C or None,
                queue_wait_s=(round(queue_wait, 6)
                              if queue_wait is not None else None),
                replayed=len(seq.request.generated_prefix) or None,
                program=program, kv_write=self.kv_write[program],
                kv_read=self.kv_read if C else None,
                blocks_written=((seq.prompt_len - 1) // bs - C // bs + 1
                                if C else len(seq.table.blocks)),
                passes=self.prefill_passes):
            with telemetry.span("serve.prefill.build"):
                # one upload: [slot, length, tokens, (positions,) rows
                # (padding -> the trash block's row 0), (window rows)]
                if C:
                    # a partially-matched tail block is SHARED: copy it
                    # before the suffix writes into it (and before the
                    # row indices below are derived from the table)
                    self._apply_copies(seq.table.ensure_writable(
                        C, seq.prompt_len, self.scheduler.allocator))
                    host = np.zeros(2 + 3 * E + W, np.int32)
                    host[2:2 + S] = seq.request.tokens[C:]
                    host[2 + E:2 + 2 * E] = W       # pad -> masked query
                    host[2 + E:2 + E + S] = np.arange(C, seq.prompt_len)
                    host[2 + 2 * E:2 + 2 * E + S] = seq.table.rows(
                        np.arange(C, seq.prompt_len))
                    host[2 + 3 * E:] = seq.table.window_rows()
                    launch = self._extend_next
                else:
                    host = np.zeros(2 + 2 * E, np.int32)
                    host[2:2 + seq.prompt_len] = seq.request.tokens
                    host[2 + E:] = seq.table.rows(np.arange(E))
                    launch = self._prefill_next
                host[:2] = seq.slot, seq.prompt_len
            with telemetry.span("serve.prefill.launch") as sp:
                self._chosen, self.pool, _, *picks = launch(
                    self.served_params, self.pool, self._chosen,
                    jnp.asarray(host))
                n = self._dispatched(sp, launch)
                if telemetry.recording():
                    # the host's share of the admission's first token:
                    # from the scheduler making the sequence to here
                    sp["since_admit_s"] = time.monotonic() - seq.admitted_s
        self.scheduler.commit_prefill(seq)
        self.scheduler.launched(seq, 1)
        self._firsts.append((seq, picks, n))
        self._m_prompt_tokens.increment(seq.prompt_len)
        if C:
            self._m_cached_tokens.increment(C)

    def _emit_token(self, seq: Sequence):
        # per-token decode breadcrumb on the request's span: index
        # counts generated tokens ACROSS preemptions (the replayed
        # prefix included), so a re-served request's token trail lines
        # up generation-to-generation
        rid = seq.request.id
        telemetry.event(
            "serve.token", id=rid, span_id=request_span_id(rid),
            index=(len(seq.request.generated_prefix)
                   + len(seq.generated)),
            step=self._step_idx)

    def _decode_span(self, seq: Sequence) -> int:
        """The tokens one decode launch gives ``seq``: ``decode_steps``,
        capped by the request's output budget left after the tokens in
        flight and by the sequence-length ceiling."""
        return max(1, min(self.decode_steps, seq.to_launch,
                          self.max_seq_len - seq.length + 1))

    def _launch_decode(self, batch: list[Sequence]) -> dict:
        """Launch the decode program for every sequence of ``batch``
        without reading anything: a sequence whose last token the host
        has not read is fed the device's own (``chosen``). One launch
        gives a sequence ``_decode_span`` tokens: one, or with
        ``decode_steps`` = k up to k, each inner step fed the token the
        last one chose. Slots outside the batch idle (length 0, the
        trash block's row 0). The launch is read by the NEXT
        :meth:`_read`, whose span counts what it read of the pool
        (:meth:`_decode_counts`). Returns the ``serve.decode`` span's
        ``launched`` and ``ahead`` (1 where a token the host had not read
        was fed)."""
        B, W, K = self.max_slots, self.window, self.decode_steps
        paged = self.kv_path == "paged"
        T = W // self.cache_cfg.block_size if paged else W
        with telemetry.span("serve.decode.build"):
            # one upload, a row a slot: [fed, length, budget, the rows
            # its K steps write, its table: the paged program walks the
            # slot's blocks, the window program gathers its window]
            host = np.zeros((B, 3 + K + T), np.int32)
            host[:, 0] = -1                         # fed: the device's
            if paged:
                host[:, 3 + K:] = TRASH_BLOCK
            for seq in batch:
                s, n, L = seq.slot, self._decode_span(seq), seq.length
                if self.prefix_caching:
                    # the writes at positions L-1.. must not land in a
                    # block a prefix-cache sibling shares: copy-on-write
                    # first (without a cache no block is shared)
                    self._apply_copies(seq.table.ensure_writable(
                        L - 1, L - 1 + n, self.scheduler.allocator))
                if not seq.unread:
                    host[s, 0] = seq.last_token
                host[s, 1:3] = L, n
                host[s, 3:3 + n] = [seq.table.row_of(L - 1 + i)
                                    for i in range(n)]
                if paged:
                    host[s, 3 + K:3 + K + len(seq.table.blocks)] = \
                        seq.table.blocks
                else:
                    host[s, 3 + K:] = seq.table.window_rows()
        with telemetry.span("serve.decode.launch") as sp:
            self._chosen, self.pool, scores, *picks = self._decode_next(
                self.served_params, self.pool, self._chosen,
                jnp.asarray(host))
            n = self._dispatched(sp, self._decode_next)
        ahead = any(seq.unread for seq in batch)
        budget = host[:, 2]
        for seq in batch:
            self.scheduler.launched(seq, int(budget[seq.slot]))
        self._launched = _Launch(
            [(seq, int(budget[seq.slot])) for seq in batch],
            scores if K > 1 else None, picks, host, n)
        return {"launched": 1, "ahead": int(ahead)}

    def _decode_counts(self, host: np.ndarray) -> dict:
        """What the decode launch handed ``host`` read of the pool, for
        the span of its read (built only while a span is recorded):
        ``token_steps``, ``blocks_read``, ``passes``, ``cache_layers``,
        ``rows_read`` and on the paged path ``runs_read``."""
        B, W, K = self.max_slots, self.window, self.decode_steps
        paged = self.kv_path == "paged"
        bs = self.cache_cfg.block_size
        budget = host[:, 2]
        # inner step i of a slot sees length + i rows while i < budget
        rows = np.where(np.arange(K) < budget[:, None],
                        host[:, 1:2] + np.arange(K), 0)        # (B, K)
        counts = {"token_steps": int(budget.sum()),
                  "blocks_read": (B * (W // bs) * K if not paged
                                  else int(np.sum(-(-rows // bs)))),
                  "passes": self.decode_passes,
                  "cache_layers": self.cache_cfg.n_layers,
                  "rows_read": int(rows.sum())}
        if paged:
            counts["runs_read"] = sum(
                paged_attention.count_runs(
                    self._kv_layout, host[:, 3 + K:],
                    -(-np.maximum(rows[:, i] - 1, 0) // bs), bs)
                for i in range(K))
        return counts

    def _fetch(self, chosen, rec: "_Launch | None", firsts: list):
        """Wait for and copy to the host, in one transfer, what a read
        takes: ``chosen`` (the step reads the vector its own decode
        launch was fed, so the read waits for the programs before that
        launch and not for it), the tokens of the decode launch ``rec``
        where they lie apart, and while a span is recorded the expert
        counts of ``rec`` and of the admissions ``firsts``."""
        picks = telemetry.recording() and self._experts_held is not None
        want = (chosen, rec.tokens if rec is not None else None,
                rec.picks[:1] if rec is not None and picks else [],
                [p[:1] for _, p, _ in firsts] if picks else [])
        return jax.device_get(want)

    def _commit(self, fetched, rec: "_Launch | None",
                firsts: list) -> tuple[dict, int]:
        """Bank what :meth:`_fetch` read: the decode launch's tokens,
        then the admissions' first tokens. A token chosen after its
        sequence ended (an end-of-sequence token read since it was
        launched) or for a sequence no longer running is dropped, its
        position given back. Each admission's banking is a
        ``serve.prefill.commit`` span, which while a session records
        names the launch it read (``read``) and the admission's TTFT
        (``ttft_s``, :meth:`_ttft`). Returns the read launch's counts
        (for the span of its read, while one is recorded; empty when
        there was none) and the tokens banked from it."""
        chosen, tokens, picks, first_picks = fetched
        sched, emit = self.scheduler, telemetry.enabled()
        recording = telemetry.recording()
        counts, banked = {}, 0
        if rec is not None:
            for seq, n in rec.batch:
                got = (tokens[seq.slot, :n] if tokens is not None
                       else chosen[seq.slot:seq.slot + 1])
                used = 0
                if sched.running.get(seq.slot) is seq:
                    for t in got:
                        if seq.done:
                            break
                        sched.read_token(seq, t)
                        used += 1
                        if emit:
                            self._emit_token(seq)
                sched.discard(seq, n - used)
                banked += used
            if recording:
                counts = dict(self._decode_counts(rec.host),
                              **self._expert_counts(picks))
        for i, (seq, _, launch) in enumerate(firsts):
            first = int(chosen[seq.slot])
            rid = seq.request.id
            with telemetry.span("serve.prefill.commit", id=rid,
                                span_id=request_span_id(rid),
                                **self._expert_counts(
                                    first_picks[i] if first_picks else [])
                                ) as sp:
                live = sched.running.get(seq.slot) is seq
                if live and seq.request.max_new_tokens:
                    sched.read_token(seq, first)
                else:
                    sched.discard(seq, 1)
                    if live:                # a scoring request's 'token'
                        seq.first_token_s = time.monotonic()
                        seq.score_token = first
                if recording:
                    sp["read"] = launch
                    if live:
                        sp["ttft_s"] = self._ttft(seq)
        return counts, banked

    def _read(self, chosen, rec: "_Launch | None" = None) -> dict:
        """The step's read, in ``serve.decode`` sub-spans: the decode
        launch ``rec`` and the step's admissions, from ``chosen``.
        Returns ``rec``'s counts, for the span of its read."""
        firsts, self._firsts = self._firsts, []
        if rec is None and not firsts:
            return {}
        with telemetry.span("serve.decode.wait"):
            fetched = self._fetch(chosen, rec, firsts)
        with telemetry.span("serve.decode.commit") as sp:
            counts, sp["tokens"] = self._commit(fetched, rec, firsts)
            if rec is not None and telemetry.recording():
                sp["read"] = rec.launch
        return counts

    def _drain(self, reason: str) -> bool:
        """Read everything in flight now, where the host needs every
        token (a preemption's replay, a version install, a migration, an
        engine left with nothing running). True when there was something
        to read."""
        rec, firsts = self._launched, self._firsts
        if rec is None and not firsts:
            return False
        self._launched, self._firsts = None, []
        with telemetry.span("serve.drain", reason=reason) as sp:
            sp["tokens"] = self._commit(
                self._fetch(self._chosen, rec, firsts), rec, firsts)[1]
            if rec is not None and telemetry.recording():
                sp["read"] = rec.launch
        return True

    def _drain_for_preemption(self) -> bool:
        """The scheduler's ``drain_hook``: drain before a victim is
        chosen, and retire what the tokens read ended."""
        if not self._drain("preempt"):
            return False
        for seq in list(self.scheduler.finished()):
            self._retired.append(self._complete(seq))
        return True

    def _expert_counts(self, picks) -> dict:
        """What a program run's expert layers did, for its span: the
        layers' counts (``experts.COUNTS``, the last thing a program with
        expert layers returns) summed, ``experts_held`` (over the layers
        too) and ``expert_layers``. Fetched only while a span is
        recorded, with the run's tokens; nothing for a model without an
        expert layer."""
        if not picks or self._experts_held is None:
            return {}
        totals = np.asarray(picks[0]).sum(axis=0)
        return dict(zip(COUNTS, map(int, totals)),
                    experts_held=self._experts_held,
                    expert_layers=self.cfg.n_layers)

    # -- speculative decoding ---------------------------------------------
    def _spec_span(self, seq: Sequence) -> int:
        """How many draft tokens speculating on ``seq`` can possibly
        commit this step: capped by k, by the request's remaining
        output budget (committing j drafts + 1 target token needs
        remaining >= j + 1), and by the sequence-length ceiling."""
        remaining = seq.request.max_new_tokens - len(seq.generated)
        return max(0, min(self.spec_k, remaining - 1,
                          self.max_seq_len - seq.length))

    def _speculative_batch(self, batch: list[Sequence]) -> int:
        """Draft-then-verify for the whole decode batch (Leviathan et
        al.): the draft proposes up to k greedy tokens per slot, the
        target scores all k+1 positions in ONE cache-aware extend
        forward, and each slot commits the longest prefix on which the
        draft agreed with the target — plus the target's own next
        token (the bonus on full acceptance, the correction on the
        first rejection). Every committed token is the target's argmax
        in its true greedy context, so outputs are EXACTLY the
        non-speculative ones. Returns tokens committed."""
        k, B, W = self.spec_k, self.max_slots, self.window
        E, S = k + 1, self.max_seq_len
        spans = {seq.slot: self._spec_span(seq) for seq in batch}

        # 1. draft proposals: k batched greedy steps, full recompute
        with telemetry.span("serve.decode.draft"):
            toks = np.zeros((B, S), np.int32)
            lens = np.zeros(B, np.int32)
            for seq in batch:
                hist = list(seq.request.tokens) + seq.generated
                toks[seq.slot, :len(hist)] = hist
                lens[seq.slot] = len(hist)
            proposals = np.zeros((B, k), np.int32)
            for i in range(k):
                nxt = np.asarray(self._draft(self._draft_params,
                                             jnp.asarray(toks),
                                             jnp.asarray(lens)))
                proposals[:, i] = nxt
                can = lens < S
                toks[np.arange(B)[can], lens[can]] = nxt[can]
                lens[can] += 1

        # 2. verify all k+1 positions in one extend forward
        with telemetry.span("serve.decode.build"):
            tokens = np.zeros((B, E), np.int32)
            positions = np.full((B, E), W, np.int32)  # pad -> masked query
            lengths = np.zeros(B, np.int32)
            write_rows = np.zeros((B, E), np.int32)   # pad -> trash row
            window_rows = np.zeros((B, W), np.int32)
            for seq in batch:
                s, L, ke = seq.slot, seq.length, spans[seq.slot]
                if self.prefix_caching:
                    self._apply_copies(seq.table.ensure_writable(
                        L - 1, L + ke, self.scheduler.allocator))
                tokens[s, 0] = seq.last_token
                tokens[s, 1:ke + 1] = proposals[s, :ke]
                positions[s, :ke + 1] = np.arange(L - 1, L + ke)
                lengths[s] = L + ke
                write_rows[s, :ke + 1] = [seq.table.row_of(p)
                                          for p in range(L - 1, L + ke)]
                window_rows[s] = seq.table.window_rows()
        with telemetry.span("serve.decode.launch") as sp:
            logits, self.pool = self._extend_spec(
                self.served_params, self.pool, jnp.asarray(tokens),
                jnp.asarray(positions), jnp.asarray(lengths),
                jnp.asarray(write_rows), jnp.asarray(window_rows))
            launch = self._dispatched(sp, self._extend_spec)
        with telemetry.span("serve.decode.wait"):
            target_next = np.asarray(jnp.argmax(logits, axis=-1))  # (B, E)

        # 3. commit the agreeing prefix + the target's next token
        with telemetry.span("serve.decode.commit") as sp:
            if telemetry.recording():
                sp["read"] = launch
            emit = telemetry.enabled()
            committed_total = 0
            for seq in batch:
                s, ke = seq.slot, spans[seq.slot]
                j = 0
                while j < ke and proposals[s, j] == target_next[s, j]:
                    j += 1
                self._m_spec_proposed.increment(ke)
                self._m_spec_accepted.increment(j)
                self._spec_proposed_n += ke
                self._spec_accepted_n += j
                for t in target_next[s, :j + 1]:
                    self.scheduler.append_token(seq, int(t))
                    committed_total += 1
                    if emit:
                        self._emit_token(seq)
                    if seq.done:
                        break
            sp["tokens"] = committed_total
        return committed_total

    def step(self) -> list[dict]:
        """One continuous-batching iteration; returns completion records
        for every request finished this step."""
        t0 = time.monotonic()
        # chaos site FIRST: an injected raise leaves scheduler/cache
        # state untouched, so the caller can simply retry the step
        faults.fire("serve.step", tag=self._step_idx)
        # a background-loaded version installs HERE — the step
        # boundary: after the fault site (an injected raise just
        # defers the flip to the retry), before any admission/decode
        # touches the old weights
        self._poll_pending_swap()
        sched = self.scheduler
        finished: list[dict] = []
        cache = sched.prefix_cache
        with telemetry.span("serve.step", step=self._step_idx) as sp:
            # 1. retire finished sequences -> blocks free immediately
            with telemetry.span("serve.retire") as rsp:
                free0 = sched.allocator.num_free
                for seq in list(sched.finished()):
                    finished.append(self._complete(seq))
                rsp["finished"] = len(finished)
                rsp["blocks_freed"] = sched.allocator.num_free - free0
            # 2. admit: prefix match, token budget, allocation, eviction
            with telemetry.span("serve.schedule") as ssp:
                defer_p0 = sched.deferred_prefill
                defer_b0 = sched.deferred_blocks
                evict0 = cache.evictions if cache is not None else 0
                admitted = sched.admit()
                ssp["admitted"] = len(admitted)
                # deferral split BY CAUSE (this step's deltas): prefill
                # budget pressure vs pool exhaustion — the bench reads
                # these to attribute p99 to interference
                counts = {
                    "deferred_prefill": sched.deferred_prefill - defer_p0,
                    "deferred_blocks": sched.deferred_blocks - defer_b0}
                if admitted and telemetry.recording():
                    ssp["prompt_tokens"] = sum(s.prompt_len
                                               for s in admitted)
                    counts["cached_tokens"] = sum(s.cached_tokens
                                                  for s in admitted)
                counts = {k: v for k, v in counts.items() if v}
                ssp.update(counts)
                if cache is not None and cache.evictions > evict0:
                    ssp["evicted_blocks"] = cache.evictions - evict0
            for seq in admitted:
                self._prefill_one(seq)
            firsts = [seq for seq, _, _ in self._firsts]
            batch = []
            if self._decode_next is None:
                self._read(self._chosen)    # prefill only: read at once
            else:
                with telemetry.span("serve.decode") as dsp:
                    if self.spec_k:
                        # drafts are built from the tokens the host has
                        # read: speculation reads first and runs in step
                        self._read(self._chosen)
                        spec_before = self._spec_proposed_n
                        acc_before = self._spec_accepted_n
                        batch = sched.grow_for_decode(
                            lambda s: self._spec_span(s) + 1)
                        if batch:
                            self._speculative_batch(batch)
                        sp["proposed_drafts"] = (self._spec_proposed_n
                                                 - spec_before)
                        sp["accepted_drafts"] = (self._spec_accepted_n
                                                 - acc_before)
                    else:
                        # launch this step's decode behind the
                        # admissions, THEN read the last decode's tokens
                        # and the admissions' first ones: the vector
                        # this launch is fed holds them all
                        batch = sched.grow_for_decode(self._decode_span)
                        dsp["kv_path"] = self.kv_path
                        fed, last = self._chosen, self._launched
                        self._launched = None
                        if batch:
                            dsp.update(self._launch_decode(batch))
                        dsp.update(self._read(fed, last))
                    dsp["live"] = len(batch)
            # admissions that their first token ended (scoring requests,
            # a budget of one, an end token) finish at prefill
            for seq in firsts:
                if seq.done and sched.running.get(seq.slot) is seq:
                    finished.append(self._complete(seq))
            finished += self._retired
            self._retired.clear()
            if not sched.running:
                self._drain("idle")
            sp["admitted"] = len(admitted)
            sp["decoded"] = len(batch)
            sp["finished"] = len(finished)
            sp["queued"] = len(sched.queue)
            sp["blocks_free"] = sched.allocator.num_free
            sp.update(counts)        # the step's record carries them too
            if cache is not None:
                self._m_cache_blocks.set(len(cache))
        self._step_idx += 1
        step_s = time.monotonic() - t0
        self._m_step.record(step_s)
        ledger = _goodput.active_ledger()
        if ledger is not None:
            ledger.serve_step(step_s)
        self._m_running.set(len(sched.running))
        self._m_queued.set(len(sched.queue))
        self._m_blocks_free.set(sched.allocator.num_free)
        if sched.preemptions > self._m_preempt.value:
            self._m_preempt.increment(
                sched.preemptions - self._m_preempt.value)
        return finished

    def _ttft(self, seq: Sequence) -> "float | None":
        """Seconds from ``submit()`` to the sequence's first token:
        queueing (``queue_wait_s`` of ``serve.prefill``) and prefill."""
        if seq.first_token_s is None:
            return None
        return seq.first_token_s - self._submit_mono.get(
            seq.request.id, seq.admitted_s)

    def _complete(self, seq: Sequence) -> dict:
        self.scheduler.finish(seq)
        req = seq.request
        now = time.time()
        arrival = self._submitted.pop(req.id, now)
        latency = max(0.0, now - arrival)
        ttft = self._ttft(seq)
        self._submit_mono.pop(req.id, None)
        generated = list(req.generated_prefix) + list(seq.generated)
        tokens = (generated if (req.max_new_tokens > 0
                                or req.generated_prefix)
                  else [getattr(seq, "score_token", -1)])
        prompt_tokens = len(req.tokens) - len(req.generated_prefix)
        replayed = len(req.generated_prefix)
        self._m_req_latency.record(latency)
        if ttft is not None:
            self._m_ttft.record(ttft)
        self._m_completed.increment()
        self._m_tokens.increment(len(seq.generated))
        if replayed:
            self._m_replayed.increment(replayed)
        ledger = _goodput.active_ledger()
        if ledger is not None:
            ledger.tokens(fresh=len(seq.generated), replayed=replayed)
        telemetry.event(
            "serve.request", id=req.id, dur_s=round(latency, 6),
            span_id=request_span_id(req.id),
            model_version=self.weights_version,
            tenant=req.tenant, pclass=req.pclass,
            prompt_tokens=prompt_tokens, new_tokens=len(generated),
            replayed_tokens=replayed,
            ttft_s=round(ttft, 6) if ttft is not None else None,
            preemptions=seq.preemptions)
        return {"id": req.id, "tokens": tokens,
                "prompt_tokens": prompt_tokens,
                "model_version": self.weights_version,
                "tenant": req.tenant, "pclass": req.pclass,
                "latency_s": latency, "ttft_s": ttft,
                "replayed_tokens": replayed,
                "preemptions": seq.preemptions}

    # -- KV-block migration ------------------------------------------------
    def pool_fingerprint(self) -> dict:
        """Pool-compatibility fingerprint a migration payload carries:
        adoption REQUIRES equality — same storage dtype, same block
        geometry, same per-row shape — or the raw exported rows would
        be reinterpreted wrongly. Weights equality is the caller's
        contract (replicas of one serving deployment share a
        checkpoint)."""
        c = self.cache_cfg
        return {"kv_dtype": str(jnp.dtype(c.dtype).name),
                "block_size": c.block_size, "n_layers": c.n_layers,
                "n_heads": c.n_heads, "head_dim": c.head_dim}

    def _migratable(self, what: str) -> None:
        """Migration moves per-head K and V (``MigrationPayload``, its
        wire format and the fingerprint name heads): a latent pool
        refuses, before a slot or a block is touched."""
        if self.cache_cfg.latent_dim:
            raise NotImplementedError(
                f"{what}: a pool of latent rows does not migrate "
                f"(serving/migrate.py describes per-head K and V)")

    def _block_rows(self, blocks) -> np.ndarray:
        bs = self.cache_cfg.block_size
        return np.concatenate(
            [np.arange(b * bs, (b + 1) * bs, dtype=np.int32)
             for b in blocks])

    def export_sequence(self, seq: Sequence, *,
                        reason: str = "migrate"):
        """Gather a PREFILLED sequence's KV blocks off the pool and
        return a :class:`~distributed_tensorflow_tpu.serving.migrate.
        MigrationPayload` holding everything another replica needs to
        continue it — raw block rows (scales included for int8), the
        request, tokens generated so far (carried as LIVE state, so the
        adopter replays nothing), and latency provenance. The
        sequence's slot and blocks are released HERE: after export the
        payload is the only copy, and publishing it is the caller's
        job (write-once blob commit makes that crash-safe).

        The exported rows include position ``length-1``'s not-yet-
        written row — the engine's KV timing invariant (the last banked
        token's KV is written by the NEXT decode step before any read),
        so shipping one stale row is byte-correct exactly like the
        monolithic step."""
        rid = seq.request.id
        sched = self.scheduler
        self._migratable(f"export {rid}")
        self._drain("export")
        if not seq.prefilled:
            raise ValueError(f"export {rid}: sequence not prefilled "
                             f"(nothing in the cache to migrate)")
        from distributed_tensorflow_tpu.serving import (
            migrate as _migrate)
        blocks = list(seq.table.blocks)
        t0 = time.monotonic()
        with telemetry.span("kv.migrate", id=rid,
                            span_id=migrate_span_id(rid),
                            direction="export", reason=reason,
                            blocks=len(blocks)) as sp:
            g = self._gather(self.pool,
                             jnp.asarray(self._block_rows(blocks)))
            arrays = {n: np.asarray(jax.device_get(a))
                      for n, a in g.items()}
            ttft = self._ttft(seq)
            payload = _migrate.MigrationPayload(
                request_id=rid, tokens=tuple(seq.request.tokens),
                max_new_tokens=seq.request.max_new_tokens,
                eos_id=seq.request.eos_id,
                generated_prefix=tuple(seq.request.generated_prefix),
                generated=tuple(seq.generated), length=seq.length,
                fingerprint=self.pool_fingerprint(),
                pool_epoch=self.pool_epoch,
                arrival_wall=self._submitted.get(rid),
                ttft_s=ttft, preemptions=seq.preemptions,
                arrays=arrays)
            sp["bytes"] = payload.nbytes
            # source-side release: the slot (unless the scheduler's
            # preemption path already freed it) and the block refs
            if sched.running.get(seq.slot) is seq:
                del sched.running[seq.slot]
                sched._free_slots.append(seq.slot)
                sched._free_slots.sort(reverse=True)
            seq.table.release(sched.allocator)
            self._submitted.pop(rid, None)
            self._submit_mono.pop(rid, None)
        ledger = _goodput.active_ledger()
        if ledger is not None:
            ledger.record("kv_migrate", time.monotonic() - t0)
        self.migrations_out += 1
        self.migrated_bytes += payload.nbytes
        return payload

    def can_adopt(self, payload) -> bool:
        """Non-destructive capacity probe: a free slot AND enough free
        blocks for the payload. The migration source MUST check before
        shipping — adoption never preempts to make room."""
        n_blocks = payload.arrays["k"].shape[1] \
            // self.cache_cfg.block_size
        return (bool(self.scheduler._free_slots)
                and self.scheduler.allocator.num_free >= n_blocks)

    def adopt_sequence(self, payload, *,
                       arrival_wall: "float | None" = None) -> Sequence:
        """Install a migrated-in sequence: allocate blocks, scatter the
        payload's rows into this pool, and register the sequence as
        prefilled-and-running. Greedy decode continues exactly where
        the source stopped — prior tokens are live generation state,
        so ``replayed_tokens`` stays 0 and the completion record is
        byte-identical to the monolithic run. Raises ``ValueError`` on
        a pool-fingerprint mismatch (never serves through an
        incompatible pool) and ``OutOfBlocksError`` when capacity is
        short (see :meth:`can_adopt`)."""
        rid = payload.request_id
        self._migratable(f"adopt {rid}")
        fp = self.pool_fingerprint()
        if payload.fingerprint != fp:
            raise ValueError(
                f"adopt {rid}: pool fingerprint mismatch "
                f"(payload {payload.fingerprint} vs engine {fp})")
        sched = self.scheduler
        bs = self.cache_cfg.block_size
        n_blocks = payload.arrays["k"].shape[1] // bs
        self._drain("adopt")
        t0 = time.monotonic()
        with telemetry.span("kv.migrate", id=rid,
                            span_id=migrate_span_id(rid),
                            direction="adopt", blocks=n_blocks,
                            bytes=payload.nbytes):
            blocks = sched.allocator.alloc(n_blocks)
            try:
                req = Request(id=rid, tokens=payload.tokens,
                              max_new_tokens=payload.max_new_tokens,
                              eos_id=payload.eos_id,
                              generated_prefix=tuple(
                                  payload.generated_prefix))
                seq = sched.adopt(req, blocks, payload.length,
                                  payload.generated)
            except Exception:
                sched.allocator.free(blocks)
                raise
            vals = {n: jnp.asarray(a)
                    for n, a in payload.arrays.items()}
            self.pool = self._insert(
                self.pool, jnp.asarray(self._block_rows(blocks)), vals)
            seq.preemptions = payload.preemptions
            self._submitted[rid] = (
                arrival_wall if arrival_wall is not None
                else payload.arrival_wall
                if payload.arrival_wall is not None else time.time())
            self._submit_mono[rid] = time.monotonic()
            if payload.ttft_s is not None:
                # preserve the SOURCE-measured time-to-first-token
                # (_complete reports first_token_s - its submit time)
                seq.first_token_s = (self._submit_mono[rid]
                                     + payload.ttft_s)
        ledger = _goodput.active_ledger()
        if ledger is not None:
            ledger.record("kv_migrate", time.monotonic() - t0)
        self.migrations_in += 1
        self.migrated_bytes += payload.nbytes
        return seq

    def block_accounting(self) -> dict:
        """Allocator conservation audit (the chaos --disagg gate):
        every live reference must be owned by a running sequence's
        table or a prefix-cache entry, and free + allocated must equal
        the usable pool. ``leaked_refs != 0`` or ``conserved: False``
        means a migration path dropped or duplicated block ownership."""
        sched = self.scheduler
        alloc = sched.allocator
        seq_refs = sum(len(s.table.blocks)
                       for s in sched.running.values())
        cache_refs = (len(sched.prefix_cache)
                      if sched.prefix_cache is not None else 0)
        return {
            "free": alloc.num_free,
            "allocated": alloc.num_allocated,
            "usable": self.cache_cfg.usable_blocks,
            "total_refs": alloc.total_refs,
            "seq_refs": seq_refs,
            "cache_refs": cache_refs,
            "leaked_refs": alloc.total_refs - seq_refs - cache_refs,
            "conserved": (alloc.num_free + alloc.num_allocated
                          == self.cache_cfg.usable_blocks),
        }

    # -- convenience -------------------------------------------------------
    def run_until_idle(self, *, max_steps: int = 100000,
                       retry_faults: bool = False) -> dict:
        """Drive :meth:`step` until queue and slots drain; returns
        ``{request_id: completion record}``. ``retry_faults=True``
        re-runs a step whose ``serve.step`` chaos site raised (the
        replica runtime's behavior)."""
        from distributed_tensorflow_tpu.resilience.faults import (
            FaultInjected)
        out: dict[str, dict] = {}
        for _ in range(max_steps):
            if self.scheduler.idle:
                break
            try:
                for rec in self.step():
                    out[rec["id"]] = rec
            except FaultInjected:
                if not retry_faults:
                    raise
        self._drain("idle")
        return out

    def generate(self, prompts, *, max_new_tokens: int = 16,
                 eos_id: int | None = None) -> list[list[int]]:
        """Batch convenience: greedy-decode ``prompts`` (lists of token
        ids) through the continuous-batching path; returns the generated
        token lists in prompt order."""
        for i, p in enumerate(prompts):
            self.submit(Request(id=f"g{i}", tokens=tuple(p),
                                max_new_tokens=max_new_tokens,
                                eos_id=eos_id))
        done = self.run_until_idle()
        return [done[f"g{i}"]["tokens"] for i in range(len(prompts))]

    def stats(self) -> dict:
        sched = self.scheduler
        out = {
            "steps": self._step_idx,
            "running": len(sched.running),
            "queued": len(sched.queue),
            "blocks_free": sched.allocator.num_free,
            "blocks_total": self.cache_cfg.usable_blocks,
            "preemptions": sched.preemptions,
            "deferred_prefill": sched.deferred_prefill,
            "deferred_blocks": sched.deferred_blocks,
            "migrated_out": sched.migrated_out,
            "migrations_out": self.migrations_out,
            "migrations_in": self.migrations_in,
            "migrated_bytes": self.migrated_bytes,
            "queue_rejected": sched.queue.rejected,
            "queue_evicted": sched.queue.evicted,
            "requests_completed": self._m_completed.value,
            "tokens_generated": self._m_tokens.value,
            "tokens_replayed": self._m_replayed.value,
            "serve_time_s": self._m_step.export().get("sum", 0.0),
            "kv_dtype": str(jnp.dtype(self.cache_cfg.dtype).name),
            "weights_step": self.weights_step,
            "weights_version": self.weights_version,
            "swaps": self.swaps,
        }
        if sched.prefix_cache is not None:
            out["prefix_cache"] = sched.prefix_cache.stats()
        if self.spill_tier is not None:
            out["spill_tier"] = self.spill_tier.stats()
        if self.spec_k:
            prop = self._spec_proposed_n
            out["speculative"] = {
                "k": self.spec_k,
                "proposed": prop,
                "accepted": self._spec_accepted_n,
                "accepted_rate": (self._spec_accepted_n / prop
                                  if prop else 0.0),
            }
        return out
