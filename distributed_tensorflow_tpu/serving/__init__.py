"""Multi-host sharded inference: KV-cache decode, continuous batching,
supervised serving replicas.

The serving stack over the trained sharded models (ROADMAP item 1 —
"serves heavy traffic from millions of users"):

- :mod:`kv_cache`  — block-allocated KV pool (PagedAttention-style
  fixed-size blocks; mixed-length requests share one batch; finished
  sequences free blocks immediately), head axis sharded over ``tp``.
- :mod:`decode`    — compiled prefill (full forward over a right-padded
  mixed-length batch, masked by the factored
  ``ops.attention.length_valid_mask`` rule) and one-token incremental
  decode over the block windows; greedy decode through the cache
  matches argmax over full-sequence recompute (tests/test_serving.py).
- :mod:`scheduler` — Orca-style continuous batching: admission queue,
  step-boundary admission under a token budget, newest-first preemption
  back to the queue when the pool runs dry.
- :mod:`engine`    — :class:`~distributed_tensorflow_tpu.serving.engine.
  InferenceEngine`: weights restored down the checkpoint recovery
  ladder, ``serve.step``/``serve.request`` telemetry, the ``serve.step``
  chaos site.
- :mod:`replica`   — the supervised replica worker function: heartbeats
  like a trainer (the recovery supervisor restarts a dead serving
  replica exactly like a dead trainer) and re-queues in-flight requests
  across restarts via its completion log (zero dropped requests).

Serving-speed optimisations (ISSUE 14, all output-invariant, all off
by default): copy-on-write **prefix caching**
(``InferenceEngine(prefix_caching=True)`` — committed prompt prefixes
shared across requests, refcounted in :class:`BlockAllocator`, indexed
by :class:`PrefixCache`), **speculative decoding**
(``speculative_k=k`` — draft-then-verify in one cache-aware forward,
greedy outputs exactly equal non-speculative), and a **quantized KV
pool** (``kv_dtype="bf16"|"int8"`` — 2-3.8x the servable slots per
chip; ``kv_quantization_probe`` measures the logit-error bound).

Disaggregated serving (ISSUE 16): :mod:`migrate` ships a sequence's
live KV blocks between replicas over the write-once chunked blob
transport — :class:`DisaggregatedEngine` splits prefill from decode
(a prefill burst stops blowing decode p99), drain mode ``migrate``
hands live sequences to a successor with zero replay, and cold
prefix-cache blocks spill to a :class:`HostTier` and re-adopt on hit
(``InferenceEngine(spill_tier=...)``). Greedy outputs stay
byte-identical to the monolithic engine throughout.

Multi-tenant frontend (ISSUE 20): :mod:`router` + :mod:`tenancy` put a
crash-tolerant, tenant-aware router in front of the replica fleet —
prefix-cache-affinity routing (falling back to least-loaded by scraped
queue depth, measured against random), weighted-fair admission with
priority classes (interactive ahead of batch, batch aged past its
starvation deadline promoted), per-tenant token-bucket quotas + SLO
burn windows, and a line-buffered decision journal that re-routes a
killed replica's in-flight work and survives a router kill without
double-serving. Chaos: ``python tools/chaos_sweep.py --router``.

Quick start::

    from distributed_tensorflow_tpu import serving

    engine = serving.InferenceEngine(cfg, params, mesh=mesh)
    engine.submit(serving.Request(id="a", tokens=prompt, max_new_tokens=32))
    while not engine.scheduler.idle:
        for done in engine.step():
            print(done["id"], done["tokens"])

Bench: ``python3 benchmark/run.py --workload tbig_serve.batch_chat``
(cells in ``BENCHMARK.json``; chip only); chaos: ``python
tools/chaos_sweep.py --serve``.
"""

from distributed_tensorflow_tpu.serving.engine import InferenceEngine
from distributed_tensorflow_tpu.serving.kv_cache import (
    BlockAllocator,
    BlockTable,
    CacheConfig,
    HostTier,
    OutOfBlocksError,
    PrefixCache,
    init_pool,
    pool_shardings,
)
from distributed_tensorflow_tpu.serving.migrate import (
    DisaggregatedEngine,
    FileKV,
    MigrationPayload,
    fetch_payload,
    pack_payload,
    publish_payload,
    unpack_payload,
)
from distributed_tensorflow_tpu.serving.scheduler import (
    AdmissionQueue,
    ContinuousBatchingScheduler,
    QueueOverflowError,
    Request,
    Sequence,
)
from distributed_tensorflow_tpu.serving.decode import (
    canonical_params,
    kv_quantization_probe,
    make_decode_fn,
    make_draft_fn,
    make_extend_fn,
    make_prefill_fn,
    model_forward,
    param_shardings,
    truncated_draft,
)
from distributed_tensorflow_tpu.serving.replica import (
    completed_ids,
    seeded_requests,
    serving_replica,
)
from distributed_tensorflow_tpu.serving.router import (
    Router,
    RouterJournal,
    RoutingPolicy,
    prefix_chain_keys,
    seeded_tenant_workload,
)
from distributed_tensorflow_tpu.serving.tenancy import (
    TenancyController,
    TenantConfig,
    TokenBucket,
    default_tenants,
    evaluate_tenants,
    fair_shares,
)

__all__ = [
    "InferenceEngine",
    "BlockAllocator", "BlockTable", "CacheConfig", "HostTier",
    "OutOfBlocksError", "PrefixCache", "init_pool", "pool_shardings",
    "DisaggregatedEngine", "FileKV", "MigrationPayload",
    "fetch_payload", "pack_payload", "publish_payload", "unpack_payload",
    "AdmissionQueue", "ContinuousBatchingScheduler", "QueueOverflowError",
    "Request", "Sequence",
    "canonical_params", "kv_quantization_probe", "make_decode_fn",
    "make_draft_fn", "make_extend_fn", "make_prefill_fn",
    "model_forward", "param_shardings", "truncated_draft",
    "completed_ids", "seeded_requests", "serving_replica",
    "Router", "RouterJournal", "RoutingPolicy", "prefix_chain_keys",
    "seeded_tenant_workload",
    "TenancyController", "TenantConfig", "TokenBucket",
    "default_tenants", "evaluate_tenants", "fair_shares",
]
