"""Config #4 end-to-end: DLRM/Wide&Deep + embedding API + async PS.

≙ the reference's ParameterServerStrategyV2 + TPUEmbedding training flow
(parameter_server_strategy_v2.py:77 coordinator-owned variables +
tpu_embedding_v2.py:76 feature-config tables, BASELINE.md config #4):
the ClusterCoordinator schedules gradient closures onto workers holding
per-worker datasets, and the coordinator folds results into the server
copy asynchronously as they arrive.

Run locally (thread-lane workers, any backend)::

    python examples/train_dlrm_ps.py --steps 200 --workers 4

The REAL multi-process form (remote worker processes + kill-failover) is
exercised by tests/test_multi_process.py::test_dlrm_async_ps_end_to_end;
a production job runs the same `train_dlrm_async_ps` loop on process 0
with `remote_worker_ids=[1..N]` after `bootstrap.initialize()`, workers
running `remote_dispatch.run_worker_loop()`.
"""

from __future__ import annotations

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=64)
    args = ap.parse_args()

    from distributed_tensorflow_tpu.coordinator.cluster_coordinator import (
        ClusterCoordinator)
    from distributed_tensorflow_tpu.models import wide_deep as wd
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache)

    enable_compile_cache()
    cfg = wd.WideDeepConfig.tiny()
    coord = ClusterCoordinator(num_workers=args.workers)
    try:
        state, losses = wd.train_dlrm_async_ps(
            cfg, coord, steps=args.steps, batch_size=args.batch_size,
            log_every=20)
    finally:
        coord.shutdown()
    first = sum(losses[:20]) / min(20, len(losses))
    last = sum(losses[-20:]) / min(20, len(losses))
    print(f"loss: first-20 avg {first:.4f} -> last-20 avg {last:.4f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
