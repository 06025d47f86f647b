#!/usr/bin/env python3
"""Find the knee of an open-loop cell once: one process, one set-up,
stages of the same generator at rising rates.

    python benchmark/sweep.py --workload <name> --rates 3,4,5,6,7,8 --seconds 20

The knee is the highest rate whose stage ends with no more requests
waiting than it began with (the mean over the steps of the window's
last quarter against its first quarter, since bursts of four come and
go); a stage in which the engine's queue overflows ends the sweep.
Between stages the engine runs dry. The table goes
into PERF.md and 0.7 of the knee into the mix file as ``rate_rps``;
the benchmark itself never searches for a rate.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    from benchmark import harness, metric_math
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    _, _, _, config, traffic = harness.load_cell(ROOT, args.workload)
    runner = harness.make_runner(config, traffic, args.seed,
                                 jax.devices()[:1])
    runner.build()
    runner.warm()
    knee = None
    for stage, rate in enumerate(float(r) for r in args.rates.split(",")):
        source = runner.source(tag=f"s{stage}-", rate_rps=rate)
        source.seed = args.seed + 1000 * (stage + 1)   # documents anew
        rec = runner.drive(source, args.seconds, harness.Tracer(None))
        runner.engine.run_until_idle()
        p = lambda series, q: metric_math.percentile(rec[series], q)
        began, ended = metric_math.quarter_means(rec["waiting"])
        row = {"rate_rps": rate, "attempted": rec["attempted"],
               "failed": rec["failed"], "rejected": rec["rejected"],
               "waiting_first_quarter": began,
               "waiting_last_quarter": ended,
               "ttft_p50_ms": p("ttft_ms", 50),
               "ttft_p90_ms": p("ttft_ms", 90),
               "tpot_p50_ms": p("tpot_ms", 50),
               "queue_wait_p90_ms": p("queue_wait_ms", 90),
               "decode_batch_mean": metric_math.reduce(
                   rec, {"stat": "mean", "series": "decode_batch"}),
               "tokens_per_s": rec["tokens"] / rec["elapsed_s"],
               "plain_step_ms_p50": p("plain_step_ms", 50)}
        print(json.dumps(row), flush=True)
        if rec["rejected"]:
            break
        if ended <= began + 0.5:
            knee = rate
    print(json.dumps({"knee_rps": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
