"""Interleaved A/B: optax adamw vs the fused Pallas adamw update
(ops/fused_adamw.py) on the headline bench config, one process, same
chip (tools/ce_ab.py protocol — bursts of one arm lie under the
run-to-run variance; interleaving cancels it).

Usage: python tools/opt_ab.py [batch] [n_iters] [rounds]
"""

from __future__ import annotations

import sys

import jax

sys.path.insert(0, ".")
from tools.ce_ab import build, time_one, PEAK    # noqa: E402
from bench import step_flops                     # noqa: E402


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    n_iters = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    rounds = int(sys.argv[3]) if len(sys.argv) > 3 else 6

    arms = {}
    for name, fused in (("optax", False), ("fused", True)):
        try:
            arms[name] = build("kernel", batch, fused_optimizer=fused)
        except Exception as e:                    # noqa: BLE001
            print(f"{name}: BUILD FAILED {type(e).__name__}: "
                  f"{str(e)[:300]}")
            return

    for name, (loop, state, tokens, _, _) in arms.items():
        jax.block_until_ready(loop(state, tokens, 1))
        jax.block_until_ready(loop(state, tokens, 1 + n_iters))
        print(f"{name}: warmed")

    best = {name: [float("inf"), float("inf")] for name in arms}
    for _ in range(rounds):
        for name, (loop, state, tokens, _, _) in arms.items():
            best[name][0] = min(best[name][0],
                                time_one(loop, state, tokens, 1))
            best[name][1] = min(best[name][1],
                                time_one(loop, state, tokens,
                                         1 + n_iters))

    for name, (loop, state, tokens, n_params, cfg) in arms.items():
        dt = (best[name][1] - best[name][0]) / n_iters
        mfu = (step_flops(cfg, batch, n_params) / dt) / PEAK
        print(f"{name}: step {dt*1e3:.2f} ms  mfu {mfu:.4f}  "
              f"tokens/s {batch*cfg.max_seq_len/dt:,.0f}")


if __name__ == "__main__":
    main()
