"""SP micro-bench: ring attention per-step compute, unfused vs flash.

The ring's wall-clock is (#unskipped blocks on the critical rank) x
(per-block compute time): ppermute synchronizes every step, so the
per-block kernel IS the knob. This bench times both per-step paths on
the real chip at long-context chunk sizes (the driver's single chip
can't host a real sp>1 mesh):

- "unfused": the original ``_local_attn_stats`` path — materializes the
  full (sq, sk) fp32 logits per step (sequence_parallel.py round-1 form);
- "flash": the Pallas kernel path ``ring_flash_attention`` now uses.

Timing methodology (= bench.py): each candidate runs inside an on-device
``lax.fori_loop`` whose body CHAINS q through the attention output (no
loop-invariant hoisting, no per-call dispatch), timed as the delta
between a 1-iteration and an (N+1)-iteration loop with scalar readback —
launch and async-dispatch artifacts cancel.

Also reports the causal work-skip factor (blocks computed old vs new).

Run on TPU:  python tools/sp_bench.py [seq_per_chunk] [ring_size]
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.ops import attention as attn
from distributed_tensorflow_tpu.parallel import sequence_parallel as sp

N_ITERS = 20
REPS = 5


def _timed_loop(step_fn, q, k, v):
    """Per-call time of step_fn via fori_loop delta (bench.py method)."""

    @functools.partial(jax.jit, static_argnums=3)
    def loop(q, k, v, n):
        def body(_, qc):
            return step_fn(qc, k, v).astype(qc.dtype)
        return jax.lax.fori_loop(0, n, body, q)

    def timed(n):
        best = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            out = loop(q, k, v, n)
            float(out.sum())          # scalar readback = true completion
            best = min(best, time.perf_counter() - t0)
        return best

    jax.block_until_ready(loop(q, k, v, 1))
    jax.block_until_ready(loop(q, k, v, 1 + N_ITERS))
    return (timed(1 + N_ITERS) - timed(1)) / N_ITERS


def main():
    seq = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    ring = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    b, h, d = 1, 16, 64
    rng = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(r, (b, h, seq, d), jnp.bfloat16)
               for r in jax.random.split(rng, 3))
    scale = d ** -0.5

    def unfused(qc, k, v):
        o, _, l = sp._local_attn_stats(qc, k, v, sm_scale=scale)
        return (o / jnp.maximum(l, 1e-9))

    def flash(qc, k, v):
        return attn._flash_forward(qc, k, v, scale, False, 512, 1024,
                                   False)[0]

    t_unfused = _timed_loop(unfused, q, k, v)
    t_flash = _timed_loop(flash, q, k, v)
    flops = 4 * b * h * seq * seq * d
    print({"bench": "sp_per_step_fwd", "seq_chunk": seq,
           "unfused_ms": round(t_unfused * 1e3, 3),
           "flash_ms": round(t_flash * 1e3, 3),
           "unfused_tflops": round(flops / t_unfused / 1e12, 1),
           "flash_tflops": round(flops / t_flash / 1e12, 1),
           "speedup": round(t_unfused / t_flash, 2)})

    # fwd+bwd through each per-step path (grad w.r.t. q chains the loop)
    def unfused_g(qc, k, v):
        return jax.grad(lambda qq: unfused(qq, k, v)
                        .astype(jnp.float32).sum())(qc)

    def flash_g(qc, k, v):
        return jax.grad(lambda qq: attn.flash_attention(
            qq, k, v, implementation="pallas")
            .astype(jnp.float32).sum())(qc)

    t_unfused_g = _timed_loop(unfused_g, q, k, v)
    t_flash_g = _timed_loop(flash_g, q, k, v)
    print({"bench": "sp_per_step_fwd_bwd", "seq_chunk": seq,
           "unfused_ms": round(t_unfused_g * 1e3, 3),
           "flash_ms": round(t_flash_g * 1e3, 3),
           "speedup": round(t_unfused_g / t_flash_g, 2)})

    blocks_old = ring * ring          # every rank computes every step
    blocks_new = ring * (ring + 1) // 2
    print({"bench": "causal_blocks_computed", "ring": ring,
           "old": blocks_old, "new": blocks_new,
           "flop_factor": round(blocks_old / blocks_new, 2)})


if __name__ == "__main__":
    main()
