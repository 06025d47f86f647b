"""Interleaved A/B: scan-chunked CE vs Pallas fused-CE kernel, one
process, same chip (the round-3 measurement protocol — back-to-back
bursts of one arm lie under the ±0.02 MFU run-to-run variance;
interleaving cancels it).

Usage: python tools/ce_ab.py [batch] [n_iters] [rounds]
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from distributed_tensorflow_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, TransformerLM, make_optimizer, make_train_step,
    synthetic_tokens)
from bench import PEAK_TFLOPS, step_flops  # noqa: E402  (shared cost model)

PEAK = PEAK_TFLOPS["tpu"] * 1e12


def build(loss_impl: str, batch: int, **cfg_kw):
    base = dict(max_seq_len=1024, remat=False, scan_layers=False,
                loss_chunks=8, attn_block_q=1024, attn_block_k=1024,
                loss_impl=loss_impl)
    base.update(cfg_kw)
    cfg = TransformerConfig.transformer_big(**base)
    model = TransformerLM(cfg)
    tx = make_optimizer(cfg)
    tokens = synthetic_tokens(batch, cfg.max_seq_len, cfg.vocab_size)

    @jax.jit
    def init_fn(rng):
        params = model.init(rng, tokens)["params"]
        return {"params": params, "opt_state": tx.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.block_until_ready(init_fn(jax.random.PRNGKey(0)))
    step = make_train_step(cfg, model, tx)

    @functools.partial(jax.jit, static_argnums=2)
    def loop(state, toks, n):
        def body(_, s):
            s2, _ = step(s, {"tokens": toks})
            return s2
        return jax.lax.fori_loop(0, n, body, state)

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        state["params"]))
    return loop, state, tokens, n_params, cfg


def time_one(loop, state, tokens, n):
    t0 = time.perf_counter()
    out = loop(state, tokens, n)
    float(out["step"])
    return time.perf_counter() - t0


def grad_parity_check():
    """Compiled-mode numerics: kernel CE loss + grads vs the naive
    full-logits CE ON THE CHIP (the merged backward's aliased-buffer
    accumulation only exists in compiled mode — the CPU interpret
    tests cannot see it). Runs twice to catch nondeterministic
    pipelining races."""
    import numpy as np
    from distributed_tensorflow_tpu.ops.fused_ce import (
        ce_reference, fused_cross_entropy)
    N, V, D = 2048, 32768, 1024
    h = jax.random.normal(jax.random.PRNGKey(0), (N, D), jnp.bfloat16)
    E = jax.random.normal(jax.random.PRNGKey(1), (V, D),
                          jnp.bfloat16) * 0.02
    t = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, V, jnp.int32)

    def mean(impl):
        def f(h, E):
            l = (fused_cross_entropy(h, E, t, implementation=impl)
                 if impl else ce_reference(h, E, t))
            return l.mean()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))

    lk1, gk1 = jax.block_until_ready(mean("pallas")(h, E))
    lk2, gk2 = jax.block_until_ready(mean("pallas")(h, E))
    lr, gr = jax.block_until_ready(mean(None)(h, E))
    np.testing.assert_allclose(float(lk1), float(lr), rtol=2e-3)
    for a, b in zip(gk1, gk2):   # determinism across runs
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(gk1, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.1, atol=2e-4)  # bf16 grads, bf16-resolution bound
    print("grad_parity_check: OK "
          f"(loss {float(lk1):.5f} vs {float(lr):.5f})")


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    n_iters = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    rounds = int(sys.argv[3]) if len(sys.argv) > 3 else 6
    grad_parity_check()

    arms = {}
    for name in ("scan", "kernel"):
        try:
            arms[name] = build(name, batch)
        except Exception as e:                    # noqa: BLE001
            print(f"{name}: BUILD FAILED {type(e).__name__}: "
                  f"{str(e)[:300]}")
            return

    # Warm all compilations.
    for name, (loop, state, tokens, _, _) in arms.items():
        jax.block_until_ready(loop(state, tokens, 1))
        jax.block_until_ready(loop(state, tokens, 1 + n_iters))
        print(f"{name}: warmed")

    best = {name: [float("inf"), float("inf")] for name in arms}
    for r in range(rounds):
        for name, (loop, state, tokens, _, _) in arms.items():
            best[name][0] = min(best[name][0],
                                time_one(loop, state, tokens, 1))
            best[name][1] = min(best[name][1],
                                time_one(loop, state, tokens,
                                         1 + n_iters))

    for name, (loop, state, tokens, n_params, cfg) in arms.items():
        dt = (best[name][1] - best[name][0]) / n_iters
        tps = batch * cfg.max_seq_len
        mfu = (step_flops(cfg, batch, n_params) / dt) / PEAK
        print(f"{name}: step {dt*1e3:.2f} ms  mfu {mfu:.4f}  "
              f"tokens/s {tps/dt:,.0f}")


if __name__ == "__main__":
    main()
