"""Flash attention kernel vs unfused reference (CPU, interpret mode).

On the CPU test mesh both paths are exact fp32, so tolerances are tight —
the TPU bf16-MXU run is the benchmark's (``tbig_train.steady``) and
``chip_smoke.py``'s on hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops.attention import (
    flash_attention, mha_reference)


@pytest.fixture(scope="module")
def qkv():
    rng = jax.random.PRNGKey(0)
    return jax.random.normal(rng, (3, 2, 3, 64, 32), dtype=jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(qkv, causal):
    q, k, v = qkv
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal,
                          implementation="interpret",
                          block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(qkv, causal):
    q, k, v = qkv

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    def loss_pal(q, k, v):
        return (flash_attention(q, k, v, causal=causal,
                                implementation="interpret",
                                block_q=16, block_k=16) ** 2).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [False, True])
def test_uneven_blocks(qkv, causal):
    """Sequence length not a multiple of the block size (40 = 2.5 blocks)."""
    q, k, v = qkv
    q, k, v = q[:, :, :40], k[:, :, :40], v[:, :, :40]
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal,
                          implementation="interpret",
                          block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    gr = jax.grad(lambda *a: (mha_reference(*a, causal=causal) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(lambda *a: (flash_attention(
        *a, causal=causal, implementation="interpret",
        block_q=16, block_k=16) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_fully_masked_rows_zero_grads(qkv):
    """Causal with q_len > k_len: bottom-right alignment leaves the first
    q_len - k_len query rows with no visible keys. Their outputs and their
    contribution to dq/dk/dv must be exactly zero (ADVICE r1: the saved
    lse must not make backward recompute p = 1 on those rows)."""
    q, k, v = qkv
    k_short, v_short = k[:, :, :32], v[:, :, :32]
    ref = mha_reference(q, k_short, v_short, causal=True)
    out = flash_attention(q, k_short, v_short, causal=True,
                          implementation="interpret",
                          block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert np.abs(np.asarray(out)[:, :, :32]).max() == 0.0

    gr = jax.grad(lambda *a: (mha_reference(*a, causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k_short, v_short)
    gp = jax.grad(lambda *a: (flash_attention(
        *a, causal=True, implementation="interpret",
        block_q=16, block_k=16) ** 2).sum(), argnums=(0, 1, 2))(
            q, k_short, v_short)
    for name, a, b in zip("qkv", gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")
    # The empty query rows themselves get zero gradient.
    assert np.abs(np.asarray(gp[0])[:, :, :32]).max() == 0.0


def test_sharded_flash_no_allgather(devices):
    """sharded_flash_attention partitions the Pallas custom call over
    batch/head axes via shard_map: the compiled module must contain no
    all-gather (replicated-kernel symptom, ADVICE r1 medium)."""
    from distributed_tensorflow_tpu.cluster.topology import make_mesh
    from distributed_tensorflow_tpu.ops.attention import \
        sharded_flash_attention
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh({"dp": 4, "tp": 2})
    rng = jax.random.PRNGKey(0)
    q, k, v = jax.random.normal(rng, (3, 8, 4, 64, 16), dtype=jnp.float32)
    shard = NamedSharding(mesh, P("dp", "tp", None, None))
    q, k, v = (jax.device_put(t, shard) for t in (q, k, v))

    fn = jax.jit(lambda q, k, v: sharded_flash_attention(
        q, k, v, mesh, causal=True, implementation="interpret",
        block_q=16, block_k=16))
    compiled = fn.lower(q, k, v).compile()
    hlo = compiled.as_text()
    assert "all-gather" not in hlo and "all-to-all" not in hlo, \
        "attention operands were gathered — kernel not partitioned"
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_cross_attention_shapes(qkv, causal):
    """kv length != q length (decode / encoder-decoder attention).

    Causal alignment is bottom-right (tril k=ks-qs), matching
    mha_reference: the last query row sees all keys.
    """
    q, k, v = qkv
    q_short = q[:, :, :32]
    ref = mha_reference(q_short, k, v, causal=causal)
    out = flash_attention(q_short, k, v, causal=causal,
                          implementation="interpret",
                          block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    gr = jax.grad(lambda *a: (mha_reference(*a, causal=causal) ** 2).sum(),
                  argnums=(0, 1, 2))(q_short, k, v)
    gp = jax.grad(lambda *a: (flash_attention(
        *a, causal=causal, implementation="interpret",
        block_q=16, block_k=16) ** 2).sum(), argnums=(0, 1, 2))(q_short, k, v)
    for name, a, b in zip("qkv", gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")
