"""Ring attention / Ulysses SP vs single-device full attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.cluster.topology import make_mesh
from distributed_tensorflow_tpu.ops.attention import mha_reference
from distributed_tensorflow_tpu.parallel.sequence_parallel import (
    make_ring_attention)


@pytest.fixture(scope="module")
def qkv():
    rng = jax.random.PRNGKey(7)
    # seq 64 sharded 8 ways -> 8-token chunks; 8 heads so ulysses divides
    return jax.random.normal(rng, (3, 2, 8, 64, 16), dtype=jnp.float32)


@pytest.fixture(scope="module")
def qkv4():
    """Smaller operand for the GRADIENT tests on an sp=4 mesh: autodiff
    through the unrolled ring multiplies jaxpr size by ring length, and
    on the 1-core CI box the sp=8 grad programs alone cost minutes of
    XLA-CPU compile. Ring semantics (multi-step rotation, causal skip,
    rotating dk/dv accumulators) are length-independent; forward parity
    vs full attention stays at sp=8 below."""
    rng = jax.random.PRNGKey(11)
    return jax.random.normal(rng, (3, 2, 4, 32, 16), dtype=jnp.float32)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
# causal=False duplicates the easier half of the machinery the
# causal=True variant already exercises (no block skipping/mask
# edge) — tiered out of tier-1 (ISSUE 3 cold-suite item)
@pytest.mark.parametrize(
    "causal", [pytest.param(False, marks=pytest.mark.slow), True])
def test_sp_matches_full_attention(qkv, impl, causal, devices):
    q, k, v = qkv
    mesh = make_mesh({"sp": 8})
    fn = make_ring_attention(mesh, causal=causal, impl=impl)
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# causal=False duplicates the easier half of the machinery the
# causal=True variant already exercises (no block skipping/mask
# edge) — tiered out of tier-1 (ISSUE 3 cold-suite item)
@pytest.mark.parametrize(
    "causal", [pytest.param(False, marks=pytest.mark.slow), True])
def test_ring_attention_grads(qkv4, causal, devices):
    """ppermute has a well-defined transpose, so autodiff through the ring
    must match full-attention gradients."""
    q, k, v = qkv4
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    fn = make_ring_attention(mesh, causal=causal, impl="ring")
    gr = jax.grad(lambda *a: (mha_reference(*a, causal=causal) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
# causal=False duplicates the easier half of the machinery the
# causal=True variant already exercises (no block skipping/mask
# edge) — tiered out of tier-1 (ISSUE 3 cold-suite item)
@pytest.mark.parametrize(
    "causal", [pytest.param(False, marks=pytest.mark.slow), True])
def test_sp_flash_matches_full_attention(qkv4, impl, causal, devices):
    """The Pallas-kernel SP paths (interpret mode on CPU): forward parity
    with full attention — the fast path the chip runs. (sp=4 for CI
    compile time; no cell runs the Mosaic kernels under sp yet.)"""
    q, k, v = qkv4
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    fn = make_ring_attention(mesh, causal=causal, impl=impl,
                             attn_impl="interpret", block_q=8, block_k=8)
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# causal=False duplicates the easier half of the machinery the
# causal=True variant already exercises (no block skipping/mask
# edge) — tiered out of tier-1 (ISSUE 3 cold-suite item)
@pytest.mark.parametrize(
    "causal", [pytest.param(False, marks=pytest.mark.slow), True])
def test_ring_flash_grads(qkv4, causal, devices):
    """Flash-ring custom VJP (per-block backward against the global lse,
    rotating dk/dv accumulators) == full-attention gradients."""
    q, k, v = qkv4
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    fn = make_ring_attention(mesh, causal=causal, impl="ring",
                             attn_impl="interpret", block_q=8, block_k=8)
    gr = jax.grad(lambda *a: (mha_reference(*a, causal=causal) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")


def test_striped_attention_matches_full(qkv4, devices):
    """Striped (load-balanced) causal ring == full attention, forward."""
    q, k, v = qkv4
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    fn = make_ring_attention(mesh, causal=True, impl="striped",
                             attn_impl="interpret", block_q=8, block_k=8)
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_striped_attention_grads(qkv4, devices):
    """Striped custom VJP == full-attention gradients."""
    q, k, v = qkv4
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    fn = make_ring_attention(mesh, causal=True, impl="striped",
                             attn_impl="interpret", block_q=8, block_k=8)
    gr = jax.grad(lambda *a: (mha_reference(*a, causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")


def test_stripe_layout_roundtrip(devices):
    from distributed_tensorflow_tpu.parallel.sequence_parallel import (
        stripe_layout, unstripe_layout)
    x = jnp.arange(2 * 3 * 16 * 4).reshape(2, 3, 16, 4).astype(jnp.float32)
    s = stripe_layout(x, 8)
    np.testing.assert_allclose(np.asarray(unstripe_layout(s, 8)),
                               np.asarray(x))
    # device 0's shard (rows 0..1 of 16/8) holds global positions 0 and 8
    np.testing.assert_allclose(np.asarray(s[:, :, 0]),
                               np.asarray(x[:, :, 0]))
    np.testing.assert_allclose(np.asarray(s[:, :, 1]),
                               np.asarray(x[:, :, 8]))


def test_ring_attention_in_jit(qkv, devices):
    q, k, v = qkv
    mesh = make_mesh({"sp": 8})
    fn = jax.jit(make_ring_attention(mesh, causal=True))
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_striped_one_token_per_device_no_nan(devices):
    """seq == sp size: every strict step is an EMPTY block (the kernel's
    +inf-lse sentinel) — the recombination must treat it as zero
    contribution, not poison the output with NaN."""
    rng = jax.random.PRNGKey(3)
    q, k, v = jax.random.normal(rng, (3, 2, 4, 8, 16), jnp.float32)
    mesh = make_mesh({"sp": 8})
    fn = make_ring_attention(mesh, causal=True, impl="striped",
                             attn_impl="interpret", block_q=8, block_k=8)
    out = fn(q, k, v)
    assert np.isfinite(np.asarray(out)).all()
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_make_ring_attention_rejects_unknown_impl(devices):
    mesh = make_mesh({"sp": 8})
    with pytest.raises(ValueError, match="impl="):
        make_ring_attention(mesh, impl="zigzag")
    with pytest.raises(ValueError, match="flash kernel"):
        make_ring_attention(mesh, causal=True, impl="striped",
                            attn_impl="unfused")


# causal=False duplicates the easier half of the machinery the
# causal=True variant already exercises (no block skipping/mask
# edge) — tiered out of tier-1 (ISSUE 3 cold-suite item)
@pytest.mark.parametrize(
    "causal", [pytest.param(False, marks=pytest.mark.slow), True])
def test_ulysses_grads(qkv4, causal, devices):
    """all_to_all has a well-defined transpose: Ulysses gradients must
    match full attention (the one SP schedule previously without
    gradient coverage)."""
    q, k, v = qkv4
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    fn = make_ring_attention(mesh, causal=causal, impl="ulysses")
    gr = jax.grad(lambda *a: (mha_reference(*a, causal=causal) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")
