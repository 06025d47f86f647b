"""Fused AdamW update for TPU (Pallas, aliased in-place buffers).

The optimizer update is the bandwidth-bound tail of the train step: for
the flagship transformer (235M fp32 params) the information floor is
read {p, g, mu, nu} + write {p, mu, nu} = 28 B/param ≈ 6.6 GB, ~8 ms at
v5e HBM bandwidth — but the XLA lowering of the optax chain measures
~14 ms (≈13% of the step): the (updates, new_state) functional shape of
``scale_by_adam`` → ``add_decayed_weights`` → ``scale`` materializes
intermediate trees that fusion does not fully collapse. This kernel does
the whole read-modify-write in ONE pass per parameter block, with every
output aliased onto its input buffer (true in-place update, no second
allocation), which pins the traffic at the floor.

≙ the reference's fused training ops (TF/python/training/training_ops.py
``resource_apply_adam`` — a single fused C++/CUDA kernel mutating the
variable and slots in place; the functional-JAX equivalent of "mutate in
place" is input→output aliasing + donation).

Semantics match ``optax.adamw`` exactly (same bias correction, eps
placement outside the sqrt, decoupled weight decay, update order):
    mu'  = b1·mu + (1-b1)·g
    nu'  = b2·nu + (1-b2)·g²
    u    = (mu'/(1-b1^t)) / (sqrt(nu'/(1-b2^t)) + eps) + wd·p
    p'   = p - lr·u
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Elementwise tiles: (rows, 1024) fp32. 256×1024×4B = 1 MiB per operand
# block; 4 in + 3 aliased out keep VMEM well under the 16 MiB default.
_LANES = 1024
_ROWS = 256


def adamw_reference(p, g, mu, nu, c1, c2, *, lr, b1, b2, eps, wd):
    """Plain-jnp contract (and non-TPU fallback); c1 = 1/(1-b1^t),
    c2 = 1/(1-b2^t) are the (dynamic) bias corrections."""
    p32, g32 = p.astype(jnp.float32), g.astype(jnp.float32)
    mu2 = b1 * mu.astype(jnp.float32) + (1.0 - b1) * g32
    nu2 = b2 * nu.astype(jnp.float32) + (1.0 - b2) * jnp.square(g32)
    u = (mu2 * c1) / (jnp.sqrt(nu2 * c2) + eps) + wd * p32
    return ((p32 - lr * u).astype(p.dtype), mu2.astype(mu.dtype),
            nu2.astype(nu.dtype))


def _adamw_kernel(c_ref, p_ref, g_ref, mu_ref, nu_ref,
                  po_ref, muo_ref, nuo_ref, *, lr, b1, b2, eps, wd):
    c1 = c_ref[0]
    c2 = c_ref[1]
    g = g_ref[:].astype(jnp.float32)
    mu2 = b1 * mu_ref[:].astype(jnp.float32) + (1.0 - b1) * g
    nu2 = b2 * nu_ref[:].astype(jnp.float32) + (1.0 - b2) * g * g
    p = p_ref[:].astype(jnp.float32)
    u = (mu2 * c1) / (jnp.sqrt(nu2 * c2) + eps) + wd * p
    po_ref[:] = (p - lr * u).astype(po_ref.dtype)
    muo_ref[:] = mu2.astype(muo_ref.dtype)
    nuo_ref[:] = nu2.astype(nuo_ref.dtype)


def _fused_leaf_update(p, g, mu, nu, corrections, *, lr, b1, b2, eps, wd,
                       interpret):
    """One parameter leaf in one aliased pallas pass. The three outputs
    alias their input buffers — with jit donation this is a true
    in-place update.

    Layout discipline: a leaf that is already (..., cols) with a
    128-multiple minor dim is viewed as (prod(leading), cols) — under
    TPU tiling that collapse is physically free, whereas flattening to
    a fixed (N/1024, 1024) grid re-tiles the buffer (a full extra
    read+write per operand, which is how the first version of this
    kernel LOST to XLA's fusions). Only oddly-shaped small leaves
    (biases, norm scales) take the pad-and-reshape path."""
    shape = p.shape
    n = p.size
    if p.ndim >= 2 and shape[-1] % 128 == 0:
        cols = shape[-1]
        rows_total = n // cols
    else:
        cols = _LANES if n >= _LANES else max(
            128, 1 << (n - 1).bit_length())
        rows_total = -(-n // cols)
    block_rows = min(max(_ROWS // max(cols // _LANES, 1), 8), rows_total)

    def prep(x):
        if x.ndim >= 2 and x.shape[-1] % 128 == 0:
            return x.reshape(-1, x.shape[-1])
        flat = x.reshape(-1)
        pad = rows_total * cols - n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return flat.reshape(rows_total, cols)

    grid = (pl.cdiv(rows_total, block_rows),)

    def spec_for(dtype):
        return pl.BlockSpec((block_rows, cols), lambda i: (i, 0))

    p2, mu2, nu2 = pl.pallas_call(
        functools.partial(_adamw_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                          wd=wd),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            spec_for(p.dtype), spec_for(g.dtype),
            spec_for(mu.dtype), spec_for(nu.dtype),
        ],
        out_specs=[spec_for(p.dtype), spec_for(mu.dtype),
                   spec_for(nu.dtype)],
        out_shape=[
            jax.ShapeDtypeStruct((rows_total, cols), p.dtype),
            jax.ShapeDtypeStruct((rows_total, cols), mu.dtype),
            jax.ShapeDtypeStruct((rows_total, cols), nu.dtype),
        ],
        # operands: 0=corrections(SMEM), 1=p, 2=g, 3=mu, 4=nu
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        name="fused_adamw",
    )(corrections, prep(p), prep(g), prep(mu), prep(nu))

    def unprep(x):
        return x.reshape(-1)[:n].reshape(shape)

    return unprep(p2), unprep(mu2), unprep(nu2)


def fused_adamw_update(params, grads, mu, nu, count, *,
                       lr: float, b1: float = 0.9, b2: float = 0.999,
                       eps: float = 1e-8, weight_decay: float = 0.0,
                       implementation: str | None = None,
                       mesh=None, param_specs=None):
    """Apply one AdamW step to a whole pytree in fused one-pass kernels.

    params/grads/mu/nu: matching pytrees; count: the PRE-increment step
    counter (optax convention: bias corrections use count+1). Returns
    (new_params, new_mu, new_nu, new_count).

    implementation: "pallas" | "interpret" | "reference" | None (auto:
    pallas on TPU, reference elsewhere). With ``mesh`` + ``param_specs``
    (a pytree of PartitionSpecs matching params' structure) each leaf's
    kernel runs per-shard under shard_map — the update is elementwise,
    so any sharding layout is valid and no collectives are needed.
    """
    if implementation is None:
        implementation = ("pallas" if jax.default_backend() == "tpu"
                          else "reference")
    new_count = count + 1
    cf = new_count.astype(jnp.float32)
    c1 = 1.0 / (1.0 - jnp.power(b1, cf))
    c2 = 1.0 / (1.0 - jnp.power(b2, cf))

    leaves_p, treedef = jax.tree_util.tree_flatten(params)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_m = treedef.flatten_up_to(mu)
    leaves_v = treedef.flatten_up_to(nu)

    if implementation == "reference":
        out = [adamw_reference(p, g, m, v, c1, c2, lr=lr, b1=b1, b2=b2,
                               eps=eps, wd=weight_decay)
               for p, g, m, v in zip(leaves_p, leaves_g, leaves_m,
                                     leaves_v)]
    else:
        interp = implementation == "interpret"
        corrections = jnp.stack([c1, c2])
        leaf_fn = functools.partial(_fused_leaf_update, lr=lr, b1=b1,
                                    b2=b2, eps=eps, wd=weight_decay,
                                    interpret=interp)
        sharded = (mesh is not None and mesh.size > 1
                   and param_specs is not None)
        if sharded:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            leaves_s = jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda x: isinstance(x, P))
            if len(leaves_s) != len(leaves_p):
                raise ValueError(
                    f"param_specs has {len(leaves_s)} specs for "
                    f"{len(leaves_p)} parameter leaves")
            out = []
            for p, g, m, v, s in zip(leaves_p, leaves_g, leaves_m,
                                     leaves_v, leaves_s):
                out.append(shard_map(
                    leaf_fn, mesh=mesh, in_specs=(s, s, s, s, P()),
                    out_specs=(s, s, s), check_vma=False)(
                        p, g, m, v, corrections))
        else:
            out = [leaf_fn(p, g, m, v, corrections)
                   for p, g, m, v in zip(leaves_p, leaves_g, leaves_m,
                                         leaves_v)]

    unflat = lambda i: jax.tree_util.tree_unflatten(
        treedef, [t[i] for t in out])
    return unflat(0), unflat(1), unflat(2), new_count
