"""ZeRO-1/2: optimizer-state (and gradient) sharding over data parallel.

The reference's ``ShardedVariable``/ParameterServer layer
(sharded_variable.py:843) is the ancestral form of training-state
sharding: variables partitioned across stores, each optimizer update
touching only the owning shard. This module is the modern descendant
for a synchronous dp mesh — the ZeRO family (Rajbhandari et al.):

- **ZeRO-1**: gradients are still all-reduced (full grads everywhere,
  bit-identical to the replicated path), but Adam's mu/nu slots exist
  only for this rank's 1/N slice of the parameters. After the sliced
  update, an all-gather over dp rebuilds the full parameters. State
  per device: 4P param bytes + 8P/N slot bytes (f32 slots).
- **ZeRO-2**: the gradient bucket is reduce-scattered instead — each
  rank only ever materializes its grad shard, saving the full-gradient
  buffer as well as the slots.

Exactness by construction: parameters pack into the same dtype-pure
buckets ``GradientBucketer`` uses for gradient sync
(collectives.plan_buckets — packing concatenates, never casts), and
every transform in the AdamW chain (scale_by_adam, add_decayed_weights,
scale-by-lr, apply_updates) is elementwise given the shared step count,
so running ``optax.adamw`` on flat bucket shards produces exactly the
bits the replicated tree update produces for those elements. The
reduce-scatter uses the same packed buffer the bucketed allreduce
would, so ZeRO-2 grads are the replicated grads' own slices
(``lax.psum_scatter`` + /N vs ``pmean``-then-slice is bitwise tested
in tests/test_collectives.py). tests/test_zero.py pins params
bit-identical to replicated Adam after N steps.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.parallel.collectives import (
    DEFAULT_BYTES_PER_PACK, ReduceOp, plan_buckets, reduce_scatter)


class ZeroPartition:
    """Static ZeRO partition plan over a flat list of parameter leaves.

    Leaves pack into the same dtype-pure buckets ``GradientBucketer``
    plans for gradient sync (reverse layer order), each bucket
    flattened to one 1-D vector zero-padded to a multiple of
    ``n_shards``. Rank r owns the r-th equal slice of every bucket.
    Padding elements stay zero under AdamW (zero grad, zero param ->
    zero update), so they are inert forever.
    """

    def __init__(self, leaves: Sequence, n_shards: int, *,
                 bytes_per_pack: int = DEFAULT_BYTES_PER_PACK,
                 reverse: bool = True):
        self.n_shards = int(n_shards)
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.shapes = [tuple(jnp.shape(x)) for x in leaves]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.dtypes = [jnp.dtype(jnp.result_type(x)) for x in leaves]
        self.buckets = plan_buckets(self.sizes, self.dtypes,
                                    bytes_per_pack, reverse=reverse)
        self.bucket_sizes = [sum(self.sizes[i] for i in b)
                             for b in self.buckets]
        self.padded_sizes = [s + (-s) % self.n_shards
                             for s in self.bucket_sizes]
        self.shard_sizes = [p // self.n_shards for p in self.padded_sizes]
        self.bucket_dtypes = [self.dtypes[b[0]] for b in self.buckets]

    def pack(self, leaves: Sequence) -> list:
        """Leaves -> per-bucket flat padded 1-D vectors."""
        flats = []
        for b, bucket in enumerate(self.buckets):
            flat = jnp.concatenate(
                [jnp.ravel(jnp.asarray(leaves[i])) for i in bucket])
            pad = self.padded_sizes[b] - self.bucket_sizes[b]
            if pad:
                flat = jnp.pad(flat, (0, pad))
            flats.append(flat)
        return flats

    def unpack(self, flats: Sequence) -> list:
        """Per-bucket flat vectors (padded) -> leaves."""
        out: list = [None] * len(self.sizes)
        for b, bucket in enumerate(self.buckets):
            off = 0
            for i in bucket:
                out[i] = jnp.reshape(flats[b][off:off + self.sizes[i]],
                                     self.shapes[i])
                off += self.sizes[i]
        return out

    def shard(self, flats: Sequence, rank) -> list:
        """This rank's slice of each packed bucket (rank may be traced)."""
        return [lax.dynamic_slice_in_dim(f, rank * s, s)
                for f, s in zip(flats, self.shard_sizes)]

    def reduce_scatter_mean(self, leaves: Sequence, axis_name: str) -> list:
        """ZeRO-2 gradient sync: pack each bucket and reduce-scatter it
        over ``axis_name`` — this rank receives only its mean-reduced
        shard; the full gradient bucket never materializes. Bitwise
        equal to pmean-then-slice of the same packed buffer."""
        return [reduce_scatter(f, axis_name, axis=0, op=ReduceOp.MEAN)
                for f in self.pack(leaves)]

    def all_gather_flats(self, shards: Sequence, axis_name: str) -> list:
        return [lax.all_gather(s, axis_name, axis=0, tiled=True)
                for s in shards]

    def shard_templates(self) -> list:
        return [jax.ShapeDtypeStruct((s,), dt)
                for s, dt in zip(self.shard_sizes, self.bucket_dtypes)]

    def summary(self) -> dict:
        return {"n_shards": self.n_shards,
                "buckets": len(self.buckets),
                "elements": sum(self.bucket_sizes),
                "padded_elements": sum(self.padded_sizes),
                "shard_elements": sum(self.shard_sizes)}


def zero_opt_state(tx, partition: ZeroPartition, mesh: Mesh,
                   axes: tuple | None = None):
    """Materialize the sharded optimizer state + shardings + specs.

    The optax state over bucket shards is structurally
    (count, mu=[shards], nu=[shards], ...): every 1-D leaf is one
    rank's slice, laid out globally as a ``shard * N`` vector sharded
    ``P(axes)`` (rank r's slice at offset r); 0-D leaves (the step
    count) are replicated. AdamW's init is zeros everywhere, so the
    global arrays are plain sharded zeros — verified against the real
    ``tx.init`` so a tx with non-zero init state fails loudly.
    """
    axes = tuple(mesh.axis_names) if axes is None else tuple(axes)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    abstract = jax.eval_shape(tx.init, partition.shard_templates())
    concrete = tx.init([jnp.zeros((s,), dt) for s, dt in
                        zip(partition.shard_sizes, partition.bucket_dtypes)])
    for leaf in jax.tree_util.tree_leaves(concrete):
        if np.any(np.asarray(leaf)):
            raise ValueError(
                "ZeRO sharding supports optimizers whose init state is "
                "all-zero (optax.adamw); got a non-zero init leaf")

    def sharding_of(leaf):
        return NamedSharding(mesh, P() if leaf.ndim == 0 else P(axes))

    shardings = jax.tree_util.tree_map(sharding_of, abstract)
    opt_state = jax.tree_util.tree_map(
        lambda leaf, s: jax.device_put(
            jnp.zeros((leaf.shape[0] * n,) if leaf.ndim else (),
                      leaf.dtype), s),
        abstract, shardings)
    specs = jax.tree_util.tree_map(lambda s: s.spec, shardings,
                                   is_leaf=lambda x: isinstance(
                                       x, NamedSharding))
    return opt_state, shardings, specs


def _local_shape(shape: tuple, spec: P, mesh: Mesh) -> tuple:
    """Per-device block shape of a global array under ``spec``."""
    out = list(shape)
    for d, entry in enumerate(tuple(spec)[:len(shape)]):
        if entry is None:
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        for a in names:
            size = mesh.shape[a]
            if out[d] % size:
                raise ValueError(
                    f"dim {d} of shape {shape} not divisible by mesh "
                    f"axis {a!r} (size {size}) — ZeRO's shard_map update "
                    f"needs exact divisibility")
            out[d] //= size
    return tuple(out)


def make_zero_update(tx, mesh: Mesh, param_specs, params_abstract, *,
                     axis_name: str = "dp",
                     bytes_per_pack: int = DEFAULT_BYTES_PER_PACK):
    """Build a ZeRO-sharded optimizer step for an arbitrary mesh.

    Returns ``(opt_state, opt_shardings, update_fn)`` where
    ``update_fn(params, grads, opt_state) -> (new_params,
    new_opt_state)`` is a shard_map over the whole mesh, callable from
    inside the caller's jitted train step. Parameters and gradients
    arrive as their mesh-local blocks (per ``param_specs`` — e.g.
    tp-sharded, pp-stage-sharded), the partition is over those LOCAL
    blocks, and only the ``axis_name`` (dp) dimension is ZeRO-sliced:
    each dp rank updates its 1/N of the local blocks and an all-gather
    over dp alone rebuilds them.

    Gradients must already be dp-synced (GSPMD's mean-objective grads,
    or the pipeline schedule's pmean over batch axes): they are sliced,
    never re-reduced. On a mesh without ``axis_name`` the partition is
    trivial (n_shards=1) and the update degenerates to a plain sharded
    optimizer step.
    """
    from distributed_tensorflow_tpu import telemetry

    leaves, treedef = jax.tree_util.tree_flatten(params_abstract)
    spec_leaves = jax.tree_util.tree_leaves(
        param_specs, is_leaf=lambda x: isinstance(x, P))
    if len(spec_leaves) != len(leaves):
        raise ValueError(f"{len(spec_leaves)} param specs for "
                         f"{len(leaves)} param leaves")
    local = [jax.ShapeDtypeStruct(_local_shape(l.shape, s, mesh), l.dtype)
             for l, s in zip(leaves, spec_leaves)]
    n_dp = mesh.shape.get(axis_name, 1)
    partition = ZeroPartition(local, n_dp, bytes_per_pack=bytes_per_pack)
    opt_state, opt_shardings, opt_specs = zero_opt_state(
        tx, partition, mesh)
    telemetry.event("zero.partition", axis=axis_name, **partition.summary())
    has_axis = axis_name in mesh.shape

    def local_update(params_loc, grads_loc, opt_loc):
        pl, td = jax.tree_util.tree_flatten(params_loc)
        gl = jax.tree_util.tree_leaves(grads_loc)
        rank = lax.axis_index(axis_name) if has_axis else 0
        p_shards = partition.shard(partition.pack(pl), rank)
        g_shards = partition.shard(partition.pack(gl), rank)
        updates, new_opt = tx.update(g_shards, opt_loc, p_shards)
        new_shards = optax.apply_updates(p_shards, updates)
        if has_axis:
            flats = partition.all_gather_flats(new_shards, axis_name)
        else:
            flats = new_shards
        new_params = jax.tree_util.tree_unflatten(
            td, partition.unpack(flats))
        return new_params, new_opt

    update_fn = jax.shard_map(
        local_update, mesh=mesh,
        in_specs=(param_specs, param_specs, opt_specs),
        out_specs=(param_specs, opt_specs),
        check_vma=False)
    return opt_state, opt_shardings, update_fn


def zero_state_bytes(n_params: int, n_shards: int, level: int,
                     *, param_bytes: int = 4, slot_bytes: int = 8,
                     grad_bytes: int = 4) -> int:
    """Analytic persistent+transient training-state bytes per device.

    Replicated (level 0): P*(param + grad + slot); ZeRO-1 shards the
    slots; ZeRO-2 shards the gradient buffer too. A closed form:
    ``tests/test_zero.py`` holds it against real shard shapes.
    """
    if level not in (0, 1, 2):
        raise ValueError(f"level must be 0, 1, or 2, got {level}")
    total = n_params * param_bytes
    total += (n_params * slot_bytes // n_shards if level >= 1
              else n_params * slot_bytes)
    total += (n_params * grad_bytes // n_shards if level >= 2
              else n_params * grad_bytes)
    return total
