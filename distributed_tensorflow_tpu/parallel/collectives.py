"""Typed collective operations lowered to XLA collectives over ICI/DCN.

TPU-native replacement of the reference's three-piece communication backend
(SURVEY.md §5.8): NCCL (core/nccl/nccl_manager.h), the C++ collective
framework (core/framework/collective.h — RingReducer et al.), and the grpc
remote-access plane. Here every collective is an XLA HLO op emitted inside a
single compiled SPMD program; the compiler picks the algorithm and schedules
it on ICI (or DCN across slices), so there is no runtime executor, no group /
instance-key rendezvous protocol, and no per-tensor RPC.

The six-type taxonomy mirrors the reference's ``CollectiveType`` enum
(reference: tensorflow/core/framework/collective.h:45-53):
REDUCTION, BROADCAST, GATHER, PERMUTE, ALL_TO_ALL, REDUCE_SCATTER.

Functions in this module must run inside an SPMD context that binds the mesh
axis name — i.e. under ``jax.shard_map`` (or ``Strategy.run``). Outside SPMD,
use ``cross_device_ops`` which wraps these in compiled programs.
"""

from __future__ import annotations

import enum
import dataclasses
import functools
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class CollectiveType(enum.Enum):
    """≙ tensorflow/core/framework/collective.h:45-53."""

    REDUCTION = "reduction"
    BROADCAST = "broadcast"
    GATHER = "gather"
    PERMUTE = "permute"
    ALL_TO_ALL = "all_to_all"
    REDUCE_SCATTER = "reduce_scatter"


class ReduceOp(enum.Enum):
    """≙ tf.distribute.ReduceOp plus the nccl_ops.py op set
    (all_sum/all_prod/all_min/all_max, nccl_ops.py:29+)."""

    SUM = "sum"
    MEAN = "mean"
    PROD = "prod"
    MIN = "min"
    MAX = "max"

    @classmethod
    def from_any(cls, op) -> "ReduceOp":
        if isinstance(op, cls):
            return op
        return cls(str(op).lower())


class CommunicationImplementation(enum.Enum):
    """≙ collective_util.py:41-43 ``CommunicationImplementation``.

    AUTO/RING/NCCL are kept for config compatibility; on TPU all three lower
    to XLA collectives — the compiler owns algorithm choice the way
    ``communication_hint`` used to pick RingReducer vs NcclManager. ICI is
    the honest TPU name for the fast path.
    """

    AUTO = "AUTO"
    RING = "RING"
    NCCL = "NCCL"
    ICI = "ICI"


@dataclasses.dataclass(frozen=True)
class CommunicationOptions:
    """≙ collective_util.Options (collective_util.py:117).

    ``bytes_per_pack`` feeds gradient-bucket packing in cross_device_ops
    (same role as CollectiveReplicaLauncher's pack-by-size,
    cross_device_utils.py:436-449); ``timeout_seconds`` maps to the
    coordination-service barrier timeout rather than a per-collective
    timeout, because in-program XLA collectives cannot individually time out.
    """

    bytes_per_pack: int = 0
    timeout_seconds: float | None = None
    implementation: CommunicationImplementation = CommunicationImplementation.AUTO

    def merge(self, other: "CommunicationOptions | None") -> "CommunicationOptions":
        """≙ collective_util.py:139 Options.merge."""
        if other is None:
            return self
        return CommunicationOptions(
            bytes_per_pack=other.bytes_per_pack or self.bytes_per_pack,
            timeout_seconds=(other.timeout_seconds
                             if other.timeout_seconds is not None
                             else self.timeout_seconds),
            implementation=(other.implementation
                            if other.implementation
                            is not CommunicationImplementation.AUTO
                            else self.implementation),
        )


class CollectiveKeys:
    """Group/instance key bookkeeping (≙ cross_device_utils.py:173).

    XLA needs no instance keys — collective matching is positional within the
    single program — but the coordinator/PS path still uses keys to name
    host-side rendezvous (e.g. per-variable update channels), and tests use
    them to assert launch ordering, so the bookkeeping survives.
    """

    def __init__(self, group_key_start: int = 1):
        self._group_key = group_key_start
        self._instance_keys: dict[int, int] = {}
        self._lock = threading.Lock()

    def get_group_key(self, devices: Sequence) -> int:
        with self._lock:
            key = self._group_key
            self._group_key += 1
            self._instance_keys[key] = 0
            return key

    def get_instance_key(self, group_key: int) -> int:
        with self._lock:
            if group_key not in self._instance_keys:
                raise ValueError(f"Unknown group key {group_key}")
            self._instance_keys[group_key] += 1
            return self._instance_keys[group_key]


# ---------------------------------------------------------------------------
# In-SPMD collective functions (must be called under an axis binding).
# These are the op surface ≙ tensorflow/python/ops/collective_ops.py and
# tensorflow/python/tpu/ops/tpu_ops.py (SURVEY §2.2/§2.6), lowered to XLA.
# ---------------------------------------------------------------------------

AxisName = str | Sequence[str]


def all_reduce(x, axis_name: AxisName, op: ReduceOp | str = ReduceOp.SUM):
    """REDUCTION: ≙ collective_ops.all_reduce_v2 (collective_ops.py:95),
    nccl_ops.all_sum (nccl_ops.py:29), tpu_ops.cross_replica_sum
    (tpu_ops.py:92). Lowers to HLO AllReduce on ICI."""
    op = ReduceOp.from_any(op)
    if op is ReduceOp.SUM:
        return lax.psum(x, axis_name)
    if op is ReduceOp.MEAN:
        return lax.pmean(x, axis_name)
    if op is ReduceOp.MAX:
        return lax.pmax(x, axis_name)
    if op is ReduceOp.MIN:
        return lax.pmin(x, axis_name)
    if op is ReduceOp.PROD:
        # no pprod primitive; gather contributions and multiply (correct for
        # zero/negative values, unlike the log-sum-exp trick)
        gathered = lax.all_gather(x, axis_name, axis=0, tiled=False)
        return jnp.prod(gathered, axis=0)
    raise ValueError(f"Unsupported reduce op {op}")


def all_gather(x, axis_name: AxisName, axis: int = 0, tiled: bool = True):
    """GATHER: ≙ collective_ops.all_gather_v2 (collective_ops.py:200).
    ``tiled=True`` concatenates along ``axis`` (TF semantics); ``False``
    stacks a fresh leading axis."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: AxisName, axis: int = 0,
                   op: ReduceOp | str = ReduceOp.SUM):
    """REDUCE_SCATTER: ≙ CollectiveType::REDUCE_SCATTER (collective.h:53).
    The building block of FSDP gradient sync."""
    op = ReduceOp.from_any(op)
    if op not in (ReduceOp.SUM, ReduceOp.MEAN):
        raise ValueError("reduce_scatter supports SUM and MEAN")
    out = lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)
    if op is ReduceOp.MEAN:
        out = out / lax.psum(1, axis_name)
    return out


def combined_axis_index(axis_names: AxisName):
    """Row-major flat index over one or several mesh axes."""
    if isinstance(axis_names, str):
        return lax.axis_index(axis_names)
    idx = 0
    for name in axis_names:
        idx = idx * lax.axis_size(name) + lax.axis_index(name)
    return idx


def broadcast(x, axis_name: AxisName, source: int = 0):
    """BROADCAST: ≙ collective_ops.broadcast_send_v2/recv_v2
    (collective_ops.py:314/:392). One source shard wins; implemented as a
    masked psum so it stays a single fused collective."""
    idx = combined_axis_index(axis_name)
    mask = (idx == source).astype(x.dtype)
    return lax.psum(x * mask, axis_name)


def permute(x, axis_name: str, perm: Sequence[tuple[int, int]]):
    """PERMUTE: ≙ tpu_ops.collective_permute (tpu_ops.py:111) /
    core Permuter (permuter.h). ``perm`` is (source, dest) pairs; devices not
    named as a dest receive zeros."""
    return lax.ppermute(x, axis_name, perm=list(perm))


def permute_shift(x, axis_name: str, shift: int = 1):
    """Ring shift helper built on PERMUTE — the ring-attention data motion."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm=perm)


def all_to_all(x, axis_name: AxisName, split_axis: int, concat_axis: int,
               tiled: bool = True):
    """ALL_TO_ALL: ≙ collective_ops.all_to_all_v2 (collective_ops.py:501),
    tpu_ops.all_to_all (tpu_ops.py:43). The Ulysses sequence<->head
    re-sharding primitive."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def axis_index(axis_name: AxisName):
    """Replica id along ``axis_name`` (≙ replica_id_in_sync_group)."""
    return lax.axis_index(axis_name)


def axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
# Hierarchical reduction (≙ HierarchicalCopyAllReduce, cross_device_ops.py:997
# and _build_nccl_hybrid, v1/all_reduce.py:710).
# ---------------------------------------------------------------------------

def _hierarchical_flat(flat, inner_axis: str, outer_axis: str):
    """scatter(inner) -> reduce(outer) -> gather(inner) on a 1-D vector."""
    size = flat.shape[0]
    n_inner = lax.axis_size(inner_axis)
    pad = (-size) % n_inner
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = lax.psum_scatter(flat, inner_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, outer_axis)
    full = lax.all_gather(shard, inner_axis, axis=0, tiled=True)
    return full[:size]


def hierarchical_all_reduce(x, inner_axis: str, outer_axis: str,
                            op: ReduceOp | str = ReduceOp.SUM,
                            *, chunks: int = 1):
    """Two-level allreduce: reduce-scatter on the fast inner axis (ICI),
    allreduce the shard on the slow outer axis (DCN), all-gather back on the
    inner axis.

    This is the TPU-native form of the reference's hierarchical GPU reduce
    (cross_device_utils.py:55 ``aggregate_gradients_using_hierarchical_copy``
    with its hard-coded 2-group DMA topology, and the NCCL-hybrid graph
    builder v1/all_reduce.py:710): each DCN hop moves only 1/|inner| of the
    bytes. XLA emits the same decomposition for a flat psum over both axes on
    multi-slice topologies, but the explicit form lets the Transformer
    2-slice config (BASELINE.md #5) control it and lets tests assert the
    traffic split.

    ``chunks > 1`` splits the vector into that many independent
    scatter->reduce->gather chains, so the slow outer (DCN) hop of chunk
    *i* can overlap the fast inner (ICI) phases of chunk *i+1* instead of
    the three phases serializing end-to-end — async dispatch across the
    hybrid mesh. The per-element arithmetic is unchanged (chunking only
    partitions the vector), so results are bit-identical to ``chunks=1``.
    """
    op = ReduceOp.from_any(op)
    orig_shape = x.shape
    orig_size = x.size
    flat = x.reshape(-1)
    chunks = max(1, min(int(chunks), orig_size or 1))
    if chunks == 1:
        full = _hierarchical_flat(flat, inner_axis, outer_axis)
    else:
        seg = -(-orig_size // chunks)          # ceil division
        parts = [flat[i * seg:(i + 1) * seg] for i in range(chunks)]
        full = jnp.concatenate(
            [_hierarchical_flat(p, inner_axis, outer_axis)
             for p in parts if p.shape[0]])
    out = full.reshape(orig_shape)
    if op is ReduceOp.MEAN:
        out = out / (lax.axis_size(inner_axis) * lax.axis_size(outer_axis))
    elif op is not ReduceOp.SUM:
        raise ValueError("hierarchical_all_reduce supports SUM and MEAN")
    return out


# ---------------------------------------------------------------------------
# Reverse-order bucketed gradient collectives (≙ the reference's
# NcclAllReduce gradient packing: CollectiveReplicaLauncher pack-by-size,
# cross_device_utils.py:436-449 / group_by_size :679 — plus Horovod-style
# fusion-buffer scheduling in reverse layer order).
# ---------------------------------------------------------------------------

# Default fusion-buffer size when packing is enabled but unconfigured
# (CommunicationOptions.bytes_per_pack == 0). Same order of magnitude as
# Horovod's 64 MB fusion buffer / DDP's 25 MB bucket, sized down for the
# smaller per-bucket latency of ICI.
DEFAULT_BYTES_PER_PACK = 4 * 1024 * 1024


def plan_buckets(sizes: Sequence[int], dtypes: Sequence,
                 bytes_per_pack: int, *, reverse: bool = False
                 ) -> list[list[int]]:
    """Greedy size-bucketing of flattened-tensor indices.

    Buckets NEVER mix dtypes: concatenating bf16 and f32 leaves into one
    buffer would silently upcast (and double the bf16 wire bytes), so a
    dtype change always closes the current bucket. A bucket closes once
    its byte count reaches ``bytes_per_pack`` — a leaf landing exactly on
    the boundary is included and the next leaf starts a fresh bucket.
    ``bytes_per_pack=0`` packs everything (per dtype run) into one bucket.

    ``reverse=True`` emits buckets in reverse leaf order — last-layer
    gradients are produced FIRST by backprop, so their bucket's collective
    can launch while earlier layers are still differentiating (the
    Horovod/DDP overlap idiom; the reference gets the same effect from its
    gradient tape firing allreduces in completion order).
    """
    n = len(sizes)
    order = range(n - 1, -1, -1) if reverse else range(n)
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    cur_dtype = None
    for i in order:
        dt = jnp.dtype(dtypes[i])
        if cur and dt != cur_dtype:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_dtype = dt
        cur_bytes += int(sizes[i]) * dt.itemsize
        if bytes_per_pack and cur_bytes >= bytes_per_pack:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return buckets


class GradientBucketer:
    """Packs a gradient pytree into size-bounded single-dtype buckets and
    reduces each bucket as ONE collective, scheduled in reverse layer
    order so reduction overlaps the remaining backward pass.

    Must run inside an SPMD context binding ``axis_names`` (shard_map /
    Strategy.run). On a hybrid mesh pass ``outer_axis``/``inner_axis``
    (e.g. "dcn"/"dp"): each bucket then takes the hierarchical
    scatter->DCN-reduce->gather path, and because buckets are independent
    chains the DCN hop of one bucket overlaps the ICI phases of the next
    (the async hybrid dispatch of ISSUE 6).

    Equivalent wire behavior to the reference's
    ``CollectiveReplicaLauncher`` pack path (cross_device_utils.py:436);
    results are bit-identical to per-leaf ``psum`` — packing concatenates
    buffers but never changes any element's reduction.
    """

    def __init__(self, axis_names: AxisName,
                 *, bytes_per_pack: int = DEFAULT_BYTES_PER_PACK,
                 reverse: bool = True,
                 outer_axis: str | None = None,
                 inner_axis: str | None = None):
        self.axis_names = ((axis_names,) if isinstance(axis_names, str)
                           else tuple(axis_names))
        self.bytes_per_pack = int(bytes_per_pack)
        self.reverse = bool(reverse)
        if (outer_axis is None) != (inner_axis is None):
            raise ValueError("outer_axis and inner_axis must be set "
                             "together (hybrid mesh) or both omitted")
        self.outer_axis = outer_axis
        self.inner_axis = inner_axis

    def plan(self, leaves: Sequence) -> list[list[int]]:
        sizes = [int(np.prod(jnp.shape(x))) if jnp.shape(x) else 1
                 for x in leaves]
        dtypes = [jnp.result_type(x) for x in leaves]
        return plan_buckets(sizes, dtypes, self.bytes_per_pack,
                            reverse=self.reverse)

    def _reduce_flat(self, flat, op: ReduceOp):
        if self.outer_axis is not None:
            return hierarchical_all_reduce(
                flat, inner_axis=self.inner_axis,
                outer_axis=self.outer_axis, op=op)
        return all_reduce(flat, self.axis_names, op)

    def plan_summary(self, leaves: Sequence) -> "list[dict]":
        """Human/bench-readable view of :meth:`plan`: one dict per
        bucket with ``{"leaves": n, "bytes": b, "dtype": name}`` in
        launch order. ``tools/trace_report.py`` and bench rows report
        these so the overlap numbers can be checked against the actual
        bucket schedule."""
        sizes = [int(np.prod(jnp.shape(x))) if jnp.shape(x) else 1
                 for x in leaves]
        dtypes = [jnp.result_type(x) for x in leaves]
        out = []
        for bucket in plan_buckets(sizes, dtypes, self.bytes_per_pack,
                                   reverse=self.reverse):
            dt = jnp.dtype(dtypes[bucket[0]])
            out.append({"leaves": len(bucket),
                        "bytes": sum(sizes[i] * dt.itemsize
                                     for i in bucket),
                        "dtype": dt.name})
        return out

    def all_reduce(self, tree, op: ReduceOp | str = ReduceOp.SUM):
        """Bucketed allreduce of a pytree (the gradient-sync shape)."""
        op = ReduceOp.from_any(op)
        if op not in (ReduceOp.SUM, ReduceOp.MEAN):
            raise ValueError("GradientBucketer supports SUM and MEAN")
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        out: list = [None] * len(leaves)
        for bucket in self.plan(leaves):
            flat = jnp.concatenate(
                [jnp.ravel(jnp.asarray(leaves[i])) for i in bucket])
            reduced = self._reduce_flat(flat, op)
            off = 0
            for i in bucket:
                shape = jnp.shape(leaves[i])
                size = int(np.prod(shape)) if shape else 1
                out[i] = jnp.reshape(reduced[off:off + size], shape)
                off += size
        return jax.tree_util.tree_unflatten(treedef, out)


def simulate_overlap(ready_s: Sequence[float], dur_s: Sequence[float],
                     backward_end_s: float | None = None) -> dict:
    """Model the overlapped bucket schedule and account its win.

    ``ready_s[i]`` is when backprop has produced bucket *i*'s gradients
    (so its collective may launch); ``dur_s[i]`` is that bucket's
    reduction time. Buckets run on ONE communication channel in launch
    order (the wire serializes), each starting at
    ``max(ready, previous bucket's finish)`` — the Horovod/DDP fusion
    buffer model. ``backward_end_s`` defaults to the last ready time.

    Returns::

        {"serial_s":   sum of dur_s (what an unoverlapped tail sync
                       would add to the step),
         "exposed_s":  how far the last bucket finishes past the end of
                       backward — the part that actually extends the
                       critical path,
         "overlap_eff": 1 - exposed/serial (1.0 = fully hidden),
         "finish_s":   per-bucket finish times}

    This is the hand-checkable counterpart of the *measured* overlap
    efficiency (the device trace's collective time); tests pin this
    model against a hand-computed 2-bucket schedule.
    """
    if len(ready_s) != len(dur_s):
        raise ValueError(f"{len(ready_s)} ready times vs "
                         f"{len(dur_s)} durations")
    finish: list[float] = []
    t = 0.0
    for ready, dur in zip(ready_s, dur_s):
        t = max(float(ready), t) + float(dur)
        finish.append(t)
    serial = float(sum(dur_s))
    bwd_end = (float(backward_end_s) if backward_end_s is not None
               else (max(ready_s) if ready_s else 0.0))
    exposed = max(0.0, (finish[-1] if finish else 0.0) - bwd_end)
    eff = None
    if serial > 0:
        eff = max(0.0, min(1.0, 1.0 - exposed / serial))
    return {"serial_s": serial, "exposed_s": exposed,
            "overlap_eff": eff, "finish_s": finish}


# ---------------------------------------------------------------------------
# Host-level compiled collectives over a mesh (outside SPMD).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _compiled_mesh_reduce(mesh, axis_names: tuple, op: ReduceOp):
    from jax.sharding import PartitionSpec as P

    def f(x):
        return all_reduce(x, axis_names, op)

    return jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P(axis_names), out_specs=P(),
        check_vma=False))


def mesh_all_reduce(mesh, x, axis_names: Sequence[str] | str,
                    op: ReduceOp | str = ReduceOp.SUM):
    """Reduce a host array whose leading axis spans ``axis_names`` of
    ``mesh``. Used by CrossDeviceOps for eager-style reductions."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    return _compiled_mesh_reduce(mesh, tuple(axis_names),
                                 ReduceOp.from_any(op))(x)
