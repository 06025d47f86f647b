"""TPU topology, device assignment, and mesh construction.

TPU-native redesign of the reference's topology layer
(reference: tensorflow/python/tpu/topology.py:41 ``Topology``,
tensorflow/python/tpu/device_assignment.py:70 ``DeviceAssignment``, per
SURVEY.md §2.6). Instead of mapping logical replicas onto physical cores by
hand-building ring orders for the torus, the TPU-native design delegates
device ordering to ``jax.make_mesh`` (which knows the ICI fabric) and exposes
the result as a ``jax.sharding.Mesh`` — the single object every parallelism
axis (dp/fsdp/tp/sp/pp/ep) hangs off.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical logical axis names, in priority order. Outer axes are the ones
# whose collectives tolerate lower bandwidth (DCN), inner axes want ICI.
DATA_AXIS = "dp"          # data parallel (gradient allreduce)
FSDP_AXIS = "fsdp"        # fully-sharded data parallel (param all-gather)
TENSOR_AXIS = "tp"        # tensor/model parallel (activation collectives)
SEQUENCE_AXIS = "sp"      # sequence/context parallel (ring attention)
PIPELINE_AXIS = "pp"      # pipeline parallel (ppermute between stages)
EXPERT_AXIS = "ep"        # expert parallel (all_to_all dispatch)

ALL_AXES = (DATA_AXIS, FSDP_AXIS, TENSOR_AXIS, SEQUENCE_AXIS, PIPELINE_AXIS,
            EXPERT_AXIS)


@dataclasses.dataclass(frozen=True)
class Topology:
    """Physical accelerator topology of the current job.

    Counterpart of ``tf.tpu.experimental.Topology``
    (reference: tensorflow/python/tpu/topology.py:41): the reference
    deserializes a TopologyProto returned by the ``ConfigureDistributedTPU``
    op; here the information comes straight from the PJRT client
    (``jax.devices()``), which already reflects libtpu's view of the slice.
    """

    devices: tuple  # all global devices, PJRT enumeration order
    num_processes: int
    process_index: int
    platform: str

    @classmethod
    def detect(cls, devices: Sequence | None = None) -> "Topology":
        devices = tuple(devices if devices is not None else jax.devices())
        return cls(
            devices=devices,
            num_processes=jax.process_count(),
            process_index=jax.process_index(),
            platform=devices[0].platform if devices else "none",
        )

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    @property
    def num_devices_per_process(self) -> int:
        return max(1, self.num_devices // max(1, self.num_processes))

    def local_devices(self) -> list:
        return [d for d in self.devices
                if getattr(d, "process_index", 0) == self.process_index]

    @property
    def mesh_shape(self) -> tuple:
        """Physical mesh shape (x, y, z, core) when the backend reports
        coords; falls back to a flat (num_devices,) shape on CPU/GPU."""
        coords = [getattr(d, "coords", None) for d in self.devices]
        if any(c is None for c in coords):
            return (self.num_devices,)
        dims = tuple(max(c[i] for c in coords) + 1 for i in range(len(coords[0])))
        cores = max(getattr(d, "core_on_chip", 0) for d in self.devices) + 1
        return dims + (cores,)


@dataclasses.dataclass(frozen=True)
class DeviceAssignment:
    """Maps logical replicas to physical devices.

    Counterpart of tensorflow/python/tpu/device_assignment.py:70. The
    reference computes per-replica core rings (``_ring_3d``,
    device_assignment.py:241) because TF's TPUStrategy launches one program
    per replica; under single-program SPMD the assignment degenerates to "in
    which mesh position does each logical replica live", which is what this
    class records. Kept as an explicit object for API parity and for the
    coordinator/PS path, which still addresses individual devices.
    """

    topology: Topology
    num_replicas: int
    num_cores_per_replica: int = 1

    @classmethod
    def build(cls, topology: Topology | None = None,
              num_replicas: int | None = None,
              num_cores_per_replica: int = 1) -> "DeviceAssignment":
        topology = topology or Topology.detect()
        if num_replicas is None:
            num_replicas = topology.num_devices // num_cores_per_replica
        if num_replicas * num_cores_per_replica > topology.num_devices:
            raise ValueError(
                f"Requested {num_replicas} replicas x {num_cores_per_replica} "
                f"cores > {topology.num_devices} devices")
        return cls(topology, num_replicas, num_cores_per_replica)

    def device(self, replica: int, logical_core: int = 0):
        idx = replica * self.num_cores_per_replica + logical_core
        return self.topology.devices[idx]

    def replica_devices(self, replica: int) -> list:
        base = replica * self.num_cores_per_replica
        return list(self.topology.devices[base:base + self.num_cores_per_replica])


def _normalize_axes(axes, num_devices: int):
    """Resolve an axis spec into (names, sizes), filling one -1 wildcard."""
    if isinstance(axes, Mapping):
        names = tuple(axes.keys())
        sizes = list(axes.values())
    else:
        names, sizes = zip(*axes)
        sizes = list(sizes)
    wild = [i for i, s in enumerate(sizes) if s == -1]
    if len(wild) > 1:
        raise ValueError("At most one axis size may be -1")
    if wild:
        known = math.prod(s for s in sizes if s != -1)
        if num_devices % known:
            raise ValueError(
                f"{num_devices} devices not divisible by fixed axes {known}")
        sizes[wild[0]] = num_devices // known
    if math.prod(sizes) != num_devices:
        raise ValueError(
            f"Mesh axes {dict(zip(names, sizes))} need {math.prod(sizes)} "
            f"devices but {num_devices} are available")
    return names, tuple(sizes)


def make_mesh(axes: Mapping[str, int] | Sequence[tuple] | None = None,
              *, devices: Sequence | None = None) -> Mesh:
    """Build a ``jax.sharding.Mesh`` over the slice.

    ``axes`` maps logical axis name -> size, e.g. ``{"dp": 4, "tp": 2}``;
    one size may be ``-1`` (inferred). Defaults to pure data parallelism over
    every device. Axis order is semantic: earlier axes are "outer" (their
    collectives cross the slower links on multi-host topologies), later axes
    are "inner" (mapped to the fastest ICI neighbourhoods by
    ``jax.make_mesh``'s device ordering).

    This replaces the reference's hand-built core rings
    (tensorflow/python/tpu/device_assignment.py:343) with the mesh-first
    design XLA GSPMD expects.
    """
    devs = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {DATA_AXIS: len(devs)}
    names, sizes = _normalize_axes(axes, len(devs))
    # Auto axis types: the framework works in GSPMD mode (sharding
    # constraints + propagation), not the explicit-sharding-in-types mode.
    axis_types = (jax.sharding.AxisType.Auto,) * len(names)
    if devices is None:
        # jax.make_mesh picks the ICI-aware device order; a shape it
        # cannot lay out raises rather than silently taking enumeration
        # order (which changes which chips are neighbours).
        return jax.make_mesh(sizes, names, axis_types=axis_types)
    # explicit devices: the caller chose the order
    arr = np.asarray(devs, dtype=object).reshape(sizes)
    return Mesh(arr, names, axis_types=axis_types)


def mesh_axis_size(mesh: Mesh, *names: str) -> int:
    """Product of the sizes of ``names`` that exist on ``mesh``."""
    return math.prod(mesh.shape[n] for n in names if n in mesh.shape)


DCN_AXIS = "dcn"

# Axes over which input batches shard, outermost first. Every model's
# batch sharding and shard_map spec must derive from this one list.
DATA_AXES = (DCN_AXIS, DATA_AXIS, FSDP_AXIS)


def data_axes(mesh: Mesh) -> tuple:
    """The subset of DATA_AXES present on ``mesh`` (batch-sharding axes)."""
    return tuple(a for a in DATA_AXES if a in mesh.shape)


def attention_shard_spec(mesh: Mesh):
    """PartitionSpec for (batch, heads, seq, head_dim) attention operands:
    batch over the data axes, heads over tp, seq/head_dim unsharded.
    Single source of truth for every attention entry point (dense
    shard_map path and the sp ring/ulysses path)."""
    from jax.sharding import PartitionSpec as P
    batch_axes = data_axes(mesh)
    head_axis = TENSOR_AXIS if TENSOR_AXIS in mesh.shape else None
    return P(batch_axes if batch_axes else None, head_axis, None, None)


def make_hybrid_mesh(dcn_axes: Mapping[str, int],
                     ici_axes: Mapping[str, int],
                     *, devices: Sequence | None = None) -> Mesh:
    """Mesh spanning multiple TPU slices: ``dcn_axes`` cross the
    data-center network (slow, between slices), ``ici_axes`` stay inside
    a slice (fast). ≙ the reference's two-level
    ``HierarchicalCopyAllReduce`` / ``_build_nccl_hybrid`` topology split
    (reference: tensorflow/python/distribute/cross_device_ops.py:997,
    v1/all_reduce.py:710) — but expressed once in the mesh, so every
    collective GSPMD inserts is automatically hierarchical: reduce-scatter
    inside the slice over ICI, small cross-slice reduce over DCN.

    On real multi-slice TPU, uses ``mesh_utils.create_hybrid_device_mesh``
    (slice boundaries from PJRT); elsewhere (CPU testing, single slice)
    devices are grouped contiguously, outer axes slowest-varying — the
    same comm hierarchy shape without physical DCN.
    """
    from jax.experimental import mesh_utils

    if -1 in dcn_axes.values() and -1 in ici_axes.values():
        raise ValueError("only one -1 wildcard allowed across "
                         "dcn_axes + ici_axes")
    devs = list(devices if devices is not None else jax.devices())
    dcn_names, dcn_sizes = _normalize_axes(dcn_axes, math.prod(
        dcn_axes.values()) if -1 not in dcn_axes.values() else len(devs)
        // math.prod(ici_axes.values()))
    ici_names, ici_sizes = _normalize_axes(
        ici_axes, len(devs) // math.prod(dcn_sizes))
    names = dcn_names + ici_names
    axis_types = (jax.sharding.AxisType.Auto,) * len(names)

    multi_slice = len({getattr(d, "slice_index", 0) for d in devs}) > 1
    if multi_slice:
        # create_hybrid_device_mesh combines shapes elementwise, so pad
        # with 1s to keep the dcn axes distinct from the ici axes.
        ici_shape = (1,) * len(dcn_sizes) + tuple(ici_sizes)
        dcn_shape = tuple(dcn_sizes) + (1,) * len(ici_sizes)
        arr = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devs)
        return Mesh(arr, names, axis_types=axis_types)
    arr = np.asarray(devs, dtype=object).reshape(dcn_sizes + ici_sizes)
    return Mesh(arr, names, axis_types=axis_types)
