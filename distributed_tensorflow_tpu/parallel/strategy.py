"""Strategy API: scope / run / reduce over a device mesh.

TPU-native counterpart of tensorflow/python/distribute/distribute_lib.py
(SURVEY.md §2.1): ``Strategy`` (:2026), ``StrategyExtendedV2`` (:2394),
``ReplicaContext`` (:3670), ``Strategy.run`` (:1557), ``reduce`` (:1675),
``scope`` (:1223).

Design shift (SURVEY §7 "Design stance"): the reference's MirroredStrategy
runs one *Python thread per device* with a ``merge_call`` rendezvous
(mirrored_run.py:289) and the grpc worker service moves tensors between
processes. Here ``Strategy.run`` traces the replica function ONCE under
``jax.shard_map`` over the mesh's data axes and compiles a single SPMD
program — the model the reference's own TPUStrategy uses (SURVEY §3.4),
generalized to every strategy. Cross-replica communication inside ``run`` is
an XLA collective; there are no replica threads, no rendezvous, no
per-tensor RPC.

Two ways to use a strategy:

1. **TF-parity path** — ``scope()`` + ``Variable`` + ``run`` + ``reduce``
   with implicit variable capture/write-back, matching tf.distribute
   semantics for porting reference-style training scripts.
2. **Native path** — explicit functional state: ``init_state`` /
   ``compile_step`` return jit-compiled SPMD steps over pytrees (flax/optax
   style). This is the benchmark hot path.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.cluster import topology as topo_lib
from distributed_tensorflow_tpu.parallel import collectives
from distributed_tensorflow_tpu.parallel.collectives import (
    CommunicationOptions,
    ReduceOp,
)
from distributed_tensorflow_tpu.parallel.cross_device_ops import (
    CrossDeviceOps,
    select_cross_device_ops,
)
from distributed_tensorflow_tpu.parallel.values import (
    DistributedValues,
    DistributedVariable,
    Mirrored,
    MirroredVariable,
    PerReplica,
    SyncOnReadVariable,
    VariableAggregation,
    VariableSynchronization,
)

# ---------------------------------------------------------------------------
# Context plumbing (≙ distribution_strategy_context)
# ---------------------------------------------------------------------------

_CTX = threading.local()


def _strategy_stack() -> list:
    if not hasattr(_CTX, "stack"):
        _CTX.stack = []
    return _CTX.stack


def get_strategy() -> "Strategy":
    stack = _strategy_stack()
    if not stack:
        raise RuntimeError("No strategy in scope; use `with strategy.scope():`")
    return stack[-1]


def has_strategy() -> bool:
    return bool(_strategy_stack())


def get_replica_context() -> "ReplicaContext | None":
    return getattr(_CTX, "replica_context", None)


def in_cross_replica_context() -> bool:
    return has_strategy() and get_replica_context() is None


@contextlib.contextmanager
def _replica_context(ctx: "ReplicaContext | None"):
    prev = getattr(_CTX, "replica_context", None)
    _CTX.replica_context = ctx
    try:
        yield
    finally:
        _CTX.replica_context = prev


# Traced-variable overlay: while an SPMD `run` is being traced, variable
# reads/writes resolve against traced values instead of the host arrays.
# This is the single mechanism replacing TF's FuncGraph variable capture.

@contextlib.contextmanager
def _variable_overlay(overlay: dict):
    prev = getattr(_CTX, "var_overlay", None)
    _CTX.var_overlay = overlay
    try:
        yield
    finally:
        _CTX.var_overlay = prev


def _current_overlay() -> dict | None:
    return getattr(_CTX, "var_overlay", None)


# Patch DistributedVariable read/write paths to consult the overlay.
_orig_value = DistributedVariable.value.fget
_orig_read_value = DistributedVariable.read_value
_orig_assign = DistributedVariable.assign


def _overlay_value(self):
    ov = _current_overlay()
    if ov is not None and id(self) in ov:
        return ov[id(self)]
    return _orig_value(self)


def _overlay_read_value(self):
    ov = _current_overlay()
    if ov is not None and id(self) in ov:
        return ov[id(self)]
    return _orig_read_value(self)


def _overlay_assign(self, value):
    ov = _current_overlay()
    if ov is not None and id(self) in ov:
        ov[id(self)] = jnp.asarray(value, dtype=self.dtype)
        return self
    return _orig_assign(self, value)


def _overlay_assign_add(self, delta):
    ov = _current_overlay()
    if ov is not None and id(self) in ov:
        ov[id(self)] = ov[id(self)] + jnp.asarray(delta, dtype=self.dtype)
        return self
    return _orig_assign(self, _orig_value(self) + jnp.asarray(delta, self.dtype))


def _overlay_assign_sub(self, delta):
    ov = _current_overlay()
    if ov is not None and id(self) in ov:
        ov[id(self)] = ov[id(self)] - jnp.asarray(delta, dtype=self.dtype)
        return self
    return _orig_assign(self, _orig_value(self) - jnp.asarray(delta, self.dtype))


DistributedVariable.value = property(_overlay_value)
DistributedVariable.read_value = _overlay_read_value
DistributedVariable.assign = _overlay_assign
DistributedVariable.assign_add = _overlay_assign_add
DistributedVariable.assign_sub = _overlay_assign_sub


# ---------------------------------------------------------------------------
# ReplicaContext
# ---------------------------------------------------------------------------

class ReplicaContext:
    """Per-replica API inside ``Strategy.run`` (≙ distribute_lib.py:3670).

    Collectives lower to XLA HLO over the bound mesh axes. ``merge_call``
    exists for optimizer-compatibility: under SPMD there are no replica
    threads to rendezvous (mirrored_run.py:433's parked-thread dance), so it
    simply runs ``fn`` in cross-replica context — reductions inside become
    in-program collectives. This is exactly TF's own `_use_merge_call=False`
    escape hatch made the default (mirrored_strategy.py:351).
    """

    def __init__(self, strategy: "Strategy", axis_names: tuple):
        self.strategy = strategy
        self._axis_names = axis_names

    @property
    def num_replicas_in_sync(self) -> int:
        return self.strategy.num_replicas_in_sync

    @property
    def replica_id_in_sync_group(self):
        idx = 0
        for name in self._axis_names:
            idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
        return idx

    def all_reduce(self, reduce_op, value, options=None):
        op = ReduceOp.from_any(reduce_op)
        return jax.tree_util.tree_map(
            lambda v: collectives.all_reduce(v, self._axis_names, op), value)

    def all_gather(self, value, axis: int = 0, options=None):
        return jax.tree_util.tree_map(
            lambda v: collectives.all_gather(v, self._axis_names, axis=axis),
            value)

    def reduce_scatter(self, value, axis: int = 0, reduce_op=ReduceOp.SUM):
        op = ReduceOp.from_any(reduce_op)
        return jax.tree_util.tree_map(
            lambda v: collectives.reduce_scatter(v, self._axis_names,
                                                 axis=axis, op=op), value)

    def collective_permute(self, value, perm):
        if len(self._axis_names) != 1:
            raise ValueError("collective_permute needs a single replica axis")
        return jax.tree_util.tree_map(
            lambda v: collectives.permute(v, self._axis_names[0], perm), value)

    def all_to_all(self, value, split_axis: int, concat_axis: int):
        return jax.tree_util.tree_map(
            lambda v: collectives.all_to_all(
                v, self._axis_names, split_axis=split_axis,
                concat_axis=concat_axis), value)

    def merge_call(self, merge_fn: Callable, args=(), kwargs=None):
        with _replica_context(None):
            return merge_fn(self.strategy, *args, **(kwargs or {}))


# ---------------------------------------------------------------------------
# StrategyExtended (parity shim)
# ---------------------------------------------------------------------------

class StrategyExtended:
    """≙ StrategyExtendedV2 (distribute_lib.py:2394) — the lower-level API
    Keras-style integrations call."""

    def __init__(self, strategy: "Strategy"):
        self._strategy = strategy

    @property
    def worker_devices(self) -> tuple:
        return tuple(self._strategy.replica_devices)

    @property
    def parameter_devices(self) -> tuple:
        return tuple(self._strategy.replica_devices)

    def reduce_to(self, reduce_op, value, destinations=None, options=None):
        """In replica tracing: lowers to an in-program collective. On host:
        delegates to cross_device_ops."""
        op = ReduceOp.from_any(reduce_op)
        if _current_overlay() is not None or _in_spmd_trace():
            return jax.tree_util.tree_map(
                lambda v: collectives.all_reduce(
                    v, self._strategy.data_axis_names, op), value)
        return self._strategy.cross_device_ops.reduce(op, value,
                                                      options=options)

    def batch_reduce_to(self, reduce_op, value_and_destination_pairs,
                        options=None):
        return [self.reduce_to(reduce_op, v, d, options)
                for v, d in value_and_destination_pairs]

    def call_for_each_replica(self, fn, args=(), kwargs=None):
        return self._strategy.run(fn, args=args, kwargs=kwargs)

    def variable_created_in_scope(self, v) -> bool:
        return any(v is var for var in self._strategy.variables)

    def update(self, var: DistributedVariable, fn, args=(), kwargs=None):
        """≙ StrategyExtended.update: apply ``fn(var, *args)`` once, in
        cross-replica context."""
        with _replica_context(None):
            return fn(var, *args, **(kwargs or {}))


def _in_spmd_trace() -> bool:
    return bool(getattr(_CTX, "in_spmd", False))


@contextlib.contextmanager
def _spmd_trace():
    prev = getattr(_CTX, "in_spmd", False)
    _CTX.in_spmd = True
    try:
        yield
    finally:
        _CTX.in_spmd = prev


# ---------------------------------------------------------------------------
# Strategy
# ---------------------------------------------------------------------------

class Strategy:
    """Base distribution strategy over a ``jax.sharding.Mesh``.

    ≙ tf.distribute.Strategy (distribute_lib.py:2026). Subclasses configure
    the mesh and axis roles; the run/reduce machinery is shared. ``mesh`` may
    have axes beyond the data axes (tp/sp/pp) — ``run`` replicates over
    those by default and model code shards over them with explicit specs.
    """

    def __init__(self, mesh: Mesh | None = None,
                 data_axis_names: Sequence[str] = (topo_lib.DATA_AXIS,),
                 cross_device_ops: CrossDeviceOps | None = None,
                 communication_options: CommunicationOptions | None = None):
        if mesh is None:
            mesh = topo_lib.make_mesh()
        self.mesh = mesh
        self.data_axis_names = tuple(
            a for a in data_axis_names if a in mesh.shape)
        if not self.data_axis_names:
            self.data_axis_names = tuple(mesh.axis_names[:1])
        self.communication_options = (communication_options
                                      or CommunicationOptions())
        self.cross_device_ops = cross_device_ops or select_cross_device_ops(
            mesh, self.data_axis_names, communication_options)
        self.extended = StrategyExtended(self)
        self._variables: list[DistributedVariable] = []
        # Bounded LRU of compiled run() programs. The BOUND is the real
        # protection: each entry's compiled fn closes over its variables,
        # pinning them (and their device arrays) until eviction — so the
        # cache holds at most _run_cache_size programs' worth. Keys use
        # weakref tokens rather than raw id()s for hygiene (an id can be
        # reused by a new object after GC; a weakref cannot compare equal
        # to a different object's ref).
        import collections
        self._run_cache: "collections.OrderedDict" = collections.OrderedDict()
        self._run_cache_size = 128

    # -- basic facts ------------------------------------------------------
    @property
    def num_replicas_in_sync(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.data_axis_names)

    @property
    def replica_devices(self) -> list:
        return list(self.mesh.devices.flat)

    @property
    def variables(self) -> list[DistributedVariable]:
        return list(self._variables)

    # -- scope ------------------------------------------------------------
    @contextlib.contextmanager
    def scope(self):
        """≙ Strategy.scope (distribute_lib.py:1223): variables created
        inside are placed on the mesh with this strategy's policy."""
        from distributed_tensorflow_tpu.utils.summary import (
            api_gauge, strategy_gauge)
        strategy_gauge.set(type(self).__name__)   # ≙ distribute_lib.py:190
        api_gauge.set("scope")
        _strategy_stack().append(self)
        try:
            yield self
        finally:
            _strategy_stack().pop()

    def create_variable(self, value, *, name=None, trainable=True,
                        synchronization=VariableSynchronization.AUTO,
                        aggregation=VariableAggregation.NONE,
                        dtype=None) -> DistributedVariable:
        if synchronization in (VariableSynchronization.AUTO,
                               VariableSynchronization.ON_WRITE):
            var = MirroredVariable(
                value, mesh=self.mesh, name=name, trainable=trainable,
                aggregation=(aggregation
                             if aggregation is not VariableAggregation.NONE
                             else VariableAggregation.MEAN),
                dtype=dtype)
        else:
            # ON_READ state carries a leading per-replica axis; each
            # replica starts from the init value (≙ values.py:1294).
            # ALWAYS broadcast — the init value is the per-replica value,
            # never a pre-stacked (R, ...) array (callers needing custom
            # per-replica init construct SyncOnReadVariable directly).
            val = jnp.asarray(value, dtype=dtype)
            val = jnp.broadcast_to(
                val, (self.num_replicas_in_sync,) + val.shape)
            var = SyncOnReadVariable(
                val, mesh=self.mesh, data_axes=self.data_axis_names,
                name=name, aggregation=aggregation, dtype=dtype)
        self._variables.append(var)
        return var

    # -- data -------------------------------------------------------------
    def experimental_distribute_dataset(self, dataset, options=None):
        from distributed_tensorflow_tpu.input.dataset import DistributedDataset
        return DistributedDataset(dataset, self, options=options)

    def distribute_datasets_from_function(self, dataset_fn, options=None):
        from distributed_tensorflow_tpu.input.dataset import (
            DistributedDataset, InputContext)
        ctx = InputContext(
            num_input_pipelines=jax.process_count(),
            input_pipeline_id=jax.process_index(),
            num_replicas_in_sync=self.num_replicas_in_sync)
        return DistributedDataset(dataset_fn(ctx), self, options=options)

    def experimental_distribute_values_from_function(self, value_fn):
        """≙ distribute_lib.py experimental_distribute_values_from_function:
        value_fn(ValueContext) -> per-replica value."""
        vals = []
        for rid in range(self.num_replicas_in_sync):
            vals.append(value_fn(ValueContext(rid, self.num_replicas_in_sync)))
        return PerReplica(vals)

    # -- run (TF-parity SPMD path) ----------------------------------------
    def run(self, fn: Callable, args=(), kwargs=None) -> Any:
        """Run ``fn`` once per replica as a single SPMD program
        (≙ Strategy.run, distribute_lib.py:1557 — but via shard_map tracing,
        not per-device threads).

        ``PerReplica``/stacked leaves of ``args`` are split over the data
        axes; other leaves are replicated. Variables created in this
        strategy's scope may be read and assigned inside ``fn``; updates are
        written back after the step. Returns per-replica outputs as
        ``PerReplica`` (scalars and arrays get a leading replica axis while
        stacked).
        """
        kwargs = kwargs or {}
        R = self.num_replicas_in_sync
        axes = self.data_axis_names

        def is_dist(v):
            return isinstance(v, DistributedValues)

        def is_data_sharded(v):
            """A device array already sharded over this mesh's data axes
            (a distributed-dataset batch): each replica gets its local
            shard, matching the reference's per-replica dataset element
            semantics (input_lib.py DistributedIterator)."""
            sh = getattr(v, "sharding", None)
            if not isinstance(v, jax.Array) or \
                    not isinstance(sh, NamedSharding):
                return False
            if sh.mesh.devices.shape != self.mesh.devices.shape or \
                    set(sh.mesh.axis_names) != set(self.mesh.axis_names):
                return False
            spec = sh.spec
            if not spec or spec[0] is None:
                return False
            first = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
            return any(a in axes for a in first)

        flat_args, args_treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=is_dist)
        split_mask = [is_dist(v) for v in flat_args]
        sharded_mask = [not m and is_data_sharded(v)
                        for v, m in zip(flat_args, split_mask)]
        stacked = [
            jnp.stack([jnp.asarray(x) for x in v.values]) if m else
            (v if sh else jnp.asarray(v))
            for v, m, sh in zip(flat_args, split_mask, sharded_mask)]

        variables = self._variables

        def mesh_value(v):
            """Mesh-placed values pass through; values pinned elsewhere
            (AggregatingVariable home devices — central storage) are
            re-placed onto the mesh (the PS read — an async device copy,
            not a blocking host round-trip)."""
            val = _orig_value(v)
            sh = getattr(val, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh == self.mesh:
                return val
            try:
                return jax.device_put(val, NamedSharding(self.mesh, v.spec))
            except Exception:
                return np.asarray(val)     # cross-backend fallback

        var_vals = [mesh_value(v) for v in variables]
        var_specs = [v.spec for v in variables]

        # Cache the traced+compiled program per (fn, structure, shapes):
        # without this the TF-parity path would retrace every step.
        # NOTE: a lambda recreated each call defeats the cache — pass a
        # stable function object in training loops.
        import weakref

        def stable_token(v):
            # weakref tokens cannot alias a new object after GC the way
            # raw id()s can (two refs compare unequal once a referent
            # dies); unweakreffable objects fall back to identity.
            try:
                return weakref.ref(v)
            except TypeError:
                return id(v)

        cache_key = (
            fn, args_treedef, tuple(split_mask), tuple(sharded_mask),
            tuple((x.shape, str(x.dtype)) for x in stacked),
            tuple(stable_token(v) for v in variables),
            tuple((tuple(v.shape), str(v.dtype)) for v in variables),
        )
        cached = self._run_cache.get(cache_key)
        if cached is not None:
            self._run_cache.move_to_end(cache_key)
            new_var_vals, out_stacked = cached(tuple(var_vals), *stacked)
            for v, val in zip(variables, new_var_vals):
                v._set_raw(val)
            return self._unstack_outputs(out_stacked)

        def spmd_fn(var_vals_in, *leaves):
            on_read = [v.synchronization is VariableSynchronization.ON_READ
                       for v in variables]
            var_locals = [jnp.squeeze(val, axis=0) if r else val
                          for v, val, r in zip(variables, var_vals_in, on_read)]
            overlay = {id(v): val for v, val in zip(variables, var_locals)}
            # PerReplica leaves: drop the stacked replica axis (size 1
            # locally). Data-sharded leaves: the local shard IS the
            # replica's sub-batch — pass through.
            local = [jnp.squeeze(v, axis=0) if m else v
                     for v, m in zip(leaves, split_mask)]
            (largs, lkwargs) = jax.tree_util.tree_unflatten(args_treedef, local)
            ctx = ReplicaContext(self, axes)
            # run() implicitly enters the strategy's scope (TF semantics:
            # get_strategy() works inside a replica fn)
            with self.scope(), _spmd_trace(), _variable_overlay(overlay), \
                    _replica_context(ctx):
                out = fn(*largs, **lkwargs)
            new_vals = []
            for v, orig, r in zip(variables, var_locals, on_read):
                cur = overlay[id(v)]
                if r:
                    cur = jnp.expand_dims(cur, 0)
                elif cur is not orig:
                    # assigned in replica context: apply the variable's
                    # cross-replica aggregation (≙ values.py OnWrite policy
                    # :1705 — mirrored writes must agree across replicas)
                    agg = v.aggregation
                    if agg is VariableAggregation.MEAN:
                        cur = collectives.all_reduce(cur, axes, ReduceOp.MEAN)
                    elif agg is VariableAggregation.SUM:
                        cur = collectives.all_reduce(cur, axes, ReduceOp.SUM)
                    elif agg is VariableAggregation.ONLY_FIRST_REPLICA:
                        cur = collectives.broadcast(cur, axes, source=0)
                new_vals.append(cur)
            def stack_leaf(x):
                # fns like `var.assign_add` return the variable itself;
                # resolve it to its (traced) value rather than materializing
                if isinstance(x, DistributedVariable):
                    x = overlay.get(id(x), _orig_value(x))
                return jnp.expand_dims(jnp.asarray(x), 0)

            out_stacked = jax.tree_util.tree_map(
                stack_leaf, out,
                is_leaf=lambda x: isinstance(x, DistributedVariable))
            return tuple(new_vals), out_stacked

        in_specs = (
            [P(axes) if (m or sh) else P()
             for m, sh in zip(split_mask, sharded_mask)])
        shard_fn = jax.jit(jax.shard_map(
            spmd_fn,
            mesh=self.mesh,
            in_specs=(tuple(var_specs),) + tuple(in_specs),
            out_specs=(tuple(var_specs), P(axes)),
            check_vma=False,
        ))
        self._run_cache[cache_key] = shard_fn
        while len(self._run_cache) > self._run_cache_size:
            self._run_cache.popitem(last=False)
        new_var_vals, out_stacked = shard_fn(tuple(var_vals), *stacked)

        for v, val in zip(variables, new_var_vals):
            v._set_raw(val)
        return self._unstack_outputs(out_stacked)

    def _unstack_outputs(self, out_stacked):
        """Split stacked (R, ...) outputs into PerReplica host views. The
        stacked array is replica-sharded; indexing it eagerly is ambiguous
        to GSPMD, so re-place replicated first (outputs of the TF-parity
        path are host-consumed, not hot-path)."""
        R = self.num_replicas_in_sync
        repl = self.replicated_sharding()

        def unstack(x):
            x = jax.device_put(x, repl)
            return PerReplica([x[i] for i in range(R)])
        return jax.tree_util.tree_map(unstack, out_stacked)

    # -- reduce (host side) -----------------------------------------------
    def reduce(self, reduce_op, value, axis=None):
        """≙ Strategy.reduce (distribute_lib.py:1675): reduce a PerReplica
        across replicas (and optionally across ``axis`` within each)."""
        op = ReduceOp.from_any(reduce_op)
        if isinstance(value, DistributedValues):
            vals = [jnp.asarray(v) for v in value.values]
        else:
            vals = [jnp.asarray(value)]
        if axis is not None:
            inner = {ReduceOp.MEAN: jnp.mean, ReduceOp.SUM: jnp.sum,
                     ReduceOp.MAX: jnp.max, ReduceOp.MIN: jnp.min}[op]
            vals = [inner(v, axis=axis) for v in vals]
        stacked = jnp.stack(vals)
        if op is ReduceOp.MEAN:
            return jnp.mean(stacked, axis=0)
        if op is ReduceOp.SUM:
            return jnp.sum(stacked, axis=0)
        if op is ReduceOp.MAX:
            return jnp.max(stacked, axis=0)
        if op is ReduceOp.MIN:
            return jnp.min(stacked, axis=0)
        raise ValueError(f"Unsupported reduce op {op}")

    def gather(self, value, axis: int = 0):
        """≙ Strategy.gather: concatenate per-replica values."""
        if isinstance(value, DistributedValues):
            return jnp.concatenate(
                [jnp.asarray(v) for v in value.values], axis=axis)
        return jnp.asarray(value)

    # -- native functional path -------------------------------------------
    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def data_sharding(self, batch_axis: int = 0) -> NamedSharding:
        names = self.data_axis_names
        if isinstance(names, (tuple, list)) and len(names) == 1:
            # single data axis: use the bare name — identical sharding,
            # but P('dp') (the canonical form newer jax normalizes to)
            # instead of the vintage-dependent P(('dp',))
            names = names[0]
        spec = [None] * (batch_axis + 1)
        spec[batch_axis] = names
        return NamedSharding(self.mesh, P(*spec))

    def shard_batch(self, batch):
        """Place a host global-batch pytree on the mesh, sharded on axis 0
        over the data axes (≙ distributed-dataset device placement)."""
        sharding = self.data_sharding()
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x), sharding), batch)

    def replicate(self, tree):
        """Place a pytree fully replicated on the mesh."""
        sharding = self.replicated_sharding()
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x), sharding), tree)

    def init_state(self, init_fn: Callable, *args,
                   sharding_rules=None, **kwargs):
        """Initialize a state pytree on the mesh. ``sharding_rules`` maps the
        state to PartitionSpecs (default: fully replicated = mirrored)."""
        abstract = jax.eval_shape(init_fn, *args, **kwargs)
        if sharding_rules is None:
            out_shardings = jax.tree_util.tree_map(
                lambda _: self.replicated_sharding(), abstract)
        else:
            out_shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec), sharding_rules,
                is_leaf=lambda s: isinstance(s, P))
        return jax.jit(init_fn, out_shardings=out_shardings)(*args, **kwargs)

    def gradient_bucketer(self):
        """Reverse-order bucketed gradient collectives for this strategy's
        data axes (≙ the reference's NcclAllReduce gradient packing,
        cross_device_utils.py:436-449) — ON by default whenever the
        strategy spans more than one replica. Pack size comes from
        ``CommunicationOptions.bytes_per_pack`` (0 -> the
        ``DEFAULT_BYTES_PER_PACK`` fusion-buffer default). On a hybrid
        dcn×dp reduction the bucketer takes the hierarchical path so the
        cross-slice DCN hop of each bucket overlaps the ICI phases of the
        next. Returns None when there is nothing to reduce (single
        replica); subclasses whose variables live off-mesh (central
        storage, parameter server) also return None.
        """
        if self.num_replicas_in_sync <= 1:
            return None
        axes = self.data_axis_names
        bpp = (self.communication_options.bytes_per_pack
               or collectives.DEFAULT_BYTES_PER_PACK)
        outer = inner = None
        if (len(axes) == 2 and axes[0] == topo_lib.DCN_AXIS
                and all(self.mesh.shape[a] > 1 for a in axes)):
            outer, inner = axes
        return collectives.GradientBucketer(
            axes, bytes_per_pack=bpp, outer_axis=outer, inner_axis=inner)

    def compile_step(self, step_fn: Callable, donate_state: bool = True):
        """Compile ``step_fn(state, batch) -> (state, aux)`` into the SPMD
        hot path: batch sharded over data axes, shardings of ``state``
        propagated by GSPMD, state buffers donated.

        This is the ≙ of the reference's TPUStrategy model (SURVEY §3.4):
        one compiled program per step, Python out of the loop.
        """
        donate = (0,) if donate_state else ()
        return jax.jit(step_fn, donate_argnums=donate)


class ValueContext:
    """≙ tf.distribute.experimental.ValueContext."""

    def __init__(self, replica_id_in_sync_group: int,
                 num_replicas_in_sync: int):
        self.replica_id_in_sync_group = replica_id_in_sync_group
        self.num_replicas_in_sync = num_replicas_in_sync
