"""Wide&Deep / DLRM — benchmark workload #4
(BASELINE.md: ParameterServerStrategy async-PS reference).

The reference shards its embedding tables across parameter servers with
axis-0 partitioners and looks them up remotely per step (reference:
tensorflow/python/distribute/sharded_variable.py:843 ``ShardedVariable``,
:995 ``embedding_lookup``; parameter_server_strategy_v2.py:689 variable
round-robin). The TPU-native redesign keeps tables *on device*, sharded
over the mesh's model axis ("tp"), and lets GSPMD turn gather + combine
into the same partitioned-lookup pattern SparseCore embedding uses
(reference tpu_embedding_v3.py:498) — no RPC per lookup.

Two training modes:
- **SPMD sync** (`make_sharded_train_step`): embeddings row-sharded over
  tp, dense layers replicated, batch over dp. One jit program.
- **Async PS** (`examples`/coordinator): the ClusterCoordinator schedules
  steps on workers with host-memory tables via ShardedVariable
  (parallel/sharded_variable.py) — API-parity path with the reference.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp

import numpy as np
import optax
from flax import linen as nn
from flax.linen import partitioning as nn_partitioning
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

param_with_axes = nn_partitioning.param_with_axes


@dataclasses.dataclass(frozen=True)
class WideDeepConfig:
    vocab_sizes: tuple = (1000, 1000, 500, 100)   # one per categorical col
    embed_dim: int = 32
    num_dense_features: int = 13
    mlp_dims: tuple = (256, 128, 64)
    dtype: Any = jnp.float32
    learning_rate: float = 1e-3
    # "dot" = DLRM pairwise feature interaction; "concat" = Wide&Deep
    interaction: str = "concat"

    @classmethod
    def tiny(cls, **kw):
        defaults = dict(vocab_sizes=(64, 64, 32), embed_dim=8,
                        num_dense_features=4, mlp_dims=(32, 16))
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def dlrm_like(cls, **kw):
        defaults = dict(vocab_sizes=(int(1e5),) * 26, embed_dim=64,
                        num_dense_features=13, mlp_dims=(512, 256, 128),
                        interaction="dot")
        defaults.update(kw)
        return cls(**defaults)


# Logical axes: embedding rows shard over the model axis, the TPU-native
# form of the reference's axis-0 PS sharding (sharded_variable.py:47
# Partitioner family).
WIDE_DEEP_RULES = (
    ("table_rows", "tp"),
    ("table_cols", None),
    ("hidden", None),
    ("features", None),
)


def _interact(cfg: WideDeepConfig, embs: Sequence, dense):
    """Feature interaction shared by both towers: DLRM pairwise dots
    ("dot") or plain concatenation ("concat")."""
    if cfg.interaction == "dot":
        stacked = jnp.stack(list(embs), axis=1)        # (B, T, E)
        inter = jnp.einsum("bte,bse->bts", stacked, stacked)
        iu = jnp.triu_indices(len(embs), k=1)
        feats = [inter[:, iu[0], iu[1]], dense]
    else:
        feats = list(embs) + [dense]
    return jnp.concatenate(feats, axis=-1).astype(cfg.dtype)


class WideDeep(nn.Module):
    cfg: WideDeepConfig

    @nn.compact
    def __call__(self, dense, categorical):
        """dense: (B, num_dense); categorical: (B, n_tables) int ids."""
        cfg = self.cfg
        embs = []
        wide_logits = []
        for i, vocab in enumerate(cfg.vocab_sizes):
            table = param_with_axes(
                f"table_{i}", nn.initializers.normal(0.01),
                (vocab, cfg.embed_dim), jnp.float32,
                axes=("table_rows", "table_cols"))
            # Row gather — GSPMD partitions this lookup across the tp
            # shards of the table (SparseCore-style), ≙ reference
            # sharded_variable.embedding_lookup (:995).
            embs.append(table[categorical[:, i]])
            wide = param_with_axes(
                f"wide_{i}", nn.initializers.zeros, (vocab,), jnp.float32,
                axes=("table_rows",))
            wide_logits.append(wide[categorical[:, i]])

        # DLRM pairwise dots or Wide&Deep concat — shared helper
        x = _interact(cfg, embs, dense)

        for j, width in enumerate(cfg.mlp_dims):
            w = param_with_axes(
                f"mlp_{j}", nn.initializers.lecun_normal(),
                (x.shape[-1], width), jnp.float32,
                axes=("features", "hidden"))
            b = param_with_axes(f"bias_{j}", nn.initializers.zeros,
                                (width,), jnp.float32, axes=("hidden",))
            x = nn.relu(jnp.dot(x, w.astype(cfg.dtype)) + b)

        w_out = param_with_axes("out", nn.initializers.lecun_normal(),
                                (x.shape[-1], 1), jnp.float32,
                                axes=("features", None))
        deep_logit = jnp.dot(x, w_out.astype(cfg.dtype))[:, 0]
        return deep_logit.astype(jnp.float32) + sum(wide_logits)


def make_optimizer(cfg: WideDeepConfig):
    return optax.adagrad(cfg.learning_rate)   # the classic W&D/DLRM choice


def make_train_step(cfg: WideDeepConfig, model: WideDeep, tx):
    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["dense"],
                             batch["categorical"])
        return optax.sigmoid_binary_cross_entropy(
            logits, batch["label"].astype(jnp.float32)).mean()

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        updates, opt_state = tx.update(grads, state["opt_state"],
                                       state["params"])
        params = optax.apply_updates(state["params"], updates)
        return ({"params": params, "opt_state": opt_state,
                 "step": state["step"] + 1},
                {"loss": loss})

    return train_step


def make_sharded_train_step(cfg: WideDeepConfig, mesh: Mesh,
                            global_batch: int, seed: int = 0):
    """SPMD: tables row-sharded over tp, batch over dp, one jit program."""
    model = WideDeep(cfg)
    tx = make_optimizer(cfg)
    rng = jax.random.PRNGKey(seed)
    n_tables = len(cfg.vocab_sizes)
    dense_shape = jnp.zeros((global_batch, cfg.num_dense_features))
    cat_shape = jnp.zeros((global_batch, n_tables), jnp.int32)

    from distributed_tensorflow_tpu.models.transformer import \
        mesh_axis_rules
    rules = mesh_axis_rules(mesh, WIDE_DEEP_RULES)

    with nn_partitioning.axis_rules(rules):
        var_shapes = jax.eval_shape(
            lambda r: model.init(r, dense_shape, cat_shape), rng)
        logical = nn_partitioning.get_axis_names(var_shapes["params_axes"])
        mesh_specs = nn_partitioning.logical_to_mesh(logical)
    param_shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), mesh_specs,
        is_leaf=lambda x: isinstance(x, P))
    if hasattr(param_shardings, "unfreeze"):
        param_shardings = param_shardings.unfreeze()

    replicated = NamedSharding(mesh, P())
    # adagrad state mirrors params
    from distributed_tensorflow_tpu.models.transformer import _shard_like
    params_treedef = jax.tree_util.tree_structure(var_shapes["params"])
    opt_shapes = jax.eval_shape(tx.init, var_shapes["params"])
    opt_shardings = _shard_like(opt_shapes, params_treedef,
                                param_shardings, replicated)
    state_shardings = {"params": param_shardings,
                       "opt_state": opt_shardings, "step": replicated}

    def init_fn(rng):
        params = model.init(rng, dense_shape, cat_shape)["params"]
        return {"params": params, "opt_state": tx.init(params),
                "step": jnp.zeros((), jnp.int32)}

    from distributed_tensorflow_tpu.cluster.topology import \
        data_axes as mesh_data_axes
    data_axes = mesh_data_axes(mesh) or None
    batch_shardings = {
        "dense": NamedSharding(mesh, P(data_axes)),
        "categorical": NamedSharding(mesh, P(data_axes)),
        "label": NamedSharding(mesh, P(data_axes)),
    }

    step = make_train_step(cfg, model, tx)
    with mesh, nn_partitioning.axis_rules(rules):
        state = jax.jit(init_fn, out_shardings=state_shardings)(rng)
        step_jit = jax.jit(step,
                           in_shardings=(state_shardings, batch_shardings),
                           out_shardings=(state_shardings, replicated),
                           donate_argnums=(0,))

    def wrapped(state, batch):
        with mesh, nn_partitioning.axis_rules(rules):
            return step_jit(state, batch)

    return state, wrapped


def build_feature_config(cfg: WideDeepConfig):
    """The Wide&Deep feature/table layout for the embedding API: a deep
    table (embed_dim) and a dim-1 wide table (combiner=sum) per
    categorical column, each with per-table Adagrad (≙ the feature_config
    trees passed to reference tpu_embedding_v2.py:76)."""
    from distributed_tensorflow_tpu import embedding as emb_lib
    deep_tables = [emb_lib.TableConfig(v, cfg.embed_dim, name=f"table_{i}",
                                       optimizer=emb_lib.Adagrad(
                                           cfg.learning_rate))
                   for i, v in enumerate(cfg.vocab_sizes)]
    wide_tables = [emb_lib.TableConfig(v, 1, name=f"wide_{i}",
                                       combiner="sum",
                                       optimizer=emb_lib.Adagrad(
                                           cfg.learning_rate))
                   for i, v in enumerate(cfg.vocab_sizes)]
    return {
        "deep": tuple(emb_lib.FeatureConfig(t, name=f"deep_{i}")
                      for i, t in enumerate(deep_tables)),
        "wide": tuple(emb_lib.FeatureConfig(t, name=f"wide_{i}")
                      for i, t in enumerate(wide_tables)),
    }


def _embedding_loss_fn(cfg: WideDeepConfig, feature_config, model):
    """Shared W&D-through-embedding-API objective: deep acts into the
    dense tower, wide acts summed into the logit, sigmoid CE."""
    from distributed_tensorflow_tpu import embedding as emb_lib
    n_tables = len(cfg.vocab_sizes)

    def loss_fn(dense_params, tables, batch):
        feats = {
            "deep": tuple(batch["categorical"][:, i]
                          for i in range(n_tables)),
            "wide": tuple(batch["categorical"][:, i]
                          for i in range(n_tables)),
        }
        acts = emb_lib.lookup(tables, feature_config, feats)
        logits = model.apply({"params": dense_params},
                             list(acts["deep"]), batch["dense"])
        logits = logits + sum(w[:, 0] for w in acts["wide"])
        return optax.sigmoid_binary_cross_entropy(
            logits, batch["label"].astype(jnp.float32)).mean()

    return loss_fn


class WideDeepDense(nn.Module):
    """The dense tower only: consumes PRE-LOOKED-UP embedding activations
    (the TPUEmbedding API path — ≙ how reference DLRM models consume
    dequeued activations from tpu_embedding_v2.py while the tables train
    decoupled)."""
    cfg: WideDeepConfig

    @nn.compact
    def __call__(self, emb_acts: Sequence, dense):
        cfg = self.cfg
        x = _interact(cfg, emb_acts, dense)
        for j, width in enumerate(cfg.mlp_dims):
            x = nn.relu(nn.Dense(width, name=f"mlp_{j}")(x))
        return nn.Dense(1, name="out")(x)[:, 0].astype(jnp.float32)


def make_embedding_train_step(cfg: WideDeepConfig, mesh: Mesh,
                              global_batch: int, seed: int = 0):
    """DLRM/W&D through the TPU embedding API (embedding/embedding.py):

    - one TableConfig per categorical column (+ a dim-1 "wide" table per
      column, combiner=sum — the wide half of Wide&Deep);
    - tables row-sharded over "tp" via the embedding layer's own state
      (≙ tpu_embedding_v3.py:498 SparseCore sharding), NOT flax params;
    - table gradients applied by the per-table Adagrad — decoupled from
      the dense tower's optax optimizer (≙ tpu_embedding_v2.py:754
      apply_gradients).

    Returns (state, step_fn) with state = {"dense": ..., "emb": ...}.
    """
    from distributed_tensorflow_tpu import embedding as emb_lib

    feature_config = build_feature_config(cfg)

    rng = jax.random.PRNGKey(seed)
    rng, emb_rng, dense_rng = jax.random.split(rng, 3)
    emb_state = emb_lib.create_state(feature_config, mesh=mesh,
                                     shard_axis="tp", rng=emb_rng)

    model = WideDeepDense(cfg)
    n_tables = len(cfg.vocab_sizes)
    sample_acts = [jnp.zeros((global_batch, cfg.embed_dim))
                   for _ in range(n_tables)]
    sample_dense = jnp.zeros((global_batch, cfg.num_dense_features))
    dense_params = model.init(dense_rng, sample_acts, sample_dense)["params"]
    tx = make_optimizer(cfg)

    from distributed_tensorflow_tpu.cluster.topology import \
        data_axes as mesh_data_axes
    data_axes = mesh_data_axes(mesh) or None
    replicated = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(data_axes))
    table_sh = (NamedSharding(mesh, P("tp", None))
                if "tp" in mesh.shape else replicated)
    emb_shardings = jax.tree_util.tree_map(
        lambda x: table_sh if getattr(x, "ndim", 0) == 2 else replicated,
        emb_state)
    dense_state = {"params": dense_params, "opt_state": tx.init(dense_params)}
    dense_shardings = jax.tree_util.tree_map(lambda _: replicated,
                                             dense_state)
    state = {"dense": jax.device_put(dense_state, replicated),
             "emb": jax.tree_util.tree_map(jax.device_put, emb_state,
                                           emb_shardings)}
    state_shardings = {"dense": dense_shardings, "emb": emb_shardings}
    batch_shardings = {"dense": batch_sh, "categorical": batch_sh,
                       "label": batch_sh}

    loss_fn = _embedding_loss_fn(cfg, feature_config, model)

    def train_step(state, batch):
        loss, (dgrads, tgrads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(state["dense"]["params"],
                                     state["emb"]["tables"], batch)
        updates, opt_state = tx.update(dgrads, state["dense"]["opt_state"],
                                       state["dense"]["params"])
        dense_params = optax.apply_updates(state["dense"]["params"], updates)
        emb = emb_lib.apply_gradients(state["emb"], tgrads, feature_config)
        return ({"dense": {"params": dense_params, "opt_state": opt_state},
                 "emb": emb}, {"loss": loss})

    with mesh:
        step_jit = jax.jit(train_step,
                           in_shardings=(state_shardings, batch_shardings),
                           out_shardings=(state_shardings, replicated),
                           donate_argnums=(0,))

    def wrapped(state, batch):
        with mesh:
            return step_jit(state, batch)

    return state, wrapped


def synthetic_clicks(cfg: WideDeepConfig, n: int, seed: int = 0):
    """Click-through data where the label depends on feature crosses."""
    rng = np.random.default_rng(seed)
    dense = rng.normal(size=(n, cfg.num_dense_features)).astype("float32")
    cat = np.stack([rng.integers(0, v, size=n) for v in cfg.vocab_sizes],
                   axis=1).astype("int32")
    score = dense.mean(1) + 0.3 * np.cos(cat.sum(1))
    label = (score > np.median(score)).astype("int32")
    return {"dense": jnp.asarray(dense), "categorical": jnp.asarray(cat),
            "label": jnp.asarray(label)}


# ---------------------------------------------------------------------------
# Async parameter-server composition (BASELINE.md config #4):
# embedding API tables + dense tower, trained asynchronously through the
# ClusterCoordinator's remote dispatch. ≙ parameter_server_strategy_v2.py:77
# (coordinator-owned variables, worker-computed steps) composed with
# tpu_embedding_v2.py:76 (feature_config-driven tables) — the two APIs the
# reference's config #4 uses together.
#
# Topology: the coordinator process owns the "server copy" of all state
# (tables + slots + dense params + optax state); workers hold per-worker
# datasets and compute gradients for whatever parameter snapshot each
# scheduled closure carries; the coordinator applies gradients AS RESULTS
# ARRIVE — the async-PS staleness semantics (a gradient may be computed
# against parameters a few updates old, exactly like the reference's
# unsynchronized PS reads/writes).
# ---------------------------------------------------------------------------

import functools as _functools


@_functools.lru_cache(maxsize=4)
def _ps_feature_config(cfg: WideDeepConfig):
    return build_feature_config(cfg)


@_functools.lru_cache(maxsize=4)
def _ps_optimizer(cfg: WideDeepConfig):
    return make_optimizer(cfg)


def ps_init_state(cfg: WideDeepConfig, seed: int = 0) -> dict:
    """Coordinator-side server copy of the full DLRM state (host arrays —
    small enough to ship inside scheduled closures; bulk activations
    never leave the workers)."""
    from distributed_tensorflow_tpu import embedding as emb_lib
    rng = jax.random.PRNGKey(seed)
    rng, emb_rng, dense_rng = jax.random.split(rng, 3)
    feature_config = build_feature_config(cfg)
    emb_state = emb_lib.create_state(feature_config, rng=emb_rng)
    model = WideDeepDense(cfg)
    n_tables = len(cfg.vocab_sizes)
    sample_acts = [jnp.zeros((2, cfg.embed_dim)) for _ in range(n_tables)]
    sample_dense = jnp.zeros((2, cfg.num_dense_features))
    dense_params = model.init(dense_rng, sample_acts,
                              sample_dense)["params"]
    tx = make_optimizer(cfg)
    return {"dense": {"params": dense_params,
                      "opt_state": tx.init(dense_params)},
            "emb": emb_state}


@_functools.lru_cache(maxsize=4)
def _ps_grad_program(cfg: WideDeepConfig):
    """Worker-side compiled grad program, built once per process (the
    worker's analogue of the reference's per-worker function library)."""
    feature_config = build_feature_config(cfg)
    model = WideDeepDense(cfg)
    loss_fn = _embedding_loss_fn(cfg, feature_config, model)
    return jax.jit(jax.value_and_grad(
        lambda dp, tabs, batch: loss_fn(dp, tabs, batch),
        argnums=(0, 1)))


def ps_worker_grads(cfg: WideDeepConfig, dense_params, tables, it):
    """Runs ON a worker (scheduled closure): pull the next batch from
    THIS worker's dataset iterator (a per-worker resource handle) and
    return (loss, dense grads, table grads) as host arrays."""
    batch = next(it)
    loss, (dgrads, tgrads) = _ps_grad_program(cfg)(dense_params, tables,
                                                   batch)
    host = lambda t: jax.tree_util.tree_map(np.asarray, t)  # noqa: E731
    return host(loss), host(dgrads), host(tgrads)


def ps_apply_grads(cfg: WideDeepConfig, state: dict, dgrads,
                   tgrads) -> dict:
    """Coordinator-side asynchronous apply: update the CURRENT server
    copy with a (possibly stale) worker gradient."""
    from distributed_tensorflow_tpu import embedding as emb_lib
    tx = _ps_optimizer(cfg)
    updates, opt_state = tx.update(dgrads, state["dense"]["opt_state"],
                                   state["dense"]["params"])
    dense_params = optax.apply_updates(state["dense"]["params"], updates)
    emb = emb_lib.apply_gradients(state["emb"], tgrads,
                                  _ps_feature_config(cfg))
    return {"dense": {"params": dense_params, "opt_state": opt_state},
            "emb": emb}


def train_dlrm_async_ps(cfg: WideDeepConfig, coord, *, steps: int,
                        batch_size: int = 32, max_in_flight: int = 4,
                        dataset_seed: int = 0, log_every: int = 0,
                        on_step=None):
    """Drive config #4 end-to-end: per-worker datasets live on the
    workers, grad closures are scheduled across them with transparent
    preemption retry, and the coordinator folds results into the server
    copy as they land. Returns (final_state, losses).

    ``coord`` is a ClusterCoordinator (local lanes or remote worker
    processes — the same loop runs over both transports).
    """
    state = ps_init_state(cfg)
    dataset_fn = _functools.partial(_ps_dataset, cfg, batch_size,
                                    dataset_seed)
    per_worker_it = coord.create_per_worker_dataset(dataset_fn)
    losses: list = []
    in_flight: list = []
    scheduled = 0
    while scheduled < steps or in_flight:
        while scheduled < steps and len(in_flight) < max_in_flight:
            rv = coord.schedule(
                ps_worker_grads,
                args=(cfg, state["dense"]["params"],
                      state["emb"]["tables"], per_worker_it))
            in_flight.append(rv)
            scheduled += 1
        rv = in_flight.pop(0)
        loss, dgrads, tgrads = rv.fetch()
        state = ps_apply_grads(cfg, state, dgrads, tgrads)
        losses.append(float(loss))
        if on_step is not None:
            on_step(len(losses))
        if log_every and len(losses) % log_every == 0:
            recent = losses[-log_every:]
            print(f"step {len(losses):4d}  loss "
                  f"{sum(recent) / len(recent):.4f}", flush=True)
    return state, losses


_LOCAL_DS_COUNTER = iter(range(1 << 30))


def _ps_dataset(cfg: WideDeepConfig, batch_size: int, seed: int):
    """Per-worker dataset factory (runs on the worker): an endless
    shuffled stream over the synthetic click data. Each worker's stream
    is decorrelated by its worker id (remote lanes) or a process-local
    counter (thread lanes) — N workers must not feed N clones of the
    same batch sequence (≙ the reference's per-worker dataset_fn
    receiving a distinct InputContext.input_pipeline_id)."""
    from distributed_tensorflow_tpu.coordinator.remote_dispatch import (
        current_worker_service)
    svc = current_worker_service()
    wid = svc.worker_id if svc is not None else next(_LOCAL_DS_COUNTER)
    seed = seed * 1009 + wid
    data = synthetic_clicks(cfg, 1024, seed=seed)
    data = {k: np.asarray(v) for k, v in data.items()}
    n = data["label"].shape[0]

    def gen():
        rng = np.random.default_rng(seed)
        while True:
            idx = rng.integers(0, n, size=batch_size)
            yield {k: jnp.asarray(v[idx]) for k, v in data.items()}

    return gen()
